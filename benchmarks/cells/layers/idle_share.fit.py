"""idle_share.fit: share of the traced slice in which no operation ran on
the device, in an offline cell (traffic kind ``rounds``): 100 (1 - busy /
slice), busy the union of device-operation intervals (device trace)."""


def read(run):
    if run.kind != "rounds" or run.trace is None or not run.trace["busy_s"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
