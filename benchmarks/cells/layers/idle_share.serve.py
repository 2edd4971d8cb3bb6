"""idle_share.serve: share of the traced window in which no operation ran
on the device, in a serving cell: 100 (1 - busy / window)."""


def read(run):
    if run.kind != "open_loop" or run.trace is None or not run.trace["busy_s"]:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])
