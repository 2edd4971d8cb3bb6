"""mll_device_ms: mean device time of one run of the ``log_likelihood``
program (the banded log-dets and the stochastic log-det of Mhat), over the
runs that lie wholly in the traced slice (likelihood layer; device trace).
"""
import numpy as np


def read(run):
    if run.trace is None:
        return None
    d = [t for name, t in run.trace["module_runs"] if "log_likelihood" in name]
    return float(np.mean(d) * 1e3) if d else None
