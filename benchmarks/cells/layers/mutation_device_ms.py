"""mutation_device_ms: mean device time of one run of the insert, evict or
Gband resync program, over the runs that lie wholly in the traced slice
(mutation layer, ``streaming/updates.py``, ``core/gband_update.py``)."""
import numpy as np

PROGRAMS = ("_insert_impl", "_evict_impl", "_resync_impl")


def read(run):
    if run.trace is None:
        return None
    d = [t for name, t in run.trace["module_runs"]
         if any(p in name for p in PROGRAMS)]
    return float(np.mean(d) * 1e3) if d else None
