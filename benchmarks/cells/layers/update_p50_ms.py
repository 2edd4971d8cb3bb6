"""update_p50_ms: median over the window's inserts of (return of the
``step()`` whose fence applied it) - (its scheduled arrival) (mutation
layer). Its spread comes from the seeds' arrival patterns, too wide for an
end-to-end bound (PERF.md, section 2)."""
import numpy as np


def read(run):
    lat = run.values.get("update_latency_ms")
    return float(np.median(lat)) if lat is not None and len(lat) else None
