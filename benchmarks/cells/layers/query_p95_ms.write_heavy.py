"""query_p95_ms.write_heavy: 95th percentile of the query latencies of a
write-heavy cell, whose window holds too few queries (under 200) for the
tail to be an end-to-end metric (engine layer: queries wait behind fences).
"""
import numpy as np


def read(run):
    lat = run.values.get("query_latency_ms")
    return float(np.percentile(lat, 95)) if lat is not None and len(lat) else None
