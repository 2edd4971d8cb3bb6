"""tick_ms: median host time of the engine's ``step()`` calls that ran a
query tick and no fence; each call ends in a blocking fetch (engine layer,
``streaming/gp_engine.py``)."""
import numpy as np


def read(run):
    d = run.spans.durations("tick")
    return float(np.median(d) * 1e3) if len(d) else None
