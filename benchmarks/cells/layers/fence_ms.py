"""fence_ms: median over the window's fences of the engine's
``engine.fence`` span: the staged evicts and inserts, the health fetch
that waits for them on the device, and the new best value; no query tick
(the harness's ``fence`` span also holds the tick that follows) (mutation
layer, ``streaming/gp_engine.py``; host clock)."""
import progtrace


def read(run):
    return progtrace.median_ms(progtrace.durations_ms(run, "engine.fence"))
