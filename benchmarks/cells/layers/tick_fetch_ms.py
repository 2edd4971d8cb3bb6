"""tick_fetch_ms: median over the window's query ticks of the engine's
``engine.fetch`` span, the blocking fetch of the tick's outputs: the
device's run of the tick program plus the transfer back (engine layer,
``streaming/gp_engine.py``; host clock)."""
import progtrace


def read(run):
    return progtrace.median_ms(progtrace.durations_ms(run, "engine.fetch"))
