"""queue_wait_ms: median over the window's queries of ``engine.queued``,
from ``submit()`` to the step that admits the query: the time it waits in
the engine's queue, held by a fence or by full slots (engine layer; host
clock)."""
import progtrace


def read(run):
    return progtrace.median_ms(progtrace.durations_ms(run, "engine.queued"))
