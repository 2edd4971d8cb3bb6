"""tick_host_ms: median over the window's query ticks of the engine's
``engine.step`` span less the ``engine.fetch`` and ``engine.fence`` spans
inside it: the host's own time per tick (admission, the copies and
dispatch, retirement), while the device has no tick to run (engine layer;
host clock)."""
import progtrace


def read(run):
    recs = progtrace.records(run)
    steps = [(s, e) for n, s, e in recs if n == "engine.step"]
    inner = sorted((s, e) for n, s, e in recs
                   if n in ("engine.fetch", "engine.fence"))
    fetches = {s for n, s, _ in recs if n == "engine.fetch"}
    host = [(e - s - sum(t - u for u, t in got)) * 1e3
            for (s, e), got in zip(steps, progtrace.within(steps, inner))
            if any(u in fetches for u, _ in got)]
    return progtrace.median_ms(host)
