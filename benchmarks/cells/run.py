#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last line of stdout.

    python3 benchmarks/cells/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell's configuration, traffic mix and metrics are found by name from
``BENCHMARK.json`` (see ``spec.py``). A run makes its data from the seed,
warms the cell's own programs (set-up, reported as ``setup_s`` and phase by
phase on earlier lines), measures for ``--seconds``, compares what the timed
path produced with the dense reference outside the window, and prints one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``, each
compared number beside its limit (also the last lines of stderr). With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, from a profiler trace of the window.

It refuses to run without a TPU, or with fewer chips than the cell asks
for. ``--rate`` (an open-loop rate sweep) and ``--precision`` (the
lower-precision control: the program's own float32 path) are for the
benchmark's author; a cell's own runs never pass them.
"""
from __future__ import annotations

T_START = __import__("time").perf_counter()

import os  # noqa: E402

# the host's BLAS threads (the reference's Cholesky) to the CPUs this process
# may use, before NumPy loads it
os.environ.setdefault("OPENBLAS_NUM_THREADS", str(len(os.sched_getaffinity(0))))

import argparse  # noqa: E402
import faulthandler  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
from contextlib import contextmanager  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import spec  # noqa: E402
import xtrace  # noqa: E402

CACHE_DIR = os.path.join(spec.ROOT, ".jax_cache")
WATCHDOG_S = 120.0
TRACE_DIR = os.path.join(spec.ROOT, ".bench_trace")


class NoChip(RuntimeError):
    pass


class Context:
    """What a driver is given: logging, set-up phases, spans, the window's
    edges, and where to leave its counters."""

    def __init__(self, tag: str, dtype: str, rate, tracer, mix: dict):
        self.tag, self.dtype, self.rate, self.tracer = tag, dtype, rate, tracer
        self.mix = mix
        self.spans = xtrace.Spans()
        self.counters: dict = {}
        self.values: dict = {}
        self.events = {"hit": 0, "miss": 0, "compile": 0}
        self.setup_s = None
        self.memory_peak = None
        self.window_compiles = None

    def log(self, msg: str) -> None:
        print(f"[{self.tag}] {msg}", flush=True)

    @contextmanager
    def phase(self, name: str):
        before = dict(self.events)
        t0 = time.perf_counter()
        yield
        d = {k: self.events[k] - before[k] for k in before}
        self.log(f"phase {name}: {time.perf_counter() - t0:.3f}s; "
                 f"{d['compile']} programs built, {d['hit']} of them loaded "
                 f"from the cache, {d['miss']} compiled")

    def window_open(self):
        """Set-up ends here."""
        self.setup_s = time.perf_counter() - T_START
        self.log(f"setup: {self.setup_s:.3f}s")
        self._compiles_at_open = self.events["compile"]
        self._window_t0 = time.perf_counter()

    def before_step(self, kind: str):
        """Called by the driver before each step of the window (``kind``
        names it: tick, fence, round). Starts the traced slice at the first
        step of the mix's ``trace_align`` kind (any kind when absent) once
        ``trace_at_s`` seconds of the window have passed."""
        tr, mix = self.tracer, self.mix
        if (tr is None or tr.started is not None
                or time.perf_counter() - self._window_t0 < mix["trace_at_s"]
                or mix.get("trace_align", kind) != kind):
            return
        tr.start(mix["trace_seconds"])

    def window_close(self):
        # a run that stalls after its window says where, on stderr
        faulthandler.dump_traceback_later(WATCHDOG_S, repeat=True)
        tr = self.tracer
        if tr is not None and tr.started is not None:
            t0 = time.perf_counter()
            tr.join()
            self.log(f"trace: slice {tr.stopped - tr.started:.3f}s, profiler "
                     f"stopped {time.perf_counter() - t0:.3f}s after the "
                     "window closed")
            self.spans.items.append(("traced", tr.started, tr.stopped))
        self.spans.items.append(("window", self._window_t0, time.perf_counter()))
        self.window_compiles = self.events["compile"] - self._compiles_at_open
        self.log(f"window: {self.window_compiles} programs built inside it")

    def deadline(self, seconds):
        """End the process, with every thread's stack on stderr and a
        non-zero exit, if it is still running ``seconds`` from now: a fetch
        that never returns ends the run instead of hanging it. ``None``
        puts back the watchdog of ``window_close``, which only prints.
        faulthandler keeps one timer, so each call replaces the last."""
        if seconds is None:
            faulthandler.dump_traceback_later(WATCHDOG_S, repeat=True)
        else:
            faulthandler.dump_traceback_later(seconds, exit=True)

    def read_memory(self):
        import jax

        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.local_devices()]
        peaks = [p for p in peaks if p is not None]
        self.memory_peak = max(peaks) if peaks else None


def _listen(ctx: Context) -> None:
    import jax.monitoring as mon

    def on_event(name, **_):
        if name == "/jax/compilation_cache/cache_hits":
            ctx.events["hit"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            ctx.events["miss"] += 1

    def on_duration(name, _secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            ctx.events["compile"] += 1

    mon.register_event_listener(on_event)
    mon.register_event_duration_secs_listener(on_duration)


def _device(jax, chips: int, require_tpu: bool) -> dict:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU: jax.devices()[0].platform = "
                     f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX finds {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def run(argv=None, require_tpu: bool = True, root: str = spec.ROOT) -> dict:
    """One run; returns the result object (``main`` prints it)."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="open-loop rate override, for a capacity sweep")
    ap.add_argument("--precision", default=None,
                    help="dtype override, for the lower-precision control")
    a = ap.parse_args(argv)
    cell = spec.cell(a.workload, root=root)

    # the cache is the checkout's, also for any code of the program that
    # asks the environment for it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    device = _device(jax, cell.chips, require_tpu)
    jax.config.update("jax_enable_x64", True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # no eviction: an LRU size cap set in the environment would make later
    # runs compile again, and its eviction trips over entries written by
    # another process
    jax.config.update("jax_compilation_cache_max_size", -1)
    sys.path.insert(0, os.path.join(root, "src"))
    tracer = xtrace.Tracer(os.path.join(TRACE_DIR, cell.name)) if a.trace else None
    dtype = a.precision or cell.config["dtype"]
    ctx = Context(f"{device['kind']} x{device['count']}", dtype, a.rate, tracer,
                  cell.traffic)
    _listen(ctx)
    ctx.log(f"cell {cell.name}: config {cell.config['name']}, dtype {dtype}, "
            f"seed {a.seed}, {a.seconds}s, trace {a.trace}"
            + (f", rate {a.rate}/s" if a.rate is not None else ""))
    driver = spec.driver(cell.traffic["kind"], root)
    out = driver.run(cell, a.seed, a.seconds, ctx)

    checks = out["checks"]
    correct = bool(out["complete"]) and all(
        v == v and v <= lim for _, v, lim in checks)
    device["memory_peak_bytes"] = ctx.memory_peak
    result = {"correct": correct, "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    if a.trace:
        if tracer.started is None:
            raise RuntimeError("the window ended before its traced slice began")
        t0 = time.perf_counter()
        tr = tracer.result(ctx.log, ctx.spans.items, device["count"])
        ctx.log(f"trace: reduced in {time.perf_counter() - t0:.3f}s")
        device["busy_s"], device["window_s"] = tr["busy_s"], tr["window_s"]
        run_ = Run(cell, ctx.spans, ctx.counters, ctx.values, tr)
        metrics = {}
        for m in cell.per_layer:
            v = spec.layer_reader(m["name"], root)(run_)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["device"] = device
        result["breakdown"] = {"device_ops": [list(x) for x in tr["device_ops"]],
                               "idle_gaps": [list(x) for x in tr["idle_gaps"]]}
        ctx.log("programs by device time: " + ", ".join(
            f"{n}={s:.6f}s" for n, s in tr["modules"][:xtrace.TOP]))
    else:
        vals = dict(out["e2e"], setup_s=ctx.setup_s)
        result["metrics"] = {m["name"]: {"value": vals[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end if m["name"] in vals}
        result["device"] = device
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    ctx.log("counters: " + json.dumps(ctx.counters, sort_keys=True))
    for n, v, lim in checks:
        print(f"check {n}: {v!r} (limit {lim!r})", file=sys.stderr)
    faulthandler.cancel_dump_traceback_later()
    print(f"check complete: {bool(out['complete'])}; correct: {correct}",
          file=sys.stderr, flush=True)
    return result


class Run:
    """What a per-layer reader (``layers/<metric>.py``) is given."""

    def __init__(self, cell, spans, counters, values, trace):
        self.cell, self.spans, self.counters = cell, spans, counters
        self.values, self.trace = values, trace
        self.kind = cell.traffic["kind"]


def main(argv=None) -> int:
    try:
        result = run(argv)
    except NoChip as e:
        print(f"run.py: {e}; refusing to run", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
