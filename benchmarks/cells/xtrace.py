"""Host spans, and the reduction of a profiler trace to device numbers.

The harness wraps each call into a layer of the program in a span: a
``jax.profiler.TraceAnnotation`` (so the profiler's trace holds it on the
same clock as the device's operations) and a host-clock record (so the
host-clock metrics need no trace). The reduction takes the device planes'
operation intervals and the harness's spans, and gives busy time (the union
of operation intervals), the operations that took most time, the programs'
device time, and the longest idle gaps named by what the host was doing.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import time
from contextlib import contextmanager

import numpy as np

PREFIX = "bench."  # names of the harness's own spans in the trace
TOP = 10


class Spans:
    """Host spans of one run: (name, start_s, end_s) on ``perf_counter``."""

    def __init__(self):
        self.items: list[tuple[str, float, float]] = []

    @contextmanager
    def span(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(PREFIX + name):
            yield
        self.items.append((name, t0, time.perf_counter()))

    def durations(self, name: str) -> np.ndarray:
        return np.array([e - s for n, s, e in self.items if n == name])


@dataclasses.dataclass
class Events:
    """What a trace holds, as plain intervals in seconds on one clock."""

    ops: list       # (name, start, end, device): device operations
    modules: list   # (name, start, end, device): program executions
    host: list      # (name, start, end): the harness's spans
    devices: int


def union_length(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _clip(items, lo, hi):
    return [(t[0], max(t[1], lo), min(t[2], hi)) + tuple(t[3:])
            for t in items if t[2] > lo and t[1] < hi]


def _gaps(intervals, lo, hi):
    """Idle (start, end) gaps of [lo, hi] outside the union of intervals."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def _name_gap(host, s, e) -> str:
    """The harness span that covers most of the gap; of spans that cover
    it alike, the innermost (shortest)."""
    best, key = "untraced host", (0.0, 0.0)
    for n, hs, he in host:
        cover = min(e, he) - max(s, hs)
        if cover > 0 and (cover, -(he - hs)) > key:
            best, key = n, (cover, -(he - hs))
    return best


def reduce(ev: Events, window: tuple[float, float]) -> dict:
    """busy_s (averaged over devices), window_s, top device operations and
    programs, and the longest idle gaps named by the host's spans, over the
    (start, end) window on the trace's clock."""
    lo, hi = window
    ops = _clip(ev.ops, lo, hi)
    mods = _clip(ev.modules, lo, hi)
    host = _clip(ev.host, lo, hi)
    # busy: the union of each device's own intervals, averaged over devices
    by_dev: dict = {}
    for _, s, e, dev in ops:
        by_dev.setdefault(dev, []).append((s, e))
    busy = sum(union_length(iv) for iv in by_dev.values()) / max(1, ev.devices)
    per_op: dict = {}
    for n, s, e, _ in ops:
        per_op[n] = per_op.get(n, 0.0) + (e - s) / max(1, ev.devices)
    per_mod: dict = {}
    for n, s, e, _ in mods:
        per_mod[n] = per_mod.get(n, 0.0) + (e - s) / max(1, ev.devices)
    # program executions wholly inside the window, for per-call device times
    runs = [(n, e - s) for n, s, e, _ in ev.modules if lo <= s and e <= hi]
    gaps = sorted(_gaps([(t[1], t[2]) for t in ops], lo, hi),
                  key=lambda g: g[0] - g[1])[:TOP]
    return {
        "busy_s": busy,
        "window_s": hi - lo,
        "device_ops": sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP],
        "modules": sorted(per_mod.items(), key=lambda kv: -kv[1]),
        "module_runs": runs,
        "idle_gaps": [(_name_gap(host, s, e), e - s) for s, e in gaps],
    }


def _module_name(name: str) -> str:
    """'jit__insert_impl(1234)' -> 'jit__insert_impl'."""
    return name.split("(")[0]


def read_profile(path: str) -> tuple[Events, dict]:
    """Events of an ``.xplane.pb`` written by ``jax.profiler``, and a count
    of the events on each plane's lines (printed, for a reader of the log)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, host, devices, layout = [], [], [], 0, {}
    for plane in pd.planes:
        device = plane.name.startswith("/device:") and "CPU" not in plane.name
        dev = devices
        devices += device
        layout[plane.name] = 0
        for line in plane.lines:
            layout[f"{plane.name}|{line.name}"] = 0
            for e in line.events:
                layout[f"{plane.name}|{line.name}"] += 1
                s = e.start_ns * 1e-9
                iv = (s, s + e.duration_ns * 1e-9)
                if device and line.name == "XLA Ops":
                    # '%while.204 = (u32[], ...) while(...)' -> '%while.204'
                    ops.append((e.name.split(" ", 1)[0],) + iv + (dev,))
                elif device and line.name == "XLA Modules":
                    modules.append((_module_name(e.name),) + iv + (dev,))
                elif not device and e.name.startswith(PREFIX):
                    host.append((e.name[len(PREFIX):],) + iv)
    return Events(ops=ops, modules=modules, host=host, devices=devices), layout


class Tracer:
    """The profiler over one slice of the measured window, written into a
    fixed directory of the checkout; ``result()`` reduces what it wrote.

    A device trace of this program records every operation inside its
    loops, and the profiler keeps only some millions of events, so a slice
    is short and ends on a timer, whatever the host is doing then. Marker
    spans at its two ends bound the reduction."""

    def __init__(self, directory: str):
        self.dir = directory
        self.timer = None
        self.started = self.stopped = None

    def start(self, seconds: float):
        import shutil
        import threading

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        jax.profiler.start_trace(self.dir)
        with jax.profiler.TraceAnnotation(PREFIX + "traced_begin"):
            self.started = time.perf_counter()
        self.timer = threading.Timer(seconds, self.stop)
        self.timer.start()

    def stop(self):
        import jax

        if self.stopped is not None:
            return
        with jax.profiler.TraceAnnotation(PREFIX + "traced_end"):
            self.stopped = time.perf_counter()
        jax.profiler.stop_trace()

    def join(self):
        """Wait for the slice to end (the timer thread stops the profiler)."""
        if self.timer is not None:
            self.timer.join()
            self.stop()

    def result(self, log, spans=(), chips: int = 1) -> dict:
        """The reduction of the slice. ``spans`` are the harness's host
        spans on ``perf_counter``; they are put on the trace's clock by the
        begin marker, since the profiler records a span only when it ends
        and a step in flight when the slice ends would be lost. Busy time is
        averaged over the ``chips`` the run uses (the profiler also writes
        planes for devices the run does not use)."""
        files = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if not files:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        t0 = time.perf_counter()
        ev, layout = read_profile(max(files, key=os.path.getmtime))
        begin = [s for n, s, _ in ev.host if n == "traced_begin"]
        end = [s for n, s, _ in ev.host if n == "traced_end"]
        if not begin or not end:
            raise RuntimeError("the trace lacks the slice's marker spans")
        ev.devices = chips
        shift = begin[0] - self.started
        ev.host = [(n, s + shift, e + shift) for n, s, e in spans
                   if n not in ("window", "traced")]  # containers name nothing
        out = reduce(ev, (begin[0], end[0]))
        host = sum(v for k, v in layout.items() if not k.startswith("/device:"))
        log(f"trace: {os.path.getsize(files[0])} bytes, read in "
            f"{time.perf_counter() - t0:.1f}s; {host} host events; device "
            "events per line: " + ", ".join(
                f"{k}={v}" for k, v in sorted(layout.items())
                if k.startswith("/device:")))
        return out
