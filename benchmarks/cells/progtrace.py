"""What the program says of itself: its host spans and counters
(``repro.obs``), and the device trace read by the program's named scopes.

The per-layer readers of the engine, mutation and solve layers read from
here. Host-clock numbers come from ``repro.obs.records()``, restricted to
the harness's ``window`` span (both on ``time.perf_counter``), and need no
trace. Device numbers come from the profile that ``--trace 1`` writes: each
device operation is put under the innermost ``repro.`` scope of its
``op_name`` path (read from the compiled programs, see ``program_scopes``),
and the program's spans are moved onto the trace's clock by the slice's
begin marker, so that each idle gap of the slice is named by the innermost
span that holds most of it, or ``in <program>`` when it lies inside one
program's run on the device (the host was not the cause).

A program without ``repro.obs`` has no spans and no scopes: every reader
then returns None, and nothing here raises.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import time

import numpy as np

import spec
import xtrace

TRACE_DIR = os.path.join(spec.ROOT, ".bench_trace")  # where run.py traces
# a scope in an op_name path: after its start, a "/" or a transform's "("
SCOPE = re.compile(r"(?:^|[/(\"])repro\.([A-Za-z0-9_]+(?:\.[A-Za-z0-9_]+)+)")
# an instruction of an HLO module's text and its op_name
HLO_OP = re.compile(r'^\s*(?:ROOT\s+)?%?([^\s=]+) = .*?\bop_name="([^"]*)"',
                    re.MULTILINE)
TOP = xtrace.TOP
UNSCOPED = "(no scope)"
WAITS = ("engine.queued",)  # records of waiting, not of host work

_cache: dict = {}


def _log(msg: str) -> None:
    print(f"[progtrace] {msg}", flush=True)


def obs():
    """The program's ``repro.obs``, or None where the program has none."""
    try:
        from repro import obs as o
    except ImportError:
        return None
    return o


def window(run):
    """(start, end) of the harness's measured window, on perf_counter."""
    w = [(s, e) for n, s, e in run.spans.items if n == "window"]
    return w[0] if w else None


def records(run, name: str | None = None) -> list:
    """The program's records that lie in the window, oldest first."""
    o, w = obs(), window(run)
    if o is None or w is None:
        return []
    return [r for r in o.records()
            if w[0] <= r[1] and r[2] <= w[1] and (name is None or r[0] == name)]


def durations_ms(run, name: str) -> np.ndarray:
    return np.array([(e - s) * 1e3 for _, s, e in records(run, name)])


def within(outer, inner) -> list:
    """For each (start, end) of ``outer``, the intervals of ``inner`` that
    lie inside it (both lists sorted by start)."""
    starts = [s for s, _ in inner]
    out = []
    for s, e in outer:
        i = bisect.bisect_left(starts, s)
        got = []
        while i < len(inner) and inner[i][0] <= e:
            if inner[i][1] <= e:
                got.append(inner[i])
            i += 1
        out.append(got)
    return out


def median_ms(v) -> float | None:
    return float(np.median(v)) if len(v) else None


# --- the device trace, by scope --------------------------------------------

def scope_of(path: str) -> str | None:
    """The innermost ``repro.`` scope of an op_name path (or of any text
    that holds one), without the prefix."""
    found = SCOPE.findall(path)
    return found[-1] if found else None


def hlo_scopes(text: str) -> dict:
    """{instruction: innermost scope} of an HLO module's text, for the
    instructions whose ``op_name`` holds a ``repro.`` scope."""
    out = {}
    for m in HLO_OP.finditer(text):
        sc = scope_of(m.group(2))
        if sc is not None:
            out[m.group(1)] = sc
    return out


def program_scopes() -> dict:
    """{program: {instruction: scope}} of every compiled program alive in
    this process. A v5e device trace names each operation by its
    instruction in the optimized HLO and carries no ``op_name`` (its
    ``XLA Ops`` events hold only device offsets and durations), so the
    metadata is read from the programs themselves: they live on in JAX's
    caches after the window."""
    import gc

    try:
        from jax._src.interpreters.pxla import MeshExecutable
    except ImportError:
        return {}
    out: dict = {}
    for obj in gc.get_objects():
        if not isinstance(obj, MeshExecutable):
            continue
        try:
            mods = obj.xla_extension_executable().hlo_modules()
        except Exception:  # an executable that cannot show its HLO
            continue
        for m in mods:
            out.setdefault(m.name, {}).update(hlo_scopes(m.to_string()))
    return out


def _profile_file(cell: str) -> str | None:
    files = glob.glob(os.path.join(TRACE_DIR, cell, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(files, key=os.path.getmtime) if files else None


def read_scoped(path: str, chips: int, table: dict):
    """Device operations of a profile with their program and scope.

    Returns ``(ops, modules, markers)``: ops as (start, end, device, scope,
    module) on the trace's clock in seconds, sorted by start with an
    enclosing op before the ops nested in it, the scope looked up in
    ``table`` ({program: {instruction: scope}}); modules as (name, start,
    end, device); the slice's marker spans by name."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    raw, modules, markers, dev = [], [], {}, 0
    for plane in pd.planes:
        device = plane.name.startswith("/device:") and "CPU" not in plane.name
        if device:
            dev += 1
            if dev > chips:  # planes of devices the run does not use
                continue
        for line in plane.lines:
            if device and line.name == "XLA Modules":
                for e in line.events:
                    s = e.start_ns * 1e-9
                    modules.append((xtrace._module_name(e.name), s,
                                    s + e.duration_ns * 1e-9, dev - 1))
            elif device and line.name == "XLA Ops":
                raw.append((dev - 1, line.events))
            elif not device:
                for e in line.events:
                    if e.name in ("bench.traced_begin", "bench.traced_end"):
                        markers[e.name[len("bench."):]] = e.start_ns * 1e-9
    modules.sort(key=lambda m: (m[3], m[1]))
    ops = []
    for d, events in raw:
        mods = [m for m in modules if m[3] == d]
        starts = [m[1] for m in mods]
        for e in events:
            s = e.start_ns * 1e-9
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][0] if i >= 0 and s <= mods[i][2] else ""
            # '%fusion.12 = f64[...] fusion(...), ...' -> 'fusion.12'
            name = e.name.split(" ", 1)[0].lstrip("%")
            ops.append((s, s + e.duration_ns * 1e-9, d,
                        table.get(mod, {}).get(name), mod))
    ops.sort(key=lambda o: (o[0], -o[1]))  # an enclosing op before its body
    return ops, modules, markers


def resolve(ops):
    """Each op's own device time, its length less that of the ops nested in
    it (a loop's body runs inside the loop's event), so that the times add
    up to the busy time of each device; and its scope, that of the op
    enclosing it where it has none of its own (an operation the compiler
    added inside a scoped loop belongs to that loop's scope)."""
    own = [t - s for s, t, *_ in ops]
    scopes = [o[3] for o in ops]
    stacks: dict = {}
    for i, (s, t, d, *_) in enumerate(ops):
        st = stacks.setdefault(d, [])
        while st and ops[st[-1]][1] <= s:
            st.pop()
        if st:
            p = st[-1]
            own[p] -= min(t, ops[p][1]) - s
            if scopes[i] is None:
                scopes[i] = scopes[p]
        st.append(i)
    return [max(0.0, x) for x in own], scopes


def calls(ops, scope: str, lo: float, hi: float) -> list:
    """Device time of each call of ``scope`` that lies wholly inside
    [lo, hi]: a call is a maximal run of consecutive ops (by start, on one
    device) whose innermost scope is ``scope``; its time is the union of
    its ops. The runs at either edge of the slice may be cut, so they are
    left out."""
    out = []
    for d in sorted({o[2] for o in ops}):
        seq = [o for o in ops if o[2] == d and lo <= o[0] and o[1] <= hi]
        runs, cur = [], None
        for o in seq:
            if cur is not None and cur[0] == o[3]:
                cur[1].append((o[0], o[1]))
            else:
                cur = [o[3], [(o[0], o[1])]]
                runs.append(cur)
        for j, (sc, iv) in enumerate(runs):
            if sc == scope and 0 < j < len(runs) - 1:
                out.append(xtrace.union_length(iv))
    return out


def _name_gap(mods, host, s, e) -> str:
    """``in <program>`` for a gap inside one program's run on the device;
    else the name that holds most of the gap when each instant of it goes
    to the innermost span covering it, ``untraced host`` where none does."""
    for n, ms, me, _ in mods:
        if ms <= s and e <= me:
            return f"in {n}"
    cover = [(n, max(s, hs), min(e, he), he - hs) for n, hs, he in host
             if hs < e and he > s]
    cuts = sorted({s, e} | {c[1] for c in cover} | {c[2] for c in cover})
    held: dict = {}
    for a, b in zip(cuts, cuts[1:]):
        inner = [c for c in cover if c[1] <= a and b <= c[2]]
        n = min(inner, key=lambda c: c[3])[0] if inner else "untraced host"
        held[n] = held.get(n, 0.0) + b - a
    return max(held.items(), key=lambda kv: kv[1])[0] if held else \
        "untraced host"


def analyse(run):
    """The scoped reduction of this run's traced slice, once per run; None
    without a trace or without a program that scopes its work."""
    if run.trace is None or obs() is None:
        return None
    path = _profile_file(run.cell.name)
    traced = [(s, e) for n, s, e in run.spans.items if n == "traced"]
    if path is None or not traced:
        return None
    key = (path, os.path.getmtime(path))
    if key in _cache:
        return _cache[key]
    t0 = time.perf_counter()
    table = program_scopes()
    ops, modules, markers = read_scoped(path, run.cell.chips, table)
    if "traced_begin" not in markers or "traced_end" not in markers:
        _cache[key] = None
        return None
    lo, hi = markers["traced_begin"], markers["traced_end"]
    chips = max(1, run.cell.chips)
    ops = [o for o in ops if o[1] > lo and o[0] < hi]
    clipped = [(max(s, lo), min(t, hi)) + tuple(r) for s, t, *r in ops]
    own, scopes = resolve(clipped)
    ops = [o[:3] + (sc,) + o[4:] for o, sc in zip(ops, scopes)]
    by_scope: dict = {}
    n_events: dict = {}
    for sc, x in zip(scopes, own):
        sc = sc or UNSCOPED
        by_scope[sc] = by_scope.get(sc, 0.0) + x / chips
        n_events[sc] = n_events.get(sc, 0) + 1
    total = sum(by_scope.values())
    by_dev: dict = {}
    for s, t, d, *_ in clipped:
        by_dev.setdefault(d, []).append((s, t))
    busy = sum(xtrace.union_length(v) for v in by_dev.values()) / chips
    # idle gaps, named by the program's running, or by the innermost span
    # of the program or the harness; spans moved onto the trace's clock by
    # the begin marker, as xtrace does with the harness's
    shift = lo - traced[0][0]
    host = [(n, s + shift, e + shift) for n, s, e in obs().records()
            if n not in WAITS]
    host += [(n, s + shift, e + shift) for n, s, e in run.spans.items
             if n not in ("window", "traced")]
    host = [h for h in host if h[2] > lo and h[1] < hi]
    mods = [m for m in modules if m[2] > lo and m[1] < hi]
    gaps = sorted(xtrace._gaps([(s, t) for s, t, *_ in clipped], lo, hi),
                  key=lambda g: g[0] - g[1])[:TOP]
    out = {
        "window_s": hi - lo,
        "busy_s": busy,
        "scoped_share": 1.0 - by_scope.get(UNSCOPED, 0.0) / total
        if total else None,
        "scopes": sorted(by_scope.items(), key=lambda kv: -kv[1]),
        "idle_gaps": [(_name_gap(mods, host, s, e), e - s) for s, e in gaps],
        "ops": ops,
        "own": own,
        "modules": mods,
        "lo": lo,
        "hi": hi,
    }
    _cache[key] = out
    _log(f"{len(ops)} device ops in the slice read in "
         f"{time.perf_counter() - t0:.1f}s; scoped instructions in "
         + ", ".join(f"{m}={len(v)}" for m, v in sorted(table.items()) if v))
    _log("scopes (device s/events): " + ", ".join(
        f"{n}={v:.6f}s/{n_events[n]}" for n, v in out["scopes"]))
    if total:
        _log(f"busy {busy:.6f}s of {hi - lo:.6f}s; "
             f"{100 * out['scoped_share']:.2f}% of the device time under a "
             "repro scope")
    _log("idle gaps: " + ", ".join(
        f"{n}={t:.6f}s" for n, t in out["idle_gaps"]))
    return out


def mean_call_ms(run, scope: str) -> float | None:
    a = analyse(run)
    if a is None:
        return None
    d = calls(a["ops"], scope, a["lo"], a["hi"])
    if d:
        _log(f"{scope}: {len(d)} calls wholly in the slice, "
             f"mean {np.mean(d) * 1e3:.4f}ms")
    return float(np.mean(d) * 1e3) if d else None


def per_run_ms(run, scope: str, program: str | None = None) -> float | None:
    """Mean device time under ``scope`` per run of a program that holds it,
    over the program runs wholly inside the slice: the scope's own time
    summed within each run. XLA interleaves a scope's operations with
    independent work of the same program (the RGF sweep of a mutation with
    its warm solve), so where a program calls a scope once, this is its
    time per call, and a run of consecutive ops is only a piece of it.
    ``program``, where given, keeps only the programs whose name holds it
    (the fit's PCG apart from the variance's)."""
    a = analyse(run)
    if a is None:
        return None
    per = []
    for name, ms, me, d in a["modules"]:
        if program is not None and program not in name:
            continue
        if a["lo"] <= ms and me <= a["hi"]:
            t = sum(x for o, x in zip(a["ops"], a["own"])
                    if o[2] == d and o[3] == scope and ms <= o[0] < me)
            if t > 0:
                per.append(t)
    if per:
        _log(f"{scope}: {len(per)} program runs wholly in the slice, "
             f"mean {np.mean(per) * 1e3:.4f}ms")
    return float(np.mean(per) * 1e3) if per else None
