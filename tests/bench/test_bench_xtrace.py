"""The reduction from a trace to device numbers, on synthesized events and
on a trace the profiler records on the CPU."""
import glob
import os

import pytest

import benchtiny  # noqa: F401
import xtrace


def _events():
    # one device; window [0, 10]: busy [1, 3] (two overlapping ops), [4, 5],
    # [7, 9.5]; the host waited in [3, 4], fenced in [5, 7], ticked elsewhere
    ops = [("fusion.1", 1.0, 2.5, 0), ("fusion.2", 2.0, 3.0, 0),
           ("while.3", 4.0, 5.0, 0), ("fusion.1", 7.0, 9.5, 0),
           ("fusion.9", 11.0, 12.0, 0)]  # outside the window
    mods = [("jit__engine_step", 1.0, 3.0, 0), ("jit__insert_impl", 4.0, 5.0, 0),
            ("jit__engine_step", 7.0, 9.5, 0)]
    host = [("tick", 0.5, 3.2), ("wait", 3.2, 4.0),
            ("fence", 4.0, 7.0), ("admit", 5.5, 6.9), ("tick", 7.0, 10.0)]
    return xtrace.Events(ops=ops, modules=mods, host=host, devices=1)


def test_union_and_idle():
    r = xtrace.reduce(_events(), (0.0, 10.0))
    assert r["window_s"] == pytest.approx(10.0)
    assert r["busy_s"] == pytest.approx(2.0 + 1.0 + 2.5)
    # per-op totals, largest first, clipped to the window
    assert r["device_ops"][0] == ("fusion.1", pytest.approx(4.0))
    assert dict(r["modules"])["jit__insert_impl"] == pytest.approx(1.0)
    # whole program runs only: the window cuts none of these three
    assert sorted(n for n, _ in r["module_runs"]) == [
        "jit__engine_step", "jit__engine_step", "jit__insert_impl"]
    assert xtrace.reduce(_events(), (0.0, 9.0))["module_runs"] == [
        ("jit__engine_step", pytest.approx(2.0)),
        ("jit__insert_impl", pytest.approx(1.0))]
    # the longest gaps first, each named by the span that covers most of it:
    # [5, 7] lies in the fence; [0, 1] half in the first tick; [3, 4] is
    # 0.8 s of waiting against 0.2 s of ticking; [9.5, 10] in the last tick
    assert [(n, round(t, 6)) for n, t in r["idle_gaps"]] == [
        ("fence", 2.0), ("tick", 1.0), ("wait", 1.0), ("tick", 0.5)]
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(10.0 - r["busy_s"])


def test_devices_are_averaged():
    ev = _events()
    ev.ops += [(n, s, e, 1) for n, s, e, _ in ev.ops]
    ev.devices = 2
    assert xtrace.reduce(ev, (0.0, 10.0))["busy_s"] == pytest.approx(5.5)


def test_union_length():
    assert xtrace.union_length([]) == 0.0
    assert xtrace.union_length([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3.0)
    assert xtrace.union_length([(3, 4), (0, 10)]) == pytest.approx(10.0)


def test_recorded_cpu_trace(tmp_path):
    """The profiler's own file: the slice's markers and the harness's
    spans come back by name and on one clock, and the reduction runs on
    what it holds."""
    import jax
    import jax.numpy as jnp

    spans = xtrace.Spans()
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tracer = xtrace.Tracer(str(tmp_path / "t"))
    tracer.start(60.0)
    for _ in range(3):
        with spans.span("tick"):
            f(x).block_until_ready()
    tracer.timer.cancel()
    tracer.join()
    assert tracer.started < tracer.stopped
    r = tracer.result(lambda msg: None, spans.items)
    assert 0.0 < r["window_s"] < tracer.stopped - tracer.started + 1.0
    # every gap of the slice lies in a tick or between them
    assert all(n in ("tick", "untraced host") for n, _ in r["idle_gaps"])
    assert 0.0 <= r["busy_s"] <= r["window_s"]
    files = glob.glob(os.path.join(str(tmp_path), "t", "plugins", "profile",
                                   "*", "*.xplane.pb"))
    ev, layout = xtrace.read_profile(files[0])
    names = [n for n, _, _ in ev.host]
    assert names.count("tick") == 3 and layout
    b = [s for n, s, _ in ev.host if n == "traced_begin"][0]
    e = [s for n, s, _ in ev.host if n == "traced_end"][0]
    assert all(b <= s <= t <= e for n, s, t in ev.host if n == "tick")


def test_timer_ends_the_slice(tmp_path):
    tracer = xtrace.Tracer(str(tmp_path / "t"))
    tracer.start(0.2)
    tracer.join()
    assert 0.15 < tracer.stopped - tracer.started < 5.0
