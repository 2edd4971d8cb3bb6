"""The readers of what the program says of itself (``progtrace.py``): device
operations by scope and their calls, idle gaps named by the program's spans
or by the program running on the device, and the host-clock readers of the
program's records, on synthesized intervals and in a traced run on the CPU."""
import types

import pytest

import benchtiny
import progtrace
import spec
import xtrace
from repro import obs

NEW = ("tick_fetch_ms", "tick_host_ms", "fence_ms", "queue_wait_ms",
       "banded_solve_ms", "rgf_sweep_ms")


@pytest.fixture(autouse=True)
def _clean():
    obs.reset()
    progtrace._cache.clear()
    yield
    obs.reset()
    progtrace._cache.clear()


def _ops():
    # one device, slice [0, 20]. (start, end, device, scope, module); a
    # loop's event encloses its body, and a solve's loop sits in the PCG's
    S, B = "banded.solve", "backfit.solve"
    ops = [(0.0, 1.0, 0, S, "m"),                 # cut by the slice's start
           (1.0, 2.0, 0, B, "m"),
           (2.0, 10.0, 0, B, "m"),                # the PCG loop, enclosing:
           (2.0, 4.0, 0, S, "m"), (2.0, 3.0, 0, S, "m"), (3.5, 4.0, 0, S, "m"),
           (4.0, 5.0, 0, B, "m"),
           (5.0, 6.0, 0, S, "m"), (6.0, 7.5, 0, S, "m"),
           (8.0, 9.0, 0, "acq.grad", "m"),
           (12.0, 13.0, 0, None, "m2"),
           (19.0, 21.0, 0, S, "m2")]              # cut by the slice's end
    return sorted(ops, key=lambda o: (o[0], -o[1]))


def test_calls_split_by_scope():
    # the runs of banded.solve wholly inside: [2, 4] and [5, 7.5] (its ops
    # 5-6 and 6-7.5 are one call); the edge runs are left out
    got = progtrace.calls(_ops(), "banded.solve", 0.0, 20.0)
    assert got == [pytest.approx(2.0), pytest.approx(2.5)]
    assert progtrace.calls(_ops(), "acq.grad", 0.0, 20.0) == [
        pytest.approx(1.0)]
    assert progtrace.calls(_ops(), "band_inverse.rgf", 0.0, 20.0) == []


def test_own_times_add_up_to_busy_and_scopes_inherit():
    ops = [o for o in _ops() if o[1] <= 20.0]
    # an op the compiler added inside the PCG loop, with no scope of its own
    ops = sorted(ops + [(7.6, 7.9, 0, None, "m")], key=lambda o: (o[0], -o[1]))
    own, scopes = progtrace.resolve(ops)
    busy = xtrace.union_length([(o[0], o[1]) for o in ops])
    assert sum(own) == pytest.approx(busy)
    by = {}
    for sc, x in zip(scopes, own):
        by[sc] = by.get(sc, 0.0) + x
    # [1, 2], [4, 5], and the PCG loop [2, 10] keeps what its body does not
    # cover: [7.5, 8] and [9, 10], the added op [7.6, 7.9] among it
    assert by["backfit.solve"] == pytest.approx(1.0 + 1.0 + 1.5)
    assert by["banded.solve"] == pytest.approx(1.0 + 2.0 + 2.5)
    assert by[None] == pytest.approx(1.0)  # [12, 13], outside any scope


def test_scope_of_an_op_name_path():
    p = "jit(_engine_step)/jit(main)/repro.acq.variance/while/body/repro.banded.solve/dot"
    assert progtrace.scope_of(p) == "banded.solve"
    assert progtrace.scope_of("jit(f)/transpose(jvp(repro.kp.windows))/mul") \
        == "kp.windows"
    assert progtrace.scope_of('op_name="repro.acq.mean/add"') == "acq.mean"
    # a source path or module name is no scope
    assert progtrace.scope_of("src/repro/core/bayesopt.py:80") is None
    assert progtrace.scope_of("import repro.core.bayesopt") is None


def test_scopes_of_a_compiled_program():
    """The op_name metadata of a program compiled in this process maps its
    instructions to their innermost scopes."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with obs.scope("banded.solve"):
            y = jax.lax.fori_loop(0, 3, lambda i, c: jnp.cos(c) @ c, x)
        with obs.scope("acq.grad"):
            return jnp.sin(y) + 1.0

    f(jnp.ones((8, 8)) * 0.123).block_until_ready()
    table = progtrace.program_scopes()
    assert "jit_f" in table
    got = set(table["jit_f"].values())
    assert {"banded.solve", "acq.grad"} <= got
    text = ('  %while.3 = (s32[]) while(%t), condition=%c, body=%b, '
            'metadata={op_name="jit(g)/repro.acq.variance/while"}\n'
            '  ROOT %fusion.9 = f64[8]{0} fusion(%p), kind=kLoop, '
            'metadata={op_name="jit(g)/repro.acq.variance/while/body/'
            'repro.banded.solve/add" source_file="/src/repro/core/x.py"}\n'
            '  %copy.1 = f64[8]{0} copy(%p)\n')
    assert progtrace.hlo_scopes(text) == {"while.3": "acq.variance",
                                          "fusion.9": "banded.solve"}


def test_gaps_named_by_module_then_innermost_span():
    mods = [("jit__engine_step", 0.0, 10.0, 0)]
    host = [("tick", 0.0, 12.0), ("engine.step", 0.1, 11.9),
            ("engine.fetch", 0.5, 11.0), ("engine.retire", 11.0, 11.5)]
    # inside the program's run on the device: not the host's doing
    assert progtrace._name_gap(mods, host, 2.0, 3.0) == "in jit__engine_step"
    # after it, under a program span inside the harness's tick
    assert progtrace._name_gap(mods, host, 11.1, 11.4) == "engine.retire"
    assert progtrace._name_gap(mods, host, 10.2, 10.8) == "engine.fetch"
    # straddling the program's end: the span that covers it
    assert progtrace._name_gap(mods, host, 9.5, 10.5) == "engine.fetch"
    # split between program spans: the innermost span of the most of it
    host += [("engine.admit", 20.0, 20.2), ("engine.dispatch", 20.2, 21.0),
             ("tick", 19.9, 30.0), ("engine.step", 19.95, 29.0)]
    assert progtrace._name_gap(mods, host, 20.0, 20.5) == "engine.dispatch"
    assert progtrace._name_gap(mods, host, 19.94, 20.1) == "engine.admit"
    # mostly before any span (the profiler's own start): no span's doing
    assert progtrace._name_gap(mods, host, 19.5, 20.1) == "untraced host"
    assert progtrace._name_gap([], [], 1.0, 2.0) == "untraced host"


def test_analyse_a_slice(tmp_path, monkeypatch):
    """The scoped reduction of a synthesized slice [100, 120]: scopes by own
    device time, calls, and gaps named by the program's spans, which are
    moved onto the trace's clock by the begin marker."""
    prof = tmp_path / "x.xplane.pb"
    prof.write_bytes(b"")
    ops = [(s + 100.0, t + 100.0, d, sc, m) for s, t, d, sc, m in _ops()
           if t <= 20.0]
    mods = [("m", 100.0, 110.0, 0), ("m2", 112.0, 113.0, 0)]
    monkeypatch.setattr(progtrace, "_profile_file", lambda cell: str(prof))
    monkeypatch.setattr(progtrace, "program_scopes", lambda: {})
    monkeypatch.setattr(progtrace, "read_scoped", lambda *a: (
        ops, mods, {"traced_begin": 100.0, "traced_end": 120.0}))
    # the harness's slice began at 5.0 on perf_counter: 100.0 on the trace's
    obs.record("engine.retire", 15.0, 16.5)
    obs.record("engine.queued", 10.0, 25.0)  # a wait, names nothing
    run = _run([("tick", 4.0, 30.0), ("window", 0.0, 40.0),
                ("traced", 5.0, 25.0)], trace={})
    a = progtrace.analyse(run)
    assert a["busy_s"] == pytest.approx(11.0)
    sc = dict(a["scopes"])
    assert sc["banded.solve"] == pytest.approx(5.5)
    assert sc[progtrace.UNSCOPED] == pytest.approx(1.0)
    assert a["scoped_share"] == pytest.approx(10.0 / 11.0)
    gaps = dict((n, t) for n, t in a["idle_gaps"])
    assert gaps["engine.retire"] == pytest.approx(2.0)   # [110, 112]
    assert gaps["tick"] == pytest.approx(7.0)            # [113, 120]
    assert "in m" not in gaps  # the device never idles inside m here
    assert _reader("banded_solve_ms")(run) == pytest.approx(2250.0)
    # per program run wholly in the slice: m [100, 110] holds all of it
    assert progtrace.per_run_ms(run, "banded.solve") == pytest.approx(5500.0)
    assert _reader("rgf_sweep_ms")(run) is None


def _run(items, trace=None, name="stream-ycsb-a"):
    cell = types.SimpleNamespace(name=name, chips=1)
    return types.SimpleNamespace(cell=cell, spans=types.SimpleNamespace(
        items=items), trace=trace, counters={}, values={}, kind="open_loop")


def _reader(name):
    return spec.layer_reader(name, benchtiny.REPO)


def test_readers_return_none_without_their_records():
    run = _run([("tick", 1.0, 2.0), ("window", 0.0, 10.0)])
    for name in NEW:
        assert _reader(name)(run) is None, name
    # records outside the window count for nothing
    obs.record("engine.fetch", 11.0, 12.0)
    obs.record("engine.queued", -1.0, 0.5)
    assert _reader("tick_fetch_ms")(run) is None
    assert _reader("queue_wait_ms")(run) is None
    # a trace with no profile file for the cell
    run = _run([("window", 0.0, 10.0), ("traced", 1.0, 1.1)], trace={},
               name="no-such-cell")
    assert _reader("banded_solve_ms")(run) is None


def test_host_readers_on_records():
    # two ticks, one of them after a fence; window [0, 100]
    for n, s, e in [("engine.step", 1.0, 1.6), ("engine.fetch", 1.1, 1.5),
                    ("engine.step", 2.0, 3.2), ("engine.fence", 2.0, 2.4),
                    ("engine.fetch", 2.5, 3.0), ("engine.queued", 0.5, 2.0),
                    ("engine.queued", 1.8, 2.0), ("engine.queued", 1.9, 2.0),
                    ("engine.step", 4.0, 4.2),  # a fence alone: no tick
                    ("engine.fence", 4.0, 4.2)]:
        obs.record(n, s, e)
    run = _run([("window", 0.0, 100.0)])
    assert _reader("tick_fetch_ms")(run) == pytest.approx(450.0)
    # 1.6-1.0-0.4 = 0.2 s and 1.2-0.4-0.5 = 0.3 s of host time
    assert _reader("tick_host_ms")(run) == pytest.approx(250.0)
    assert _reader("fence_ms")(run) == pytest.approx(300.0)
    assert _reader("queue_wait_ms")(run) == pytest.approx(200.0)


def test_traced_run_on_the_cpu(tmp_path_factory, monkeypatch):
    """The harness end to end with ``--trace 1`` at tiny size: the host
    readers read the engine's spans; the device readers find no device
    planes on the CPU and give nothing."""
    import numpy as np

    import run as harness

    spans = []
    close = harness.Context.window_close

    def keep(ctx):
        close(ctx)
        spans.extend(ctx.spans.items)

    monkeypatch.setattr(harness.Context, "window_close", keep)
    root = benchtiny.tiny_root(tmp_path_factory.mktemp("cells"))
    r = benchtiny.run_cell(root, "stream-ycsb-a", 2147483659, 8.6,
                           "--rate", "6", "--trace", "1")
    assert r["correct"], r["checks"]
    m = r["metrics"]
    for name in ("tick_fetch_ms", "tick_host_ms", "fence_ms", "queue_wait_ms"):
        assert m[name]["value"] > 0, name
    assert "banded_solve_ms" not in m and "rgf_sweep_ms" not in m
    # each query tick of the harness holds the engine's fetch of that tick;
    # tick_fetch_ms also counts the fetches of the ticks after fences, so
    # its median is no bound on tick_ms's
    (w0, w1), = [(s, e) for n, s, e in spans if n == "window"]
    ticks = [(s, e) for n, s, e in spans if n == "tick"]
    fetches = [(s, e) for n, s, e in obs.records()
               if n == "engine.fetch" and w0 <= s and e <= w1]
    held = progtrace.within(ticks, fetches)
    assert ticks and all(len(f) == 1 for f in held)
    assert all(te - ts >= fe - fs for (ts, te), [(fs, fe)] in zip(ticks, held))
    assert m["tick_ms"]["value"] == pytest.approx(
        1e3 * np.median([e - s for s, e in ticks]))
    assert m["tick_fetch_ms"]["value"] == pytest.approx(
        1e3 * np.median([e - s for s, e in fetches]))


def test_offline_readers(tmp_path, monkeypatch):
    """fit_pcg_ms keeps the fit program's runs (not the variance's PCG);
    mll_device_ms reads the log_likelihood program's runs; idle_share.fit
    reads only an offline cell's trace."""
    prof = tmp_path / "x.xplane.pb"
    prof.write_bytes(b"")
    B = "backfit.solve"
    ops = [(1.0, 3.0, 0, B, "jit__fit_impl"), (4.0, 5.0, 0, B, "jit__fit_impl"),
           (6.0, 9.0, 0, B, "jit_posterior_var"),
           (10.0, 11.0, 0, "banded.logdet", "jit_log_likelihood")]
    mods = [("jit__fit_impl", 0.5, 5.5, 0), ("jit_posterior_var", 6.0, 9.5, 0),
            ("jit_log_likelihood", 10.0, 12.0, 0)]
    monkeypatch.setattr(progtrace, "_profile_file", lambda cell: str(prof))
    monkeypatch.setattr(progtrace, "program_scopes", lambda: {})
    monkeypatch.setattr(progtrace, "read_scoped", lambda *a: (
        ops, mods, {"traced_begin": 0.0, "traced_end": 20.0}))
    trace = {"busy_s": 5.0, "window_s": 20.0,
             "module_runs": [(n, e - s) for n, s, e, _ in mods]}
    run = _run([("window", 0.0, 40.0), ("traced", 0.0, 20.0)], trace=trace,
               name="fig5-fit-mll")
    run.kind = "rounds"
    assert _reader("fit_pcg_ms")(run) == pytest.approx(3000.0)
    assert progtrace.per_run_ms(run, B) == pytest.approx(3000.0)  # both
    assert _reader("mll_device_ms")(run) == pytest.approx(2000.0)
    assert _reader("idle_share.fit")(run) == pytest.approx(75.0)
    assert _reader("idle_share.serve")(run) is None
    run.kind = "open_loop"
    assert _reader("idle_share.fit")(run) is None
    for name in ("fit_pcg_ms", "mll_device_ms", "idle_share.fit"):
        assert _reader(name)(_run([("window", 0.0, 1.0)])) is None, name
