"""The program's spans, counters and device scopes (``repro.obs``): the
facility itself, the serve engine's spans and counters, and the named
scopes that the compiled GP-core programs must keep."""
import glob
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import GPConfig, fit
from repro.core.bayesopt import acquisition_stats
from repro.streaming import GPServeEngine
from repro.streaming.updates import _insert_impl

# the serving cells' engine cut to the harness's tiny sizes
# (tests/bench/benchtiny.py): window 512, 8 slots
N, SLOTS, D = 512, 8, 2
CFG = GPConfig(q=0, solver="pcg", solver_iters=40, precond="none",
               gband="full", backend="jax")


@pytest.fixture(autouse=True)
def _clean():
    obs.reset()
    yield
    obs.reset()


def _names(recs):
    return [n for n, _, _ in recs]


def test_span_nesting_and_order():
    with obs.span("outer"):
        with obs.span("inner"):
            pass
        with obs.span("second"):
            pass
    recs = obs.records()
    # a span is recorded when it ends: inner ones first
    assert _names(recs) == ["inner", "second", "outer"]
    by = {n: (s, e) for n, s, e in recs}
    so, eo = by["outer"]
    for n in ("inner", "second"):
        s, e = by[n]
        assert so <= s <= e <= eo
    assert by["inner"][1] <= by["second"][0]


def test_span_records_when_the_block_raises():
    with pytest.raises(ValueError):
        with obs.span("failing"):
            raise ValueError("x")
    assert _names(obs.records()) == ["failing"]


def test_record_and_ring_bound():
    obs.record("queued", 1.0, 2.5)
    assert obs.records() == [("queued", 1.0, 2.5)]
    for i in range(obs.RING + 5):
        obs.record("r", float(i), float(i))
    recs = obs.records()
    assert len(recs) == obs.RING
    # the oldest fell out; the newest is last
    assert recs[0] == ("r", 5.0, 5.0)
    assert recs[-1] == ("r", float(obs.RING + 4), float(obs.RING + 4))


def test_counters():
    obs.count("a")
    obs.count("a", 3)
    obs.count("b", 0)
    assert obs.counters() == {"a": 4, "b": 0}
    c = obs.counters()
    c["a"] = 99  # a copy
    assert obs.counters()["a"] == 4
    obs.reset()
    assert obs.counters() == {} and obs.records() == []


def test_span_is_a_prefixed_host_event_in_a_profile(tmp_path):
    from jax.profiler import ProfileData

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with obs.span("engine.fetch"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    files = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    names = [e.name for p in ProfileData.from_file(files[0]).planes
             if not p.name.startswith("/device:")
             for line in p.lines for e in line.events]
    assert names.count("repro.engine.fetch") == 1
    assert _names(obs.records()) == ["engine.fetch"]


def _data(n, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.random((n, D)) * 5.0
    Y = np.sin(X).sum(1) + 0.1 * rng.standard_normal(n)
    return jnp.asarray(X), jnp.asarray(Y)


@pytest.fixture(scope="module")
def gp():
    X, Y = _data(N)
    return fit(CFG, X, Y, jnp.asarray([0.9, 1.3]), 0.5)


def test_engine_tick_and_fence_spans_and_counters(gp):
    bounds = jnp.asarray([[0.0, 5.0]] * D)
    eng = GPServeEngine(gp, bounds, batch_slots=SLOTS, window=N)
    obs.reset()
    xq = np.asarray(_data(5, seed=1)[0])
    qs = [eng.submit(x, kind="acq") for x in xq[:3]]
    assert len(eng.step()) == 3  # one tick
    eng.insert(xq[3], 0.5)  # the window is full: an evict and an insert
    qs += [eng.submit(x, kind="mean") for x in xq[3:]]
    done = eng.step()  # the fence, then a tick of the queries it held
    assert len(done) == 2 and all(q.done for q in qs)

    want = {"engine.ticks": 2, "engine.slot_ticks": 5, "engine.admitted": 5,
            "engine.fences": 1, "engine.mutations": 2}
    # the solve-route counters move only where a program was traced
    assert {k: v for k, v in obs.counters().items()
            if not k.startswith("solve.")} == want
    st = eng.stats()
    assert {k: st[k] for k in want} == want
    assert {k for k in st if k.startswith("solve.")} <= {"solve.cr",
                                                         "solve.scan"}
    assert set(st["compiled"]) == {"engine_step", "insert", "evict"}
    assert all(v >= 1 for v in st["compiled"].values())

    recs = obs.records()
    names = _names(recs)
    assert names.count("engine.step") == 2
    for n in ("engine.admit", "engine.dispatch", "engine.fetch",
              "engine.retire"):
        assert names.count(n) == 2, n
    (fence,) = [(s, e) for n, s, e in recs if n == "engine.fence"]
    inside = [n for n, s, e in recs if fence[0] <= s <= e <= fence[1]]
    assert inside == ["fence.evict", "fence.insert", "fence.health",
                      "fence.best_y", "engine.fence"]
    # the fence lies in the second step, before that step's tick
    steps = [(s, e) for n, s, e in recs if n == "engine.step"]
    assert steps[1][0] <= fence[0] <= fence[1] <= steps[1][1]
    (fetch2,) = [s for n, s, _ in recs if n == "engine.fetch"][1:]
    assert fence[1] <= fetch2

    # one queued interval per admitted query, from submit to admission
    queued = [(s, e) for n, s, e in recs if n == "engine.queued"]
    assert len(queued) == 5
    assert sorted(s for s, _ in queued) == sorted(q.submitted for q in qs)
    assert all(s <= e for s, e in queued)
    admits = [e for n, _, e in recs if n == "engine.admit"]
    assert all(e <= admits[-1] for _, e in queued)
    assert all(e <= time.perf_counter() for _, e in queued)


def _op_names(hlo: str) -> str:
    return "\n".join(line for line in hlo.splitlines() if "op_name=" in line)


@pytest.fixture
def no_persistent_cache():
    # the persistent cache's key leaves out the op_name metadata: a program
    # cached by a build without the scopes would come back without them
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", was)


def test_compiled_programs_keep_the_core_scopes(gp, no_persistent_cache):
    """A refactor that drops a scope from the serve path's programs shows
    here, not as a silent hole in a device trace's breakdown."""
    X = jnp.asarray(_data(SLOTS, seed=2)[0])
    tick = acquisition_stats.lower(gp, X, 2.0, 0.0, kind="ucb").compile()
    ops = _op_names(tick.as_text())
    for s in ("banded.solve", "backfit.solve", "acq.mean", "acq.variance",
              "acq.grad", "kp.windows"):
        assert f"{obs.PREFIX}{s}" in ops, s
    from repro.core.additive_gp import with_capacity

    g = with_capacity(gp, 2 * N)
    ins = _insert_impl.lower(g, X[0], jnp.asarray(0.5), 10).compile()
    ops = _op_names(ins.as_text())
    for s in ("band_inverse.rgf", "mutation.splice", "kp.build",
              "backfit.solve", "banded.matmul"):
        assert f"{obs.PREFIX}{s}" in ops, s


@pytest.mark.parametrize("program", ["tick", "insert", "evict"])
def test_engine_programs_route_solves_to_cr(gp, program):
    """Above ``kernels.ops.CR_MIN_BLOCK_ROWS`` block rows the engine's tick,
    insert and evict programs run their unpivoted solves by block cyclic
    reduction, which ``stats()`` reports as ``solve.cr``."""
    from repro.core.additive_gp import with_capacity
    from repro.kernels.ops import CR_MIN_BLOCK_ROWS
    from repro.streaming.gp_engine import _engine_step
    from repro.streaming.updates import _evict_impl

    g = with_capacity(gp, 2 * N)
    assert 2 * N >= CR_MIN_BLOCK_ROWS
    X = jnp.asarray(_data(SLOTS, seed=3)[0])
    lower = {
        "tick": lambda: _engine_step.lower(
            g, X, 2.0, 0.0, jnp.zeros(D), jnp.full(D, 5.0), 0.05, kind="ucb"),
        "insert": lambda: _insert_impl.lower(g, X[0], jnp.asarray(0.5), 10),
        "evict": lambda: _evict_impl.lower(g, 10),
    }[program]
    jax.clear_caches()  # trace afresh: the counters move at trace time
    lower()
    bounds = jnp.asarray([[0.0, 5.0]] * D)
    st = GPServeEngine(gp, bounds, batch_slots=SLOTS).stats()
    assert st.get("solve.cr", 0) >= 1
