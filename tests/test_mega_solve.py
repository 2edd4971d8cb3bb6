"""Whole-solve mega-kernel: one dispatch, exit parity, warm starts, fleet.

``SolveConfig.fused="whole"`` (``kernels/mega_solve.py``) folds the entire
``solve_mhat`` — warm-start residual, preconditioner seed, the bounded
convergence loop with the PCG tol check, and the exit diagnostics — into ONE
``pallas_call``. The contracts pinned here:

  * the full solve's jaxpr contains exactly one ``pallas_call``, and none
    inside any host-level loop (counted statically, backend-independent);
  * jacobi / gauss_seidel are **bit-identical** at f64 to the per-iteration
    fused host loop (``fused="on"``) — same value-level ops in the same
    order — and convergence-level against the unfused jax path;
  * PCG exits at the **same realized iteration count** as the host loop
    (the tol condition is evaluated on-chip) and matches at convergence
    level (PR-6 bar: the in-kernel inner products associate differently);
  * tol early exit (including the degenerate zero-RHS solve -> 0
    iterations) and the streaming warm start both work in-kernel — the warm
    path exits at the same realized count as the warm host loop (the tol is
    relative to the initial residual, so warm starts tighten the threshold
    rather than exit earlier);
  * the fleet path: a vmapped whole-solve stays lane-for-lane bit-identical
    to the vmapped per-iteration host loop.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backfitting import DimOps, SolveConfig, solve_mhat
from repro.core.banded import add, scale
from repro.core.kernel_packets import kp_factors

METHODS = ("gauss_seidel", "jacobi", "pcg")


def _make_ops(rng, n, D, q, sigma, dtype=jnp.float64):
    X = jnp.asarray(rng.random((n, D)) * 4, dtype)
    sort_idx = jnp.argsort(X.T, axis=1)
    xs = jnp.take_along_axis(X.T, sort_idx, axis=1)
    rank_idx = jnp.argsort(sort_idx, axis=1)
    omega = jnp.asarray(0.8 + rng.random(D), dtype)
    A, Phi = jax.vmap(lambda om, x: kp_factors(q, om, x))(omega, xs)
    SAPhi = add(scale(A, sigma**2), Phi)
    return DimOps(A=A, Phi=Phi, SAPhi=SAPhi, sort_idx=sort_idx,
                  rank_idx=rank_idx, sigma2=jnp.asarray(sigma**2, dtype))


def _rel(got, want):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


def _cfg(method, fused, **kw):
    backend = "jax" if fused == "off" else "pallas"
    return SolveConfig(method=method, iters=kw.pop("iters", 24),
                      backend=backend, fused=fused, **kw)


def _subjaxprs(params):
    from jax.extend.core import ClosedJaxpr, Jaxpr

    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for u in vs:
            if isinstance(u, ClosedJaxpr):
                yield u.jaxpr
            elif isinstance(u, Jaxpr):
                yield u


def _count_pallas(jaxpr, in_loop=False):
    """(pallas_calls inside loop bodies, total pallas_calls) — static."""
    loop = total = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            total += 1
            loop += int(in_loop)
        inner = in_loop or eqn.primitive.name in ("while", "scan")
        for sub in _subjaxprs(eqn.params):
            sl, st = _count_pallas(sub, inner)
            loop += sl
            total += st
    return loop, total


@pytest.fixture(scope="module")
def problem():
    rng = np.random.default_rng(0)
    ops = _make_ops(rng, 64, 3, 1, sigma=0.7)
    v = jnp.asarray(rng.standard_normal((3, 64)))
    return ops, v


# ---------------------------------------------------------------------------
# the tentpole acceptance bar: ONE pallas_call for the whole solve
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", METHODS)
def test_whole_solve_is_one_pallas_call(problem, method):
    ops, v = problem
    cfg = _cfg(method, "whole", tol=1e-8 if method == "pcg" else 0.0)
    closed = jax.make_jaxpr(
        lambda vv: solve_mhat(ops, vv, cfg, return_info=True))(v)
    loop, total = _count_pallas(closed.jaxpr)
    assert total == 1, f"{method}: whole solve dispatched {total} kernels"
    assert loop == 0, f"{method}: a kernel still sits in a host-level loop"


def test_iter_mode_dispatches_per_iteration(problem):
    # the contrast row: fused="on" keeps one dispatch *per iteration*
    ops, v = problem
    cfg = _cfg("gauss_seidel", "on")
    closed = jax.make_jaxpr(lambda vv: solve_mhat(ops, vv, cfg))(v)
    loop, _ = _count_pallas(closed.jaxpr)
    assert loop >= 1


# ---------------------------------------------------------------------------
# stationary methods: bitwise vs the per-iteration fused host loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method", ("gauss_seidel", "jacobi"))
@pytest.mark.parametrize("warm", (False, pytest.param(True, marks=pytest.mark.slow)))
def test_stationary_bitwise_vs_host_loop(problem, method, warm):
    ops, v = problem
    x0 = 0.9 * v if warm else None
    whole, info_w = solve_mhat(ops, v, _cfg(method, "whole"), x0=x0,
                               return_info=True)
    host, info_h = solve_mhat(ops, v, _cfg(method, "on"), x0=x0,
                              return_info=True)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(host))
    # the fused-residual diagnostics agree bitwise too (same k stack)
    np.testing.assert_array_equal(np.asarray(info_w.resid),
                                  np.asarray(info_h.resid))
    unfused = solve_mhat(ops, v, _cfg(method, "off"), x0=x0)
    assert _rel(whole, unfused) < 1e-8


# ---------------------------------------------------------------------------
# PCG: convergence-level x, identical realized iteration counts
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("tol", (pytest.param(0.0, marks=pytest.mark.slow), 1e-9))
def test_pcg_parity_and_iteration_count(problem, tol):
    ops, v = problem
    whole, iw = solve_mhat(ops, v, _cfg("pcg", "whole", tol=tol, iters=40),
                           return_info=True)
    host, ih = solve_mhat(ops, v, _cfg("pcg", "on", tol=tol, iters=40),
                          return_info=True)
    assert int(iw.iters) == int(ih.iters)
    assert _rel(whole, host) < 1e-9
    unfused = solve_mhat(ops, v, _cfg("pcg", "off", tol=tol, iters=40))
    assert _rel(whole, unfused) < 1e-9
    if tol > 0:
        assert 0 < int(iw.iters) < 40  # the on-chip exit actually fired
        assert float(iw.resid) <= 1e-6 * float(iw.rhs)


def test_pcg_zero_rhs_exits_immediately(problem):
    # same cfg as the parity test above so the compiled program is reused
    ops, v = problem
    z = jnp.zeros_like(v)
    out, info = solve_mhat(ops, z, _cfg("pcg", "whole", tol=1e-9, iters=40),
                           return_info=True)
    assert int(info.iters) == 0
    assert float(jnp.max(jnp.abs(out))) == 0.0


def test_pcg_warm_start_matches_host_loop(problem):
    # The tol check is relative to the *initial* residual, so a warm start
    # tightens the exit threshold proportionally — it does NOT exit in fewer
    # iterations (verified: cold and warm both take 23 here, in both modes).
    # The contract is that the in-kernel warm path (residual seeded from x0
    # with no extra host matvec) tracks the per-iteration host loop exactly.
    # cfg matches the parity test so the cold program is a cache hit
    ops, v = problem
    cold, _ = solve_mhat(ops, v, _cfg("pcg", "whole", tol=1e-9, iters=40),
                         return_info=True)
    x0 = 0.5 * cold  # a partially converged iterate, as streaming hands over
    warm_w, iw = solve_mhat(ops, v, _cfg("pcg", "whole", tol=1e-9, iters=40),
                            x0=x0, return_info=True)
    warm_h, ih = solve_mhat(ops, v, _cfg("pcg", "on", tol=1e-9, iters=40),
                            x0=x0, return_info=True)
    assert int(iw.iters) == int(ih.iters)
    assert 0 < int(iw.iters) < 40  # the on-chip exit fired on the warm path
    assert _rel(warm_w, warm_h) < 1e-9
    assert _rel(warm_w, cold) < 1e-6


# ---------------------------------------------------------------------------
# fleet path: vmapped whole-solve == vmapped host loop, lane for lane
# ---------------------------------------------------------------------------


def test_fleet_vmap_bitwise(problem):
    ops, v = problem
    rng = np.random.default_rng(5)
    vs = jnp.asarray(rng.standard_normal((2,) + v.shape))
    run = lambda cfg: jax.vmap(lambda vv: solve_mhat(ops, vv, cfg))(vs)
    np.testing.assert_array_equal(
        np.asarray(run(_cfg("gauss_seidel", "whole"))),
        np.asarray(run(_cfg("gauss_seidel", "on"))))
    got = run(_cfg("pcg", "whole"))
    want = run(_cfg("pcg", "on"))
    assert _rel(got, want) < 1e-9


# ---------------------------------------------------------------------------
# heavier acceptance sweep: multi-RHS, q=0 degenerate solve, larger n
# ---------------------------------------------------------------------------


@pytest.mark.slow
@pytest.mark.parametrize("method", METHODS)
@pytest.mark.parametrize("q,n,B", [(0, 96, 2), (1, 200, 3), (2, 128, 1)])
def test_whole_solve_grid(method, q, n, B):
    rng = np.random.default_rng(q * 1000 + n)
    ops = _make_ops(rng, n, 2, q, sigma=0.6)
    v = jnp.asarray(rng.standard_normal((2, n, B)))
    tol = 1e-9 if method == "pcg" else 0.0
    whole, iw = solve_mhat(ops, v, _cfg(method, "whole", tol=tol, iters=30),
                           return_info=True)
    host, ih = solve_mhat(ops, v, _cfg(method, "on", tol=tol, iters=30),
                          return_info=True)
    if method == "pcg":
        assert int(iw.iters) == int(ih.iters)
        assert _rel(whole, host) < 1e-8
    else:
        np.testing.assert_array_equal(np.asarray(whole), np.asarray(host))
