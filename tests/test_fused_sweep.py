"""Fused backfitting-sweep kernel: parity, early exit, warm starts, dispatch.

The fused path (one ``pallas_call`` per iteration, ``kernels/fused_sweep.py``)
is pinned against the unfused dispatch path on BOTH backends for all three
solver methods — the unfused pallas comparison is bit-level at f64 for
jacobi/gauss_seidel (identical op order on identical operands) and
convergence-level for PCG (the host loop's inner products use the
batch-invariant ``_det_dot`` association, the kernel its own in-kernel
order); the jax-scan comparison is convergence-level. The satellite contracts ride along:

  * ``SolveConfig.tol`` early exit (bounded ``lax.while_loop``) and the
    ``solve_mhat(..., return_info=True)`` iteration count;
  * the warm-start property on a streamed splice: a spliced pre-insert
    solution must reconverge in strictly fewer iterations than a cold start;
  * ``resolve_fused`` selection rules (env/process default, "on" validation,
    the VMEM-cap decline);
  * grid-batched matvec / band-matmul / LU dispatch == per-operand calls
    (all four kernels now share the one-``pallas_call`` batch pattern).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backfitting import DimOps, SolveConfig, solve_mhat
from repro.core.banded import add, scale
from repro.core.kernel_packets import kp_factors
from repro.kernels import ops
from repro.kernels.fused_sweep import fused_vmem_bytes


def _make_ops(rng, n, D, q, sigma, dtype=jnp.float64):
    """DimOps straight from KP factors (what _fit_impl assembles)."""
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


# ---------------------------------------------------------------------------
# fused == unfused parity, all three methods x backends x dtypes
# ---------------------------------------------------------------------------

# tier-1 representatives: every method at q=1/f64 plus the f32 acceptance
# bar via pcg; the full cross (incl. the q=0 diagonal-Phi degenerate solve,
# also exercised end-to-end by the q=0 backend-dispatch tests) runs
# slow-marked — tier-1 compile count is the budget.
PARITY_FAST = {("pcg", 1, jnp.float64), ("jacobi", 1, jnp.float64),
               ("gauss_seidel", 1, jnp.float64), ("pcg", 1, jnp.float32)}


def _parity_params():
    out = []
    for method in ("pcg", "jacobi", "gauss_seidel"):
        for q in (0, 1):
            for dt in (jnp.float64, jnp.float32):
                marks = () if (method, q, dt) in PARITY_FAST else (
                    pytest.mark.slow,)
                out.append(pytest.param(method, q, dt, marks=marks,
                                        id=f"{method}-q{q}-{dt.__name__}"))
    return out


@pytest.mark.parametrize("method,q,dtype", _parity_params())
def test_fused_matches_unfused(method, q, dtype):
    """fused == unfused-pallas (bit-level at f64 for the stationary sweeps)
    == jax scan (tolerance)."""
    rng = np.random.default_rng(10 * q + len(method))
    n, D, B = 37, 3, 2
    ops_d = _make_ops(rng, n, D, q, 0.4, dtype)
    v = jnp.asarray(rng.standard_normal((D, n, B)), dtype)
    out = {}
    for label, kw in [("jax", dict(backend="jax")),
                      ("unfused", dict(backend="pallas", fused="off")),
                      ("fused", dict(backend="pallas", fused="on"))]:
        cfg = SolveConfig(method=method, iters=8, **kw)
        out[label] = solve_mhat(ops_d, v, cfg)
    # acceptance bar vs unfused: bit-identical-level f64 / <= 1e-5 rel f32
    # for jacobi/gauss_seidel (same FP ops, same order). PCG is the
    # exception since the batch-invariant host reductions landed: the host
    # loop's inner products use the fixed-association `_det_dot` tree (the
    # fleet bit-parity contract, tests/test_fleet.py) while the fused kernel
    # accumulates in-kernel in its own order, so unconverged PCG iterates
    # amplify the ulp-level association difference — that comparison is
    # convergence-level, like the jax-scan one. The jax-scan comparison is
    # cross-backend: at f32 the *unconverged* iterates of any iterative
    # scheme drift between backends, so that bar is convergence-level only.
    if method == "pcg":
        tol_u = 1e-2 if dtype == jnp.float32 else 1e-9
    else:
        tol_u = 1e-5 if dtype == jnp.float32 else 1e-13
    tol_j = 1e-2 if dtype == jnp.float32 else 1e-9
    assert _rel(out["fused"], out["unfused"]) < tol_u
    assert _rel(out["fused"], out["jax"]) < tol_j


def test_mixed_dtype_rhs_through_fused():
    """A wider RHS than the factor stack (f32 factors, f64 v) promotes the
    whole solve — the fused kernel must run in the promoted dtype, matching
    the unfused path instead of crashing on the rz store."""
    rng = np.random.default_rng(9)
    n, D = 20, 2
    ops32 = _make_ops(rng, n, D, 1, 0.5, jnp.float32)
    v = jnp.asarray(rng.standard_normal((D, n, 1)), jnp.float64)
    cfgf = SolveConfig(method="pcg", iters=3, backend="pallas", fused="on")
    cfgu = SolveConfig(method="pcg", iters=3, backend="pallas", fused="off")
    got = solve_mhat(ops32, v, cfgf)
    want = solve_mhat(ops32, v, cfgu)
    assert got.dtype == want.dtype == jnp.float64
    assert _rel(got, want) < 1e-6  # f32 factors bound the agreement


def test_vector_rhs_form_through_fused():
    """(D, n) vector form routes through the same fused kernels (B = 1)."""
    rng = np.random.default_rng(2)
    n, D = 30, 2
    ops_d = _make_ops(rng, n, D, 1, 0.4)
    v = jnp.asarray(rng.standard_normal((D, n)))
    cfgf = SolveConfig(method="jacobi", iters=5, backend="pallas", fused="on")
    cfgu = SolveConfig(method="jacobi", iters=5, backend="pallas", fused="off")
    gv = solve_mhat(ops_d, v, cfgf)
    assert gv.shape == (D, n)
    assert _rel(gv, solve_mhat(ops_d, v, cfgu)) < 1e-13


def test_fused_pivot_and_warm_start_parity():
    """pivot=True rides the pivoted block solves inside the fused kernels,
    and an x0 warm start enters the fused iteration identically."""
    rng = np.random.default_rng(3)
    n, D, B = 24, 2, 1
    ops_d = _make_ops(rng, n, D, 1, 0.5)
    v = jnp.asarray(rng.standard_normal((D, n, B)))
    x0 = jnp.asarray(0.1 * rng.standard_normal((D, n, B)))
    for method in ("pcg", "gauss_seidel"):
        cfgf = SolveConfig(method=method, iters=5, pivot=True,
                           backend="pallas", fused="on")
        cfgu = SolveConfig(method=method, iters=5, pivot=True,
                           backend="pallas", fused="off")
        got = solve_mhat(ops_d, v, cfgf, x0=x0)
        want = solve_mhat(ops_d, v, cfgu, x0=x0)
        # pcg: convergence-level — host `_det_dot` tree order vs in-kernel
        # accumulation (see test_fused_matches_unfused)
        assert _rel(got, want) < (1e-9 if method == "pcg" else 1e-13), method


# ---------------------------------------------------------------------------
# SolveConfig.tol early exit + SolveInfo
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend,fused", [("jax", "off"),
                                           ("pallas", "on")])
def test_pcg_tol_early_exit(backend, fused):
    """tol > 0 stops PCG early (bounded while_loop) at full accuracy; tol=0
    keeps the fixed-count fori_loop and reports iters == cfg.iters."""
    rng = np.random.default_rng(11)
    n, D = 40, 3
    ops_d = _make_ops(rng, n, D, 1, 0.5)
    v = jnp.asarray(rng.standard_normal((D, n, 2)))
    base = dict(method="pcg", backend=backend, fused=fused)
    x_fix, info_fix = solve_mhat(ops_d, v, SolveConfig(iters=50, **base),
                                 return_info=True)
    assert int(info_fix.iters) == 50
    x_tol, info_tol = solve_mhat(
        ops_d, v, SolveConfig(iters=50, tol=1e-10, **base), return_info=True)
    assert 0 < int(info_tol.iters) < 50
    assert _rel(x_tol, x_fix) < 1e-8
    # a looser tol exits no later
    _, info_loose = solve_mhat(
        ops_d, v, SolveConfig(iters=50, tol=1e-4, **base), return_info=True)
    assert int(info_loose.iters) <= int(info_tol.iters)


def test_pcg_tol_zero_rhs_exits_immediately():
    ops_d = _make_ops(np.random.default_rng(0), 16, 2, 0, 0.5)
    v = jnp.zeros((2, 16, 1))
    x, info = solve_mhat(ops_d, v, SolveConfig(
        method="pcg", iters=20, tol=1e-8, backend="jax"), return_info=True)
    assert int(info.iters) == 0
    assert float(jnp.abs(x).max()) == 0.0


def test_warm_start_cuts_iterations_on_streamed_splice():
    """Sec. 6 / Kernel Multigrid property: the pre-insert solution spliced at
    the streamed point reconverges in strictly fewer PCG iterations than a
    cold start, measured by the tol early exit."""
    rng = np.random.default_rng(7)
    n, D = 60, 3
    sigma = 0.5
    X = rng.random((n + 1, D)) * 4
    Y = np.sin(X).sum(axis=1)

    def make(npts):
        rng_local = np.random.default_rng(1)  # omega shared across sizes
        Xj = jnp.asarray(X[:npts])
        sort_idx = jnp.argsort(Xj.T, axis=1)
        xs = jnp.take_along_axis(Xj.T, sort_idx, axis=1)
        rank_idx = jnp.argsort(sort_idx, axis=1)
        omega = jnp.asarray(0.8 + rng_local.random(D))
        A, Phi = jax.vmap(lambda om, x: kp_factors(1, om, x))(omega, xs)
        SAPhi = add(scale(A, sigma**2), Phi)
        return DimOps(A=A, Phi=Phi, SAPhi=SAPhi, sort_idx=sort_idx,
                      rank_idx=rank_idx, sigma2=jnp.asarray(sigma**2))

    ops_n = make(n)
    v_n = jnp.broadcast_to(jnp.asarray(Y[:n])[None], (D, n))
    u_n = solve_mhat(ops_n, v_n, SolveConfig(method="pcg", iters=80,
                                             backend="jax"))

    ops_n1 = make(n + 1)
    v_n1 = jnp.broadcast_to(jnp.asarray(Y)[None], (D, n + 1))
    # splice: the new point (original index n) inherits its sorted left
    # neighbour's value per dim — exactly what streaming.insert does
    p = ops_n1.rank_idx[:, n]
    us = ops_n.to_sorted(u_n)
    est = jnp.take_along_axis(us, jnp.clip(p - 1, 0, n - 1)[:, None], axis=1)
    x0 = jnp.concatenate([u_n, est], axis=1)

    cfg = SolveConfig(method="pcg", iters=80, tol=1e-8, backend="jax")
    x_cold, info_cold = solve_mhat(ops_n1, v_n1, cfg, return_info=True)
    x_warm, info_warm = solve_mhat(ops_n1, v_n1, cfg, x0=x0,
                                   return_info=True)
    assert int(info_warm.iters) < int(info_cold.iters)
    assert _rel(x_warm, x_cold) < 1e-6


# ---------------------------------------------------------------------------
# fused-mode resolution rules
# ---------------------------------------------------------------------------


def test_resolve_fused_rules():
    sym = ((2, 2), (1, 1), (2, 2))
    asym = ((2, 1), (1, 1))
    small = dict(n=64, D=3, B=2, itemsize=8)
    assert ops.resolve_fused("on", "pallas", widths=sym) == "iter"
    assert ops.resolve_fused("whole", "pallas", widths=sym) == "whole"
    assert ops.resolve_fused("off", "pallas", widths=sym, **small) == "off"
    # auto prefers the whole-solve kernel when everything fits VMEM
    assert ops.resolve_fused(None, "pallas", widths=sym, **small) == "whole"
    # auto never fuses off the pallas backend or on asymmetric bands
    assert ops.resolve_fused(None, "jax", widths=sym, **small) == "off"
    assert ops.resolve_fused("auto", "pallas", widths=asym, **small) == "off"
    # auto steps down as the state stack outgrows VMEM: whole-solve (extra
    # iteration scratch) declines first, then the per-iteration kernel;
    # "on"/"whole" trust you
    mid = dict(n=18_000, D=3, B=2, itemsize=8)
    big = dict(n=4_000_000, D=8, B=16, itemsize=8)
    assert ops.resolve_fused(None, "pallas", widths=sym, **mid) == "iter"
    assert ops.resolve_fused(None, "pallas", widths=sym, **big) == "off"
    assert ops.resolve_fused("on", "pallas", widths=sym, **big) == "iter"
    assert ops.resolve_fused("whole", "pallas", widths=sym, **big) == "whole"
    # the kmg V-cycle is a host-level loop neither fused pcg kernel can
    # apply: auto runs unfused, an explicit "on"/"whole" is contradictory
    assert ops.resolve_fused(None, "pallas", widths=sym, precond="kmg",
                             **small) == "off"
    with pytest.raises(ValueError, match="kmg"):
        ops.resolve_fused("whole", "pallas", widths=sym, precond="kmg")
    with pytest.raises(ValueError, match="kmg"):
        ops.resolve_fused("on", "pallas", widths=sym, precond="kmg")
    # "on"/"whole" validate what they cannot do
    with pytest.raises(ValueError, match="pallas"):
        ops.resolve_fused("on", "jax", widths=sym)
    with pytest.raises(ValueError, match="pallas"):
        ops.resolve_fused("whole", "jax", widths=sym)
    with pytest.raises(ValueError, match="lo == hi"):
        ops.resolve_fused("on", "pallas", widths=asym)
    with pytest.raises(ValueError, match="lo == hi"):
        ops.resolve_fused("whole", "pallas", widths=asym)
    with pytest.raises(ValueError, match="unknown fused"):
        ops.resolve_fused("always", "pallas", widths=sym)
    # the fused kernels only solve via block CR: a solve-alg override that
    # forbids CR declines auto-fusion and invalidates "on"/"whole"
    assert ops.resolve_fused(None, "pallas", widths=sym, cr_ok=False,
                             **small) == "off"
    with pytest.raises(ValueError, match="block cyclic reduction"):
        ops.resolve_fused("on", "pallas", widths=sym, cr_ok=False)
    with pytest.raises(ValueError, match="block cyclic reduction"):
        ops.resolve_fused("whole", "pallas", widths=sym, cr_ok=False)
    # process default + context manager, mirroring backend/solve_alg
    prev = ops.get_fused()
    try:
        ops.set_fused("off")
        assert ops.resolve_fused(None, "pallas", widths=sym, **small) == "off"
        assert ops.resolve_fused("auto", "pallas", widths=sym,
                                 **small) == "off"
        with ops.use_fused("on"):
            assert ops.resolve_fused(None, "pallas", widths=sym) == "iter"
        with ops.use_fused("whole"):
            assert ops.resolve_fused(None, "pallas", widths=sym) == "whole"
        assert ops.get_fused() == "off"
        with pytest.raises(ValueError):
            ops.set_fused("sometimes")
    finally:
        ops.set_fused(prev)


def test_alg_lu_override_keeps_unfused_path():
    """SolveConfig(alg='lu') must win over auto-fusion: the fused kernel has
    no LU solve, so the solve stays on the unfused dispatch path (and
    fused='on' + alg='lu' is rejected as contradictory)."""
    rng = np.random.default_rng(4)
    ops_d = _make_ops(rng, 20, 2, 1, 0.5)
    v = jnp.asarray(rng.standard_normal((2, 20, 1)))
    cfg = SolveConfig(method="pcg", iters=6, backend="pallas", alg="lu")
    got = solve_mhat(ops_d, v, cfg)  # fused="auto" declines -> LU kernel
    want = solve_mhat(ops_d, v, dataclasses.replace(cfg, fused="off"))
    assert _rel(got, want) == 0.0
    with pytest.raises(ValueError, match="block cyclic reduction"):
        solve_mhat(ops_d, v, dataclasses.replace(cfg, fused="on"))


def test_fused_vmem_estimate_scales():
    w = [2, 1, 2]
    small = fused_vmem_bytes(1000, 4, 1, w, 8)
    big = fused_vmem_bytes(16000, 4, 1, w, 8)
    assert small < big and big < 17 * small  # ~linear in n
    assert fused_vmem_bytes(1000, 4, 1, w, 8, method="jacobi") < small


def test_fit_bakes_fused_mode():
    """fit() captures the REPRO_FUSED/set_fused process default into the
    config (like backend/solve_alg), so the jit cache keys on it."""
    from repro.core import GPConfig, fit

    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.random((8, 2)))
    Y = jnp.asarray(rng.random(8))
    om = jnp.ones(2)
    with ops.use_fused("off"):
        gp = fit(GPConfig(q=0, solver_iters=3, backend="jax"), X, Y, om, 0.5)
    assert gp.config.fused == "off"
    with ops.use_fused("off"):
        gp2 = fit(GPConfig(q=0, solver_iters=3, backend="jax",
                           fused="auto"), X, Y, om, 0.5)
    assert gp2.config.fused == "off"


# ---------------------------------------------------------------------------
# end-to-end threading: fit / posterior / streaming insert
# ---------------------------------------------------------------------------


def test_gp_fit_fused_matches_unfused():
    """fit + posterior mean/var identical numbers with the fused sweep on."""
    from repro.core import GPConfig, fit, posterior_mean, posterior_var

    rng = np.random.default_rng(0)
    n, D = 18, 2
    X = jnp.asarray(rng.random((n, D)) * 5)
    Y = jnp.asarray(np.sin(np.asarray(X)).sum(1))
    omega = jnp.asarray(0.8 + rng.random(D))
    Xq = jnp.asarray(rng.random((4, D)) * 5)
    out = {}
    for fused in ("on", "off"):
        cfg = GPConfig(q=1, solver="pcg", solver_iters=25, backend="pallas",
                       fused=fused)
        gp = fit(cfg, X, Y, omega, 0.5)
        out[fused] = (np.asarray(posterior_mean(gp, Xq)),
                      np.asarray(posterior_var(gp, Xq)))
    assert np.abs(out["on"][0] - out["off"][0]).max() < 1e-10
    assert np.abs(out["on"][1] - out["off"][1]).max() < 1e-10


@pytest.mark.slow
def test_streaming_insert_fused_matches_unfused():
    """One streamed insert through the fused path == unfused path."""
    from repro.core import GPConfig, fit, posterior_mean
    from repro.streaming import insert

    rng = np.random.default_rng(5)
    n, D = 14, 2
    X = rng.random((n, D)) * 4
    Y = np.sin(X).sum(axis=1)
    Xq = jnp.asarray(rng.random((4, D)) * 4)
    omega = jnp.asarray(0.9 + rng.random(D))
    out = {}
    for fused in ("on", "off"):
        cfg = GPConfig(q=1, solver="pcg", solver_iters=30, backend="pallas",
                       fused=fused)
        gp = fit(cfg, jnp.asarray(X), jnp.asarray(Y), omega, 0.4)
        gp1 = insert(gp, X[0] + 0.31, float(Y[0]))
        out[fused] = np.asarray(posterior_mean(gp1, Xq))
    assert np.abs(out["on"] - out["off"]).max() < 1e-8


# ---------------------------------------------------------------------------
# grid-batched dispatch: the remaining kernels match per-operand calls
# ---------------------------------------------------------------------------


def test_grid_batched_kernels_match_single_calls():
    """matvec / band-matmul / LU batched through one pallas_call reproduce
    the per-operand results exactly (the block-CR grid pattern, PR 3)."""
    from repro.kernels.band_matmul import band_matmul_pallas
    from repro.kernels.banded_lu import banded_lu_pallas
    from repro.kernels.banded_matvec import banded_matvec_pallas

    rng = np.random.default_rng(21)
    G, n, lo, hi = 3, 33, 2, 1
    w = lo + hi + 1
    i = np.arange(n)[:, None]
    m = np.arange(-lo, hi + 1)[None, :]
    mask = ((i + m) >= 0) & ((i + m) < n)
    band = jnp.asarray(
        (rng.standard_normal((G, n, w)) + 5.0 * (m == 0)) * mask)
    x = jnp.asarray(rng.standard_normal((G, n, 2)))

    ymv = banded_matvec_pallas(band, x, lo, hi, block=16, interpret=True)
    ymm = band_matmul_pallas(band, band, lo, hi, lo, hi, block=16,
                             interpret=True)
    ylu, ld = banded_lu_pallas(band, x, lo, hi, interpret=True)
    assert ylu.shape == x.shape and ld.shape == (G,)
    for g in range(G):
        np.testing.assert_array_equal(
            np.asarray(ymv[g]),
            np.asarray(banded_matvec_pallas(band[g], x[g], lo, hi, block=16,
                                            interpret=True)))
        np.testing.assert_array_equal(
            np.asarray(ymm[g]),
            np.asarray(band_matmul_pallas(band[g], band[g], lo, hi, lo, hi,
                                          block=16, interpret=True)))
        x1, ld1 = banded_lu_pallas(band[g], x[g], lo, hi, interpret=True)
        np.testing.assert_array_equal(np.asarray(ylu[g]), np.asarray(x1))
        assert float(ld[g]) == float(ld1)
