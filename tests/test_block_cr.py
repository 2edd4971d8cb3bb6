"""Block cyclic-reduction solve/logdet: oracle-verified parity + stability.

Three genuinely distinct code paths are pinned against each other across the
(w, n, dtype, pivot) grid:

  * the Pallas block-CR kernel in interpret mode (``alg="cr"``),
  * the dense block-tridiagonal oracle in ``kernels/ref.py`` (assembles the
    w x w block view densely and hits it with ``jnp.linalg``),
  * the pure-jax ``lax.scan`` banded LU reference (``backend="jax"``).

Structure mirrors ``test_backend_dispatch.py``: seeded numpy inputs, no
hypothesis; the full sweep (every w x n x dtype cross) is slow-marked, a
representative subset stays tier-1 (compile count is the real cost on CPU).

The stability half regresses the new pivoted mode: ill-conditioned KP Gram
bands (near-duplicate inputs, long lengthscales) against the dense Cholesky
oracle in ``repro.core.exact``, and a shifted-spectrum system with a singular
leading principal minor where the no-pivot LU kernel must degrade while
pivoted block CR stays finite.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import exact
from repro.core.banded import Banded, matvec, to_dense
from repro.core.kernel_packets import kp_factors
from repro.kernels import ops, ref
from repro.kernels.block_cr import block_cr_logdet_pallas, block_cr_pallas

WS = [1, 2, 3, 4]
NS = [8, 37, 256, 1000]  # 37 and 1000 are not powers (or multiples) of w
DTYPES = [jnp.float64, jnp.float32]
# tier-1 representatives: every w and every n appears at least once, f32 once;
# the full cross product runs in the slow sweep (compile count bounds tier-1)
FAST = {(1, 8, jnp.float64), (4, 37, jnp.float64), (2, 256, jnp.float64),
        (3, 8, jnp.float32), (3, 37, jnp.float64), (2, 1000, jnp.float64)}
FAST_PIVOT = {(4, 37, jnp.float64), (2, 256, jnp.float64),
              (3, 8, jnp.float32)}


def _sweep_params(fast):
    out = []
    for w in WS:
        for n in NS:
            for dt in DTYPES:
                marks = () if (w, n, dt) in fast else (pytest.mark.slow,)
                out.append(pytest.param(w, n, dt, marks=marks,
                                        id=f"w{w}-n{n}-{dt.__name__}"))
    return out


def _tol(dtype):
    # acceptance bar: <= 1e-5 (f32) / 1e-10 (f64) across the sweep grid
    return 1e-5 if dtype == jnp.float32 else 1e-10


def _band(rng, n, w, dtype, batch=(), boost=6.0):
    """Masked symmetric-bandwidth band with a dominant diagonal."""
    data = rng.standard_normal(batch + (n, 2 * w + 1))
    data[..., :, w] += boost
    i = np.arange(n)[:, None]
    m = np.arange(-w, w + 1)[None, :]
    mask = ((i + m) >= 0) & ((i + m) < n)
    return jnp.asarray(data * mask, dtype)


def _rel(got, want):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


def _check_three_way(w, n, dtype, pivot):
    """block-CR interpret == dense ref oracle == jax scan, batched (D,)."""
    rng = np.random.default_rng(1000 * w + n)
    band = _band(rng, n, w, dtype, (2,))
    rhs = jnp.asarray(rng.standard_normal((2, n, 3)), dtype)
    tol = _tol(dtype)

    got_p = ops.banded_solve(band, rhs, w, w, pivot=pivot, backend="pallas",
                             alg="cr")
    got_j = ops.banded_solve(band, rhs, w, w, pivot=pivot, backend="jax")
    ld_p = ops.banded_logdet(band, w, w, pivot=pivot, backend="pallas",
                             alg="cr")
    ld_j = ops.banded_logdet(band, w, w, pivot=pivot, backend="jax")
    assert got_p.shape == rhs.shape and ld_p.shape == (2,)
    for b in range(2):
        b64 = band[b].astype(jnp.float64)
        want = ref.block_cr_solve_ref(b64, rhs[b].astype(jnp.float64), w)
        want_ld = float(ref.block_cr_logdet_ref(b64, w))
        scale = max(abs(want_ld), 1.0)
        assert _rel(got_p[b], want) < tol, f"cr!=ref batch {b}"
        assert _rel(got_j[b], want) < tol, f"scan!=ref batch {b}"
        assert abs(float(ld_p[b]) - want_ld) / scale < tol, f"cr ld batch {b}"
        assert abs(float(ld_j[b]) - want_ld) / scale < tol, f"scan ld batch {b}"
    # unbatched vector-RHS form through the same dispatch
    v = jnp.asarray(rng.standard_normal(n), dtype)
    got_v = ops.banded_solve(band[0], v, w, w, pivot=pivot, backend="pallas",
                             alg="cr")
    want_v = ref.block_cr_solve_ref(band[0].astype(jnp.float64),
                                    v.astype(jnp.float64)[:, None], w)[:, 0]
    assert got_v.shape == (n,)
    assert _rel(got_v, want_v) < tol, "vec cr!=ref"


@pytest.mark.parametrize("w,n,dtype", _sweep_params(FAST))
def test_block_cr_parity_nopivot(w, n, dtype):
    _check_three_way(w, n, dtype, pivot=False)


@pytest.mark.parametrize("w,n,dtype", _sweep_params(FAST_PIVOT))
def test_block_cr_parity_pivot(w, n, dtype):
    _check_three_way(w, n, dtype, pivot=True)


def test_band_to_blocks_oracle_roundtrip():
    """ref's block view reassembles to exactly the dense band matrix."""
    rng = np.random.default_rng(7)
    n, w = 11, 3  # nb = 4, one mixed real/pad block
    band = _band(rng, n, w, jnp.float64)
    A, B, C = ref.band_to_blocks_ref(band, w)
    nb = B.shape[0]
    dense = np.zeros((nb * w, nb * w))
    for i in range(nb):
        dense[i * w:(i + 1) * w, i * w:(i + 1) * w] = np.asarray(B[i])
        if i > 0:
            dense[i * w:(i + 1) * w, (i - 1) * w:i * w] = np.asarray(A[i])
        if i < nb - 1:
            dense[i * w:(i + 1) * w, (i + 1) * w:(i + 2) * w] = np.asarray(C[i])
    want = np.eye(nb * w)
    want[:n, :n] = np.asarray(to_dense(Banded(band, w, w)))
    np.testing.assert_allclose(dense, want, rtol=0, atol=0)


def test_single_block_and_tiny_n():
    """n <= w (single block, zero CR levels) and n < 2w edge cases."""
    rng = np.random.default_rng(3)
    for n, w in [(3, 4), (1, 1), (5, 3), (2, 2)]:
        band = _band(rng, n, w, jnp.float64)
        rhs = jnp.asarray(rng.standard_normal((n, 2)))
        x, ld = block_cr_pallas(band, rhs, w, pivot=True, interpret=True)
        dense = np.asarray(to_dense(Banded(band, w, w)))
        np.testing.assert_allclose(np.asarray(x),
                                   np.linalg.solve(dense, np.asarray(rhs)),
                                   rtol=0, atol=1e-10)
        assert abs(float(ld) - np.linalg.slogdet(dense)[1]) < 1e-10


def test_grid_batch_matches_per_call():
    """The (D,) grid axis must reproduce D independent single calls."""
    rng = np.random.default_rng(11)
    D, n, w = 4, 33, 2
    band = _band(rng, n, w, jnp.float64, (D,))
    rhs = jnp.asarray(rng.standard_normal((D, n, 2)))
    xb, ldb = block_cr_pallas(band, rhs, w, interpret=True)
    for d in range(D):
        x1, ld1 = block_cr_pallas(band[d], rhs[d], w, interpret=True)
        np.testing.assert_allclose(np.asarray(xb[d]), np.asarray(x1),
                                   rtol=0, atol=0)
        assert float(ldb[d]) == float(ld1)


def test_logdet_only_skips_back_substitution():
    rng = np.random.default_rng(13)
    n, w = 29, 2
    band = _band(rng, n, w, jnp.float64)
    ld = block_cr_logdet_pallas(band, w, interpret=True)
    want = float(ref.block_cr_logdet_ref(band, w))
    assert abs(float(ld) - want) < 1e-10


# ---------------------------------------------------------------------------
# numerical-stability regressions (the pivoted-mode contract)
# ---------------------------------------------------------------------------


def _gram_system(q, omega, xs, sigma):
    """KP view of (K + sigma^2 I): returns (SAPhi, A) with
    (K + s^2 I)^{-1} y = (Phi + s^2 A)^{-1} A y  (since Phi = A K)."""
    from repro.core.banded import add, scale

    A, Phi = kp_factors(q, omega, xs)
    return add(scale(A, sigma**2), Phi), A


@pytest.mark.parametrize("gap,tol", [(1e-3, 1e-6), (1e-5, 1e-4)])
def test_near_duplicate_gram_pivoted_cr_matches_dense_cholesky(gap, tol):
    """Ill-conditioned Gram band (near-duplicate inputs, long lengthscale):
    pivoted block CR must stay finite and track core.exact's dense Cholesky
    with conditioning-bounded error (the KP band's condition number grows
    like 1/gap even though K + s^2 I itself stays moderate)."""
    rng = np.random.default_rng(17)
    q, sigma, omega = 1, 0.1, 0.15  # lengthscale ~ span: K is near-singular
    n = 40
    base = np.sort(rng.random(n // 2) * 8)
    xs = jnp.asarray(np.sort(np.concatenate([base, base + gap])))
    SAPhi, A = _gram_system(q, omega, xs, sigma)
    y = jnp.asarray(rng.standard_normal(n))
    # sparse path, pivoted CR kernel: (K + s^2 I)^{-1} y = SAPhi^{-1} A y
    got = ops.banded_solve(SAPhi.data, matvec(A, y, backend="jax"),
                           SAPhi.lo, SAPhi.hi, pivot=True, backend="pallas",
                           alg="cr")
    # dense oracle: exact.additive_gram + Cholesky (the FGP baseline path)
    K = exact.additive_gram(q, jnp.asarray([omega]), xs[:, None])
    cho = jax.scipy.linalg.cho_factor(K + sigma**2 * jnp.eye(n))
    want = jax.scipy.linalg.cho_solve(cho, y)
    assert np.isfinite(np.asarray(got)).all()
    assert _rel(got, want) < tol
    # pivoted CR logdet of the ill-conditioned band is finite and exact
    ld = ops.banded_logdet(SAPhi.data, SAPhi.lo, SAPhi.hi, pivot=True,
                           backend="pallas", alg="cr")
    want_ld = float(jnp.linalg.slogdet(to_dense(SAPhi))[1])
    assert np.isfinite(float(ld))
    assert abs(float(ld) - want_ld) < 1e-6 * max(abs(want_ld), 1.0)


def test_shifted_minor_nopivot_lu_degrades_pivoted_cr_survives():
    """A spectrum-shifted Gram band whose leading principal minor is singular:
    the no-pivot LU kernel hits a dead pivot and degrades; the pivoted
    block-CR path must stay finite and accurate (the new pivot=True contract).
    """
    rng = np.random.default_rng(19)
    q, sigma, omega = 1, 0.3, 1.1
    n, k = 24, 9
    xs = jnp.asarray(np.sort(rng.random(n) * 6))
    SAPhi, _ = _gram_system(q, omega, xs, sigma)
    dense = np.asarray(to_dense(SAPhi))
    # shift by a (real) eigenvalue of the leading k x k minor -> that minor
    # of the shifted system is exactly singular, so no-pivot elimination hits
    # a dead pivot at step k while the full matrix stays well-conditioned
    # (SAPhi is unsymmetric: use the general eigenvalues, keep the real ones)
    ev = np.linalg.eigvals(dense[:k, :k])
    mu = float(np.min(ev[np.abs(ev.imag) < 1e-12].real))
    band = SAPhi.data.at[:, SAPhi.lo].add(-mu)
    shifted = dense - mu * np.eye(n)
    rhs = jnp.asarray(rng.standard_normal((n, 2)))
    want = np.linalg.solve(shifted, np.asarray(rhs))

    got_cr = ops.banded_solve(band, rhs, SAPhi.lo, SAPhi.hi, pivot=True,
                              backend="pallas", alg="cr")
    assert np.isfinite(np.asarray(got_cr)).all()
    assert _rel(got_cr, want) < 1e-8

    got_lu = ops.banded_solve(band, rhs, SAPhi.lo, SAPhi.hi, pivot=False,
                              backend="pallas", alg="lu")
    err_lu = _rel(got_lu, want)
    assert (not np.isfinite(err_lu)) or err_lu > 1e6 * _rel(got_cr, want)

    # logdet: pivoted CR finite + exact; no-pivot LU blows up on log|0|
    ld_cr = ops.banded_logdet(band, SAPhi.lo, SAPhi.hi, pivot=True,
                              backend="pallas", alg="cr")
    want_ld = float(np.linalg.slogdet(shifted)[1])
    assert np.isfinite(float(ld_cr))
    assert abs(float(ld_cr) - want_ld) < 1e-8 * max(abs(want_ld), 1.0)
    ld_lu = ops.banded_logdet(band, SAPhi.lo, SAPhi.hi, pivot=False,
                              backend="pallas", alg="lu")
    assert not np.isfinite(float(ld_lu)) or \
        abs(float(ld_lu) - want_ld) > 1e3 * abs(float(ld_cr) - want_ld)


def test_solve_alg_selection_rules():
    """set_solve_alg / use_solve_alg / env seeding / validation / resolution."""
    assert ops.resolve_solve_alg("cr", 2, 2) == "cr"
    assert ops.resolve_solve_alg("lu", 2, 2) == "lu"
    assert ops.resolve_solve_alg(None, 2, 2) == "cr"   # auto: symmetric -> cr
    assert ops.resolve_solve_alg(None, 2, 1) == "lu"   # asymmetric -> lu
    assert ops.resolve_solve_alg(None, 0, 0) == "lu"   # diagonal -> lu
    assert ops.resolve_solve_alg("cr", 0, 0) == "lu"
    with pytest.raises(ValueError, match="lo == hi"):
        ops.resolve_solve_alg("cr", 2, 1)  # explicit cr on asymmetric band
    prev = ops.get_solve_alg()
    try:
        ops.set_solve_alg("lu")
        assert ops.resolve_solve_alg(None, 2, 2) == "lu"
        assert ops.resolve_solve_alg("auto", 2, 2) == "lu"
        with ops.use_solve_alg("cr"):
            assert ops.resolve_solve_alg(None, 2, 2) == "cr"
            # process-default cr is prefer-where-applicable, not an error
            assert ops.resolve_solve_alg(None, 2, 1) == "lu"
        assert ops.resolve_solve_alg(None, 2, 2) == "lu"  # context restored
        with pytest.raises(ValueError):
            ops.set_solve_alg("thomas")
        with pytest.raises(ValueError):
            ops.resolve_solve_alg("qr", 2, 2)
    finally:
        ops.set_solve_alg(prev)


def test_fit_captures_process_solve_alg():
    """fit() bakes the process-default solve alg into GPConfig (mirroring the
    backend resolution), so the jit cache keys on it and a later
    set_solve_alg cannot silently reuse a stale trace."""
    from repro.core import GPConfig, fit

    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.random((8, 2)))
    Y = jnp.asarray(rng.random(8))
    om = jnp.ones(2)
    with ops.use_solve_alg("lu"):
        gp = fit(GPConfig(q=0, solver_iters=3, backend="jax"), X, Y, om, 0.5)
    assert gp.config.solve_alg == "lu"
    # an explicit config choice wins over the process default
    with ops.use_solve_alg("lu"):
        gp2 = fit(GPConfig(q=0, solver_iters=3, backend="jax",
                           solve_alg="cr"), X, Y, om, 0.5)
    assert gp2.config.solve_alg == "cr"


def test_gp_fit_through_cr_matches_jax_backend():
    """End-to-end: fit + posterior mean with solve_alg="cr" on the pallas
    backend reproduces the jax-scan backend numbers."""
    from repro.core import GPConfig, fit, posterior_mean

    rng = np.random.default_rng(0)
    n, D = 14, 2
    X = jnp.asarray(rng.random((n, D)) * 5)
    Y = jnp.asarray(np.sin(np.asarray(X)).sum(1))
    omega = jnp.asarray(0.8 + rng.random(D))
    Xq = jnp.asarray(rng.random((4, D)) * 5)
    out = {}
    for backend in ("jax", "pallas"):
        cfg = GPConfig(q=1, solver="pcg", solver_iters=25, backend=backend,
                       solve_alg="cr")
        gp = fit(cfg, X, Y, omega, 0.5)
        out[backend] = np.asarray(posterior_mean(gp, Xq))
    assert np.abs(out["jax"] - out["pallas"]).max() < 1e-7


def test_w1_kp_system_solve():
    """The Matérn-1/2 (sigma^2 A + Phi) tridiagonal solved by block CR at
    w = 1 — the path that retired the dedicated PCR tridiagonal kernel."""
    from repro.core.banded import add, scale

    rng = np.random.default_rng(7)
    n = 256
    xs = jnp.asarray(np.sort(rng.random(n) * 10), jnp.float64)
    A, Phi = kp_factors(0, 1.3, xs)
    S = add(scale(A, 0.09), Phi)  # lo = hi = 1 tridiagonal
    rhs = jnp.asarray(rng.standard_normal((n, 4)), jnp.float64)
    want = np.linalg.solve(np.array(to_dense(S)), np.array(rhs))
    for backend in ("jax", "pallas"):
        got = ops.banded_solve(S.data, rhs, 1, 1, backend=backend, alg="cr"
                               if backend == "pallas" else None)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-7,
                                   atol=1e-7)
