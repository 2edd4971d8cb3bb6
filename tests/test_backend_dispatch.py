"""Backend dispatch parity: pallas(interpret) == ref == jax scan.

The archetype centerpiece: every op served by ``repro.kernels.ops`` is
checked across bandwidths, dtypes, batch shapes and RHS forms.

Structure (keeps tier-1 fast — compile count is the real cost on CPU):
  * per-op sweeps compare the pallas kernel against the dense ``ref.py``
    oracle (cheap compiles) over widths x dtypes x batch shapes;
  * one three-way test per op additionally pins ``jax scan == ref`` at a
    representative width (the scan paths get their own dense-oracle sweeps
    in ``test_banded.py``);
  * the widest/exotic bandwidths run in the slow-marked full sweep
    (``-m "slow or not slow"`` / ``scripts/check.sh --slow``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import banded as bd
from repro.kernels import ops, ref

WIDTHS_FAST = [(0, 0), (1, 1), (2, 1), (1, 2), (3, 3)]
WIDTHS_FULL = [(0, 2), (2, 0), (4, 2), (2, 4)]
DTYPES = [jnp.float32, jnp.float64]
F32_FAST = {(1, 1), (3, 3)}  # f32 widths kept in tier-1 (rest slow-marked)


def _sweep_params():
    out = []
    for lo, hi in WIDTHS_FAST:
        out.append(pytest.param(jnp.float64, lo, hi,
                                 marks=() if (lo, hi) != (0, 0)
                                 else (pytest.mark.slow,)))
        out.append(pytest.param(
            jnp.float32, lo, hi,
            marks=() if (lo, hi) in F32_FAST else (pytest.mark.slow,)))
    return out


def _tol(dtype):
    return 2e-4 if dtype == jnp.float32 else 1e-9


def _rand_band(rng, n, lo, hi, dtype, batch=(), boost=4.0):
    """Masked band data with a boosted diagonal (stable no-pivot LU)."""
    data = rng.standard_normal(batch + (n, lo + hi + 1))
    data[..., :, lo] += boost
    i = np.arange(n)[:, None]
    m = np.arange(-lo, hi + 1)[None, :]
    mask = ((i + m) >= 0) & ((i + m) < n)
    return jnp.asarray(data * mask, dtype)


def _assert_close(got, want, dtype, label):
    np.testing.assert_allclose(
        np.asarray(got, np.float64), np.asarray(want, np.float64),
        rtol=_tol(dtype), atol=_tol(dtype), err_msg=label)


def _check_matvec(lo, hi, dtype, n=40):
    rng = np.random.default_rng(lo * 10 + hi)
    band = _rand_band(rng, n, lo, hi, dtype, (2,))
    x = jnp.asarray(rng.standard_normal((2, n, 2)), dtype)
    got = ops.banded_matvec(band, x, lo, hi, block=32, backend="pallas")
    for b in range(2):
        want = ref.banded_matvec_ref(band[b], x[b], lo, hi)
        _assert_close(got[b], want, dtype, f"pallas!=ref batch {b}")
    # vector-RHS form, unbatched
    v = jnp.asarray(rng.standard_normal(n), dtype)
    got_v = ops.banded_matvec(band[0], v, lo, hi, block=32, backend="pallas")
    _assert_close(got_v, ref.banded_matvec_ref(band[0], v, lo, hi), dtype,
                  "vec pallas!=ref")


def _check_solve(lo, hi, dtype, n=40):
    rng = np.random.default_rng(100 + lo * 10 + hi)
    band = _rand_band(rng, n, lo, hi, dtype, (2,))
    rhs = jnp.asarray(rng.standard_normal((2, n, 2)), dtype)
    got = ops.banded_solve(band, rhs, lo, hi, pivot=False, backend="pallas")
    for b in range(2):
        want = ref.banded_solve_ref(band[b], rhs[b], lo, hi)
        _assert_close(got[b], want, dtype, f"pallas!=ref batch {b}")
    v = jnp.asarray(rng.standard_normal(n), dtype)
    got_v = ops.banded_solve(band[0], v, lo, hi, pivot=False, backend="pallas")
    _assert_close(got_v, ref.banded_solve_ref(band[0], v, lo, hi), dtype,
                  "vec pallas!=ref")


def _check_logdet(lo, hi, dtype, n=40):
    rng = np.random.default_rng(200 + lo * 10 + hi)
    band = _rand_band(rng, n, lo, hi, dtype, (3,))
    got = ops.banded_logdet(band, lo, hi, backend="pallas")
    assert got.shape == (3,)
    for b in range(3):
        want = ref.banded_logdet_ref(band[b], lo, hi)
        _assert_close(got[b], want, dtype, f"pallas!=ref batch {b}")


def _check_band_matmul(wa, wb, dtype, n=40):
    (a_lo, a_hi), (b_lo, b_hi) = wa, wb
    rng = np.random.default_rng(300 + a_lo + 7 * b_hi)
    a = _rand_band(rng, n, a_lo, a_hi, dtype, (2,))
    b = _rand_band(rng, n, b_lo, b_hi, dtype, (2,))
    got = ops.band_band_matmul(a, b, a_lo, a_hi, b_lo, b_hi, block=32,
                               backend="pallas")
    for i in range(2):
        want = ref.band_matmul_ref(a[i], b[i], a_lo, a_hi, b_lo, b_hi)
        _assert_close(got[i], want, dtype, f"pallas!=ref batch {i}")


@pytest.mark.parametrize("dtype,lo,hi", _sweep_params())
def test_matvec_parity(lo, hi, dtype):
    _check_matvec(lo, hi, dtype)


@pytest.mark.parametrize("dtype,lo,hi", _sweep_params())
def test_solve_parity(lo, hi, dtype):
    _check_solve(lo, hi, dtype)


@pytest.mark.parametrize("lo,hi", WIDTHS_FAST)
def test_logdet_parity(lo, hi):
    _check_logdet(lo, hi, jnp.float64)


@pytest.mark.parametrize("wa,wb", [((1, 1), (1, 1)), ((2, 1), (1, 2))])
def test_band_matmul_parity(wa, wb):
    _check_band_matmul(wa, wb, jnp.float64)


@pytest.mark.slow
@pytest.mark.parametrize("lo,hi", WIDTHS_FULL)
@pytest.mark.parametrize("dtype", DTYPES)
def test_full_width_sweep(lo, hi, dtype):
    """Exotic / wide bandwidths across every op (opt-in full sweep)."""
    _check_matvec(lo, hi, dtype, n=64)
    _check_solve(lo, hi, dtype, n=64)
    _check_logdet(lo, hi, dtype, n=64)
    _check_band_matmul((lo, hi), (hi, lo), dtype, n=64)


@pytest.mark.parametrize("op", ["matvec", "solve", "logdet", "band_matmul"])
def test_three_way_parity(op):
    """pallas == jax scan == dense ref at a representative width."""
    lo, hi, n = 2, 1, 40
    dtype = jnp.float64
    rng = np.random.default_rng(7)
    band = _rand_band(rng, n, lo, hi, dtype)
    rhs = jnp.asarray(rng.standard_normal((n, 2)), dtype)
    if op == "matvec":
        j = ops.banded_matvec(band, rhs, lo, hi, backend="jax")
        p = ops.banded_matvec(band, rhs, lo, hi, block=32, backend="pallas")
        r = ref.banded_matvec_ref(band, rhs, lo, hi)
    elif op == "solve":
        j = ops.banded_solve(band, rhs, lo, hi, pivot=False, backend="jax")
        p = ops.banded_solve(band, rhs, lo, hi, pivot=False, backend="pallas")
        r = ref.banded_solve_ref(band, rhs, lo, hi)
    elif op == "logdet":
        j = ops.banded_logdet(band, lo, hi, backend="jax")
        p = ops.banded_logdet(band, lo, hi, backend="pallas")
        r = ref.banded_logdet_ref(band, lo, hi)
    else:
        j = ops.band_band_matmul(band, band, lo, hi, lo, hi, backend="jax")
        p = ops.band_band_matmul(band, band, lo, hi, lo, hi, block=32,
                                 backend="pallas")
        r = ref.band_matmul_ref(band, band, lo, hi, lo, hi)
    _assert_close(j, r, dtype, f"{op}: jax!=ref")
    _assert_close(p, r, dtype, f"{op}: pallas!=ref")


@pytest.mark.parametrize("q", [0, pytest.param(1, marks=pytest.mark.slow)])
def test_kp_gram_parity(q):
    from repro.core.kernel_packets import kp_factors

    rng = np.random.default_rng(q)
    n = 100
    xs = jnp.asarray(np.sort(rng.random(n) * 8), jnp.float32)
    A, _ = kp_factors(q, 1.1, xs)
    a32 = A.data.astype(jnp.float32)
    got_j = ops.kp_gram(q, 1.1, xs, a32, backend="jax")
    got_p = ops.kp_gram(q, 1.1, xs, a32, block=64, backend="pallas")
    np.testing.assert_allclose(np.asarray(got_p, np.float64),
                               np.asarray(got_j, np.float64),
                               rtol=2e-4, atol=2e-4)


def test_pivot_routes_to_pallas_block_cr(monkeypatch):
    """pivot=True on a symmetric band now runs ON the pallas backend (the
    pivoted block-CR kernel) — the old always-fall-back-to-scan rule is gone.

    The jax scans are monkeypatched to raise, so any silent fallback fails
    loudly; correctness is pinned against the dense ref oracle on a band with
    a dead diagonal entry (where no-pivot elimination would blow up).
    """
    rng = np.random.default_rng(5)
    n, lo, hi = 30, 2, 2
    band = _rand_band(rng, n, lo, hi, jnp.float64, boost=0.0)
    band = band.at[5, lo].set(0.0)  # dead diagonal -> no-pivot LU blows up
    rhs = jnp.asarray(rng.standard_normal((n, 2)))
    want = ref.banded_solve_ref(band, rhs, lo, hi)
    want_ld = ref.banded_logdet_ref(band, lo, hi)

    def boom(*a, **k):
        raise AssertionError("pivot=True fell back to the jax scan")

    monkeypatch.setattr(bd, "_solve_scan", boom)
    monkeypatch.setattr(bd, "_logdet_scan", boom)
    got = ops.banded_solve(band, rhs, lo, hi, pivot=True, backend="pallas")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-8, atol=1e-8)
    ld = ops.banded_logdet(band, lo, hi, pivot=True, backend="pallas")
    assert np.isfinite(float(ld))
    np.testing.assert_allclose(float(ld), float(want_ld), rtol=1e-8)
    # asymmetric bandwidth has no CR view: pivot=True still needs the scan
    with pytest.raises(AssertionError, match="fell back"):
        ops.banded_solve(band[:, :4], rhs, 2, 1, pivot=True,
                         backend="pallas")
    monkeypatch.undo()
    got_asym = ops.banded_solve(band[:, :4], rhs, 2, 1, pivot=True,
                                backend="pallas")
    np.testing.assert_allclose(
        np.asarray(got_asym),
        np.asarray(ref.banded_solve_ref(band[:, :4], rhs, 2, 1)),
        rtol=1e-8, atol=1e-8)


def test_backend_selection_rules():
    """set_backend / use_backend / env override / validation."""
    assert ops.resolve_backend("jax") == "jax"
    assert ops.resolve_backend("pallas") == "pallas"
    # auto resolves by platform (pallas only on a TPU where it all lowers)
    expected_auto = ("pallas" if ops.on_tpu() and not ops.TPU_UNLOWERED
                     else "jax")
    assert ops.resolve_backend("auto") == expected_auto
    prev = ops.get_backend()
    try:
        ops.set_backend("pallas")
        assert ops.resolve_backend() == "pallas"
        # config-level "auto" (the GPConfig/SolveConfig default) defers to
        # the process default — REPRO_BACKEND/set_backend must reach the core
        assert ops.resolve_backend("auto") == "pallas"
        with ops.use_backend("jax"):
            assert ops.resolve_backend() == "jax"
            assert ops.resolve_backend("auto") == "jax"
        assert ops.resolve_backend() == "pallas"  # context restored
        with pytest.raises(ValueError):
            ops.set_backend("tpu-go-brrr")
        with pytest.raises(ValueError):
            ops.resolve_backend("nope")
    finally:
        ops.set_backend(prev)


@pytest.mark.parametrize("tpu", [False, True], ids=["cpu", "tpu"])
def test_backend_rule_64bit_resolves_to_jax(monkeypatch, tpu):
    """"auto": 64-bit operands -> jax on every platform (Mosaic has no
    64-bit types); 32-bit -> pallas exactly on a TPU where every pallas
    kernel lowers, so jax while TPU_UNLOWERED names any. fit() bakes the
    rule from the data's dtype."""
    from repro.core import GPConfig, fit

    monkeypatch.setattr(ops, "on_tpu", lambda: tpu)
    assert ops.TPU_UNLOWERED
    assert ops.resolve_backend("auto", jnp.float64) == "jax"
    assert ops.resolve_backend("auto", jnp.float32) == "jax"
    monkeypatch.setattr(ops, "TPU_UNLOWERED", frozenset())
    assert ops.resolve_backend("auto", jnp.float64) == "jax"
    assert ops.resolve_backend("auto", jnp.float32) == (
        "pallas" if tpu else "jax")
    assert ops.resolve_backend("auto") == ("pallas" if tpu else "jax")
    rng = np.random.default_rng(3)
    X = jnp.asarray(rng.random((12, 2)))
    gp = fit(GPConfig(q=0, solver_iters=5), X, jnp.asarray(rng.random(12)),
             jnp.ones(2), 0.5)
    assert gp.config.backend == "jax"


def test_explicit_pallas_64bit_on_tpu_raises(monkeypatch):
    """An explicit pallas request with 64-bit operands on a TPU is an error,
    never a silent interpreter or backend fallback; off-TPU it stays legal
    (the interpreter runs f64, which the parity suites rely on)."""
    from repro.core import GPConfig, fit

    assert ops.resolve_backend("pallas", jnp.float64) == "pallas"
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    with pytest.raises(ValueError, match="64-bit"):
        ops.resolve_backend("pallas", jnp.float64)
    assert ops.resolve_backend("pallas", jnp.float32) == "pallas"
    rng = np.random.default_rng(4)
    with pytest.raises(ValueError, match="64-bit"):
        fit(GPConfig(q=0, solver_iters=5, backend="pallas"),
            jnp.asarray(rng.random((12, 2))), jnp.asarray(rng.random(12)),
            jnp.ones(2), 0.5)
    with pytest.raises(ValueError, match="64-bit"):
        ops.banded_matvec(jnp.ones((8, 3)), jnp.ones(8), 1, 1,
                          backend="pallas")


def test_no_kernel_wrapper_defaults_to_interpret(monkeypatch):
    """Every Pallas wrapper takes ``interpret`` with no default; the one
    helper that decides it never returns True on a TPU."""
    import inspect

    from repro.kernels import (band_matmul, banded_lu, banded_matvec,
                               block_cr, fused_sweep, kp_gram, mega_solve,
                               rgf)

    wrappers = [
        band_matmul.band_matmul_pallas, banded_lu.banded_lu_pallas,
        banded_lu.banded_solve_pallas, banded_lu.banded_logdet_pallas,
        banded_matvec.banded_matvec_pallas, block_cr.block_cr_pallas,
        block_cr.block_cr_solve_pallas, block_cr.block_cr_logdet_pallas,
        kp_gram.kp_gram_pallas, rgf.rgf_blocks_pallas, rgf.rgf_inverse_band,
        fused_sweep.fused_jacobi_iter_pallas,
        fused_sweep.fused_gauss_seidel_iter_pallas,
        fused_sweep.fused_pcg_iter_pallas, fused_sweep.FusedSweep,
        mega_solve.mega_jacobi_solve_pallas,
        mega_solve.mega_gauss_seidel_solve_pallas,
        mega_solve.mega_pcg_solve_pallas,
    ]
    for fn in wrappers:
        param = inspect.signature(fn).parameters["interpret"]
        assert param.default is inspect.Parameter.empty, fn
    assert ops.interpret_kernels() is True  # this suite runs off-TPU
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert ops.interpret_kernels() is False


def test_unlowered_kernels_are_excluded_on_tpu(monkeypatch):
    """On a TPU nothing routes to a TPU_UNLOWERED kernel: "auto" resolves
    f32 to jax and "auto" fusion steps down to off, while an explicit fused
    mode or an explicit pallas op that needs one raises rather than timing
    a jax scan under the pallas name. Kernels that lower still run."""
    from repro.core.band_inverse import inverse_band
    from repro.core.banded import Banded

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    assert ops.resolve_backend("auto", jnp.float32) == "jax"
    widths = ((1, 1), (0, 0), (1, 1))
    assert not ops.kernel_lowers("mega_solve")
    assert ops.kernel_lowers("banded_matvec")
    assert ops.resolve_fused("auto", "pallas", widths=widths, n=64, D=2,
                             itemsize=4, precond="none") == "off"
    for mode in ("whole", "on"):
        with pytest.raises(ValueError, match="TPU_UNLOWERED"):
            ops.resolve_fused(mode, "pallas", widths=widths, n=64, D=2,
                              itemsize=4, precond="none")
    rng = np.random.default_rng(5)
    band = _rand_band(rng, 16, 1, 1, jnp.float32)
    rhs = jnp.asarray(rng.standard_normal((16, 2)), jnp.float32)
    for alg in ("cr", "lu"):
        with pytest.raises(ValueError, match="TPU_UNLOWERED"):
            ops.banded_solve(band, rhs, 1, 1, backend="pallas", alg=alg)
        with pytest.raises(ValueError, match="TPU_UNLOWERED"):
            ops.banded_logdet(band, 1, 1, backend="pallas", alg=alg)
    with pytest.raises(ValueError, match="TPU_UNLOWERED"):
        inverse_band(Banded(band, 1, 1), 1, backend="pallas")
    jaxpr = str(jax.make_jaxpr(lambda b, r: ops.banded_matvec(
        b, r, 1, 1, backend="pallas"))(band, rhs))
    assert "pallas_call" in jaxpr


def test_invalid_env_default_raises_on_auto(monkeypatch):
    """A typo'd REPRO_BACKEND must raise, not silently pick a backend, even
    through the config-level "auto" deferral path."""
    monkeypatch.setattr(ops, "_backend", "jaxx")  # as seeded by a bad env var
    with pytest.raises(ValueError, match="jaxx"):
        ops.resolve_backend("auto")
    with pytest.raises(ValueError, match="jaxx"):
        ops.resolve_backend()


def test_env_override_is_read_at_import(monkeypatch):
    """REPRO_BACKEND seeds the module default (checked via a fresh reload)."""
    import importlib
    import os

    monkeypatch.setenv(ops.ENV_VAR, "pallas")
    try:
        mod = importlib.reload(ops)
        assert mod.get_backend() == "pallas"
    finally:
        # restore the real environment *before* the re-seeding reload, so a
        # developer-set REPRO_BACKEND survives for the rest of the session
        monkeypatch.undo()
        mod = importlib.reload(ops)
        assert mod.get_backend() == os.environ.get(mod.ENV_VAR, "auto")


def test_core_banded_dispatch_equivalence():
    """core.banded public API with backend= matches both underlying paths."""
    rng = np.random.default_rng(8)
    n, lo, hi = 36, 2, 1
    band = _rand_band(rng, n, lo, hi, jnp.float64)
    b = bd.Banded(band, lo, hi)
    rhs = jnp.asarray(rng.standard_normal((n, 3)))
    dense = np.asarray(bd.to_dense(b))
    for backend in ("jax", "pallas"):
        assert np.allclose(np.asarray(bd.matvec(b, rhs, backend=backend)),
                           dense @ np.asarray(rhs))
        assert np.allclose(
            np.asarray(bd.solve(b, rhs, pivot=False, backend=backend)),
            np.linalg.solve(dense, np.asarray(rhs)), atol=1e-8)
        assert abs(float(bd.logdet(b, backend=backend))
                   - np.linalg.slogdet(dense)[1]) < 1e-8


@pytest.mark.slow
def test_fit_resolves_backend_into_config():
    """fit() bakes the resolved backend into the GP, so the jit cache keys on
    it and a later set_backend cannot silently reuse a stale trace."""
    from repro.core import GPConfig, fit

    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.random((12, 2)))
    Y = jnp.asarray(rng.random(12))
    om = jnp.ones(2)
    with ops.use_backend("pallas"):
        gp = fit(GPConfig(q=0, solver_iters=5), X, Y, om, 0.5)
    assert gp.config.backend == "pallas"
    gp2 = fit(GPConfig(q=0, solver_iters=5), X, Y, om, 0.5)
    assert gp2.config.backend == "jax"  # float64 data: XLA on every platform


def test_gp_end_to_end_backend_parity():
    """fit + posterior mean produce identical numbers through both backends.

    (Variance and MLL parity are covered per-op by the sweeps above and
    end-to-end by the slow-marked variant below.)"""
    from repro.core import GPConfig, fit, posterior_mean

    rng = np.random.default_rng(0)
    n, D = 20, 2
    X = jnp.asarray(rng.random((n, D)) * 5)
    Y = jnp.asarray(np.sin(np.asarray(X)).sum(1) + 0.1 * rng.standard_normal(n))
    omega = jnp.asarray(0.7 + rng.random(D))
    Xq = jnp.asarray(rng.random((4, D)) * 5)
    out = {}
    for backend in ("jax", "pallas"):
        cfg = GPConfig(q=0, solver="pcg", solver_iters=30, logdet_probes=2,
                       logdet_order=10, power_iters=5, backend=backend)
        gp = fit(cfg, X, Y, omega, 0.3)
        out[backend] = np.asarray(posterior_mean(gp, Xq))
    assert np.abs(out["jax"] - out["pallas"]).max() < 1e-7


@pytest.mark.slow
def test_gp_mll_backend_parity():
    """log-likelihood, MLL gradients and posterior variance match across
    backends end to end."""
    from repro.core import GPConfig, fit, log_likelihood, mll_gradients, \
        posterior_var

    rng = np.random.default_rng(0)
    n, D = 24, 2
    X = jnp.asarray(rng.random((n, D)) * 5)
    Y = jnp.asarray(np.sin(np.asarray(X)).sum(1) + 0.1 * rng.standard_normal(n))
    omega = jnp.asarray(0.7 + rng.random(D))
    out = {}
    for backend in ("jax", "pallas"):
        cfg = GPConfig(q=0, solver="pcg", solver_iters=40, logdet_probes=4,
                       logdet_order=20, trace_probes=8, backend=backend)
        gp = fit(cfg, X, Y, omega, 0.3)
        g_om, g_sg = mll_gradients(gp, jax.random.PRNGKey(1))
        out[backend] = (float(log_likelihood(gp, jax.random.PRNGKey(0))),
                        np.asarray(g_om), float(g_sg),
                        np.asarray(posterior_var(gp, X[:4])))
    assert abs(out["jax"][0] - out["pallas"][0]) < 1e-6
    assert np.abs(out["jax"][1] - out["pallas"][1]).max() < 1e-6
    assert abs(out["jax"][2] - out["pallas"][2]) < 1e-6
    assert np.abs(out["jax"][3] - out["pallas"][3]).max() < 1e-7


# --- jax backend: block cyclic reduction above the crossover --------------
#
# ``ops.banded_solve`` on the jax backend runs ``cr_jax.block_cr_solve_jax``
# for unpivoted lo == hi >= 1 bands of at least ``ops.CR_MIN_BLOCK_ROWS``
# block rows and the sequential scan LU for everything else. Kept small in
# D and B: each CR instance is a compile of its own on the CPU.


def _loops(jaxpr):
    """(primitive, trip count) of every loop in a jaxpr, nested ones too."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            out.append(("scan", eqn.params["length"]))
        elif eqn.primitive.name == "while":
            out.append(("while", None))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                if hasattr(sub, "jaxpr"):
                    out += _loops(getattr(sub.jaxpr, "jaxpr", sub.jaxpr))
                elif hasattr(sub, "eqns"):
                    out += _loops(sub)
    return out


def _route(band, rhs, lo, hi, **kw):
    """Trace one jax-backend solve; return (its loops, solve.* counts added
    while tracing it)."""
    from repro import obs

    before = obs.counters()
    jaxpr = jax.make_jaxpr(lambda b, r: ops.banded_solve(
        b, r, lo, hi, backend="jax", **kw))(band, rhs)
    after = obs.counters()
    moved = {k: after.get(k, 0) - before.get(k, 0)
             for k in ("solve.cr", "solve.scan")}
    return _loops(jaxpr.jaxpr), moved


def _dense_solve(band, rhs, w):
    dense = np.asarray(bd.to_dense(bd.Banded(band, w, w)))
    r = np.asarray(rhs)
    vec = r.ndim == band.ndim - 1
    out = np.linalg.solve(dense, r[..., None] if vec else r)
    return out[..., 0] if vec else out


@pytest.mark.parametrize("form", ["vec", "batched"])
@pytest.mark.parametrize("w", [1, 2, 3])
def test_jax_cr_route_above_crossover(w, form):
    """Above the crossover the unpivoted solve is block CR (``solve.cr``
    moves): two loops of ceil(log2(n/w)) trips, the reduction and the back
    substitution, where the scan LU runs two of n; within 1e-10 of the
    dense oracle and 1e-12 of the scan LU (``alg="lu"``); n is not a
    multiple of w, so CR's own block padding is exercised too."""
    n = w * ops.CR_MIN_BLOCK_ROWS + (w - 1)
    rng = np.random.default_rng(40 + w)
    batch = (2,) if form == "batched" else ()
    band = _rand_band(rng, n, w, w, jnp.float64, batch,
                      boost=4.0 * (2 * w + 1))
    shape = batch + ((n, 3) if form == "batched" else (n,))
    rhs = jnp.asarray(rng.standard_normal(shape))
    loops, moved = _route(band, rhs, w, w)
    depth = (-(-n // w) - 1).bit_length()
    assert loops == [("scan", depth)] * 2
    assert moved == {"solve.cr": 1, "solve.scan": 0}
    got = jax.jit(lambda b, r: ops.banded_solve(b, r, w, w, backend="jax"))(
        band, rhs)
    lu = jax.jit(lambda b, r: ops.banded_solve(
        b, r, w, w, backend="jax", alg="lu"))(band, rhs)
    want = _dense_solve(band, rhs, w)
    assert got.shape == rhs.shape
    scale = np.max(np.abs(want))
    assert np.max(np.abs(np.asarray(got) - want)) <= 1e-10 * scale
    assert np.max(np.abs(np.asarray(got - lu))) <= 1e-12 * scale


@pytest.mark.parametrize("case", ["below", "pivot", "lu", "asym", "diag"])
def test_jax_scan_route(case):
    """The scan LU keeps every solve the crossover does not cover: short
    systems, ``pivot=True``, an explicit ``alg="lu"``, lo != hi and diagonal
    bands."""
    C = ops.CR_MIN_BLOCK_ROWS
    lo, hi, n, kw = 1, 1, 2 * C, {}
    if case == "below":
        lo = hi = 2
        n = 2 * (C - 1)
    elif case == "pivot":
        kw = {"pivot": True}
    elif case == "lu":
        kw = {"alg": "lu"}
    elif case == "asym":
        lo, hi = 2, 1
    else:
        lo = hi = 0
    rng = np.random.default_rng(7)
    band = _rand_band(rng, n, lo, hi, jnp.float64, (2,))
    rhs = jnp.asarray(rng.standard_normal((2, n, 2)))
    loops, moved = _route(band, rhs, lo, hi, **kw)
    assert moved == {"solve.cr": 0, "solve.scan": 1}
    if lo > 0:
        assert ("scan", n) in loops


def _poisoned(band, rhs, w, n_active, cap, rng):
    """Embed (band, rhs) at capacity ``cap`` with a NaN/garbage tail."""
    bp = np.full(band.shape[:-2] + (cap, 2 * w + 1), np.nan)
    bp[..., :n_active, :] = np.asarray(band)
    rp = rng.standard_normal(rhs.shape[:-2] + (cap, rhs.shape[-1])) * 1e30
    rp[..., :n_active, :] = np.asarray(rhs)
    return jnp.asarray(bp), jnp.asarray(rp)


@pytest.mark.parametrize("w", [1, 2])
def test_jax_cr_capacity_tail_invariance(w):
    """Above the crossover a capacity-padded solve (tail poisoned before
    canonicalisation) equals the unpadded solve bitwise on the active
    prefix, and is exactly zero on the tail."""
    n_active = w * ops.CR_MIN_BLOCK_ROWS + 37
    cap = n_active + 3 * w * 64 + 5
    rng = np.random.default_rng(90 + w)
    band = _rand_band(rng, n_active, w, w, jnp.float64, (2,),
                      boost=4.0 * (2 * w + 1))
    rhs = jnp.asarray(rng.standard_normal((2, n_active, 4)))
    bp, rp = _poisoned(band, rhs, w, n_active, cap, rng)
    solve = jax.jit(lambda b, r, na: ops.banded_solve(
        b, r, w, w, backend="jax", n_active=na))
    from repro import obs

    before = obs.counters().get("solve.cr", 0)
    full = solve(bp, rp, jnp.int32(n_active))
    plain = jax.jit(lambda b, r: ops.banded_solve(b, r, w, w, backend="jax"))(
        band, rhs)
    assert obs.counters().get("solve.cr", 0) - before == 2
    np.testing.assert_array_equal(np.asarray(full[..., :n_active, :]),
                                  np.asarray(plain))
    np.testing.assert_array_equal(np.asarray(full[..., n_active:, :]), 0.0)


@pytest.mark.parametrize("how", ["stacked", "vmap"])
def test_jax_cr_lane_invariance(how):
    """A T=4 stack of capacity-padded solves, with a different active count
    per lane, equals each lane solved alone bitwise, as a leading batch dim
    and under ``vmap`` (the fleet's lanes)."""
    w, T = 1, 4
    cap = 2 * ops.CR_MIN_BLOCK_ROWS
    rng = np.random.default_rng(123)
    nas = [cap, cap - 1, cap // 2 + 5, 7]
    bands, rhss = [], []
    for na in nas:
        band = _rand_band(rng, na, w, w, jnp.float64, (2,), boost=12.0)
        rhs = jnp.asarray(rng.standard_normal((2, na, 3)))
        bp, rp = _poisoned(band, rhs, w, na, cap, rng)
        bands.append(bp)
        rhss.append(rp)
    B, R = jnp.stack(bands), jnp.stack(rhss)
    na_arr = jnp.asarray(nas, jnp.int32)

    def one(b, r, na):
        return ops.banded_solve(b, r, w, w, backend="jax", n_active=na)

    stacked = (jax.jit(one)(B, R, na_arr) if how == "stacked"
               else jax.jit(jax.vmap(one))(B, R, na_arr))
    lane = jax.jit(one)
    for t in range(T):
        np.testing.assert_array_equal(
            np.asarray(stacked[t]),
            np.asarray(lane(bands[t], rhss[t], na_arr[t])))


@pytest.mark.parametrize("w", [1, 2, 3])
def test_cr_jax_rolled_levels_match_compacted(w):
    """The unpivoted ``block_cr_solve_jax`` runs its levels rolled into
    loops over full-length arrays; it does the compacted levels' arithmetic,
    bitwise at w = 1 (the q = 0 solves of the serving path) and w = 2, and
    within 1e-15 relative at w = 3, on a batch of odd block counts."""
    from repro.kernels.cr_jax import _solve_compacted, block_cr_solve_jax

    rng = np.random.default_rng(60 + w)
    for n in (1, w * 37 + 1, w * 64):
        band = _rand_band(rng, n, w, w, jnp.float64, (2,),
                          boost=4.0 * (2 * w + 1))
        rhs = jnp.asarray(rng.standard_normal((2, n, 3)))
        rolled = np.asarray(jax.jit(lambda b, r: block_cr_solve_jax(
            b, r, w, pivot=False))(band, rhs))
        compact = np.asarray(jax.jit(lambda b, r: _solve_compacted(
            b, r, w, False))(band, rhs))
        if w <= 2:
            np.testing.assert_array_equal(rolled, compact)
        else:
            assert (np.max(np.abs(rolled - compact))
                    <= 1e-15 * np.max(np.abs(compact)))
