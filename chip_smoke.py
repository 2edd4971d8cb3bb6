#!/usr/bin/env python3
"""Chip smoke test: the main path once, on one TPU chip, through the entry
points a user calls.

    python chip_smoke.py

Phases (each program is compiled ahead of time to report its compile
seconds and device memory apart from its run seconds; the entry point then
reuses that executable):

  1. setup      — x64 on, fail unless ``jax.devices()[0].platform == "tpu"``;
                  print the device and the resolved backend / fusion mode /
                  preconditioner before anything compiles.
  2. main path  — the paper's Fig. 5 setting (Schwefel, D=10, q=0, PCG,
                  40 iterations, ``sample_test_function(seed=0)``) in f64:
                  ``fit``, ``posterior_mean``, ``posterior_var`` and
                  ``log_likelihood`` at 256 query points. The fit's compiled
                  footprint is checked against the chip's memory first.
  3. reference  — the same fit on a 2000-point subset against the dense
                  Cholesky GP of ``repro.core.exact`` (mean, variance, MLL),
                  computed on the host CPU.
  4. serving    — a ``GPServeEngine`` at capacity 16384: ticks of mean, var
                  and acq queries, 8 inserts and 1 evict behind the fence,
                  then 2 more and 1 more; the last answers are checked
                  against a fresh fit of the engine's points at the same
                  capacity.
  5. kernels    — every Pallas kernel that lowers for the TPU, compiled
                  (never interpreted), in f32 against its jax-scan form.

Any mismatch, any health verdict other than OK, any health event
(``health_stats()``) or any failed phase exits non-zero and prints no
result. On success the last line of stdout is one JSON object naming the
device. Run it on a host with a TPU; anywhere else it fails.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# the Fig. 5 grid size, the query count, the dense-oracle subset and the
# engine's capacity tier are fixed: the smoke proves this one size
N = 16000
D = 10
QUERIES = 256
SUBSET = 2000
CAPACITY = 16384
SEED = 0

# tolerances against the dense oracle on the subset: the solves stop after
# 40 PCG iterations (mean, var), and the MLL log-determinant is a 16-probe
# stochastic estimate, hence its looser bound
MEAN_RTOL = 1e-5
VAR_RTOL = 1e-6
MLL_RTOL = 1e-2
KERNEL_RTOL = 1e-5


def smoke_config():
    """The Fig. 5 configuration: the paper's block-preconditioned PCG, 40
    iterations (at n=16000 it exits at ~1e-11 relative residual). This is
    not ``GPConfig()``'s default at this n, in two ways:

    * ``precond="none"``: the KMG V-cycle that ``"auto"`` adds at
      n >= 4096 is left out, because in float64 its program is the bulk of
      the XLA compile on the TPU (ROADMAP queue 1);
    * ``gband="full"``: the data is densely oversampled (omega times the
      point gap is ~1e-3 at n=16000), outside the windowed Gband patch's
      truncation contract, so mutations keep the variance band exact with
      the full RGF sweep, as the streaming README prescribes for such data.
      The default's drift sentinel would resync after every fence instead.

    Neither default path (KMG, windowed Gband) runs here."""
    from repro.core import GPConfig

    return GPConfig(q=0, solver="pcg", solver_iters=40, precond="none",
                    gband="full")


def log(msg: str) -> None:
    print(msg, flush=True)


def compile_ahead(name, jitted, *args, **kw):
    """Compile ``jitted`` for these arguments; log seconds and footprint."""
    t0 = time.perf_counter()
    compiled = jitted.lower(*args, **kw).compile()
    dt = time.perf_counter() - t0
    mem = compiled.memory_analysis()
    log(f"compile {name}: {dt:.3f}s temp={mem.temp_size_in_bytes} "
        f"args={mem.argument_size_in_bytes} out={mem.output_size_in_bytes} "
        f"code={mem.generated_code_size_in_bytes}")
    return mem


def run(name, fn, *args, **kw):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    log(f"run {name}: {time.perf_counter() - t0:.3f}s")
    return out


def rel_err(got, want) -> float:
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)),
                                                   1e-300))


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def verdict_ok(code) -> bool:
    from repro.health import verdict as hv

    return int(code) == int(hv.OK)


def posterior(tag, X, Y, Xq, omega, sigma, key, hbm_bytes=None):
    """fit + posterior_mean + posterior_var + log_likelihood, each compiled
    ahead and then run through its entry point; verdicts must be OK."""
    from repro.core import (additive_gp, fit, log_likelihood, posterior_mean,
                            posterior_var)

    cfg = smoke_config()
    mem = compile_ahead(f"fit{tag}", additive_gp._fit_impl,
                        additive_gp.resolve_config(cfg, X.shape[0], X.dtype),
                        X, Y, omega, sigma)
    if hbm_bytes:
        need = (mem.temp_size_in_bytes + mem.argument_size_in_bytes
                + mem.output_size_in_bytes)
        log(f"fit{tag}: needs {need} of {hbm_bytes} device bytes")
        check(need < hbm_bytes, f"fit{tag} needs {need} bytes, the chip "
              f"holds {hbm_bytes}: take a smaller Fig. 5 n")
    gp = run(f"fit{tag}", fit, cfg, X, Y, omega, sigma)
    check(verdict_ok(gp.health.verdict),
          f"fit{tag} verdict {int(gp.health.verdict)}")
    compile_ahead(f"posterior_mean{tag}", posterior_mean, gp, Xq)
    mu = run(f"posterior_mean{tag}", posterior_mean, gp, Xq)
    compile_ahead(f"posterior_var{tag}", posterior_var, gp, Xq)
    var = run(f"posterior_var{tag}", posterior_var, gp, Xq)
    compile_ahead(f"log_likelihood{tag}", log_likelihood, gp, key,
                  return_verdict=True)
    ll, ll_verdict = run(f"log_likelihood{tag}", log_likelihood, gp, key,
                         return_verdict=True)
    check(verdict_ok(ll_verdict),
          f"log_likelihood{tag} verdict {int(ll_verdict)}")
    for name, v in (("mean", mu), ("var", var)):
        check(v.shape == (Xq.shape[0],) and bool(np.all(np.isfinite(v))),
              f"posterior {name}{tag}: shape {v.shape} or nonfinite values")
    check(bool(np.all(np.asarray(var) > 0)),
          f"posterior variance{tag} not positive")
    check(bool(np.isfinite(ll)), f"log likelihood{tag} not finite")
    return gp, mu, var, ll


def main_path(data, hbm_bytes):
    X, Y, Xq, omega, sigma, key = data
    gp, mu, var, ll = posterior("", X, Y, Xq, omega, sigma, key, hbm_bytes)
    log(f"main: n={X.shape[0]} mll={float(ll):.6f} "
        f"mean[0]={float(mu[0]):.6f} var[0]={float(var[0]):.6e}")
    return gp, mu, var


def host_device():
    """The host CPU device where JAX has one, else the chip. The dense
    oracle runs there: independent of the chip's float64 emulation, and
    its Cholesky would take minutes of TPU compile."""
    import jax

    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return jax.devices()[0]


def reference(data):
    import jax

    from repro.core import exact

    X, Y, Xq, omega, sigma, key = data
    Xs, Ys = X[:SUBSET], Y[:SUBSET]
    _, mu, var, ll = posterior("[subset]", Xs, Ys, Xq, omega, sigma, key)
    host = host_device()
    log(f"reference: dense oracle on {host.platform}")
    Xs_h, Ys_h, Xq_h, om_h = jax.device_put((Xs, Ys, Xq, omega), host)
    with jax.default_device(host):
        mu_x, var_x = run("exact.posterior_mean_var",
                          exact.posterior_mean_var, 0, om_h, sigma, Xs_h,
                          Ys_h, Xq_h)
        ll_x = run("exact.log_marginal_likelihood",
                   exact.log_marginal_likelihood, 0, om_h, sigma, Xs_h, Ys_h)
    errs = {"mean": rel_err(mu, mu_x), "var": rel_err(var, var_x),
            "mll": abs(float(ll) - float(ll_x)) / abs(float(ll_x))}
    log(f"reference: n={SUBSET} rel_err mean={errs['mean']:.3e} "
        f"(tol {MEAN_RTOL:g}) var={errs['var']:.3e} (tol {VAR_RTOL:g}) "
        f"mll={errs['mll']:.3e} (tol {MLL_RTOL:g}) "
        f"mll_sparse={float(ll):.6f} mll_exact={float(ll_x):.6f}")
    check(errs["mean"] <= MEAN_RTOL, f"mean rel err {errs['mean']:.3e}")
    check(errs["var"] <= VAR_RTOL, f"var rel err {errs['var']:.3e}")
    check(errs["mll"] <= MLL_RTOL, f"mll rel err {errs['mll']:.3e}")


def serving(gp, mu, var, data, bounds, f):
    from repro.core import fit, posterior_mean, posterior_var
    from repro.streaming import GPServeEngine

    X, Y, Xq, omega, sigma, key = data
    Xq_np = np.asarray(Xq)
    t0 = time.perf_counter()
    eng = GPServeEngine(gp, bounds, batch_slots=8, capacity=CAPACITY)
    log(f"time engine_init[re-home to capacity, incl. compile]: "
        f"{time.perf_counter() - t0:.3f}s")
    check(eng.capacity == CAPACITY, f"engine capacity {eng.capacity}")

    def serve(label, kinds, xs):
        qs = [eng.submit(x, kind=k) for k, x in zip(kinds, xs)]
        t0 = time.perf_counter()
        eng.run_until_done(max_ticks=64)
        log(f"time engine_ticks[{label}]: {time.perf_counter() - t0:.3f}s "
            f"for {len(qs)} queries")
        check(all(q.done for q in qs), "engine left queries unserved")
        for q in qs:
            r = q.result
            check(np.isfinite(r["mean"]) and np.isfinite(r["var"])
                  and np.isfinite(r["value"]) and np.all(np.isfinite(r["grad"])),
                  f"engine query {q.rid} ({q.kind}) nonfinite: {r}")
        return qs

    kinds = ["mean", "var", "acq"] * 8
    n_q = len(kinds)
    qs = serve("first, incl. compile", kinds, Xq_np[:n_q])
    # the engine serves the same posterior, re-homed into capacity padding
    e_mu = rel_err([q.result["mean"] for q in qs], np.asarray(mu)[:n_q])
    e_var = rel_err([q.result["var"] for q in qs], np.asarray(var)[:n_q])
    log(f"serving: engine vs posterior_mean rel_err={e_mu:.3e} "
        f"vs posterior_var rel_err={e_var:.3e}")
    check(e_mu <= MEAN_RTOL and e_var <= VAR_RTOL,
          "engine answers disagree with the direct posterior")
    serve("warm", kinds, Xq_np[n_q: 2 * n_q])

    rng = np.random.default_rng(SEED + 1)
    x_new = rng.uniform(bounds[:, 0], bounds[:, 1], size=(8, X.shape[1]))
    y_new = f(x_new) + rng.standard_normal(8)
    v0 = eng.version
    for x, y in zip(x_new, y_new):
        eng.insert(x, y)
    eng.evict()
    serve("8 inserts + 1 evict + queries, incl. compile", kinds,
          Xq_np[2 * n_q: 3 * n_q])
    for x, y in zip(x_new[:2], y_new[:2]):
        eng.insert(x + 1e-3, y)
    eng.evict()
    qs = serve("2 inserts + 1 evict + queries, warm", kinds,
               Xq_np[3 * n_q: 4 * n_q])
    check(eng.num_points == X.shape[0] + 8 - 1 + 2 - 1,
          f"engine holds {eng.num_points} points")
    check(eng.version == v0 + 12, f"engine version {eng.version}")
    check(all(q.version == eng.version for q in qs),
          "queries not served by the post-mutation posterior")
    check(verdict_ok(eng.gp.health.verdict),
          f"engine posterior verdict {int(eng.gp.health.verdict)}")
    stats = eng.health_stats()
    log(f"serving: points={eng.num_points} version={eng.version} "
        f"health={{repairs: {stats['repairs']}, resyncs: {stats['resyncs']}, "
        f"events: {len(stats['events'])}}}")
    check(not stats["events"] and stats["repairs"] == 0
          and stats["resyncs"] == 0,
          f"health events on the serve path: {stats['events']}")

    # the mutated posterior against a fresh fit of the engine's points (the
    # active prefix, oldest evicted) at the same capacity, to the oracle
    # tolerances: each insert and evict re-solves warm for 10 PCG iterations
    na = eng.num_points
    fresh = run("fit[engine points, incl. compile]", fit, smoke_config(),
                eng.gp.X[:na], eng.gp.Y[:na], omega, sigma, capacity=CAPACITY)
    check(verdict_ok(fresh.health.verdict),
          f"fresh fit verdict {int(fresh.health.verdict)}")
    xs = np.stack([q.x for q in qs])
    f_mu = run("posterior_mean[fresh, incl. compile]", posterior_mean, fresh,
               xs)
    f_var = run("posterior_var[fresh, incl. compile]", posterior_var, fresh,
                xs)
    e_mu = rel_err([q.result["mean"] for q in qs], f_mu)
    e_var = rel_err([q.result["var"] for q in qs], f_var)
    log(f"serving: after mutations, engine vs fresh fit of its {na} points "
        f"rel_err mean={e_mu:.3e} (tol {MEAN_RTOL:g}) "
        f"var={e_var:.3e} (tol {VAR_RTOL:g})")
    check(e_mu <= MEAN_RTOL, f"post-mutation mean rel err {e_mu:.3e}")
    check(e_var <= VAR_RTOL, f"post-mutation var rel err {e_var:.3e}")


def kernels():
    """Each TPU-lowering kernel, compiled, f32, against its jax-scan form."""
    import jax.numpy as jnp

    from repro.core import banded as bd
    from repro.core.kernel_packets import kp_coefficients
    from repro.kernels import ops
    from repro.kernels.band_matmul import band_matmul_pallas
    from repro.kernels.banded_matvec import banded_matvec_pallas
    from repro.kernels.kp_gram import kp_gram_pallas
    from repro.kernels.ref import kp_gram_ref

    check(not ops.interpret_kernels(), "kernels would run interpreted")
    G, n = 10, 16384
    rng = np.random.default_rng(SEED)
    f32 = jnp.float32

    def one(name, fn, want, args, keep=None):
        compile_ahead(f"kernel {name}", fn, *args, interpret=False)
        got = run(f"kernel {name}", fn, *args, interpret=False)
        err = rel_err(got if keep is None else got * keep, want)
        log(f"kernel {name}: rel_err={err:.3e} (tol {KERNEL_RTOL:g})")
        check(err <= KERNEL_RTOL, f"{name} rel err {err:.3e}")

    for w in (1, 2, 3):
        band = rng.uniform(-1.0, 1.0, size=(G, n, 2 * w + 1))
        band[..., w] = 2.0 * (2 * w + 1)  # diagonally dominant
        band = jnp.asarray(band, f32)
        x = jnp.asarray(rng.standard_normal((G, n, 1)), f32)
        B = bd.Banded(band, w, w)
        one(f"banded_matvec w={w}", banded_matvec_pallas,
            bd._matvec_scan(B, x), (band, x, w, w))
        one(f"band_matmul w={w}", band_matmul_pallas,
            bd._band_band_matmul_scan(B, B).data, (band, band, w, w, w, w),
            keep=bd._band_mask(n, 2 * w, 2 * w))
    xs = jnp.asarray(np.sort(rng.uniform(0.0, 50.0, n)), f32)
    a = kp_coefficients(0, jnp.asarray(1.0, f32), xs).data
    one("kp_gram q=0", kp_gram_pallas, kp_gram_ref(0, 1.0, xs, a),
        (0, 1.0, xs, a))
    log("kernels: not lowered, excluded on TPU: "
        + ", ".join(sorted(ops.TPU_UNLOWERED)))


def main() -> int:
    import jax

    jax.config.update("jax_enable_x64", True)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices()[0].platform = "
              f"{dev.platform!r}); refusing to run on the host",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import compile_cache

    cache = compile_cache.configure()
    import jax.numpy as jnp

    from repro.core.additive_gp import resolve_config
    from repro.data import sample_test_function
    from repro.kernels import ops

    cfg = resolve_config(smoke_config(), N, jnp.float64)
    fused = ops.resolve_fused(cfg.fused, cfg.backend,
                              widths=((1, 1), (0, 0), (1, 1)), n=N, D=D,
                              itemsize=8, method=cfg.solver,
                              precond=cfg.precond)
    hbm = (dev.memory_stats() or {}).get("bytes_limit")
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} bytes_limit={hbm}")
    log(f"config: dtype=float64 backend={cfg.backend} fused={fused} "
        f"precond={cfg.precond} solver={cfg.solver}x{cfg.solver_iters} "
        f"gband={cfg.gband} interpret_kernels={ops.interpret_kernels()} "
        f"cache={cache}")

    X, Y, f, bounds = sample_test_function("schwefel", N, D, seed=SEED)
    span = bounds[:, 1] - bounds[:, 0]
    Xq = np.random.default_rng(100 + SEED).uniform(
        bounds[:, 0], bounds[:, 1], size=(QUERIES, D))
    data = (jnp.asarray(X), jnp.asarray(Y), jnp.asarray(Xq),
            jnp.asarray(8.0 / span), 1.0,
            jnp.asarray([0, SEED], jnp.uint32))

    failed = []
    t_all = time.perf_counter()

    def phase(name, fn, *a):
        t0 = time.perf_counter()
        try:
            out = fn(*a)
            log(f"phase {name}: ok ({time.perf_counter() - t0:.1f}s)")
            return out
        except Exception:  # report every phase, then fail the run
            traceback.print_exc()
            log(f"phase {name}: FAILED ({time.perf_counter() - t0:.1f}s)")
            failed.append(name)
            return None

    main_out = phase("main", main_path, data, hbm)
    phase("reference", reference, data)
    if main_out is not None:
        phase("serving", serving, *main_out, data, bounds, f)
    else:
        failed.append("serving")
    phase("kernels", kernels)
    stats = dev.memory_stats() or {}
    log(f"device peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
        f"bytes_limit={stats.get('bytes_limit')} "
        f"total_s={time.perf_counter() - t_all:.1f}")
    if failed:
        print(f"chip_smoke: FAILED phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
