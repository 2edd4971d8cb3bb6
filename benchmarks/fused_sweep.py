"""Fused backfitting-sweep ablation: one pallas_call/iteration vs 4+.

Measures, per solve_mhat iteration on the PCG hot path (the default solver
for fit / MLL / gradients / streaming inserts):

  * ``dispatches_per_iter`` — pallas_call ops inside the iteration loop,
    counted *statically from the jaxpr* (loop bodies of while/scan), so the
    number is exact and backend-independent: 4 unfused (A-matvec, Phi-solve,
    Phi-matvec, SAPhi-solve) vs 1 fused;
  * ``hbm_bytes_per_iter_est`` — coarse per-iteration HBM traffic model:
    every dispatched op (and every pure-jax gather/scatter/axpy between
    them) reads and writes the (D, n, B) state stack, so unfused PCG moves
    ~34 state traversals per iteration while the fused kernel moves 6 (the
    carried x/r/p in and out) — both plus one read of the band stacks;
  * wall time per iteration, fused vs unfused. Off-TPU both run the pallas
    kernels in interpret mode, which charges a large constant per
    ``pallas_call`` — so interpret wall time rewards exactly what the fused
    kernel removes (dispatches), while the HBM column models the on-TPU win.

Artifact: ``benchmarks/BENCH_fused_sweep.json`` (written by ``run.py``; the
CI dispatch job fails if a benchmark run does not produce it).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backfitting import DimOps, SolveConfig, solve_mhat
from repro.core.banded import add, scale
from repro.core.kernel_packets import kp_factors


def _time(fn, reps=3):
    out = fn()  # warmup/compile
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.time() - t0) / reps


def _make_ops(n, D, q, sigma, seed=0):
    rng = np.random.default_rng(seed)
    X = jnp.asarray(rng.random((n, D)) * 10)
    sort_idx = jnp.argsort(X.T, axis=1)
    xs = jnp.take_along_axis(X.T, sort_idx, axis=1)
    rank_idx = jnp.argsort(sort_idx, axis=1)
    omega = jnp.asarray(0.9 + rng.random(D))
    A, Phi = jax.vmap(lambda om, x: kp_factors(q, om, x))(omega, xs)
    SAPhi = add(scale(A, sigma**2), Phi)
    return DimOps(A=A, Phi=Phi, SAPhi=SAPhi, sort_idx=sort_idx,
                  rank_idx=rank_idx, sigma2=jnp.asarray(sigma**2))


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
    """(pallas_calls inside loop bodies, total pallas_calls) — static count."""
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


def dispatches_per_iter(fn, *args):
    """Static pallas_call count in the iteration loop of ``fn``'s jaxpr."""
    closed = jax.make_jaxpr(fn)(*args)
    return _count_pallas(closed.jaxpr)


def _hbm_bytes_per_iter(ops_d, B, fused):
    """Coarse state-traversal model (see module docstring)."""
    D, n = ops_d.D, ops_d.n
    itemsize = ops_d.Phi.data.dtype.itemsize
    state = D * n * B * itemsize
    bands = D * n * itemsize * (ops_d.A.width + ops_d.Phi.width
                                + ops_d.SAPhi.width)
    traversals = 6 if fused else 34
    return traversals * state + bands


def run(ns=(1000, 4096), D=4, q=1, B=1, iters=8, reps=3, out_rows=None):
    rows = out_rows if out_rows is not None else []
    for n in ns:
        ops_d = _make_ops(n, D, q, sigma=1.0)
        rng = np.random.default_rng(n)
        v = jnp.asarray(rng.standard_normal((D, n, B)))
        res = {}
        for mode in ("unfused", "fused"):
            cfg = SolveConfig(method="pcg", iters=iters, backend="pallas",
                              fused="on" if mode == "fused" else "off")
            fn = jax.jit(lambda vv, cfg=cfg: solve_mhat(ops_d, vv, cfg))
            wall = _time(lambda: fn(v), reps)
            disp_iter, disp_total = dispatches_per_iter(fn, v)
            res[mode] = dict(
                wall_per_iter_s=wall / iters,
                dispatches_per_iter=disp_iter,
                dispatches_total=disp_total,
                hbm_bytes_per_iter_est=_hbm_bytes_per_iter(
                    ops_d, B, mode == "fused"),
                out=np.asarray(fn(v)),
            )
        drift = float(np.abs(res["fused"]["out"] - res["unfused"]["out"]).max()
                      / max(np.abs(res["unfused"]["out"]).max(), 1e-30))
        for mode in ("unfused", "fused"):
            r = res[mode]
            rows.append({
                "bench": "fused_sweep", "mode": mode, "method": "pcg",
                "n": n, "D": D, "q": q, "rhs_B": B, "iters": iters,
                "wall_per_iter_s": r["wall_per_iter_s"],
                "dispatches_per_iter": r["dispatches_per_iter"],
                "dispatches_total": r["dispatches_total"],
                "hbm_bytes_per_iter_est": r["hbm_bytes_per_iter_est"],
                "rel_drift_vs_unfused": drift,
            })
            print(f"fused_sweep,{mode},n={n},"
                  f"ms_per_iter={r['wall_per_iter_s']*1e3:.2f},"
                  f"dispatches_per_iter={r['dispatches_per_iter']},"
                  f"hbm_MB_per_iter={r['hbm_bytes_per_iter_est']/2**20:.1f}",
                  flush=True)
        du, df = (res["unfused"]["dispatches_per_iter"],
                  res["fused"]["dispatches_per_iter"])
        print(f"fused_sweep,summary,n={n},dispatch_ratio={du}/{df},"
              f"wall_ratio={res['unfused']['wall_per_iter_s'] / res['fused']['wall_per_iter_s']:.2f}x,"
              f"rel_drift={drift:.1e}", flush=True)
    return rows


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    args = ap.parse_args()
    run(ns=(1000, 4096, 16_384) if args.full else (1000, 4096))
