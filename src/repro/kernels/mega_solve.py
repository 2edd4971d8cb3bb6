"""Pallas TPU kernels: the ENTIRE backfitting solve in one ``pallas_call``.

``fused_sweep.py`` (PR 4) made each backfitting *iteration* a single kernel,
but the convergence loop itself stayed a host-level ``lax.while_loop`` /
``fori_loop``: every iteration re-dispatches the kernel and round-trips the
(D, n, B) state through HBM. The kernels here move that loop **on-chip** —
one ``pallas_call`` runs the whole ``solve_mhat``: warm-start residual,
preconditioner seed, ``iters`` bounded iterations with the PCG tol check
evaluated in VMEM, and the exit diagnostics (realized iteration count, final
residual stack) returned as outputs. A fit, an MLL/gradient solve, or a
streaming insert's warm solve is then exactly ONE dispatch end-to-end.

The per-dimension pipeline inside the loop reuses the *same* value-level
building blocks as the per-iteration kernels (``_mv`` / ``_gather`` /
``_solve_sym`` / ``_block_solve_dim`` from ``fused_sweep``), executed in the
same order on the same lcm/identity-tail padded operands, so:

  * jacobi / gauss_seidel whole-solves are **bit-identical** to the
    per-iteration fused host loop (and run exactly ``iters`` sweeps, like
    the host semantics — no tol exit for the stationary methods);
  * PCG matches at convergence level (the in-kernel inner products reduce
    with ``jnp.sum`` exactly like ``_pcg_kernel``; the unfused host loop's
    ``_det_dot`` halving tree associates differently at the ulp level) and
    replicates the host early-exit condition
    ``(i < iters) & any(|rz_k| > tol^2 |rz_0|)`` on-chip, so it exits at
    the same iteration count.

Iteration/residual semantics: PCG returns the realized iteration count (an
int32 scalar output) and the final recursively-updated residual stack ``r``;
the stationary sweeps always run ``iters`` and instead return the per-dim
block quantities ``k_d = Khat_d^{-1} x_d`` their final sweep already holds,
from which the caller forms the exit residual
``v - k - (sum_d x_d)/sigma^2`` with **no extra banded matvec** (the
return_info residual fusion, see ``core/backfitting.py``).

VMEM budget (what ``resolve_fused``'s "auto" checks before taking
``"whole"``): everything lives on-chip at once — the RHS, warm start, the
loop-carried state and its intermediates — so the footprint is the
per-iteration kernel's plus the iteration scratch:

    mega_vmem_bytes = D * npad * (S*B + sum_w(2w+1)) * itemsize
                      + 2 * D * npad * 4            (int32 index stacks)

with ``S = 12`` state arrays for PCG (v, x0, x, r, p, ap, z, the coupling
total and in/out copies) and ``S = 7`` for jacobi/gauss_seidel (v, x0,
carry, k, total and the two outputs). Past ``REPRO_FUSED_VMEM_CAP`` "auto"
falls back to the per-iteration kernel, then to the unfused dispatch path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .fused_sweep import (FusedSweep, _block_solve_dim, _gather, _mv,
                          _pad_len, _solve_sym)

__all__ = ["MegaSolve", "mega_vmem_bytes", "mega_jacobi_solve_pallas",
           "mega_gauss_seidel_solve_pallas", "mega_pcg_solve_pallas"]


def mega_vmem_bytes(n: int, D: int, B: int, widths, itemsize: int,
                    method: str = "pcg") -> int:
    """Estimated VMEM footprint of one whole-solve call (see module doc)."""
    npad = _pad_len(n, widths)
    state_arrays = 12 if method == "pcg" else 7
    bands = sum(2 * w + 1 for w in widths)
    return D * npad * (state_arrays * B + bands) * itemsize + 2 * D * npad * 4


def _khat_inv_dim(saphi_d, phi_d, sort_d, rank_d, s2, u_d, *, w_p, w_s,
                  pivot):
    """Khat_d^{-1} u_d from the sweep's own factors (no A stack needed).

    P^T Phi^{-1} (s^2 A + Phi) P u = s^2 Khat^{-1} u + u, so
    Khat^{-1} u = (P^T Phi^{-1} SAPhi P u - u) / s^2.
    """
    us = _gather(u_d, sort_d)
    y = _mv(saphi_d, us, w_s)
    wv = _solve_sym(phi_d, y, w_p, pivot=pivot)
    return (_gather(wv, rank_d) - u_d) / s2


# ---------------------------------------------------------------------------
# damped block-Jacobi: in-kernel fori_loop over `iters` full sweeps
# ---------------------------------------------------------------------------


def _jacobi_solve_kernel(sig_ref, v_ref, x0_ref, phi_ref, saphi_ref,
                         sort_ref, rank_ref, x_ref, k_ref, *, w_p, w_s,
                         alpha, iters, pivot, warm):
    D = v_ref.shape[0]
    s2 = sig_ref[0, 0]
    v = v_ref[...]
    phi, saphi = phi_ref[...], saphi_ref[...]
    sort, rank = sort_ref[...], rank_ref[...]
    x0 = x0_ref[...]

    if warm:
        k0 = jnp.stack([
            _khat_inv_dim(saphi[d], phi[d], sort[d], rank[d], s2, x0[d],
                          w_p=w_p, w_s=w_s, pivot=pivot) for d in range(D)])
    else:
        k0 = jnp.zeros_like(v)

    def body(_, carry):
        u, k = carry
        # same op order as the per-iteration kernel: one loop-invariant
        # cross-dim reduction, then every dim off the same total
        total = jnp.sum(u, axis=0)
        new_u, new_k = [], []
        for d in range(D):
            r_d = v[d] - (total - u[d]) / s2
            new_d = _block_solve_dim(saphi[d], phi[d], sort[d], rank[d], s2,
                                     r_d, w_p=w_p, w_s=w_s, pivot=pivot)
            new_u.append((1.0 - alpha) * u[d] + alpha * new_d)
            new_k.append((1.0 - alpha) * k[d] + alpha * (r_d - new_d / s2))
        return jnp.stack(new_u), jnp.stack(new_k)

    u, k = jax.lax.fori_loop(0, iters, body, (x0, k0))
    x_ref[...] = u
    k_ref[...] = k


@functools.partial(jax.jit, static_argnames=("w_p", "w_s", "alpha", "iters",
                                             "pivot", "warm", "interpret"))
def mega_jacobi_solve_pallas(phi, saphi, sort_idx, rank_idx, sigma2, v, x0,
                             *, w_p: int, w_s: int, alpha: float, iters: int,
                             pivot: bool = False, warm: bool = False,
                             interpret: bool):
    """Whole damped-Jacobi solve; returns ``(x, k)`` (pre-padded operands)."""
    D, npad, B = v.shape
    dtype = v.dtype
    return pl.pallas_call(
        functools.partial(_jacobi_solve_kernel, w_p=w_p, w_s=w_s, alpha=alpha,
                          iters=iters, pivot=pivot, warm=warm),
        out_shape=[jax.ShapeDtypeStruct((D, npad, B), dtype),
                   jax.ShapeDtypeStruct((D, npad, B), dtype)],
        interpret=interpret,
    )(sigma2, v, x0, phi, saphi, sort_idx, rank_idx)


# ---------------------------------------------------------------------------
# Gauss-Seidel (paper Alg 4): sequential dims inside an in-kernel fori_loop
# ---------------------------------------------------------------------------


def _gs_solve_kernel(sig_ref, v_ref, x0_ref, phi_ref, saphi_ref, sort_ref,
                     rank_ref, x_ref, k_ref, *, w_p, w_s, iters, pivot):
    D = v_ref.shape[0]
    s2 = sig_ref[0, 0]
    v = v_ref[...]
    phi, saphi = phi_ref[...], saphi_ref[...]
    sort, rank = sort_ref[...], rank_ref[...]

    def body(_, carry):
        u, k = carry
        total = jnp.sum(u, axis=0)
        rows = [u[d] for d in range(D)]
        ks = [k[d] for d in range(D)]
        for d in range(D):
            cur = rows[d]
            r_d = v[d] - (total - cur) / s2
            new_d = _block_solve_dim(saphi[d], phi[d], sort[d], rank[d], s2,
                                     r_d, w_p=w_p, w_s=w_s, pivot=pivot)
            # same update order as the per-iteration kernel: total - old + new
            total = total - cur + new_d
            rows[d] = new_d
            # exact by the block solve: Khat_d^{-1} new_d = r_d - new_d/s^2
            ks[d] = r_d - new_d / s2
        return jnp.stack(rows), jnp.stack(ks)

    u, k = jax.lax.fori_loop(0, iters, body,
                             (x0_ref[...], jnp.zeros_like(v)))
    x_ref[...] = u
    k_ref[...] = k


@functools.partial(jax.jit, static_argnames=("w_p", "w_s", "iters", "pivot",
                                             "interpret"))
def mega_gauss_seidel_solve_pallas(phi, saphi, sort_idx, rank_idx, sigma2, v,
                                   x0, *, w_p: int, w_s: int, iters: int,
                                   pivot: bool = False,
                                   interpret: bool):
    """Whole Gauss-Seidel solve; returns ``(x, k)`` (pre-padded operands)."""
    D, npad, B = v.shape
    dtype = v.dtype
    return pl.pallas_call(
        functools.partial(_gs_solve_kernel, w_p=w_p, w_s=w_s, iters=iters,
                          pivot=pivot),
        out_shape=[jax.ShapeDtypeStruct((D, npad, B), dtype),
                   jax.ShapeDtypeStruct((D, npad, B), dtype)],
        interpret=interpret,
    )(sigma2, v, x0, phi, saphi, sort_idx, rank_idx)


# ---------------------------------------------------------------------------
# PCG: bounded in-kernel while_loop with the tol check on-chip
# ---------------------------------------------------------------------------


def _pcg_solve_kernel(sig_ref, v_ref, x0_ref, a_ref, phi_ref, saphi_ref,
                      sort_ref, rank_ref, x_ref, r_ref, it_ref, *, w_a, w_p,
                      w_s, iters, tol, pivot, warm):
    D = v_ref.shape[0]
    s2 = sig_ref[0, 0]
    v = v_ref[...]
    a, phi, saphi = a_ref[...], phi_ref[...], saphi_ref[...]
    sort, rank = sort_ref[...], rank_ref[...]

    def apply_mhat(u):
        tp = jnp.sum(u, axis=0)
        return jnp.stack([
            _gather(_solve_sym(phi[d], _mv(a[d], _gather(u[d], sort[d]), w_a),
                               w_p, pivot=pivot), rank[d]) + tp / s2
            for d in range(D)])

    def precondition(r):
        return jnp.stack([
            _block_solve_dim(saphi[d], phi[d], sort[d], rank[d], s2, r[d],
                             w_p=w_p, w_s=w_s, pivot=pivot)
            for d in range(D)])

    x = x0_ref[...]
    # amv(0) == 0 exactly: a cold start skips the warm-start residual
    r = v - apply_mhat(x) if warm else v
    z = precondition(r)
    p = z
    rz = jnp.sum(r * z, axis=(0, 1))

    def body(carry):
        i, x, r, p, rz = carry
        ap = apply_mhat(p)
        denom = jnp.sum(p * ap, axis=(0, 1))
        alpha = (rz / jnp.where(denom == 0, 1.0, denom))[None, None, :]
        x = x + alpha * p
        r = r - alpha * ap
        z = precondition(r)
        rz_new = jnp.sum(r * z, axis=(0, 1))
        beta = (rz_new / jnp.where(rz == 0, 1.0, rz))[None, None, :]
        p = z + beta * p
        return i + 1, x, r, p, rz_new

    i0 = jnp.asarray(0, jnp.int32)
    if tol > 0:
        # the host loop's exit condition, evaluated on-chip: |rz| magnitudes
        # (the KMG-era contract — rz can pass through negative values)
        thresh = tol**2 * jnp.abs(rz)

        def cond(carry):
            i, _, _, _, rz = carry
            return (i < iters) & jnp.any(jnp.abs(rz) > thresh)

        i, x, r, p, rz = jax.lax.while_loop(cond, body, (i0, x, r, p, rz))
    else:
        i, x, r, p, rz = jax.lax.fori_loop(
            0, iters, lambda _, c: body(c), (i0, x, r, p, rz))
    x_ref[...] = x
    r_ref[...] = r
    it_ref[0, 0] = i


@functools.partial(jax.jit, static_argnames=("w_a", "w_p", "w_s", "iters",
                                             "tol", "pivot", "warm",
                                             "interpret"))
def mega_pcg_solve_pallas(a, phi, saphi, sort_idx, rank_idx, sigma2, v, x0,
                          *, w_a: int, w_p: int, w_s: int, iters: int,
                          tol: float = 0.0, pivot: bool = False,
                          warm: bool = False, interpret: bool):
    """Whole PCG solve; returns ``(x, r, iters_used)`` (pre-padded operands).

    ``iters_used`` is the realized iteration count (int32 scalar): the
    bounded in-kernel while_loop exits once every RHS column satisfies
    ``|rz_k| <= tol^2 |rz_0|``, exactly like the host loop; ``tol == 0``
    runs the fixed ``iters``.
    """
    D, npad, B = v.shape
    dtype = v.dtype
    x, r, it = pl.pallas_call(
        functools.partial(_pcg_solve_kernel, w_a=w_a, w_p=w_p, w_s=w_s,
                          iters=iters, tol=tol, pivot=pivot, warm=warm),
        out_shape=[jax.ShapeDtypeStruct((D, npad, B), dtype),
                   jax.ShapeDtypeStruct((D, npad, B), dtype),
                   jax.ShapeDtypeStruct((1, 1), jnp.int32)],
        interpret=interpret,
    )(sigma2, v, x0, a, phi, saphi, sort_idx, rank_idx)
    return x, r, it[0, 0]


# ---------------------------------------------------------------------------
# trace-time wrapper: pads once, one pallas_call per whole solve
# ---------------------------------------------------------------------------


class MegaSolve:
    """Whole-solve dispatch over a :class:`FusedSweep`'s padded operands.

    Composes (rather than extends) ``FusedSweep``: the padding/layout
    contract is identical — the same lcm identity-tail bands, canonical
    permutations and zero-tailed state — so the in-kernel loop executes the
    exact op sequence the per-iteration kernels would, minus the per-
    iteration dispatch + HBM round trip. States in and out are unpadded
    (D, n, B).
    """

    def __init__(self, fs: FusedSweep):
        self.fs = fs

    def _states(self, v, x0):
        fs = self.fs
        v_p = fs.pad_state(v)
        x0_p = jnp.zeros_like(v_p) if x0 is None else fs.pad_state(x0)
        return v_p, x0_p

    def jacobi(self, v, x0, *, alpha: float, iters: int):
        fs = self.fs
        v_p, x0_p = self._states(v, x0)
        x, k = mega_jacobi_solve_pallas(
            fs.phi, fs.saphi, fs.sort_idx, fs.rank_idx, fs.sigma2, v_p, x0_p,
            w_p=fs.w_p, w_s=fs.w_s, alpha=alpha, iters=iters, pivot=fs.pivot,
            warm=x0 is not None, interpret=fs.interpret)
        return fs.unpad(x), fs.unpad(k)

    def gauss_seidel(self, v, x0, *, iters: int):
        fs = self.fs
        v_p, x0_p = self._states(v, x0)
        x, k = mega_gauss_seidel_solve_pallas(
            fs.phi, fs.saphi, fs.sort_idx, fs.rank_idx, fs.sigma2, v_p, x0_p,
            w_p=fs.w_p, w_s=fs.w_s, iters=iters, pivot=fs.pivot,
            interpret=fs.interpret)
        return fs.unpad(x), fs.unpad(k)

    def pcg(self, v, x0, *, iters: int, tol: float):
        fs = self.fs
        assert fs.a is not None, "PCG needs the A factor stack"
        v_p, x0_p = self._states(v, x0)
        x, r, it = mega_pcg_solve_pallas(
            fs.a, fs.phi, fs.saphi, fs.sort_idx, fs.rank_idx, fs.sigma2, v_p,
            x0_p, w_a=fs.w_a, w_p=fs.w_p, w_s=fs.w_s, iters=iters, tol=tol,
            pivot=fs.pivot, warm=x0 is not None, interpret=fs.interpret)
        return fs.unpad(x), fs.unpad(r), it
