"""Pallas TPU kernel: on-chip blocked RGF band inverse (paper Algorithm 5).

``core/band_inverse.py`` computes the central band of ``G = H^{-1}`` with
the recursive Green's function block-tridiagonal algorithm: a forward and a
backward Schur-complement recurrence plus a local combine. As two host-level
``lax.scan``s, every T-step sweep streams its (w, w) blocks through HBM.
This kernel runs the whole algorithm inside ONE ``pallas_call`` per batch
item: the block stacks load into VMEM once, both recurrences write their
Schur complements to VMEM scratch, and the G blocks leave as outputs.
Batched inputs (the per-dim factor stacks, the fleet tenant axis) fold into
the kernel grid, as with every kernel in this package.

Parity: the kernel body reuses the *same* value-level block primitives as
the scan path — ``_mm`` (fixed-association multiply-accumulate) and
``_block_solve`` (scan-LU on the dense block viewed as a band) from
``core.band_inverse`` — applied in the same order, so the output is
bit-identical to the jax scans. Capacity padding stays with the caller:
``inverse_band`` canonicalizes to ``blockdiag(H_active, I)`` before
dispatching here, and RGF is a direct method, so identity tails in means
``blockdiag(G_active, I)`` out — exactly.

The imports from ``core.band_inverse`` are deferred to trace time:
``repro.kernels`` imports every kernel module at package load, while the
core imports ``kernels.ops`` lazily — a module-level import here would
close that cycle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rgf_blocks_pallas", "rgf_inverse_band"]


def _rgf_kernel(dg_ref, u_ref, l_ref, gd_ref, gu_ref, gl_ref, f_scr, w_scr,
                *, T, w):
    from ..core.band_inverse import _block_solve, _mm  # deferred: cycle

    Dg = dg_ref[...]
    U = u_ref[...]
    L = l_ref[...]

    # forward Schur: F_0 = D_0, F_j = D_j - L_j F_{j-1}^{-1} U_{j-1}
    f_scr[pl.ds(0, 1)] = Dg[0:1]

    def fwd(j, _):
        F_prev = f_scr[pl.ds(j - 1, 1)][0]
        D_j = jax.lax.dynamic_index_in_dim(Dg, j, 0, keepdims=False)
        U_prevj = jax.lax.dynamic_index_in_dim(U, j - 1, 0, keepdims=False)
        L_j = jax.lax.dynamic_index_in_dim(L, j, 0, keepdims=False)
        f_scr[pl.ds(j, 1)] = (D_j - _mm(L_j, _block_solve(F_prev,
                                                          U_prevj)))[None]
        return 0

    jax.lax.fori_loop(1, T, fwd, 0)

    # backward Schur: W_{T-1} = D_{T-1}, W_j = D_j - U_j W_{j+1}^{-1} L_{j+1}
    w_scr[pl.ds(T - 1, 1)] = Dg[T - 1 : T]

    def bwd(t, _):
        j = T - 2 - t
        W_next = w_scr[pl.ds(j + 1, 1)][0]
        D_j = jax.lax.dynamic_index_in_dim(Dg, j, 0, keepdims=False)
        U_j = jax.lax.dynamic_index_in_dim(U, j, 0, keepdims=False)
        L_next = jax.lax.dynamic_index_in_dim(L, j + 1, 0, keepdims=False)
        w_scr[pl.ds(j, 1)] = (D_j - _mm(U_j, _block_solve(W_next,
                                                          L_next)))[None]
        return 0

    jax.lax.fori_loop(0, T - 1, bwd, 0)

    F = f_scr[...]
    W = w_scr[...]
    eye = jnp.broadcast_to(jnp.eye(w, dtype=Dg.dtype), Dg.shape)
    # G_jj = (F_j + W_j - D_j)^{-1}; off-diagonals by block substitution
    Gd = _block_solve(F + W - Dg, eye)
    Gu = -_block_solve(F[:-1], _mm(U[:-1], Gd[1:]))
    Gl = -_block_solve(W[1:], _mm(L[1:], Gd[:-1]))
    zpad = jnp.zeros((1, w, w), Dg.dtype)
    gd_ref[...] = Gd
    gu_ref[...] = jnp.concatenate([Gu, zpad])
    gl_ref[...] = jnp.concatenate([Gl, zpad])


@functools.partial(jax.jit, static_argnames=("T", "w", "interpret"))
def rgf_blocks_pallas(Dg, U, L, *, T: int, w: int, interpret: bool):
    """(G, T, w, w) block-tridiagonal stacks -> (Gd, Gu, Gl) of the inverse.

    ``Gu[j] = G_{j, j+1}``, ``Gl[j] = G_{j+1, j}`` (last entries zero), as
    in ``core.band_inverse._rgf``. One grid step per batch item; the whole
    T-step recurrence runs on-chip.
    """
    G = Dg.shape[0]
    dtype = Dg.dtype
    spec = pl.BlockSpec((None, T, w, w), lambda g: (g, 0, 0, 0))
    shape = jax.ShapeDtypeStruct((G, T, w, w), dtype)
    return pl.pallas_call(
        functools.partial(_rgf_kernel, T=T, w=w),
        grid=(G,),
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[shape, shape, shape],
        scratch_shapes=[pltpu.VMEM((T, w, w), dtype),   # forward Schur F
                        pltpu.VMEM((T, w, w), dtype)],  # backward Schur W
        interpret=interpret,
    )(Dg, U, L)


def rgf_inverse_band(data, lo: int, hi: int, hw: int, *,
                     interpret: bool):
    """Band (half-bw ``hw``) of H^{-1}; ``data`` (..., n, lo+hi+1) band rows.

    The block partition and band extraction are the scan path's own
    ``_to_blocks`` / ``_blocks_to_band`` (pure gathers, vmapped over the
    batch); only the recurrences run in the kernel. Returns the (..., n,
    2*hw+1) band data — callers wrap it back into a Banded with their
    ``n_active``.
    """
    from ..core.band_inverse import _blocks_to_band, _to_blocks
    from ..core.banded import Banded

    n = data.shape[-2]
    w = max(max(lo, hi), hw, 1)
    T = -(-n // w)
    batch = data.shape[:-2]
    flat = data.reshape((-1,) + data.shape[-2:])
    Dg, U, L = jax.vmap(
        lambda d: _to_blocks(Banded(d, lo, hi), w)[:3])(flat)
    gd, gu, gl = rgf_blocks_pallas(Dg, U, L, T=T, w=w, interpret=interpret)
    band = jax.vmap(
        lambda a, b, c: _blocks_to_band(a, b, c, n, hw).data)(gd, gu, gl)
    return band.reshape(batch + band.shape[-2:])
