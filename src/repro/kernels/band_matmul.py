"""Pallas TPU kernel: band x band matrix product in band form.

C[i, i+m] = sum_t A[i, i+t] * B[i+t, i+m],  t in [-a_lo, a_hi],
with result half-bandwidths lo = a_lo + b_lo, hi = a_hi + b_hi.

Same tiling as ``banded_matvec``: row blocks in VMEM, the B-band halo
(|t| <= a_lo/a_hi <= block) provided by passing the zero-padded B band three
times with shifted index maps (previous / current / next block). Each tile is
a static double loop over (t) with a fused shift-multiply-accumulate into the
output band — one read of A and B, one write of C. The flattened operand
batch G rides the kernel grid (one ``pallas_call`` for the whole stack;
2-D inputs are treated as G = 1).

Out-of-range band entries are exact zeros on input (the ``repro.core.banded``
storage invariant), and the zero halo blocks extend that across tile edges,
so no masking is needed inside the kernel; the dispatch layer re-masks the
result band for belt and braces.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..masking import canonical_band
from .banded_matvec import I0

__all__ = ["band_matmul_pallas"]

DEF_BLOCK = 512


def _kernel(a_ref, bp_ref, bc_ref, bn_ref, o_ref, *, a_lo, a_hi, b_lo, b_hi,
            block):
    lo = a_lo + b_lo
    hi = a_hi + b_hi
    a = a_ref[...]  # (block, wa)
    bb = jnp.concatenate([bp_ref[...], bc_ref[...], bn_ref[...]], axis=0)
    acc = jnp.zeros((block, lo + hi + 1), a.dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
    for t in range(-a_lo, a_hi + 1):
        rows = bb[block + t : 2 * block + t]  # static shift
        a_col = a[:, a_lo + t][:, None]
        # C[i, lo + t + s] += A[i, i+t] * B[i+t, (i+t)+s], s in [-b_lo, b_hi];
        # lane-select instead of a lane-slice update (no scatter in Mosaic)
        for s in range(-b_lo, b_hi + 1):
            prod = a_col * rows[:, b_lo + s][:, None]
            acc = acc + jnp.where(lane == lo + t + s, prod, 0.0)
    o_ref[...] = acc


@functools.partial(jax.jit,
                   static_argnames=("a_lo", "a_hi", "b_lo", "b_hi", "block",
                                    "interpret"))
def band_matmul_pallas(a_band: jax.Array, b_band: jax.Array,
                       a_lo: int, a_hi: int, b_lo: int, b_hi: int,
                       block: int = DEF_BLOCK, *, interpret: bool,
                       n_active=None):
    """a_band: (G, n, a_lo+a_hi+1), b_band: (G, n, b_lo+b_hi+1) ->
    C band (G, n, a_lo+b_lo+a_hi+b_hi+1).

    ``n_active`` (traced): masked active length — both operands are
    canonicalized to identity tails, so the product is exactly
    ``blockdiag(C_active, I)``.
    """
    if n_active is not None:
        a_band = canonical_band(a_band, a_lo, a_hi, n_active)
        b_band = canonical_band(b_band, b_lo, b_hi, n_active)
    squeeze = a_band.ndim == 2
    if squeeze:
        a_band, b_band = a_band[None], b_band[None]
    G, n, wa = a_band.shape
    wb = b_band.shape[-1]
    assert wa == a_lo + a_hi + 1 and wb == b_lo + b_hi + 1
    assert max(a_lo, a_hi) <= block
    wc = wa + wb - 1
    dtype = jnp.result_type(a_band, b_band)
    npad = -(-n // block) * block
    a_p = jnp.zeros((G, npad, wa), dtype).at[:, :n].set(a_band.astype(dtype))
    b_p = jnp.zeros((G, npad, wb), dtype).at[:, :n].set(b_band.astype(dtype))
    zblk = jnp.zeros((G, block, wb), dtype)
    bz = jnp.concatenate([zblk, b_p, zblk], axis=1)
    grid = (G, npad // block)
    out = pl.pallas_call(
        functools.partial(_kernel, a_lo=a_lo, a_hi=a_hi, b_lo=b_lo, b_hi=b_hi,
                          block=block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block, wa), lambda g, i: (g, i, I0)),
            pl.BlockSpec((None, block, wb), lambda g, i: (g, i, I0)),      # prev
            pl.BlockSpec((None, block, wb), lambda g, i: (g, i + 1, I0)),  # cur
            pl.BlockSpec((None, block, wb), lambda g, i: (g, i + 2, I0)),  # next
        ],
        out_specs=pl.BlockSpec((None, block, wc), lambda g, i: (g, i, I0)),
        out_shape=jax.ShapeDtypeStruct((G, npad, wc), dtype),
        interpret=interpret,
    )(a_p, bz, bz, bz)
    out = out[:, :n]
    return out[0] if squeeze else out
