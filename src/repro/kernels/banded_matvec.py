"""Pallas TPU kernel: banded matrix-vector/multi-vector product.

y[i] = sum_{m=-lo..hi} band[i, lo+m] * x[i+m]

This is the innermost O(n) op of every backfitting sweep, power iteration and
Hutchinson probe (paper Algs 4/6/7/8) — memory-bound, so the kernel tiles rows
into VMEM blocks and streams the band. The off-tile halo (|m| <= lo/hi <= 8)
is handled by passing x three times with shifted index maps (previous /
current / next block), avoiding overlapping BlockSpecs.

Layout: band (G, n, w), x (G, n, B) — the RHS batch dim B rides along the
VPU lanes and the flattened operand batch G rides the kernel grid (one
``pallas_call`` for the whole stack, as in ``block_cr``; 2-D inputs are
treated as G = 1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..masking import canonical_band, mask_rows

__all__ = ["banded_matvec_pallas"]

DEF_BLOCK = 512
# int32 block index for Pallas index maps (shared by the kernels of this
# package): under x64 a literal 0 in an index map is int64, which Mosaic
# refuses
I0 = np.int32(0)


def _kernel(band_ref, xp_ref, xc_ref, xn_ref, o_ref, *, lo, hi, block):
    band = band_ref[...]  # (block, w)
    xx = jnp.concatenate([xp_ref[...], xc_ref[...], xn_ref[...]], axis=0)
    # xx: (3*block, B); row i of this tile reads xx[block + i + m]
    acc = jnp.zeros_like(o_ref)
    for m in range(-lo, hi + 1):
        seg = xx[block + m : 2 * block + m]  # static shift: Mosaic-lowerable
        acc = acc + band[:, lo + m][:, None] * seg
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("lo", "hi", "block", "interpret"))
def banded_matvec_pallas(band: jax.Array, x: jax.Array, lo: int, hi: int,
                         block: int = DEF_BLOCK, *, interpret: bool,
                         n_active=None):
    """band: (G, n, lo+hi+1); x: (G, n, B) -> (G, n, B). n padded to `block`.

    ``n_active`` (traced): masked active length — rows >= n_active are
    canonicalized (identity band rows, zero x rows) instead of trusting the
    caller's padding, so the kernel's result is exact on the active prefix.
    """
    if n_active is not None:
        band = canonical_band(band, lo, hi, n_active)
        x = mask_rows(x, n_active, axis=-2)
    squeeze = band.ndim == 2
    if squeeze:
        band, x = band[None], x[None]
    G, n, w = band.shape
    assert w == lo + hi + 1
    B = x.shape[-1]
    # promote like the jax scan path (band * x), so mixed-dtype operands
    # store cleanly into the output ref
    dtype = jnp.result_type(band, x)
    npad = -(-n // block) * block
    band_p = jnp.zeros((G, npad, w), dtype).at[:, :n].set(band.astype(dtype))
    x_p = jnp.zeros((G, npad, B), dtype).at[:, :n].set(x.astype(dtype))
    grid = (G, npad // block)

    # zero the wrap-around contributions: the halo tiles past either edge are
    # explicit zero blocks appended front/back, and the shifted index maps
    # (i / i+1 / i+2 into the extended array) select prev/cur/next.
    zblk = jnp.zeros((G, block, B), dtype)
    xz = jnp.concatenate([zblk, x_p, zblk], axis=1)

    out = pl.pallas_call(
        functools.partial(_kernel, lo=lo, hi=hi, block=block),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, block, w), lambda g, i: (g, i, I0)),
            pl.BlockSpec((None, block, B), lambda g, i: (g, i, I0)),      # prev
            pl.BlockSpec((None, block, B), lambda g, i: (g, i + 1, I0)),  # cur
            pl.BlockSpec((None, block, B), lambda g, i: (g, i + 2, I0)),  # next
        ],
        out_specs=pl.BlockSpec((None, block, B), lambda g, i: (g, i, I0)),
        out_shape=jax.ShapeDtypeStruct((G, npad, B), dtype),
        interpret=interpret,
    )(band_p, xz, xz, xz)
    out = out[:, :n]
    return out[0] if squeeze else out
