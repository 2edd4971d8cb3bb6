"""Pallas TPU kernel: banded LU solve (forward/backward) + log-determinant.

One kernel runs the no-pivot banded LU forward elimination and back
substitution with the whole system resident in VMEM: U rows and
forward-substituted right-hand sides live in
scratch refs, and the row recurrences run as ``fori_loop``s over ``pl.ds``
dynamic slices. The elimination is sequential by nature (each U row feeds the
next ``lo`` rows); the per-row work is a static ``lo x (hi+1)`` update that
vectorizes over the RHS batch riding the lanes.

The same elimination yields ``log|det| = sum_i log|U[i, 0]|``, so the kernel
emits both the solution and the log-determinant; the ``ops`` dispatch layer
exposes them as separate entry points (``banded_solve`` discards the logdet,
``banded_logdet`` passes a width-1 dummy RHS and discards the solution).

The flattened operand batch G rides the kernel grid (one ``pallas_call`` for
the whole factor stack, as in ``block_cr``; 2-D inputs are treated as G = 1).
The VMEM scratch is reused across grid steps — each step fully rewrites the
regions it reads, so no cross-step state leaks.

No pivoting: callers needing the pivoted path route to the pure-jax scan in
``repro.core.banded`` (see ``repro/kernels/README.md`` dispatch rules).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..masking import canonical_band, mask_rows

__all__ = ["banded_lu_pallas", "banded_solve_pallas", "banded_logdet_pallas"]


def _kernel(band_ref, rhs_ref, x_ref, ld_ref, u_ref, y_ref, xp_ref,
            *, lo, hi, n, solve):
    wu = hi + 1
    B = rhs_ref.shape[1]
    dtype = rhs_ref.dtype

    # --- forward elimination ------------------------------------------------
    # u_ref row (i + lo) holds U row i; rows 0..lo-1 are identity padding so
    # the first rows eliminate against well-defined (no-op) pivots.
    if lo > 0:
        u_ref[0:lo, :] = jnp.zeros((lo, wu), dtype).at[:, 0].set(1.0)
        y_ref[0:lo, :] = jnp.zeros((lo, B), dtype)

        def fwd(i, carry):
            w = band_ref[pl.ds(i, 1), :][0]     # (lo+hi+1,)
            y = rhs_ref[pl.ds(i, 1), :]         # (1, B)
            pu = u_ref[pl.ds(i, lo), :]         # U rows i-lo .. i-1
            py = y_ref[pl.ds(i, lo), :]
            for t in range(lo):
                f = w[t] / pu[t, 0]
                w = w.at[t : t + wu].add(-f * pu[t])
                y = y - f * py[t][None, :]
            u_ref[pl.ds(i + lo, 1), :] = w[lo : lo + wu][None]
            y_ref[pl.ds(i + lo, 1), :] = y
            return carry

        jax.lax.fori_loop(0, n, fwd, 0)
    else:
        u_ref[...] = band_ref[...]
        y_ref[...] = rhs_ref[...]

    ld = jnp.sum(jnp.log(jnp.abs(u_ref[lo : lo + n, 0:1])))
    ld_ref[...] = jnp.full(ld_ref.shape, ld, dtype)

    # --- back substitution (skipped for logdet-only calls) ------------------
    if not solve:
        x_ref[...] = jnp.zeros((n, B), dtype)
    elif hi == 0:
        x_ref[...] = y_ref[lo : lo + n, :] / u_ref[lo : lo + n, 0][:, None]
    else:
        xp_ref[...] = jnp.zeros((n + hi, B), dtype)

        def bwd(j, carry):
            i = n - 1 - j
            u_row = u_ref[pl.ds(i + lo, 1), :][0]  # (hi+1,)
            y = y_ref[pl.ds(i + lo, 1), :][0]      # (B,)
            xn = xp_ref[pl.ds(i + 1, hi), :]       # rows i+1 .. i+hi
            acc = y - jnp.sum(u_row[1:][:, None] * xn, axis=0)
            xp_ref[pl.ds(i, 1), :] = (acc / u_row[0])[None]
            return carry

        jax.lax.fori_loop(0, n, bwd, 0)
        x_ref[...] = xp_ref[0:n, :]


@functools.partial(jax.jit, static_argnames=("lo", "hi", "interpret", "solve"))
def banded_lu_pallas(band: jax.Array, rhs: jax.Array, lo: int, hi: int,
                     *, interpret: bool, solve: bool = True,
                     n_active=None):
    """band: (G, n, lo+hi+1) row-aligned; rhs: (G, n, B).
    Returns (x (G, n, B), logdet (G,)); 2-D inputs squeeze the G axis.

    No-pivot LU; requires a stably-factorizable band (e.g. the diagonally
    dominant KP systems). Whole system in VMEM — n bounded by ~VMEM size.
    ``solve=False`` skips the sequential back-substitution (logdet-only
    callers; x comes back zero-filled). ``n_active`` (traced) is the masked
    active length: rows past it are canonicalized to identity rows / zero
    RHS, so the elimination runs on ``blockdiag(M_active, I)`` — identity
    pivots, zero logdet contribution, zero solution tail.
    """
    if n_active is not None:
        band = canonical_band(band, lo, hi, n_active)
        rhs = mask_rows(rhs, n_active, axis=-2)
    squeeze = band.ndim == 2
    if squeeze:
        band, rhs = band[None], rhs[None]
    G, n, w = band.shape
    assert w == lo + hi + 1, (band.shape, lo, hi)
    B = rhs.shape[-1]
    dtype = jnp.result_type(band, rhs)
    x, ld = pl.pallas_call(
        functools.partial(_kernel, lo=lo, hi=hi, n=n, solve=solve),
        grid=(G,),
        in_specs=[
            pl.BlockSpec((None, n, w), lambda g: (g, 0, 0)),
            pl.BlockSpec((None, n, B), lambda g: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, n, B), lambda g: (g, 0, 0)),
            # (1, 1) trailing block == full trailing dims: the (8, 128)
            # tiling rule holds for the per-item logdet
            pl.BlockSpec((None, 1, 1), lambda g: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, n, B), dtype),
            jax.ShapeDtypeStruct((G, 1, 1), dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((n + lo, hi + 1), dtype),   # U rows (+ identity padding)
            pltpu.VMEM((n + lo, B), dtype),        # forward-substituted rhs
            pltpu.VMEM((n + max(hi, 1), B), dtype),  # back-sub workspace
        ],
        interpret=interpret,
    )(band.astype(dtype), rhs.astype(dtype))
    ld = ld[:, 0, 0]
    return (x[0], ld[0]) if squeeze else (x, ld)


def banded_solve_pallas(band, rhs, lo: int, hi: int, *, interpret: bool,
                        n_active=None):
    """Solve M x = rhs (no pivoting); rhs (G, n, B) or (n, B)."""
    x, _ = banded_lu_pallas(band, rhs, lo, hi, interpret=interpret,
                            n_active=n_active)
    return x


def banded_logdet_pallas(band, lo: int, hi: int, *, interpret: bool,
                         n_active=None):
    """log|det M| from the same elimination (width-1 dummy RHS, no back-sub)."""
    n = band.shape[-2]
    dummy = jnp.zeros(band.shape[:-2] + (n, 1), band.dtype)
    _, ld = banded_lu_pallas(band, dummy, lo, hi, interpret=interpret,
                             solve=False, n_active=n_active)
    return ld
