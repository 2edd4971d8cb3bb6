"""Pallas TPU kernel: fused KP Gram-band assembly (paper Algorithm 2, step
"Phi = A P^T K P" — without ever materializing K).

Phi[i, q + m] = sum_t A[i, lo_A + t] * matern(x_{i+m}, x_{i+t}),
               m in [-q, q], t in [-(q+1), q+1].

Each grid tile loads a row block of the A band plus the x halo (prev/cur/next
block trick), evaluates the closed-form Matérn kernel on the fly in VMEM, and
contracts the (wPhi x wA) window per row. Memory traffic: one read of A and
x, one write of Phi — vs. the naive path reading an (n x wA) gather of K.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.matern import _poly_coeffs
from .banded_matvec import I0

__all__ = ["kp_gram_pallas"]

DEF_BLOCK = 512


def _matern(q, omega, r):
    coeffs = _poly_coeffs(q)
    u = omega * r
    acc = jnp.zeros_like(u) + coeffs[q]
    for m in range(q - 1, -1, -1):
        acc = acc * (2.0 * u) + coeffs[m]
    return jnp.exp(-u) * acc


def _kernel(om_ref, a_ref, xp_ref, xc_ref, xn_ref, o_ref, *, q, block, n):
    lo = q + 1
    wA = 2 * q + 3
    omega = om_ref[0, 0]
    a = a_ref[...]  # (block, wA)
    xx = jnp.concatenate([xp_ref[...], xc_ref[...], xn_ref[...]], axis=0)[:, 0]
    i0 = pl.program_id(0) * block
    rows = i0 + jax.lax.iota(jnp.int32, block)
    acc = jnp.zeros((block, 2 * q + 1), a.dtype)
    lane = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
    for m in range(-q, q + 1):
        xm = xx[block + m : 2 * block + m]  # static shift
        row_m = jnp.zeros((block,), a.dtype)
        for t in range(-lo, lo + 1):
            xt = xx[block + t : 2 * block + t]
            kv = _matern(q, omega, jnp.abs(xm - xt))
            valid = ((rows + t) >= 0) & ((rows + t) < n)
            row_m = row_m + jnp.where(valid, a[:, lo + t] * kv, 0.0)
        valid_m = ((rows + m) >= 0) & ((rows + m) < n)
        col = jnp.where(valid_m, row_m, 0.0)[:, None]
        acc = jnp.where(lane == q + m, col, acc)
    o_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("q", "block", "interpret"))
def kp_gram_pallas(q: int, omega, xs: jax.Array, a_band: jax.Array,
                   block: int = DEF_BLOCK, *, interpret: bool):
    """xs: (n,) sorted; a_band: (n, 2q+3) -> Phi band (n, 2q+1)."""
    n = xs.shape[0]
    wA = 2 * q + 3
    npad = -(-n // block) * block
    a_p = jnp.zeros((npad, wA), a_band.dtype).at[:n].set(a_band)
    x_p = jnp.zeros((npad, 1), xs.dtype).at[:n, 0].set(xs)
    xz = jnp.concatenate([jnp.zeros((block, 1), xs.dtype), x_p,
                          jnp.zeros((block, 1), xs.dtype)], axis=0)
    grid = (npad // block,)
    om = jnp.asarray(omega, xs.dtype).reshape(1, 1)
    out = pl.pallas_call(
        functools.partial(_kernel, q=q, block=block, n=n),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (I0, I0)),
            pl.BlockSpec((block, wA), lambda i: (i, I0)),
            pl.BlockSpec((block, 1), lambda i: (i, I0)),
            pl.BlockSpec((block, 1), lambda i: (i + 1, I0)),
            pl.BlockSpec((block, 1), lambda i: (i + 2, I0)),
        ],
        out_specs=pl.BlockSpec((block, 2 * q + 1), lambda i: (i, I0)),
        out_shape=jax.ShapeDtypeStruct((npad, 2 * q + 1), a_band.dtype),
        interpret=interpret,
    )(om, a_p, xz, xz, xz)
    return out[:n]
