"""Pallas TPU kernel: block cyclic-reduction banded solve + log-determinant.

Solves any symmetric bandwidth ``lo = hi = w``, including the scalar
tridiagonal case w = 1 (the KP Gram systems: every factor the GP core solves against has this shape
by construction). The band is viewed as a block-tridiagonal system of
``w x w`` blocks

    A_i x_{i-1} + B_i x_i + C_i x_{i+1} = r_i,      i = 0..nb-1,

and eliminated by even/odd block cyclic reduction: at level ``k`` (stride
``s = 2^k``) every surviving even block row folds its two odd neighbours into
itself,

    B_i <- B_i - A_i B_{i-s}^{-1} C_{i-s} - C_i B_{i+s}^{-1} A_{i+s}
    r_i <- r_i - A_i B_{i-s}^{-1} r_{i-s} - C_i B_{i+s}^{-1} r_{i+s}
    A_i <- -A_i B_{i-s}^{-1} A_{i-2s -> i},   C_i <- -C_i B_{i+s}^{-1} C_{i+2s -> i}

so after ``ceil(log2(nb))`` fully vectorized levels only block row 0 remains;
back substitution replays the levels in reverse, also vectorized. Eliminated
rows are frozen in place, which makes the log-determinant exact and free:
each level is a Schur complement against the block diagonal of the odd rows,
so ``log|det M| = sum_i log|det B_i^frozen|`` (pad blocks are identity and
contribute 0).

Per-level work is O(nb w^3) in batched ``w x w`` solves that ride the VPU
lanes — every sequential dependency of the row-by-row LU kernel is gone. The
``w x w`` block solves run a statically unrolled Gaussian elimination with an
optional partial-pivot mode (``pivot=True``): row swaps *inside* a block are
local, so — unlike the banded LU, whose pivoting grows the U bandwidth and
serializes — pivoted block-CR keeps the same data layout and step count.
This is the first Pallas path for ``pivot=True`` solves/logdets.

The (D,)-dimension batch of the additive GP is folded into the kernel grid
(one grid step per batch element) instead of the trace-time unroll used by
the other kernels — one ``pallas_call``, D grid steps.

Whole system lives in VMEM per grid step — the band (n, 2w+1), the RHS
(n, B) and the 3 w^2-per-block working triples, ~n(3w + B + 1) floats at
once — so a single f32 call caps out around n ~ 4e6/(3w + B) (larger n:
the blocked host-level fallback in
``repro.core.banded``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..masking import canonical_band, mask_rows

__all__ = ["cr_solve_values", "block_cr_pallas", "block_cr_solve_pallas",
           "block_cr_logdet_pallas"]


def _nbr(x, d):
    """x[i+d] along axis 0 with zero fill (block-row neighbour gather)."""
    n = x.shape[0]
    if d == 0:
        return x
    pad = ((0, d),) if d > 0 else ((-d, 0),)
    x = jnp.pad(x, pad + ((0, 0),) * (x.ndim - 1))
    return x[d : d + n] if d > 0 else x[:n]


def _small_solve(M, R, *, pivot):
    """Batched dense solve of (nb, w, w) against (nb, w, m), unrolled over w.

    Gaussian elimination with optional partial pivoting (the ``pivot=True``
    block mode); every step is a masked elementwise update batched over the
    block axis. Returns (X, log|det M| per block).
    """
    w = M.shape[-1]
    rows = jnp.arange(w)
    A = jnp.concatenate([M, R], axis=-1)  # (nb, w, w+m) augmented
    ld = jnp.zeros(M.shape[:-2], M.dtype)
    for t in range(w):
        if pivot and t < w - 1:
            col = jnp.where(rows >= t, jnp.abs(A[..., :, t]), -1.0)
            p = jnp.argmax(col, axis=-1)  # (nb,) pivot row >= t
            src = jnp.where(rows == t, p[..., None],
                            jnp.where(rows == p[..., None], t, rows))
            A = jnp.take_along_axis(A, src[..., None], axis=-2)
        piv = A[..., t, t]
        ld = ld + jnp.log(jnp.abs(piv))
        safe = jnp.where(piv == 0, 1.0, piv)
        f = jnp.where(rows > t, A[..., :, t] / safe[..., None], 0.0)
        A = A - f[..., None] * A[..., t : t + 1, :]
    X = jnp.zeros_like(R)
    for t in range(w - 1, -1, -1):
        acc = A[..., t, w:]
        for u in range(t + 1, w):
            acc = acc - A[..., t, u][..., None] * X[..., u, :]
        piv = A[..., t, t]
        X = X.at[..., t, :].set(acc / jnp.where(piv == 0, 1.0, piv)[..., None])
    return X, ld


def _band_to_blocks(data, w, nb):
    """(nb*w, 2w+1) row-aligned band -> block-tridiag triples (nb, w, w).

    Block I row r is band row i = I*w + r; its column ``j`` of block I+d
    holds M[i, (I+d)*w + j] = data[i, w + d*w + j - r] (zero outside the
    band). Purely static gathers — w is a compile-time constant.
    """
    blk = data.reshape(nb, w, 2 * w + 1)
    dtype = data.dtype

    def tri(off):
        out_rows = []
        for r in range(w):
            cols = []
            for c in range(w):
                j = off + c - r
                if 0 <= j <= 2 * w:
                    cols.append(blk[:, r, j])
                else:
                    cols.append(jnp.zeros((nb,), dtype))
            out_rows.append(jnp.stack(cols, axis=-1))
        return jnp.stack(out_rows, axis=-2)  # (nb, w, w)

    return tri(0), tri(w), tri(2 * w)


def cr_solve_values(data, rhs, *, w, nb, steps, pivot, solve=True):
    """Block cyclic reduction on plain values (no refs) — the kernel body.

    ``data``: (nb*w, 2w+1) row-aligned band, identity-padded past the real
    rows; ``rhs``: (nb*w, B). Returns ``(x (nb*w, B), logdet scalar)``.
    Shared by the ``block_cr`` kernel and the fused backfitting-sweep kernel
    (``fused_sweep.py``), which runs this elimination on VMEM-resident
    intermediates instead of dispatched operands.
    """
    B = rhs.shape[-1]
    dtype = data.dtype
    Ab, Bb, Cb = _band_to_blocks(data, w, nb)
    R = rhs.reshape(nb, w, B)
    idx = jnp.arange(nb)
    eye = jnp.broadcast_to(jnp.eye(w, dtype=dtype), (nb, w, w))

    # --- reduction: level k folds odd rows (stride s) into even rows --------
    for k in range(steps):
        s = 1 << k
        active = (idx % s) == 0
        even = active & ((idx // s) % 2 == 0)
        Binv, _ = _small_solve(Bb, eye, pivot=pivot)
        alpha = -jnp.einsum("nij,njk->nik", Ab, _nbr(Binv, -s))
        beta = -jnp.einsum("nij,njk->nik", Cb, _nbr(Binv, s))
        m = even[:, None, None]
        Bb = jnp.where(m, Bb + jnp.einsum("nij,njk->nik", alpha, _nbr(Cb, -s))
                       + jnp.einsum("nij,njk->nik", beta, _nbr(Ab, s)), Bb)
        R = jnp.where(m, R + jnp.einsum("nij,njk->nik", alpha, _nbr(R, -s))
                      + jnp.einsum("nij,njk->nik", beta, _nbr(R, s)), R)
        Ab = jnp.where(m, jnp.einsum("nij,njk->nik", alpha, _nbr(Ab, -s)), Ab)
        Cb = jnp.where(m, jnp.einsum("nij,njk->nik", beta, _nbr(Cb, s)), Cb)

    # Every row now holds its elimination-level (frozen) blocks; row 0 holds
    # the fully reduced system. det(M) telescopes over the Schur complements:
    X0, ld_all = _small_solve(Bb, R, pivot=pivot)
    ld = jnp.sum(ld_all)

    if not solve:
        return jnp.zeros((nb * w, B), dtype), ld

    x = jnp.where(idx[:, None, None] == 0, X0, jnp.zeros_like(X0))
    # --- back substitution: replay levels in reverse, all rows vectorized ---
    for k in range(steps - 1, -1, -1):
        s = 1 << k
        active = (idx % s) == 0
        odd = active & ((idx // s) % 2 == 1)
        rhs_k = (R - jnp.einsum("nij,njk->nik", Ab, _nbr(x, -s))
                 - jnp.einsum("nij,njk->nik", Cb, _nbr(x, s)))
        Xk, _ = _small_solve(Bb, rhs_k, pivot=pivot)
        x = jnp.where(odd[:, None, None], Xk, x)
    return x.reshape(nb * w, B), ld


def _kernel(band_ref, rhs_ref, x_ref, ld_ref, *, w, nb, steps, pivot, solve):
    x, ld = cr_solve_values(band_ref[0], rhs_ref[0], w=w, nb=nb, steps=steps,
                            pivot=pivot, solve=solve)
    x_ref[0] = x
    ld_ref[...] = jnp.full(ld_ref.shape, ld, ld_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("w", "pivot", "interpret", "solve"))
def block_cr_pallas(band: jax.Array, rhs: jax.Array, w: int,
                    pivot: bool = False, *, interpret: bool,
                    solve: bool = True, n_active=None):
    """band: (G, n, 2w+1) row-aligned, lo = hi = w; rhs: (G, n, B).

    Returns (x (G, n, B), logdet (G,)). The leading G axis is the kernel
    grid — one grid step per batch element (the GP's (D,) factor batch rides
    here instead of a trace-time unrolled loop). 2-D inputs are treated as
    G = 1. ``pivot=True`` enables partial pivoting inside the w x w block
    solves (robust to dead scalar pivots; blocks must stay nonsingular).
    ``solve=False`` skips the back substitution (logdet-only; x is zeros).
    ``n_active`` (traced) is the masked active length: rows past it become
    the same decoupled identity rows the lcm padding below uses, so the
    kernel's log2-depth elimination is exact on the active prefix — this is
    the capacity-padded representation of ``repro.masking``, of which
    the block padding here is the kernel-local special case.
    """
    if n_active is not None:
        band = canonical_band(band, w, w, n_active)
        rhs = mask_rows(rhs, n_active, axis=-2)
    squeeze = band.ndim == 2
    if squeeze:
        band, rhs = band[None], rhs[None]
    G, n, width = band.shape
    assert width == 2 * w + 1, (band.shape, w)
    B = rhs.shape[-1]
    dtype = jnp.result_type(band, rhs)
    nb = max(1, -(-n // w))
    npad = nb * w
    steps = max(0, (nb - 1).bit_length())
    # pad rows are decoupled identity rows: diag 1, off-band 0 (det factor 1)
    band_p = jnp.zeros((G, npad, width), dtype).at[:, :, w].set(1.0)
    band_p = band_p.at[:, :n].set(band.astype(dtype))
    rhs_p = jnp.zeros((G, npad, B), dtype).at[:, :n].set(rhs.astype(dtype))
    x, ld = pl.pallas_call(
        functools.partial(_kernel, w=w, nb=nb, steps=steps, pivot=pivot,
                          solve=solve),
        grid=(G,),
        in_specs=[
            pl.BlockSpec((1, npad, width), lambda g: (g, 0, 0)),
            pl.BlockSpec((1, npad, B), lambda g: (g, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, npad, B), lambda g: (g, 0, 0)),
            # (1, 1) trailing block == full trailing dims: the (8, 128)
            # tiling rule holds for the per-item logdet
            pl.BlockSpec((None, 1, 1), lambda g: (g, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((G, npad, B), dtype),
            jax.ShapeDtypeStruct((G, 1, 1), dtype),
        ],
        interpret=interpret,
    )(band_p, rhs_p)
    x, ld = x[:, :n], ld[:, 0, 0]
    return (x[0], ld[0]) if squeeze else (x, ld)


def block_cr_solve_pallas(band, rhs, w: int, pivot: bool = False,
                          *, interpret: bool, n_active=None):
    """Solve M x = rhs by block cyclic reduction; rhs (G, n, B) or (n, B)."""
    x, _ = block_cr_pallas(band, rhs, w, pivot=pivot, interpret=interpret,
                           n_active=n_active)
    return x


def block_cr_logdet_pallas(band, w: int, pivot: bool = False,
                           *, interpret: bool, n_active=None):
    """log|det M| from the same elimination (width-1 dummy RHS, no back-sub)."""
    n = band.shape[-2]
    dummy = jnp.zeros(band.shape[:-2] + (n, 1), band.dtype)
    _, ld = block_cr_pallas(band, dummy, w, pivot=pivot, interpret=interpret,
                            solve=False, n_active=n_active)
    return ld
