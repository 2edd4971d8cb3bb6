"""Pure-JAX block cyclic-reduction banded solve.

The pallas block-CR kernel (``block_cr.py``) needs a compiled pallas
backend, and has no 64-bit form on a TPU; the "jax" backend's scan-LU is
O(n) *sequential* steps, each a handful of tiny operations, which makes
any narrow multi-RHS solve (the windowed Gband maintenance of
``core/gband_update.py``, the float64 PCG of the serving path) cost like
n dependent device operations.

This module is the log-depth alternative for the ``lo == hi = w`` systems,
and the jax backend's unpivoted solve above ``ops.CR_MIN_BLOCK_ROWS``
block rows (``kernels/ops.banded_solve``): the same even/odd block cyclic
reduction as the pallas kernel, in two forms of one arithmetic:

  * **compacted** (``pivot=True``) — each level keeps only the surviving
    even block rows, so array extents halve per level and the total work
    is a geometric series ~ 2x the first level; every level is unrolled,
    with shapes of its own;
  * **rolled** (``pivot=False``) — one reduction step and one
    back-substitution step run in loops over full-length arrays with the
    block index on the last axis, masked to the level's even (odd) rows.
    More work per level, but one copy of the level code: in float64 on a
    TPU every unrolled level's fusions are megabytes of machine code: a
    compacted (10, 4096, 32) tridiagonal solve compiles to a 33 MB TPU v5e
    executable, the rolled one to 5 MB, and each program holding a solve
    loads that code before it runs;
  * **batched** — arbitrary leading batch dims ride every operation, so the
    (D,) factor batch and a vmapped (T,) fleet axis need no grid/loop;
  * **batch-invariant** — block products use the unrolled
    fixed-association loop (``_bmm``, the ``band_inverse._mm`` idiom) and
    the w x w block solves are masked elementwise Gaussian elimination
    (``block_cr._small_solve``, and ``_lsolve`` in the rolled layout), so
    results are bitwise identical at every batch width — the fleet
    bit-identity contract of the mutation path holds through these solves.

Depth is ceil(log2(n/w)) vectorized levels each way (reduction + back
substitution) instead of n scan steps.

Pivoting (``pivot=True``) is partial pivoting *inside* each w x w block —
the same robustness class as the RGF block sweep and the pivoted pallas
block-CR kernel; the block diagonal must stay nonsingular, which the
capacity-padded canonical KP systems guarantee (identity pads, Gram-based
active blocks).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .block_cr import _small_solve

__all__ = ["block_cr_solve_jax"]


def _bmm(a: jax.Array, b: jax.Array) -> jax.Array:
    """(..., m, k) @ (..., k, p) with a fixed-association unrolled k-loop."""
    k = a.shape[-1]
    out = a[..., :, 0:1] * b[..., 0:1, :]
    for t in range(1, k):
        out = out + a[..., :, t : t + 1] * b[..., t : t + 1, :]
    return out


def _band_to_blocks(data: jax.Array, w: int, nb: int):
    """(..., nb*w, 2w+1) row-aligned band -> block-tridiag (A, B, C) triples.

    Block row I, local row r is band row i = I*w + r; its column c of block
    I+d sits at band offset d*w + c - r. Static gathers (w compile-time).
    """
    blk = data.reshape(data.shape[:-2] + (nb, w, 2 * w + 1))
    zero = jnp.zeros(data.shape[:-2] + (nb,), data.dtype)

    def tri(off):
        rows = []
        for r in range(w):
            cols = []
            for c in range(w):
                j = off + c - r
                cols.append(blk[..., :, r, j] if 0 <= j <= 2 * w else zero)
            rows.append(jnp.stack(cols, axis=-1))
        return jnp.stack(rows, axis=-2)  # (..., nb, w, w)

    return tri(0), tri(w), tri(2 * w)


def _inv(M: jax.Array, pivot: bool) -> jax.Array:
    eye = jnp.broadcast_to(jnp.eye(M.shape[-1], dtype=M.dtype), M.shape)
    X, _ = _small_solve(M, eye, pivot=pivot)
    return X


def _solve(M: jax.Array, R: jax.Array, pivot: bool) -> jax.Array:
    X, _ = _small_solve(M, R, pivot=pivot)
    return X


def block_cr_solve_jax(band: jax.Array, rhs: jax.Array, w: int,
                       pivot: bool = True) -> jax.Array:
    """Solve M x = rhs for a row-aligned band with ``lo = hi = w``.

    ``band``: (..., n, 2w+1); ``rhs``: (..., n, B). Returns (..., n, B).
    Exact direct solve (no truncation); log2-depth vectorized levels.
    ``pivot=False`` runs the levels rolled into loops (``_solve_rolled``)
    with the compacted levels' arithmetic (bitwise equal to them on the
    CPU for w <= 2).
    """
    if not pivot:
        return _solve_rolled(band, rhs, w)
    return _solve_compacted(band, rhs, w, pivot)


def _pad_system(band: jax.Array, rhs: jax.Array, w: int):
    """Identity-pad the band to whole blocks (zero RHS tail); returns the
    padded band, RHS and the block count."""
    n = band.shape[-2]
    B = rhs.shape[-1]
    nb = max(1, -(-n // w))
    dtype = jnp.result_type(band, rhs)
    batch = band.shape[:-2]
    band_p = jnp.zeros(batch + (nb * w, 2 * w + 1), dtype)
    band_p = band_p.at[..., :, w].set(1.0).at[..., :n, :].set(band)
    rhs_p = jnp.zeros(batch + (nb * w, B), dtype).at[..., :n, :].set(rhs)
    return band_p, rhs_p, nb


# --- rolled levels, block index last ---------------------------------------
#
# Even rows (index a multiple of 2s) take the update, their odd neighbours
# sit s rows away, the rest keep their values. A row is odd at exactly one
# level and never updated after it, so the arrays end up holding each odd
# row's data as the compacted path freezes it.


def _lbmm(a: jax.Array, b: jax.Array) -> jax.Array:
    """``_bmm`` on (..., m, k, nb) x (..., k, p, nb) blocks."""
    k = a.shape[-2]
    out = a[..., :, 0:1, :] * b[..., 0:1, :, :]
    for t in range(1, k):
        out = out + a[..., :, t : t + 1, :] * b[..., t : t + 1, :, :]
    return out


def _lsolve(M: jax.Array, R: jax.Array) -> jax.Array:
    """``_small_solve(M, R, pivot=False)`` on (..., w, w, nb) x
    (..., w, m, nb) blocks: Gaussian elimination unrolled over w."""
    w = M.shape[-3]
    rows = [jnp.concatenate([M[..., r, :, :], R[..., r, :, :]], axis=-2)
            for r in range(w)]  # augmented rows (..., w + m, nb)
    for t in range(w):
        piv = rows[t][..., t, :]
        safe = jnp.where(piv == 0, 1.0, piv)
        for r in range(t + 1, w):
            f = rows[r][..., t, :] / safe
            rows[r] = rows[r] - f[..., None, :] * rows[t]
    X = [None] * w
    for t in range(w - 1, -1, -1):
        acc = rows[t][..., w:, :]
        for u in range(t + 1, w):
            acc = acc - rows[t][..., u : u + 1, :] * X[u]
        piv = rows[t][..., t, :]
        X[t] = acc / jnp.where(piv == 0, 1.0, piv)[..., None, :]
    return jnp.stack(X, axis=-3)


def _solve_rolled(band: jax.Array, rhs: jax.Array, w: int) -> jax.Array:
    n = band.shape[-2]
    band_p, rhs_p, nb = _pad_system(band, rhs, w)
    batch, B = band_p.shape[:-2], rhs.shape[-1]
    A, Bb, C = (jnp.moveaxis(t, -3, -1)
                for t in _band_to_blocks(band_p, w, nb))  # (..., w, w, nb)
    R = jnp.moveaxis(rhs_p.reshape(batch + (nb, w, B)), -3, -1)
    L = (nb - 1).bit_length()  # levels, as the compacted path counts them
    pad = 1 << max(L - 1, 0)  # the widest neighbour distance
    i = jnp.arange(nb)
    eye = jnp.eye(w, dtype=A.dtype)[..., None]

    def neighbours(X, s):
        """X at block rows i - s and i + s, zero outside [0, nb)."""
        z = jnp.zeros(X.shape[:-1] + (pad,), X.dtype)
        Xp = jnp.concatenate([z, X, z], axis=-1)
        return (lax.dynamic_slice_in_dim(Xp, pad - s, nb, axis=-1),
                lax.dynamic_slice_in_dim(Xp, pad + s, nb, axis=-1))

    def reduce(k, st):
        A, Bb, C, R = st
        s = jnp.left_shift(1, k)
        even = (i & (2 * s - 1)) == 0
        Bi_lo, Bi_up = neighbours(_lsolve(Bb, jnp.broadcast_to(eye, Bb.shape)),
                                  s)
        Bi_lo = jnp.where(i >= s, Bi_lo, eye)
        Bi_up = jnp.where(i + s < nb, Bi_up, eye)
        A_lo, A_up = neighbours(A, s)
        C_lo, C_up = neighbours(C, s)
        R_lo, R_up = neighbours(R, s)
        alpha = -_lbmm(A, Bi_lo)
        beta = -_lbmm(C, Bi_up)
        new = (_lbmm(alpha, A_lo),
               Bb + _lbmm(alpha, C_lo) + _lbmm(beta, A_up),
               _lbmm(beta, C_up),
               R + _lbmm(alpha, R_lo) + _lbmm(beta, R_up))
        return tuple(jnp.where(even, u, v) for u, v in zip(new, st))

    A, Bb, C, R = lax.fori_loop(0, L, reduce, (A, Bb, C, R))
    x0 = _lsolve(Bb[..., :1], R[..., :1])
    x = jnp.concatenate([x0, jnp.zeros(x0.shape[:-1] + (nb - 1,), x0.dtype)],
                        axis=-1)

    def back(j, x):
        s = jnp.left_shift(1, L - 1 - j)
        odd = (i & (2 * s - 1)) == s
        x_lo, x_up = neighbours(x, s)
        xo = _lsolve(Bb, R - _lbmm(A, x_lo) - _lbmm(C, x_up))
        return jnp.where(odd, xo, x)

    x = lax.fori_loop(0, L, back, x)
    x = jnp.moveaxis(x, -1, -3).reshape(batch + (nb * w, B))
    return x[..., :n, :]


# --- compacted levels, unrolled ---------------------------------------------


def _solve_compacted(band: jax.Array, rhs: jax.Array, w: int,
                     pivot: bool) -> jax.Array:
    n = band.shape[-2]
    B = rhs.shape[-1]
    # decoupled identity pad rows; zero RHS tail
    band_p, rhs_p, nb = _pad_system(band, rhs, w)
    npad = nb * w
    dtype = band_p.dtype
    batch = band.shape[:-2]

    A, Bb, C = _band_to_blocks(band_p, w, nb)
    R = rhs_p.reshape(batch + (nb, w, B))

    ident1 = jnp.broadcast_to(jnp.eye(w, dtype=dtype), batch + (1, w, w))
    zeroA = jnp.zeros(batch + (1, w, w), dtype)
    zeroR = jnp.zeros(batch + (1, w, B), dtype)

    # --- reduction: compact to the even block rows, level by level ---------
    levels = []  # per-level frozen odd data for back substitution
    while nb > 1:
        Ae, Be, Ce, Re = (A[..., 0::2, :, :], Bb[..., 0::2, :, :],
                          C[..., 0::2, :, :], R[..., 0::2, :, :])
        Ao, Bo, Co, Ro = (A[..., 1::2, :, :], Bb[..., 1::2, :, :],
                          C[..., 1::2, :, :], R[..., 1::2, :, :])
        ne = Ae.shape[-3]
        levels.append((Ao, Bo, Co, Ro, nb))
        # odd neighbours of even row m: odd m-1 (below, padded index m) and
        # odd m (above, padded index m+1); identity/zero pads make the
        # missing boundary neighbours no-ops (the corresponding A_e[0] /
        # C_e[ne-1] couplings are zero anyway)
        Bi = jnp.concatenate([ident1, _inv(Bo, pivot), ident1], axis=-3)
        Ap = jnp.concatenate([zeroA, Ao, zeroA], axis=-3)
        Cp = jnp.concatenate([zeroA, Co, zeroA], axis=-3)
        Rp = jnp.concatenate([zeroR, Ro, zeroR], axis=-3)
        lo = slice(0, ne)
        up = slice(1, ne + 1)
        alpha = -_bmm(Ae, Bi[..., lo, :, :])
        beta = -_bmm(Ce, Bi[..., up, :, :])
        Bb = Be + _bmm(alpha, Cp[..., lo, :, :]) + _bmm(beta, Ap[..., up, :, :])
        R = Re + _bmm(alpha, Rp[..., lo, :, :]) + _bmm(beta, Rp[..., up, :, :])
        A = _bmm(alpha, Ap[..., lo, :, :])
        C = _bmm(beta, Cp[..., up, :, :])
        nb = ne

    x = _solve(Bb, R, pivot)  # (..., 1, w, B)

    # --- back substitution: replay the levels in reverse -------------------
    for Ao, Bo, Co, Ro, nb in reversed(levels):
        no = Ao.shape[-3]
        ne = nb - no
        # even neighbours of odd row m: even m (below) and even m+1 (above)
        x_up = jnp.concatenate([x, zeroR], axis=-3)[..., 1 : no + 1, :, :]
        x_lo = x[..., :no, :, :]
        xo = _solve(Bo, Ro - _bmm(Ao, x_lo) - _bmm(Co, x_up), pivot)
        full = jnp.zeros(x.shape[:-3] + (nb, w, B), dtype)
        x = full.at[..., 0::2, :, :].set(x).at[..., 1::2, :, :].set(xo)

    return x.reshape(batch + (npad, B))[..., :n, :]
