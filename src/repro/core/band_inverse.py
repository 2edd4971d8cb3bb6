"""Central band of the inverse of a banded matrix (paper Algorithm 5).

Computes the band of ``G = (A Phi^T)^{-1} = Phi^{-T} A^{-1}`` needed for the
posterior-variance middle term phi^T G phi (Eq. (25)).

TPU adaptation: instead of the paper's three-coupled-recurrence sweep we use
the RGF (recursive Green's function) block-tridiagonal algorithm — two
independent ``lax.scan``s (forward/backward Schur complements) plus a local
combine, which exposes more parallelism and is numerically equivalent.
``H = A Phi^T`` has half-bandwidth 2q+1; with block size w >= 2q+1 it is
block-tridiagonal, and the diagonal + first off-diagonal blocks of G cover
the full 2q+1 band required by Eq. (25) (the paper's text says nu+1/2 but its
own Eq. (25) consumes offsets up to 2*nu; we provide the full 2*nu band).

Complexity O(n * w^2) like the paper's Algorithm 5.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .. import obs
from ..masking import canonical_band
from .banded import (Banded, _solve_scan, band_band_matmul, mask_band,
                     transpose)

__all__ = ["inverse_band", "variance_band"]


def _mm(a: jax.Array, b: jax.Array) -> jax.Array:
    """(..., w, w) @ (..., w, w) with a fixed-association k-loop.

    ``@`` / ``einsum`` lower to dot_general, whose CPU tiling (and therefore
    accumulation order) varies with the surrounding batch width — the same
    block product then rounds differently inside a vmapped fleet stack than
    standalone. ``w`` is a small static bandwidth, so an unrolled
    multiply-accumulate loop costs the same and is bitwise batch-invariant.
    """
    w = a.shape[-1]
    out = a[..., :, 0:1] * b[..., 0:1, :]
    for k in range(1, w):
        out = out + a[..., :, k : k + 1] * b[..., k : k + 1, :]
    return out


def _block_solve(M: jax.Array, B: jax.Array) -> jax.Array:
    """Solve M X = B for dense (..., w, w) blocks via the banded scan LU.

    ``jnp.linalg.solve`` is a LAPACK custom call wrapped in shape-dependent
    XLA glue; the repo's scan-based pivoted LU compiles to a self-contained
    loop that rounds identically at every batch width. A w x w dense block
    is just a band of half-width w-1.
    """
    w = M.shape[-1]
    i = jnp.arange(w)[:, None]
    # zero-based arange + shift: lowers to a traced iota, so this helper can
    # run inside a pallas kernel body (nonzero-start jnp.arange materializes
    # a concrete array that pallas would reject as a captured constant).
    j = i + (jnp.arange(2 * w - 1) - (w - 1))[None, :]
    valid = (j >= 0) & (j < w)
    jc = jnp.clip(j, 0, w - 1)
    band = jnp.where(valid, jnp.take_along_axis(
        M, jnp.broadcast_to(jc, M.shape[:-2] + jc.shape), axis=-1), 0.0)
    return _solve_scan(Banded(band, w - 1, w - 1), B, pivot=True)


def _to_blocks(b: Banded, w: int):
    """Partition banded matrix into block-tridiagonal (D_j, U_j, L_j).

    Pads n up to a multiple of w with an identity tail (decoupled, so the
    leading principal inverse is unchanged).
    """
    n = b.n
    T = -(-n // w)
    npad = T * w
    dense_band = jnp.zeros((npad, b.lo + b.hi + 1), b.data.dtype)
    dense_band = dense_band.at[:n].set(b.data)
    # identity tail
    pad_rows = jnp.arange(npad) >= n
    dense_band = jnp.where(
        pad_rows[:, None],
        jnp.zeros_like(dense_band).at[:, b.lo].set(1.0),
        dense_band,
    )
    i = jnp.arange(npad)[:, None]
    m = jnp.arange(-b.lo, b.hi + 1)[None, :]
    j = i + m
    valid = (j >= 0) & (j < npad)
    jc = jnp.clip(j, 0, npad - 1)
    # scatter into dense blocks row by row: build (T, w, 3w) local strips
    strip = jnp.zeros((npad, 3 * w), b.data.dtype)
    # column offset within strip: j - (block_start - w) = j - (i//w)*w + w
    block_start = (i // w) * w
    off = jc - block_start + w
    ok = valid & (off >= 0) & (off < 3 * w)
    strip = strip.at[jnp.broadcast_to(i, off.shape), jnp.clip(off, 0, 3 * w - 1)].add(
        jnp.where(ok, dense_band, 0.0)
    )
    strip = strip.reshape(T, w, 3 * w)
    L = strip[:, :, 0:w]  # H_{j, j-1}
    Dg = strip[:, :, w : 2 * w]  # H_{j, j}
    U = strip[:, :, 2 * w : 3 * w]  # H_{j, j+1}
    return Dg, U, L, T, npad


def _rgf(Dg, U, L):
    """RGF: returns (Gd, Gu, Gl) = diagonal, upper, lower blocks of H^{-1}.

    Gu[j] = G_{j, j+1}, Gl[j] = G_{j+1, j} (last entries unused).
    """
    T, w, _ = Dg.shape
    eye = jnp.eye(w, dtype=Dg.dtype)

    # forward Schur: F_0 = D_0, F_j = D_j - L_j F_{j-1}^{-1} U_{j-1}
    def fwd(F_prev, inputs):
        D_j, U_prevj, L_j = inputs
        F_j = D_j - _mm(L_j, _block_solve(F_prev, U_prevj))
        return F_j, F_j

    U_shift = jnp.concatenate([jnp.zeros((1, w, w), Dg.dtype), U[:-1]], axis=0)
    _, F_rest = jax.lax.scan(fwd, Dg[0], (Dg[1:], U_shift[1:], L[1:]))
    F = jnp.concatenate([Dg[0][None], F_rest], axis=0)

    # backward Schur: W_{T-1} = D_{T-1}, W_j = D_j - U_j W_{j+1}^{-1} L_{j+1}
    def bwd(W_next, inputs):
        D_j, U_j, L_next = inputs
        W_j = D_j - _mm(U_j, _block_solve(W_next, L_next))
        return W_j, W_j

    L_shift = jnp.concatenate([L[1:], jnp.zeros((1, w, w), Dg.dtype)], axis=0)
    _, W_rest = jax.lax.scan(
        bwd, Dg[-1], (Dg[:-1], U[:-1], L_shift[:-1]), reverse=True
    )
    W = jnp.concatenate([W_rest, Dg[-1][None]], axis=0)

    # G_jj = (F_j + W_j - D_j)^{-1}
    Gd = _block_solve(F + W - Dg, jnp.broadcast_to(eye, Dg.shape))
    # G_{j, j+1} = -F_j^{-1} U_j G_{j+1, j+1}  (from block forward substitution)
    Gu = -_block_solve(F[:-1], _mm(U[:-1], Gd[1:]))
    # G_{j+1, j} = -W_{j+1}^{-1} L_{j+1} G_{jj}
    Gl = -_block_solve(W[1:], _mm(L[1:], Gd[:-1]))
    zpad = jnp.zeros((1, w, w), Dg.dtype)
    return Gd, jnp.concatenate([Gu, zpad]), jnp.concatenate([Gl, zpad])


def _blocks_to_band(Gd, Gu, Gl, n: int, hw: int) -> Banded:
    """Extract band (half-bw hw <= w) from block-tridiagonal blocks of G."""
    T, w, _ = Gd.shape
    npad = T * w
    rows = jnp.arange(npad)
    blk = rows // w
    r_in = rows % w
    m = jnp.arange(-hw, hw + 1)
    cols = rows[:, None] + m[None, :]
    cblk = cols // w
    c_in = cols % w
    same = cblk == blk[:, None]
    nxt = cblk == blk[:, None] + 1
    prv = cblk == blk[:, None] - 1
    cb = jnp.clip(c_in, 0, w - 1)
    vals = jnp.where(
        same,
        Gd[blk[:, None], r_in[:, None], cb],
        jnp.where(
            nxt,
            Gu[jnp.clip(blk[:, None], 0, T - 1), r_in[:, None], cb],
            jnp.where(
                prv,
                Gl[jnp.clip(blk[:, None] - 1, 0, T - 1), r_in[:, None], cb],
                0.0,
            ),
        ),
    )
    valid = (cols >= 0) & (cols < n)
    vals = jnp.where(valid, vals, 0.0)
    return Banded(vals[:n], hw, hw)


@partial(jax.jit, static_argnums=(1,))
def inverse_band_single(H: Banded, hw: int) -> Banded:
    """Band (half-bw hw) of H^{-1} for one banded matrix (lo == hi)."""
    w = max(max(H.lo, H.hi), hw, 1)
    Dg, U, L, T, npad = _to_blocks(H, w)
    Gd, Gu, Gl = _rgf(Dg, U, L)
    return _blocks_to_band(Gd, Gu, Gl, H.n, hw)


@obs.scope("band_inverse.rgf")
def inverse_band(H: Banded, hw: int, backend: str | None = None) -> Banded:
    """Band of H^{-1}; batched over leading dims of H.data.

    Capacity padding: when ``H.n_active`` is set the data is canonicalized
    to ``blockdiag(H_active, I)`` first, so the RGF sweep — a direct method —
    returns ``blockdiag(G_active, I)`` exactly: active band rows match the
    unpadded inverse and tail rows are identity rows.

    On the pallas backend the recurrences run on-chip
    (``kernels/rgf.py`` — one ``pallas_call`` for the whole batch, bit-
    identical to the scans here); on a TPU that cannot compile it yet this
    raises (``kernels.ops.require_lowered``). ``backend`` resolves like
    every dispatched op (``kernels.ops.resolve_backend``).
    """
    n_active = H.n_active
    if n_active is not None:
        H = H.canonical()
    from ..kernels import ops as _kops

    if _kops.resolve_backend(backend, H.data.dtype) == "pallas":
        from ..kernels.rgf import rgf_inverse_band

        _kops.require_lowered("rgf")
        out = rgf_inverse_band(H.data, H.lo, H.hi, hw,
                               interpret=_kops.interpret_kernels())
        return Banded(out, hw, hw, n_active)
    if H.data.ndim == 2:
        out_b = inverse_band_single(Banded(H.data, H.lo, H.hi), hw)
        return Banded(out_b.data, hw, hw, n_active)
    flat = H.data.reshape((-1,) + H.data.shape[-2:])
    out = jax.vmap(lambda d: inverse_band_single(Banded(d, H.lo, H.hi), hw).data)(flat)
    return Banded(out.reshape(H.data.shape[:-2] + out.shape[-2:]), hw, hw,
                  n_active)


def variance_band(A: Banded, Phi: Banded, backend: str | None = None,
                  *, return_h: bool = False):
    """Algorithm 5 entry point: the 2q+1 band of (A Phi^T)^{-1} = Phi^{-T} A^{-1}.

    ``return_h=True`` additionally returns the canonical band of
    ``H = A Phi^T`` itself — the cache carried on ``AdditiveGP.Hband`` that
    lets streaming mutations update the inverse band with the windowed
    Woodbury correction (``core/gband_update.py``) instead of re-running
    this sweep.
    """
    H = mask_band(band_band_matmul(A, transpose(Phi), backend=backend))
    hw = A.lo + Phi.lo  # 2q+1
    G = inverse_band(H, hw, backend=backend)
    if return_h:
        return G, H.canonical()
    return G
