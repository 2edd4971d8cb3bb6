"""Coarse-level construction for the kernel-multigrid (KMG) preconditioner.

Each coarse level is a *sparse-GP view* of the fine additive system (Kernel
Multigrid, arXiv 2403.13300): a strided subset of the original points acts as
the inducing set, and because kernel packets make every one-dimensional prior
banded at any point set, the coarse prior is just a *smaller* banded KP
system built by the exact same row routines the fine fit (and the streaming
window rebuilds) already use — ``kernel_packets.kp_coefficient_rows`` /
``gram_band_rows`` at the subsampled coordinates.

A :class:`CoarseLevel` therefore carries:

  * a capacity-padded, mask-aware :class:`~repro.core.backfitting.DimOps`
    stack at the coarse size — coarse KP factors ``(A_c, Phi_c)`` with
    ``Khat_c^{-1} = P_c^T Phi_c^{-1} A_c P_c`` per dimension, plus the
    smoother system ``SAPhi = sigma_b^2 A_c + Phi_c`` whose per-dimension
    block solves run through the same kernel dispatch as the fine level
    (block cyclic reduction on the pallas backend);
  * the sparse prolongation operator in window form: per-dimension
    order-``(2q+1)`` Lagrange interpolation from coarse sorted coordinates
    to fine sorted coordinates, stored as a window start ``j0 (D, n)`` and
    weights ``W (D, n, npts)`` — restriction is its exact adjoint
    (``vcycle.restrict`` scatter-adds through the same maps);
  * the SPD-safe inverse Gram ``EG`` of the rank-D per-dimension-constant
    deflation basis (see ``vcycle`` — the directions backfitting stalls on).

The coarse *operator* the cycle inverts is deliberately NOT the rediscretized
additive system ``Khat_c^{-1} + sigma_c^{-2} S S^T`` (whose naive data term
badly overweights the coarse points): it is the *mixed* operator

    M_c = Khat_c^{-1} + sigma^{-2} P^T S S^T P

with the banded rediscretized prior but the data term applied exactly through
the fine grid (Galerkin on the data part; ``vcycle.coarse_matvec``). The
smoother noise level ``sigma_b^2 = 3 sigma^2 / (2 c)`` compensates the block
solve for the ~c-fold larger per-point data precision of the stride-``c``
subset.

Capacity padding: everything is allocated at the static coarse capacity
``ceil(capacity / stride)`` with the traced active count
``ceil(n_active / stride)``; the strided subset of an active prefix is again
a prefix, so the coarse system inherits the fine level's zero-recompilation
streaming property — inserts/evicts rebuild the hierarchy at fixed shapes.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ..core import matern as mk
from ..core.backfitting import DimOps
from ..core.banded import Banded, add, scale
from ..core.kernel_packets import gram_band_rows, kp_coefficient_rows
from ..core.ordering import argsort_rows, inverse_perm, separate_ties
from ..masking import mask_rows, tree_sum

__all__ = ["CoarseLevel", "build_hierarchy", "coarse_capacity",
           "interp_order"]

# Span-relative tie separation for coarse sorted coordinates — same constant
# and placement as the fine fit's bump (additive_gp.TIE_EPS), so a coarse
# subset of tied points stays strictly sorted for the KP construction.
_TIE_EPS = 1e-9


def interp_order(q: int) -> int:
    """Prolongation polynomial order 2q+1: matches the Matérn-(q+1/2) sample
    smoothness (piecewise-linear for q=0, cubic for q=1) so interpolated
    coarse corrections carry finite energy in the fine prior norm."""
    return 2 * q + 1


def coarse_capacity(capacity: int, stride: int) -> int:
    """Static coarse allocation size for a strided subset: ceil(cap/stride)."""
    return -(-capacity // stride)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("ops", "j0", "W", "EG"),
    meta_fields=("stride", "npts"),
)
@dataclasses.dataclass(frozen=True)
class CoarseLevel:
    """One level of the KMG hierarchy (see module docstring).

    ops:    coarse-capacity DimOps — KP factors, smoother band (sigma_b^2),
            sorted/rank permutations, traced coarse active count.
    j0:     (D, n_fine) int32 window starts into coarse *sorted* order.
    W:      (D, n_fine, npts) Lagrange prolongation weights.
    EG:     (D, D) SPD-safe inverse Gram of the per-dim-constant deflation
            basis under the mixed coarse operator.
    stride: static subsampling stride relative to the FINE level.
    npts:   static interpolation window size (interp_order(q) + 1).
    """

    ops: DimOps
    j0: jax.Array
    W: jax.Array
    EG: jax.Array
    stride: int
    npts: int

    @property
    def nc(self) -> int:
        """Static coarse capacity."""
        return self.ops.n


def _coarse_sorted(Xc_t: jax.Array, nc_active):
    """Per-dim masked sort of the coarse subset coordinates.

    ``Xc_t`` (D, nc) may hold garbage in slots >= nc_active (gathered from
    the fine capacity tail). Inactive slots are overwritten with a strictly
    increasing sequence above every active value, so a single stable argsort
    yields active coordinates ascending followed by an identity tail —
    exactly the canonical permutation layout the mask-aware ops expect.
    Exact ties among active points get the fit's span-relative bump.
    """
    D, nc = Xc_t.shape
    j = jnp.arange(nc)
    if nc_active is None:
        act = jnp.ones((nc,), bool)
        na = nc
    else:
        na = nc_active
        act = j < na
    hi = jnp.max(jnp.where(act, Xc_t, -jnp.inf), axis=1, keepdims=True)
    lo = jnp.min(jnp.where(act, Xc_t, jnp.inf), axis=1, keepdims=True)
    span = hi - lo + 1.0
    fill = hi + span * (j[None, :] - na + 1.0)
    xc = jnp.where(act[None, :], Xc_t, fill)
    sort_idx = argsort_rows(xc)
    xs_c = jnp.take_along_axis(xc, sort_idx, axis=1)
    rank_idx = inverse_perm(sort_idx)
    xs_c = separate_ties(xs_c, span * _TIE_EPS)
    return xs_c, sort_idx, rank_idx


def _interp_maps(xs_f: jax.Array, xs_c: jax.Array, nc_active, npts: int):
    """Window starts + Lagrange weights, coarse sorted -> fine sorted.

    ``xs_c`` is the canonical coarse sorted array from ``_coarse_sorted``
    (active ascending, strictly increasing finite tail above all active
    values), so a plain ``searchsorted`` over the full capacity equals the
    masked active-prefix bracket for every real fine coordinate. Windows are
    clamped inside the active prefix (``[0, nc_active - npts]``); fine rows
    past the fine active count get finite placeholder weights that the
    state masks zero out downstream.
    """
    D, n = xs_f.shape
    nc = xs_c.shape[1]
    na = nc if nc_active is None else nc_active

    def per_dim(xf, xc):
        j = jnp.searchsorted(xc, xf, side="right").astype(jnp.int32) - 1
        s0 = jnp.clip(j - (npts // 2 - 1), 0,
                      jnp.maximum(na - npts, 0)).astype(jnp.int32)
        pts = xc[jnp.clip(s0[:, None] + jnp.arange(npts)[None, :], 0, nc - 1)]
        # Lagrange basis: W[i, a] = prod_{b != a} (xf_i - p_b) / (p_a - p_b)
        pd = pts[:, :, None] - pts[:, None, :]               # (n, npts, npts)
        eye = jnp.eye(npts, dtype=bool)
        denom = jnp.prod(jnp.where(eye, 1.0, pd), axis=2)    # (n, npts)
        xd = xf[:, None] - pts                               # (n, npts)
        numer = jnp.prod(jnp.where(eye[None], 1.0, xd[:, None, :]), axis=2)
        return s0, numer / denom

    j0, W = jax.vmap(per_dim)(xs_f, xs_c)
    return j0, W


def _deflation_gram(level: CoarseLevel, fine_ops: DimOps) -> jax.Array:
    """SPD-safe inverse Gram of the per-dim-constant basis under M_c.

    The basis E_k (k = 0..D-1) is the indicator of dimension k, constant 1
    over the active coarse rows. Its Gram ``E^T M_c E`` is assembled with
    fixed-association reductions, symmetrized, and eigenvalue-clamped to a
    positive floor — band-assembly noise (severe at q >= 1, where
    ``Khat^{-1}`` entries reach ~1e13) can make the raw Gram indefinite, and
    the clamp keeps the deflation a bounded SPD correction instead of a
    divergence.
    """
    from .vcycle import coarse_matvec  # deferred: vcycle imports this module

    D, nc = level.ops.D, level.ops.n
    dt = level.ops.Phi.data.dtype
    E = jnp.zeros((D, D, nc, 1), dt)
    E = E.at[jnp.arange(D), jnp.arange(D)].set(1.0)
    E = mask_rows(E, level.ops.n_active, axis=2)
    ME = jax.vmap(lambda e: coarse_matvec(level, fine_ops, e))(E)
    prod = E[:, None] * ME[None, :]                  # (D, D, D, nc, 1)
    EME = tree_sum(tree_sum(prod, axis=3), axis=2)[..., 0]
    EME = 0.5 * (EME + EME.T)
    lam, V = jnp.linalg.eigh(EME)
    floor = jnp.maximum(lam[-1], 1.0) * 1e-8
    lam = jnp.maximum(lam, floor)
    return (V / lam[None, :]) @ V.T


def _build_level(q: int, omega: jax.Array, sigma2, X: jax.Array,
                 xs_f: jax.Array, fine_ops: DimOps, stride: int) -> CoarseLevel:
    """One coarse level at ``stride`` (relative to the FINE level)."""
    capacity, D = X.shape
    nc = coarse_capacity(capacity, stride)
    na_f = fine_ops.n_active
    nc_active = None if na_f is None else (na_f + stride - 1) // stride
    # strided ORIGINAL-index inducing subset, shared across dimensions; the
    # strided subset of an active prefix is again a prefix (slot s is active
    # iff s * stride < n_active iff s < nc_active)
    Ic = jnp.arange(nc) * stride
    xs_c, sort_idx, rank_idx = _coarse_sorted(X[Ic].T, nc_active)

    rows = jnp.arange(nc)

    def per_dim(om, x):
        a_rows = kp_coefficient_rows(q, om, x, rows, n_active=nc_active)
        kfun = lambda a, b: mk.matern(q, om, a, b)
        phi_rows = gram_band_rows(kfun, x, a_rows, rows, q + 1, q + 1, q,
                                  n_active=nc_active)
        return a_rows, phi_rows

    a_data, phi_data = jax.vmap(per_dim)(omega, xs_c)
    A = Banded(a_data, q + 1, q + 1, nc_active).canonical()
    Phi = Banded(phi_data, q, q, nc_active).canonical()
    # smoother noise: each stride-c point stands in for ~c fine observations
    # (data precision ~c/sigma^2 per coarse point); 3/(2c) is the prototype's
    # calibration of the block smoother against the mixed operator
    sigma2_b = 3.0 * sigma2 / (2.0 * stride)
    SAPhi = add(scale(A, sigma2_b), Phi)
    ops_c = DimOps(A=A, Phi=Phi, SAPhi=SAPhi, sort_idx=sort_idx,
                   rank_idx=rank_idx, sigma2=sigma2_b, n_active=nc_active)

    npts = interp_order(q) + 1
    j0, W = _interp_maps(xs_f, xs_c, nc_active, npts)
    level = CoarseLevel(ops=ops_c, j0=j0, W=W,
                        EG=jnp.eye(D, dtype=W.dtype), stride=stride,
                        npts=npts)
    return dataclasses.replace(level, EG=_deflation_gram(level, fine_ops))


def build_hierarchy(q: int, omega: jax.Array, sigma2, X: jax.Array,
                    xs_f: jax.Array, fine_ops: DimOps, *, levels: int = 2,
                    coarsen: int = 8) -> tuple[CoarseLevel, ...]:
    """Build the coarse hierarchy for a fitted fine system.

    Level ``l`` (1-based) subsamples the original points at stride
    ``coarsen**l`` — nested subsets, each mapped *directly* to the fine grid
    (every level's transfer operators interpolate fine <-> that level, so
    the data term stays exactly Galerkin at every depth). ``levels`` counts
    the fine level: the default 2 is one coarse grid. Levels whose static
    coarse capacity falls below one interpolation window are dropped.

    All inputs may be capacity-padded (``fine_ops.n_active`` traced); the
    returned levels are shape-stable per (capacity, stride) and safe under
    jit/vmap (fleet stacking).
    """
    if levels < 2:
        return ()
    out = []
    npts = interp_order(q) + 1
    for lvl in range(1, levels):
        stride = coarsen ** lvl
        if coarse_capacity(X.shape[0], stride) < max(npts, 2 * q + 4):
            break
        out.append(_build_level(q, omega, sigma2, X, xs_f, fine_ops, stride))
    return tuple(out)
