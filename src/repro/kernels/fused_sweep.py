"""Pallas TPU kernel: one *fused* backfitting iteration per ``pallas_call``.

Every backfitting scheme in ``repro.core.backfitting`` iterates the same
per-dimension pipeline on the ``(D, n, B)`` state stack:

    sort-permute -> banded matvec -> banded solve -> rank-permute
                 -> sum-over-D / sigma^2 coupling

Unfused, each stage is its own dispatched op, so every iteration pays 4+
kernel launches and a full HBM round trip on the state between stages. The
kernels here run *one whole iteration* — all D dimensions, all stages — in a
single ``pallas_call``: the state stack, the banded factors and every
intermediate stay in VMEM, and the only HBM traffic per iteration is one read
and one write of the carried state.

Layout (one shared convention across the three kernels):

  * the (D,) dimension batch rides the kernel **grid** (as in ``block_cr``):
    one grid step per dimension, plus a leading *phase* axis for PCG, whose
    inner products need all-D barriers (grid = (3, D): apply / update /
    direction);
  * per-dimension operands (the banded factors, the sort/rank permutations,
    the per-dim slice of per-d outputs) are per-grid-step blocks; the state
    stack uses constant index maps, so it is fetched once, revisited in VMEM
    by every step, and written back once at the end;
  * cross-phase intermediates (PCG's ``A p``, ``z`` and the two reductions)
    live in VMEM scratch, which persists across grid steps;
  * the banded solve inside each step is the block cyclic reduction of
    ``block_cr.cr_solve_values`` (the PR-3 kernel body, reused verbatim), so
    the fused sweep inherits its log2-depth critical path and its block
    partial-pivot mode. A zero-halfwidth factor (Phi at q = 0) degenerates to
    an exact diagonal division.

Padding: rows are padded to ``npad`` (n rounded up to lcm of the solve block
sizes) so every CR solve sees whole blocks. Band tails are decoupled identity
rows, state tails are zero, permutation tails map to themselves — pad rows
stay exactly zero through gathers, matvecs and solves, so no masking is
needed anywhere in the kernels. Since PR 5 this identity-tail form is the
*core-wide* capacity representation (``repro.masking``): a traced
``n_active`` canonicalizes rows in ``[n_active, n)`` the same way, so one
static shape serves every active length and streaming insert/evict never
retraces.

VMEM residency per call (the ``fused_vmem_bytes`` estimate the "auto" fusion
mode checks): the carried state in and out plus the scratch intermediates —
``(3 + 3 + 2) * D * npad * B`` floats for PCG (3 for Jacobi/Gauss-Seidel) —
plus the three band stacks ``D * npad * (2w+1)`` and two int32 index stacks.
At f32 with ~16 MB of VMEM that caps a fused PCG call around
``n ~ 4e5 / (D * B)``; past the cap "auto" falls back to the unfused
dispatch path (``REPRO_FUSED_VMEM_CAP`` overrides).
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .block_cr import cr_solve_values
from ..masking import canonical_band, canonical_perm, mask_rows

__all__ = ["FusedSweep", "fused_vmem_bytes", "fused_jacobi_iter_pallas",
           "fused_gauss_seidel_iter_pallas", "fused_pcg_iter_pallas"]

# "auto" declines to fuse past this estimated per-call VMEM footprint
# (~TPU VMEM size; interpret mode has no hard limit but stays faithful).
VMEM_CAP_BYTES = int(os.environ.get("REPRO_FUSED_VMEM_CAP", 14 * 2**20))


def _pad_len(n: int, widths) -> int:
    """n rounded up so every solved band's w x w block view tiles evenly."""
    L = 1
    for w in widths:
        if w > 0:
            L = L * w // math.gcd(L, w)
    return -(-n // L) * L


def fused_vmem_bytes(n: int, D: int, B: int, widths, itemsize: int,
                     method: str = "pcg") -> int:
    """Estimated VMEM footprint of one fused-iteration call (see module doc).

    ``widths``: half-bandwidths of the factor stacks the sweep holds
    (A, Phi, SAPhi for PCG; Phi, SAPhi otherwise).
    """
    npad = _pad_len(n, widths)
    state_arrays = 8 if method == "pcg" else 3  # in + out + scratch stacks
    bands = sum(2 * w + 1 for w in widths)
    return D * npad * (state_arrays * B + bands) * itemsize + 2 * D * npad * 4


# ---------------------------------------------------------------------------
# in-kernel building blocks (plain values, VMEM-resident)
# ---------------------------------------------------------------------------


def _shift_rows(x, m):
    """x[i + m] along axis 0 with zero fill."""
    if m == 0:
        return x
    n = x.shape[0]
    pad = ((0, m),) if m > 0 else ((-m, 0),)
    x = jnp.pad(x, pad + ((0, 0),) * (x.ndim - 1))
    return x[m : m + n] if m > 0 else x[:n]


def _mv(band, x, w):
    """Banded matvec, same shift-multiply order as ``banded_matvec``'s kernel.

    band (npad, 2w+1) row-aligned; x (npad, B).
    """
    acc = jnp.zeros_like(x)
    for m in range(-w, w + 1):
        acc = acc + band[:, w + m][:, None] * _shift_rows(x, m)
    return acc


def _gather(x, idx):
    """x[idx] over rows: (npad, B) gathered by (npad,) int32 indices."""
    return jnp.take_along_axis(x, jnp.broadcast_to(idx[:, None], x.shape),
                               axis=0)


def _solve_sym(band, rhs, w, *, pivot):
    """Symmetric-bandwidth banded solve: block CR, or division when w == 0."""
    if w == 0:
        return rhs / band[:, :1]
    npad = band.shape[0]
    nb = npad // w
    steps = max(0, (nb - 1).bit_length())
    x, _ = cr_solve_values(band, rhs, w=w, nb=nb, steps=steps, pivot=pivot)
    return x


def _block_solve_dim(saphi, phi, sort_idx, rank_idx, s2, r, *, w_p, w_s,
                     pivot):
    """One dim's (Khat^{-1} + s^{-2} I)^{-1} r = s^2 P^T SAPhi^{-1} Phi P r."""
    rs = _gather(r, sort_idx)
    y = _mv(phi, rs, w_p)
    xw = s2 * _solve_sym(saphi, y, w_s, pivot=pivot)
    return _gather(xw, rank_idx)


def _dim(x, d):
    """Row d of a (D, ...) VMEM-resident value, d traced."""
    return jax.lax.dynamic_index_in_dim(x, d, axis=0, keepdims=False)


# ---------------------------------------------------------------------------
# damped block-Jacobi iteration: grid = (D,), one step per dimension
# ---------------------------------------------------------------------------


def _jacobi_kernel(sig_ref, v_ref, vt_ref, phi_ref, saphi_ref, sort_ref,
                   rank_ref, *refs, w_p, w_s, alpha, pivot,
                   want_resid=False):
    if want_resid:
        k_ref, out_ref, ko_ref, total_scr = refs
    else:
        out_ref, total_scr = refs
    d = pl.program_id(0)

    @pl.when(d == 0)
    def _():
        # the cross-dim sum is loop-invariant within a sweep: reduce once
        total_scr[...] = jnp.sum(vt_ref[...], axis=0)

    s2 = sig_ref[0, 0]
    vt_d = _dim(vt_ref[...], d)
    r = v_ref[...] - (total_scr[...] - vt_d) / s2
    new = _block_solve_dim(saphi_ref[...], phi_ref[...], sort_ref[0],
                           rank_ref[0], s2, r, w_p=w_p, w_s=w_s, pivot=pivot)
    out_ref[...] = (1.0 - alpha) * vt_d + alpha * new
    if want_resid:
        # carry k_d ~ Khat_d^{-1} x_d under the same damping: the block
        # solve guarantees Khat_d^{-1} new = r - new/s^2 exactly, so the
        # exit residual costs no extra matvec (see core/backfitting.py)
        ko_ref[...] = (1.0 - alpha) * k_ref[...] + alpha * (r - new / s2)


@functools.partial(jax.jit, static_argnames=("w_p", "w_s", "alpha", "pivot",
                                             "interpret", "want_resid"))
def fused_jacobi_iter_pallas(phi, saphi, sort_idx, rank_idx, sigma2, v, vt,
                             k=None, *, w_p: int, w_s: int, alpha: float,
                             pivot: bool = False, interpret: bool,
                             want_resid: bool = False):
    """One damped block-Jacobi sweep; all operands pre-padded (D, npad, ...).

    With ``want_resid`` the sweep also carries ``k`` (the damped running
    ``Khat_d^{-1} x_d`` stack) and returns ``(out, k_out)``; the x update is
    op-identical to the plain sweep.
    """
    D, npad, B = vt.shape
    dtype = vt.dtype
    per_d = pl.BlockSpec((None, npad, B), lambda d: (d, 0, 0))
    in_specs = [
        pl.BlockSpec((1, 1), lambda d: (0, 0)),
        per_d,
        pl.BlockSpec((D, npad, B), lambda d: (0, 0, 0)),
        pl.BlockSpec((None, npad, 2 * w_p + 1), lambda d: (d, 0, 0)),
        pl.BlockSpec((None, npad, 2 * w_s + 1), lambda d: (d, 0, 0)),
        pl.BlockSpec((1, npad), lambda d: (d, 0)),
        pl.BlockSpec((1, npad), lambda d: (d, 0)),
    ]
    operands = (sigma2, v, vt, phi, saphi, sort_idx, rank_idx)
    out_specs, out_shape = per_d, jax.ShapeDtypeStruct((D, npad, B), dtype)
    if want_resid:
        in_specs = in_specs + [per_d]
        operands = operands + (k,)
        out_specs = [per_d, per_d]
        out_shape = [out_shape, jax.ShapeDtypeStruct((D, npad, B), dtype)]
    return pl.pallas_call(
        functools.partial(_jacobi_kernel, w_p=w_p, w_s=w_s, alpha=alpha,
                          pivot=pivot, want_resid=want_resid),
        grid=(D,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((npad, B), dtype)],
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# Gauss-Seidel sweep (paper Alg 4): grid = (D,), running total in scratch
# ---------------------------------------------------------------------------


def _gs_kernel(sig_ref, v_ref, vt_ref, phi_ref, saphi_ref, sort_ref, rank_ref,
               out_ref, *refs, w_p, w_s, pivot, want_resid=False):
    if want_resid:
        ko_ref, total_scr = refs
    else:
        (total_scr,) = refs
    d = pl.program_id(0)

    @pl.when(d == 0)
    def _():
        out_ref[...] = vt_ref[...]
        total_scr[...] = jnp.sum(vt_ref[...], axis=0)

    s2 = sig_ref[0, 0]
    cur = out_ref[pl.ds(d, 1)][0]
    r = v_ref[...] - (total_scr[...] - cur) / s2
    new = _block_solve_dim(saphi_ref[...], phi_ref[...], sort_ref[0],
                           rank_ref[0], s2, r, w_p=w_p, w_s=w_s, pivot=pivot)
    # same update order as the unfused sweep: total - old + new
    total_scr[...] = total_scr[...] - cur + new
    out_ref[pl.ds(d, 1)] = new[None]
    if want_resid:
        # Khat_d^{-1} new = r - new/s^2 exactly (by the block solve), and a
        # GS exit residual only depends on the final sweep's values — so
        # return_info costs no extra matvec (see core/backfitting.py)
        ko_ref[...] = r - new / s2


@functools.partial(jax.jit, static_argnames=("w_p", "w_s", "pivot",
                                             "interpret", "want_resid"))
def fused_gauss_seidel_iter_pallas(phi, saphi, sort_idx, rank_idx, sigma2, v,
                                   vt, *, w_p: int, w_s: int,
                                   pivot: bool = False,
                                   interpret: bool,
                                   want_resid: bool = False):
    """One sequential-over-dims Gauss-Seidel sweep (pre-padded operands).

    With ``want_resid`` (the solve's *final* sweep) additionally returns the
    per-dim ``k_d = Khat_d^{-1} x_d`` stack: ``(out, k)``.
    """
    D, npad, B = vt.shape
    dtype = vt.dtype
    full = pl.BlockSpec((D, npad, B), lambda d: (0, 0, 0))
    per_d = pl.BlockSpec((None, npad, B), lambda d: (d, 0, 0))
    shape = jax.ShapeDtypeStruct((D, npad, B), dtype)
    return pl.pallas_call(
        functools.partial(_gs_kernel, w_p=w_p, w_s=w_s, pivot=pivot,
                          want_resid=want_resid),
        grid=(D,),
        in_specs=[
            pl.BlockSpec((1, 1), lambda d: (0, 0)),
            per_d,
            full,
            pl.BlockSpec((None, npad, 2 * w_p + 1), lambda d: (d, 0, 0)),
            pl.BlockSpec((None, npad, 2 * w_s + 1), lambda d: (d, 0, 0)),
            pl.BlockSpec((1, npad), lambda d: (d, 0)),
            pl.BlockSpec((1, npad), lambda d: (d, 0)),
        ],
        out_specs=[full, per_d] if want_resid else full,
        out_shape=[shape, shape] if want_resid else shape,
        scratch_shapes=[pltpu.VMEM((npad, B), dtype)],
        interpret=interpret,
    )(sigma2, v, vt, phi, saphi, sort_idx, rank_idx)


# ---------------------------------------------------------------------------
# PCG iteration: grid = (3, D) — phase 0 applies Mhat, phase 1 updates x/r
# and preconditions, phase 2 forms the new direction. The two inner products
# are all-D barriers, hence the phase axis; ap/z and the reductions persist
# in scratch between phases.
# ---------------------------------------------------------------------------


def _pcg_kernel(sig_ref, rz_ref, x_ref, r_ref, p_ref, a_ref, phi_ref,
                saphi_ref, sort_ref, rank_ref, xo_ref, ro_ref, po_ref,
                rzo_ref, ap_scr, z_scr, red_scr, tp_scr, *, w_a, w_p, w_s,
                pivot):
    ph = pl.program_id(0)
    d = pl.program_id(1)
    s2 = sig_ref[0, 0]
    sort_d = sort_ref[0]
    rank_d = rank_ref[0]

    @pl.when(ph == 0)
    def _():
        @pl.when(d == 0)
        def _():
            # loop-invariant within the phase: reduce the p stack once
            tp_scr[...] = jnp.sum(p_ref[...], axis=0)

        # ap_d = Khat_d^{-1} p_d + (sum_d' p_d') / s^2   (mhat_matvec)
        us = _gather(_dim(p_ref[...], d), sort_d)
        y = _mv(a_ref[...], us, w_a)
        wv = _solve_sym(phi_ref[...], y, w_p, pivot=pivot)
        ap_scr[pl.ds(d, 1)] = (_gather(wv, rank_d) + tp_scr[...] / s2)[None]

    @pl.when(ph == 1)
    def _():
        @pl.when(d == 0)
        def _():
            red_scr[0:1, :] = jnp.sum(p_ref[...] * ap_scr[...],
                                      axis=(0, 1))[None]

        rz = rz_ref[0]
        denom = red_scr[0]
        alpha = (rz / jnp.where(denom == 0, 1.0, denom))[None, :]
        ap_d = ap_scr[pl.ds(d, 1)][0]
        xo_ref[pl.ds(d, 1)] = (x_ref[...] + alpha * _dim(p_ref[...], d))[None]
        rn = r_ref[...] - alpha * ap_d
        ro_ref[pl.ds(d, 1)] = rn[None]
        z_scr[pl.ds(d, 1)] = _block_solve_dim(
            saphi_ref[...], phi_ref[...], sort_d, rank_d, s2, rn, w_p=w_p,
            w_s=w_s, pivot=pivot)[None]

    @pl.when(ph == 2)
    def _():
        @pl.when(d == 0)
        def _():
            rz_new = jnp.sum(ro_ref[...] * z_scr[...], axis=(0, 1))
            red_scr[1:2, :] = rz_new[None]
            rzo_ref[0:1, :] = rz_new[None]

        rz = rz_ref[0]
        beta = (red_scr[1] / jnp.where(rz == 0, 1.0, rz))[None, :]
        po_ref[pl.ds(d, 1)] = (z_scr[pl.ds(d, 1)][0]
                               + beta * _dim(p_ref[...], d))[None]


@functools.partial(jax.jit, static_argnames=("w_a", "w_p", "w_s", "pivot",
                                             "interpret"))
def fused_pcg_iter_pallas(a, phi, saphi, sort_idx, rank_idx, sigma2, x, r, p,
                          rz, *, w_a: int, w_p: int, w_s: int,
                          pivot: bool = False, interpret: bool):
    """One PCG iteration on Mhat; returns ``(x, r, p, rz)`` updated.

    All array operands pre-padded (D, npad, ...); ``rz`` is the carried
    ``r^T z`` inner product, shape (1, B).
    """
    D, npad, B = x.shape
    dtype = x.dtype
    per_d = lambda w: pl.BlockSpec((None, npad, 2 * w + 1),
                                   lambda ph, d: (d, 0, 0))
    full = pl.BlockSpec((D, npad, B), lambda ph, d: (0, 0, 0))
    xo, ro, po, rzo = pl.pallas_call(
        functools.partial(_pcg_kernel, w_a=w_a, w_p=w_p, w_s=w_s, pivot=pivot),
        grid=(3, D),
        in_specs=[
            pl.BlockSpec((1, 1), lambda ph, d: (0, 0)),
            pl.BlockSpec((1, B), lambda ph, d: (0, 0)),
            pl.BlockSpec((None, npad, B), lambda ph, d: (d, 0, 0)),
            pl.BlockSpec((None, npad, B), lambda ph, d: (d, 0, 0)),
            full,
            per_d(w_a),
            per_d(w_p),
            per_d(w_s),
            pl.BlockSpec((1, npad), lambda ph, d: (d, 0)),
            pl.BlockSpec((1, npad), lambda ph, d: (d, 0)),
        ],
        out_specs=[full, full, full,
                   pl.BlockSpec((1, B), lambda ph, d: (0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((D, npad, B), dtype),
            jax.ShapeDtypeStruct((D, npad, B), dtype),
            jax.ShapeDtypeStruct((D, npad, B), dtype),
            jax.ShapeDtypeStruct((1, B), dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((D, npad, B), dtype),  # A p
            pltpu.VMEM((D, npad, B), dtype),  # z = M_pre^{-1} r
            pltpu.VMEM((2, B), dtype),        # [denom, rz_new] reductions
            pltpu.VMEM((npad, B), dtype),     # sum-over-D of p (coupling)
        ],
        interpret=interpret,
    )(sigma2, rz, x, r, p, a, phi, saphi, sort_idx, rank_idx)
    return xo, ro, po, rzo


# ---------------------------------------------------------------------------
# trace-time container: pads the factor stack once per solve
# ---------------------------------------------------------------------------


class FusedSweep:
    """Padded factor stack + static meta for the fused-iteration kernels.

    Built once at trace time by the backfitting solvers (padding is hoisted
    out of the iteration loop); the iteration methods then map 1:1 onto one
    ``pallas_call`` each. ``a`` may be None for methods that never apply
    ``Khat^{-1}`` (Jacobi / Gauss-Seidel).

    ``n_active`` (traced, optional) is the capacity-padded masked length
    (``repro.masking``): rows in ``[n_active, n)`` are canonicalized
    to the same identity-tail form the lcm padding below applies to rows in
    ``[n, npad)`` — the kernel sees one uninterrupted decoupled tail.
    """

    def __init__(self, phi, saphi, sort_idx, rank_idx, sigma2, *, w_p: int,
                 w_s: int, a=None, w_a: int = 0, pivot: bool = False,
                 interpret: bool, dtype=None, n_active=None):
        D, n = sort_idx.shape
        self.D, self.n = D, n
        self.w_a, self.w_p, self.w_s = w_a, w_p, w_s
        self.pivot, self.interpret = pivot, interpret
        self.n_active = n_active
        self.npad = _pad_len(n, (w_p, w_s))
        # the solve's compute dtype — may be wider than the factor dtype
        # (mixed-dtype RHS); everything in the kernel runs in it
        self.dtype = saphi.dtype if dtype is None else jnp.dtype(dtype)
        self.phi = self._pad_band(phi, w_p)
        self.saphi = self._pad_band(saphi, w_s)
        self.a = None if a is None else self._pad_band(a, w_a)
        self.sort_idx = self._pad_idx(sort_idx)
        self.rank_idx = self._pad_idx(rank_idx)
        self.sigma2 = jnp.asarray(sigma2, self.dtype).reshape(1, 1)

    def _pad_band(self, data, w):
        """Identity tail: decoupled pad rows (unit diagonal, zero couplings)."""
        D, n, npad = self.D, self.n, self.npad
        data = canonical_band(data, w, w, self.n_active)
        out = jnp.zeros((D, npad, 2 * w + 1), self.dtype).at[:, :, w].set(1.0)
        return out.at[:, :n].set(data.astype(self.dtype))

    def _pad_idx(self, idx):
        D, n, npad = self.D, self.n, self.npad
        idx = canonical_perm(idx, self.n_active)
        tail = jnp.broadcast_to(jnp.arange(n, npad, dtype=jnp.int32),
                                (D, npad - n))
        return jnp.concatenate([idx.astype(jnp.int32), tail], axis=1)

    def pad_state(self, u):
        """(D, n, B) -> (D, npad, B) with a zero tail."""
        D, npad = self.D, self.npad
        u = mask_rows(u, self.n_active, axis=1)
        out = jnp.zeros((D, npad) + u.shape[2:], self.dtype)
        return out.at[:, : self.n].set(u.astype(self.dtype))

    def unpad(self, u):
        return u[:, : self.n]

    def jacobi_iter(self, v, vt, alpha: float, k=None):
        """One sweep; pass ``k`` to also carry the residual stack (out, k)."""
        return fused_jacobi_iter_pallas(
            self.phi, self.saphi, self.sort_idx, self.rank_idx, self.sigma2,
            v, vt, k, w_p=self.w_p, w_s=self.w_s, alpha=alpha,
            pivot=self.pivot, interpret=self.interpret,
            want_resid=k is not None)

    def gauss_seidel_iter(self, v, vt, want_resid: bool = False):
        return fused_gauss_seidel_iter_pallas(
            self.phi, self.saphi, self.sort_idx, self.rank_idx, self.sigma2,
            v, vt, w_p=self.w_p, w_s=self.w_s, pivot=self.pivot,
            interpret=self.interpret, want_resid=want_resid)

    def pcg_iter(self, x, r, p, rz):
        assert self.a is not None, "PCG needs the A factor stack"
        return fused_pcg_iter_pallas(
            self.a, self.phi, self.saphi, self.sort_idx, self.rank_idx,
            self.sigma2, x, r, p, rz, w_a=self.w_a, w_p=self.w_p,
            w_s=self.w_s, pivot=self.pivot, interpret=self.interpret)
