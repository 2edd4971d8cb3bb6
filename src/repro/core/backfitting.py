"""Backfitting solvers for the additive-GP system (paper Algorithm 4).

All solvers apply ``[P Phi^{-1} A P^T + sigma^{-2} S S^T]^{-1}`` — i.e.
``Mhat^{-1} = [Khat^{-1} + sigma^{-2} S S^T]^{-1}`` — to batches of vectors.
Vectors are stacked ``(D, n, B)`` in *original* (unsorted) point order; the
per-dimension banded factors live in sorted order and are conjugated by the
sort permutations on the fly.

Three variants:
  * ``gauss_seidel`` — the paper's Algorithm 4 (sequential over dimensions).
  * ``jacobi``       — beyond-paper: all D one-dimensional solves in parallel
                       (damped); maps onto the ``model`` mesh axis.
  * ``pcg``          — beyond-paper: conjugate gradients preconditioned by the
                       block solve; fastest convergence per banded solve.

On the pallas backend each iteration can run as ONE fused ``pallas_call``
(``kernels/fused_sweep.py``): the permutation gathers, banded matvecs, the
block-CR solve and the sum-over-D coupling all stay in VMEM instead of
round-tripping the (D, n, B) state through HBM between 4+ dispatched ops.
One step further, the *whole solve* — warm-start residual, the convergence
loop with its on-chip tol check, and the exit diagnostics — can run as one
``pallas_call`` (``kernels/mega_solve.py``), collapsing O(iters) dispatches
per solve to exactly 1. ``SolveConfig.fused`` ("auto" | "on" | "whole" |
"off"; default auto prefers the whole-solve kernel on pallas when the VMEM
budget fits and the preconditioner is not kmg, then the per-iteration
kernel) selects among them; all paths are numerically interchangeable
(jacobi/gauss_seidel bit-level at f64 across the pallas variants, pcg to
convergence level).

``return_info=True`` residuals cost no extra matvec on any path: pcg
returns the recursively-updated ``r`` it already carries, and the
jacobi/gauss_seidel sweeps carry the per-dim block quantity
``k_d = Khat_d^{-1} x_d`` (exact by each block solve), from which
``v - k - (sum_d x_d)/sigma^2`` is the exit residual elementwise.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from .. import obs
from ..health.verdict import classify_solve
from ..masking import canonical_perm, mask_rows, tree_sum
from .banded import Banded, matvec, solve

__all__ = ["SolveConfig", "SolveInfo", "DimOps", "solve_mhat", "mhat_matvec"]


@partial(
    jax.tree_util.register_dataclass,
    data_fields=(),
    meta_fields=("method", "iters", "damping", "pivot", "tol", "backend",
                 "alg", "fused", "precond", "precond_levels",
                 "precond_coarsen", "precond_smooth"),
)
@dataclasses.dataclass(frozen=True)
class SolveConfig:
    method: str = "pcg"  # "gauss_seidel" | "jacobi" | "pcg"
    iters: int = 30
    damping: float = 0.0  # jacobi under-relaxation; 0 -> auto (1/D, provably safe)
    pivot: bool = False  # banded LU pivoting
    # pcg-only early exit: stop once sqrt(|rz_k| / |rz_0|) <= tol in the
    # preconditioned residual norm (jit-friendly bounded lax.while_loop,
    # evaluated on-chip under fused="whole"); 0 -> fixed iteration count.
    # gauss_seidel/jacobi always run `iters`.
    tol: float = 0.0
    backend: str = "auto"  # banded-algebra backend ("auto" | "jax" | "pallas")
    alg: str = "auto"  # pallas solve kernel ("auto" | "lu" | "cr")
    fused: str = "auto"  # fused kernels ("auto" | "on" | "whole" | "off")
    # pcg preconditioner: "none" (per-dim block solve) | "kmg" (kernel
    # multigrid V-cycle over a coarse hierarchy — requires the caller to
    # thread ``hier`` into solve_mhat) | "auto" (resolved at GP fit time
    # via kernels.ops.resolve_precond; at solve time, "auto" with no
    # hierarchy degrades to "none")
    precond: str = "none"
    precond_levels: int = 2  # hierarchy depth incl. the fine level
    precond_coarsen: int = 8  # subsampling stride per level
    precond_smooth: int = 1  # deflated block-Jacobi sweeps per coarse solve


class SolveInfo(NamedTuple):
    """Diagnostics from ``solve_mhat(..., return_info=True)``."""

    iters: jax.Array  # iterations executed (== cfg.iters unless tol fired)
    # active system size the solve ran over (== the static n when unpadded;
    # the traced active prefix length under capacity padding)
    n_active: jax.Array = None
    # L2 norm of the residual v - Mhat x at exit, over the active prefix
    # and all RHS columns (pcg: the recursively-updated r it already
    # carries; jacobi/gauss_seidel: composed elementwise from the final
    # sweep's carried Khat_d^{-1} x_d stack — no extra matvec; the explicit
    # matvec survives only for the degenerate iters == 0 solve)
    resid: jax.Array = None
    # L2 norm of the (masked) RHS v — the scale resid is judged against
    rhs: jax.Array = None
    # int32 health code from repro.health.verdict (OK | STALLED | DIVERGED
    # | NONFINITE), classified in-graph from resid/rhs/the state itself —
    # a few scalar reductions, free to materialize at the host boundary
    verdict: jax.Array = None


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("A", "Phi", "SAPhi", "sort_idx", "rank_idx", "sigma2",
                 "n_active"),
    meta_fields=(),
)
@dataclasses.dataclass(frozen=True)
class DimOps:
    """Stacked per-dimension banded factors + permutations.

    A, Phi:    Banded with data (D, n, w)
    SAPhi:     Banded sigma^2*A + Phi, data (D, n, w)
    sort_idx:  (D, n) int — xs[d] = X[sort_idx[d], d]
    rank_idx:  (D, n) int — inverse permutation
    sigma2:    scalar observation-noise variance
    n_active:  traced active length under capacity padding (None = all n
               rows are real points). The factor Bandeds carry the same
               value; here it canonicalizes the permutations (identity
               tails) and keeps solver state exactly zero past the prefix.
    """

    A: Banded
    Phi: Banded
    SAPhi: Banded
    sort_idx: jax.Array
    rank_idx: jax.Array
    sigma2: jax.Array
    n_active: jax.Array | None = None

    @property
    def D(self) -> int:
        return self.sort_idx.shape[0]

    @property
    def n(self) -> int:
        return self.sort_idx.shape[1]

    def to_sorted(self, u: jax.Array) -> jax.Array:
        """(D, n, B) original order -> sorted order per dim."""
        return _permute_rows(u, self.sort_idx, self.n_active)

    def from_sorted(self, u: jax.Array) -> jax.Array:
        return _permute_rows(u, self.rank_idx, self.n_active)

    def khat_inv_mv(self, u: jax.Array, pivot: bool = False,
                    backend: str | None = None,
                    alg: str | None = None) -> jax.Array:
        """Khat^{-1} u = P^T Phi^{-1} A P u (per dim), u: (D, n, B)."""
        us = self.to_sorted(u)
        w = solve(self.Phi, matvec(self.A, us, backend=backend), pivot=pivot,
                  backend=backend, alg=alg)
        return self.from_sorted(w)

    def khat_mv(self, u: jax.Array, pivot: bool = False,
                backend: str | None = None,
                alg: str | None = None) -> jax.Array:
        """Khat u = P^T A^{-1} Phi P u (per dim)."""
        us = self.to_sorted(u)
        w = solve(self.A, matvec(self.Phi, us, backend=backend), pivot=pivot,
                  backend=backend, alg=alg)
        return self.from_sorted(w)

    def block_solve(self, r: jax.Array, pivot: bool = False,
                    backend: str | None = None,
                    alg: str | None = None) -> jax.Array:
        """(Khat^{-1} + sigma^{-2} I)^{-1} r = sigma^2 P^T (s^2 A + Phi)^{-1} Phi P r."""
        rs = self.to_sorted(r)
        w = self.sigma2 * solve(self.SAPhi, matvec(self.Phi, rs, backend=backend),
                                pivot=pivot, backend=backend, alg=alg)
        return self.from_sorted(w)


def _permute_rows(u: jax.Array, idx: jax.Array, n_active) -> jax.Array:
    """``u[d, idx[d, i], ...]`` for (D, n) or (D, n, B) ``u``.

    The index keeps a size-1 trailing axis, so the gather moves whole rows:
    broadcasting it over ``B`` first makes an element gather, whose TPU
    compile grows with n (~16 s at n=16000, against 0.3 s for this form).
    Under capacity padding the gather uses canonical (identity-tail)
    permutations and re-zeros the tail, so poisoned pad slots in either the
    indices or the state can never leak into reductions.
    """
    idx = canonical_perm(idx, n_active)
    idx = idx[..., None] if u.ndim == 3 else idx
    return mask_rows(jnp.take_along_axis(u, idx, axis=1), n_active, axis=1)


def mhat_matvec(ops: DimOps, u: jax.Array, pivot: bool = False,
                backend: str | None = None,
                alg: str | None = None) -> jax.Array:
    """Mhat u = Khat^{-1} u + sigma^{-2} S S^T u; u: (D, n, B)."""
    # fixed-association sum over dims: keeps the matvec (and every Krylov
    # iterate built on it) bitwise batch-invariant — see masking.tree_sum
    ssT = tree_sum(u, axis=0)[None]
    return ops.khat_inv_mv(u, pivot=pivot, backend=backend,
                           alg=alg) + ssT / ops.sigma2


def _maybe_fused(ops: DimOps, v: jax.Array, cfg: SolveConfig):
    """Resolve ``cfg.fused`` against this solve; ``(mode, FusedSweep|None)``.

    Trace-time decision (shapes, backend and bandwidths are all static): the
    fused paths need the pallas backend and symmetric bandwidths on every
    factor, and "auto" additionally requires the state + factor stack to fit
    the chosen kernel's VMEM residency model — preferring the whole-solve
    mega-kernel, then the per-iteration sweep (see ``fused_sweep`` /
    ``mega_solve``). ``mode`` is "whole" | "iter" | "off"; the FusedSweep
    (the padded operand stack both kernel families run on) is None when off.
    """
    from ..kernels import ops as _kops
    from ..kernels.fused_sweep import FusedSweep

    need_a = cfg.method == "pcg"
    widths = ((ops.Phi.lo, ops.Phi.hi), (ops.SAPhi.lo, ops.SAPhi.hi))
    if need_a:
        widths = ((ops.A.lo, ops.A.hi),) + widths
    # the fused kernel solves via block CR only (w = 0 degenerates to
    # division); an explicit/process alg="lu" must keep the unfused path
    cr_ok = all(
        b.lo != b.hi or b.lo == 0
        or _kops.resolve_solve_alg(cfg.alg, b.lo, b.hi) == "cr"
        for b in (ops.Phi, ops.SAPhi))
    # v is already promoted to the compute dtype (solve_mhat entry), which
    # is what the fused kernel runs in — size the VMEM estimate by it
    mode = _kops.resolve_fused(cfg.fused, cfg.backend, widths=widths,
                               n=ops.n, D=ops.D, B=v.shape[-1],
                               itemsize=v.dtype.itemsize,
                               method=cfg.method, cr_ok=cr_ok,
                               precond=cfg.precond)
    if mode == "off":
        return "off", None
    return mode, FusedSweep(
        ops.Phi.data, ops.SAPhi.data, ops.sort_idx, ops.rank_idx, ops.sigma2,
        w_p=ops.Phi.lo, w_s=ops.SAPhi.lo,
        a=ops.A.data if need_a else None, w_a=ops.A.lo, pivot=cfg.pivot,
        interpret=_kops.interpret_kernels(), dtype=v.dtype,
        n_active=ops.n_active)


def _kinv0(ops: DimOps, x0: jax.Array, cfg: SolveConfig) -> jax.Array:
    """Khat^{-1} x0 from the factors in hand (warm-started jacobi carry).

    SAPhi = sigma^2 A + Phi, so P^T Phi^{-1} SAPhi P x0 =
    sigma^2 Khat^{-1} x0 + x0 — one banded matvec + solve, paid only on a
    warm-started jacobi solve that asks for diagnostics.
    """
    x0s = ops.to_sorted(x0)
    w = solve(ops.Phi, matvec(ops.SAPhi, x0s, backend=cfg.backend),
              pivot=cfg.pivot, backend=cfg.backend, alg=cfg.alg)
    return (ops.from_sorted(w) - x0) / ops.sigma2


def _resid_from_k(ops: DimOps, v: jax.Array, out: jax.Array,
                  k: jax.Array) -> jax.Array:
    """Exit-residual norm from the sweep's carried Khat_d^{-1} x_d stack.

    r = v - Mhat x = v - k - (sum_d x_d)/sigma^2 — elementwise only, no
    banded matvec (the PR-7 return_info extra-matvec note, resolved).
    """
    r = v - k - tree_sum(out, axis=0)[None] / ops.sigma2
    return jnp.sqrt(tree_sum(_det_dot(r, r), axis=0))


def _gauss_seidel(ops: DimOps, v: jax.Array, cfg: SolveConfig,
                  x0: jax.Array | None = None, want_resid: bool = False):
    """Algorithm 4: block Gauss-Seidel sweeps, sequential over dimensions.

    Returns ``(out, resid|None)``. A GS exit residual depends only on the
    final sweep's per-dim block solves, so ``want_resid`` instruments just
    that sweep (identical x ops) and composes the norm elementwise; resid is
    None when ``cfg.iters == 0`` (nothing swept — caller falls back to the
    explicit matvec).
    """
    D = ops.D
    vt = jnp.zeros_like(v) if x0 is None else x0
    want_resid = want_resid and cfg.iters > 0

    mode, fs = _maybe_fused(ops, v, cfg)
    if mode == "whole":
        from ..kernels.mega_solve import MegaSolve

        out, k = MegaSolve(fs).gauss_seidel(v, x0, iters=cfg.iters)
        if want_resid:
            return out, _resid_from_k(ops, v, out, k)
        return out, None
    if fs is not None:
        v_p = fs.pad_state(v)
        u = fs.pad_state(vt)
        sweeps = cfg.iters - 1 if want_resid else cfg.iters
        u = jax.lax.fori_loop(0, sweeps,
                              lambda _, u: fs.gauss_seidel_iter(v_p, u), u)
        if want_resid:
            u, k = fs.gauss_seidel_iter(v_p, u, want_resid=True)
            out = fs.unpad(u)
            return out, _resid_from_k(ops, v, out, fs.unpad(k))
        return fs.unpad(u), None

    def solve_one_dim(d, r_d):
        # single-dim block solve (r_d: (n, B))
        na = ops.n_active
        saphi = Banded(ops.SAPhi.data[d], ops.SAPhi.lo, ops.SAPhi.hi, na)
        phi = Banded(ops.Phi.data[d], ops.Phi.lo, ops.Phi.hi, na)
        idx = canonical_perm(ops.sort_idx[d], na)[:, None]
        rs = jnp.take_along_axis(r_d, idx, axis=0)
        w = ops.sigma2 * solve(saphi, matvec(phi, rs, backend=cfg.backend),
                               pivot=cfg.pivot, backend=cfg.backend,
                               alg=cfg.alg)
        ridx = canonical_perm(ops.rank_idx[d], na)[:, None]
        out = jnp.take_along_axis(w, ridx, axis=0)
        return mask_rows(out, na, axis=0)

    def sweep(vt, instrument=False):
        total = tree_sum(vt, axis=0)
        ks = []
        for d in range(D):
            r_d = v[d] - (total - vt[d]) / ops.sigma2
            new_d = solve_one_dim(d, r_d)
            total = total - vt[d] + new_d
            vt = vt.at[d].set(new_d)
            if instrument:
                # exact by the block solve: Khat_d^{-1} new_d = r_d - new_d/s^2
                ks.append(r_d - new_d / ops.sigma2)
        return (vt, jnp.stack(ks)) if instrument else vt

    sweeps = cfg.iters - 1 if want_resid else cfg.iters
    vt = jax.lax.fori_loop(0, sweeps, lambda _, u: sweep(u), vt)
    if want_resid:
        vt, k = sweep(vt, instrument=True)
        return vt, _resid_from_k(ops, v, vt, k)
    return vt, None


def _jacobi(ops: DimOps, v: jax.Array, cfg: SolveConfig,
            x0: jax.Array | None = None, want_resid: bool = False):
    """Damped block Jacobi: all D dims in parallel (one batched banded solve).

    The block-Jacobi iteration matrix for Mhat has eigenvalues in
    (-(D-1), 1]; damping alpha <= 2/D guarantees convergence — auto uses 1/D.

    Returns ``(out, resid|None)``. Unlike GS, the damped iterate mixes every
    sweep into the exit state, so ``want_resid`` carries the matching damped
    ``k ~ Khat^{-1} x`` stack through the whole loop (x ops unchanged);
    a warm start seeds it with ``_kinv0``.
    """
    vt = jnp.zeros_like(v) if x0 is None else x0
    alpha = cfg.damping if cfg.damping > 0 else 1.0 / ops.D
    want_resid = want_resid and cfg.iters > 0

    mode, fs = _maybe_fused(ops, v, cfg)
    if mode == "whole":
        from ..kernels.mega_solve import MegaSolve

        out, k = MegaSolve(fs).jacobi(v, x0, alpha=alpha, iters=cfg.iters)
        if want_resid:
            return out, _resid_from_k(ops, v, out, k)
        return out, None
    if fs is not None:
        v_p = fs.pad_state(v)
        if want_resid:
            k0 = jnp.zeros_like(v) if x0 is None else _kinv0(ops, x0, cfg)
            u, k = jax.lax.fori_loop(
                0, cfg.iters,
                lambda _, c: fs.jacobi_iter(v_p, c[0], alpha, c[1]),
                (fs.pad_state(vt), fs.pad_state(k0)))
            out = fs.unpad(u)
            return out, _resid_from_k(ops, v, out, fs.unpad(k))
        out = jax.lax.fori_loop(
            0, cfg.iters, lambda _, u: fs.jacobi_iter(v_p, u, alpha),
            fs.pad_state(vt))
        return fs.unpad(out), None

    def sweep(vt):
        total = tree_sum(vt, axis=0)[None]
        r = v - (total - vt) / ops.sigma2
        new = ops.block_solve(r, pivot=cfg.pivot, backend=cfg.backend,
                              alg=cfg.alg)
        return (1.0 - alpha) * vt + alpha * new, r, new

    if want_resid:
        k0 = jnp.zeros_like(v) if x0 is None else _kinv0(ops, x0, cfg)

        def sweep_k(_, carry):
            vt, k = carry
            vt, r, new = sweep(vt)
            return vt, (1.0 - alpha) * k + alpha * (r - new / ops.sigma2)

        vt, k = jax.lax.fori_loop(0, cfg.iters, sweep_k, (vt, k0))
        return vt, _resid_from_k(ops, v, vt, k)

    return jax.lax.fori_loop(0, cfg.iters, lambda _, u: sweep(u)[0],
                             vt), None


def _det_dot(a: jax.Array, b: jax.Array) -> jax.Array:
    """Per-column inner products <a, b> over the (D, n) axes of (D, n, B)
    states, with fixed-association reductions (bitwise batch-invariant)."""
    return tree_sum(tree_sum(a * b, axis=1), axis=0)


def _pcg(ops: DimOps, v: jax.Array, cfg: SolveConfig,
         x0: jax.Array | None = None, hier=None):
    """Preconditioned CG on the SPD system Mhat x = v.

    The preconditioner is the per-dim block solve (``cfg.precond ==
    "none"``) or the kernel-multigrid V-cycle over ``hier``
    (``cfg.precond == "kmg"`` — see :mod:`repro.precond`). Returns
    ``(x, iters_used, resid)``. With ``cfg.tol > 0`` the loop is a bounded
    ``lax.while_loop`` that exits once every RHS column satisfies
    ``sqrt(|rz_k| / |rz_0|) <= tol`` (rz = r^T M_pre^{-1} r, the quantity
    PCG already carries — no extra reductions on the hot path). The
    magnitudes matter: the KMG cycle is symmetric but can be indefinite on
    part of the spectrum (the damped smoother does not contract every
    mode), so rz may pass through negative values on the way down; PCG
    still converges on these systems and |rz| -> 0 remains the exit signal.
    """

    def amv(u):
        return mhat_matvec(ops, u, pivot=cfg.pivot, backend=cfg.backend,
                           alg=cfg.alg)

    if cfg.precond == "kmg":
        if hier is None:
            raise ValueError(
                "precond='kmg' needs the coarse hierarchy: pass hier= to "
                "solve_mhat (fitted GPs carry it as gp.hier)")
        if cfg.fused in ("on", "whole"):
            raise ValueError(
                f"fused={cfg.fused!r} is incompatible with precond='kmg': "
                "the fused pcg kernels hard-code the block preconditioner")
        # the V-cycle spans the full (D, n, B) state through transfer
        # operators the fused kernel knows nothing about — host-level loop
        fs = None
        from ..precond.vcycle import kmg_preconditioner

        pre = kmg_preconditioner(ops, hier, damping=cfg.damping,
                                 smooth=cfg.precond_smooth, pivot=cfg.pivot,
                                 backend=cfg.backend, alg=cfg.alg)
    else:
        mode, fs = _maybe_fused(ops, v, cfg)
        if mode == "whole":
            from ..kernels.mega_solve import MegaSolve

            # the whole solve — warm residual, preconditioned loop, on-chip
            # tol check — in ONE pallas_call; the kernel hands back the
            # recursively-updated r and the realized iteration count
            x, r_fin, iters_used = MegaSolve(fs).pcg(
                v, x0, iters=cfg.iters, tol=cfg.tol)
            resid = jnp.sqrt(tree_sum(_det_dot(r_fin, r_fin), axis=0))
            return x, iters_used, resid

        def pre(u):
            return ops.block_solve(u, pivot=cfg.pivot, backend=cfg.backend,
                                   alg=cfg.alg)

    x = jnp.zeros_like(v) if x0 is None else x0
    # amv(0) == 0 exactly: skip the two dispatches on a cold start
    r = v if x0 is None else v - amv(x0)
    z = pre(r)
    p = z
    rz = _det_dot(r, z)

    if fs is not None:
        x, r, p = fs.pad_state(x), fs.pad_state(r), fs.pad_state(p)

        def body(state):
            x, r, p, rz = state
            x, r, p, rz1 = fs.pcg_iter(x, r, p, rz[None])
            return (x, r, p, rz1[0])
    else:

        def body(state):
            x, r, p, rz = state
            ap = amv(p)
            denom = _det_dot(p, ap)
            alpha = rz / jnp.where(denom == 0, 1.0, denom)
            x = x + alpha * p
            r = r - alpha * ap
            z = pre(r)
            rz_new = _det_dot(r, z)
            beta = rz_new / jnp.where(rz == 0, 1.0, rz)
            p = z + beta * p
            return (x, r, p, rz_new)

    state = (x, r, p, rz)
    if cfg.tol > 0:
        thresh = cfg.tol**2 * jnp.abs(rz)

        def cond(carry):
            i, state = carry
            return (i < cfg.iters) & jnp.any(jnp.abs(state[3]) > thresh)

        iters_used, state = jax.lax.while_loop(
            cond, lambda c: (c[0] + 1, body(c[1])),
            (jnp.asarray(0, jnp.int32), state))
    else:
        state = jax.lax.fori_loop(0, cfg.iters, lambda _, s: body(s), state)
        iters_used = jnp.asarray(cfg.iters, jnp.int32)
    x, r_fin = state[0], state[1]
    if fs is not None:
        x, r_fin = fs.unpad(x), fs.unpad(r_fin)
    resid = jnp.sqrt(tree_sum(_det_dot(r_fin, r_fin), axis=0))
    return x, iters_used, resid


@obs.scope("backfit.solve")
def solve_mhat(ops: DimOps, v: jax.Array, cfg: SolveConfig = SolveConfig(),
               x0: jax.Array | None = None, return_info: bool = False,
               hier=None):
    """Apply Mhat^{-1} to v: (D, n) or (D, n, B), original point order.

    ``x0`` optionally warm-starts the iteration from a previous solution
    (same shape as ``v``). All three methods are fixed-point/Krylov schemes
    whose iterate *is* the solution estimate, so a near-converged ``x0`` —
    e.g. the pre-insert solution spliced at a streamed point — cuts the
    iteration count to O(1) (paper Sec. 6; Kernel Multigrid's warm-started
    back-fitting argument). Combined with ``cfg.tol > 0`` (pcg) the solve
    then actually *exits* after those few iterations; ``return_info=True``
    additionally returns a :class:`SolveInfo` with the realized count.

    ``hier`` is the tuple of :class:`~repro.precond.CoarseLevel` built by
    ``precond.build_hierarchy`` (fitted GPs carry it as ``gp.hier``); it is
    required when ``cfg.precond == "kmg"`` and ignored otherwise.
    """
    precond = cfg.precond
    if precond == "auto":
        # unresolved config reaching a raw solve: enable kmg only when a
        # hierarchy was actually threaded through, using the static gate
        if hier is None or cfg.method != "pcg":
            precond = "none"
        else:
            from ..kernels import ops as _kops

            precond = _kops.resolve_precond("auto", q=ops.Phi.lo, n=ops.n)
        cfg = dataclasses.replace(cfg, precond=precond)
    if precond == "kmg" and cfg.method != "pcg":
        raise ValueError(
            f"precond='kmg' applies to method='pcg' only (got "
            f"{cfg.method!r}); use precond='none' for relaxation sweeps")
    vec_in = v.ndim == 2
    if vec_in:
        v = v[..., None]
        if x0 is not None:
            x0 = x0[..., None]
    # iterate in the dtype the banded ops produce (mixed-dtype RHS would
    # otherwise promote mid-iteration and break the loop carry)
    dtype = jnp.result_type(v, ops.SAPhi.data)
    # under capacity padding zero the state tails up front: every iterate
    # then stays exactly zero past the active prefix, so the PCG inner
    # products / tol residual norms are computed over the active prefix only
    # (a padded tail can never dilute them)
    v = mask_rows(v.astype(dtype), ops.n_active, axis=1)
    if x0 is not None:
        x0 = mask_rows(x0.astype(dtype), ops.n_active, axis=1)
    iters_used = jnp.asarray(cfg.iters, jnp.int32)
    resid = None
    if cfg.method == "gauss_seidel":
        out, resid = _gauss_seidel(ops, v, cfg, x0, want_resid=return_info)
    elif cfg.method == "jacobi":
        out, resid = _jacobi(ops, v, cfg, x0, want_resid=return_info)
    elif cfg.method == "pcg":
        out, iters_used, resid = _pcg(ops, v, cfg, x0, hier)
    else:
        raise ValueError(f"unknown method {cfg.method!r}")
    if not return_info:
        return out[..., 0] if vec_in else out
    if resid is None:
        # only the degenerate iters == 0 relaxation solve reaches here (the
        # sweeps otherwise carry their own residual) — one explicit matvec
        r = v - mhat_matvec(ops, out, pivot=cfg.pivot, backend=cfg.backend,
                            alg=cfg.alg)
        resid = jnp.sqrt(tree_sum(_det_dot(r, r), axis=0))
    out = out[..., 0] if vec_in else out
    n_active = jnp.asarray(
        ops.n if ops.n_active is None else ops.n_active, jnp.int32)
    rhs_norm = jnp.sqrt(tree_sum(_det_dot(v, v), axis=0))
    verdict = classify_solve(out, resid, rhs_norm,
                             at_cap=iters_used >= cfg.iters)
    return out, SolveInfo(iters=iters_used, n_active=n_active, resid=resid,
                          rhs=rhs_norm, verdict=verdict)
