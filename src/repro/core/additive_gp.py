"""Additive Matérn GP with sparse (Kernel Packet) algebra — the paper's API.

Implements Theorems 1-2 via the sparse reformulations Eqs. (12)-(15):

    mean      mu(x*)   = sum_d phi_d(x*)^T b_d,  b = Phi^{-T} P^T Mhat^{-1} S Y / s^2
    variance  s(x*)    = sum_d k_d(x*,x*) - sum_d phi_d^T G_d phi_d + w^T Mhat^{-1} w
    likelihood l       = -1/2 [ Y^T R Y + log|Mhat| + sum_d(log|Phi_d|-log|A_d|)
                                + 2n log s + n log 2pi ]
    gradient  dl/dw_d  = 1/2 [ u^T (dK_d) u - tr(R dK_d) ],   u = R Y,
                         dK_d = P^T B_d^{-1} Psi_d P   (generalized KPs)

where Mhat = Khat^{-1} + s^{-2} S S^T is applied/inverted in O(n) per sweep by
``repro.core.backfitting`` and all banded factors come from
``repro.core.kernel_packets``. Everything is O(n log n); every function is
validated against the dense oracle in ``repro.core.exact``.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from ..health import verdict as hv
from ..masking import mask_rows
from . import matern as mk
from .backfitting import DimOps, SolveConfig, solve_mhat, mhat_matvec
from .band_inverse import variance_band
from .banded import Banded, add, logdet, matvec, scale, solve, transpose
from .kernel_packets import gkp_factors, kp_factors, phi_at, phi_grad_at
from .ordering import argsort_rows, inverse_perm, separate_ties
from .stochastic import logdet_taylor, rademacher_rows

__all__ = ["GPConfig", "AdditiveGP", "fit", "resolve_config", "with_capacity",
           "mean_caches",
           "posterior_caches", "posterior_mean", "posterior_var",
           "log_likelihood", "mll_gradients", "fit_hyperparams", "TIE_EPS"]

# Span-relative separation applied to exactly-tied sorted coordinates (KP
# construction needs distinct points); streaming inserts reuse it so an
# incrementally grown GP matches a from-scratch fit.
TIE_EPS = 1e-9

# posterior_var solves its per-query Mhat right-hand sides in static-size
# column chunks so peak temp memory is O(D * n * _VAR_CHUNK) instead of
# O(D * n * m) for a size-m query batch (benchmarks/fleet_serving.py pins
# the regression). Chunking is static: the jit specializes per ceil(m/mc).
_VAR_CHUNK = 32


@partial(
    jax.tree_util.register_dataclass,
    data_fields=(),
    meta_fields=("q", "solver", "solver_iters", "pivot", "logdet_order",
                 "logdet_probes", "trace_probes", "power_iters", "logdet_method",
                 "backend", "solve_alg", "fused", "precond", "precond_levels",
                 "precond_coarsen", "precond_smooth", "gband", "health"),
)
@dataclasses.dataclass(frozen=True)
class GPConfig:
    q: int = 0  # nu = q + 1/2
    solver: str = "pcg"  # backfitting method for Mhat^{-1}
    solver_iters: int = 50
    pivot: bool = False
    # banded-algebra backend: "auto" (64-bit data -> jax; else pallas on a
    # TPU where every kernel lowers, jax otherwise) | "jax" | "pallas";
    # threaded through every
    # matvec/solve/logdet via kernels.ops
    backend: str = "auto"
    # pallas solve/logdet kernel: "auto" (block CR when lo == hi, else LU) |
    # "lu" | "cr"; also settable process-wide via REPRO_SOLVE_ALG
    solve_alg: str = "auto"
    # fused backfitting-sweep kernel: "auto" (fuse on pallas when the state
    # fits VMEM) | "on" | "off"; also settable process-wide via REPRO_FUSED.
    # Reaches every solve_mhat — fit, MLL, gradients, streaming inserts.
    fused: str = "auto"
    # backfitting PCG preconditioner: "auto" (kernel multigrid at q == 0 and
    # n >= kernels.ops.KMG_AUTO_MIN_N, else plain block) | "none" | "kmg";
    # also settable process-wide via REPRO_PRECOND. Resolved and baked at
    # fit() like backend/solve_alg; "kmg" additionally stores the coarse
    # hierarchy on the fitted GP (gp.hier) and threads it through every
    # solve — posterior caches, variance, MLL gradients, streaming inserts.
    precond: str = "auto"
    precond_levels: int = 2  # hierarchy depth incl. the fine level
    precond_coarsen: int = 8  # subsampling stride per level
    precond_smooth: int = 1  # coarse deflated-Jacobi sweeps per V-cycle
    # streaming Gband maintenance: "auto" (-> "windowed") | "windowed"
    # (exact splice + window-Woodbury update of the cached variance band per
    # insert/evict — O(window) + two narrow banded solves, no O(n) RGF
    # sweep) | "full" (recompute the band with the RGF sweep per mutation);
    # also settable process-wide via REPRO_GBAND. Resolved and baked at
    # fit() like backend/solve_alg (see core/gband_update.py).
    gband: str = "auto"
    # serve-path health tracking: "auto" (-> "on") | "on" (the fitted GP
    # carries a repro.health.HealthState — latest solve verdict + the Gband
    # drift sentinel accumulators — and the engines act on bad verdicts) |
    # "off" (no state, bit-identical to the pre-health serve path); also
    # settable process-wide via REPRO_HEALTH. Resolved and baked at fit().
    health: str = "auto"
    logdet_order: int = 30
    logdet_probes: int = 16
    trace_probes: int = 16
    power_iters: int = 20
    # "taylor" = paper Alg 8; "taylor_pc" = beyond-paper block-preconditioned
    # variant: log|Mhat| = log|C| (exact, banded) + log|C^{-1} Mhat| (Taylor on
    # a spectrum compressed from kappa(Mhat) ~ lam_max(Khat^{-1})/sigma^-2 down
    # to <= D * (1 + sigma^{-2} lam_max(Khat)).
    logdet_method: str = "taylor_pc"

    def solve_cfg(self) -> SolveConfig:
        return SolveConfig(method=self.solver, iters=self.solver_iters,
                           pivot=self.pivot, backend=self.backend,
                           alg=self.solve_alg, fused=self.fused,
                           precond=self.precond,
                           precond_levels=self.precond_levels,
                           precond_coarsen=self.precond_coarsen,
                           precond_smooth=self.precond_smooth)


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("X", "Y", "omega", "sigma", "xs", "ops", "B", "Psi", "bY",
                 "u_sy", "Gband", "n_active", "hier", "Hband", "health"),
    meta_fields=("config",),
)
@dataclasses.dataclass(frozen=True)
class AdditiveGP:
    """Fitted additive GP: data, banded factors, posterior caches.

    All row-indexed arrays share one static row count ``n`` — the *capacity*.
    When ``n_active`` is set (traced int32) only the first ``n_active`` rows
    are real observations; the tail is padding that every op treats as a
    decoupled identity block (see ``repro.masking``). ``n_active is
    None`` means fully active (the legacy unpadded representation).
    """

    X: jax.Array          # (n, D)
    Y: jax.Array          # (n,)
    omega: jax.Array      # (D,)
    sigma: jax.Array      # scalar noise std
    xs: jax.Array         # (D, n) sorted coordinates
    ops: DimOps           # stacked banded factors + permutations
    B: Banded             # generalized-KP coefficients (D, n, 2q+5)
    Psi: Banded           # generalized-KP Gram (D, n, 2q+3)
    bY: jax.Array         # (D, n) posterior-mean weights, sorted order
    u_sy: jax.Array       # (D, n) Mhat^{-1} (S Y), original order
    Gband: Banded         # (D, n, 4q+3) band of (A Phi^T)^{-1)
    config: GPConfig
    n_active: jax.Array | None = None
    # coarse KMG hierarchy (tuple of precond.CoarseLevel) when
    # config.precond == "kmg"; None otherwise. Rebuilt (cheap, no solve)
    # whenever the point set changes: fit, insert, evict, with_capacity.
    hier: tuple | None = None
    # (D, n, 4q+3) canonical band of H = A Phi^T — the carried cache that
    # lets streaming insert/evict update Gband with the windowed Woodbury
    # correction (core/gband_update.py) instead of the O(n) RGF sweep.
    # None only on legacy pytrees (pre-windowed checkpoints); the mutation
    # path then falls back to the full sweep.
    Hband: Banded | None = None
    # per-GP health scalars (latest solve verdict, Gband drift sentinel
    # accumulators) when config.health == "on"; None when "off". All-scalar
    # leaves, so the fleet's vmapped tenant axis carries them for free.
    health: hv.HealthState | None = None

    @property
    def n(self) -> int:
        """Static row count — the capacity when ``n_active`` is set."""
        return self.X.shape[0]

    @property
    def capacity(self) -> int:
        return self.X.shape[0]

    @property
    def D(self) -> int:
        return self.X.shape[1]

    def active(self):
        """Active observation count: a python int when unpadded, the traced
        ``n_active`` scalar otherwise (usable in jit arithmetic either way)."""
        return self.n if self.n_active is None else self.n_active

    def num_points(self) -> int:
        """Concrete active count (host-side; syncs when padded)."""
        return self.n if self.n_active is None else int(self.n_active)


def _build_factors(q: int, omega: jax.Array, xs: jax.Array):
    """Stacked (A, Phi, B, Psi) for all dims via vmap over the D axis."""
    A, Phi = jax.vmap(lambda om, x: kp_factors(q, om, x))(omega, xs)
    B, Psi = jax.vmap(lambda om, x: gkp_factors(q, om, x))(omega, xs)
    return A, Phi, B, Psi


def build_gp_hier(config: GPConfig, omega: jax.Array, sigma, X: jax.Array,
                  xs: jax.Array, ops: DimOps):
    """Coarse KMG hierarchy for a fitted system; None unless precond="kmg".

    O(n) band assembly at the subsampled points — no solves — so fit,
    ``with_capacity`` and every streaming insert/evict rebuild it outright
    instead of patching levels incrementally. vmap-safe (fleet stacking).
    """
    if config.precond != "kmg":
        return None
    from ..precond.coarse import build_hierarchy

    return build_hierarchy(config.q, omega, jnp.asarray(sigma) ** 2, X, xs,
                           ops, levels=config.precond_levels,
                           coarsen=config.precond_coarsen)


def fit(config: GPConfig, X: jax.Array, Y: jax.Array, omega: jax.Array, sigma,
        capacity: int | None = None) -> AdditiveGP:
    """Build all sparse factors and posterior caches — O(n log n).

    The banded-algebra backend is resolved here (config "auto" -> concrete
    "jax"/"pallas" via the process default / REPRO_BACKEND / the backend
    rule of ``kernels.ops``: 64-bit data -> "jax", else by platform) and
    baked into the returned GP, so the jit cache keys on the *resolved*
    backend and later ``set_backend`` calls can't silently hit a stale trace.
    The solve algorithm gets the same treatment: a config-level "auto"
    captures the process default (REPRO_SOLVE_ALG / set_solve_alg) at fit
    time ("auto" then means the static bandwidth-based choice: CR when
    lo == hi, LU otherwise). Likewise the fused-sweep mode: "auto" captures
    the REPRO_FUSED / set_fused process default; the residual "auto" is the
    per-solve shape check (pallas backend + symmetric bands + VMEM fit) in
    ``backfitting._maybe_fused``.

    ``capacity`` (static, >= n) returns a capacity-padded GP: all arrays
    allocated at ``capacity`` rows with ``n_active = n``. Active-prefix
    results are identical to the unpadded fit (the padding is fitted
    unpadded, then padded — bit-for-bit); streaming ``insert``/``evict``
    then mutate it in place with zero recompilation until the capacity is
    exhausted.
    """
    config = resolve_config(config, X.shape[0], jnp.result_type(X, Y))
    gp = _fit_impl(config, X, Y, omega, sigma)
    if capacity is not None:
        gp = with_capacity(gp, capacity)
    return gp


def resolve_config(config: GPConfig, n: int, dtype) -> GPConfig:
    """Bake every "auto" mode of ``config`` for an ``n``-point fit computed
    in ``dtype``. The backend follows the rule in ``kernels.ops`` (64-bit
    -> "jax"; else "pallas" on a TPU where every kernel lowers); shared by
    ``fit`` and ``fleet_fit``."""
    from ..kernels import ops as _kops

    return dataclasses.replace(
        config,
        backend=_kops.resolve_backend(config.backend, dtype),
        solve_alg=(config.solve_alg if config.solve_alg != "auto"
                   else _kops.get_solve_alg()),
        fused=(config.fused if config.fused != "auto"
               else _kops.get_fused()),
        precond=_kops.resolve_precond(config.precond, q=config.q, n=n),
        gband=_kops.resolve_gband(config.gband),
        health=_kops.resolve_health(config.health))


def _pad_rows(x: jax.Array, capacity: int, axis: int) -> jax.Array:
    """Zero-pad ``x`` to ``capacity`` rows along ``axis``."""
    n = x.shape[axis]
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, capacity - n)
    return jnp.pad(x, pad)


def _pad_band_rows(b: Banded, capacity: int, n_active) -> Banded:
    """Pad a Banded to ``capacity`` rows with a decoupled identity tail."""
    data = _pad_rows(b.data, capacity, axis=-2)
    n = b.n
    tail = jnp.arange(capacity) >= n
    ident = jnp.zeros((capacity, b.width), data.dtype).at[:, b.lo].set(1.0)
    data = jnp.where(tail[:, None], ident, data)
    return Banded(data, b.lo, b.hi, n_active)


def _pad_perm(idx: jax.Array, capacity: int) -> jax.Array:
    """Pad permutations (D, n) -> (D, capacity) with identity tails."""
    D, n = idx.shape
    tail = jnp.broadcast_to(jnp.arange(n, capacity, dtype=idx.dtype),
                            (D, capacity - n))
    return jnp.concatenate([idx, tail], axis=1)


@partial(jax.jit, static_argnums=(1,))
def _with_capacity_impl(gp: AdditiveGP, capacity: int) -> AdditiveGP:
    na = jnp.asarray(gp.active(), jnp.int32)
    ops = gp.ops
    ops_p = DimOps(
        A=_pad_band_rows(ops.A, capacity, na),
        Phi=_pad_band_rows(ops.Phi, capacity, na),
        SAPhi=_pad_band_rows(ops.SAPhi, capacity, na),
        sort_idx=_pad_perm(ops.sort_idx, capacity),
        rank_idx=_pad_perm(ops.rank_idx, capacity),
        sigma2=ops.sigma2, n_active=na)
    # xs pad values are never read through an active mask; keep them finite
    # and above the active range so the arrays stay visibly "sorted-ish"
    span = gp.xs[:, -1:] - gp.xs[:, :1] + 1.0
    steps = jnp.arange(1, capacity - gp.n + 1, dtype=gp.xs.dtype)
    xs_tail = gp.xs[:, -1:] + span * steps[None, :]
    xs_p = jnp.concatenate([gp.xs, xs_tail], axis=1)
    X_p = _pad_rows(gp.X, capacity, axis=0)
    # the coarse hierarchy is capacity-shaped (strided subset of the padded
    # rows): rebuild it at the new allocation rather than padding levels
    hier_p = build_gp_hier(gp.config, gp.omega, gp.sigma, X_p, xs_p, ops_p)
    return AdditiveGP(
        X=X_p, Y=_pad_rows(gp.Y, capacity, 0),
        omega=gp.omega, sigma=gp.sigma, xs=xs_p, ops=ops_p,
        B=_pad_band_rows(gp.B, capacity, na),
        Psi=_pad_band_rows(gp.Psi, capacity, na),
        bY=_pad_rows(gp.bY, capacity, axis=1),
        u_sy=_pad_rows(gp.u_sy, capacity, axis=1),
        Gband=_pad_band_rows(gp.Gband, capacity, na),
        Hband=(None if gp.Hband is None
               else _pad_band_rows(gp.Hband, capacity, na)),
        config=gp.config, n_active=na, hier=hier_p, health=gp.health)


def with_capacity(gp: AdditiveGP, capacity: int) -> AdditiveGP:
    """Re-home a fitted GP into a ``capacity``-row padded allocation.

    Pure array padding — no re-solve: active rows are copied bit-for-bit,
    band tails become decoupled identity rows, state tails zeros, permutation
    tails the identity. Works on unpadded and already-padded GPs alike
    (growing a full GP to the next capacity tier). O(capacity) and jitted
    per (old capacity, new capacity) pair.
    """
    capacity = int(capacity)
    if capacity < gp.n:
        raise ValueError(
            f"capacity {capacity} < current allocation {gp.n} "
            "(capacity shrinking is not supported; evict instead)")
    if capacity == gp.n and gp.n_active is not None:
        return gp
    return _with_capacity_impl(gp, capacity)


def mean_caches(config: GPConfig, ops: DimOps, Y: jax.Array,
                x0: jax.Array | None = None, iters: int | None = None,
                hier=None, return_info: bool = False):
    """(u_sy, bY) solve-dependent posterior-mean caches.

    Shared by ``fit`` (cold start) and ``repro.streaming`` mutations, which
    pass ``x0`` — the pre-mutation ``Mhat^{-1} S Y`` spliced at the changed
    point — to warm-start the backfitting solve and ``iters`` to cap it.
    ``hier`` is the KMG coarse hierarchy (required when config.precond ==
    "kmg"). The variance band is *not* recomputed here: the streaming path
    maintains it with the windowed update (``core/gband_update.py``) and
    only the cold-start ``posterior_caches`` runs the full RGF sweep.

    ``return_info=True`` (trace-time static) additionally returns the
    solve's classified :class:`~repro.core.backfitting.SolveInfo`; its
    verdict also absorbs a nonfinite probe of ``bY`` (the triangular
    follow-up solve), so a NaN that first appears there is still caught.
    """
    cfg = config.solve_cfg()
    if iters is not None:
        cfg = dataclasses.replace(cfg, iters=iters)
    D, n = ops.D, ops.n
    SY = jnp.broadcast_to(Y[None, :], (D, n))
    res = solve_mhat(ops, SY, cfg, x0=x0, hier=hier,
                     return_info=return_info)  # Mhat^{-1} S Y, original order
    u_sy, info = res if return_info else (res, None)
    bY = solve(transpose(ops.Phi), ops.to_sorted(u_sy) / ops.sigma2,
               pivot=config.pivot, backend=config.backend,
               alg=config.solve_alg)
    if not return_info:
        return u_sy, bY
    bad_by = jnp.where(jnp.all(jnp.isfinite(bY)), hv.OK, hv.NONFINITE)
    info = info._replace(
        verdict=jnp.maximum(info.verdict, bad_by).astype(jnp.int32))
    return u_sy, bY, info


def posterior_caches(config: GPConfig, ops: DimOps, Y: jax.Array,
                     x0: jax.Array | None = None, iters: int | None = None,
                     hier=None, return_info: bool = False):
    """(u_sy, bY, Gband, Hband) posterior caches from assembled factors.

    The cold-start path: :func:`mean_caches` plus the full RGF variance-band
    sweep (which also yields the ``H = A Phi^T`` band carried on the GP for
    the windowed streaming updates). ``return_info=True`` appends the
    classified solve info (see :func:`mean_caches`).
    """
    res = mean_caches(config, ops, Y, x0=x0, iters=iters, hier=hier,
                      return_info=return_info)
    Gband, Hband = variance_band(ops.A, ops.Phi, backend=config.backend,
                                 return_h=True)
    if return_info:
        u_sy, bY, info = res
        return u_sy, bY, Gband, Hband, info
    u_sy, bY = res
    return u_sy, bY, Gband, Hband


@partial(jax.jit, static_argnums=(0,))
def _fit_impl(config: GPConfig, X: jax.Array, Y: jax.Array, omega: jax.Array,
              sigma) -> AdditiveGP:
    q = config.q
    n, D = X.shape
    sigma = jnp.asarray(sigma, X.dtype)
    sort_idx = argsort_rows(X.T)  # (D, n)
    xs = jnp.take_along_axis(X.T, sort_idx, axis=1)
    rank_idx = inverse_perm(sort_idx)
    # KP construction (Thm 3) requires distinct sorted points; BO proposals
    # clipped to the box boundary can create exact ties. Separate ties by a
    # span-relative epsilon (preserves order; perturbation ~1e-9 of range).
    span = xs[:, -1:] - xs[:, :1] + 1.0
    xs = separate_ties(xs, span * TIE_EPS)
    A, Phi, B, Psi = _build_factors(q, omega, xs)
    SAPhi = add(scale(A, sigma**2), Phi)
    ops = DimOps(A=A, Phi=Phi, SAPhi=SAPhi, sort_idx=sort_idx, rank_idx=rank_idx,
                 sigma2=sigma**2)
    hier = build_gp_hier(config, omega, sigma, X, xs, ops)
    # a config that never went through fit() (health still "auto") carries
    # no state — only a resolved "on" pays for the verdict reductions
    if config.health == "on":
        u_sy, bY, Gband, Hband, info = posterior_caches(
            config, ops, Y, hier=hier, return_info=True)
        health = hv.HealthState.fresh(Y.dtype).with_solve(info)
    else:
        u_sy, bY, Gband, Hband = posterior_caches(config, ops, Y, hier=hier)
        health = None
    return AdditiveGP(X=X, Y=Y, omega=omega, sigma=sigma, xs=xs, ops=ops, B=B,
                      Psi=Psi, bY=bY, u_sy=u_sy, Gband=Gband, Hband=Hband,
                      config=config, hier=hier, health=health)


# ---------------------------------------------------------------------------
# Prediction (Sec. 5.2): O(log n) per query for the mean; variance adds one
# batched Mhat solve per query batch (the paper's "predetermined x*" path).
# ---------------------------------------------------------------------------


def _phi_windows(gp: AdditiveGP, Xq: jax.Array):
    """Sparse phi_d(x*_d) for all dims/queries: rows, vals (D, m, 2q+2)."""
    q = gp.config.q
    na = gp.n_active  # shared scalar; closed over, not vmapped

    def per_dim(om, x_sorted, a_data, xq_d):
        A_d = Banded(a_data, q + 1, q + 1)
        return phi_at(q, om, x_sorted, A_d, xq_d, n_active=na)

    return jax.vmap(per_dim)(gp.omega, gp.xs, gp.ops.A.data, Xq.T)


@jax.jit
def posterior_mean(gp: AdditiveGP, Xq: jax.Array) -> jax.Array:
    """mu(x*) for Xq (m, D) — Eq. (12); O(log n) per query."""
    rows, vals, _ = _phi_windows(gp, Xq)  # (D, m, W)
    bwin = jnp.take_along_axis(gp.bY[:, None, :], rows, axis=2)
    return jnp.sum(vals * bwin, axis=(0, 2))


@jax.jit
def posterior_var(gp: AdditiveGP, Xq: jax.Array) -> jax.Array:
    """s(x*) for Xq (m, D) — Eq. (13)."""
    q = gp.config.q
    W = 2 * q + 2
    D, n = gp.D, gp.n
    m = Xq.shape[0]
    rows, vals, _ = _phi_windows(gp, Xq)  # (D, m, W)

    # term 2: sum_d phi_d^T G_d phi_d  — local window quadratic, O(1) per query
    hw = gp.Gband.lo
    off = jnp.arange(W)[None, :] - jnp.arange(W)[:, None]  # b - a
    g_entries = gp.Gband.data[
        jnp.arange(D)[:, None, None, None],
        rows[:, :, :, None],
        hw + off[None, None, :, :],
    ]  # (D, m, W, W)
    term2 = jnp.einsum("dma,dmab,dmb->m", vals, g_entries, vals)

    # term 3: w^T Mhat^{-1} w with w_d = P^T Phi_d^{-1} phi_d. The RHS is
    # window-sparse ((D, m, W) nonzeros), but the Phi / Mhat solves need a
    # dense column per query — materializing all m at once costs O(D n m)
    # peak bytes in the hot serve path. Batch the query axis into
    # static-size column chunks instead (lax.map keeps ONE compiled chunk
    # body alive at a time), so peak temp memory is O(D n mc) at identical
    # per-column arithmetic (each column's solve is independent).
    mc = min(m, _VAR_CHUNK)
    nchunk = -(-m // mc)
    pad = nchunk * mc - m
    rows_c = jnp.pad(rows, ((0, 0), (0, pad), (0, 0))).transpose(1, 0, 2)
    vals_c = jnp.pad(vals, ((0, 0), (0, pad), (0, 0))).transpose(1, 0, 2)
    rows_c = rows_c.reshape(nchunk, mc, D, W)
    vals_c = vals_c.reshape(nchunk, mc, D, W)
    d_idx = jnp.arange(D)[None, :, None]
    m_idx = jnp.arange(mc)[:, None, None]

    def _term3_chunk(args):
        rc, vc = args  # (mc, D, W)
        phi_cols = jnp.zeros((D, n, mc), Xq.dtype)
        phi_cols = phi_cols.at[
            jnp.broadcast_to(d_idx, rc.shape),
            rc,
            jnp.broadcast_to(m_idx, rc.shape),
        ].add(vc)
        w_sorted = solve(gp.ops.Phi, phi_cols, pivot=gp.config.pivot,
                         backend=gp.config.backend,
                         alg=gp.config.solve_alg)  # (D, n, mc)
        w = gp.ops.from_sorted(w_sorted)
        z = solve_mhat(gp.ops, w, gp.config.solve_cfg(), hier=gp.hier)
        return jnp.sum(w * z, axis=(0, 1))

    term3 = jax.lax.map(_term3_chunk, (rows_c, vals_c)).reshape(-1)[:m]

    return prior_var(gp, Xq.dtype) - term2 + term3


def prior_var(gp: AdditiveGP, dtype) -> jax.Array:
    """Prior variance sum_d k_d(x*, x*), derived from the kernel itself
    rather than hardcoding D. matern() is unit-amplitude by construction
    (matern._poly_coeffs fixes the constant coefficient to 1), so each
    term is exactly 1.0 and the sum folds to float(D) bit-for-bit today —
    but if an amplitude hyperparameter is ever added, this stays correct
    where a literal D would go silently wrong. Stationary, so independent
    of the query point."""
    zero = jnp.zeros((), dtype)
    kdiag = jax.vmap(lambda om: mk.matern(gp.config.q, om, zero, zero))(
        gp.omega)
    return jnp.sum(kdiag).astype(dtype)


# ---------------------------------------------------------------------------
# Likelihood + gradients (Sec. 5.1, Eqs. (14)-(15))
# ---------------------------------------------------------------------------


def _r_apply(gp: AdditiveGP, v: jax.Array, cfg: SolveConfig) -> jax.Array:
    """R v = sigma^{-2} v - sigma^{-4} S^T Mhat^{-1} S v, v: (n,) or (n, B)."""
    D = gp.D
    SV = jnp.broadcast_to(v[None], (D,) + v.shape)
    z = solve_mhat(gp.ops, SV, cfg, hier=gp.hier)
    return v / gp.sigma**2 - jnp.sum(z, axis=0) / gp.sigma**4


def _probe_block(gp: AdditiveGP, key: jax.Array, Q: int) -> jax.Array:
    """Row-keyed masked Rademacher probes (D, n, Q).

    Row i depends only on (key, i), so a capacity-padded GP and an unpadded
    GP draw the *same* probe values on the active prefix — the stochastic
    estimators are invariant to the padding, not just unbiased under it.
    """
    v = rademacher_rows(key, gp.n, (gp.D, Q), dtype=gp.Y.dtype)
    return mask_rows(v.transpose(1, 0, 2), gp.n_active, axis=1)


def _logdet_mhat(gp: AdditiveGP, key: jax.Array) -> jax.Array:
    """log|Mhat| — paper Alg 8 ("taylor") or preconditioned ("taylor_pc").

    Under capacity padding the operators act as the identity on the padded
    tail (canonical factors + masked probes), so the estimates target the
    active block; the ``dim * log(lam)`` normalization uses the *active*
    dimension count.
    """
    c = gp.config
    n, D = gp.n, gp.D
    dim = D * gp.active()
    k1, k2 = jax.random.split(key)
    pm_v0 = _probe_block(gp, k1, 4)  # power_method's default restarts
    probe_v = _probe_block(gp, k2, c.logdet_probes)
    if c.logdet_method == "taylor":
        mv = lambda u: mhat_matvec(gp.ops, u, pivot=c.pivot, backend=c.backend,
                                   alg=c.solve_alg)
        return logdet_taylor(
            mv, dim, (D, n), key, order=c.logdet_order, probes=c.logdet_probes,
            power_iters=c.power_iters, dtype=gp.Y.dtype, probe_v=probe_v,
            power_v0=pm_v0,
        )
    # taylor_pc: C = Khat^{-1} + sigma^{-2} I (block diag). log|C| is exact:
    # log|K_d^{-1} + s^{-2} I| = log|A_d + s^{-2} Phi_d| - log|Phi_d|.
    APhi = add(gp.ops.A, scale(gp.ops.Phi, 1.0 / gp.sigma**2))
    ld_c = jnp.sum(logdet(APhi, pivot=c.pivot, backend=c.backend,
                          alg=c.solve_alg)) - jnp.sum(
        logdet(gp.ops.Phi, pivot=c.pivot, backend=c.backend, alg=c.solve_alg))
    nv = lambda u: gp.ops.block_solve(
        mhat_matvec(gp.ops, u, pivot=c.pivot, backend=c.backend,
                    alg=c.solve_alg),
        pivot=c.pivot, backend=c.backend, alg=c.solve_alg)
    ld_n = logdet_taylor(
        nv, dim, (D, n), key, order=c.logdet_order, probes=c.logdet_probes,
        power_iters=c.power_iters, dtype=gp.Y.dtype, probe_v=probe_v,
        power_v0=pm_v0,
    )
    return ld_c + ld_n


@partial(jax.jit, static_argnames=("return_verdict",))
def log_likelihood(gp: AdditiveGP, key: jax.Array,
                   return_verdict: bool = False):
    """Eq. (14): exact quadratic term + stochastic log-det (Algs 6-8).

    Capacity padding: the quadratic term masks the (potentially arbitrary)
    padded tails, the banded log-dets pick up exactly 0 from the identity
    tails, and the size-dependent constants use the active count.

    ``return_verdict=True`` additionally returns an int32 health code: the
    MLL reuses the fitted ``u_sy`` cache (no fresh Mhat solve), so the
    verdict is a nonfinite probe of the value — NONFINITE or OK.
    """
    na = gp.active()
    Ym = mask_rows(gp.Y, gp.n_active, axis=0)
    um = mask_rows(jnp.sum(gp.u_sy, axis=0), gp.n_active, axis=0)
    quad = Ym @ Ym / gp.sigma**2 - (Ym @ um) / gp.sigma**4
    ld_mhat = _logdet_mhat(gp, key)
    be, pv, sa = gp.config.backend, gp.config.pivot, gp.config.solve_alg
    ld_k = jnp.sum(logdet(gp.ops.Phi, pivot=pv, backend=be, alg=sa)) - jnp.sum(
        logdet(gp.ops.A, pivot=pv, backend=be, alg=sa))
    ll = -0.5 * (
        quad + ld_mhat + ld_k + 2.0 * na * jnp.log(gp.sigma)
        + na * jnp.log(2.0 * jnp.pi)
    )
    if not return_verdict:
        return ll
    verdict = jnp.where(jnp.isfinite(ll), hv.OK, hv.NONFINITE).astype(
        jnp.int32)
    return ll, verdict


def _dk_apply(gp: AdditiveGP, v: jax.Array) -> jax.Array:
    """Apply dK_d = P^T B_d^{-1} Psi_d P to v for all d: v (n, B) -> (D, n, B)."""
    D = gp.D
    vb = jnp.broadcast_to(v[None], (D,) + v.shape)
    vs = gp.ops.to_sorted(vb)
    be = gp.config.backend
    w = solve(gp.B, matvec(gp.Psi, vs, backend=be), pivot=gp.config.pivot,
              backend=be, alg=gp.config.solve_alg)
    return gp.ops.from_sorted(w)


@partial(jax.jit, static_argnames=("return_info",))
def mll_gradients(gp: AdditiveGP, key: jax.Array, return_info: bool = False):
    """(d MLL / d omega (D,), d MLL / d sigma) — Eq. (15) + Hutchinson traces.

    Capacity padding: masked row-keyed probes and a masked ``u = R Y`` keep
    every trace/quadratic estimate on the active block; ``tr R``'s exact
    ``n / sigma^2`` part uses the active count.

    ``return_info=True`` additionally returns a classified
    :class:`~repro.core.backfitting.SolveInfo` whose verdict is the worst
    over the two trace-probe Mhat solves plus a nonfinite probe of the
    gradients themselves.
    """
    c = gp.config
    cfg = c.solve_cfg()
    n, D, Q = gp.n, gp.D, c.trace_probes
    na = gp.active()
    # u = R Y (exact, reusing the fitted Mhat^{-1} S Y)
    u = mask_rows(gp.Y / gp.sigma**2 - jnp.sum(gp.u_sy, axis=0) / gp.sigma**4,
                  gp.n_active, axis=0)
    gu = _dk_apply(gp, u[:, None])[..., 0]  # (D, n)
    term1 = gu @ u  # (D,)

    # Hutchinson trace of R dK_d (Eq. (24)), batched over probes AND dims;
    # probes are row-keyed (capacity-invariant draw) and masked to the
    # active prefix
    V = mask_rows(rademacher_rows(key, n, (Q,), dtype=gp.Y.dtype),
                  gp.n_active, axis=0)
    Wd = _dk_apply(gp, V)  # (D, n, Q)
    first = jnp.einsum("nq,dnq->dq", V, Wd) / gp.sigma**2
    rhs = jnp.broadcast_to(
        Wd.transpose(1, 0, 2).reshape(1, n, D * Q), (D, n, D * Q)
    )
    rz = solve_mhat(gp.ops, rhs, cfg, hier=gp.hier,
                    return_info=return_info)  # (D, n, D*Q)
    z, info_z = rz if return_info else (rz, None)
    stz = jnp.sum(z, axis=0).reshape(n, D, Q)
    second = jnp.einsum("nq,ndq->dq", V, stz) / gp.sigma**4
    trace = jnp.mean(first - second, axis=1)  # (D,)
    grad_omega = 0.5 * (term1 - trace)

    # sigma gradient: dMLL/dsigma^2 = 0.5 (||u||^2 - tr R), tr R via same probes
    rzs = solve_mhat(gp.ops, jnp.broadcast_to(V[None], (D, n, Q)), cfg,
                     hier=gp.hier, return_info=return_info)
    zs, info_s = rzs if return_info else (rzs, None)
    quadS = jnp.einsum("nq,nq->q", V, jnp.sum(zs, axis=0))
    tr_r = na / gp.sigma**2 - jnp.mean(quadS) / gp.sigma**4
    grad_sigma2 = 0.5 * (u @ u - tr_r)
    grad_sigma = grad_sigma2 * 2.0 * gp.sigma
    if not return_info:
        return grad_omega, grad_sigma
    fin = jnp.all(jnp.isfinite(grad_omega)) & jnp.isfinite(grad_sigma)
    verdict = jnp.maximum(
        jnp.maximum(info_z.verdict, info_s.verdict),
        jnp.where(fin, hv.OK, hv.NONFINITE)).astype(jnp.int32)
    return grad_omega, grad_sigma, info_z._replace(verdict=verdict)


def fit_hyperparams(
    config: GPConfig,
    X: jax.Array,
    Y: jax.Array,
    omega0: jax.Array,
    sigma0,
    key: jax.Array,
    steps: int = 50,
    lr: float = 0.1,
):
    """Gradient ascent on (log omega, log sigma) using the sparse gradients.

    Returns (fitted AdditiveGP, (omega, sigma), trace of grad norms).
    """
    log_om = jnp.log(omega0)
    log_sg = jnp.log(jnp.asarray(sigma0, X.dtype))
    # Adam state
    m = jnp.zeros(log_om.shape[0] + 1, X.dtype)
    v = jnp.zeros(log_om.shape[0] + 1, X.dtype)

    @partial(jax.jit, static_argnums=())
    def step(i, log_om, log_sg, m, v, key):
        gp = fit(config, X, Y, jnp.exp(log_om), jnp.exp(log_sg))
        g_om, g_sg = mll_gradients(gp, key)
        g = jnp.concatenate([g_om * jnp.exp(log_om), (g_sg * jnp.exp(log_sg))[None]])
        m = 0.9 * m + 0.1 * g
        v = 0.999 * v + 0.001 * g * g
        mh = m / (1 - 0.9 ** (i + 1.0))
        vh = v / (1 - 0.999 ** (i + 1.0))
        upd = lr * mh / (jnp.sqrt(vh) + 1e-8)
        return log_om + upd[:-1], log_sg + upd[-1], m, v, jnp.linalg.norm(g)

    norms = []
    for i in range(steps):
        key, sub = jax.random.split(key)
        log_om, log_sg, m, v, gn = step(
            jnp.asarray(i, X.dtype), log_om, log_sg, m, v, sub
        )
        norms.append(float(gn))
    omega, sigma = jnp.exp(log_om), jnp.exp(log_sg)
    return fit(config, X, Y, omega, sigma), (omega, sigma), norms


@jax.jit
def posterior_mean_grad(gp: AdditiveGP, Xq: jax.Array) -> jax.Array:
    """grad_x mu(x*) (m, D) — Eq. (30) left, via sparse KP derivative windows."""
    q = gp.config.q
    na = gp.n_active

    def per_dim(om, x_sorted, a_data, xq_d, b_d):
        A_d = Banded(a_data, q + 1, q + 1)
        rows, dvals, _ = phi_grad_at(q, om, x_sorted, A_d, xq_d, n_active=na)
        bwin = jnp.take_along_axis(b_d[None, :], rows.reshape(1, -1), axis=1)
        bwin = bwin.reshape(rows.shape)
        return jnp.sum(dvals * bwin, axis=-1)

    out = jax.vmap(per_dim)(gp.omega, gp.xs, gp.ops.A.data, Xq.T, gp.bY)
    return out.T  # (m, D)
