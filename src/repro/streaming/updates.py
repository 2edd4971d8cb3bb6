"""Incremental posterior updates — the paper's Sec. 6 streaming formulas.

Capacity-padded, in-place streaming (this module + the mask-aware core):
a fitted :class:`AdditiveGP` carries a static ``capacity`` and a traced
``n_active`` (``repro.core.additive_gp.with_capacity`` / ``fit(...,
capacity=)``). ``insert`` and ``evict`` mutate the *same-shaped* arrays —
write into the next free slot / drop the oldest slot — so a stream of
mutations at fixed capacity reuses ONE compiled step: zero recompilation,
no shape-polymorphic retrace machinery anywhere on the hot path.

``insert(gp, x_new, y_new)`` grows a fitted GP by one observation without
the O(n log n) refit:

  * the new coordinate's sorted position is found by a masked count over the
    active prefix (the capacity-safe ``searchsorted``), and the sort/rank
    permutations are updated in closed form, in place;
  * the banded KP factors (A, Phi) and generalized-KP factors (B, Psi) are
    updated only in the O(q) window of rows whose point windows — or
    Algorithm-2 boundary category — contain the insertion point; every other
    row is a shifted copy of the pre-insert band (Thm 3 locality);
  * the posterior caches are rebuilt with a *warm-started* backfitting solve
    (block cyclic-reduction kernel on the pallas backend; with
    ``GPConfig.fused`` each warm iteration is ONE fused ``pallas_call``):
    the pre-insert ``Mhat^{-1} S Y`` with the new slot seeded from its
    sorted neighbour is an O(sigma^2)-accurate initial iterate, so a handful
    of PCG iterations reconverge it (the Kernel Multigrid warm-start
    argument).

``evict(gp)`` is the sliding-window counterpart: it drops the *oldest*
observation (original index 0) with the mirrored windowed factor deletion —
rows shift up past the evicted sorted position, the O(q) window around it is
rebuilt exactly, permutations update in closed form — plus a warm re-solve
from the surviving entries of ``Mhat^{-1} S Y``. ``insert`` + ``evict`` at a
fixed capacity is a bounded-memory serving loop: peak memory is pinned by
the capacity, forever.

The per-mutation cost is O(q) factor work plus a short warm solve and one
O(capacity) band-inverse sweep for the variance band — asymptotically far
below the refit's n window SVDs and cold iteration, which is exactly the
gap ``benchmarks/streaming_updates.py`` / ``benchmarks/capacity_streaming.py``
measure.

``refresh_local_cache`` is the companion O(1) small-learning-rate path for
the dense acquisition cache (paper Sec. 6 "given the posterior"): the new
row/column inherit the nearest sorted neighbour's entries (no solve at all in
``mode="copy"``), optionally refined exactly inside the insertion window with
one narrow solve batch (``mode="window"``).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

import numpy as np

from .. import obs
from ..core import matern as mk
from ..core.additive_gp import (AdditiveGP, TIE_EPS, build_gp_hier,
                                mean_caches, with_capacity)
from ..health import verdict as hv
from ..core.backfitting import DimOps, solve_mhat
from ..core.band_inverse import variance_band
from ..core.banded import Banded, add, scale, solve, transpose
from ..core.gband_update import gband_evict, gband_insert
from ..core.bayesopt import LocalAcqCache
from ..core.fleet import GPFleet, select_tenants
from ..core.kernel_packets import gram_band_rows, kp_coefficient_rows
from ..masking import canonical_band, mask_rows

__all__ = ["insert", "evict", "with_capacity", "refresh_local_cache",
           "fleet_insert", "fleet_evict", "fleet_resync", "maybe_resync",
           "resync_gband"]


def _splice_vec(v: jax.Array, p, val) -> jax.Array:
    """(C,) -> (C,) with ``val`` inserted at position ``p`` (last slot drops)."""
    n = v.shape[0]
    j = jnp.arange(n)
    out = v[jnp.clip(j - (j > p), 0, n - 1)]
    return jnp.where(j == p, val, out)


def _delete_vec(v: jax.Array, p) -> jax.Array:
    """(C,) -> (C,) with slot ``p`` removed (rows > p shift up; last repeats)."""
    n = v.shape[0]
    j = jnp.arange(n)
    return v[jnp.clip(j + (j >= p), 0, n - 1)]


def _expand_rows(data: jax.Array, p) -> jax.Array:
    """(C, w) -> (C, w): rows >= p shift down; row p is a placeholder copy.

    Every row whose band-validity pattern differs between the k- and
    (k+1)-point matrices lies within the recompute window around ``p`` (its
    band reaches the insertion index), so the placeholder and any stale
    copies are always overwritten by exact window rows.
    """
    n = data.shape[0]
    j = jnp.arange(n)
    return data[jnp.clip(j - (j > p), 0, n - 1)]


def _delete_rows(data: jax.Array, p) -> jax.Array:
    """(C, ...) -> (C, ...): row ``p`` removed, rows > p shift up."""
    n = data.shape[0]
    j = jnp.arange(n)
    return data[jnp.clip(j + (j >= p), 0, n - 1)]


def _insert_dim(q: int, k, omega_d, xs_d, sort_d, rank_d, a_d, phi_d, b_d,
                psi_d, x_val):
    """One dimension's in-place spliced order, permutations, band windows.

    ``k`` is the traced pre-insert active count; all arrays stay at their
    static capacity. Recompute radii: an A/Phi row reads xs only within
    +-(q+1) of itself and its Algorithm-2 boundary category shifts by at
    most q+2 rows, so radius 2q+4 strictly covers every changed row (2q+6
    for the order-(q+1) B/Psi factors). Rows outside the window are exact
    shifted copies.
    """
    C = xs_d.shape[0]
    j = jnp.arange(C)
    active = j < k
    span = jnp.take(xs_d, k - 1) - xs_d[0] + 1.0
    # p = #active coords <= x_val — capacity-safe searchsorted(side="right"),
    # matching fit's stable argsort (the appended point sorts after equal
    # values); separate an exact tie like fit's TIE_EPS bump, capped at half
    # the gap to the right neighbour so repeated inserts of the same
    # coordinate stay strictly increasing.
    p = jnp.sum(((xs_d <= x_val) & active).astype(jnp.int32))
    left = jnp.take(xs_d, jnp.clip(p - 1, 0, C - 1))
    right = jnp.take(xs_d, jnp.clip(p, 0, C - 1))
    gap = jnp.where(p < k, right - left, jnp.inf)
    bump = jnp.minimum(span * TIE_EPS, 0.5 * gap)
    x_val = jnp.where((p > 0) & (x_val <= left), left + bump, x_val)
    xs_new = _splice_vec(xs_d, p, x_val)
    # permutations in closed form; canonical identity tails past the new
    # active count k+1 (rows 0..k are active)
    sort_new = _splice_vec(sort_d, p, jnp.asarray(k, sort_d.dtype))
    sort_new = jnp.where(j <= k, sort_new, j.astype(sort_d.dtype))
    rank_new = jnp.where(
        j < k, rank_d + (rank_d >= p).astype(rank_d.dtype),
        jnp.where(j == k, jnp.asarray(p, rank_d.dtype),
                  j.astype(rank_d.dtype)))

    k1 = k + 1
    ra = 2 * q + 4
    rows_a = jnp.clip(p - ra + jnp.arange(2 * ra + 1), 0, k)
    a_rows = kp_coefficient_rows(q, omega_d, xs_new, rows_a, n_active=k1)
    a_new = _expand_rows(a_d, p).at[rows_a].set(a_rows)
    kfun = lambda x, y: mk.matern(q, omega_d, x, y)
    phi_rows = gram_band_rows(kfun, xs_new, a_rows, rows_a, q + 1, q + 1, q,
                              n_active=k1)
    phi_new = _expand_rows(phi_d, p).at[rows_a].set(phi_rows)

    rb = 2 * q + 6
    rows_b = jnp.clip(p - rb + jnp.arange(2 * rb + 1), 0, k)
    b_rows = kp_coefficient_rows(q + 1, omega_d, xs_new, rows_b, n_active=k1)
    b_new = _expand_rows(b_d, p).at[rows_b].set(b_rows)
    dkfun = lambda x, y: mk.matern_domega(q, omega_d, x, y)
    psi_rows = gram_band_rows(dkfun, xs_new, b_rows, rows_b, q + 2, q + 2,
                              q + 1, n_active=k1)
    psi_new = _expand_rows(psi_d, p).at[rows_b].set(psi_rows)
    # canonical identity tails: the stored factors equal what a padded
    # from-scratch fit stores, bit-for-bit outside the solve windows
    a_new = canonical_band(a_new, q + 1, q + 1, k1)
    phi_new = canonical_band(phi_new, q, q, k1)
    b_new = canonical_band(b_new, q + 2, q + 2, k1)
    psi_new = canonical_band(psi_new, q + 1, q + 1, k1)
    return xs_new, sort_new, rank_new, a_new, phi_new, b_new, psi_new, p


def _mutated_gband(gp: AdditiveGP, ops: DimOps, p: jax.Array, k1: jax.Array,
                   evicting: bool):
    """Post-mutation ``(Gband, Hband, drift)`` caches.

    With a baked ``gband="windowed"`` config and a populated ``Hband`` cache
    this runs the O(window) Woodbury correction of ``core/gband_update.py``;
    otherwise (``gband="full"``, or a legacy checkpoint without the cache)
    it falls back to the full O(capacity) RGF sweep. The branch is resolved
    at trace time — both sides are the same pytree shape, so the compiled
    program contains only the selected path. ``drift`` is the windowed
    update's per-mutation truncation estimate for the health sentinel
    (exactly zero on the full-sweep path, which is exact by construction).
    """
    config = gp.config
    if config.gband != "full" and gp.Hband is not None:
        fn = gband_evict if evicting else gband_insert
        return fn(gp.Hband, ops.A, ops.Phi, gp.Gband, p, k1, config.q,
                  backend=config.backend, alg=config.solve_alg)
    Gband, Hband = variance_band(ops.A, ops.Phi, backend=config.backend,
                                 return_h=True)
    return Gband, Hband, jnp.zeros((), Gband.data.dtype)


def _mutated_health(gp: AdditiveGP, info, drift):
    """Post-mutation ``HealthState``: fold this mutation's classified warm
    solve and its Gband truncation estimate into the carried scalars. The
    branch is static (config.health is baked meta): a health-off GP carries
    (and pays for) nothing."""
    if gp.config.health != "on":
        return None
    base = (gp.health if gp.health is not None
            else hv.HealthState.fresh(gp.Y.dtype))
    return base.with_solve(info).with_drift(drift)


def _insert_core(gp: AdditiveGP, x_new: jax.Array, y_new: jax.Array,
                 iters: int) -> AdditiveGP:
    """Traced in-place insert body — shared by the jitted single-GP step and
    the fleet's masked vmapped tenant-axis step (``_fleet_insert_impl``)."""
    config = gp.config
    q = config.q
    C = gp.n
    k = jnp.asarray(gp.active(), jnp.int32)
    with obs.scope("mutation.splice"):
        xs, sort_idx, rank_idx, a, phi, b, psi, p = jax.vmap(
            lambda om, xd, sd, rd, ad, pd, bd, qd, xv: _insert_dim(
                q, k, om, xd, sd, rd, ad, pd, bd, qd, xv)
        )(gp.omega, gp.xs, gp.ops.sort_idx, gp.ops.rank_idx, gp.ops.A.data,
          gp.ops.Phi.data, gp.B.data, gp.Psi.data, x_new)
    k1 = k + 1
    A = Banded(a, q + 1, q + 1, k1)
    Phi = Banded(phi, q, q, k1)
    B = Banded(b, q + 2, q + 2, k1)
    Psi = Banded(psi, q + 1, q + 1, k1)
    SAPhi = add(scale(A, gp.sigma**2), Phi)
    ops = DimOps(A=A, Phi=Phi, SAPhi=SAPhi, sort_idx=sort_idx,
                 rank_idx=rank_idx, sigma2=gp.sigma**2, n_active=k1)
    # the new observation's original index is k: one in-place slot write
    X = gp.X.at[k].set(x_new)
    Y = mask_rows(gp.Y, k, axis=0).at[k].set(y_new)
    # warm start: the pre-insert solution with slot k seeded from its sorted
    # left neighbour — the solve is a smoothed field per dim, so this is
    # already near-converged.
    us = gp.ops.to_sorted(gp.u_sy)  # (D, C), canonical zero tail
    est = jnp.take_along_axis(us, jnp.clip(p - 1, 0, C - 1)[:, None], axis=1)
    x0 = mask_rows(gp.u_sy, k, axis=1).at[jnp.arange(gp.D), k].set(est[:, 0])
    # coarse levels are O(q)-cheap strided re-assemblies; rebuilt per
    # mutation — but only when the baked config can consume them (a
    # non-"kmg" precond never reads the hierarchy, so rebuilding it per
    # mutation would be pure wasted work)
    hier = (build_gp_hier(config, gp.omega, gp.sigma, X, xs, ops)
            if config.precond == "kmg" else None)
    if config.health == "on":
        u_sy, bY, info = mean_caches(config, ops, Y, x0=x0, iters=iters,
                                     hier=hier, return_info=True)
    else:
        u_sy, bY = mean_caches(config, ops, Y, x0=x0, iters=iters, hier=hier)
    Gband, Hband, drift = _mutated_gband(gp, ops, p, k1, evicting=False)
    health = _mutated_health(gp, info if config.health == "on" else None,
                             drift)
    return AdditiveGP(X=X, Y=Y, omega=gp.omega, sigma=gp.sigma, xs=xs,
                      ops=ops, B=B, Psi=Psi, bY=bY, u_sy=u_sy, Gband=Gband,
                      Hband=Hband, config=config, n_active=k1, hier=hier,
                      health=health)


def _lane1(core_call):
    """Run a single-GP traced body as the one-lane case of its vmapped form.

    The compiled single-GP and vmapped-fleet programs would otherwise be
    *different* XLA programs, and CPU XLA's fusion choices (reduce chunking,
    FMA contraction) round shape-dependently — the same insert could then
    differ by ~1 ulp per solver iterate between a standalone GP and a fleet
    lane, breaking the fleet's bit-identity guarantee. The vmapped program
    is bitwise invariant in the lane count (verified T = 1..64 in
    tests/test_fleet.py), so routing the single-GP step through a one-lane
    vmap makes single == fleet-lane hold by construction.
    """
    def wrapped(args, lane_args):
        stacked = jax.tree_util.tree_map(lambda a: a[None], args)
        lane = jax.tree_util.tree_map(lambda a: jnp.asarray(a)[None],
                                      lane_args)
        out = core_call(stacked, lane)
        return jax.tree_util.tree_map(lambda a: a[0], out)

    return wrapped


@partial(jax.jit, static_argnums=(3,))
def _insert_impl(gp: AdditiveGP, x_new: jax.Array, y_new: jax.Array,
                 iters: int) -> AdditiveGP:
    return _lane1(
        lambda s, xy: jax.vmap(
            lambda g, x, y: _insert_core(g, x, y, iters))(s, *xy)
    )(gp, (x_new, y_new))


def insert(gp: AdditiveGP, x_new, y_new, *, iters: int | None = None,
           count: int | None = None) -> AdditiveGP:
    """Grow ``gp`` by one observation with O(q)-window factor updates.

    Posterior mean/variance match a full ``fit`` on the concatenated dataset
    (same factors bit-for-bit outside the insertion window; warm-started
    solve inside). ``iters`` caps the warm backfitting solve; the default
    ``solver_iters // 4`` (>= 8) reconverges from the spliced previous
    solution on well-conditioned problems.

    With free capacity (``n_active < capacity``) the update is fully in
    place: one compiled step serves every insert at that capacity — zero
    recompilation. A full (or unpadded) GP is first re-homed into a
    one-larger allocation, which recompiles; callers that stream many
    inserts should pre-pad via ``fit(..., capacity=)`` /
    ``with_capacity`` (the serving engine grows by doubling).

    ``count`` optionally supplies the host-known active point count; without
    it the capacity-overflow guard reads ``n_active`` back from the device,
    which blocks on the previous insert's computation (one sync per insert —
    callers that track the count, like the serving engine, should pass it
    so back-to-back inserts dispatch asynchronously).

    Drift sentinel (``count is None`` only): checked *before* the mutation,
    on the incoming GP — whose health scalars the previous step already
    materialized, so the fetch rides the same round trip as the ``count``
    guard instead of blocking on the insert just dispatched. The returned GP
    therefore carries THIS insert's drift unchecked until the next mutation
    (one-mutation lag); streams that stop mutating should finish with an
    explicit :func:`maybe_resync`. Engines pass ``count=`` and schedule
    their own sentinel.
    """
    if iters is None:
        iters = max(8, gp.config.solver_iters // 4)
    if count is None:
        gp, _ = maybe_resync(gp)
    if gp.n_active is None:
        gp = with_capacity(gp, gp.n + 1)
    elif (gp.num_points() if count is None else int(count)) >= gp.n:
        gp = with_capacity(gp, gp.n + 1)
    x_new = jnp.asarray(x_new, gp.X.dtype)
    y_new = jnp.asarray(y_new, gp.Y.dtype)
    return _insert_impl(gp, x_new, y_new, int(iters))


def _evict_dim(q: int, k, omega_d, xs_d, sort_d, rank_d, a_d, phi_d, b_d,
               psi_d, p):
    """One dimension's windowed deletion at sorted position ``p``.

    The mirror image of ``_insert_dim``: rows past ``p`` shift up, the O(q)
    window around ``p`` is rebuilt exactly at the new active count ``k - 1``,
    and the permutations update in closed form (the evicted point is original
    index 0, so every surviving original index decrements).
    """
    C = xs_d.shape[0]
    j = jnp.arange(C)
    xs_new = _delete_vec(xs_d, p)
    k1 = k - 1
    sort_new = jnp.where(j < k1, _delete_vec(sort_d, p) - 1,
                         j.astype(sort_d.dtype))
    rank_shift = _delete_vec(rank_d, 0)  # original-index axis shifts down
    rank_new = jnp.where(
        j < k1, rank_shift - (rank_shift > p).astype(rank_d.dtype),
        j.astype(rank_d.dtype))

    ra = 2 * q + 4
    rows_a = jnp.clip(p - ra + jnp.arange(2 * ra + 1), 0, jnp.maximum(k1 - 1, 0))
    a_rows = kp_coefficient_rows(q, omega_d, xs_new, rows_a, n_active=k1)
    a_new = _delete_rows(a_d, p).at[rows_a].set(a_rows)
    kfun = lambda x, y: mk.matern(q, omega_d, x, y)
    phi_rows = gram_band_rows(kfun, xs_new, a_rows, rows_a, q + 1, q + 1, q,
                              n_active=k1)
    phi_new = _delete_rows(phi_d, p).at[rows_a].set(phi_rows)

    rb = 2 * q + 6
    rows_b = jnp.clip(p - rb + jnp.arange(2 * rb + 1), 0, jnp.maximum(k1 - 1, 0))
    b_rows = kp_coefficient_rows(q + 1, omega_d, xs_new, rows_b, n_active=k1)
    b_new = _delete_rows(b_d, p).at[rows_b].set(b_rows)
    dkfun = lambda x, y: mk.matern_domega(q, omega_d, x, y)
    psi_rows = gram_band_rows(dkfun, xs_new, b_rows, rows_b, q + 2, q + 2,
                              q + 1, n_active=k1)
    psi_new = _delete_rows(psi_d, p).at[rows_b].set(psi_rows)
    a_new = canonical_band(a_new, q + 1, q + 1, k1)
    phi_new = canonical_band(phi_new, q, q, k1)
    b_new = canonical_band(b_new, q + 2, q + 2, k1)
    psi_new = canonical_band(psi_new, q + 1, q + 1, k1)
    return xs_new, sort_new, rank_new, a_new, phi_new, b_new, psi_new


def _evict_core(gp: AdditiveGP, iters: int) -> AdditiveGP:
    """Traced drop-oldest evict body — shared by the jitted single-GP step
    and the fleet's masked vmapped tenant-axis step (``_fleet_evict_impl``)."""
    config = gp.config
    q = config.q
    k = jnp.asarray(gp.active(), jnp.int32)
    p = gp.ops.rank_idx[:, 0]  # sorted position of the oldest point, per dim
    with obs.scope("mutation.splice"):
        xs, sort_idx, rank_idx, a, phi, b, psi = jax.vmap(
            lambda om, xd, sd, rd, ad, pd, bd, qd, pp: _evict_dim(
                q, k, om, xd, sd, rd, ad, pd, bd, qd, pp)
        )(gp.omega, gp.xs, gp.ops.sort_idx, gp.ops.rank_idx, gp.ops.A.data,
          gp.ops.Phi.data, gp.B.data, gp.Psi.data, p)
    k1 = k - 1
    A = Banded(a, q + 1, q + 1, k1)
    Phi = Banded(phi, q, q, k1)
    B = Banded(b, q + 2, q + 2, k1)
    Psi = Banded(psi, q + 1, q + 1, k1)
    SAPhi = add(scale(A, gp.sigma**2), Phi)
    ops = DimOps(A=A, Phi=Phi, SAPhi=SAPhi, sort_idx=sort_idx,
                 rank_idx=rank_idx, sigma2=gp.sigma**2, n_active=k1)
    # original order shifts down by one everywhere (index 0 evicted)
    X = _delete_rows(gp.X, 0)
    Y = mask_rows(_delete_vec(gp.Y, 0), k1, axis=0)
    # warm start: the surviving entries of the pre-evict solution
    x0 = mask_rows(jax.vmap(lambda u: _delete_vec(u, 0))(gp.u_sy), k1, axis=1)
    hier = (build_gp_hier(config, gp.omega, gp.sigma, X, xs, ops)
            if config.precond == "kmg" else None)
    if config.health == "on":
        u_sy, bY, info = mean_caches(config, ops, Y, x0=x0, iters=iters,
                                     hier=hier, return_info=True)
    else:
        u_sy, bY = mean_caches(config, ops, Y, x0=x0, iters=iters, hier=hier)
    Gband, Hband, drift = _mutated_gband(gp, ops, p, k1, evicting=True)
    health = _mutated_health(gp, info if config.health == "on" else None,
                             drift)
    return AdditiveGP(X=X, Y=Y, omega=gp.omega, sigma=gp.sigma, xs=xs,
                      ops=ops, B=B, Psi=Psi, bY=bY, u_sy=u_sy, Gband=Gband,
                      Hband=Hband, config=config, n_active=k1, hier=hier,
                      health=health)


@partial(jax.jit, static_argnums=(1,))
def _evict_impl(gp: AdditiveGP, iters: int) -> AdditiveGP:
    return _lane1(
        lambda s, _: jax.vmap(lambda g: _evict_core(g, iters))(s)
    )(gp, ())


def evict(gp: AdditiveGP, *, iters: int | None = None,
          count: int | None = None) -> AdditiveGP:
    """Drop the *oldest* observation (sliding-window mode) — in place.

    The capacity (and therefore peak memory and the compiled step) is
    unchanged: the freed slot becomes padding and the next ``insert`` reuses
    it. ``insert`` + ``evict`` pairs at a fixed capacity are the
    bounded-memory serving loop of a long-running stream. ``iters`` caps the
    warm re-solve exactly like ``insert``'s; ``count`` is the same optional
    host-known active count (skips the device sync of the emptiness guard).
    The drift sentinel runs pre-mutation on the incoming GP exactly like
    ``insert``'s (same one-mutation lag; same explicit trailing
    :func:`maybe_resync` for streams that stop mutating).
    """
    if iters is None:
        iters = max(8, gp.config.solver_iters // 4)
    if count is None:
        gp, _ = maybe_resync(gp)
    if gp.n_active is None:
        gp = with_capacity(gp, gp.n)  # mark active count; capacity unchanged
    if (gp.num_points() if count is None else int(count)) <= 1:
        raise ValueError("cannot evict from a GP with a single observation")
    return _evict_impl(gp, int(iters))


def _resync_core(gp: AdditiveGP) -> AdditiveGP:
    """Traced exact-resync body — shared by the single-GP and fleet steps."""
    Gband, Hband = variance_band(gp.ops.A, gp.ops.Phi,
                                 backend=gp.config.backend, return_h=True)
    health = None if gp.health is None else gp.health.after_resync()
    return dataclasses.replace(gp, Gband=Gband, Hband=Hband, health=health)


@jax.jit
def _resync_impl(gp: AdditiveGP) -> AdditiveGP:
    """Exact full-RGF recompute of the variance caches + sentinel reset."""
    return _resync_core(gp)


@jax.jit
def _fleet_resync_impl(stack: AdditiveGP, do: jax.Array) -> AdditiveGP:
    new = jax.vmap(_resync_core)(stack)
    return select_tenants(do, new, stack)


def fleet_resync(fleet: GPFleet, do=None) -> GPFleet:
    """Masked exact Gband resync over selected tenant lanes — ONE compiled
    step. The fleet engine's sentinel dispatches this when a lane's
    accumulated windowed-Gband drift crosses the threshold; unselected
    lanes are returned bit-identical to their inputs."""
    do_h = (np.ones(fleet.T, bool) if do is None else np.asarray(do, bool))
    return GPFleet(gp=_fleet_resync_impl(fleet.gp, jnp.asarray(do_h)))


def resync_gband(gp: AdditiveGP) -> AdditiveGP:
    """Recompute ``Gband``/``Hband`` exactly with the O(n) RGF sweep.

    The escape hatch the drift sentinel dispatches: discards whatever the
    windowed maintenance accumulated (truncation on densely oversampled
    streams, long-stream roundoff) and zeroes the sentinel counters. One
    jitted program per capacity; the healthy mutation path never calls it.
    """
    return _resync_impl(gp)


def maybe_resync(gp: AdditiveGP, *, drift_tol: float = hv.DRIFT_TOL,
                 every: int = hv.RESYNC_EVERY):
    """Host-side Gband drift sentinel. Returns ``(gp, resynced)``.

    Reads the accumulated truncation estimate off ``gp.health`` (one device
    fetch of two scalars) and dispatches :func:`resync_gband` when it
    crosses ``drift_tol`` or after ``every`` windowed mutations — turning
    the windowed-Gband truncation contract (see ``core/gband_update.py``)
    into an automatic guarantee instead of a manual ``REPRO_GBAND=full``.
    No-op (never syncs) for health-off GPs and ``gband="full"`` configs.
    """
    if gp.health is None or gp.config.gband == "full":
        return gp, False
    drift, muts = jax.device_get((gp.health.drift, gp.health.muts))
    if float(drift) > drift_tol or int(muts) >= every:
        return _resync_impl(gp), True
    return gp, False


@partial(jax.jit, static_argnums=(4,))
def _fleet_insert_impl(stack: AdditiveGP, do: jax.Array, x_new: jax.Array,
                       y_new: jax.Array, iters: int) -> AdditiveGP:
    """Masked vmapped insert over a tenant stack: every lane runs the same
    traced body, lanes with ``do[t]`` False keep their old state.

    The keep/discard choice is a ``jnp.where`` select per leaf, so whatever a
    discarded lane computed (e.g. the dropped out-of-range writes of an
    insert into a full lane) can never reach a kept lane.
    """
    new = jax.vmap(lambda g, x, y: _insert_core(g, x, y, iters))(
        stack, x_new, y_new)
    return select_tenants(do, new, stack)


@partial(jax.jit, static_argnums=(2,))
def _fleet_evict_impl(stack: AdditiveGP, do: jax.Array,
                      iters: int) -> AdditiveGP:
    """Masked vmapped drop-oldest evict over a tenant stack."""
    new = jax.vmap(lambda g: _evict_core(g, iters))(stack)
    return select_tenants(do, new, stack)


def fleet_insert(fleet: GPFleet, x_new, y_new, do=None, *,
                 iters: int | None = None, counts=None) -> GPFleet:
    """Insert one observation into each selected tenant — ONE compiled step.

    ``x_new`` (T, D), ``y_new`` (T,); ``do`` (T,) bool selects the tenants
    that mutate this round (default: all). Selected lanes must have free
    capacity — re-home the fleet to a doubled tier first (the fleet engine
    does this per tenant); a full selected lane raises. ``counts`` optionally
    supplies the host-known per-tenant active counts, skipping the device
    sync of the guard exactly like ``insert(..., count=)``.

    Each selected tenant's post-insert state is bit-identical to running the
    single-GP ``insert`` on its unstacked GP; unselected lanes are returned
    bit-identical to their inputs.
    """
    if iters is None:
        iters = max(8, fleet.config.solver_iters // 4)
    T = fleet.T
    do_h = np.ones(T, bool) if do is None else np.asarray(do, bool)
    counts_h = np.asarray(fleet.counts() if counts is None else counts)
    if np.any(do_h & (counts_h >= fleet.capacity)):
        full = np.nonzero(do_h & (counts_h >= fleet.capacity))[0]
        raise ValueError(
            f"fleet_insert into full tenant lanes {full.tolist()} at capacity "
            f"{fleet.capacity}; re-home those tenants to a larger tier first")
    x_new = jnp.asarray(x_new, fleet.gp.X.dtype)
    y_new = jnp.asarray(y_new, fleet.gp.Y.dtype)
    return GPFleet(gp=_fleet_insert_impl(fleet.gp, jnp.asarray(do_h), x_new,
                                         y_new, int(iters)))


def fleet_evict(fleet: GPFleet, do=None, *, iters: int | None = None,
                counts=None) -> GPFleet:
    """Drop the oldest observation of each selected tenant — ONE compiled
    step. Selected lanes must keep >= 1 observation (a 1-point selected lane
    raises); see :func:`fleet_insert` for ``do`` / ``counts`` semantics."""
    if iters is None:
        iters = max(8, fleet.config.solver_iters // 4)
    T = fleet.T
    do_h = np.ones(T, bool) if do is None else np.asarray(do, bool)
    counts_h = np.asarray(fleet.counts() if counts is None else counts)
    if np.any(do_h & (counts_h <= 1)):
        low = np.nonzero(do_h & (counts_h <= 1))[0]
        raise ValueError(
            f"fleet_evict from tenant lanes {low.tolist()} holding a single "
            "observation")
    return GPFleet(gp=_fleet_evict_impl(fleet.gp, jnp.asarray(do_h),
                                        int(iters)))


def refresh_local_cache(gp: AdditiveGP, cache: LocalAcqCache, *,
                        mode: str = "window",
                        exact_radius: int | None = None) -> LocalAcqCache:
    """Update the dense ``M~`` acquisition cache after one ``insert``.

    ``gp`` is the post-insert GP (n points); ``cache`` is the pre-insert
    cache (n-1 points). Requires a *full* GP (``n_active == capacity`` — the
    shape of the dense cache tracks the point count, so the capacity-padded
    partial case has no O(1) cache to refresh). The spliced row/column at
    each dimension's insertion position start as copies of the nearest
    sorted neighbour:

      * ``mode="copy"`` stops there — zero solves, the paper's O(1)
        small-learning-rate path. Entries are stale by the (exponentially
        decaying) change of ``Mhat^{-1}`` around the new point.
      * ``mode="window"`` additionally recomputes the columns within
        ``exact_radius`` (default 2q+4) of each insertion exactly, using one
        narrow batched solve — O(q D) right-hand sides instead of the
        O(n D) full rebuild of ``build_local_cache``.
    """
    D, n = gp.D, gp.n
    if gp.num_points() != n:
        raise ValueError(
            "refresh_local_cache needs a full GP (n_active == capacity); "
            f"got {gp.num_points()} active of {n}")
    q = gp.config.q
    R = exact_radius if exact_radius is not None else 2 * q + 4
    M = cache.M_tilde  # (D, n-1, D, n-1), sorted indices on both sides
    p = gp.ops.rank_idx[:, n - 1]  # (D,) sorted insert position per dim
    j = jnp.arange(n)
    src = jnp.clip(j[None, :] - (j[None, :] > p[:, None]), 0, n - 2)  # (D, n)
    d_i = jnp.arange(D)[:, None, None, None]
    e_i = jnp.arange(D)[None, None, :, None]
    M1 = M[d_i, src[:, :, None, None], e_i, src[None, None, :, :]]
    if mode == "copy":
        return LocalAcqCache(M_tilde=M1)
    if mode != "window":
        raise ValueError(f"unknown mode {mode!r}; expected 'copy' or 'window'")

    W = 2 * R + 1
    c_idx = jnp.clip(p[:, None] - R + jnp.arange(W)[None, :], 0, n - 1)  # (D, W)
    K = D * W
    rhs = jnp.zeros((D, n, K), M.dtype)
    rhs = rhs.at[jnp.repeat(jnp.arange(D), W), c_idx.reshape(-1),
                 jnp.arange(K)].set(1.0)
    pv, be, sa = gp.config.pivot, gp.config.backend, gp.config.solve_alg
    ws = solve(gp.ops.Phi, rhs, pivot=pv, backend=be, alg=sa)
    w = gp.ops.from_sorted(ws)
    z = solve_mhat(gp.ops, w, gp.config.solve_cfg(), hier=gp.hier)
    y = solve(transpose(gp.ops.Phi), gp.ops.to_sorted(z), pivot=pv, backend=be,
              alg=sa)
    cols = y.reshape(D, n, D, W)  # cols[d, i, e, k] = M_new[d, i, e, c_idx[e, k]]
    M1 = M1.at[d_i, jnp.arange(n)[None, :, None, None], e_i,
               c_idx[None, None, :, :]].set(cols)
    # mirror into the rows (M~ is symmetric)
    M1 = M1.at[jnp.arange(D)[:, None, None, None], c_idx[:, :, None, None],
               jnp.arange(D)[None, None, :, None],
               jnp.arange(n)[None, None, None, :]].set(cols.transpose(2, 3, 0, 1))
    return LocalAcqCache(M_tilde=M1)
