"""Multi-tenant posterior fleet: thousands of independent GPs as ONE program.

A :class:`GPFleet` stacks ``T`` capacity-padded :class:`AdditiveGP` pytrees
along a leading *tenant* axis: every data leaf gains a ``(T, ...)`` batch
dim (``n_active`` becomes the ``(T,)`` per-tenant active count) while the
static ``GPConfig`` is shared. Because the PR-5 capacity representation made
every per-tenant array shape-stable — static capacity, traced active length,
canonicalized padding — a fleet is *just* this stacking plus ``jax.vmap``:

  * queries (``fleet_posterior_mean`` / ``fleet_posterior_var`` /
    ``fleet_acquisition_stats``) vmap the single-GP entry points over the
    tenant axis. Each tenant's result is bit-identical (f64) to the same
    call on its unstacked GP: no op in the core mixes tenants (all
    reductions are over per-tenant axes), so vmap is exact batching, not an
    approximation.
  * the pallas kernels never dispatch per tenant: every wrapper in
    ``repro.kernels.ops`` flattens leading batch dims into the kernel grid
    (``_flatten_batch``), and under vmap the ``pallas_call`` batching rule
    prepends the tenant axis to that grid — tenants x D x RHS-batch become
    one grid, ONE ``pallas_call`` per op (and one fused sweep call per
    backfitting iteration) for the whole fleet.
  * per-tenant mutations (the streaming insert/evict tenant-axis steps) live
    in ``repro.streaming.updates.fleet_insert`` / ``fleet_evict`` — masked
    vmapped bodies so any subset of tenants mutates in one compiled step.

The tenant axis is a *data* axis for sharding: ``repro.distributed.sharding``
maps the logical ``tenant`` dim to the ``(pod, data)`` mesh axes
(MaxText-style batch sharding) with divisibility fallback to replication —
see ``fleet_pspecs`` there.

Tenants in one stack must share (static) capacity, D, dtype and GPConfig;
heterogeneous populations are served as one stack *per capacity tier* by
``repro.streaming.GPFleetEngine``, which also owns per-tenant versioned
mutation fences, sliding windows and tier re-homing.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .additive_gp import (AdditiveGP, GPConfig, _fit_impl, _with_capacity_impl,
                          posterior_mean, posterior_var, resolve_config,
                          with_capacity)
from .bayesopt import acquisition_stats

__all__ = ["GPFleet", "stack_gps", "fleet_fit", "fleet_posterior_mean",
           "fleet_posterior_var", "fleet_acquisition_stats", "tenant_gp",
           "select_tenants", "replicate_gp"]


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("gp",),
    meta_fields=(),
)
@dataclasses.dataclass(frozen=True)
class GPFleet:
    """Stacked fleet: an ``AdditiveGP`` whose every data leaf carries a
    leading ``(T,)`` tenant axis (``n_active``: ``(T,)`` per-tenant counts).
    """

    gp: AdditiveGP

    @property
    def T(self) -> int:
        return self.gp.X.shape[0]

    @property
    def capacity(self) -> int:
        return self.gp.X.shape[1]

    @property
    def D(self) -> int:
        return self.gp.X.shape[2]

    @property
    def config(self) -> GPConfig:
        return self.gp.config

    def counts(self) -> np.ndarray:
        """Per-tenant active observation counts (host-side sync)."""
        return np.asarray(self.gp.n_active)

    def tenant(self, i) -> AdditiveGP:
        """Extract tenant ``i`` as a standalone capacity-padded GP."""
        return tenant_gp(self.gp, jnp.asarray(i, jnp.int32))


@jax.jit
def tenant_gp(stack: AdditiveGP, lane) -> AdditiveGP:
    """Gather one tenant's GP out of a stacked fleet pytree (traced lane)."""
    return jax.tree_util.tree_map(lambda a: a[lane], stack)


@jax.jit
def set_tenant_gp(stack: AdditiveGP, gp: AdditiveGP, lane) -> AdditiveGP:
    """Write a single GP into lane ``lane`` of a stacked fleet pytree."""
    return jax.tree_util.tree_map(lambda a, b: a.at[lane].set(b), stack, gp)


def replicate_gp(gp: AdditiveGP, T: int) -> AdditiveGP:
    """Broadcast one capacity-padded GP into a ``T``-lane stack."""
    if gp.n_active is None:
        gp = with_capacity(gp, gp.n)
    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (T,) + a.shape), gp)


def select_tenants(do, new_stack: AdditiveGP, old_stack: AdditiveGP):
    """Per-lane pytree select: lane t takes ``new`` where ``do[t]``.

    ``jnp.where`` (a select, not arithmetic), so NaN/garbage computed in a
    discarded lane can never leak into a kept one.
    """
    do = jnp.asarray(do)

    def sel(a, b):
        d = do.reshape(do.shape + (1,) * (a.ndim - do.ndim))
        return jnp.where(d, a, b)

    return jax.tree_util.tree_map(sel, new_stack, old_stack)


def stack_gps(gps, capacity: int | None = None) -> GPFleet:
    """Stack fitted GPs into one fleet (leading tenant axis).

    All tenants must share D, dtype and (resolved) ``GPConfig``; they are
    re-homed to a common capacity first (the max, or ``capacity``) — pure
    padding, so each tenant's stacked state equals its standalone state
    bit-for-bit on the active prefix.
    """
    if not gps:
        raise ValueError("stack_gps needs at least one GP")
    cap = max(g.n for g in gps)
    if capacity is not None:
        if capacity < cap:
            raise ValueError(
                f"capacity {capacity} < largest tenant allocation {cap}")
        cap = capacity
    cfg0 = gps[0].config
    for g in gps:
        if g.config != cfg0:
            raise ValueError(
                "all fleet tenants must share one GPConfig; got "
                f"{g.config} vs {cfg0}")
    padded = [with_capacity(g, cap) for g in gps]
    stacked = jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *padded)
    return GPFleet(gp=stacked)


@partial(jax.jit, static_argnums=(0, 5))
def _fleet_fit_impl(config: GPConfig, X, Y, omega, sigma,
                    capacity: int) -> AdditiveGP:
    def one(Xt, Yt, om, sg):
        return _with_capacity_impl(_fit_impl(config, Xt, Yt, om, sg), capacity)

    return jax.vmap(one)(X, Y, omega, sigma)


def fleet_fit(config: GPConfig, X, Y, omega, sigma,
              capacity: int) -> GPFleet:
    """Fit ``T`` tenants in one vmapped program: X ``(T, n, D)``, Y
    ``(T, n)``, omega ``(T, D)``, sigma ``(T,)`` (or scalar, broadcast).

    One trace, one kernel grid over all tenants; each tenant's fit equals
    ``fit(config, X[t], Y[t], omega[t], sigma[t], capacity=capacity)``.
    Backend / solve-alg / fused resolution happens once here, exactly like
    ``fit``.
    """
    X = jnp.asarray(X)
    T, n, D = X.shape
    if capacity < n:
        raise ValueError(f"capacity {capacity} < n {n}")
    config = resolve_config(config, n, jnp.result_type(X, jnp.asarray(Y)))
    sigma = jnp.broadcast_to(jnp.asarray(sigma, X.dtype), (T,))
    omega = jnp.broadcast_to(jnp.asarray(omega, X.dtype), (T, D))
    return GPFleet(gp=_fleet_fit_impl(config, X, jnp.asarray(Y), omega, sigma,
                                      int(capacity)))


# ---------------------------------------------------------------------------
# vmapped query paths — one jitted program per (T, capacity, m) shape
# ---------------------------------------------------------------------------


@jax.jit
def fleet_posterior_mean(fleet: GPFleet, Xq: jax.Array) -> jax.Array:
    """Per-tenant posterior means: Xq ``(T, m, D)`` -> ``(T, m)``."""
    return jax.vmap(posterior_mean)(fleet.gp, Xq)


@jax.jit
def fleet_posterior_var(fleet: GPFleet, Xq: jax.Array) -> jax.Array:
    """Per-tenant posterior variances: Xq ``(T, m, D)`` -> ``(T, m)``."""
    return jax.vmap(posterior_var)(fleet.gp, Xq)


@partial(jax.jit, static_argnames=("kind",))
def fleet_acquisition_stats(fleet: GPFleet, Xq: jax.Array, beta, best_y,
                            kind: str = "ucb"):
    """Per-tenant ``(value, grad, mean, variance)`` in one vmapped pass.

    Xq ``(T, m, D)``; ``beta`` / ``best_y`` scalars or ``(T,)`` per-tenant.
    """
    T = fleet.T
    dt = Xq.dtype
    beta = jnp.broadcast_to(jnp.asarray(beta, dt), (T,))
    best_y = jnp.broadcast_to(jnp.asarray(best_y, dt), (T,))
    return jax.vmap(
        lambda gp, X, b, by: acquisition_stats(gp, X, b, by, kind=kind)
    )(fleet.gp, Xq, beta, best_y)
