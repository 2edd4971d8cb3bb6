"""Bayesian optimization with sparse additive-GP posteriors (paper Sec. 6).

Acquisition functions (GP-UCB, EI) and their gradients are computed from the
sparse KP windows: the mean/gradient terms are O(1) gathers per query given
the fitted caches, and the variance term costs one batched ``Mhat`` solve per
query batch (the "operator" path) or O(1) with the dense ``M-tilde`` cache
(the paper's "given the posterior" path — O(n^2) memory, small-n only).

The gradient formulas follow Eq. (29)-(30); they are verified against finite
differences of ``posterior_var`` in tests (the paper's Eq. (30) drops a
factor of 2 on the band term; we use the calculus-derived version).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..masking import mask_rows, tree_sum
from .additive_gp import (AdditiveGP, GPConfig, fit, fit_hyperparams,
                          _phi_windows, prior_var)
from .backfitting import solve_mhat
from .banded import Banded, solve, transpose
from .kernel_packets import phi_grad_at

__all__ = [
    "BOConfig",
    "acquisition_value_and_grad",
    "acquisition_stats",
    "propose_next",
    "bayes_opt_loop",
    "LocalAcqCache",
    "build_local_cache",
    "acq_local",
]


@partial(
    jax.tree_util.register_dataclass,
    data_fields=(),
    meta_fields=("kind", "beta", "ascent_steps", "lr", "n_starts", "refit_every",
                 "hyper_steps", "hyper_lr", "incremental", "use_engine",
                 "insert_iters"),
)
@dataclasses.dataclass(frozen=True)
class BOConfig:
    kind: str = "ucb"  # "ucb" | "ei"
    beta: float = 2.0
    ascent_steps: int = 40
    lr: float = 0.05
    n_starts: int = 32
    refit_every: int = 10  # hyperparameter re-learning cadence (0 = never)
    hyper_steps: int = 10
    hyper_lr: float = 0.05
    # Sec. 6 streaming path (repro.streaming): grow the posterior by
    # O(q)-window inserts between refit rounds / serve the acquisition ascent
    # from the slot-batched engine. False = legacy refit-every-round loop.
    incremental: bool = True
    use_engine: bool = True
    insert_iters: int = 0  # warm backfitting iters per insert (0 = auto)


def _grad_windows(gp: AdditiveGP, Xq: jax.Array):
    q = gp.config.q
    na = gp.n_active

    def per_dim(om, x_sorted, a_data, xq_d):
        A_d = Banded(a_data, q + 1, q + 1)
        return phi_grad_at(q, om, x_sorted, A_d, xq_d, n_active=na)

    return jax.vmap(per_dim)(gp.omega, gp.xs, gp.ops.A.data, Xq.T)


def _acq_core(gp: AdditiveGP, Xq: jax.Array, beta, best_y, kind: str):
    """Shared acquisition math: (value, grad, mean, variance) for Xq (m, D)."""
    q = gp.config.q
    D, n = gp.D, gp.n
    m = Xq.shape[0]
    with obs.scope("acq.mean"):
        rows, vals, _ = _phi_windows(gp, Xq)          # (D, m, W)
        rows_g, dvals, _ = _grad_windows(gp, Xq)      # same sparsity

        # mean + mean gradient (sparse gathers on bY)
        bwin = jnp.take_along_axis(gp.bY[:, None, :], rows, axis=2)
        mu = jnp.sum(vals * bwin, axis=(0, 2))                       # (m,)
        dmu = jnp.sum(dvals * bwin, axis=2).T                        # (m, D)

    with obs.scope("acq.variance"):
        # variance pieces
        W = 2 * q + 2
        hw = gp.Gband.lo
        off = jnp.arange(W)[None, :] - jnp.arange(W)[:, None]
        g_entries = gp.Gband.data[
            jnp.arange(D)[:, None, None, None], rows[:, :, :, None],
            hw + off[None, None, :, :],
        ]                                                        # (D, m, W, W)
        g_phi = jnp.einsum("dmab,dmb->dma", g_entries, vals)     # (G phi)|window
        term2 = jnp.einsum("dma,dma->m", vals, g_phi)

        phi_dense = jnp.zeros((D, n, m), Xq.dtype)
        d_idx = jnp.broadcast_to(jnp.arange(D)[:, None, None], rows.shape)
        m_idx = jnp.broadcast_to(jnp.arange(m)[None, :, None], rows.shape)
        phi_dense = phi_dense.at[d_idx, rows, m_idx].add(vals)
        ws = solve(gp.ops.Phi, phi_dense, pivot=gp.config.pivot,
                   backend=gp.config.backend,
                   alg=gp.config.solve_alg)                         # sorted
        w = gp.ops.from_sorted(ws)
        z = solve_mhat(gp.ops, w, gp.config.solve_cfg(), hier=gp.hier)
        # fixed-association reduction over the (D, capacity) axes: the zero
        # tail collapses bitwise, so the padded acquisition variance equals
        # the unpadded one bit-for-bit at any capacity tier (and any vmap)
        term3 = tree_sum(tree_sum(w * z, axis=1), axis=0)
        var = jnp.maximum(prior_var(gp, Xq.dtype) - term2 + term3, 1e-12)

    with obs.scope("acq.grad"):
        # variance gradient:
        # dvar/dx_d = -2 dphi^T (G phi) + 2 dphi^T Phi^{-T} z
        y_s = solve(transpose(gp.ops.Phi), gp.ops.to_sorted(z),
                    pivot=gp.config.pivot, backend=gp.config.backend,
                    alg=gp.config.solve_alg)
        ywin = y_s[d_idx, rows, m_idx]  # (D, m, W): y_s[d, rows[d,m,w], m]
        dvar = (-2.0 * jnp.einsum("dma,dma->dm", dvals, g_phi)
                + 2.0 * jnp.einsum("dma,dma->dm", dvals, ywin)).T    # (m, D)

        if kind == "ucb":
            sqrt_s = jnp.sqrt(var)
            val = mu + beta * sqrt_s
            grad = dmu + (beta / (2.0 * sqrt_s))[:, None] * dvar
        elif kind == "ei":
            sqrt_s = jnp.sqrt(var)
            imp = mu - best_y
            zz = imp / sqrt_s
            pdf = jnp.exp(-0.5 * zz**2) / jnp.sqrt(2.0 * jnp.pi)
            cdf = 0.5 * (1.0 + jax.scipy.special.erf(zz / jnp.sqrt(2.0)))
            val = imp * cdf + sqrt_s * pdf
            dval_dmu = cdf
            dval_ds = pdf / (2.0 * sqrt_s)
            grad = dval_dmu[:, None] * dmu + dval_ds[:, None] * dvar
        else:
            raise ValueError(kind)
    return val, grad, mu, var


@partial(jax.jit, static_argnames=("kind",))
def acquisition_value_and_grad(gp: AdditiveGP, Xq: jax.Array, beta, best_y,
                               kind: str = "ucb"):
    """(A(x*), grad A(x*)) for a batch Xq (m, D) — Eq. (28)-(29)."""
    val, grad, _, _ = _acq_core(gp, Xq, beta, best_y, kind)
    return val, grad


@partial(jax.jit, static_argnames=("kind",))
def acquisition_stats(gp: AdditiveGP, Xq: jax.Array, beta, best_y,
                      kind: str = "ucb"):
    """(value, grad, mean, variance) in one pass — the serving-engine step."""
    return _acq_core(gp, Xq, beta, best_y, kind)


def ascent_step(X: jax.Array, grad: jax.Array, lo, hi, step_len) -> jax.Array:
    """One normalized projected-gradient ascent update (shared with the
    serving engine, which must reproduce ``propose_next`` tick-for-tick)."""
    gn = jnp.linalg.norm(grad, axis=1, keepdims=True)
    return jnp.clip(X + step_len * grad / jnp.maximum(gn, 1e-12), lo, hi)


@partial(jax.jit, static_argnames=("cfg",))
def propose_next(gp: AdditiveGP, bounds: jax.Array, key: jax.Array,
                 cfg: BOConfig, best_y) -> jax.Array:
    """Multi-start projected gradient ascent on the acquisition (Sec. 6)."""
    D = gp.D
    lo, hi = bounds[:, 0], bounds[:, 1]
    starts = jax.random.uniform(key, (cfg.n_starts, D), dtype=bounds.dtype)
    X0 = lo + starts * (hi - lo)
    span = hi - lo

    def body(_, X):
        _, g = acquisition_value_and_grad(gp, X, cfg.beta, best_y, kind=cfg.kind)
        return ascent_step(X, g, lo, hi, cfg.lr * span)

    X = jax.lax.fori_loop(0, cfg.ascent_steps, body, X0)
    val, _ = acquisition_value_and_grad(gp, X, cfg.beta, best_y, kind=cfg.kind)
    return X[jnp.argmax(val)]


def bayes_opt_loop(
    f: Callable[[jax.Array], float],
    bounds: jax.Array,
    budget: int,
    gp_config: GPConfig,
    bo_config: BOConfig,
    key: jax.Array,
    n_init: int = 20,
    omega0=None,
    sigma0: float = 0.5,
    verbose: bool = False,
):
    """Algorithm 1 with sparse posteriors; maximizes ``f``. Returns history.

    Sec. 6 streaming path (the default): between hyperparameter refits the
    posterior is grown by ``repro.streaming.insert`` — O(q)-window factor
    updates plus a warm-started backfitting solve — instead of a full
    O(n log n) refit, and the acquisition ascent is served by the
    slot-batched ``GPServeEngine``. Hyperparameter refits always re-seed the
    optimizer from the previously *learned* ``(omega, sigma)``, never the
    config defaults; the per-round values are recorded in
    ``hist["omega"]``/``hist["sigma"]``. Set
    ``BOConfig(incremental=False, use_engine=False)`` for the legacy loop.
    """
    D = bounds.shape[0]
    key, sub = jax.random.split(key)
    lo, hi = bounds[:, 0], bounds[:, 1]
    X = lo + jax.random.uniform(sub, (n_init, D), dtype=bounds.dtype) * (hi - lo)
    Y = jnp.asarray([f(x) for x in X], bounds.dtype)
    omega = (jnp.ones((D,), bounds.dtype) * (4.0 / (hi - lo))
             if omega0 is None else jnp.asarray(omega0))
    sigma = jnp.asarray(sigma0, bounds.dtype)
    hist = {"x": [], "y": [], "best": [], "omega": [], "sigma": []}
    gp = fit(gp_config, X, Y, omega, sigma)
    engine = None
    if bo_config.use_engine or bo_config.incremental:
        from ..streaming import GPServeEngine, insert as stream_insert, \
            propose_via_engine
    if bo_config.use_engine:
        engine = GPServeEngine(gp, bounds, batch_slots=bo_config.n_starts,
                               kind=bo_config.kind, beta=bo_config.beta,
                               lr=bo_config.lr,
                               insert_iters=bo_config.insert_iters or None)
    for t in range(budget):
        key, k1, k2 = jax.random.split(key, 3)
        if bo_config.refit_every and t % bo_config.refit_every == 0 and t > 0:
            # warm init: the previously learned (omega, sigma) seed the refit
            gp, (omega, sigma), _ = fit_hyperparams(
                gp_config, X, Y, omega, sigma, k2,
                steps=bo_config.hyper_steps, lr=bo_config.hyper_lr,
            )
            if engine is not None:
                engine.set_posterior(gp)
        best_y = jnp.max(Y)
        if engine is not None:
            x_new = propose_via_engine(engine, k1, bo_config, best_y)
        else:
            x_new = propose_next(gp, bounds, k1, bo_config, best_y)
        y_new = f(x_new)
        X = jnp.concatenate([X, x_new[None]], axis=0)
        Y = jnp.concatenate([Y, jnp.asarray([y_new], Y.dtype)])
        if bo_config.incremental:
            if engine is not None:
                # in-place capacity insert behind the engine fence: one
                # compiled step per capacity tier, no retrace per round
                engine.insert(np.asarray(x_new), float(y_new))
                engine.step()  # drain/apply so engine.gp is current
                gp = engine.gp
            else:
                gp = stream_insert(gp, x_new, jnp.asarray(y_new, Y.dtype),
                                   iters=bo_config.insert_iters or None)
        else:
            gp = fit(gp_config, X, Y, omega, sigma)
            if engine is not None:
                engine.set_posterior(gp)
        hist["x"].append(np.asarray(x_new))
        hist["y"].append(float(y_new))
        hist["best"].append(float(jnp.max(Y)))
        # host-side copies: every hist field is numpy/python — appending the
        # device array would retain traced buffers for the loop's lifetime
        hist["omega"].append(np.asarray(omega))
        hist["sigma"].append(float(sigma))
        if verbose and (t + 1) % 10 == 0:
            print(f"  BO iter {t+1}/{budget} best={hist['best'][-1]:.4f}")
    return gp, X, Y, hist


# ---------------------------------------------------------------------------
# Paper's O(1)-per-evaluation path: dense M-tilde cache ("given the posterior")
# ---------------------------------------------------------------------------


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("M_tilde",),
    meta_fields=(),
)
@dataclasses.dataclass(frozen=True)
class LocalAcqCache:
    """Dense M~ = Phi^{-T} P^T Mhat^{-1} P Phi^{-1}, laid out (D, n, D, n)."""

    M_tilde: jax.Array


def build_local_cache(gp: AdditiveGP) -> LocalAcqCache:
    """Operation 2 of Sec. 5.1.1 — O(n^2) time/memory; small n only.

    Layout: ``M_tilde[d_row, i_row, d_col, i_col]`` in sorted indices on both
    sides. ``Mhat`` is SPD, so ``M~`` equals its ``(d,i) <-> (e,j)``
    transpose (pinned by a symmetry test). Under capacity padding the e_i
    right-hand sides are masked to the active prefix, so padded tail
    rows/columns are exact zeros and the active block matches the unpadded
    cache bit-for-bit (no identity-tail garbage in the dense cache).
    """
    D, n = gp.D, gp.n
    eye = mask_rows(jnp.eye(n, dtype=gp.Y.dtype), gp.n_active, axis=0)
    cols = []
    for d in range(D):
        rhs = jnp.zeros((D, n, n), gp.Y.dtype).at[d].set(eye)  # Phi^{-1} e_i batch
        ws = solve(gp.ops.Phi, rhs, pivot=gp.config.pivot,
                   backend=gp.config.backend, alg=gp.config.solve_alg)
        w = gp.ops.from_sorted(ws)
        z = solve_mhat(gp.ops, w, gp.config.solve_cfg(), hier=gp.hier)
        y = solve(transpose(gp.ops.Phi), gp.ops.to_sorted(z),
                  pivot=gp.config.pivot, backend=gp.config.backend,
                  alg=gp.config.solve_alg)
        cols.append(y)  # (D, n, n): row block d', cols for dim d
    M = jnp.stack(cols, axis=2)  # [d_row, i_row, d_col, i_col]
    return LocalAcqCache(M_tilde=M)


@partial(jax.jit, static_argnames=("kind",))
def acq_local(gp: AdditiveGP, cache: LocalAcqCache, xq: jax.Array, beta, best_y,
              kind: str = "ucb"):
    """O(1) acquisition value+grad at a single point given the dense cache."""
    Xq = xq[None, :]
    q = gp.config.q
    D = gp.D
    W = 2 * q + 2
    rows, vals, _ = _phi_windows(gp, Xq)      # (D, 1, W)
    _, dvals, _ = _grad_windows(gp, Xq)
    rows = rows[:, 0]
    vals = vals[:, 0]
    dvals = dvals[:, 0]

    bwin = jnp.take_along_axis(gp.bY, rows, axis=1)
    mu = jnp.sum(vals * bwin)
    dmu = jnp.sum(dvals * bwin, axis=1)

    hw = gp.Gband.lo
    off = jnp.arange(W)[None, :] - jnp.arange(W)[:, None]
    g_entries = gp.Gband.data[
        jnp.arange(D)[:, None, None], rows[:, :, None], hw + off[None]
    ]
    g_phi = jnp.einsum("dab,db->da", g_entries, vals)
    term2 = jnp.einsum("da,da->", vals, g_phi)

    # M~ window block: (D, W, D, W) gather
    mwin = cache.M_tilde[
        jnp.arange(D)[:, None, None, None], rows[:, :, None, None],
        jnp.arange(D)[None, None, :, None], rows[None, None, :, :],
    ]
    term3 = jnp.einsum("da,daeb,eb->", vals, mwin, vals)
    var = jnp.maximum(prior_var(gp, xq.dtype) - term2 + term3, 1e-12)
    dvar = -2.0 * jnp.einsum("da,da->d", dvals, g_phi) + 2.0 * jnp.einsum(
        "da,daeb,eb->d", dvals, mwin, vals
    )

    sqrt_s = jnp.sqrt(var)
    if kind == "ucb":
        return mu + beta * sqrt_s, dmu + beta / (2.0 * sqrt_s) * dvar
    imp = mu - best_y
    zz = imp / sqrt_s
    pdf = jnp.exp(-0.5 * zz**2) / jnp.sqrt(2.0 * jnp.pi)
    cdf = 0.5 * (1.0 + jax.scipy.special.erf(zz / jnp.sqrt(2.0)))
    val = imp * cdf + sqrt_s * pdf
    return val, cdf * dmu + pdf / (2.0 * sqrt_s) * dvar
