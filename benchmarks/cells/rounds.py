"""Offline rounds of the paper's Fig. 5 job: a fit, a prediction and a
marginal likelihood (MLL), again and again on fresh data.

Set-up draws a warm round's data from the seed and runs that round, so that
every program of the cell is loaded from the cache (or compiled) before the
window opens. The window then runs rounds back to back for ``--seconds``;
the round in flight at the close finishes and counts. Round ``r`` draws a
fresh data set from the seed's ``data`` stream keyed by ``r``: the
configuration's ``n`` points and the mix's ``test_points`` uniform places.
``fit_s`` times ``fit`` from the host arrays, then ``posterior_mean`` and
``posterior_var`` at the test places, fetched to the host: the unit that
Fig. 5 times. ``mll_s`` times ``log_likelihood`` with the round's own probe
key, fetched as a host float. Both are medians over the window's rounds;
fewer than forty rounds fit in a run, so there is no tail.

The check, outside the window, draws the data of the last round and of
``check_rounds - 1`` more, picked by the seed, again from the seed, and
compares that round's predictions and MLL with the dense float64 GP
(``refgp.py``).

Every blocking call has a deadline from the mix (``setup_deadline_s`` for
each part of set-up, ``round_deadline_s`` for each round,
``check_deadline_s`` for all that follows the window): past it the process
prints every thread's stack and exits non-zero, so that a fetch that never
returns ends the run instead of hanging it.
"""
from __future__ import annotations

import time

import numpy as np

import loadgen
import refgp
from serve import _rel


def round_data(conf: dict, mix: dict, g: np.random.Generator):
    """One round's data set: ``n`` noisy observations and the test places."""
    X, Y = loadgen.observe(conf, g, conf["n"])
    Xq, _ = loadgen.observe(conf, g, mix["test_points"])
    return X, Y, Xq


def probe_key(g: np.random.Generator) -> np.ndarray:
    """A raw JAX PRNG key (two uint32 words) for the MLL's probes."""
    return g.integers(0, 2 ** 32, size=2, dtype=np.uint32)


def run(cell, seed: int, seconds: float, ctx) -> dict:
    import jax.numpy as jnp

    from repro import core

    conf, mix, log, spans = cell.config, cell.traffic, ctx.log, ctx.spans
    dtype = jnp.dtype(ctx.dtype)
    config = core.GPConfig(**conf["gp"])
    sigma = conf["sigma"]

    def fit_predict(X, Y, Xq):
        """The fitted GP and its mean and variance at ``Xq`` on the host.
        The module's names are looked up at each call, so that a test can
        plant a fault in them."""
        with spans.span("fit"):
            gp = core.fit(config, jnp.asarray(X, dtype), jnp.asarray(Y, dtype),
                          omega, sigma)
            xq = jnp.asarray(Xq, dtype)
            mu, var = core.posterior_mean(gp, xq), core.posterior_var(gp, xq)
            return gp, np.asarray(mu), np.asarray(var)

    def mll(gp, key):
        with spans.span("mll"):
            return float(core.log_likelihood(gp, jnp.asarray(key)))

    # --- set-up: the warm round, part by part -----------------------------
    with ctx.phase("data"):
        omega = jnp.asarray(loadgen.omega(conf), dtype)
        g = loadgen.rng(seed, "warmup")
        warm = round_data(conf, mix, g)
        warm_key = probe_key(g)
    ctx.deadline(mix["setup_deadline_s"])
    with ctx.phase("warm_fit"):
        gp, _, _ = fit_predict(*warm)
    ctx.deadline(mix["setup_deadline_s"])
    with ctx.phase("warm_mll"):
        mll(gp, warm_key)
    del gp

    # --- the window ---------------------------------------------------------
    results = []  # per round: (mean, var, mll) on the host
    fit_s, mll_s = [], []
    ctx.window_open()
    close = time.perf_counter() + seconds
    while not results or time.perf_counter() < close:
        r = len(results)
        with spans.span("data"):
            X, Y, Xq = round_data(conf, mix, loadgen.rng(seed, "data", r))
            key = probe_key(loadgen.rng(seed, "probes", r))
        ctx.before_step("round")
        ctx.deadline(mix["round_deadline_s"])
        t0 = time.perf_counter()
        gp, mu, var = fit_predict(X, Y, Xq)
        t1 = time.perf_counter()
        ll = mll(gp, key)
        t2 = time.perf_counter()
        del gp
        results.append((mu, var, ll))
        fit_s.append(t1 - t0)
        mll_s.append(t2 - t1)
    ctx.window_close()
    ctx.deadline(mix["check_deadline_s"])
    ctx.read_memory()

    # --- numbers -------------------------------------------------------------
    bad = sum(not (np.all(np.isfinite(mu)) and np.all(np.isfinite(var))
                   and np.isfinite(ll)) for mu, var, ll in results)
    fit_s, mll_s = np.asarray(fit_s), np.asarray(mll_s)
    log(f"window: {len(results)} rounds in {seconds}s, n={conf['n']}, "
        f"{mix['test_points']} test places; fit_s "
        + " ".join(f"{t:.4f}" for t in fit_s) + "; mll_s "
        + " ".join(f"{t:.4f}" for t in mll_s) + f"; nonfinite={bad}")
    ctx.counters.update(rounds=len(results))
    ctx.values.update(fit_s=fit_s, mll_s=mll_s)
    e2e = {"fit_s": float(np.median(fit_s)), "mll_s": float(np.median(mll_s))}

    checks = _check(conf, mix, seed, results, log)
    ctx.deadline(None)
    return {"e2e": e2e, "attempted": len(results), "failed": bad,
            "checks": checks, "complete": True}


def _check(conf, mix, seed, results, log):
    """Worst relative errors of the checked rounds against the dense GP."""
    last = len(results) - 1
    g = loadgen.rng(seed, "check")
    more = min(last, int(mix["check_rounds"]) - 1)
    pick = [last] + sorted(int(r) for r in g.choice(last, size=more,
                                                    replace=False)) if more \
        else [last]
    omega = loadgen.omega(conf)
    worst = {"mean_rel": 0.0, "var_rel": 0.0, "mll_rel": 0.0}
    for r in pick:
        t0 = time.perf_counter()
        X, Y, Xq = round_data(conf, mix, loadgen.rng(seed, "data", r))
        ref = refgp.DenseGP(conf["gp"]["q"], omega, conf["sigma"], X, Y)
        mu_ref, var_ref = ref.mean_var(Xq)
        ll_ref = ref.log_marginal()
        del ref
        mu, var, ll = results[r]
        got = {"mean_rel": _rel(mu, mu_ref), "var_rel": _rel(var, var_ref),
               "mll_rel": _rel(ll, ll_ref)}
        for k, v in got.items():
            worst[k] = max(worst[k], v)
        log(f"check round {r}: mean {got['mean_rel']:.3e} var "
            f"{got['var_rel']:.3e} mll {got['mll_rel']:.3e} (MLL {ll!r}, "
            f"dense {ll_ref!r}); reference {time.perf_counter() - t0:.1f}s")
    log(f"check: rounds {pick} of {len(results)} against the dense GP on "
        f"their {conf['n']} points")
    return [(k, v, conf["limits"][k]) for k, v in worst.items()]
