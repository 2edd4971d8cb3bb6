"""Open-loop serving of a sliding-window GP behind ``GPServeEngine``.

Set-up fits the seed's initial window and warms the engine's own programs
through the engine (one query tick, one fence with its evict and insert).
The window then offers the mix's schedule on time, whatever the engine's
progress: each operation is submitted once it is due and the engine is
stepped while it has work. A query is timed from its due time to the return
of the ``step()`` that retires it (which ends in a blocking fetch); an
insert from its due time to the return of the ``step()`` whose fence
applied it, with the new posterior on the device. What is in flight when
the window closes is drained and timed too.

The check replays the point history from the harness's own records: the
posterior that served a query at version v holds the initial window plus
the inserts applied by then, oldest dropped. Queries served at a sample of
versions, the last among them, are compared with the dense reference.
"""
from __future__ import annotations

import time

import numpy as np

import loadgen
import refgp

DRAIN_S = 60.0  # how long past the window's close in-flight work may take


def _rel(got, want) -> float:
    got, want = np.asarray(got, float), np.asarray(want, float)
    scale = max(float(np.max(np.abs(want))), 1e-300)
    err = float(np.max(np.abs(got - want))) / scale
    return err if np.all(np.isfinite(got)) else float("inf")


def run(cell, seed: int, seconds: float, ctx) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.core import GPConfig, fit
    from repro.streaming import GPServeEngine

    conf, mix, log, spans = cell.config, cell.traffic, ctx.log, ctx.spans
    eng_conf = conf["engine"]
    dtype = jnp.dtype(ctx.dtype)
    window = int(eng_conf["window"])
    bounds = loadgen.bounds(conf)
    omega = loadgen.omega(conf)

    # --- set-up: data, initial fit, the engine, its programs warmed --------
    with ctx.phase("data"):
        X0, Y0 = loadgen.observe(conf, loadgen.rng(seed, "data"), conf["n"])
        g = loadgen.rng(seed, "warmup")
        wq, _ = loadgen.observe(conf, g, eng_conf["batch_slots"])
        wx, wy = loadgen.observe(conf, g, 1)
        sched = loadgen.open_loop(conf, mix, seed, seconds, rate=ctx.rate)
    with ctx.phase("fit"):
        gp = fit(GPConfig(**conf["gp"]), jnp.asarray(X0, dtype),
                 jnp.asarray(Y0, dtype), jnp.asarray(omega, dtype),
                 conf["sigma"])
        jax.block_until_ready(gp)
    with ctx.phase("engine"):
        eng = GPServeEngine(gp, jnp.asarray(bounds, dtype),
                            batch_slots=eng_conf["batch_slots"],
                            kind=eng_conf["acquisition"],
                            beta=eng_conf["beta"], window=window)
        jax.block_until_ready(eng.gp)
        del gp
    with ctx.phase("warm_tick"):
        kinds = mix["query_kinds"]
        for j, x in enumerate(wq):
            eng.submit(x, kind=kinds[j % len(kinds)])
        eng.run_until_done()
    with ctx.phase("warm_fence"):
        eng.insert(wx[0], wy[0])
        eng.step()
        jax.block_until_ready(eng.gp)
    history_x = [X0, wx]
    history_y = [Y0, wy]
    base_inserts = 1  # the warm-up insert
    h0 = eng.health_stats()
    v_map = {eng.version: 0}  # version -> window inserts applied by then

    # --- the window --------------------------------------------------------
    N = len(sched.t)
    queries: dict[int, object] = {}
    retired: dict[int, float] = {}
    applied: dict[int, float] = {}
    unapplied: list[int] = []
    lag = np.zeros(N)
    n_applied = 0
    i = 0
    ctx.window_open()
    t0 = time.perf_counter()
    close = t0 + seconds
    while True:
        now = time.perf_counter()
        if i < N and t0 + sched.t[i] <= now:
            with spans.span("admit"):
                while i < N and t0 + sched.t[i] <= now:
                    lag[i] = now - t0 - sched.t[i]
                    if sched.insert[i]:
                        eng.insert(sched.x[i], sched.y[i])
                        unapplied.append(i)
                    else:
                        queries[i] = eng.submit(sched.x[i], kind=sched.kind[i])
                    i += 1
        busy = bool(eng.pending) or bool(unapplied) or any(
            s is not None for s in eng.slots)
        if not busy:
            if i >= N:
                break
            with spans.span("wait"):
                time.sleep(max(0.0, t0 + sched.t[i] - time.perf_counter()))
            continue
        if now > close + DRAIN_S:
            break
        fence = bool(unapplied) and all(s is None for s in eng.slots)
        ctx.before_step("fence" if fence else "tick")
        with spans.span("fence" if fence else "tick"):
            done = eng.step()
            if fence:
                jax.block_until_ready(eng.gp)
        end = time.perf_counter()
        for q in done:
            retired[q.rid] = end
        if fence:
            for j in unapplied:
                applied[j] = end
            n_applied += len(unapplied)
            unapplied = []
        v_map.setdefault(eng.version, n_applied)
    t_end = time.perf_counter()
    ctx.window_close()
    ctx.read_memory()

    # --- numbers -----------------------------------------------------------
    q_lat, q_bad, missing = [], 0, 0
    served = []  # (op index, query)
    for j, q in queries.items():
        if q.rid not in retired:
            missing += 1
            continue
        q_lat.append(retired[q.rid] - t0 - sched.t[j])
        r = q.result
        if not all(np.all(np.isfinite(r[k]))
                   for k in ("mean", "var", "value", "grad")):
            q_bad += 1
        served.append((j, q))
    u_lat = [applied[j] - t0 - sched.t[j] for j in applied]
    missing += int(np.sum(sched.insert)) - len(applied)
    h1 = eng.health_stats()
    repairs = h1["repairs"] - h0["repairs"]
    resyncs = h1["resyncs"] - h0["resyncs"]
    log(f"window: {N} ops due in {seconds}s ({len(queries)} queries, "
        f"{int(np.sum(sched.insert))} inserts), drained {t_end - close:.3f}s "
        f"past the close; {len(q_lat)} queries and {len(applied)} inserts "
        f"completed, {missing} never; nonfinite={q_bad} repairs={repairs} "
        f"resyncs={resyncs}; generator lag p50={np.median(lag) * 1e3:.3f}ms "
        f"max={np.max(lag) * 1e3:.3f}ms; final version {eng.version}")
    ms = lambda v, p: float(np.percentile(np.asarray(v) * 1e3, p))
    e2e = {}
    if q_lat:
        e2e["query_p50_ms"] = ms(q_lat, 50)
        e2e["query_p95_ms"] = ms(q_lat, 95)
    ctx.counters.update(queries=len(q_lat), inserts=len(applied),
                        mutations=2 * len(applied), repairs=repairs,
                        resyncs=resyncs, versions=len(v_map))
    ctx.values.update(query_latency_ms=np.asarray(q_lat) * 1e3,
                      update_latency_ms=np.asarray(u_lat) * 1e3)

    # --- the check against the dense reference -----------------------------
    eng_results = [(j, q.result) for j, q in served]
    del eng, queries, served
    checks = _check(conf, mix, seed, eng_results, sched, history_x, history_y,
                    base_inserts, v_map, applied, omega, window, log)
    return {"e2e": e2e, "attempted": N, "failed": q_bad + repairs + resyncs
            + missing, "checks": checks, "complete": missing == 0}


def _check(conf, mix, seed, results, sched, hx, hy, base, v_map, applied,
           omega, window, log):
    """Worst relative errors of the served answers at sampled versions."""
    order = sorted(applied, key=lambda j: applied[j])  # engine applies FIFO
    ins_x = np.concatenate(hx + [sched.x[order]]) if order else np.concatenate(hx)
    ins_y = np.concatenate(hy + [sched.y[order]]) if order else np.concatenate(hy)
    n0 = hx[0].shape[0] + base
    by_version: dict[int, list] = {}
    for j, r in results:
        by_version.setdefault(r["version"], []).append((j, r))
    g = loadgen.rng(seed, "check")
    versions = sorted(by_version)
    last = versions[-1]
    others = [v for v in versions if v != last]
    pick = [last] + list(g.choice(others, size=min(len(others),
                                                   mix["check_versions"] - 1),
                                  replace=False))
    worst = {"mean_rel": 0.0, "var_rel": 0.0, "acq_rel": 0.0, "grad_rel": 0.0}
    checked = 0
    for v in pick:
        if v not in v_map:
            log(f"check: version {v} is not one the harness saw")
            return [(k, float("inf"), conf["limits"][k]) for k in worst]
        m = n0 + v_map[v]
        lo = max(0, m - window)
        ref = refgp.DenseGP(conf["gp"]["q"], omega, conf["sigma"],
                            ins_x[lo:m], ins_y[lo:m])
        rs = by_version[v]
        xq = sched.x[[j for j, _ in rs]]
        mu, var = ref.mean_var(xq)
        val, grad = ref.ucb(xq, conf["engine"]["beta"])
        got = lambda k: np.stack([np.asarray(r[k], float) for _, r in rs])
        worst["mean_rel"] = max(worst["mean_rel"], _rel(got("mean"), mu))
        worst["var_rel"] = max(worst["var_rel"], _rel(got("var"), var))
        worst["acq_rel"] = max(worst["acq_rel"], _rel(got("value"), val))
        worst["grad_rel"] = max(worst["grad_rel"], _rel(got("grad"), grad))
        checked += len(rs)
        log(f"check v{v}: mean {_rel(got('mean'), mu):.3e} var {_rel(got('var'), var):.3e} grad {_rel(got('grad'), grad):.3e}")
    log(f"check: {checked} answers at versions {sorted(int(v) for v in pick)} "
        f"against the dense GP on the window's {window} points")
    return [(k, v, conf["limits"][k]) for k, v in worst.items()]
