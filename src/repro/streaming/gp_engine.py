"""Slot-batched GP query serving engine over a streaming posterior.

Modeled on the classic LM decode-engine shape: a fixed pool of B request
slots, one shape-stable jit'd step, and an admit/retire lifecycle. Each tick
evaluates the batched posterior mean / variance / acquisition (+gradient)
for every occupied slot against one shared fitted GP; multi-tick "ascend"
requests run projected gradient ascent on the acquisition, so many
concurrent acquisition maximizations — at different stages — share each
batched evaluation.

Consistency / versioning: the posterior carries a version counter. Mutations
(``insert`` / ``evict`` — the Sec. 6 incremental updates — or
``set_posterior``) are *staged* and act as a fence: admission pauses,
running slots drain, then the mutations apply, the version bumps once per
mutation, and admission resumes. A query is pinned to the version current
at *admit* time and is served by that posterior for its whole lifetime; its
result carries the version.

Capacity tiers: the engine holds its posterior capacity-padded (traced
``n_active``, static capacity — see ``repro.masking``), so the jit'd
step and the insert/evict steps compile ONCE per capacity tier and are
reused across every mutation at that tier. When an insert would overflow
the tier, the posterior is re-homed into a doubled allocation (one new
trace per tier, amortized O(log n) traces over any stream). With
``window=W`` the engine runs in sliding-window mode — drop-oldest eviction
before each overflowing insert — which pins peak memory at the ``W`` tier
forever.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..core.additive_gp import AdditiveGP, with_capacity
from ..core.bayesopt import BOConfig, acquisition_stats, ascent_step
from ..health import verdict as hv
from .updates import (_evict_impl, _insert_impl, evict as stream_evict,
                      insert as stream_insert, resync_gband)

__all__ = ["GPServeEngine", "Query", "propose_via_engine"]


def _next_tier(m: int) -> int:
    """Smallest power-of-two capacity >= m (>= 8)."""
    return max(8, 1 << (int(m) - 1).bit_length())


@dataclasses.dataclass
class Query:
    """One posterior request; ``kind`` selects what retires into ``result``.

    kinds "mean" / "var" / "acq" retire after a single tick with the
    posterior mean / variance / acquisition value (+gradient) at ``x``;
    "ascend" first runs ``steps`` acquisition-ascent ticks from ``x``.
    ``result`` holds x, mean, var, value, grad, and the serving version.
    """

    rid: int
    x: np.ndarray
    kind: str = "acq"
    steps: int = 0
    version: int = -1
    result: dict | None = None
    done: bool = False
    # owning tenant id when served by the multi-tenant GPFleetEngine (the
    # single-GP engine leaves it 0)
    tenant: int = 0
    # time.perf_counter at submit(): the start of its ``engine.queued`` wait
    submitted: float = 0.0


@partial(jax.jit, static_argnames=("kind",))
def _engine_step(gp: AdditiveGP, X: jax.Array, beta, best_y, lo, hi, step_len,
                 kind: str):
    """One batched tick: stats at X plus the next ascent iterate."""
    val, grad, mu, var = acquisition_stats(gp, X, beta, best_y, kind=kind)
    return val, grad, mu, var, ascent_step(X, grad, lo, hi, step_len)


class GPServeEngine:
    """Fixed-slot batched server for posterior/acquisition queries.

    ``capacity`` pins the initial allocation tier (default: the next
    power-of-two above the point count, leaving insert headroom);
    ``window`` enables sliding-window serving: once ``window`` points are
    held, each staged insert is preceded by a drop-oldest evict, bounding
    memory and per-tick cost for the lifetime of the engine.
    """

    def __init__(self, gp: AdditiveGP, bounds, batch_slots: int = 8,
                 kind: str = "ucb", beta: float = 2.0, lr: float = 0.05,
                 insert_iters: int | None = None,
                 capacity: int | None = None, window: int | None = None,
                 checkpointer=None, checkpoint_every: int = 64):
        n_points = gp.num_points()
        if window is not None and window < 2:
            raise ValueError(f"window must be >= 2; got {window}")
        if capacity is None:
            capacity = _next_tier(
                min(n_points + 1, window) if window is not None
                else n_points + 1)
        self.window = window
        self.gp = with_capacity(gp, max(capacity, gp.n))
        self.bounds = jnp.asarray(bounds)
        # the ascent box and step, on the device once (not per tick)
        self._lo, self._hi = self.bounds[:, 0], self.bounds[:, 1]
        self._step_len = lr * (self._hi - self._lo)
        self.B = batch_slots
        self.kind = kind
        self.beta = beta
        self.lr = lr
        self.insert_iters = insert_iters
        self.version = 0
        self.slots: list[Query | None] = [None] * batch_slots
        self.pending: deque[Query] = deque()
        self._staged: list[tuple] = []
        self._xs = np.zeros((batch_slots, gp.D), np.asarray(gp.X).dtype)
        # per-slot best_y, pinned at admit time like the posterior version —
        # a mid-flight change to engine.best_y must not bend in-flight EI
        # trajectories
        self._besty = np.zeros(batch_slots, np.asarray(gp.Y).dtype)
        self._next_rid = 0
        self._count = n_points
        # health plumbing (active only when the GP was fitted health="on"):
        # the fence runs the drift sentinel + verdict-driven ladder repairs,
        # the query tick holds-and-repairs on nonfinite results, and an
        # optional Checkpointer keeps a durable last-good snapshot
        self._ckpt = checkpointer
        self._ckpt_every = max(1, int(checkpoint_every))
        self._repairs = 0
        self._resyncs = 0
        self._health_events: list = []
        self.best_y = float(jnp.max(self._active_y()))

    def _active_y(self) -> jax.Array:
        return self.gp.Y[: self._count]

    @property
    def num_points(self) -> int:
        """Active observation count (the capacity may be larger)."""
        return self._count

    @property
    def capacity(self) -> int:
        return self.gp.n

    # -- health --------------------------------------------------------------

    def health_stats(self) -> dict:
        """Counters + the structured :class:`~repro.health.HealthEvent`
        trail of every ladder escalation / sentinel resync so far."""
        return {"repairs": self._repairs, "resyncs": self._resyncs,
                "events": list(self._health_events)}

    def _post_mutation_health(self) -> None:
        """Fence-time health pass: one fetch of the carried scalars, then
        host-dispatched sentinel resync and/or ladder repair. The healthy
        path costs the fetch only — no new compiled programs."""
        h = self.gp.health
        if h is None:
            return
        with obs.span("fence.health"):
            verdict, drift, muts = jax.device_get((h.verdict, h.drift,
                                                   h.muts))
        if float(drift) > hv.DRIFT_TOL or int(muts) >= hv.RESYNC_EVERY:
            from ..health.ladder import HealthEvent

            self.gp = resync_gband(self.gp)
            self._resyncs += 1
            self._health_events.append(HealthEvent(
                op="sentinel", rung="gband_resync", before=int(verdict),
                after=int(verdict),
                detail=f"drift={float(drift):.3e} after {int(muts)} "
                       "windowed mutation(s)"))
        if int(verdict) != int(hv.OK):
            self._repair("mutation")
        elif (self._ckpt is not None
              and self.version % self._ckpt_every == 0):
            self._ckpt.save(self.version, self.gp)

    def _repair(self, op: str) -> bool:
        """Ladder-repair the posterior; last-good checkpoint as backstop.
        Returns whether the posterior changed."""
        from ..health.ladder import HealthEvent, probe_gp, repair

        gp, events = repair(self.gp, op=op)
        if not events:
            return False
        if (probe_gp(gp) != int(hv.OK) and self._ckpt is not None
                and self._ckpt.latest_step() is not None):
            restored, step = self._ckpt.restore(self.gp)
            if restored is not None:
                gp = jax.tree_util.tree_map(jnp.asarray, restored)
                events.append(HealthEvent(
                    op=op, rung="checkpoint_restore", before=events[-1].after,
                    after=probe_gp(gp),
                    detail=f"last-good checkpoint step {step}"))
        self._health_events += events
        self._repairs += 1
        self.gp = gp
        self._count = gp.num_points()
        self.version += 1
        self.best_y = float(jnp.max(self._active_y()))
        return True

    # -- request lifecycle ---------------------------------------------------

    def submit(self, x, kind: str = "acq", steps: int = 0) -> Query:
        """Queue a query; returns its handle (mutated in place on retire)."""
        if kind not in ("mean", "var", "acq", "ascend"):
            raise ValueError(f"unknown query kind {kind!r}")
        q = Query(rid=self._next_rid, x=np.asarray(x, self._xs.dtype),
                  kind=kind, steps=steps if kind == "ascend" else 0,
                  submitted=time.perf_counter())
        self._next_rid += 1
        self.pending.append(q)
        return q

    def stats(self) -> dict:
        """The engine and solve-route counters of :mod:`repro.obs`
        (process-wide, summed over every engine of the process) and, under
        ``"compiled"``, how many programs each of the tick, insert and evict
        steps holds: a number that grows in steady serving names the step
        that retraced. ``solve.cr`` / ``solve.scan`` count, per traced
        program, the banded solves that took block cyclic reduction or the
        scan LU (``kernels.ops.banded_solve``)."""
        out = {k: v for k, v in obs.counters().items()
               if k.startswith(("engine.", "solve."))}
        out["compiled"] = {"engine_step": _engine_step._cache_size(),
                           "insert": _insert_impl._cache_size(),
                           "evict": _evict_impl._cache_size()}
        return out

    def step(self) -> list[Query]:
        """One engine tick; returns the queries retired this tick."""
        with obs.span("engine.step"):
            return self._step()

    def _step(self) -> list[Query]:
        if self._staged and all(s is None for s in self.slots):
            self._apply_staged()
        if not self._staged:  # staged mutations fence admission
            with obs.span("engine.admit"):
                now = time.perf_counter()
                for i in range(self.B):
                    if self.slots[i] is None and self.pending:
                        q = self.pending.popleft()
                        q.version = self.version
                        self.slots[i] = q
                        self._xs[i] = q.x
                        self._besty[i] = self.best_y
                        obs.record("engine.queued", q.submitted, now)
                        obs.count("engine.admitted")
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return []
        obs.count("engine.ticks")
        obs.count("engine.slot_ticks", len(active))
        with obs.span("engine.dispatch"):
            out = _engine_step(self.gp, jnp.asarray(self._xs), self.beta,
                               jnp.asarray(self._besty), self._lo, self._hi,
                               self._step_len, self.kind)
        with obs.span("engine.fetch"):
            val, grad, mu, var, Xn = map(np.asarray, out)
        with obs.span("engine.retire"):
            return self._retire(active, val, grad, mu, var, Xn)

    def _retire(self, active, val, grad, mu, var, Xn) -> list[Query]:
        # query-path detection (health-on posteriors only): a nonfinite
        # result means a corrupt artifact reached serving. Hold the affected
        # slots (no retire, no ascend advance), ladder-repair the posterior,
        # and re-serve them next tick. If the repair finds nothing wrong the
        # NaN belongs to the query itself and it retires as-is — health-off
        # engines always retire as-is (the pre-health corrupt behavior).
        held: set[int] = set()
        if self.gp.health is not None:
            bad = [i for i in active
                   if not (np.isfinite(val[i]) and np.isfinite(mu[i])
                           and np.isfinite(var[i])
                           and np.all(np.isfinite(grad[i])))]
            if bad and self._repair("query"):
                held = set(bad)
        finished = []
        for i in active:
            if i in held:
                continue
            q = self.slots[i]
            if q.kind == "ascend" and q.steps > 0:
                self._xs[i] = Xn[i]
                q.steps -= 1
                continue
            q.result = {"x": self._xs[i].copy(), "mean": float(mu[i]),
                        "var": float(var[i]), "value": float(val[i]),
                        "grad": grad[i].copy(), "version": q.version}
            q.done = True
            finished.append(q)
            self.slots[i] = None
        return finished

    def run_until_done(self, max_ticks: int = 10_000) -> list[Query]:
        done: list[Query] = []
        for _ in range(max_ticks):
            done += self.step()
            if (not self.pending and not self._staged
                    and all(s is None for s in self.slots)):
                break
        return done

    # -- posterior mutations (versioned, fence semantics) ----------------------

    def insert(self, x_new, y_new) -> None:
        """Stage an incremental observation insert (applied at the fence)."""
        self._staged.append(("insert", np.asarray(x_new), float(y_new)))

    def evict(self) -> None:
        """Stage a drop-oldest eviction (applied at the fence).

        Validated against the *projected* count (current count plus the
        already-staged mutations), so an over-eviction fails here — at
        stage time — instead of poisoning the fence, which would otherwise
        re-raise on every subsequent ``step()``.
        """
        projected = self._count
        for op in self._staged:
            if op[0] == "insert":
                projected += 1
            elif op[0] == "evict":
                projected -= 1
            else:  # set_posterior resets the count
                projected = op[1].num_points()
        if projected <= 1:
            raise ValueError(
                "cannot stage evict: the engine would drop below one "
                f"observation ({projected} projected after staged mutations)")
        self._staged.append(("evict",))

    def set_posterior(self, gp: AdditiveGP) -> None:
        """Stage a full posterior replacement (e.g. a hyperparameter refit)."""
        self._staged.append(("set", gp))

    def _apply_staged(self) -> None:
        with obs.span("engine.fence"):
            obs.count("engine.fences")
            for op in self._staged:
                self._apply(op)
            self._staged.clear()
            self._post_mutation_health()
            with obs.span("fence.best_y"):
                self.best_y = float(jnp.max(self._active_y()))

    def _apply(self, op: tuple) -> None:
        if op[0] == "insert":
            # sliding window: free oldest slots first — capacity, and
            # therefore the compiled steps, never grow. A loop (not a
            # single evict) so an engine constructed *above* the window
            # drains down to it instead of staying pinned forever.
            while self.window is not None and self._count >= self.window:
                self._evict_one()
            if self._count >= self.gp.n:
                # tier overflow: re-home into a doubled allocation (one
                # new trace per tier; no version bump — same posterior)
                self.gp = with_capacity(self.gp, _next_tier(2 * self.gp.n))
            with obs.span("fence.insert"):
                self.gp = stream_insert(self.gp, op[1], op[2],
                                        iters=self.insert_iters,
                                        count=self._count)
            self._count += 1
        elif op[0] == "evict":
            self._evict_one()
            return
        else:
            gp = op[1]
            # keep the tier: re-home the replacement into (at least) the
            # current capacity so the compiled step stays warm — but
            # never below the replacement's own allocation (a pre-padded
            # fit may already be larger; capacity cannot shrink)
            self.gp = with_capacity(
                gp, max(self.gp.n, gp.n, _next_tier(gp.num_points() + 1)))
            self._count = gp.num_points()
        self.version += 1
        obs.count("engine.mutations")

    def _evict_one(self) -> None:
        with obs.span("fence.evict"):
            self.gp = stream_evict(self.gp, iters=self.insert_iters,
                                   count=self._count)
        self._count -= 1
        self.version += 1
        obs.count("engine.mutations")


def propose_via_engine(engine: GPServeEngine, key: jax.Array, cfg: BOConfig,
                       best_y=None) -> jax.Array:
    """Multi-start acquisition ascent routed through the engine slots.

    Same start sampling and update rule as ``propose_next``, served
    tick-by-tick so concurrent queries (and staged inserts) interleave.
    The acquisition settings live on the engine (its jit'd step is
    specialized on them), so ``cfg`` must agree with them.
    """
    if (cfg.kind, cfg.beta, cfg.lr) != (engine.kind, engine.beta, engine.lr):
        raise ValueError(
            f"BOConfig(kind={cfg.kind!r}, beta={cfg.beta}, lr={cfg.lr}) does "
            f"not match the engine's (kind={engine.kind!r}, "
            f"beta={engine.beta}, lr={engine.lr}); construct the engine from "
            "the same config")
    bounds = engine.bounds
    lo, hi = bounds[:, 0], bounds[:, 1]
    starts = jax.random.uniform(key, (cfg.n_starts, engine.gp.D),
                                dtype=bounds.dtype)
    X0 = lo + starts * (hi - lo)
    if best_y is not None:
        engine.best_y = float(best_y)
    qs = [engine.submit(np.asarray(x), kind="ascend", steps=cfg.ascent_steps)
          for x in X0]
    # each request needs steps+1 ticks; admission waves add B-sized rounds,
    # and queries already queued ahead of ours occupy slots first
    waves = -(-len(engine.pending) // engine.B) + 1  # +1: occupied slots
    engine.run_until_done(max_ticks=waves * (cfg.ascent_steps + 2) + 8)
    if not all(q.done for q in qs):
        raise RuntimeError("engine tick budget exhausted before all ascent "
                           "requests retired (staged mutations fencing "
                           "admission, or external queries hogging slots?)")
    best = max(qs, key=lambda q: q.result["value"])
    return jnp.asarray(best.result["x"], bounds.dtype)
