"""Layered discrete-event simulation of federated training (DESIGN.md §9).

Four layers, composed here:

* **task substrate** (repro.core.tasks) — *what* the clients train: model
  init, local loss, data samplers, eval metrics. ``PaperTask`` wraps the
  paper's MLP/CNN/LSTM byte-identically; ``ArchTask`` wraps an assigned
  ``ModelConfig`` architecture at reduced scale — the same runtime drives
  both (DESIGN.md §10);
* **event runtime** (repro.core.events) — virtual clock, typed arrival
  events, the burst-drain loop, and the batch-window policies (fixed or
  the ``"auto"`` inter-arrival-density controller, optionally gamma-aware);
* **client behavior** (repro.core.behavior) — *when* updates land:
  ``paper`` reproduces the paper's §B.2 environment exactly (lognormal
  device heterogeneity, TCP transmission, random suspension), ``trace`` /
  ``poisson-burst`` / ``diurnal`` open other worlds, all with churn and
  dropout knobs;
* **protocol** (repro.core.server / client / cohort) — what an arrival
  does: aggregation through either server backend, local training through
  any client engine, with fan-outs planned against the memory budget
  (repro.core.budget) — vmap-width clamping, K-scan microbatching, and
  the cohort->loop fallback, reported in ``SimResult.summary()``.

Every aggregator sees the same event trace for a given seed and behavior,
so curves are comparable across algorithms. Burst-arrival batching
(DESIGN.md §4.3): with a positive (or auto-opened) window, all updates
landing within the window of the first one drain through
``server.on_update_batch`` in one multi-delta sweep; ``batch_window = 0``
preserves one-aggregation-per-arrival exactly. Under the ``paper`` model
with a fixed window the runtime is byte-identical — RNG draw order, event
trace, batcher PCG64 states — to the pre-refactor monolithic loop
(pinned by tests/test_event_runtime.py).

Synchronous baselines (FedAvg/FedProx) run the same clients but the round
duration is the max over clients — the straggler effect the paper targets.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import FedConfig
from repro.core import budget as budget_mod
from repro.core import cohort
from repro.core import population as population_mod
from repro.core import screening
from repro.core import tasks as tasks_mod
from repro.core.adversary import make_adversary
from repro.core.behavior import make_behavior
from repro.core.client import Client
from repro.core.events import (CHECKIN, EventLoop, VirtualClock,
                               make_window_controller)
from repro.core.server import ClientUpdate, ServerReply, make_server
from repro.utils import pytree as pt
from repro.utils import trace

PyTree = Any


@dataclasses.dataclass
class EvalPoint:
    time: float
    iteration: int
    accuracy: float
    loss: float


@dataclasses.dataclass
class SimResult:
    algorithm: str
    points: List[EvalPoint]
    history: list
    total_updates: int
    #: server drain calls (== aggregations for window 0; < total_updates
    #: when burst windows batch arrivals; == rounds for sync servers)
    total_drains: int = 0
    #: the memory-budget plan the last cohort fan-out ran under
    #: (budget.CohortPlan.to_dict()); None when no cohort fan-out happened
    plan: Optional[dict] = None
    #: norm-screening counters (server.screen_stats(): accept/clip/reject
    #: + threshold state); None when screening is off
    screen: Optional[dict] = None
    #: adversary stats (attack name, corrupted client ids, applications);
    #: None for benign runs
    attack: Optional[dict] = None
    #: population-engine telemetry (population.PopulationState.stats():
    #: contacted/materialized counts, check-ins, max in-flight); None for
    #: roster runs
    population: Optional[dict] = None

    def max_accuracy(self, within_time: Optional[float] = None) -> float:
        pts = [p for p in self.points
               if within_time is None or p.time <= within_time]
        return max((p.accuracy for p in pts), default=0.0)

    def time_to_accuracy(self, target: float) -> float:
        for p in self.points:
            if p.accuracy >= target:
                return p.time
        return float("inf")

    def summary(self) -> dict:
        """The scalar row every benchmark driver reports."""
        out = {
            "algorithm": self.algorithm,
            "final_acc": float(self.points[-1].accuracy),
            "max_acc": float(self.max_accuracy()),
            "t90": float(self.time_to_accuracy(0.9 * self.max_accuracy())),
            "updates": self.total_updates,
            "drains": self.total_drains,
        }
        # mean observed staleness over FINITE gammas only: rejected
        # arrivals record gamma = NaN (no aggregation happened), and one
        # NaN would otherwise poison the mean forever — the same skip rule
        # AutoWindow.observe_gamma applies to its EWMA
        gammas = [h.gamma for h in self.history if math.isfinite(h.gamma)]
        if gammas:
            out["mean_gamma"] = float(sum(gammas) / len(gammas))
        if self.plan is not None:
            out["plan"] = self.plan
        if self.screen is not None:
            out["screen"] = self.screen
        if self.attack is not None:
            out["attack"] = self.attack
        if self.population is not None:
            out["population"] = self.population
        return out

    def to_json(self) -> dict:
        """JSON-serializable record: the summary plus the accuracy curve
        (used by benchmarks/common.summarize_runs — drivers should not
        re-implement this)."""
        out = self.summary()
        out["curve"] = [(p.time, p.accuracy) for p in self.points]
        return out


class FederatedSimulation:
    def __init__(self, task, fed: FedConfig,
                 algorithm: str = "asyncfeded", seed: int = 0,
                 heterogeneity: float = 0.6,
                 server_kwargs: Optional[dict] = None,
                 batch_window: Optional[Any] = None,
                 behavior: Optional[str] = None,
                 behavior_kwargs: Optional[dict] = None):
        # any handle as_task accepts: a LocalTask, a raw PaperTaskConfig
        # (every pre-substrate call site), a ModelConfig, a name
        self.task = tasks_mod.as_task(task)
        self.fed = fed
        # engine-name validation lives in FedConfig.__post_init__ — a bad
        # name can't reach this constructor
        self.algorithm = algorithm
        # a float or "auto"; resolved to a window controller per run
        self.batch_window = (fed.batch_window if batch_window is None
                             else batch_window)
        # population engine (DESIGN.md §12): no roster, no O(num_clients)
        # work anywhere in this constructor — clients materialize lazily
        # on first contact from (seed, index)
        self._population: Optional[population_mod.PopulationState] = None
        if fed.population != "off":
            self._population = population_mod.PopulationState(
                self.task, fed, seed=seed)
            eval_batch = self._population.eval_batch
        else:
            train_sets, eval_batch = self.task.load_data(fed, seed=seed)
        self.eval_batch = jax.tree.map(jnp.asarray, eval_batch)
        params = self.task.init(jax.random.PRNGKey(seed))
        self.model_bytes = pt.tree_bytes(params)
        kw = dict(server_kwargs or {})
        if (algorithm.startswith("asyncfeded")
                and algorithm != "asyncfeded-perleaf"):
            # per-leaf staleness only exists on the pytree backend
            kw.setdefault("backend", fed.backend)
        self.server = make_server(algorithm, params, fed, **kw)
        if self._population is not None:
            self.clients = []
            if self.server.screen is not None and fed.population == "table":
                # re-home the norm screen's per-client EWMA baselines into
                # the active-set table's stacked array (the materialized
                # reference keeps the default dict — same mapping
                # semantics, different backing, identical traces)
                self.server.screen = screening.make_screen(
                    fed, store=self._population.screen_store())
        else:
            self.clients = [Client(i, self.task, train_sets[i], fed,
                                   seed=seed)
                            for i in range(fed.num_clients)]
        # arrival dynamics: the behavior model owns the timing RNG and the
        # per-client device speeds (behavior-name validation lives in
        # FedConfig.__post_init__; kwargs: config tuple < explicit dict)
        bkw = dict(fed.behavior_params)
        bkw.setdefault("churn_prob", fed.churn_prob)
        bkw.setdefault("dropout_prob", fed.dropout_prob)
        bkw.update(behavior_kwargs or {})
        if self._population is not None:
            bkw.setdefault("population", True)
            bkw.setdefault("arrival_rate", fed.arrival_rate)
            bkw.setdefault("session_stay_prob", fed.session_stay_prob)
        self.behavior = make_behavior(
            behavior or fed.client_behavior, fed, seed=seed,
            model_bytes=self.model_bytes, heterogeneity=heterogeneity, **bkw)
        if self._population is not None and fed.population == "materialized":
            self._population.materialize_all(self.behavior)
        # byzantine cohort (DESIGN.md §11): None for benign configs, so no
        # extra RNG stream exists and traces replay byte-identically
        self.adversary = make_adversary(fed, seed=seed)
        self._eval = jax.jit(
            lambda p: self.task.eval_metrics(p, self.eval_batch))
        self.prox_mu = fed.fedprox_mu if algorithm == "fedprox" else 0.0
        #: the last run's window controller (events.WindowController) —
        #: benchmarks read its .stats() for the autotune telemetry
        self.window_controller = None
        #: the last cohort fan-out's memory plan (budget.CohortPlan)
        self.cohort_plan = None
        # optional early stop on update count (run(max_updates=...)) —
        # an attribute, not a _run_async parameter, so frozen legacy loop
        # copies keep their original signatures
        self._max_updates: Optional[int] = None

    # --------------------------------------------------------------- eval --
    def _eval_point(self, time: float) -> EvalPoint:
        with trace.span("loop.eval", reads=2):
            acc, loss = self._eval(self.server.params)
            return EvalPoint(time, self.server.t, float(acc), float(loss))

    def _plan_dict(self) -> Optional[dict]:
        return None if self.cohort_plan is None else self.cohort_plan.to_dict()

    def _attack_dict(self) -> Optional[dict]:
        return None if self.adversary is None else self.adversary.stats()

    # ------------------------------------------------------- local training --
    def _run_locals(self, jobs: List[Tuple[Client, ServerReply]]
                    ) -> List[ClientUpdate]:
        """Train every ``(client, reply)`` fan-out job, in job order.

        ``FedConfig.client_engine`` picks the execution engine: the exact
        per-client loop, the vectorized cohort engine — one
        vmap-over-clients/scan-over-K dispatch — or the pod-sharded
        cohort engine, the same cores shard_mapped over a ``pod`` mesh so
        each pod trains its own client shard (repro.core.cohort,
        DESIGN.md §7-8). All engines consume identical batcher/RNG
        streams, so the event trace is engine-independent up to float
        tolerance. Cohort fan-outs are planned against
        ``FedConfig.memory_budget_mb`` first (repro.core.budget): the
        plan clamps the vmap width, microbatches the K-scan, or demotes
        the fan-out to the loop engine when even a 2-client chunk
        overflows.
        """
        engine = "loop"
        with trace.span("client.fanout", jobs=len(jobs),
                        engine=lambda: engine):
            if (self.fed.client_engine in cohort.COHORT_ENGINES
                    and len(jobs) > 1):
                ks = [r.k_next for _, r in jobs]
                plan = budget_mod.plan_cohort(
                    self.task, self.fed, clients=len(jobs), k=max(ks),
                    param_bytes=self.model_bytes, prox_mu=self.prox_mu,
                    ragged=len(set(ks)) > 1)
                self.cohort_plan = plan
                engine = plan.engine
            if engine != "loop":
                # run_cohort collapses identical snapshot objects to the
                # broadcast fast path itself (every server path hands a
                # burst one shared model object)
                out = cohort.run_cohort(
                    self.task, [c for c, _ in jobs],
                    [r.params for _, r in jobs], ks,
                    [r.iteration for _, r in jobs], prox_mu=self.prox_mu,
                    per_client_params=True, engine=engine, plan=plan)
                return [u for u, _ in out]
            return [c.run_local(r.params, r.k_next, r.iteration,
                                self.prox_mu)[0] for c, r in jobs]

    def _dispatch(self, loop: EventLoop, now: float,
                  jobs: List[Tuple[Client, ServerReply]]) -> int:
        """Train a fan-out (one cohort job), then arm one arrival per
        client. Behavior draws happen after training, in job order, so the
        event trace is engine-independent. Returns the number of updates
        dispatched (dropped-out clients still count — their aggregation
        happened; they just never come back). Byzantine clients' deltas
        are corrupted here, at emission time — after local training,
        before the event queue — so every client engine and both server
        backends see the identical attacked stream. Compression happens
        after corruption for the same reason: the attacker perturbs what
        the client computed, the wire carries what the attacker emitted
        (DESIGN.md §13)."""
        for (c, reply), upd in zip(jobs, self._run_locals(jobs)):
            if self.adversary is not None:
                upd = self.adversary.corrupt(upd)
            upd = c.compress_update(upd)
            delay = self.behavior.dispatch(c.client_id, reply.k_next, now)
            if delay is not None:
                loop.queue.push(now + delay, c.client_id, upd)
            else:
                c.release_residual()   # permanent dropout: session over
        return len(jobs)

    # ---------------------------------------------------------------- run --
    def run(self, max_time: float = 300.0, eval_every: int = 5,
            max_updates: Optional[int] = None) -> SimResult:
        """Run until virtual ``max_time`` — or until ``max_updates``
        aggregated updates, whichever comes first (the arch path's
        ``--steps`` knob maps onto the event runtime this way)."""
        self._max_updates = max_updates
        if self._population is not None:
            if not self.server.is_async:
                raise ValueError(
                    "population mode drives the async drain loop; "
                    "synchronous aggregators need population='off'")
            return self._run_population(max_time, eval_every)
        if self.server.is_async:
            return self._run_async(max_time, eval_every)
        return self._run_sync(max_time, eval_every)

    def _run_async(self, max_time: float, eval_every: int) -> SimResult:
        points = [self._eval_point(0.0)]
        auto_kw = {}
        if self.fed.window_gamma_threshold > 0:
            auto_kw["gamma_threshold"] = self.fed.window_gamma_threshold
        self.window_controller = make_window_controller(
            self.batch_window, batch_limit=self.server.batch_limit(),
            **auto_kw)
        loop = EventLoop(self.window_controller, max_time)
        # initial seeding: every client fans out at once -> one cohort job
        self._dispatch(loop, 0.0, [(c, self.server.on_connect(c.client_id))
                                   for c in self.clients])
        updates = 0

        def handle(now: float, batch) -> None:
            nonlocal updates
            # one aggregation sweep per drained batch (a batch of one is
            # exactly on_update) ...
            n_hist = len(self.server.history)
            replies = self.server.on_update_batch(
                [ev.payload for ev in batch])
            # staleness feedback for gamma-aware window policies (no-op
            # for fixed windows and plain auto controllers)
            self.window_controller.observe_gamma(
                [h.gamma for h in self.server.history[n_hist:]])
            # ... one eval per drained batch even when it spans several
            # eval_every boundaries — params and clock are identical for
            # every update in the window
            if updates // eval_every != (updates + len(batch)) // eval_every:
                points.append(self._eval_point(now))
            # re-dispatch: every drained client resumes at once from the
            # window's final model -> one cohort job
            updates += self._dispatch(
                loop, now, [(self.clients[ev.client_id], reply)
                            for ev, reply in zip(batch, replies)])
            if self._max_updates is not None and updates >= self._max_updates:
                loop.stop()

        end = loop.run(handle)
        self.server.finalize(end)      # e.g. FedBuff flushes a partial buffer
        points.append(self._eval_point(end))
        return SimResult(self.algorithm, points, self.server.history,
                         updates, loop.drains, self._plan_dict(),
                         self.server.screen_stats(), self._attack_dict())

    def _dispatch_population(self, loop: EventLoop, now: float,
                             jobs: List[Tuple[Client, ServerReply]]) -> None:
        """Population-mode fan-out: identical to :meth:`_dispatch` plus
        active-set bookkeeping — a dropout is permanent (the arrival
        sampler never re-admits the index), a live dispatch marks the
        index in flight so a check-in cannot start a second concurrent
        session for it."""
        pop = self._population
        for (c, reply), upd in zip(jobs, self._run_locals(jobs)):
            if self.adversary is not None:
                upd = self.adversary.corrupt(upd)
            upd = c.compress_update(upd)
            delay = self.behavior.dispatch(c.client_id, reply.k_next, now)
            if delay is None:
                pop.mark_dropped(c.client_id)
                c.release_residual()
                self.server.on_disconnect(c.client_id)
            else:
                pop.mark_dispatch(c.client_id, reply.iteration)
                loop.queue.push(now + delay, c.client_id, upd)

    def _run_population(self, max_time: float, eval_every: int) -> SimResult:
        """The population drain loop (DESIGN.md §12).

        Two event species share one queue: *uploads* (a dispatched
        client's update landing, exactly as in :meth:`_run_async`) and
        *check-ins* (the ``events.CHECKIN`` sentinel — an anonymous client
        from the population contacting the server). The check-in process
        self-chains: each drained check-in schedules the next one, so
        exactly one pending check-in event exists at any time and queue
        size stays O(in-flight cohort), never O(num_clients).

        Per drained batch, in event order: uploads aggregate through
        ``on_update_batch`` (burst semantics identical to the roster
        loop), each drained client draws ``session_continue`` (stay for
        another round, or return to the pool); then each drained check-in
        draws its population index (rejection-sampled over dropped and
        in-flight indices) and connects. Both groups fan out as ONE cohort
        job, so the batched client engines serve check-in admissions and
        session continuations together. All per-index randomness derives
        from (seed, index), so the lazy table and the eager materialized
        reference replay identical traces.
        """
        pop = self._population
        beh = self.behavior
        points = [self._eval_point(0.0)]
        auto_kw = {}
        if self.fed.window_gamma_threshold > 0:
            auto_kw["gamma_threshold"] = self.fed.window_gamma_threshold
        self.window_controller = make_window_controller(
            self.batch_window, batch_limit=self.server.batch_limit(),
            **auto_kw)
        loop = EventLoop(self.window_controller, max_time)
        loop.queue.push(beh.next_checkin(0.0), -1, CHECKIN)
        updates = 0

        def handle(now: float, batch) -> None:
            nonlocal updates
            uploads = [ev for ev in batch if ev.payload is not CHECKIN]
            checkins = [ev for ev in batch if ev.payload is CHECKIN]
            # chain the check-in process first: follow-ups exist before
            # any training happens, so an empty drain cannot stall the run
            for ev in checkins:
                loop.queue.push(beh.next_checkin(ev.time), -1, CHECKIN)
            jobs: List[Tuple[Client, ServerReply]] = []
            if uploads:
                n_hist = len(self.server.history)
                replies = self.server.on_update_batch(
                    [ev.payload for ev in uploads])
                self.window_controller.observe_gamma(
                    [h.gamma for h in self.server.history[n_hist:]])
                before = updates
                updates += len(uploads)
                if before // eval_every != updates // eval_every:
                    points.append(self._eval_point(now))
                for ev, reply in zip(uploads, replies):
                    if beh.session_continue(ev.client_id):
                        # stays in flight: a same-batch check-in cannot
                        # draw this index into a second concurrent session
                        jobs.append((pop.client(ev.client_id), reply))
                    else:
                        pop.mark_returned(ev.client_id)
                        # session over: error-feedback residual released
                        # like the server-side GMIS registration below
                        pop.client(ev.client_id).release_residual()
                        self.server.on_disconnect(ev.client_id)
            for ev in checkins:
                pop.checkins += 1
                idx = beh.sample_index(pop.excluded)
                if idx is None:          # pool exhausted (tiny N only)
                    pop.skipped_checkins += 1
                    continue
                jobs.append((pop.client(idx), self.server.on_connect(idx)))
            if jobs:
                self._dispatch_population(loop, now, jobs)
            if self._max_updates is not None and updates >= self._max_updates:
                loop.stop()

        end = loop.run(handle)
        self.server.finalize(end)    # e.g. FedBuff flushes a partial buffer
        points.append(self._eval_point(end))
        return SimResult(self.algorithm, points, self.server.history,
                         updates, loop.drains, self._plan_dict(),
                         self.server.screen_stats(), self._attack_dict(),
                         pop.stats())

    def _run_sync(self, max_time: float, eval_every: int) -> SimResult:
        points = [self._eval_point(0.0)]
        clock = VirtualClock()
        roster = list(self.clients)
        rounds = 0
        while clock.now < max_time and roster:
            reply0 = self.server.on_connect(0)
            # synchronous round: the whole (surviving) client set is one
            # cohort job
            updates = self._run_locals([(c, reply0) for c in roster])
            if self.adversary is not None:
                updates = [self.adversary.corrupt(u) for u in updates]
            durations = [self.behavior.dispatch(c.client_id, reply0.k_next,
                                                clock.now)
                         for c in roster]
            # dropout permanence matches the async loop: a dropped client's
            # update still aggregates (it uploaded, then left) but it never
            # joins another round — and never bounds another round's
            # straggler max
            roster = [c for c, d in zip(roster, durations) if d is not None]
            live = [d for d in durations if d is not None]
            if not live:                   # every client dropped out
                break
            clock.advance(max(live))       # straggler-bound round time
            self.server.round(updates)
            rounds += 1
            if rounds % max(1, eval_every // 2) == 0 or clock.now >= max_time:
                points.append(self._eval_point(min(clock.now, max_time)))
            if self._max_updates is not None and rounds >= self._max_updates:
                break
        self.server.finalize(min(clock.now, max_time))
        return SimResult(self.algorithm, points, self.server.history,
                         rounds, rounds, self._plan_dict(),
                         self.server.screen_stats(), self._attack_dict())


def run_comparison(task, algorithms: List[str],
                   fed: Optional[FedConfig] = None, max_time: float = 300.0,
                   seeds: Tuple[int, ...] = (0,), eval_every: int = 5,
                   suspension_prob: Optional[float] = None, *,
                   heterogeneity: float = 0.6,
                   server_kwargs: Optional[dict] = None,
                   batch_window: Optional[Any] = None,
                   behavior_kwargs: Optional[dict] = None
                   ) -> Dict[str, List[SimResult]]:
    """Fig. 2/3 driver: same task + clients + clock across algorithms.

    ``task`` is any substrate handle (PaperTaskConfig, LocalTask, name).
    ``heterogeneity``, ``server_kwargs`` (e.g. ``{"backend": "pallas"}``),
    ``batch_window`` (a float or ``"auto"``), and ``behavior_kwargs`` are
    threaded straight into every :class:`FederatedSimulation`, so drivers
    can compare backends/engines/windows without hand-rolling the loop.
    """
    task = tasks_mod.as_task(task)
    fed = fed or task.fed
    if suspension_prob is not None:
        fed = dataclasses.replace(fed, suspension_prob=suspension_prob)
    out: Dict[str, List[SimResult]] = {}
    for alg in algorithms:
        runs = []
        for seed in seeds:
            sim = FederatedSimulation(
                task, fed, algorithm=alg, seed=seed,
                heterogeneity=heterogeneity, server_kwargs=server_kwargs,
                batch_window=batch_window, behavior_kwargs=behavior_kwargs)
            runs.append(sim.run(max_time=max_time, eval_every=eval_every))
        out[alg] = runs
    return out
