"""Barrier-free async generations: the event-driven ES scheduler.

``ES.train`` is a hard per-generation barrier: one straggler sets the
step time, and eval + update costs ADD instead of overlapping
(ROADMAP item 2).  This module removes the barrier two ways, picked per
backend by ``ES.train_async``:

**fold** (host backend, thread + process workers) — the IMPACT
architecture (PAPERS.md, arxiv 1912.00167) built on the IW-ES math
(arxiv 1811.04624, ``algo/iwes.py``):

- member rollouts are *tasks* on an event queue, not a blocking gather:
  workers continuously drain whatever is dispatched, and the scheduler
  keeps roughly two populations in flight so a straggler occupies one
  worker instead of the whole generation;
- an *update* fires whenever one population's worth of results has
  arrived — regardless of which dispatch they came from.  Results
  sampled under an older center (θ_s, σ_s) are FOLDED in with clipped
  importance weights (λ self-normalized per source dispatch, clipped at
  ``iw_clip`` — IMPACT's truncated ratios; the ratio formula is the
  IW-ES one, keyed on the σ/θ the sample was drawn under) instead of
  being discarded or waited on;
- results staler than ``max_stale`` center versions are discarded WITH
  EVIDENCE: the ``stale_discarded`` counter and the event log record
  every one — nothing is silently dropped;
- a deterministic event log records every dispatch (and the center
  version it sampled), every update's consumed set (in arrival order,
  with observed fitness/steps), and every discard.  :meth:`replay`
  re-drives the recorded schedule as pure math — bit-identical
  parameters, every time, independent of wall clock, chaos, or load.

**overlap** (device / pooled / sharded backends) — the fused generation
is one XLA program with no partial results to fold; the barrier there
is the host-side fence + record keeping between dispatches.  The
overlap scheduler submits generation g+1's program from a background
thread before generation g's metrics are materialized, so the host-side
tail (fence, D2H, best-member tracking, record emit) runs while the
device executes the next generation.  Same program sequence, same
states: bit-identical to the synchronous loop.

Resilience contracts preserved (docs/resilience.md): the post-update
anomaly guard rejects non-finite updates with the pre-update center
intact (fold mode re-applies the same batch; overlap mode discards the
speculative program and re-runs — on the sharded engine the speculative
step consumed the in-program-rolled-back state, which makes it the
deterministic re-run itself), chaos hooks fire with the same
once-semantics (member faults keyed on the dispatch index, which is the
generation number in the synchronous loop), and ``es.state`` /
``es.generation`` advance only at update boundaries so checkpoint /
supervisor resume see the same protocol as the synchronous loop.

Telemetry (docs/observability.md): ``async/dispatch`` and ``async/fold``
spans on the shared hub, ``overlap_efficiency`` and
``stale_reuse_ratio`` gauges, ``results_folded`` / ``stale_discarded``
/ ``results_lost`` / ``speculative_discarded`` counters, and a per-
update ``record["async"]`` block that ``obs summarize`` renders as the
async section.
"""

from __future__ import annotations

import dataclasses
import math
import queue
import threading
import time

import numpy as np

from ..host.engine import member_sign_offset
from ..resilience.chaos import member_fault, mutate_fitness
from ..utils.fault import rank_weights_with_failures
from .iwes import clipped_stale_lambdas, mirrored_member_stats

# short poll slice for every blocking point in the event loop: the loop
# must wake to notice dead workers / shutdown, never sleep unbounded
# (esguard R11 blocking-wait-in-scheduler is this rule, mechanized)
POLL_SLICE_S = 0.05


def _count_quantile(counts: dict[int, int], q: float) -> float:
    """Exact nearest-rank quantile over a value → count dict (the
    staleness distribution: small bounded integers)."""
    total = sum(counts.values())
    k = max(1, math.ceil(q * total))
    cum = 0
    for v in sorted(counts):
        cum += counts[v]
        if cum >= k:
            return float(v)
    return float(max(counts))


@dataclasses.dataclass(frozen=True)
class Source:
    """What one dispatch sampled under — the (θ, σ) the importance
    ratio of every late result from it is keyed on."""

    dispatch: int  # dispatch index == the noise-stream generation number
    version: int  # center version (update count) at dispatch time
    params: np.ndarray  # (dim,) float32 center snapshot
    sigma: float
    offsets: np.ndarray  # per-pair (mirrored) or per-member table offsets
    t_dispatch: float = 0.0  # perf_counter at snapshot (0 in replay)


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One member's result landing on the event queue."""

    dispatch: int
    member: int
    fitness: float
    steps: int
    eval_s: float  # worker busy seconds (straggler sleeps included)
    t_arrival: float = 0.0  # perf_counter at event-queue entry (0 in replay)


class AsyncEventLog:
    """The deterministic schedule of a fold-mode run.

    JSON-able; :meth:`GenerationScheduler.replay` consumes it.  The log
    is the full accounting contract: every dispatched member appears in
    exactly one of ``consumed`` (in the fold's canonical order, with the
    fitness/steps the update actually ranked — the importance weight
    re-derives from the sources), ``discarded`` (too stale or past run
    end, counted), or ``lost`` (its worker died, counted)."""

    def __init__(self):
        self.dispatches: list[list] = []  # [dispatch, version]
        self.updates: list[dict] = []
        self.discarded: list[list] = []  # [dispatch, member]
        self.lost: list[list] = []  # [dispatch, member]
        # elastic multi-host runs (parallel/elastic.py) additionally
        # record membership transitions: {"event": "join"|"leave",
        # "host": id, "at_dispatch": count}.  Forensic, not replayed —
        # replay is pure math over dispatches/updates; membership is
        # WHY the schedule looked the way it did
        self.membership: list[dict] = []

    def to_dict(self) -> dict:
        out = {
            "schema": 1,
            "dispatches": [list(d) for d in self.dispatches],
            "updates": self.updates,
            "discarded": [list(d) for d in self.discarded],
            "lost": [list(d) for d in self.lost],
        }
        if self.membership:
            out["membership"] = [dict(m) for m in self.membership]
        return out

    @classmethod
    def from_dict(cls, data: dict) -> "AsyncEventLog":
        log = cls()
        log.dispatches = [list(d) for d in data.get("dispatches", [])]
        log.updates = list(data.get("updates", []))
        log.discarded = [list(d) for d in data.get("discarded", [])]
        log.lost = [list(d) for d in data.get("lost", [])]
        log.membership = [dict(m) for m in data.get("membership", [])]
        return log


# ---------------------------------------------------------------------
# result sources: who evaluates dispatched members and how results
# arrive.  Thread source = member-granular; process source = slice-
# granular over the ProcessPool async API.
# ---------------------------------------------------------------------


class _ThreadSource:
    """Member-granular task pool over scheduler-OWNED scratch workers.

    Each worker thread owns one (scratch policy, agent) pair and drains
    a shared task queue; results land on the scheduler's event queue.
    A chaos straggler sleeps inside ONE worker's rollout — the others
    keep draining, which is the whole point.

    The scratch pairs are built fresh here rather than borrowed from
    ``engine._workers``: ``close()`` bounds its join (R11), so a
    straggler can outlive the run as a leaked daemon thread — it must
    then be touching only objects a subsequent ``train()`` call will
    never load a new θ into."""

    def __init__(self, engine, events: "queue.Queue"):
        from ..host.engine import HostEngine

        self.engine = engine
        self.events = events
        self._tasks: queue.Queue = queue.Queue()
        self._stop = threading.Event()
        self._call_rollout = HostEngine._call_rollout
        self._workers = [
            (engine._new_scratch_policy(), engine.agent_factory())
            for _ in range(engine.n_proc)
        ]
        self._threads = [
            threading.Thread(target=self._worker, args=(w,), daemon=True)
            for w in range(engine.n_proc)
        ]
        for t in self._threads:
            t.start()

    def dispatch(self, source: Source) -> list[int]:
        """Queue every member of ``source``; returns the member list."""
        members = list(range(self.engine.population_size))
        for i in members:
            self._tasks.put((source, i))
        return members

    def _worker(self, w: int) -> None:
        policy, agent = self._workers[w]
        eng = self.engine
        while not self._stop.is_set():
            try:
                source, i = self._tasks.get(timeout=POLL_SLICE_S)
            except queue.Empty:
                continue
            sign, off = member_sign_offset(source.offsets, i, eng.mirrored)
            theta = source.params + source.sigma * sign * eng._eps(off)
            eng._load(policy, theta)
            t0 = time.perf_counter()
            try:
                # chaos keyed on the dispatch index — the same
                # (generation, member) coordinates a synchronous run's
                # plan uses, with the same fire-once semantics
                member_fault(source.dispatch, i)
                res = self._call_rollout(agent, policy)
                fit, steps = res.total_reward, res.steps
            except Exception:  # noqa: BLE001 — NaN marks the member failed
                fit, steps = float("nan"), 0
            t1 = time.perf_counter()
            self.events.put(Arrival(source.dispatch, i, float(fit),
                                    int(steps), t1 - t0, t1))

    def poll_lost(self, timeout_s: float = POLL_SLICE_S
                  ) -> list[tuple[int, int]]:
        return []  # threads don't die silently; exceptions became NaN

    def notify_update(self, version: int, state) -> None:
        pass  # workers read θ from the Source snapshot, not a push

    def close(self) -> None:
        self._stop.set()
        for w, t in enumerate(self._threads):
            t.join(timeout=5.0)
            if t.is_alive():
                # a straggler outliving the bounded join leaks as a
                # daemon thread — harmless (it holds only scheduler-
                # owned scratch), but it must leave evidence (R08)
                self.engine.telemetry.counters.inc("worker_threads_leaked")
                self.engine.telemetry.event("worker_thread_leaked",
                                            worker=w)

    @property
    def n_workers(self) -> int:
        return len(self._threads)


class _ProcessSource:
    """Slice-granular dispatch over the ProcessPool async API.

    One message per (dispatch, worker); a slow slice delays only its
    own members.  Late replies are returned by ``ProcessPool.poll`` —
    never discarded by sequence tag — and a worker that died with
    slices outstanding surrenders them as LOST (counted, evented)."""

    def __init__(self, engine, events: "queue.Queue"):
        self.engine = engine
        self.events = events
        self._ensure_pool()
        # seq -> (dispatch, member indices, worker) for loss accounting
        self._outstanding: dict[int, tuple[int, list[int], int]] = {}
        self._lost_now: list[tuple[int, int]] = []

    def _ensure_pool(self):
        from ..host.procpool import ProcessPool

        eng = self.engine
        if eng._proc_pool is None or eng._proc_pool.n_proc != eng.n_proc:
            if eng._proc_pool is not None:
                eng._proc_pool.close()
            eng._proc_pool = ProcessPool(
                eng.policy_factory, eng.agent_factory, eng.n_proc,
                eng.population_size, eng.dim, eng.table,
                master_state=eng.master.state_dict(),
                mirrored=eng.mirrored,
            )
        eng._proc_pool.telemetry = eng.telemetry
        self.pool = eng._proc_pool

    def dispatch(self, source: Source) -> list[int]:
        from ..resilience.chaos import kill_workers

        # respawn closes dead workers' pipes, which would ORPHAN their
        # outstanding slices (never swept as lost — a permanent phantom
        # in the scheduler's inflight set): drain whatever they managed
        # to buffer, surrender the rest as lost, THEN respawn
        self._drain(0.0)
        self._sweep_dead(final=True)
        self.pool.respawn_dead()  # dispatch boundary = respawn boundary
        killed = kill_workers(source.dispatch, self.pool.worker_pids)
        if killed:
            self.engine.telemetry.counters.inc("chaos_worker_kills",
                                               len(killed))
            self.engine.telemetry.event("chaos_worker_kill", pids=killed,
                                        gen=int(source.dispatch))
        members: list[int] = []
        n, w_n = self.engine.population_size, self.pool.n_proc
        for w in range(w_n):
            indices = list(range(w, n, w_n))
            seq = self.pool.dispatch(
                w, source.params, source.sigma, source.offsets,
                source.dispatch, indices=None,
            )
            if seq is None:
                # send failed (dead pipe): the slice is lost up front
                self._lose(source.dispatch, indices)
                continue
            self._outstanding[seq] = (source.dispatch, indices, w)
            members.extend(indices)
        return members

    def _lose(self, dispatch: int, indices: list[int]) -> None:
        tel = self.engine.telemetry
        tel.counters.inc("results_lost", len(indices))
        tel.event("results_lost", dispatch=int(dispatch), n=len(indices))
        self._lost_now.extend((dispatch, i) for i in indices)

    def _drain(self, timeout_s: float) -> None:
        """Pull buffered replies into the event queue.  A zero timeout
        drains only what is already readable; repeated until dry because
        one wait round returns at most one message per connection."""
        while True:
            got = self.pool.poll(timeout_s)
            for seq, indices, fitness, _bc, steps, eval_s in got:
                info = self._outstanding.pop(seq, None)
                if info is None:
                    continue  # a reply from a pre-scheduler sequence
                dispatch, _, _ = info
                k = max(len(indices), 1)
                per = eval_s / k
                base_steps, rem = divmod(int(steps), k)
                t_arr = time.perf_counter()
                for j, i in enumerate(indices):
                    # remainder spread keeps the slice's step total
                    # EXACT — env_steps is the headline metric
                    self.events.put(Arrival(
                        dispatch, int(i), float(fitness[j]),
                        base_steps + (1 if j < rem else 0), per, t_arr))
            if not got:
                return
            timeout_s = 0.0  # first wait bounded; the rest just drain

    def _sweep_dead(self, final: bool = False) -> None:
        """Account slices owned by dead workers as lost.  ``final``
        surrenders even slices whose pipe still has buffered data (the
        caller is about to close those pipes); otherwise drainable
        replies are left for the next poll."""
        dead = {w for w in range(self.pool.n_proc)
                if not self.pool.worker_alive(w)}
        if not dead:
            return
        for seq in [s for s, (_, _, w) in self._outstanding.items()
                    if w in dead]:
            dispatch, indices, w = self._outstanding[seq]
            if not final and self.pool.conn_has_data(w):
                continue  # buffered reply — the next drain gets it
            del self._outstanding[seq]
            self._lose(dispatch, indices)

    def poll_lost(self, timeout_s: float = POLL_SLICE_S
                  ) -> list[tuple[int, int]]:
        """Drain arrived slices into the event queue; returns members
        lost to dead workers (accumulated since the last call)."""
        self._drain(timeout_s)
        # slices owned by workers that died with an empty pipe never
        # arrive: account them as lost so nothing is silently dropped
        self._sweep_dead(final=False)
        out, self._lost_now = self._lost_now, []
        return out

    def notify_update(self, version: int, state) -> None:
        pass  # workers read θ from the Source snapshot, not a push

    def close(self) -> None:
        pass  # the pool belongs to the engine; HostEngine.close owns it

    @property
    def n_workers(self) -> int:
        return self.pool.n_proc


# ---------------------------------------------------------------------
# the fold scheduler
# ---------------------------------------------------------------------


class GenerationScheduler:
    """Event-driven barrier-free generations for the host backend.

    One instance drives one ``es.train_async`` call; ``run`` is the
    live event loop, ``replay`` re-drives a recorded schedule."""

    def __init__(self, es, max_stale: int = 16, iw_clip: float = 2.0,
                 max_consecutive_rejections: int = 3):
        self._check_es(es)
        if max_stale < 1:
            raise ValueError(f"max_stale must be >= 1, got {max_stale}")
        if iw_clip < 1.0:
            raise ValueError(
                f"iw_clip must be >= 1 (1 = mean-normalized ratios fully "
                f"truncated), got {iw_clip}")
        self.es = es
        self.engine = es.engine
        self.obs = es.obs
        self.max_stale = int(max_stale)
        self.iw_clip = float(iw_clip)
        self.max_consecutive_rejections = int(max_consecutive_rejections)
        self.n = es.population_size
        self.log = AsyncEventLog()
        self._sources: dict[int, Source] = {}
        self._consumed_total = 0
        self._folded_total = 0
        self._discarded_total = 0
        # causal/tail accounting for the CURRENT update window: dispatch
        # ids snapshotted and results discarded since the last record —
        # the record's `async` block carries them so `obs trace` can draw
        # the dispatch → fold/discard flow arrows
        self._dispatched_since_update: list[int] = []
        self._discards_since_update: dict[int, int] = {}
        # exact staleness distribution: value → count.  Staleness is a
        # SMALL INTEGER (bounded by max_stale), so the log-seconds hist
        # ladder would distort it (0 → the underflow midpoint ~9e-6);
        # this dict is bounded by max_stale+1 keys and quantiles walk it
        # exactly
        self._staleness_counts: dict[int, int] = {}

    # -------------------------------------------------- backend hooks
    # (the elastic host-granular scheduler overrides these; everything
    # else — pacing, staleness, accounting, replay — is shared)

    def _check_es(self, es) -> None:
        if es.backend != "host":
            raise ValueError(
                "GenerationScheduler folds partial host results; device/"
                "pooled/sharded backends use the overlap scheduler "
                f"(got backend={es.backend!r})"
            )

    def _sigma_of(self, st) -> float:
        return float(self.engine._state_sigma(st))

    def _offsets_for(self, st, dispatch: int) -> np.ndarray:
        return np.asarray(
            self.engine._pair_offsets(st._replace(generation=dispatch)))

    def _ensure_compiled(self) -> None:
        es = self.es
        if es.compile_time_s is None:
            es.compile_time_s = self.engine.compile(es.state)

    def _make_source(self, events: "queue.Queue"):
        source_cls = (_ProcessSource
                      if self.engine.worker_mode == "process"
                      else _ThreadSource)
        return source_cls(self.engine, events)

    def _inflight_budget(self, src_pool) -> int:
        """Member count to keep in flight beyond the arrived backlog —
        one population here (the loop adds a population per dispatch, so
        ~2 stay in flight); the elastic scheduler scales it by live
        hosts."""
        return self.n

    # ------------------------------------------------------------ sources

    def _snapshot(self, dispatch: int, version: int) -> Source:
        """Freeze the center the noise stream of ``dispatch`` samples
        under.  Offsets derive from (key, dispatch) exactly like the
        synchronous loop's (key, generation) — dispatch d of an async
        run and generation d of a sync run draw the same noise."""
        st = self.es.state
        src = Source(
            dispatch=dispatch, version=version,
            params=np.array(np.asarray(st.params_flat), np.float32,
                            copy=True),
            sigma=self._sigma_of(st),
            offsets=self._offsets_for(st, dispatch),
            t_dispatch=time.perf_counter(),
        )
        self._sources[dispatch] = src
        self.log.dispatches.append([dispatch, version])
        self._dispatched_since_update.append(dispatch)
        self.obs.event("async_dispatch", trace=f"d{dispatch}",
                       dispatch=int(dispatch), version=int(version))
        return src

    def _prune_sources(self, version: int,
                       referenced: set[int] = frozenset()) -> None:
        """Drop snapshots no longer foldable (staler than max_stale —
        the exact complement of the fold-eligibility rule, so a still-
        consumable source is never pruned) and not referenced by any
        in-flight or arrived-but-unconsumed result — bounded memory
        however long the run."""
        for d in [d for d, s in self._sources.items()
                  if s.version < version - self.max_stale
                  and d not in referenced]:
            del self._sources[d]

    # ---------------------------------------------------------- fold math

    def _fold_batch(self, batch: list[Arrival], version: int):
        """One combined update from a mixed-staleness batch.

        Pure given (center state, sources, batch): the live loop and
        :meth:`replay` share it, which is WHY replay is bit-identical.
        Members are processed sorted by (dispatch, member) so the float
        summation order depends only on batch membership, not on the
        arrival interleave inside the batch."""
        eng = self.engine
        st = self.es.state
        batch = sorted(batch, key=lambda a: (a.dispatch, a.member))
        fit = np.asarray([a.fitness for a in batch], np.float32)
        # chaos nan_fitness keyed on the state's generation number —
        # the same coordinate the sync loop's gather mutation uses
        fit = mutate_fitness(int(st.generation), fit)
        n_valid = int(np.isfinite(fit).sum())
        if n_valid < 2:
            return None, None, fit, {"n_valid": n_valid}
        w = rank_weights_with_failures(fit)
        sigma_u = self._sigma_of(st)
        center = np.asarray(st.params_flat, np.float32)
        dim = eng.dim

        grad = np.zeros(dim, np.float32)
        n_fresh = 0
        lam_stale: list[float] = []
        by_dispatch: dict[int, list[int]] = {}
        for j, a in enumerate(batch):
            by_dispatch.setdefault(a.dispatch, []).append(j)
        with self.obs.phase("async"):
            with self.obs.phase("fold"):
                for d in sorted(by_dispatch):
                    src = self._sources[d]
                    idx = by_dispatch[d]
                    k = len(idx)
                    signs = np.empty(k, np.float32)
                    offs = np.empty(k, np.int64)
                    for kk, j in enumerate(idx):
                        sign, off = member_sign_offset(
                            src.offsets, batch[j].member, eng.mirrored)
                        signs[kk] = sign
                        offs[kk] = off
                    if src.version == version:
                        lam = np.ones(k, np.float32)
                        c = 1.0
                        d_vec = None
                        n_fresh += k
                    else:
                        d_vec = ((src.params - center) / sigma_u).astype(
                            np.float32)
                        c = src.sigma / sigma_u
                        dots = np.empty(k, np.float32)
                        norms = np.empty(k, np.float32)
                        for kk in range(k):
                            eps = eng._eps(int(offs[kk]))
                            dots[kk] = float(eps @ d_vec) * signs[kk]
                            norms[kk] = float(eps @ eps)
                        d2 = float(d_vec @ d_vec)
                        lam = clipped_stale_lambdas(dots, norms, d2, c,
                                                    dim, self.iw_clip)
                        lam_stale.extend(float(x) for x in lam)
                    coeff = (np.asarray([w[j] for j in idx], np.float32)
                             * lam)
                    # ε'_i = d + c·s_i·ε_i — the reused perturbation seen
                    # from the CURRENT center (fresh: d=0, c=1 → s·ε),
                    # streamed row-by-row from zero-copy table views like
                    # the synchronous apply_weights (no (k, dim) temp)
                    for kk in range(k):
                        grad += ((coeff[kk] * signs[kk] * c)
                                 * eng._eps(int(offs[kk])))
                    if d_vec is not None:
                        grad += float(coeff.sum()) * d_vec
        grad /= len(batch) * sigma_u
        with self.obs.phase("update"):
            new_state, gnorm = eng.apply_grad(st, grad)
        stats = {
            "n_valid": n_valid,
            "fresh": n_fresh,
            "folded": len(batch) - n_fresh,
            "mean_lambda": (round(float(np.mean(lam_stale)), 4)
                            if lam_stale else None),
            "max_staleness": version - min(
                self._sources[d].version for d in by_dispatch),
            # (dispatch, member count) pairs this update consumed — the
            # causal half of the record's async block (`obs trace` flow
            # arrows link each dispatch to the update that folded it)
            "consumed_by_dispatch": [[int(d), len(by_dispatch[d])]
                                     for d in sorted(by_dispatch)],
        }
        return new_state, gnorm, fit, stats

    def _best_theta(self, arrival: Arrival) -> np.ndarray:
        src = self._sources[arrival.dispatch]
        sign, off = member_sign_offset(src.offsets, arrival.member,
                                       self.engine.mirrored)
        return src.params + src.sigma * sign * np.asarray(
            self.engine._eps(off))

    # -------------------------------------------------------- update step

    def _apply_update(self, batch: list[Arrival], version: int,
                      t_start, log_fn, verbose: bool,
                      rejected_streak: int) -> tuple[bool, int]:
        """Rank + fold + anomaly-guard + record for one batch.
        ``t_start`` is when the previous update finished (None in
        replay); the record's wall window closes AFTER the fold+apply so
        the update's own cost is inside it.  Returns (applied,
        rejected_streak)."""
        es = self.es
        obs = self.obs
        new_state, gnorm, fit, stats = self._fold_batch(batch, version)
        dt = (time.perf_counter() - t_start) if t_start is not None else 0.0
        # the shared rejection policy (ES._update_anomaly — the ONE
        # definition): feed it the same metrics shape the engines report
        reason = es._update_anomaly({
            "n_valid": stats["n_valid"],
            "update_finite": bool(
                new_state is not None and np.isfinite(gnorm)
                and np.isfinite(new_state.params_flat).all()),
        })
        if reason is not None:
            # the center was never touched (apply_grad returns a NEW
            # state); count, event, and re-apply the same batch — chaos
            # nan_update fires once, so the re-apply is clean
            obs.counters.inc("generations_rejected")
            obs.event("generation_rejected", reason=reason,
                      n_valid=int(stats["n_valid"]))
            obs.discard_phases()
            rejected_streak += 1
            if rejected_streak > self.max_consecutive_rejections:
                raise RuntimeError(
                    f"{reason}; {rejected_streak} consecutive updates "
                    "rejected — check env/rollout health")
            return False, rejected_streak

        # best tracking with source-aware member reconstruction;
        # fit is in sorted-batch order (the fold's canonical order)
        batch_sorted = sorted(batch, key=lambda a: (a.dispatch, a.member))
        finite_any = bool(np.isfinite(fit).any())
        gen_best = float(np.nanmax(fit)) if finite_any else float("nan")
        improved = finite_any and gen_best > es.best_reward
        if improved:
            es.best_reward = gen_best
            es._best_flat = np.asarray(
                self._best_theta(batch_sorted[int(np.nanargmax(fit))]),
                np.float32)

        # dispatch-lifecycle distributions (docs/observability.md "Tails
        # & traces"): judged per CONSUMED member at the accepted fold —
        # a rejected batch's retry must not double-observe.  Wall-clock
        # legs (arrival→fold queue wait, dispatch→fold latency) are
        # live-only (t_start is None in replay, whose clocks are fake);
        # staleness is pure math and recorded in both.
        t_now = time.perf_counter() if t_start is not None else None
        for a in batch:
            src = self._sources[a.dispatch]
            staleness = version - src.version
            self._staleness_counts[staleness] = (
                self._staleness_counts.get(staleness, 0) + 1)
            # the hub histogram (exported by /metrics) uses a ladder
            # sized for small integers, not the default seconds ladder
            obs.hists.observe("async/staleness", staleness,
                              lo=0.5, decades=4, per_decade=3)
            if t_now is not None:
                if a.t_arrival:
                    obs.hists.observe("async/queue_wait_s",
                                      t_now - a.t_arrival)
                if src.t_dispatch:
                    obs.hists.observe("async/fold_latency_s",
                                      t_now - src.t_dispatch)

        steps = int(sum(a.steps for a in batch))
        sigma = self._sigma_of(es.state)
        es.state = new_state
        # the log append rides IMMEDIATELY on the state transition: the
        # two together are "this batch was consumed" — anything raising
        # later (record plumbing, a user log_fn) must not let the run
        # loop re-queue or the shutdown sweep double-account the batch
        self.log.updates.append({
            "u": version,
            "consumed": [[a.dispatch, a.member, float(fit[j]), a.steps]
                         for j, a in enumerate(batch_sorted)],
        })
        self._consumed_total += len(batch)
        self._folded_total += int(stats["folded"])
        busy = sum(a.eval_s for a in batch)
        oe = self._overlap_efficiency(busy, dt)
        record = {
            "generation": es.generation,
            "reward_max": gen_best,
            "reward_mean": (float(np.nanmean(fit)) if finite_any
                            else float("nan")),
            "reward_min": (float(np.nanmin(fit)) if finite_any
                           else float("nan")),
            "n_failed": int(np.size(fit) - np.isfinite(fit).sum()),
            "best_reward": es.best_reward,
            "improved_best": improved,
            "env_steps": steps,
            "env_steps_per_sec": steps / dt if dt > 0 else 0.0,
            "grad_norm": float(gnorm),
            "sigma": sigma,
            "wall_time_s": dt,
            "async": {
                "consumed": len(batch),
                "fresh": int(stats["fresh"]),
                "folded": int(stats["folded"]),
                "stale_discarded": int(
                    sum(self._discards_since_update.values())),
                "max_staleness": int(stats["max_staleness"]),
                "mean_lambda": stats["mean_lambda"],
                "overlap_efficiency": oe,
                # causal identity: dispatches snapshotted this window,
                # (dispatch, count) consumed by THIS update, (dispatch,
                # count) discarded this window — `obs trace` renders
                # them as flow arrows (docs/observability.md)
                "dispatches": [int(d) for d in
                               self._dispatched_since_update],
                "consumed_dispatches": stats["consumed_by_dispatch"],
                "discarded_dispatches": [
                    [int(d), int(n)] for d, n in
                    sorted(self._discards_since_update.items())],
            },
        }
        qw50 = obs.hists.quantile("async/queue_wait_s", 0.5)
        qw99 = obs.hists.quantile("async/queue_wait_s", 0.99)
        if qw50 is not None and qw99 is not None:
            record["async"]["queue_wait_s"] = {"p50": round(qw50, 6),
                                               "p99": round(qw99, 6)}
        if self._staleness_counts:
            record["async"]["staleness_q"] = {
                "p50": _count_quantile(self._staleness_counts, 0.5),
                "p99": _count_quantile(self._staleness_counts, 0.99)}
        self._dispatched_since_update = []
        self._discards_since_update = {}
        obs.counters.inc("async_updates")
        if stats["folded"]:
            obs.counters.inc("results_folded", int(stats["folded"]))
        obs.counters.gauge("overlap_efficiency", oe if oe is not None else 0.0)
        obs.counters.gauge(
            "stale_reuse_ratio",
            round(self._folded_total / max(self._consumed_total, 1), 4))
        # (the logged fitness is the POST-chaos-mutation value the fold
        # actually ranked, in canonical sorted order: a replay reproduces
        # a nan_fitness-burst run exactly without re-firing the burst)
        es._emit_record(es._finalize_record(record), log_fn, verbose)
        return True, 0

    def _overlap_efficiency(self, busy_s: float, wall_s: float):
        """Worker-busy fraction of the consuming update's wall window:
        (Σ eval seconds of the batch / n_workers) / wall, clipped to
        [0, 1].  1.0 = the workers never idled while this update's
        window elapsed — evaluation fully hidden behind the rolling
        updates; a synchronous barrier loop scores eval/(eval+update).
        Approximate by construction (a late result's busy seconds were
        spent in earlier windows) and documented as such
        (docs/async.md)."""
        if wall_s <= 0 or not self._n_workers:
            return None
        ratio = (busy_s / self._n_workers) / wall_s
        return round(float(min(max(ratio, 0.0), 1.0)), 4)

    _n_workers = 0

    # ---------------------------------------------------------- live loop

    def run(self, n_steps: int, log_fn=None, verbose: bool = True):
        es = self.es
        obs = self.obs
        obs.discard_phases()
        self._ensure_compiled()
        events: queue.Queue = queue.Queue()
        src_pool = self._make_source(events)
        self._n_workers = src_pool.n_workers
        self._discards_since_update = {}

        version = 0
        dispatched = 0
        # dispatch ids continue the state's generation numbering, so a
        # chaos plan's (gen, member) coordinates and the (key, gen)
        # noise streams mean the same thing in sync and async runs.
        # A lossy run dispatches MORE generations than it applies
        # updates (loss replacement), and state.generation only counts
        # updates — the high-water mark keeps a follow-up train_async
        # call off the already-consumed streams (a follow-up *sync*
        # train() can still overlap them; statistical correlation, not
        # corruption — docs/async.md)
        base = max(int(es.state.generation),
                   int(getattr(es, "_async_next_dispatch", 0)))
        inflight: dict[tuple[int, int], bool] = {}
        arrived: list[Arrival] = []
        updates_done = 0
        rejected_streak = 0
        lost = 0
        t_update = time.perf_counter()

        def discard(a: Arrival, staleness) -> None:
            obs.counters.inc("stale_discarded")
            obs.event("stale_discarded", dispatch=int(a.dispatch),
                      member=int(a.member), staleness=staleness,
                      trace=f"d{a.dispatch}")
            self.log.discarded.append([a.dispatch, a.member])
            self._discarded_total += 1
            self._discards_since_update[a.dispatch] = (
                self._discards_since_update.get(a.dispatch, 0) + 1)
            if a.t_arrival:
                obs.hists.observe("async/discard_latency_s",
                                  time.perf_counter() - a.t_arrival)

        empty_dispatches = 0
        try:
            while updates_done < n_steps:
                # ---- keep the workers fed: at most ~2 populations in
                # flight, and never fewer results in the pipeline than
                # the remaining updates demand — results LOST to dead
                # workers are replaced by extra dispatches (fresh noise
                # generations), so a lossy run still finishes its
                # schedule with full batches
                remaining = (n_steps - updates_done) * self.n - len(arrived)
                while len(inflight) < min(self._inflight_budget(src_pool),
                                          remaining):
                    # the dispatch's trace id threads through its span,
                    # the async_dispatch event, and every later fold /
                    # discard event — one grep through the flight
                    # recorder follows a dispatch end to end
                    with obs.trace_ctx(f"d{base + dispatched}"), \
                            obs.phase("async"):
                        with obs.phase("dispatch"):
                            src = self._snapshot(base + dispatched, version)
                            members = src_pool.dispatch(src)
                            for i in members:
                                inflight[(src.dispatch, i)] = True
                            dispatched += 1
                    # a dispatch that could reach NO worker (every pipe
                    # dead even after respawn) must not spin forever
                    empty_dispatches = (0 if members
                                        else empty_dispatches + 1)
                    if empty_dispatches > 3:
                        raise RuntimeError(
                            f"async scheduler ran dry after "
                            f"{updates_done}/{n_steps} updates: "
                            f"{empty_dispatches} consecutive dispatches "
                            f"reached no live worker ({lost} results "
                            f"lost so far)")

                # ---- collect arrivals (one bounded wait, then drain);
                # with a full population already waiting the wait drops
                # to a pure drain, so a ready update never sits behind a
                # poll slice
                with obs.phase("eval"):
                    ready = len(arrived) >= self.n
                    for d, i in src_pool.poll_lost(
                            0.0 if ready else POLL_SLICE_S):
                        inflight.pop((d, i), None)
                        self.log.lost.append([d, i])
                        lost += 1
                    try:
                        a = (events.get_nowait() if ready
                             else events.get(timeout=POLL_SLICE_S))
                    except queue.Empty:
                        a = None
                    while a is not None:
                        inflight.pop((a.dispatch, a.member), None)
                        # per-member eval seconds as a distribution: the
                        # straggler tail the mean-shaped overlap metrics
                        # fold away
                        obs.hists.observe("async/eval_s", a.eval_s)
                        arrived.append(a)
                        try:
                            a = events.get_nowait()
                        except queue.Empty:
                            a = None

                # ---- staleness is judged when the batch forms (the
                # center may have moved while a result sat in the
                # arrived list): too-stale results are discarded WITH
                # EVIDENCE — counter + event + log entry, never silently
                still: list[Arrival] = []
                for a in arrived:
                    s = self._sources.get(a.dispatch)
                    if s is None or s.version < version - self.max_stale:
                        discard(a, version - s.version if s else None)
                    else:
                        still.append(a)
                arrived = still

                # ---- update trigger: one population's worth arrived
                # (lost results were re-dispatched above, so every
                # update consumes a full population's worth)
                if len(arrived) >= self.n:
                    batch, arrived = arrived[:self.n], arrived[self.n:]
                    n_logged = len(self.log.updates)
                    try:
                        applied, rejected_streak = self._apply_update(
                            batch, version, t_update, log_fn, verbose,
                            rejected_streak)
                    except BaseException:
                        # an aborting update (persistent-rejection raise,
                        # KeyboardInterrupt, a raising user log_fn) must
                        # not lose its batch from the finally's
                        # accounting sweep — unless the batch was already
                        # CONSUMED (state advanced + logged), in which
                        # case re-queueing would double-account it
                        if len(self.log.updates) == n_logged:
                            arrived = batch + arrived
                        raise
                    if applied:
                        t_update = time.perf_counter()
                        version += 1
                        updates_done += 1
                        # the elastic source pushes the new center to
                        # every live host here (O(dim) broadcast);
                        # in-process sources have nothing to push
                        src_pool.notify_update(version, es.state)
                        self._prune_sources(
                            version,
                            {d for d, _ in inflight}
                            | {a.dispatch for a in arrived})
                    else:
                        # rejected: re-queue the batch for the retried
                        # apply (same membership → deterministic re-run)
                        arrived = batch + arrived
        finally:
            # one final zero-timeout loss drain: a dispatch surrendered
            # as lost moments before an aborting raise (the dry-out
            # guard fires straight after the empty dispatch) must still
            # land on the log — no poll ever ran after it
            try:
                for d, i in src_pool.poll_lost(0.0):
                    inflight.pop((d, i), None)
                    self.log.lost.append([d, i])
                    lost += 1
            except Exception:  # noqa: BLE001 — the run is already over
                obs.event("final_loss_drain_failed")
            src_pool.close()
            # tail accounting: results still in flight or arrived-but-
            # unconsumed at shutdown are recorded as discarded (the run
            # is over; they fold nowhere) — the accounting invariant
            # dispatched == consumed + discarded + lost always holds
            leftovers = list(inflight) + [(a.dispatch, a.member)
                                          for a in arrived]
            for d, i in leftovers:
                self.log.discarded.append([d, i])
            if leftovers:
                obs.counters.inc("stale_discarded", len(leftovers))
                obs.event("run_end_discard", n=len(leftovers))
                self._discarded_total += len(leftovers)
            es._async_next_dispatch = base + dispatched
            # the log is the torn run's forensic artifact — it must
            # survive a raising run, not only a clean one
            es._async_log = self.log
        return es

    # -------------------------------------------------------------- replay

    def replay(self, log: "AsyncEventLog | dict", log_fn=None,
               verbose: bool = False, n_steps: int | None = None):
        """Re-drive a recorded schedule as pure math: same dispatch
        snapshots, same batches in the same order, same fold formula —
        bit-identical parameters, independent of wall clock or chaos.

        The recorded fitness/steps are applied directly (no re-rollout),
        so a replay reproduces a chaos-torn live run exactly: a member
        the live run saw NaN (injected rollout_exc) stays NaN here.
        ``n_steps`` (when given) must match the recorded update count —
        a mismatch is a caller error, not something to silently ignore."""
        if isinstance(log, dict):
            log = AsyncEventLog.from_dict(log)
        if n_steps is not None and n_steps != len(log.updates):
            raise ValueError(
                f"replay drives the RECORDED schedule: n_steps={n_steps} "
                f"but the log holds {len(log.updates)} updates — pass the "
                "log's own count (or drop n_steps)")
        es = self.es
        es.obs.discard_phases()
        dispatch_iter = iter(log.dispatches)
        next_dispatch = next(dispatch_iter, None)
        version = 0
        rejected_streak = 0
        self._n_workers = 0
        self._dispatched_since_update = []
        self._discards_since_update = {}
        self._staleness_counts = {}
        for entry in log.updates:
            # materialize every snapshot the schedule took at <= this
            # version, in recorded order (dispatch versions are
            # non-decreasing by construction)
            while (next_dispatch is not None
                   and next_dispatch[1] <= version):
                self._snapshot(int(next_dispatch[0]),
                               int(next_dispatch[1]))
                next_dispatch = next(dispatch_iter, None)
            batch = [Arrival(int(d), int(i), float(f), int(s), 0.0)
                     for d, i, f, s in entry["consumed"]]
            applied = False
            while not applied:
                applied, rejected_streak = self._apply_update(
                    batch, version, None, log_fn, verbose, rejected_streak)
            version += 1
            self._prune_sources(version)
        es._async_log = self.log
        return es


# ---------------------------------------------------------------------
# the elastic host-granular scheduler (parallel/elastic.py fleets)
# ---------------------------------------------------------------------


class _HostSource:
    """Host-granular source: each dispatch is a FULL population evaluated
    by one remote host of an elastic fleet (parallel/elastic.py), results
    arrive a population at a time, and a dead host's in-flight dispatches
    surrender as ``results_lost`` — the PR-8 worker-source contract lifted
    to host granularity.

    The fleet object (``ElasticCoordinator``) owns the sockets and the
    membership table; this adapter owns the scheduler-facing accounting:
    Arrival conversion, membership entries on the event log, the per-host
    latency distributions, and the loss/membership counters."""

    def __init__(self, scheduler: "ElasticScheduler", fleet,
                 events: "queue.Queue"):
        self.sched = scheduler
        self.fleet = fleet
        self.events = events
        self.n = scheduler.n
        self.obs = scheduler.obs
        self._fold_p99: dict[int, float] = {}
        self._lost_now: list[tuple[int, int]] = []

    def dispatch(self, source: Source) -> list[int]:
        host = self.fleet.dispatch(source.dispatch, source.version)
        if host is None:
            # grace expired with no live host: the never-sent population
            # is surrendered as lost UP FRONT (the _ProcessSource dead-
            # pipe contract), because the dispatch is already on the log
            # — dispatched == consumed + discarded + lost must survive
            # even a run that recovers when a host finally joins.  The
            # empty member list still feeds the dry-out guard
            self.obs.counters.inc("results_lost", self.n)
            self.obs.event("results_lost", dispatch=int(source.dispatch),
                           host=None, n=self.n)
            self._lost_now.extend((int(source.dispatch), i)
                                  for i in range(self.n))
            return []
        self.obs.event("elastic_dispatch", trace=f"d{source.dispatch}",
                       dispatch=int(source.dispatch), host=int(host))
        return list(range(self.n))

    def _note_membership(self, events: list[dict]) -> None:
        for m in events:
            entry = dict(m, at_dispatch=len(self.sched.log.dispatches))
            self.sched.log.membership.append(entry)
            if m["event"] == "join":
                self.obs.counters.inc("hosts_joined")
            else:
                self.obs.counters.inc("hosts_lost")
                # the worst-host rollup must not be pinned by a dead
                # straggler's history: drop its distribution snapshot
                if self._fold_p99.pop(int(m["host"]), None) is not None:
                    self.obs.counters.gauge(
                        "elastic_fold_p99_worst_s",
                        round(max(self._fold_p99.values()), 6)
                        if self._fold_p99 else 0.0)
            self.obs.event(f"host_{m['event']}", host=int(m["host"]))
        self.obs.counters.gauge("elastic_hosts", self.fleet.n_live())

    def poll_lost(self, timeout_s: float = POLL_SLICE_S
                  ) -> list[tuple[int, int]]:
        results, lost_dispatches, membership = self.fleet.poll(timeout_s)
        if membership:
            self._note_membership(membership)
        t_arr = time.perf_counter()
        for r in results:
            d, host = int(r["dispatch"]), int(r["host"])
            src = self.sched._sources.get(d)
            if src is None:
                # a stray from a PREVIOUS run on this fleet (the fleet
                # outlives runs; a straggler can answer run 1's dispatch
                # during run 2): not this log's dispatch, so folding or
                # even discard-logging it would break the run's
                # dispatched == consumed + discarded + lost invariant —
                # dropped WITH evidence, outside the log
                self.obs.counters.inc("foreign_results_dropped")
                self.obs.event("foreign_result_dropped", dispatch=d,
                               host=host)
                continue
            fit = np.asarray(r["fitness"], np.float32)
            k = max(len(fit), 1)
            per = float(r["eval_s"]) / k
            base_steps, rem = divmod(int(r["steps"]), k)
            if src.t_dispatch:
                # per-host dispatch→arrival latency: the host's whole
                # contribution lag, the tail `obs dash`'s host column
                # renders (worst host p99 rides a gauge so the dash can
                # read it from the store alone)
                lat = t_arr - src.t_dispatch
                self.obs.hists.observe("elastic/fold_s", lat)
                self.obs.hists.observe(f"elastic/h{host}/fold_s", lat)
                p99 = self.obs.hists.quantile(f"elastic/h{host}/fold_s",
                                              0.99)
                if p99 is not None:
                    self._fold_p99[host] = p99
                    self.obs.counters.gauge(f"elastic_fold_p99_s_h{host}",
                                            round(p99, 6))
                    self.obs.counters.gauge(
                        "elastic_fold_p99_worst_s",
                        round(max(self._fold_p99.values()), 6))
            self.obs.event("elastic_result", trace=f"d{d}", dispatch=d,
                           host=host, eval_s=round(float(r["eval_s"]), 4))
            for i in range(len(fit)):
                self.events.put(Arrival(
                    d, i, float(fit[i]),
                    base_steps + (1 if i < rem else 0), per, t_arr))
        lost: list[tuple[int, int]] = []
        for d, host in lost_dispatches:
            if self.sched._sources.get(int(d)) is None:
                # same foreign-dispatch rule as above: a host that died
                # still holding a PREVIOUS run's dispatch must not
                # inflate this run's loss accounting
                self.obs.event("foreign_loss_dropped", dispatch=int(d),
                               host=int(host))
                continue
            self.obs.counters.inc("results_lost", self.n)
            self.obs.event("results_lost", dispatch=int(d),
                           host=int(host), n=self.n)
            lost.extend((int(d), i) for i in range(self.n))
        out = self._lost_now + lost
        self._lost_now = []
        return out

    def notify_update(self, version: int, state) -> None:
        self.fleet.push_center(
            version, np.asarray(state.params_flat, np.float32),
            float(np.asarray(state.sigma)))

    def close(self) -> None:
        # the fleet outlives the run (hosts stay joined for the next
        # train_elastic call / operator shutdown) — nothing to tear down
        self.obs.counters.gauge("elastic_hosts", self.fleet.n_live())

    @property
    def n_workers(self) -> int:
        return max(self.fleet.n_live(), 1)


class ElasticScheduler(GenerationScheduler):
    """The fold scheduler at HOST granularity on the device engine
    (docs/multihost.md): dispatches go to remote hosts running the
    sharded/replicated generation program as async sources
    (parallel/elastic.py), per-host fitness contributions fold in with
    the same clipped-importance-weight math (``iwes.stale_log_ratios``,
    mean-1 self-normalized, truncated at ``iw_clip``), an update fires
    per population's-worth of arrivals, and only the O(dim) center rides
    the wire back to the hosts.

    The coordinator's update programs are the REPLICATED device engine's
    split path: a batch whose single source is the current center is the
    plain ``apply_weights`` update (the exact synchronous estimator); a
    batch carrying stale sources routes through ``apply_weights_reuse``
    (the IW-ES combined-estimator program) with λ per source dispatch.
    Event log, staleness discards, loss replacement, accounting and
    bit-exact ``replay`` are all inherited from the base scheduler —
    host granularity changes who evaluates, not what is recorded."""

    def __init__(self, es, fleet, max_stale: int = 16,
                 iw_clip: float = 2.0,
                 max_consecutive_rejections: int = 3):
        self.fleet = fleet
        super().__init__(
            es, max_stale=max_stale, iw_clip=iw_clip,
            max_consecutive_rejections=max_consecutive_rejections)

    # ----------------------------------------------------- backend hooks

    def _check_es(self, es) -> None:
        if es.backend != "device" or getattr(es, "_shard_params", False):
            raise ValueError(
                "ElasticScheduler runs on the coordinator's replicated "
                "device engine (table noise); hosts may run the sharded "
                "program, the coordinator's fold/update programs are the "
                f"replicated split path (got backend={es.backend!r}"
                f"{', shard_params=True' if getattr(es, '_shard_params', False) else ''})"
            )
        es.engine._require_dense_noise("elastic host fold")
        if getattr(es, "_obs_norm", False):
            raise ValueError(
                "elastic folding does not support obs_norm: a stale "
                "host's fitness was measured under OLDER running stats, "
                "so the density ratio's fixed-f(θ) assumption silently "
                "breaks (same refusal as IW_ES)")

    def _sigma_of(self, st) -> float:
        return float(np.asarray(st.sigma))

    def _offsets_for(self, st, dispatch: int) -> np.ndarray:
        import jax.numpy as jnp

        return np.asarray(self.engine.all_pair_offsets(
            st._replace(generation=jnp.asarray(int(dispatch),
                                               jnp.int32))))

    def _ensure_compiled(self) -> None:
        es = self.es
        if es.compile_time_s is not None:
            return
        es.compile_time_s = self.engine.compile_split(es.state)
        # warm the single-source-group reuse shape (the host-granular
        # common case: one whole stale population per update) outside
        # the timed loop — the IW_ES._warm_reuse_programs discipline
        import jax.numpy as jnp

        t0 = time.perf_counter()
        st = es.state
        offs = self.engine.all_pair_offsets(st)
        zeros_d = jnp.zeros_like(st.params_flat)
        self.engine.noise_stats(offs, zeros_d)
        out, _ = self.engine.apply_weights_reuse(
            st, jnp.zeros((self.n,), jnp.float32),
            offs, jnp.zeros((int(offs.shape[0]),), jnp.float32),
            zeros_d[None, :], jnp.zeros((1,), jnp.float32))
        jnp.asarray(out.params_flat).block_until_ready()
        dt = time.perf_counter() - t0
        self.obs.compile_event("elastic_fold_warm", dt,
                               count_recompiles=2, programs=2,
                               first_call=True)
        es.compile_time_s += dt

    def _make_source(self, events: "queue.Queue"):
        # seed the fleet's center (version 0) so hosts that joined
        # before this run — or join during it — sync the right state
        st = self.es.state
        self.fleet.push_center(
            0, np.asarray(st.params_flat, np.float32), self._sigma_of(st))
        return _HostSource(self, self.fleet, events)

    def _inflight_budget(self, src_pool) -> int:
        # one population in flight PER LIVE HOST (plus the one the loop
        # is about to add): every host stays fed, a straggling host
        # queues at most ~one extra dispatch
        return self.n * max(1, self.fleet.n_live())

    # -------------------------------------------------------- fold math

    def _best_theta(self, arrival: Arrival) -> np.ndarray:
        eng = self.engine
        src = self._sources[arrival.dispatch]
        sign, off = member_sign_offset(src.offsets, arrival.member,
                                       bool(eng.config.mirrored))
        eps = np.asarray(eng.table.slice(int(off), eng.spec.dim))
        return src.params + src.sigma * sign * eps

    def _fold_batch(self, batch: list[Arrival], version: int):
        """Device-path fold of one mixed-staleness batch — pure given
        (center state, sources, batch), exactly like the host fold, so
        replay stays bit-identical.  All-fresh single-source batches are
        the synchronous estimator through ``apply_weights``; anything
        else is the IW-ES combined-estimator program with per-source λ.
        """
        import jax.numpy as jnp

        from ..ops.gradient import fold_mirrored_weights

        eng = self.engine
        st = self.es.state
        dim = int(eng.spec.dim)
        mirrored = bool(eng.config.mirrored)
        batch = sorted(batch, key=lambda a: (a.dispatch, a.member))
        fit = np.asarray([a.fitness for a in batch], np.float32)
        fit = mutate_fitness(int(np.asarray(st.generation)), fit)
        n_valid = int(np.isfinite(fit).sum())
        if n_valid < 2:
            return None, None, fit, {"n_valid": n_valid}
        w = rank_weights_with_failures(fit)
        sigma_u = self._sigma_of(st)
        n_tot = len(batch)
        center = np.asarray(st.params_flat, np.float32)

        by_dispatch: dict[int, list[int]] = {}
        for j, a in enumerate(batch):
            by_dispatch.setdefault(a.dispatch, []).append(j)
        lam_stale: list[float] = []
        n_fresh = 0
        with self.obs.phase("async"):
            with self.obs.phase("fold"):
                only = next(iter(by_dispatch))
                fresh_single = (
                    len(by_dispatch) == 1 and n_tot == self.n
                    and self._sources[only].version == version)
                if fresh_single:
                    w_vec = np.zeros(self.n, np.float32)
                    for kk, j in enumerate(by_dispatch[only]):
                        w_vec[batch[j].member] = w[j]
                    n_fresh = n_tot
                    reuse_args = None
                else:
                    offs_parts, oldw_parts, d_rows, coeffs = [], [], [], []
                    for d in sorted(by_dispatch):
                        src = self._sources[d]
                        idx = by_dispatch[d]
                        k = len(idx)
                        if src.version == version:
                            lam = np.ones(k, np.float32)
                            c = 1.0
                            d_vec = np.zeros(dim, np.float32)
                            n_fresh += k
                        else:
                            d_vec = ((src.params - center)
                                     / sigma_u).astype(np.float32)
                            c = src.sigma / sigma_u
                            dots, norms = eng.noise_stats(
                                jnp.asarray(src.offsets),
                                jnp.asarray(d_vec))
                            dots, norms = (np.asarray(dots),
                                           np.asarray(norms))
                            if mirrored:
                                dots, norms = mirrored_member_stats(
                                    dots, norms)
                            members = np.asarray(
                                [batch[j].member for j in idx], np.intp)
                            d2 = float(d_vec @ d_vec)
                            lam = clipped_stale_lambdas(
                                dots[members], norms[members], d2, c,
                                dim, self.iw_clip)
                            lam_stale.extend(float(x) for x in lam)
                        # per-member weights over the dispatch's FULL
                        # population; members not in the batch weigh 0
                        w_eff = np.zeros(self.n, np.float32)
                        for kk, j in enumerate(idx):
                            w_eff[batch[j].member] = w[j] * lam[kk]
                        folded = (np.asarray(fold_mirrored_weights(
                            jnp.asarray(w_eff))) if mirrored else w_eff)
                        oldw_parts.append(
                            folded * np.float32(c / (n_tot * sigma_u)))
                        offs_parts.append(src.offsets)
                        d_rows.append(d_vec)
                        coeffs.append(float(w_eff.sum())
                                      / (n_tot * sigma_u))
                    reuse_args = (
                        np.concatenate(offs_parts),
                        np.concatenate(oldw_parts).astype(np.float32),
                        np.stack(d_rows).astype(np.float32),
                        np.asarray(coeffs, np.float32),
                    )
            with self.obs.phase("update"):
                if reuse_args is None:
                    new_state, gnorm = eng.apply_weights(
                        st._replace(generation=jnp.asarray(int(only),
                                                           jnp.int32)),
                        jnp.asarray(w_vec))
                else:
                    new_state, gnorm = eng.apply_weights_reuse(
                        st, jnp.zeros((self.n,), jnp.float32),
                        jnp.asarray(reuse_args[0]),
                        jnp.asarray(reuse_args[1]),
                        jnp.asarray(reuse_args[2]),
                        jnp.asarray(reuse_args[3]))
                # state.generation counts UPDATES (the fold-scheduler
                # contract); the per-dispatch noise generation was an
                # operand of this one program only
                new_state = new_state._replace(
                    generation=jnp.asarray(version + 1, jnp.int32))
                gnorm = float(np.asarray(gnorm))
        stats = {
            "n_valid": n_valid,
            "fresh": n_fresh,
            "folded": len(batch) - n_fresh,
            "mean_lambda": (round(float(np.mean(lam_stale)), 4)
                            if lam_stale else None),
            "max_staleness": version - min(
                self._sources[d].version for d in by_dispatch),
            "consumed_by_dispatch": [[int(d), len(by_dispatch[d])]
                                     for d in sorted(by_dispatch)],
        }
        return new_state, gnorm, fit, stats


# ---------------------------------------------------------------------
# the overlap scheduler (device / pooled / sharded backends)
# ---------------------------------------------------------------------


def train_overlap(es, n_steps: int, log_fn=None, verbose: bool = True,
                  max_consecutive_rejections: int = 3,
                  step_timeout_s: float = 3600.0):
    """Pipelined generations: generation g+1's program is submitted from
    a background thread before generation g's metrics are materialized,
    so the host-side tail (fence, D2H, best tracking, record emit)
    overlaps the next dispatch.  Same program sequence and inputs as the
    synchronous loop — bit-identical parameters and records.

    Rejection protocol: a rejected generation's speculative successor
    consumed a poisoned state, so it is DISCARDED (counted in
    ``speculative_discarded``) and the loop re-runs from the restored
    state — except on the sharded engine, whose in-program rollback
    means the speculative step already re-ran the SAME generation on the
    rolled-back state: its result is kept as the deterministic re-run.
    """
    import concurrent.futures as cf
    import itertools

    import jax

    obs = es.obs
    obs.discard_phases()
    if es.compile_time_s is None:
        es.compile_time_s = es.engine.compile(es.state)
    ex = cf.ThreadPoolExecutor(max_workers=1,
                               thread_name_prefix="estorch-overlap")

    dispatch_seq = itertools.count(int(es.state.generation))

    def submit(state):
        # speculative dispatches carry trace ids too, so a wedged
        # program's last recorder span names WHICH dispatch wedged
        with obs.trace_ctx(f"d{next(dispatch_seq)}"), obs.phase("async"):
            with obs.phase("dispatch"):
                return ex.submit(es.engine.generation_step, state)

    def result_of(fut):
        # bounded wait in poll slices: the event loop must never block
        # unbounded on a wedged program (esguard R11)
        deadline = time.monotonic() + step_timeout_s
        while True:
            try:
                return fut.result(timeout=POLL_SLICE_S)
            except cf.TimeoutError:
                if time.monotonic() > deadline:
                    raise TimeoutError(
                        f"generation program silent for {step_timeout_s}s"
                        " — wedged dispatch") from None

    try:
        done = 0
        rejected_streak = 0
        prev_state = es.state
        t0 = time.perf_counter()
        pending = submit(prev_state)
        while done < n_steps:
            new_state, metrics = result_of(pending)
            speculative = None
            if done + 1 < n_steps:
                # dispatch g+1 BEFORE touching g's metrics: on the
                # device path the fence below runs while the next
                # program executes
                speculative = submit(new_state)
            with obs.phase("host_sync"):
                fitness = np.asarray(metrics["fitness"])
                if es.backend != "host":
                    if es._shard_params:
                        jax.block_until_ready(
                            jax.tree_util.tree_leaves(new_state.params))
                    else:
                        jax.block_until_ready(new_state.params_flat)
            dt = time.perf_counter() - t0

            reason = es._update_anomaly(metrics)
            if reason is not None:
                obs.counters.inc("generations_rejected")
                obs.event("generation_rejected", reason=reason)
                obs.discard_phases()
                rejected_streak += 1
                if rejected_streak > max_consecutive_rejections:
                    raise RuntimeError(
                        f"{reason}; {rejected_streak} consecutive "
                        "generations rejected — check env/rollout health")
                if es._shard_params:
                    # in-program rollback: new_state IS the rolled-back
                    # input, so the speculative program is re-running
                    # the SAME generation deterministically — keep it
                    es.state = new_state
                    prev_state = new_state
                    pending = (speculative if speculative is not None
                               else submit(new_state))
                else:
                    if speculative is not None:
                        result_of(speculative)  # drain, then drop
                        obs.counters.inc("speculative_discarded")
                        obs.event("speculative_discarded",
                                  generation=int(done))
                    pending = submit(prev_state)
                t0 = time.perf_counter()
                continue
            rejected_streak = 0
            es.state = new_state
            record = es._base_record(
                prev_state, fitness, int(metrics["steps"]),
                float(np.asarray(metrics["grad_norm"])), dt,
                metrics=metrics if es._shard_params else None,
            )
            es._attach_scenarios(record, fitness, metrics)
            es._emit_record(record, log_fn, verbose)
            done += 1
            prev_state = new_state
            t0 = time.perf_counter()
            if speculative is not None:
                pending = speculative
    finally:
        ex.shutdown(wait=False)
    return es
