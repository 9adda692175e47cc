"""HostEngine — the reference-parity execution backend.

The reference's entire runtime is this path: per-member Python loop calling
a user-supplied ``Agent.rollout(policy)`` (policy = a ``torch.nn.Module``),
fitness gathered, master applies a torch-optimizer step (SURVEY.md §3.2-3.3).
estorch_tpu keeps that contract alive so reference users' Agents, torch
policies, and torch optimizers run unchanged:

    es = ES(TorchPolicy, GymAgent, torch.optim.Adam, ...)
    es.train(n_steps, n_proc=8)

Differences from the reference runtime (deliberate upgrades):
- ``n_proc`` maps to a thread pool with per-worker scratch policy + agent
  instances instead of ``torch.distributed`` processes — no MPI, no gloo,
  no parameter broadcast; gym/mujoco/torch release the GIL in their C cores.
- noise comes from the same shared-noise-table design as the device path
  (offsets per antithetic pair, regenerated — never stored per member), so
  memory is O(table), not O(population×dim).
- the update is the identical folded mirrored-pair estimator
  (ops/gradient.py math, NumPy edition).

This backend exists for PARITY and portability; the TPU engine
(parallel/engine.py) is the performance path.  Both implement the same
engine interface, so ES / NS_ES / NSR_ES / NSRA_ES run on either.
"""

from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, NamedTuple

import numpy as np

from ..obs.spans import NULL_TELEMETRY
from ..ops.ranks import centered_rank_np


class HostState(NamedTuple):
    """Host twin of parallel.engine.ESState (numpy-backed)."""

    params_flat: np.ndarray
    opt_state: Any  # opaque: the torch optimizer mutates in place; None otherwise
    key: int
    generation: int
    sigma: float | None = None  # current perturbation scale (annealable,
    # per center); None = pre-sigma-field state, engine falls back to init σ


class HostEvalResult(NamedTuple):
    fitness: np.ndarray
    bc: np.ndarray
    steps: int


class HostRolloutResult(NamedTuple):
    total_reward: float
    bc: np.ndarray
    steps: int


def member_sign_offset(offs: np.ndarray, i: int, mirrored: bool) -> tuple[float, int]:
    """Member i's perturbation sign and noise-table offset.  THE single
    definition of the host noise indexing — thread workers (HostEngine),
    fork workers (procpool), and member_params reconstruction must all
    agree or fitness attribution silently corrupts."""
    if mirrored:
        return (1.0 if i % 2 == 0 else -1.0), int(offs[i // 2])
    return 1.0, int(offs[i])


class HostEngine:
    """Same interface as ESEngine, executed by host workers.

    ``policy_factory()`` must return a fresh policy instance; ``agent_factory()``
    a fresh agent whose ``rollout(policy)`` returns ``reward`` or
    ``(reward, bc)`` — the reference's duck-typed contract (SURVEY.md
    Appendix A).
    """

    # span telemetry hub; ES hands over its own at construction
    # (obs/spans.py).
    # Class-level null default so instrumented paths never branch on None.
    telemetry = NULL_TELEMETRY

    def __init__(
        self,
        policy_factory: Callable[[], Any],
        agent_factory: Callable[[], Any],
        optimizer_ctor,  # torch.optim class
        optimizer_kwargs: dict,
        population_size: int,
        sigma: float,
        table_size: int,
        seed: int,
        n_proc: int = 1,
        device: str = "cpu",
        prototype_agent: Any | None = None,
        weight_decay: float = 0.0,
        worker_mode: str = "thread",
        proc_timeout_s: float = 600.0,
        sigma_decay: float = 1.0,
        sigma_min: float = 0.0,
        mirrored: bool = True,
        telemetry=None,
    ):
        import torch

        if telemetry is not None:
            self.telemetry = telemetry
        self.torch = torch
        self.mirrored = bool(mirrored)
        if mirrored and population_size % 2 != 0:
            raise ValueError(
                f"population_size must be even (mirrored sampling), got {population_size}"
            )
        self.population_size = population_size
        self.n_pairs = population_size // 2
        self.sigma = float(sigma)
        self.sigma_decay = float(sigma_decay)
        self.sigma_min = float(sigma_min)
        self.weight_decay = float(weight_decay)
        self.seed = int(seed)
        self.device = device
        self.policy_factory = policy_factory
        self.agent_factory = agent_factory

        self.master = policy_factory().to(device)
        self.dim = int(
            sum(p.numel() for p in self.master.parameters())
        )
        if self.dim > table_size:
            raise ValueError(
                f"parameter dim {self.dim} exceeds noise table size {table_size}"
            )
        # float32 standard-normal table; same role as ops/noise.py, host edition
        self.table = (
            np.random.default_rng(seed).standard_normal(table_size, dtype=np.float32)
        )
        self.table_size = table_size
        self._optimizer_ctor = optimizer_ctor
        self._optimizer_kwargs = dict(optimizer_kwargs)
        self.optimizer = optimizer_ctor(self.master.parameters(), **optimizer_kwargs)

        if worker_mode not in ("thread", "process"):
            raise ValueError(
                f"worker_mode must be 'thread' or 'process', got {worker_mode!r}"
            )
        self.worker_mode = worker_mode
        # per-generation straggler budget PER WORKER in process mode; size to
        # population/n_proc × slowest-rollout (slices that exceed it are
        # NaN-dropped). Mutable attribute: es.engine.proc_timeout_s = ...
        self.proc_timeout_s = float(proc_timeout_s)
        self._prototype_agent = prototype_agent
        self._workers: list[tuple[Any, Any]] = []  # (scratch policy, agent)
        self._pool: ThreadPoolExecutor | None = None
        self._proc_pool = None  # lazily built ProcessPool (process mode)
        self.set_n_proc(n_proc)

    # ---------------------------------------------------------------- setup

    def _new_scratch_policy(self):
        p = self.policy_factory().to(self.device)
        # sync buffers too (e.g. TorchVirtualBatchNorm frozen stats):
        # parameter loads later only overwrite parameters
        p.load_state_dict(self.master.state_dict())
        return p

    def set_n_proc(self, n_proc: int) -> None:
        """Grow the worker set (scratch policy + agent per worker) and keep a
        persistent thread pool — no per-generation thread spawn/join.

        Process mode builds only worker 0 (used by evaluate_center); the
        fork pool owns its own per-process policies/agents."""
        n_proc = max(1, int(n_proc))
        want_local = 1 if self.worker_mode == "process" else n_proc
        while len(self._workers) < want_local:
            agent = (
                self._prototype_agent
                if not self._workers and self._prototype_agent is not None
                else self.agent_factory()
            )
            self._workers.append((self._new_scratch_policy(), agent))
        if self.worker_mode == "thread" and (
            self._pool is None or n_proc != getattr(self, "n_proc", None)
        ):
            if self._pool is not None:
                self._pool.shutdown(wait=False)
            self._pool = ThreadPoolExecutor(max_workers=n_proc)
        self.n_proc = n_proc

    def freeze_vbn(self, reference_batch) -> None:
        """(Re-)freeze TorchVirtualBatchNorm stats in master from a reference
        batch and propagate the buffers to every existing scratch policy
        (future workers inherit via _new_scratch_policy's state_dict copy)."""
        import torch

        from ..models.vbn_torch import TorchVirtualBatchNorm

        # clear any previously-frozen stats so this batch actually takes
        # (forward only lazy-initializes on the FIRST batched pass)
        for m in self.master.modules():
            if isinstance(m, TorchVirtualBatchNorm):
                m.initialized.fill_(False)
        with torch.no_grad():
            self.master(torch.as_tensor(np.asarray(reference_batch),
                                        dtype=torch.float32))
        for policy, _ in self._workers:
            policy.load_state_dict(self.master.state_dict())
        if self._proc_pool is not None:
            # forked workers carry the OLD buffers; rebuild with fresh state
            self._proc_pool.close()
            self._proc_pool = None

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=False)
            self._pool = None
        if self._proc_pool is not None:
            self._proc_pool.close()
            self._proc_pool = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def _flat(self) -> np.ndarray:
        import torch

        with torch.no_grad():
            vec = torch.nn.utils.parameters_to_vector(self.master.parameters())
        return vec.detach().cpu().numpy().astype(np.float32)

    def _load(self, policy, flat: np.ndarray) -> None:
        import torch

        with torch.no_grad():
            # .clone() is load-bearing: vector_to_parameters RE-POINTS each
            # param.data into views of the vector, and torch.from_numpy shares
            # memory with `flat` — without the clone, optimizer.step() would
            # silently mutate the caller's (immutable-by-contract) state array
            torch.nn.utils.vector_to_parameters(
                torch.from_numpy(np.ascontiguousarray(flat)).clone(),
                policy.parameters(),
            )

    def init_state(self, params_flat=None, key: int | None = None) -> HostState:
        with self.telemetry.phase("setup/init_state"):
            flat = (self._flat() if params_flat is None
                    else np.asarray(params_flat, np.float32))
            return HostState(
                params_flat=flat,
                opt_state=None,
                key=self.seed if key is None else int(key),
                generation=0,
                sigma=self.sigma,
            )

    def compile(self, state: HostState) -> float:
        # nothing to compile on the host path; the span's entry is the
        # heartbeat's last-known phase, as on the other engines
        with self.telemetry.phase("setup/compile"):
            return 0.0

    compile_split = compile

    # ------------------------------------------------------------ noise math

    def _pair_offsets(self, state: HostState) -> np.ndarray:
        """Per-generation noise offsets; deterministic in (key, gen),
        mirroring the device engine's fold_in derivation.  One offset per
        antithetic PAIR when mirrored, one per MEMBER otherwise (the
        reference's plain per-member sampling)."""
        n = self.n_pairs if self.mirrored else self.population_size
        rng = np.random.default_rng(
            np.random.SeedSequence(entropy=state.key, spawn_key=(state.generation,))
        )
        return rng.integers(
            0, self.table_size - self.dim + 1, size=n, dtype=np.int64
        )

    def _eps(self, offset: int) -> np.ndarray:
        return self.table[offset : offset + self.dim]

    def _member_sign_off(self, offs: np.ndarray, i: int) -> tuple[float, int]:
        return member_sign_offset(offs, i, self.mirrored)

    def member_theta(self, state: HostState, member_index: int) -> np.ndarray:
        offs = self._pair_offsets(state)
        sign, off = self._member_sign_off(offs, member_index)
        return state.params_flat + self._state_sigma(state) * sign * self._eps(off)

    def _state_sigma(self, state: HostState) -> float:
        # pre-sigma-field states (e.g. hand-built in tests) fall back to init
        # σ; None (not 0.0) is the sentinel so a fully-decayed σ==0 is honored
        return self.sigma if state.sigma is None else float(state.sigma)

    # alias matching the device engine's name
    def member_params(self, state: HostState, member_index: int) -> np.ndarray:
        return self.member_theta(state, member_index)

    # ------------------------------------------------------------- rollouts

    @staticmethod
    def _call_rollout(agent, policy) -> HostRolloutResult:
        out = agent.rollout(policy)
        if isinstance(out, tuple):
            reward, bc = out[0], np.asarray(out[1], dtype=np.float32).reshape(-1)
        else:
            reward, bc = out, np.zeros(0, dtype=np.float32)
        steps = int(getattr(agent, "last_episode_steps", 0))
        return HostRolloutResult(float(reward), bc, steps)

    def _proc_evaluate(self, state: HostState, offs=None) -> HostEvalResult:
        from ..resilience.chaos import kill_workers
        from .procpool import ProcessPool

        if self._proc_pool is None or self._proc_pool.n_proc != self.n_proc:
            if self._proc_pool is not None:
                self._proc_pool.close()
            self._proc_pool = ProcessPool(
                self.policy_factory, self.agent_factory, self.n_proc,
                self.population_size, self.dim, self.table,
                master_state=self.master.state_dict(),
                mirrored=self.mirrored,
            )
        self._proc_pool.telemetry = self.telemetry
        # generation boundary: workers lost last generation come back now,
        # restoring full population participation (docs/resilience.md)
        self._proc_pool.respawn_dead()
        killed = kill_workers(state.generation, self._proc_pool.worker_pids)
        if killed:
            self.telemetry.counters.inc("chaos_worker_kills", len(killed))
            self.telemetry.event("chaos_worker_kill", pids=killed,
                                 gen=int(state.generation))
        if offs is None:
            offs = self._pair_offsets(state)
        fitness, bc, steps = self._proc_pool.evaluate(
            state.params_flat, self._state_sigma(state), offs,
            timeout_s=self.proc_timeout_s,
            generation=int(state.generation),
        )
        return HostEvalResult(fitness=fitness, bc=bc, steps=int(steps))

    def evaluate(self, state: HostState, offs=None) -> HostEvalResult:
        """Population evaluation.  ``offs`` lets generation_step hand in
        offsets it already derived under the ``sample`` span (the
        default None re-derives them — same deterministic values)."""
        if self.worker_mode == "process":
            return self._proc_evaluate(state, offs)
        if offs is None:
            offs = self._pair_offsets(state)
        sigma = self._state_sigma(state)
        results: list[HostRolloutResult | None] = [None] * self.population_size

        from ..resilience.chaos import member_fault

        def run_slice(w: int):
            policy, agent = self._workers[w]
            for i in range(w, self.population_size, self.n_proc):
                sign, off = self._member_sign_off(offs, i)
                theta = state.params_flat + sigma * sign * self._eps(off)
                self._load(policy, theta)
                try:
                    member_fault(state.generation, i)  # chaos injection
                    results[i] = self._call_rollout(agent, policy)
                except Exception:  # noqa: BLE001 — a dead member must not
                    # kill the generation (reference behavior: one worker
                    # exception hangs the whole MPI gather, SURVEY.md §5);
                    # NaN fitness marks the member for straggler-drop
                    # renormalization in utils/fault.py
                    results[i] = HostRolloutResult(
                        float("nan"), np.zeros(0, dtype=np.float32), 0
                    )

        if self.n_proc == 1:
            run_slice(0)
        else:
            list(self._pool.map(run_slice, range(self.n_proc)))

        fitness = np.array([r.total_reward for r in results], dtype=np.float32)
        bc_dim = max((r.bc.shape[0] for r in results), default=0)
        bc = np.zeros((self.population_size, bc_dim), dtype=np.float32)
        for i, r in enumerate(results):
            if r.bc.shape[0]:
                bc[i] = r.bc
        steps = int(sum(r.steps for r in results))
        return HostEvalResult(fitness=fitness, bc=bc, steps=steps)

    def evaluate_center(self, state: HostState) -> HostRolloutResult:
        policy, agent = self._workers[0]
        self._load(policy, state.params_flat)
        return self._call_rollout(agent, policy)

    # -------------------------------------------------------------- updates

    def apply_weights(self, state: HostState, weights,
                      offs=None) -> tuple[HostState, float]:
        """Folded mirrored-pair estimator + torch optimizer step (the
        reference's param.grad → optimizer.step() flow, SURVEY.md §3.2).

        Optimizer moments travel WITH the state (``opt_state`` holds the torch
        optimizer state_dict), so independent centers — the novelty family's
        meta-population — never blend Adam statistics through the shared
        master optimizer.
        """
        w = np.asarray(weights, dtype=np.float32)
        if offs is None:
            offs = self._pair_offsets(state)
        sigma = self._state_sigma(state)
        grad_ascent = np.zeros(self.dim, dtype=np.float32)
        if self.mirrored:
            pair_w = w[0::2] - w[1::2]  # fold_mirrored_weights, numpy edition
            for k, o in enumerate(offs):
                grad_ascent += pair_w[k] * self._eps(int(o))
        else:
            for i, o in enumerate(offs):
                grad_ascent += w[i] * self._eps(int(o))
        grad_ascent /= self.population_size * sigma
        return self.apply_grad(state, grad_ascent)

    def apply_grad(self, state: HostState,
                   grad_ascent: np.ndarray) -> tuple[HostState, float]:
        """Torch-optimizer step from an ALREADY-SCALED ascent direction
        (the 1/(n·σ) division is the caller's — apply_weights above, or
        the async scheduler's mixed-staleness fold, algo/scheduler.py).
        Weight decay, chaos update poisoning, σ annealing, and the
        immutable-state contract all live here so the two callers can
        never diverge."""
        import copy

        import torch

        sigma = self._state_sigma(state)
        if self.weight_decay > 0.0:
            # same L2 pull as the device engine's _update_from_weights
            grad_ascent = grad_ascent - self.weight_decay * state.params_flat
        from ..resilience.chaos import poison_update

        if poison_update(state.generation):
            # chaos: a poisoned update direction — the post-update anomaly
            # guard (ES.train on metrics["update_finite"]) must catch this
            grad_ascent = np.full_like(grad_ascent, np.nan)

        self._load(self.master, state.params_flat)
        if state.opt_state is not None:
            # deepcopy is load-bearing: load_state_dict keeps the INPUT
            # tensors when dtype/device already match, so the live
            # optimizer would alias state.opt_state and step() would
            # mutate the caller's (immutable-by-contract) state in place —
            # corrupting any rollback/rejection path that re-applies from
            # the same state (docs/resilience.md)
            self.optimizer.load_state_dict(copy.deepcopy(state.opt_state))
        else:
            # fresh center: reset any moments left by another state
            self.optimizer = self._optimizer_ctor(
                self.master.parameters(), **self._optimizer_kwargs
            )
        self.optimizer.zero_grad()
        # torch optimizers minimize: descend on -ascent
        g = torch.from_numpy(-np.ascontiguousarray(grad_ascent))
        i = 0
        for p in self.master.parameters():
            n = p.numel()
            p.grad = g[i : i + n].view_as(p).clone()
            i += n
        self.optimizer.step()

        new_sigma = sigma
        if self.sigma_decay != 1.0:
            # same multiplicative anneal + floor as the device engine
            new_sigma = max(sigma * self.sigma_decay, self.sigma_min)
        new_state = HostState(
            params_flat=self._flat(),
            opt_state=copy.deepcopy(self.optimizer.state_dict()),
            key=state.key,
            generation=state.generation + 1,
            sigma=new_sigma,
        )
        return new_state, float(np.linalg.norm(grad_ascent))

    def generation_step(self, state: HostState):
        from ..resilience.chaos import mutate_fitness
        from ..utils.fault import rank_weights_with_failures

        obs = self.telemetry
        # span names (docs/observability.md): sample = per-generation
        # noise-offset derivation (cheap BY DESIGN — the shared-table
        # scheme regenerates ε instead of storing it; a fat sample span
        # here means that design broke); eval = every member rollout;
        # update = rank transform + folded estimator + optimizer step
        with obs.phase("sample"):
            offs = self._pair_offsets(state)
        with obs.phase("eval"):
            ev = self.evaluate(state, offs=offs)
        fitness = mutate_fitness(state.generation, ev.fitness)
        n_valid = int(np.isfinite(np.asarray(fitness)).sum())
        base = {"fitness": fitness, "bc": ev.bc, "steps": ev.steps,
                "n_valid": n_valid}
        if n_valid < 2:
            # population collapse: not this layer's call to crash or retry —
            # state is untouched, n_valid reports it, and ES.train owns the
            # reject/re-run policy (docs/resilience.md failure model)
            return state, {**base, "grad_norm": float("nan"),
                           "update_finite": True}
        with obs.phase("update"):
            weights = rank_weights_with_failures(fitness)
            new_state, gnorm = self.apply_weights(state, weights, offs=offs)
        metrics = {
            **base,
            "grad_norm": gnorm,
            # post-update anomaly guard input: a non-finite parameter or
            # update norm means this generation must be rejected upstream,
            # not trained on
            "update_finite": bool(
                np.isfinite(gnorm)
                and np.isfinite(new_state.params_flat).all()
            ),
        }
        return new_state, metrics
