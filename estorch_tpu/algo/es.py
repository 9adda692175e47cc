"""ES — the user-facing algorithm class, API-parity with the reference.

Reference surface (SURVEY.md Appendix A, ``estorch/estorch.py`` class ``ES``):

    es = ES(policy, agent, optimizer, population_size=..., sigma=...,
            device=..., policy_kwargs={}, agent_kwargs={}, optimizer_kwargs={})
    es.train(n_steps, n_proc=1)
    es.policy; es.best_policy; es.best_reward

estorch_tpu keeps that shape.  Differences forced by the TPU-first design:

- ``policy`` is a flax ``nn.Module`` class (or instance); ``agent`` is a
  ``JaxAgent`` naming a device-native env (host Gym agents are served by the
  host backend, envs/host_pool.py).  ``optimizer`` is an optax factory
  (``optax.adam``) or transformation — ``optimizer_kwargs`` go to the
  factory, so ``ES(..., optimizer=optax.adam, optimizer_kwargs={"learning_rate": 1e-2})``
  reads like the reference's ``torch.optim.Adam`` usage.
- ``device`` selects the mesh: ``None`` → all local devices (population DP
  over chips via one psum — the reference's n_proc workers, minus the MPI).
- ``train(n_steps, n_proc)``: ``n_proc`` is accepted for compatibility and
  ignored on the device path (the mesh already parallelizes).

Where the reference's generation is a Python loop + MPI round-trips
(SURVEY.md §3.2), here it is ONE jitted XLA program (parallel/engine.py).
"""

from __future__ import annotations

import logging
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..envs.agent import JaxAgent, collect_reference_batch
from ..models.perturbed import declaration_of
from ..models.vbn import capture_reference_stats
from ..obs.spans import format_setup, resolve_telemetry, setup_summary
from ..ops.noise import DEFAULT_TABLE_SIZE, make_noise_table
from ..ops.params import make_param_spec
from ..parallel.engine import (EngineConfig, ESEngine, build_fact_gauges,
                               build_fact_manifest)
from ..parallel.mesh import population_mesh

logger = logging.getLogger(__name__)


def _as_optax(optimizer, optimizer_kwargs) -> optax.GradientTransformation:
    if isinstance(optimizer, optax.GradientTransformation):
        if optimizer_kwargs:
            raise ValueError(
                "optimizer_kwargs were given alongside an already-constructed "
                f"optax transformation; they would be ignored: {optimizer_kwargs}. "
                "Pass the factory (e.g. optax.adam) with optimizer_kwargs, or "
                "the instance without them."
            )
        return optimizer
    if callable(optimizer):
        return optimizer(**optimizer_kwargs)
    raise TypeError(f"optimizer must be an optax factory or GradientTransformation, got {optimizer!r}")


def _is_jax_env(env) -> bool:
    """A JaxEnv has pure reset/step plus the static-shape attributes of
    envs/base.py — a gym env (which also has reset/step) does not."""
    return env is not None and all(
        hasattr(env, a)
        for a in ("reset", "step", "obs_dim", "action_dim", "discrete", "bc_dim")
    )


def _instantiate(cls_or_obj, kwargs, what: str):
    if isinstance(cls_or_obj, type):
        return cls_or_obj(**kwargs)
    if kwargs:
        raise ValueError(
            f"{what}_kwargs were given alongside an already-constructed "
            f"{what} instance; they would be ignored: {kwargs}. Pass the "
            f"class with {what}_kwargs, or the instance without them."
        )
    return cls_or_obj


class ES:
    """Vanilla OpenAI-ES (Salimans et al. 2017) on the TPU-native engine."""

    def __init__(
        self,
        policy,
        agent,
        optimizer,
        population_size: int = 256,
        sigma: float = 0.02,
        device=None,
        policy_kwargs: dict | None = None,
        agent_kwargs: dict | None = None,
        optimizer_kwargs: dict | None = None,
        seed: int = 0,
        table_size: int = DEFAULT_TABLE_SIZE,
        eval_chunk: int = 0,
        grad_chunk: int = 256,
        weight_decay: float = 0.0,
        mesh=None,
        vbn_batch: int = 128,
        compute_dtype: str = "float32",
        sigma_decay: float = 1.0,
        sigma_min: float = 0.0,
        mirrored: bool = True,
        episodes_per_member: int = 1,
        worker_mode: str = "thread",
        low_rank: int = 0,
        obs_norm: bool = False,
        obs_clip: float = 5.0,
        obs_probe_episodes: int = 1,
        obs_warmup_episodes: int = 0,
        telemetry=None,
        shard_params: bool = False,
        model_shards: int | None = None,
        partition_rules=None,
        noise_mode: str = "auto",
        scenarios=None,
    ):
        # telemetry first: every backend-init path below runs with spans/
        # counters available.  None → default-on honoring ESTORCH_OBS /
        # ESTORCH_OBS_HEARTBEAT env vars; bool forces; or pass a Telemetry
        self.obs = resolve_telemetry(telemetry)
        # all of construction is ONE set-up span (obs/spans.py): its entry
        # beats BEFORE backend init (device bring-up is a known wedge point,
        # and "last phase=setup/init" beats "no heartbeat written"), its
        # children name the parts, and nothing under it fences
        with self.obs.phase("setup/init"):
            self.population_size = population_size
            self.sigma = sigma
            self.seed = seed
            if compute_dtype not in ("float32", "bfloat16"):
                raise ValueError(
                    f"compute_dtype must be float32 or bfloat16, got {compute_dtype!r}"
                )
            self._compute_dtype = compute_dtype
            self._sigma_decay = float(sigma_decay)
            self._sigma_min = float(sigma_min)
            self._mirrored = bool(mirrored)
            self._episodes_per_member = int(episodes_per_member)
            self._low_rank = int(low_rank)
            self._obs_norm = bool(obs_norm)
            self._obs_clip = float(obs_clip)
            self._obs_probe_episodes = int(obs_probe_episodes)
            self._obs_warmup_episodes = int(obs_warmup_episodes)
            if self._obs_warmup_episodes and not self._obs_norm:
                raise ValueError(
                    "obs_warmup_episodes warm-starts the running obs stats; "
                    "it requires obs_norm=True"
                )
            # hyperscale param sharding (parallel/sharded.py, docs/sharding.md):
            # params + optimizer state sharded over a (pop, model) mesh per
            # regex partition rules, ε generated in-program, generation_step
            # donated — for policies too big to replicate per device
            self._shard_params = bool(shard_params)
            self._model_shards = model_shards
            self._partition_rules = partition_rules
            if noise_mode not in ("auto", "program", "table"):
                raise ValueError(
                    f"noise_mode must be auto|program|table, got {noise_mode!r}")
            self._noise_mode = (
                "program" if noise_mode == "auto" else noise_mode)
            if not shard_params and (model_shards is not None
                                     or partition_rules is not None
                                     or noise_mode != "auto"):
                raise ValueError(
                    "model_shards/partition_rules/noise_mode configure the "
                    "param-sharded engine; pass shard_params=True"
                )

            # scenario suite (estorch_tpu/scenarios, docs/scenarios.md):
            # domain randomization over the native env families — the env is
            # wrapped in a ScenarioEnv below, device paths only (host/pooled
            # agents step their envs host-side, where per-episode traced
            # physics constants have no representation)
            self._scenarios = scenarios
            if scenarios is not None:
                from ..scenarios import ScenarioDistribution

                if not isinstance(scenarios, ScenarioDistribution):
                    raise TypeError(
                        "scenarios must be a ScenarioDistribution "
                        "(estorch_tpu.scenarios; e.g. "
                        "default_distribution(env, n_variants=10)), got "
                        f"{scenarios!r}")

            self._policy_arg = policy
            self._policy_kwargs = dict(policy_kwargs or {})
            self._agent_arg = agent
            self._agent_kwargs = dict(agent_kwargs or {})

            self.agent = _instantiate(agent, dict(agent_kwargs or {}), "agent")
            # Dispatch order matters: a reference-style Agent usually holds a
            # `self.env` (a *gym* env) AND a rollout() — the rollout contract is
            # the host marker, so it is checked first; `env` only routes to the
            # device path when it is a JaxEnv (pure reset/step + static dims).
            if hasattr(self.agent, "rollout"):
                if shard_params:
                    raise ValueError(
                        "shard_params is a device-path option "
                        "(parallel/sharded.py); host torch agents replicate"
                    )
                if compute_dtype != "float32":
                    raise ValueError(
                        "compute_dtype is a device/pooled-path option; the host "
                        "backend runs torch policies in their native dtype"
                    )
                if episodes_per_member != 1:
                    raise ValueError(
                        "episodes_per_member is a device-path option; host agents "
                        "control their own rollout count inside rollout()"
                    )
                if low_rank:
                    raise ValueError(
                        "low_rank is a device-path option (ops/lowrank.py)"
                    )
                if obs_norm:
                    raise ValueError(
                        "obs_norm is a device/pooled-path option (running stats "
                        "ride the training state); host agents own their "
                        "rollouts — use models.TorchRunningObsNorm there"
                    )
                if scenarios is not None:
                    raise ValueError(
                        "scenarios is a device-path option: randomized physics "
                        "constants enter the jitted rollout as traced operands "
                        "(estorch_tpu/scenarios); host agents step their envs "
                        "in Python"
                    )
                self.backend = "host"
                self._init_host(
                    optimizer, dict(optimizer_kwargs or {}), table_size, device,
                    weight_decay, worker_mode,
                )
                self._post_engine_init()
                return
            if worker_mode != "thread":
                raise ValueError(
                    "worker_mode is a host-path option (thread|process); device/"
                    "pooled paths parallelize on the mesh"
                )
            if _is_jax_env(getattr(self.agent, "env", None)):
                self.backend = "device"
            elif hasattr(self.agent, "env_name"):
                # pooled path: C++ envpool stepping + device-batched inference
                if shard_params:
                    raise ValueError(
                        "shard_params needs device-native rollouts: the pooled "
                        "path materializes per-member thetas host-side, the "
                        "exact replicate the sharded engine exists to avoid"
                    )
                if self._obs_warmup_episodes:
                    raise ValueError(
                        "obs_warmup_episodes is a device-path option; the "
                        "pooled path's stats are fed by every member's "
                        "observations from generation 0, so its init "
                        "transient is one generation long already"
                    )
                if scenarios is not None:
                    raise ValueError(
                        "scenarios needs device-native rollouts (traced "
                        "physics constants); the pooled path steps C++ envs "
                        "host-side with compiled-in constants "
                        "(estorch_tpu/scenarios, docs/scenarios.md)"
                    )
                self.backend = "pooled"
                self._init_pooled(
                    policy, dict(policy_kwargs or {}), optimizer,
                    dict(optimizer_kwargs or {}), table_size, eval_chunk,
                    grad_chunk, weight_decay, mesh, device, vbn_batch,
                )
                self._post_engine_init()
                return
            else:
                raise TypeError(
                    "agent must be a JaxAgent wrapping a JaxEnv (device path), a "
                    "PooledAgent naming a native envpool env (pooled path), or a "
                    "reference-style agent exposing rollout(policy) (host path)"
                )
            self.env = self.agent.env
            if scenarios is not None:
                # ONE wrapper serves every device engine (replicated fused,
                # split-path, sharded): ScenarioEnv implements the JaxEnv
                # protocol with the drawn params riding the env state as
                # traced operands, so engines compile exactly one program
                # regardless of variant count (compile-ledger proof in
                # bench.py --scenario-ab)
                from ..scenarios import ScenarioEnv

                self.env = ScenarioEnv(self.env, scenarios)
            _, obs0 = self.env.reset(jax.random.PRNGKey(0))

            def vbn_ref(vbn_key):
                return collect_reference_batch(self.env, vbn_key, n_steps=vbn_batch)

            if self._shard_params and mesh is None:
                from ..parallel.mesh import hyperscale_mesh

                devs = (
                    [device] if device is not None
                    and not isinstance(device, (list, tuple)) else device
                )
                with self.obs.phase("mesh"):
                    mesh = hyperscale_mesh(model_shards=self._model_shards,
                                           devices=devs)
            flat, state_key = self._init_flax_common(
                policy, dict(policy_kwargs or {}), optimizer,
                dict(optimizer_kwargs or {}), obs0, self.agent.rollout_horizon,
                vbn_ref, table_size, eval_chunk, grad_chunk, weight_decay,
                mesh, device,
            )
            if self._shard_params:
                from ..parallel.sharded import ShardedESEngine

                if self._recurrent:
                    raise ValueError(
                        "shard_params currently supports feedforward policies; "
                        "recurrent carries stay on the replicated engine "
                        "(docs/sharding.md)"
                    )
                # low-rank rows from the table: the non-materialising
                # evaluation, for policies with a perturbed forward
                lr_apply, lr_spec = (
                    self._perturbed_form(flat)
                    if self._low_rank and self._noise_mode == "table"
                    else (None, None))
                with self.obs.phase("engine_build"):
                    self.engine = ShardedESEngine(
                        self.env, self._policy_apply, self._spec, self.table,
                        self.optimizer, self.config, self.mesh,
                        partition_rules=self._partition_rules,
                        noise_mode=self._noise_mode,
                        perturbed_apply=lr_apply, lowrank_spec=lr_spec,
                        policy=declaration_of(self.module),
                        telemetry=self.obs,
                    )
                # the whole flat vector leaves the device before the sharded
                # state is placed from it, a leaf at a time: a tree this
                # engine exists for does not fit one chip beside its own state
                self.state = self.engine.init_state(np.asarray(flat), state_key)
                self._post_engine_init()
                return
            from ..models.decomposed import mlp_decomposed_apply, supports_decomposed

            dec_apply = None
            if supports_decomposed(self.module):
                # how the engine learns the module has the x@W + c·(x@ε) form:
                # it takes the pair-shared forward for mirrored runs by itself
                # (ESEngine.forward_form)
                module = self.module

                def dec_apply(shared, noise, c, obs):
                    return mlp_decomposed_apply(module, shared, noise, c, obs)

            lr_apply, lr_spec = None, None
            if self._low_rank:
                from ..ops.lowrank import make_lowrank_tree_spec

                if self._recurrent:
                    # recurrent form (round-4 verdict next #7): the generic
                    # tree spec — factored noise for every 2-D kernel (trunk,
                    # cell gates, head), per-episode materialization in the
                    # engine, standard carry-threaded rollout.  No per-step
                    # factored apply needed.
                    lr_spec = make_lowrank_tree_spec(
                        self._spec.unravel(flat), self._low_rank
                    )
                else:
                    lr_apply, lr_spec = self._perturbed_form(flat)
                    if lr_apply is None:
                        raise ValueError(
                            "low_rank needs a policy with a perturbed forward "
                            "(models/perturbed.py: MLPPolicy without VBN, "
                            "HybridLM, LoopedLM) or a recurrent policy (tree "
                            "form); "
                            f"got {type(self.module).__name__}"
                        )

            with self.obs.phase("engine_build"):
                self.engine = ESEngine(
                    self.env, self._policy_apply, self._spec, self.table,
                    self.optimizer, self.config, self.mesh,
                    decomposed_apply=dec_apply,
                    lowrank_apply=lr_apply,
                    lowrank_spec=lr_spec,
                    carry_init=(self.module.carry_init if self._recurrent
                                else None),
                    telemetry=self.obs,
                )
            self.state = self.engine.init_state(flat, state_key)
            self._post_engine_init()

    def _sequence_facts(self) -> dict:
        """What a whole-episode (token sequence) run works through a
        generation, and what its model states of itself
        (``PolicyDeclaration.facts``): gauges and
        ``run_manifest()["config"]``."""
        if not getattr(getattr(self, "env", None), "whole_episode", False):
            return {}
        return {"tokens_per_generation":
                self.population_size * self.config.horizon,
                **declaration_of(self.module).facts}

    def _perturbed_form(self, flat):
        """``(perturbed apply, noise layout)`` of the module for
        ``low_rank`` (models/perturbed.py), or ``(None, None)`` when the
        module has no perturbed forward."""
        from ..models.perturbed import lowrank_spec_for, perturbed_forward

        apply = perturbed_forward(self.module)
        if apply is None:
            return None, None
        return apply, lowrank_spec_for(
            self.module, jax.eval_shape(self._spec.unravel, flat),
            self._low_rank)

    def _init_flax_common(
        self, policy, policy_kwargs, optimizer, optimizer_kwargs, obs0,
        horizon, vbn_ref_fn, table_size, eval_chunk, grad_chunk,
        weight_decay, mesh, device,
    ):
        """Shared flax-path construction (device + pooled backends): module
        init from a real observation, frozen-collection split, VBN reference
        capture, param spec, noise table, optax, mesh, EngineConfig."""
        self.module = _instantiate(policy, policy_kwargs, "policy")
        self._recurrent = bool(getattr(self.module, "is_recurrent", False))
        init_key, state_key, vbn_key = jax.random.split(
            jax.random.PRNGKey(self.seed), 3
        )
        self._obs0 = obs0
        with self.obs.phase("module_init"):
            variables = self._module_init(init_key)
        params = variables["params"]
        self._frozen = {k: v for k, v in variables.items() if k != "params"}

        # VirtualBatchNorm: freeze reference-batch statistics once
        if "vbn_stats" in variables:
            if self._recurrent:
                raise ValueError(
                    "VirtualBatchNorm + recurrent policies is unsupported: "
                    "the reference-batch capture applies the module "
                    "statelessly (models/vbn.py)"
                )
            if self._obs_norm:
                raise ValueError(
                    "VirtualBatchNorm + obs_norm is unsupported: the VBN "
                    "reference batch is captured in RAW observation space "
                    "at init, so its frozen stats would mis-calibrate "
                    "against normalized rollout inputs — pick one input-"
                    "normalization scheme"
                )
            self._frozen["vbn_stats"] = capture_reference_stats(
                self.module, variables, vbn_ref_fn(vbn_key)
            )

        frozen = self._frozen

        if self._recurrent:

            def policy_apply(p, obs, h):
                return self.module.apply({"params": p, **frozen}, obs, h)

        else:

            def policy_apply(p, obs):
                return self.module.apply({"params": p, **frozen}, obs)

        self._policy_apply = policy_apply
        with self.obs.phase("param_spec"):
            flat, self._spec = make_param_spec(params)
        # sharded program-mode noise never touches a table — don't spend
        # 4·table_size bytes of HBM on one (the whole point of in-program ε)
        with self.obs.phase("noise_table"):
            self.table = (
                None if (self._shard_params and self._noise_mode != "table")
                else make_noise_table(table_size, seed=self.seed)
            )
        self.optimizer = _as_optax(optimizer, optimizer_kwargs)
        if mesh is None:
            with self.obs.phase("mesh"):
                mesh = population_mesh(
                    [device] if device is not None
                    and not isinstance(device, (list, tuple)) else device)
        self.mesh = mesh
        self.config = EngineConfig(
            population_size=self.population_size,
            sigma=self.sigma,
            horizon=int(horizon),
            eval_chunk=eval_chunk,
            grad_chunk=grad_chunk,
            weight_decay=weight_decay,
            compute_dtype=self._compute_dtype,
            sigma_decay=self._sigma_decay,
            sigma_min=self._sigma_min,
            mirrored=self._mirrored,
            episodes_per_member=self._episodes_per_member,
            low_rank=self._low_rank,
            obs_norm=self._obs_norm,
            obs_clip=self._obs_clip,
            obs_probe_episodes=self._obs_probe_episodes,
            obs_warmup_episodes=self._obs_warmup_episodes,
        )
        return flat, state_key

    def _module_init(self, key):
        """Flax module init honoring the policy kind's apply contract —
        the ONE place that knows recurrent modules take a carry (used for
        both the main init and the novelty family's fresh meta-centers,
        so the two can never diverge)."""
        if self._recurrent:
            return self.module.init(key, self._obs0, self.module.carry_init())
        return self.module.init(key, self._obs0)

    def _post_engine_init(self):
        # the engine has shared the ES's telemetry hub since it was built
        # (a constructor argument), so its set-up spans and sub-generation
        # spans (host sample/eval/update, pooled obsnorm merge, engine
        # compile events) land in this hub
        with self.obs.phase("cost_model"):
            # what the engine resolved at build, so that a record's
            # counters and a flight-recorder dump say which forms ran (the
            # strings are skipped by the numeric exporters), then what the
            # model states
            for name, value in build_fact_gauges(self.engine).items():
                self.obs.counters.gauge(name, value)
            for name, value in self._sequence_facts().items():
                self.obs.counters.gauge(name, value)
            # analytic FLOPs/bytes model of this configuration
            # (obs/profile/): rides the first generation record so `obs
            # profile` can turn the phase spans into achieved rates against
            # a roofline.  Skipped when telemetry is off (set_cost_model
            # would discard it anyway)
            if self.obs.enabled:
                self.obs.set_cost_model(self._build_cost_model())
        self._cost_model_emitted = False
        self._setup_emitted = False
        self.best_reward = -np.inf
        self._best_flat = None
        self._best_policy_host = None
        self.history: list[dict] = []
        self.generation = 0
        self.compile_time_s: float | None = None
        self._eval_policy_fn = None  # lazily-built jitted eval rollout
        self._eval_gait_fn = None  # same, with the env-metrics channel
        self._predict_fn = None  # lazily-built jitted serving-parity predict

    # --------------------------------------------------------- pooled backend

    def _init_pooled(
        self, policy, policy_kwargs, optimizer, optimizer_kwargs,
        table_size, eval_chunk, grad_chunk, weight_decay, mesh, device, vbn_batch,
    ):
        from ..envs.gym_vec_pool import pool_env_spec
        from ..parallel.pooled import PooledEngine

        if getattr(policy, "learned_carry", False) or (
                policy_kwargs or {}).get("learned_carry"):
            raise ValueError(
                "learned_carry is a device-path feature: the pooled "
                "backend initializes episode carries before member params "
                "exist (parallel/pooled.py), so a params-dependent "
                "episode-start carry has no pooled form yet"
            )
        env_kwargs = getattr(self.agent, "env_kwargs", None)
        spec_info = pool_env_spec(self.agent.env_name, env_kwargs)
        prep = getattr(self.agent, "prep", None)
        if prep:
            from ..envs.atari_wrappers import apply_prep_to_spec

            spec_info = apply_prep_to_spec(spec_info, prep["frame_stack"])
        self.env = None
        obs0 = jnp.zeros(spec_info["obs_shape"], jnp.float32)

        def vbn_ref(vbn_key):
            del vbn_key  # pool RNG is numpy-seeded
            return self._pooled_reference_batch(vbn_batch)

        flat, state_key = self._init_flax_common(
            policy, policy_kwargs, optimizer, optimizer_kwargs, obs0,
            self.agent.horizon, vbn_ref, table_size, eval_chunk, grad_chunk,
            weight_decay, mesh, device,
        )
        with self.obs.phase("engine_build"):
            self.engine = PooledEngine(
                self.agent.env_name, self._policy_apply, self._spec,
                self.table, self.optimizer, self.config, self.mesh,
                n_threads=self.agent.n_threads, seed=self.seed,
                double_buffer=getattr(self.agent, "double_buffer", False),
                prep=prep,
                carry_init=(self.module.carry_init if self._recurrent
                            else None),
                env_kwargs=env_kwargs,
                bc_indices=getattr(self.agent, "bc_indices", None),
                telemetry=self.obs,
            )
        self.state = self.engine.init_state(flat, state_key)

    def _pooled_reference_batch(self, n: int):
        """Random-action observations from the pool for VBN statistics,
        reshaped to the policy-facing observation shape (pixels etc.)."""
        from ..envs.gym_vec_pool import make_pool

        pool = make_pool(self.agent.env_name, max(1, n // 4),
                         env_kwargs=getattr(self.agent, "env_kwargs", None))
        prep = getattr(self.agent, "prep", None)
        if prep:
            # VBN statistics must be collected in the policy's actual input
            # distribution — stacked/repeated frames, not raw ones
            from ..envs.atari_wrappers import AtariPreprocessPool

            pool = AtariPreprocessPool(pool, seed=self.seed, **prep)
        rng = np.random.default_rng(self.seed)
        frames = [pool.reset()]
        for _ in range(4):
            if pool.discrete:
                acts = rng.integers(0, pool.n_actions, (pool.n_envs, 1)).astype(
                    np.float32
                )
            else:
                acts = rng.uniform(-1, 1, (pool.n_envs, pool.act_dim)).astype(np.float32)
            obs, _, _ = pool.step(acts)
            frames.append(obs)
        obs_shape = pool.obs_shape
        pool.close()
        batch = np.concatenate(frames, axis=0)[:n]
        return jnp.asarray(batch.reshape((-1,) + tuple(obs_shape)))

    # ----------------------------------------------------------- host backend

    def _init_host(self, optimizer, optimizer_kwargs, table_size, device,
                   weight_decay=0.0, worker_mode="thread"):
        """Reference-parity path: torch policy + host Agent.rollout workers."""
        import copy

        from ..host.engine import HostEngine

        policy_arg, policy_kwargs = self._policy_arg, self._policy_kwargs
        agent_arg, agent_kwargs = self._agent_arg, self._agent_kwargs

        if isinstance(policy_arg, type):
            def policy_factory():
                return policy_arg(**policy_kwargs)
        else:
            if policy_kwargs:
                raise ValueError(
                    "policy_kwargs were given alongside a policy instance; "
                    "pass the class, or the instance without kwargs"
                )
            def policy_factory():
                return copy.deepcopy(policy_arg)

        if isinstance(agent_arg, type):
            def agent_factory():
                return agent_arg(**agent_kwargs)
        else:
            # shared instance: workers would race on it — engine caps at the
            # instances it gets; we pin n_proc to 1 in train() via this flag
            def agent_factory():
                return agent_arg
        self._agent_is_shared_instance = not isinstance(agent_arg, type)

        self.env = None
        self.module = None
        # torch module init draws from torch's global RNG; pin it so two ES
        # constructions with the same seed get identical master policies
        # (the device path gets this for free from jax.random keys)
        import torch

        torch.manual_seed(self.seed)
        with self.obs.phase("engine_build"):
            self.engine = HostEngine(
                telemetry=self.obs,
                policy_factory=policy_factory,
                agent_factory=agent_factory,
                optimizer_ctor=optimizer,
                optimizer_kwargs=optimizer_kwargs,
                population_size=self.population_size,
                sigma=self.sigma,
                table_size=table_size,
                seed=self.seed,
                n_proc=1,
                device="cpu" if device is None else str(device),
                prototype_agent=self.agent,  # dispatch probe doubles as worker 0
                weight_decay=weight_decay,
                worker_mode=worker_mode,
                sigma_decay=self._sigma_decay,
                sigma_min=self._sigma_min,
                mirrored=self._mirrored,
            )
        self.state = self.engine.init_state()

    # ------------------------------------------------------------------ train

    def train(
        self,
        n_steps: int,
        n_proc: int = 1,
        log_fn: Callable[[dict], None] | None = None,
        verbose: bool = True,
        max_consecutive_rejections: int = 3,
    ):
        """Run ``n_steps`` generations (reference: ``es.train(n_steps, n_proc)``).

        On the device path ``n_proc`` is accepted for API parity only (the
        mesh already parallelizes — SURVEY.md §2 'Parallelism strategies');
        on the host path it sizes the worker pool, exactly like the
        reference's ``train(n_steps, n_proc)``.

        Rejection policy (docs/resilience.md): a generation whose
        population collapsed (<2 valid members) or whose post-update
        parameters/norm came out non-finite is REJECTED — the state is
        restored to the pre-generation snapshot, ``generations_rejected``
        is counted, and the same generation re-runs (the noise stream is
        keyed on ``(key, generation)``, so a transient fault's re-run is
        bit-identical to a run that never faulted).  Up to
        ``max_consecutive_rejections`` consecutive rejections are
        retried; one more marks the fault persistent, not transient, and
        raises — with the pre-fault state intact.
        """
        self._setup_n_proc(n_proc)
        obs = self.obs
        # a previous generation that raised mid-phase (dead env,
        # catch-and-resume) must not leak its partial spans into the
        # first record of this call
        obs.discard_phases()
        if self.compile_time_s is None:
            # AOT-compile outside the timed loop so env_steps_per_sec (the
            # primary metric) never includes XLA trace+compile time (the
            # engine opens the set-up span ``setup/compile``)
            self.compile_time_s = self.engine.compile(self.state)
        done = 0
        rejected_streak = 0
        while done < n_steps:
            t0 = time.perf_counter()
            prev_state = self.state
            if self.backend == "device":
                # the fused generation is ONE XLA program — the finest
                # honest split is dispatch (host python + trace lookup) /
                # device (fenced: everything up to the updated params) /
                # host_sync (D2H of the metrics).  sample/eval/update
                # live inside the program; the split-path algorithms
                # (novelty family) and the host/pooled engines emit them
                # as real spans (docs/observability.md span names)
                with obs.phase("dispatch"):
                    self.state, metrics = self.engine.generation_step(
                        prev_state)
                with obs.phase("device"):
                    if self._shard_params:
                        # donated sharded state: fence on the sharded
                        # leaves — .params_flat would GATHER the full
                        # vector every generation
                        jax.block_until_ready(
                            jax.tree_util.tree_leaves(self.state.params))
                    else:
                        jax.block_until_ready(self.state.params_flat)
                with obs.phase("host_sync"):
                    fitness = np.asarray(metrics["fitness"])
            else:
                # host/pooled engines span their own sample/eval/update
                self.state, metrics = self.engine.generation_step(
                    prev_state)
                fitness = np.asarray(metrics["fitness"])
                if self.backend != "host":
                    jax.block_until_ready(self.state.params_flat)
            dt = time.perf_counter() - t0

            # ---- anomaly guards: reject instead of training on poison ----
            # population collapse (every backend reports n_valid) and the
            # post-update non-finite check (metrics["update_finite"]) both
            # restore the pre-generation state; silently keeping a NaN
            # update would poison every subsequent generation
            n_valid = metrics.get("n_valid")
            reason = self._update_anomaly(metrics)
            if reason is not None:
                if self._shard_params:
                    # the donated program already rolled back in-program
                    # (same generation, params/opt untouched —
                    # parallel/sharded.py); prev_state's buffers are gone
                    pass
                else:
                    self.state = prev_state
                rejected_streak += 1
                obs.counters.inc("generations_rejected")
                obs.event("generation_rejected", reason=reason,
                          n_valid=int(n_valid) if n_valid is not None else -1)
                obs.discard_phases()  # the rejected generation's spans
                if rejected_streak > max_consecutive_rejections:
                    raise RuntimeError(
                        f"{reason}; {rejected_streak} consecutive "
                        "generations rejected — check env/rollout health"
                    )
                continue  # re-run the SAME generation (deterministic noise)
            rejected_streak = 0

            record = self._base_record(
                prev_state, fitness, int(metrics["steps"]),
                float(np.asarray(metrics["grad_norm"])), dt,
                metrics=metrics if self._shard_params else None,
            )
            self._attach_scenarios(record, fitness, metrics)
            if "expert_load" in metrics:
                # (token, k) pairs that landed on each held expert over
                # the population's expert layers (models/moe_lm.py)
                load = np.asarray(metrics["expert_load"], np.float64)
                record["routed_pairs"] = int(load.sum())
                record["expert_load_max_over_mean"] = float(
                    load.max() / max(load.mean(), 1e-12))
            if "selected_pairs" in metrics:
                # (query, key) pairs the members' indexers selected over
                # their layers (models/indexed_moe_lm.py)
                record["selected_pairs"] = int(np.asarray(
                    metrics["selected_pairs"], np.int64).sum())
            self._emit_record(record, log_fn, verbose)
            done += 1
            # the sharded program's best member is param-sized: let go of
            # it before the next dispatch unless it became the best
            metrics = None
        return self

    def _attach_scenarios(self, record: dict, fitness, metrics) -> None:
        """Per-variant fitness block onto a generation record (and thus
        the obs hub) — the variant id is the BC's last column, the
        ScenarioEnv.behavior contract (docs/scenarios.md).  ONE
        definition shared by the sync loop and the overlap scheduler
        (algo/scheduler.py) so async records carry the same block."""
        if self._scenarios is None or "bc" not in (metrics or {}):
            return
        from ..scenarios import scenario_fitness_block, variant_of_bc

        record["scenarios"] = scenario_fitness_block(
            fitness, variant_of_bc(metrics["bc"]),
            self._scenarios.n_variants)

    def _update_anomaly(self, metrics) -> str | None:
        """The ONE definition of a rejectable generation (shared by
        ``train`` and the async schedulers, algo/scheduler.py): returns
        the rejection reason or None (docs/resilience.md)."""
        n_valid = metrics.get("n_valid")
        if n_valid is not None and int(n_valid) < 2:
            return (
                f"only {int(n_valid)}/{self.population_size} population "
                "members produced valid fitness — cannot form an update"
            )
        if not bool(np.asarray(metrics.get("update_finite", True))):
            return ("non-finite parameters/update norm after the "
                    "optimizer step")
        return None

    # ------------------------------------------------- async generations

    def train_async(
        self,
        n_steps: int,
        n_proc: int = 1,
        log_fn: Callable[[dict], None] | None = None,
        verbose: bool = True,
        max_consecutive_rejections: int = 3,
        strategy: str = "auto",
        max_stale: int = 16,
        iw_clip: float = 2.0,
        replay=None,
    ):
        """Barrier-free generations (docs/async.md, algo/scheduler.py).

        ``strategy``: ``"fold"`` (host backend) runs the event-driven
        scheduler — rollouts are member/slice tasks on worker queues,
        an update fires per population's-worth of ARRIVED results, and
        late results (chaos stragglers, slow pooled workers) fold into
        the current update with clipped importance weights keyed on the
        σ/θ they were sampled under, instead of being waited on.
        ``"overlap"`` (device/pooled/sharded; also valid on host)
        pipelines generation g+1's program dispatch with generation g's
        host-side tail — bit-identical to ``train``.  ``"auto"`` picks
        fold on host, overlap elsewhere.

        ``max_stale``: fold horizon in center versions — older results
        are discarded with evidence (``stale_discarded``).  ``iw_clip``:
        IMPACT-style truncation of the mean-normalized importance
        ratios.  ``replay``: an :class:`~estorch_tpu.algo.scheduler.
        AsyncEventLog` (or its dict form) — re-drive that recorded
        schedule instead of running live; bit-identical parameters.
        The live run's log is left on ``es.async_event_log``.
        """
        from .scheduler import GenerationScheduler, train_overlap

        if strategy not in ("auto", "fold", "overlap"):
            raise ValueError(
                f"strategy must be auto|fold|overlap, got {strategy!r}")
        if strategy == "auto":
            strategy = "fold" if self.backend == "host" else "overlap"
        self._setup_n_proc(n_proc)
        if strategy == "overlap":
            if replay is not None:
                raise ValueError(
                    "replay re-drives a fold-mode event log; the overlap "
                    "scheduler is bit-identical to train() already")
            return train_overlap(
                self, n_steps, log_fn=log_fn, verbose=verbose,
                max_consecutive_rejections=max_consecutive_rejections)
        sched = GenerationScheduler(
            self, max_stale=max_stale, iw_clip=iw_clip,
            max_consecutive_rejections=max_consecutive_rejections)
        if replay is not None:
            return sched.replay(replay, log_fn=log_fn, verbose=verbose,
                                n_steps=n_steps)
        return sched.run(n_steps, log_fn=log_fn, verbose=verbose)

    def train_elastic(
        self,
        n_steps: int,
        fleet=None,
        log_fn: Callable[[dict], None] | None = None,
        verbose: bool = True,
        max_consecutive_rejections: int = 3,
        max_stale: int = 16,
        iw_clip: float = 2.0,
        replay=None,
    ):
        """Elastic multi-host generations (docs/multihost.md,
        parallel/elastic.py): remote hosts evaluate whole-population
        dispatches as async sources, THIS process folds their
        contributions with clipped importance weights and broadcasts
        only the O(dim) center per update.  A slow host costs
        throughput, a dead host costs ``results_lost`` (replaced by
        extra dispatches) — never the fleet.

        ``fleet`` is an :class:`~estorch_tpu.parallel.elastic.
        ElasticCoordinator` hosts have joined / will join (membership is
        elastic — joining mid-run is the point).  ``replay`` re-drives a
        recorded :class:`~estorch_tpu.algo.scheduler.AsyncEventLog` as
        pure math (no fleet needed): bit-identical parameters.  The live
        run's log is left on ``es.async_event_log``."""
        from .scheduler import ElasticScheduler

        if fleet is None and replay is None:
            raise ValueError(
                "train_elastic needs a fleet (ElasticCoordinator) to run "
                "live, or replay= to re-drive a recorded log")
        sched = ElasticScheduler(
            self, fleet, max_stale=max_stale, iw_clip=iw_clip,
            max_consecutive_rejections=max_consecutive_rejections)
        if replay is not None:
            return sched.replay(replay, log_fn=log_fn, verbose=verbose,
                                n_steps=n_steps)
        return sched.run(n_steps, log_fn=log_fn, verbose=verbose)

    @property
    def async_event_log(self):
        """The last ``train_async``/``train_elastic`` fold run's
        deterministic event log (None before any fold-mode run)."""
        return getattr(self, "_async_log", None)

    def _setup_n_proc(self, n_proc: int) -> None:
        if self.backend != "host":
            return
        if getattr(self, "_agent_is_shared_instance", False) and n_proc > 1:
            import warnings

            warnings.warn(
                "agent was passed as a shared instance; host workers would "
                "race on it — running with n_proc=1. Pass the agent CLASS "
                "(with agent_kwargs) to parallelize.",
                stacklevel=3,
            )
            n_proc = 1
        self.engine.set_n_proc(n_proc)

    def _build_cost_model(self) -> dict | None:
        """Analytic per-phase FLOPs/bytes for THIS configuration
        (obs/profile/costmodel.py): policy matmul shapes from the live
        parameter tree, population/horizon/noise-representation from the
        config.  Diagnostic only — returns None rather than ever failing
        construction (an exotic policy without 2-D kernels has no matmul
        model, and that is a note in ``obs profile``, not an error).

        A sequence policy (one that states ``_sequence_facts``) is NOT
        modelled: "every 2-D leaf is a matmul of one env-step" would count
        an untied embedding as a matmul and leave out stacked experts and
        attention, and ``obs profile`` says nothing rather than something
        wrong (the benchmark's ``part.*`` metrics count those models)."""
        from ..obs.profile.costmodel import generation_cost

        if self._sequence_facts():
            return None
        try:
            if self.backend == "host":
                params = list(self.engine.master.parameters())
                shapes = [tuple(p.shape) for p in params if p.dim() == 2]
                param_dim = int(sum(p.numel() for p in params))
                horizon = None  # host agents own their rollout length
                dtype_bytes, episodes = 4, 1
            else:
                # shapes only: a sharded state's params_flat would gather
                # the whole vector onto one device
                params = jax.tree_util.tree_leaves(jax.eval_shape(
                    self._spec.unravel, jax.ShapeDtypeStruct(
                        (self._spec.dim,), jnp.float32)))
                shapes = [tuple(int(d) for d in p.shape)
                          for p in params if len(p.shape) == 2]
                param_dim = int(self._spec.dim)
                horizon = int(self.config.horizon)
                dtype_bytes = 2 if self._compute_dtype == "bfloat16" else 4
                episodes = int(self.config.episodes_per_member)
            if not shapes:
                return None
            mesh = getattr(self, "mesh", None)
            n_devices = int(mesh.devices.size) if mesh is not None else 1
            model_shards = 1
            if self._shard_params:
                from ..parallel.mesh import MODEL_AXIS

                model_shards = int(dict(zip(
                    mesh.axis_names, mesh.devices.shape))[MODEL_AXIS])
            return generation_cost(
                population=self.population_size, matmul_shapes=shapes,
                param_dim=param_dim, horizon=horizon,
                episodes_per_member=episodes, mirrored=self._mirrored,
                low_rank=self._low_rank, dtype_bytes=dtype_bytes,
                noise=(self._noise_mode if self._shard_params else "table"),
                n_devices=n_devices, model_shards=model_shards)
        except Exception:  # noqa: BLE001 — diagnostic, never construction
            return None

    # ------------------------------------------- shared generation plumbing

    def _track_best(self, prev_state, fitness: np.ndarray,
                    metrics: dict | None = None) -> tuple[float, bool]:
        """Best-member snapshot (reference: es.best_policy/best_reward).
        Returns (generation max, whether a new best was set).

        NaN-aware: failed members (host fault tolerance marks them NaN) must
        not disable best tracking or poison the metrics.

        ``metrics`` is the sharded path's donated-state protocol: the
        generation program already reconstructed the best member's θ
        (``metrics["best_theta"]``, sharded) because ``prev_state`` —
        which ``member_params`` would need — was donated; the gather
        happens only on improvement.
        """
        finite_any = np.isfinite(fitness).any()
        gen_best = float(np.nanmax(fitness)) if finite_any else float("nan")
        improved = finite_any and gen_best > self.best_reward
        if improved:
            self.best_reward = gen_best
            idx = int(np.nanargmax(fitness))
            if metrics is not None and "best_theta" in metrics:
                # kept sharded, on the mesh (a later best in the buffers of
                # the one it replaces); gathered into a flat host vector
                # only when somebody reads _best_flat
                held = (None if self._best is None
                        or isinstance(self._best, np.ndarray) else self._best)
                self._best_flat = self.engine.keep_best(
                    metrics["best_theta"], held)
            else:
                self._best_flat = np.asarray(
                    self.engine.member_params(prev_state, idx))
        return gen_best, improved

    @property
    def _best_flat(self) -> np.ndarray | None:
        """Best-ever member's flat θ on the host.  The sharded engine hands
        over a sharded TREE (``metrics["best_theta"]``); it stays on the
        mesh until this is read, so that a new best inside a training loop
        costs no gather of the whole vector."""
        if self._best is not None and not isinstance(self._best, np.ndarray):
            from jax.flatten_util import ravel_pytree

            self._best = np.asarray(ravel_pytree(self._best)[0])
        return self._best

    @_best_flat.setter
    def _best_flat(self, value) -> None:
        self._best = value

    def _base_record(self, prev_state, fitness, steps, grad_norm, dt,
                     metrics: dict | None = None) -> dict:
        with self.obs.phase("record"):
            # best-member snapshot can dispatch a device program
            # (member_params) — it deserves phase attribution too
            gen_best, improved = self._track_best(prev_state, fitness,
                                                  metrics)
        finite_any = np.isfinite(fitness).any()
        record = {
            "generation": self.generation,
            "reward_max": gen_best,
            "reward_mean": float(np.nanmean(fitness)) if finite_any else float("nan"),
            "reward_min": float(np.nanmin(fitness)) if finite_any else float("nan"),
            "n_failed": int(np.size(fitness) - np.isfinite(fitness).sum()),
            "best_reward": self.best_reward,
            "improved_best": improved,
            "env_steps": steps,
            "env_steps_per_sec": steps / dt if dt > 0 else 0.0,
            "grad_norm": grad_norm,
            # the sharded path donates prev_state — its pre-step σ rides
            # the metrics instead of a (deleted) state buffer
            "sigma": float(np.asarray(metrics["sigma"]))
            if metrics is not None and "sigma" in metrics
            else float(np.asarray(prev_state.sigma))
            if hasattr(prev_state, "sigma") and prev_state.sigma is not None
            else self.sigma,
            "wall_time_s": dt,
        }
        return self._finalize_record(record)

    def _finalize_record(self, record: dict) -> dict:
        """Record plumbing shared by every train loop (sync, fold,
        overlap — algo/scheduler.py builds its own core dict and calls
        this): span flush, compile-ledger merge, one-shot cost model,
        run-level counters."""
        # flush this generation's span accumulator into the record and
        # export the run-level counters (obs/summarize.py consumes both)
        record["phases"] = self.obs.take_phases()
        # performance-attribution facts ride the same record: compile-
        # ledger entries since the last flush, and (once per run) the
        # analytic cost model — `obs profile` joins them with the spans
        compile_events = self.obs.take_compile_events()
        if compile_events:
            record["compile_events"] = compile_events
        if not self._cost_model_emitted and self.obs.cost_model is not None:
            record["cost_model"] = self.obs.cost_model
            self._cost_model_emitted = True
        if not self._setup_emitted and self.obs.enabled:
            # the process's start-up rides the first record too: the
            # set-up spans whole and what the executables' acquisitions
            # came to (obs/spans.py), and ONE line says where the time to
            # the first generation went
            record["setup"] = setup_summary()
            self._setup_emitted = True
            logger.info("%s", format_setup(record["setup"]))
        self.obs.counters.inc("env_steps", record["env_steps"])
        if record["n_failed"]:
            self.obs.counters.inc("rollout_failures", record["n_failed"])
        return record

    def _emit_record(self, record: dict, log_fn, verbose: bool) -> None:
        self.history.append(record)
        self.generation += 1
        if log_fn is not None:
            log_fn(record)
        elif verbose:
            print(self._format_record(record))

    def _format_record(self, r: dict) -> str:
        return (
            f"gen {r['generation']:4d}  "
            f"max {r['reward_max']:9.2f}  "
            f"mean {r['reward_mean']:9.2f}  "
            f"best {r['best_reward']:9.2f}  "
            f"steps/s {r['env_steps_per_sec']:,.0f}"
        )

    # ----------------------------------------------------------- observability

    def run_manifest(self, extra: dict | None = None) -> dict:
        """Immutable facts of THIS run (obs/manifest.py): algorithm +
        backend config, jax version, device topology, git sha.  Safe to
        call any time after construction — the backend is already up, so
        reading device attributes cannot wedge a cold runtime."""
        from ..obs.manifest import collect_manifest

        cfg = {
            "algorithm": type(self).__name__,
            "backend": self.backend,
            "population_size": self.population_size,
            "sigma": self.sigma,
            "seed": self.seed,
            "compute_dtype": self._compute_dtype,
            "mirrored": self._mirrored,
            "obs_norm": self._obs_norm,
            "low_rank": self._low_rank,
            # what the engine resolved at build (None: a form this engine
            # does not resolve)
            **build_fact_manifest(self.engine),
            "shard_params": self._shard_params,
            **self._sequence_facts(),
        }
        if self._scenarios is not None:
            # scenario provenance: the distribution spec + draw seed ARE
            # the scenarios (draws are deterministic in them), so the
            # manifest names exactly what this run trained under
            cfg["scenarios"] = self._scenarios.spec_json()
        if self._shard_params:
            from ..parallel.mesh import partition_rules_to_json

            cfg["noise_mode"] = self._noise_mode
            cfg["mesh_axes"] = dict(zip(
                self.mesh.axis_names,
                [int(s) for s in self.mesh.devices.shape]))
            cfg["partition_rules"] = partition_rules_to_json(
                self.engine.partition_rules)
        mesh = getattr(self, "mesh", None)
        devices = list(mesh.devices.flat) if mesh is not None else None
        return collect_manifest(config=cfg, devices=devices, extra=extra)

    def write_manifest(self, path: str, extra: dict | None = None) -> str:
        from ..obs.manifest import write_manifest

        return write_manifest(path, self.run_manifest(extra))

    # ------------------------------------------------------------- inspection

    @property
    def policy(self):
        """Current center policy (reference: es.policy).

        Device path: the flax params pytree.  Host path: the torch master
        module loaded with the current center parameters — exactly the
        reference's ``es.policy``.
        """
        if self.backend == "host":
            self.engine._load(self.engine.master, self.state.params_flat)
            return self.engine.master
        return self._spec.unravel(self.state.params_flat)

    @property
    def policy_variables(self):
        """Full flax variables for ``module.apply`` (params + frozen stats)."""
        if self.backend == "host":
            raise AttributeError("policy_variables is device-path only; use .policy")
        return {"params": self.policy, **self._frozen}

    @property
    def best_policy(self):
        """Best-ever member's parameters (reference: es.best_policy)."""
        if self._best_flat is None:
            return self.policy
        if self.backend == "host":
            if self._best_policy_host is None:
                self._best_policy_host = self.engine.policy_factory()
            self.engine._load(self._best_policy_host, self._best_flat)
            return self._best_policy_host
        return self._spec.unravel(jnp.asarray(self._best_flat))

    @property
    def best_policy_variables(self):
        if self.backend == "host":
            raise AttributeError("best_policy_variables is device-path only; use .best_policy")
        return {"params": self.best_policy, **self._frozen}

    def evaluate_policy(self, n_episodes: int = 10, use_best: bool = False,
                        seed: int = 0, meta_index: int | None = None,
                        return_details: bool = False):
        """Mean/std episode return of the current (or best) policy.

        The reference's users hand-roll this with ``agent.rollout(es.policy)``
        loops; here it is one vmapped compiled program on the device path,
        one batched pooled pass on the pooled path (all episodes step
        concurrently in native threads — ``seed`` picks the episode set on
        both), and the engine's own serial center-evaluation on the host
        path (episode randomness from the env RNG; host agents own their
        rollouts).  ``meta_index`` selects a specific meta-population center
        (novelty family; default = center 0, the one ``es.policy`` exposes).

        ``return_details=True`` adds per-episode arrays: ``rewards``
        (n_episodes,) and — device/pooled paths — ``bc`` (n_episodes, bc_dim),
        the behavior characterizations (e.g. final torso position for the
        locomotion family), for studies that measure more than the return.
        On the device path it also adds ``steps`` (n_episodes,) and — for
        envs exposing the gait-metrics protocol (``step_metrics`` /
        ``episode_metrics``, the locomotion family) — ``gait``: per-episode
        arrays such as ``forward_velocity_mps`` and ``upright_fraction``,
        so "it walks" is stated in m/s and %-upright, not reward units.
        """
        if meta_index is not None:
            if not hasattr(self, "meta_states"):
                raise ValueError(
                    "meta_index applies to the novelty family (NS/NSR/NSRA)"
                )
            if use_best:
                raise ValueError(
                    "use_best evaluates the GLOBAL best member snapshot — "
                    "it cannot be combined with meta_index (per-center eval)"
                )
            base_state = self.meta_states[meta_index]
        else:
            base_state = self.state
        use_best = use_best and self._best_flat is not None
        if self.backend == "device":
            flat = jnp.asarray(self._best_flat) if use_best else base_state.params_flat
            want_gait = return_details and hasattr(self.env, "step_metrics")
            fn = self._eval_gait_fn if want_gait else self._eval_policy_fn
            if fn is None:
                from ..envs.rollout import make_rollout

                apply_fn = self._policy_apply
                if self._obs_norm:
                    from ..parallel.engine import normalize_obs

                    base_apply, clip = self._policy_apply, self._obs_clip
                    if self._recurrent:
                        def apply_fn(packed, obs, h):
                            p, stats = packed
                            return base_apply(
                                p, normalize_obs(obs, stats, clip), h
                            )
                    else:
                        def apply_fn(packed, obs):
                            p, stats = packed
                            return base_apply(p, normalize_obs(obs, stats, clip))
                single = make_rollout(
                    self.env, apply_fn, self.config.horizon,
                    carry_init=self.module.carry_init if self._recurrent else None,
                    with_env_metrics=want_gait,
                )
                # one cached callable: jit re-specializes per n_episodes shape
                fn = jax.jit(jax.vmap(single, in_axes=(None, 0)))
                if want_gait:
                    self._eval_gait_fn = fn
                else:
                    self._eval_policy_fn = fn
            keys = jax.random.split(jax.random.PRNGKey(seed), n_episodes)
            p = self._spec.unravel(flat)
            if self._obs_norm:
                # evaluate with the CURRENT running stats (also for use_best:
                # the snapshot's own stats are part of training state, and
                # the freshest moments are the best estimate of the env)
                p = (p, base_state.obs_stats)
            gait_sums = None
            if want_gait:
                res, gait_sums = fn(p, keys)
                gait_sums = np.asarray(gait_sums)
            else:
                res = fn(p, keys)
            rewards = np.asarray(res.total_reward)
            bc = np.asarray(res.bc)
            eval_steps = np.asarray(res.steps)
        elif self.backend == "pooled":
            # engines read only state.params_flat (+ obs_stats), so a
            # params-swapped state evaluates the requested policy
            flat = self._best_flat if use_best else base_state.params_flat
            eval_state = base_state._replace(params_flat=jnp.asarray(flat))
            res = self.engine.evaluate_center_batch(
                eval_state, n_episodes, seed=seed
            )
            rewards = np.asarray(res.fitness, np.float32)
            bc = np.asarray(res.bc)
        else:
            # host path: torch agents own their rollouts — serial by design
            flat = self._best_flat if use_best else base_state.params_flat
            eval_state = base_state._replace(
                params_flat=np.asarray(flat, np.float32)
            )
            rewards = np.asarray(
                [
                    float(self.engine.evaluate_center(eval_state).total_reward)
                    for _ in range(n_episodes)
                ],
                np.float32,
            )
            bc = None
        out = {
            "mean": float(rewards.mean()),
            "std": float(rewards.std()),
            "min": float(rewards.min()),
            "max": float(rewards.max()),
            "episodes": int(n_episodes),
        }
        if return_details:
            out["rewards"] = rewards
            out["bc"] = bc
            if self.backend == "device":
                out["steps"] = eval_steps
                if gait_sums is not None:
                    per_ep = [
                        self.env.episode_metrics(bc[i], eval_steps[i],
                                                 gait_sums[i])
                        for i in range(n_episodes)
                    ]
                    out["gait"] = {
                        k: np.asarray([m[k] for m in per_ep], np.float32)
                        for k in per_ep[0]
                    }
        return out

    def predict(self, obs, use_best: bool = False, carry=None):
        """Policy forward pass with current (or best) parameters.

        Recurrent policies return ``(out, new_carry)``; pass the returned
        carry back in on the next step (``carry=None`` starts an episode).

        Runs through the SAME jitted program the serving stack builds
        (serve/predictor.py) — normalization composed inside, params and
        running obs stats as arguments — so an exported bundle's
        ``predict`` and a server's batched responses are bit-comparable
        to this method (docs/serving.md "Bit-exactness contract").
        Batched ``obs`` (leading batch axis) is supported and lands in
        the same execution family as the server's bucketed batches.
        """
        if self.backend == "host":
            import torch

            policy = self.best_policy if use_best else self.policy
            with torch.no_grad():
                return policy(torch.as_tensor(np.asarray(obs), dtype=torch.float32))
        p = self.best_policy if use_best else self.policy
        obs = jnp.asarray(obs)
        stats = self.state.obs_stats if self._obs_norm else None
        if self._predict_fn is None:
            from ..serve.predictor import make_single_predict

            self._predict_fn = make_single_predict(
                self._policy_apply, recurrent=self._recurrent,
                obs_norm=self._obs_norm, obs_clip=self._obs_clip,
            )
        if self._recurrent:
            if carry is None:
                # same compat contract as make_rollout: a custom module
                # with the historical zero-arg carry_init() must work here
                # exactly as it does in the rollout path
                from ..envs.rollout import carry_init_takes_params

                ci = self.module.carry_init
                if not hasattr(self, "_ci_takes_params"):
                    self._ci_takes_params = carry_init_takes_params(ci)
                carry = ci(p) if self._ci_takes_params else ci()
            return self._predict_fn(p, stats, obs, carry)
        return self._predict_fn(p, stats, obs)

    # ---------------------------------------------------------------- serving

    def export_bundle(self, path: str, use_best: bool = False,
                      version: str | int | None = None,
                      extra: dict | None = None, **kwargs) -> str:
        """Export this policy as a versioned serving bundle (serve/bundle.py):
        params + frozen stats + obs-normalization moments + a manifest
        (module spec, git sha, jax version, provenance), committed
        atomically.  Serve it with ``python -m estorch_tpu.serve --bundle
        <path>`` (docs/serving.md)."""
        from ..serve.bundle import export_bundle

        return export_bundle(self, path, use_best=use_best, version=version,
                             extra=extra, **kwargs)
