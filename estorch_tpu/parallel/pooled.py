"""PooledEngine — C++ host envs + device-batched policy inference.

The execution model for envs that cannot run on-device (the reference's
Gym/MuJoCo/Atari configs, SURVEY.md §7 'Path B'): N = population envs step
in parallel C++ threads (envs/native_pool.py → native/envpool.cpp) while the
accelerator runs ONE batched forward for the whole population per env step —
(population, obs_dim) in, (population, act_dim) out.  Per-member perturbed
parameters are materialized once per generation from the shared noise table;
the update is the identical psum program as the device path (ESEngine in
update-only mode), so offsets/weights stay bit-consistent between
evaluation and update.

vs the reference's design for the same configs: estorch steps ONE env per
Python process and runs the policy forward per single observation
(SURVEY.md §3.3) — here the policy forward is a population-wide batched
matmul on the MXU and env stepping is native threads, with one
host↔device round-trip per env step instead of per member-step.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..envs.gym_vec_pool import make_pool
from ..obs.spans import NULL_TELEMETRY
from ..ops.noise import member_offsets, pair_signs
from ..utils.fault import rank_weights_with_failures
from .engine import ESEngine, ESState, replicate_on_mesh


class PooledEvalResult:
    def __init__(self, fitness, bc, steps):
        self.fitness = fitness
        self.bc = bc
        self.steps = steps


class PooledEngine:
    """Same engine interface as ESEngine/HostEngine, pooled evaluation."""

    # span telemetry hub; ES hands over its own at construction
    # (obs/spans.py)
    telemetry = NULL_TELEMETRY

    def __init__(
        self,
        env_name: str,
        policy_apply,
        spec,
        table,
        optimizer,
        config,
        mesh,
        n_threads: int = 0,
        seed: int = 0,
        double_buffer: bool = False,
        prep: dict | None = None,
        carry_init=None,
        env_kwargs: dict | None = None,
        bc_indices=None,
        telemetry=None,
    ):
        if telemetry is not None:
            self.telemetry = telemetry
        self.env_name = env_name
        self.env_kwargs = dict(env_kwargs) if env_kwargs else None
        self.prep = dict(prep) if prep else None
        self.spec = spec
        self.config = config
        if config.episodes_per_member != 1:
            raise ValueError(
                "episodes_per_member is a device-path option; the pooled "
                "path rolls one episode per member env"
            )
        if config.low_rank:
            raise ValueError(
                "low_rank is a device-path option (ops/lowrank.py); the "
                "pooled path materializes per-member thetas"
            )
        # obs_norm on the pooled path: normalization + raw-moment
        # accumulation happen HOST-side in the step loop below (the obs
        # batches are already on the host); the running Welford stats ride
        # ESState.obs_stats exactly like the device path — checkpointed,
        # split==fused — while the CORE update programs stay stats-agnostic
        # (they carry obs_stats through untouched), so the core config has
        # the flag stripped.  Richer than the device path's center-probe:
        # the stats see every member's observations.
        self.obs_norm = bool(config.obs_norm)
        self._obs_clip = float(config.obs_clip)
        self._pending_moments = None
        self._pending_moments_key = None
        if self.obs_norm and self.prep:
            raise ValueError(
                "obs_norm + Atari preprocessing is unsupported: pixel "
                "policies normalize via VBN / their own /255 scaling"
            )
        import dataclasses as _dc

        core_config = (
            _dc.replace(config, obs_norm=False) if self.obs_norm else config
        )
        # update-only device engine: shares offsets/psum/optax with the
        # fully-on-device path; its ctor also applies the compute_dtype wrap
        # (incl. the stateful bf16 shim + carry cast for recurrent policies),
        # which we reuse below instead of wrapping a second time
        self.core = ESEngine(None, policy_apply, spec, table, optimizer,
                             core_config, mesh, carry_init=carry_init)
        policy_apply = self.core.policy_apply
        carry_init = self.core._carry_init  # bf16 path: pre-cast variant
        self.recurrent = carry_init is not None
        self._carry_init = carry_init
        self.double_buffer = bool(double_buffer)
        def _pool(n_envs, threads, pool_seed):
            pool = make_pool(env_name, n_envs, n_threads=threads,
                             seed=pool_seed, env_kwargs=self.env_kwargs)
            if self.prep:
                from ..envs.atari_wrappers import AtariPreprocessPool

                pool = AtariPreprocessPool(pool, seed=pool_seed, **self.prep)
            return pool

        self._make_pool = _pool

        if self.double_buffer:
            half = config.population_size // 2
            if half * 2 != config.population_size or half == 0:
                raise ValueError(
                    "double_buffer needs an even population of at least 2"
                )
            self.pool_a = _pool(half, n_threads, seed)
            self.pool_b = _pool(half, n_threads, seed + 10_007)
            self.pool = self.pool_a  # dims/metadata accessor
        else:
            self.pool = _pool(config.population_size, n_threads, seed)
        # n_threads=0 (auto): a 1-env pool gains nothing from threads, and a
        # nonzero value would trip GymVecPool's unused-n_threads warning
        self.center_pool = _pool(1, 0, seed + 1)
        # BC = final observation, optionally sliced to bc_indices (e.g.
        # (0,) = final x-position when the env exposes it — the canonical
        # locomotion BC the novelty family's archive searches over)
        self._bc_idx = (
            np.asarray(bc_indices, np.intp) if bc_indices is not None else None
        )
        if self._bc_idx is not None:
            if len(self.pool.obs_shape) != 1:
                # the BC frame is the FLAT final obs; on pixel/prep pools
                # the last axis is channels, not the flat vector — slicing
                # there would silently break the archive's (n, bc_dim)
                # contract
                raise ValueError(
                    "bc_indices need a 1-D observation; got obs_shape "
                    f"{self.pool.obs_shape} — pixel policies characterize "
                    "behavior via the full final frame"
                )
            if self._bc_idx.min() < 0 or self._bc_idx.max() >= self.pool.obs_dim:
                raise ValueError(
                    f"bc_indices {list(self._bc_idx)} out of range for "
                    f"obs_dim {self.pool.obs_dim}"
                )
        self.bc_dim = (
            len(self._bc_idx) if self._bc_idx is not None else self.pool.obs_dim
        )
        discrete = self.pool.discrete
        obs_shape = self.pool.obs_shape  # policy-facing shape (pixels etc.)

        # core.policy_apply is the obs/output shim only (engine.py): the
        # bf16 param cast is the caller's job.  Perturbation stays f32; the
        # materialized theta matrix casts ONCE per generation — unravel
        # preserves dtype for single-dtype trees, so every per-step
        # inference below reads bf16 weights with no further casts.
        bf16 = config.compute_dtype == "bfloat16"

        def materialize(params_flat, sigma, all_offs):
            """(population, dim) perturbed parameter matrix from the table.
            ``all_offs`` is per-pair (mirrored) or per-member (unmirrored),
            matching core.all_pair_offsets."""
            if config.mirrored:
                offs = member_offsets(all_offs)
                signs = pair_signs(config.population_size)
            else:
                offs = all_offs
                signs = jnp.ones((config.population_size,), jnp.float32)
            def one(off, sign):
                eps = self.core.table.slice(off, spec.dim)
                return params_flat + sigma * sign * eps
            thetas = jax.vmap(one)(offs, signs)
            return thetas.astype(jnp.bfloat16) if bf16 else thetas

        self._materialize = jax.jit(materialize)

        def _params(flat):
            return spec.unravel(flat.astype(jnp.bfloat16) if bf16 else flat)

        def _act(out):
            """Shared action rule: argmax logits (discrete) / flat values."""
            if discrete:
                return jnp.argmax(out, axis=-1).astype(jnp.float32)
            return out.reshape(-1)

        if self.recurrent:
            # the hidden carry lives host-side across the generation's step
            # loop: (population, …) stacked carries in, stacked carries out
            def batch_actions(thetas, obs, carries):
                def one(theta, o, h):
                    out, h2 = policy_apply(
                        spec.unravel(theta), o.reshape(obs_shape), h
                    )
                    return _act(out), h2
                return jax.vmap(one)(thetas, obs, carries)

            def center_action(params_flat, obs, h):
                out, h2 = policy_apply(
                    _params(params_flat), obs.reshape(obs_shape), h
                )
                return _act(out), h2
        else:
            def batch_actions(thetas, obs):
                """One env step's policy forward for the whole population."""
                def one(theta, o):
                    return _act(
                        policy_apply(spec.unravel(theta), o.reshape(obs_shape))
                    )
                return jax.vmap(one)(thetas, obs)

            def center_action(params_flat, obs):
                return _act(
                    policy_apply(_params(params_flat), obs.reshape(obs_shape))
                )

        self._batch_actions = jax.jit(batch_actions)  # re-specializes per
        # batch shape, so the same callable serves full and half populations
        self._center_action = jax.jit(center_action)

    def _carries(self, n: int):
        """Stacked episode-start carries for an n-member batch."""
        one = self._carry_init()
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (n,) + x.shape), one
        )

    # ------------------------------------------------------------ interface

    def init_state(self, params_flat, key) -> ESState:
        with self.telemetry.phase("setup/init_state"):
            return self._init_state(params_flat, key)

    def _init_state(self, params_flat, key) -> ESState:
        state = self.core.init_state(params_flat, key)
        if self.obs_norm:
            # same init as the device path: count=1, mean=0, m2=1 → var 1
            d = self.pool.obs_dim
            state = state._replace(obs_stats=replicate_on_mesh((
                jnp.float32(1.0),
                jnp.zeros((d,), jnp.float32),
                jnp.ones((d,), jnp.float32),
            ), self.core.mesh))
        return state

    # ---- obs_norm host-side helpers ----

    def _norm_params(self, state):
        """(mean, rstd) numpy pair from the state's Welford triple."""
        c, m, m2 = state.obs_stats
        c = float(c)
        mean = np.asarray(m, np.float32)
        var = np.maximum(np.asarray(m2, np.float32) / c, 1e-8)
        return mean, (1.0 / np.sqrt(var)).astype(np.float32)

    def _norm_np(self, obs, mean, rstd):
        return np.clip((obs - mean) * rstd, -self._obs_clip,
                       self._obs_clip).astype(np.float32)

    def compile(self, state: ESState) -> float:
        with self.telemetry.phase("setup/compile"):
            return self._compile(state)

    def _compile(self, state: ESState) -> float:
        import time as _time

        t0 = _time.perf_counter()
        pair_offs = self.core.all_pair_offsets(state)
        thetas = self._materialize(state.params_flat, state.sigma, pair_offs)
        # warm the batch size the evaluator will actually use
        warm_n = (
            self.config.population_size // 2
            if self.double_buffer
            else self.config.population_size
        )
        obs = jnp.zeros((warm_n, self.pool.obs_dim), jnp.float32)
        if self.recurrent:
            acts, _ = self._batch_actions(
                thetas[:warm_n], obs, self._carries(warm_n)
            )
            acts.block_until_ready()
        else:
            self._batch_actions(thetas[:warm_n], obs).block_until_ready()
        fwd_dt = _time.perf_counter() - t0
        # the forward warm is a traced-and-executed jit call (its compile
        # can't be split from the warm execution), so its ledger entry
        # carries wall seconds only — the AOT'd update below contributes
        # XLA cost facts via its Compiled object
        self.telemetry.compile_event("pooled_forward", fwd_dt,
                                     first_call=True)
        t1 = _time.perf_counter()
        dummy_w = jnp.zeros((self.config.population_size,), jnp.float32)
        compiled = self.core._apply_weights.lower(state, dummy_w).compile()
        self.telemetry.compile_event(
            "apply_weights", _time.perf_counter() - t1, compiled=compiled,
            first_call=True)
        return _time.perf_counter() - t0

    compile_split = compile

    def member_params(self, state: ESState, member_index: int):
        return self.core.member_params(state, member_index)

    def evaluate(self, state: ESState) -> PooledEvalResult:
        with self.telemetry.phase("sample"):
            pair_offs = self.core.all_pair_offsets(state)
            thetas = self._materialize(state.params_flat, state.sigma,
                                       pair_offs)
            # fence: materialization is device work — unfenced, this span
            # would clock dispatch only and the first batched forward of
            # the step loop would absorb the compute (esguard R07)
            jax.block_until_ready(thetas)
        norm = self._norm_params(state) if self.obs_norm else None
        if self.obs_norm:
            # raw-moment accumulators for this generation's alive steps —
            # merged into the state by apply_weights/generation_step.
            # Stamped with the evaluated state's generation AND its params
            # buffer identity so a discarded evaluation (eval-only probe,
            # exception between the calls) or a DIFFERENT center at the
            # same generation (meta-population NS/NSR/NSRA share gen
            # numbers across centers) can never fold its observations into
            # an unrelated update's running stats — apply_weights drops on
            # any mismatch.
            self._pending_moments = [
                0.0,
                np.zeros(self.pool.obs_dim, np.float64),
                np.zeros(self.pool.obs_dim, np.float64),
            ]
            # hold the buffer itself (not its id()) so the identity can't
            # be recycled by the allocator between the two calls
            self._pending_moments_key = (
                int(state.generation), state.params_flat,
            )
        if self.double_buffer:
            return self._evaluate_double_buffered(thetas, norm)
        return self._evaluate_sync(thetas, norm)

    def _accumulate_moments(self, obs, alive) -> None:
        raw = obs[alive]
        if len(raw):
            m = self._pending_moments
            m[0] += float(len(raw))
            m[1] += raw.sum(axis=0, dtype=np.float64)
            m[2] += (raw.astype(np.float64) ** 2).sum(axis=0)

    def _evaluate_sync(self, thetas, norm=None) -> PooledEvalResult:
        return self._run_pool(
            self.pool, thetas, self.config.population_size, norm,
            accumulate=norm is not None,
        )

    def _run_pool(self, pool, thetas, n, norm, accumulate) -> PooledEvalResult:
        """Step ``n`` episodes (one per pool env, one theta row each) to
        completion: native-thread env stepping + one batched device forward
        per step.  ``accumulate`` feeds the alive observations into the
        pending obs moments (training evaluations only — held-out evals
        must not touch the running stats)."""
        horizon = self.config.horizon

        obs = pool.reset()
        total = np.zeros(n, np.float32)
        alive = np.ones(n, bool)
        final_obs = obs.copy()
        steps = 0
        carry = self._carries(n) if self.recurrent else None
        for _ in range(horizon):
            if norm is not None:
                if accumulate:
                    self._accumulate_moments(obs, alive)
                feed = jnp.asarray(self._norm_np(obs, *norm))
            else:
                feed = jnp.asarray(obs)
            if self.recurrent:
                acts_dev, carry = self._batch_actions(thetas, feed, carry)
                actions = np.asarray(acts_dev)
            else:
                actions = np.asarray(self._batch_actions(thetas, feed))
            next_obs, rew, done = pool.step(actions)
            total += rew * alive
            steps += int(alive.sum())
            # record the observation at termination as the BC frame
            just_died = alive & done
            if just_died.any():
                final_obs[just_died] = obs[just_died]
            alive &= ~done
            obs = next_obs
            if not alive.any():
                break
        final_obs[alive] = obs[alive]  # survivors: last frame
        return PooledEvalResult(
            fitness=total, bc=self._bc(final_obs.copy()), steps=steps
        )

    def _bc(self, final_obs):
        """BC frame → characterization: identity, or the bc_indices dims."""
        return (
            final_obs if self._bc_idx is None else final_obs[..., self._bc_idx]
        )

    def _evaluate_double_buffered(self, thetas, norm=None) -> PooledEvalResult:
        """Overlap device inference with native env stepping (SURVEY.md §7
        hard-part 1).

        The population splits into two halves with independent env pools.
        jax dispatch is asynchronous, so while half A's actions are being
        synced to the host and its envs stepped in C++ threads, half B's
        batched forward is already executing on the device — per step the
        device and the env team work concurrently instead of taking turns.
        Results are identical to running each half through the sync path.
        """
        n = self.config.population_size
        h = n // 2
        horizon = self.config.horizon
        halves = [
            dict(pool=self.pool_a, thetas=thetas[:h], lo=0),
            dict(pool=self.pool_b, thetas=thetas[h:], lo=h),
        ]
        total = np.zeros(n, np.float32)
        alive = np.ones(n, bool)
        steps = 0

        def dispatch(half):
            # NO moment accumulation here: the trailing dispatch after the
            # last stepped iteration computes actions that are never
            # stepped — accumulating at dispatch time would over-count vs
            # the sync path (moments are taken at STEP time below)
            if norm is not None:
                feed = jnp.asarray(self._norm_np(half["obs"], *norm))
            else:
                feed = jnp.asarray(half["obs"])
            if self.recurrent:
                acts, half["carry"] = self._batch_actions(
                    half["thetas"], feed, half["carry"]
                )
                half["fut"] = acts
            else:
                half["fut"] = self._batch_actions(half["thetas"], feed)

        for half in halves:
            half["obs"] = half["pool"].reset()
            if self.recurrent:
                half["carry"] = self._carries(h)
            dispatch(half)
        final_obs = np.concatenate([halves[0]["obs"], halves[1]["obs"]], axis=0)

        for _ in range(horizon):
            if not alive.any():
                break
            for half in halves:
                # syncing this half's actions lets the OTHER half's forward
                # (dispatched at the end of its previous turn) run on-device
                # while this half's envs step in C++ threads
                actions = np.asarray(half["fut"])
                sl = slice(half["lo"], half["lo"] + h)
                if norm is not None:
                    # accumulate exactly the observations that get STEPPED
                    # (pre-step alive mask) — count == env_steps invariant,
                    # identical to the sync path
                    self._accumulate_moments(half["obs"], alive[sl])
                next_obs, rew, done = half["pool"].step(actions)
                total[sl] += rew * alive[sl]
                steps += int(alive[sl].sum())
                just_died = alive[sl] & done
                if just_died.any():
                    final_obs[sl][just_died] = half["obs"][just_died]
                alive[sl] &= ~done
                half["obs"] = next_obs
                dispatch(half)

        for half in halves:
            sl = slice(half["lo"], half["lo"] + h)
            final_obs[sl][alive[sl]] = half["obs"][alive[sl]]
        return PooledEvalResult(
            fitness=total, bc=self._bc(final_obs), steps=steps
        )

    def evaluate_center_batch(
        self, state: ESState, n_episodes: int, seed: int = 0
    ) -> PooledEvalResult:
        """All ``n_episodes`` center-policy episodes in ONE pooled pass
        (not serially, one episode after another): a
        fresh n_episodes-env pool steps in native threads while the device
        runs one batched forward per step.  Episode randomness comes from
        the pool seed, so ``seed`` picks the episode set.  Raw moments are
        NOT accumulated — held-out evaluation must not feed the training
        stats.

        The fresh pool per call is deliberate, not an oversight: pools
        seed only on their FIRST reset (see GymVecPool.reset), so caching
        a pool across calls would silently turn "same seed → same episode
        set" into "same seed → wherever the RNG stream got to" — the
        determinism contract held-out comparisons rely on.  The repeated
        ``_batch_actions`` specialization per distinct n_episodes is the
        jit cache working as intended (same shapes hit the cache)."""
        bf16 = self.config.compute_dtype == "bfloat16"
        theta = jnp.asarray(
            state.params_flat, jnp.bfloat16 if bf16 else jnp.float32
        )
        thetas = jnp.broadcast_to(theta, (n_episodes, theta.shape[0]))
        pool = self._make_pool(n_episodes, 0, 20_011 + int(seed))
        norm = self._norm_params(state) if self.obs_norm else None
        try:
            return self._run_pool(pool, thetas, n_episodes, norm,
                                  accumulate=False)
        finally:
            pool.close()

    def evaluate_center(self, state: ESState):
        from ..envs.rollout import RolloutResult

        obs = self.center_pool.reset()[0]
        total, steps = 0.0, 0
        h = self._carry_init() if self.recurrent else None
        norm = self._norm_params(state) if self.obs_norm else None
        for _ in range(self.config.horizon):
            feed = (
                jnp.asarray(self._norm_np(obs[None], *norm)[0])
                if norm is not None else jnp.asarray(obs)
            )
            if self.recurrent:
                a_dev, h = self._center_action(state.params_flat, feed, h)
                a = np.asarray(a_dev)
            else:
                a = np.asarray(self._center_action(state.params_flat, feed))
            nobs, rew, done = self.center_pool.step(a[None])
            total += float(rew[0])
            steps += 1
            if bool(done[0]):
                # post-done nobs[0] is not this episode's frame (C++ pool:
                # fresh reset state; gym pool: terminal obs) — keep the
                # pre-step frame as the BC, matching evaluate()'s convention
                break
            obs = nobs[0]
        return RolloutResult(
            total_reward=jnp.float32(total),
            bc=jnp.asarray(self._bc(np.asarray(obs)), jnp.float32),
            steps=jnp.int32(steps),
        )

    def apply_weights(self, state: ESState, weights):
        new_state, gnorm = self.core.apply_weights(state, jnp.asarray(weights))
        key = self._pending_moments_key
        self._pending_moments_key = None
        if (
            self.obs_norm
            and self._pending_moments is not None
            and key is not None
            and key[0] == int(state.generation)
            and key[1] is state.params_flat
        ):
            # fold the generation's observed raw moments (accumulated by
            # evaluate) into the running Welford triple — the f64 host
            # merge: population×horizon samples per generation would
            # cancel catastrophically in the f32 in-program merge
            from .engine import merge_obs_moments_np

            with self.telemetry.phase("obsnorm_merge"):
                c1, s1, q1 = self._pending_moments
                self._pending_moments = None
                if c1 > 0:
                    new_state = new_state._replace(
                        obs_stats=replicate_on_mesh(merge_obs_moments_np(
                            new_state.obs_stats, c1, s1, q1
                        ), self.core.mesh)
                    )
        else:
            # stale moments from a discarded evaluation: drop, never merge
            self._pending_moments = None
        return new_state, gnorm

    def generation_step(self, state: ESState):
        from ..resilience.chaos import mutate_fitness

        obs = self.telemetry
        with obs.phase("eval"):
            ev = self.evaluate(state)
            fit = np.asarray(ev.fitness)
        fit = mutate_fitness(state.generation, fit)
        n_valid = int(np.isfinite(fit).sum())
        base = {"fitness": fit, "bc": ev.bc, "steps": ev.steps,
                "n_valid": n_valid}
        if n_valid < 2:
            # population collapse: report via n_valid with state untouched —
            # ES.train owns the reject/re-run policy (docs/resilience.md)
            return state, {**base, "grad_norm": float("nan"),
                           "update_finite": True}
        # NaN-safe: a crashed/diverged rollout must not win the top rank
        # (np.argsort sorts NaN last) — drop it and renormalize survivors
        with obs.phase("update"):
            weights = rank_weights_with_failures(fit)
            new_state, gnorm = self.apply_weights(state, weights)
            # fence the psum/optax program so the span is device time
            jax.block_until_ready(new_state.params_flat)
        metrics = {
            **base,
            "grad_norm": gnorm,
            # post-update anomaly guard input (ES.train rejects on False)
            "update_finite": bool(
                np.isfinite(np.asarray(gnorm))
                and np.isfinite(np.asarray(new_state.params_flat)).all()
            ),
        }
        return new_state, metrics
