"""Compiled episode rollouts: fixed-horizon ``lax.scan`` with done masking.

Replaces the reference's host-side ``while not done: policy(obs); env.step``
loop (SURVEY.md §3.3) with a single traced scan so XLA sees the whole
episode — and, after ``vmap``, the whole population — as one program:
policy matmuls batch onto the MXU, env math fuses into the surrounding ops,
and nothing touches the host until the generation's fitness vector exists.

Done masking: after an episode terminates, further steps still execute
(static shapes — the TPU way) but rewards are masked and state is frozen,
so results are exactly equal to early termination.  ``steps`` counts the
genuinely-alive steps for honest env-steps/sec accounting.
"""

from __future__ import annotations

import inspect
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..obs.trace import ENV, POLICY, stage


def carry_init_takes_params(carry_init: Callable[..., Any]) -> bool:
    """Whether ``carry_init`` is the params-aware form (``carry_init(params)
    -> carry``, the learned episode-start carry of models/policies.py) or
    the historical zero-arg form (``carry_init() -> carry``).

    Detected ONCE at build time and shared by every consumer of the compat
    contract (make_rollout, the engine's bf16 carry wrapper, ES.predict) so
    the two forms can never diverge between code paths.  When
    ``inspect.signature`` cannot introspect the callable, the form is
    PROBED — the zero-arg call is attempted under ``except TypeError`` —
    instead of guessed, so a non-introspectable zero-arg callable works
    rather than crashing at trace time with an unexpected argument.
    """
    try:
        return bool(inspect.signature(carry_init).parameters)
    except (TypeError, ValueError):
        pass
    try:
        carry_init()
        return False
    except TypeError:
        return True


class RolloutResult(NamedTuple):
    total_reward: jax.Array  # () float32 — the episode return (fitness)
    bc: jax.Array  # (bc_dim,) float32 — behavior characterization
    steps: jax.Array  # () int32 — alive steps actually taken
    # what a whole-episode policy returns beyond what its env scores (a
    # sparse-expert model's pairs per held expert); None for every other
    extras: Any = None


def select_action(policy_out: jax.Array, discrete: bool) -> jax.Array:
    """Reference action rule: argmax for discrete policies (SURVEY.md §3.3);
    continuous policies emit actions directly (models apply their own squash)."""
    if discrete:
        return jnp.argmax(policy_out, axis=-1)
    return policy_out


def make_rollout(
    env: Any,
    policy_apply: Callable[..., jax.Array],
    horizon: int,
    carry_init: Callable[..., Any] | None = None,
    with_obs_moments: bool = False,
    with_env_metrics: bool = False,
) -> Callable[[Any, jax.Array], Any]:
    """Build ``rollout(params, key) -> RolloutResult`` for one episode.

    ``policy_apply(params, obs) -> action logits/values``.  The returned
    function is pure and jit/vmap-safe; vmap it over ``(params, key)`` to
    evaluate a whole population slice in one program.

    Recurrent policies (``carry_init`` given): ``policy_apply(params, obs,
    h) -> (out, h')`` and the hidden carry is threaded through the episode
    scan — reset to ``carry_init(params)`` at episode start (so a policy
    with a LEARNED initial carry reads it from the member's perturbed
    params — models/policies.py ``learned_carry``), frozen (like env
    state) after termination.  The reference has no recurrent machinery
    (its ``agent.rollout`` owns the loop, SURVEY.md §3.3, so torch users
    thread hidden state themselves); here the loop is a compiled scan, so
    the framework must thread it.

    ``with_obs_moments=True``: the SAME scan additionally accumulates
    alive-masked raw-observation moments over the observations the policy
    acted on (including the reset frame) and the rollout returns
    ``(RolloutResult, (count, obs_sum, obs_sumsq))`` — the obs_norm
    probe's data source (parallel/engine.py), sharing one step body with
    the plain rollout so the two can never desynchronize.

    ``with_env_metrics=True`` (requires ``env.step_metrics(state) ->
    (k,) float32``): the scan additionally sums the env's per-step metric
    vector over the states reached by alive steps, and the rollout
    returns ``(RolloutResult, metric_sums (k,))``.  The env converts the
    sums into episode quantities via ``env.episode_metrics`` (e.g. the
    locomotion family's upright fraction) — measured gait claims instead
    of reward-scale ones.
    """
    if getattr(env, "whole_episode", False):
        return _make_whole_episode_rollout(env, policy_apply, carry_init)
    discrete = bool(env.discrete)
    stateful = carry_init is not None
    if stateful:
        # carry_init may be the historical zero-arg form (custom user
        # callables) or the params-aware form (learned episode-start
        # carry, models/policies.py) — detect once at build time
        _ci_takes_params = carry_init_takes_params(carry_init)
    if with_env_metrics and with_obs_moments:
        raise ValueError("one aux channel per rollout: obs moments are the "
                         "training probe, env metrics the evaluation one")
    if with_env_metrics:
        n_metrics = len(env.metric_names)

    def rollout(params: Any, key: jax.Array):
        with stage(ENV):
            state0, obs0 = env.reset(key)
        # episode-start carry may be learned: carry_init reads it from the
        # member's (perturbed) params when the policy asks for that
        if stateful:
            with stage(POLICY):
                h0 = carry_init(params) if _ci_takes_params else carry_init()
        else:
            h0 = None
        zeros = jnp.zeros_like(obs0, dtype=jnp.float32)

        def step_fn(carry, _):
            state, obs, done, total, steps, h, moments = carry
            with stage(ENV):
                alive = jnp.logical_not(done)
                alive_f = alive.astype(jnp.float32)
                if with_obs_moments:
                    cnt, osum, osumsq = moments
                    of = obs.astype(jnp.float32)
                    moments = (
                        cnt + alive_f,
                        osum + alive_f * of,
                        osumsq + alive_f * of * of,
                    )
            with stage(POLICY):
                if stateful:
                    out, h_new = policy_apply(params, obs, h)
                else:
                    out, h_new = policy_apply(params, obs), h
                action = select_action(out, discrete)
            with stage(ENV):
                nstate, nobs, reward, ndone = env.step(state, action)
                if with_env_metrics:
                    # metrics of the state this alive step REACHED; frozen
                    # (post-termination) pseudo-steps contribute nothing
                    moments = moments + alive_f * env.step_metrics(nstate)
                total = total + reward * alive_f
                steps = steps + alive.astype(jnp.int32)
                # freeze state/obs after termination so BC reads the final
                # frame
                keep = lambda new, old: jnp.where(alive, new, old)
                state_next = jax.tree_util.tree_map(keep, nstate, state)
                obs_next = keep(nobs, obs)
                h_next = jax.tree_util.tree_map(keep, h_new, h)
                done_next = done | ndone
            return (
                state_next, obs_next, done_next, total, steps, h_next, moments
            ), None

        if with_obs_moments:
            aux0 = (jnp.float32(0.0), zeros, zeros)
        elif with_env_metrics:
            aux0 = jnp.zeros((n_metrics,), jnp.float32)
        else:
            aux0 = None
        init = (
            state0,
            obs0,
            jnp.bool_(False),
            jnp.float32(0.0),
            jnp.int32(0),
            h0,
            aux0,
        )
        (state, obs, done, total, steps, _, moments), _ = jax.lax.scan(
            step_fn, init, None, length=horizon
        )
        with stage(ENV):
            bc = env.behavior(state, obs).astype(jnp.float32)
        res = RolloutResult(total_reward=total, bc=bc, steps=steps)
        return (
            (res, moments) if (with_obs_moments or with_env_metrics) else res
        )

    return rollout


def _make_whole_episode_rollout(env, policy_apply, carry_init):
    """An env that scores a whole episode in one call (``whole_episode``:
    envs/sequence.py): one policy call over the sequence the reset hands
    out, no step scan, nothing to mask."""
    if carry_init is not None:
        raise ValueError("a whole-episode env calls the policy once; it "
                         "threads no carry")

    def rollout(params: Any, key: jax.Array):
        with stage(ENV):
            state, obs = env.reset(key)
        with stage(POLICY):
            out = policy_apply(params, obs)
        with stage(ENV):
            total, bc, steps = env.score(state, obs, out)
        return RolloutResult(total_reward=total, bc=bc, steps=steps,
                             extras=tuple(out[2:]) or None)

    return rollout


def make_obs_probe(
    env: Any,
    policy_apply: Callable[..., jax.Array],
    horizon: int,
    carry_init: Callable[..., Any] | None = None,
) -> Callable[[Any, jax.Array], tuple[jax.Array, jax.Array, jax.Array]]:
    """One episode's raw-observation moments: ``probe(params, key) ->
    (count, obs_sum, obs_sumsq)``.

    Thin wrapper over :func:`make_rollout` with ``with_obs_moments=True``
    — the probe IS a center-policy episode (same step body, same
    termination/freeze semantics); only the moments are kept.  When the
    apply is the engine's normalization-packed form, normalization
    happens inside it, so the moments stay in raw observation space (what
    the running stats normalize).  Powers ``EngineConfig.obs_norm``.
    """
    rollout = make_rollout(env, policy_apply, horizon,
                           carry_init=carry_init, with_obs_moments=True)

    def probe(params: Any, key: jax.Array):
        _, moments = rollout(params, key)
        return moments

    return probe


def make_population_rollout(
    env: Any,
    policy_apply: Callable[..., jax.Array],
    horizon: int,
    carry_init: Callable[..., Any] | None = None,
) -> Callable[[Any, jax.Array], RolloutResult]:
    """vmap of ``make_rollout`` over stacked params and per-member keys.

    ``params`` leaves have a leading population axis; ``keys`` is (n,).
    Returns batched RolloutResult arrays — (n,), (n, bc_dim), (n,).
    ``carry_init`` as in :func:`make_rollout` (recurrent policies).
    """
    single = make_rollout(env, policy_apply, horizon, carry_init=carry_init)
    return jax.vmap(single, in_axes=(0, 0))


def make_batched_rollout(
    env: Any,
    horizon: int,
) -> Callable[[Callable[[jax.Array], jax.Array], jax.Array], RolloutResult]:
    """Population-batched episode scan: ONE policy call per step for ALL
    members, instead of vmapping a per-member rollout.

    ``rollout(batched_apply, keys)``: ``batched_apply(obs_batch (n, obs_dim))
    -> (n, act)`` closes over whatever per-member parameterization the
    caller uses — the entry point of the pair-shared forward
    (parallel/engine.py ``_eval_local_pairs``), which vmaps the policy over
    antithetic pairs while the env steps a flat member axis.  Env dynamics
    are vmapped; masking
    semantics are identical to :func:`make_rollout`.
    """
    discrete = bool(env.discrete)
    v_reset = jax.vmap(env.reset)
    v_step = jax.vmap(env.step)
    v_behavior = jax.vmap(env.behavior)

    def rollout(batched_apply, keys: jax.Array) -> RolloutResult:
        with stage(ENV):
            states0, obs0 = v_reset(keys)
        n = obs0.shape[0]

        def step_fn(carry, _):
            states, obs, done, total, steps = carry
            with stage(POLICY):
                out = batched_apply(obs)
                action = select_action(out, discrete)
            with stage(ENV):
                nstate, nobs, reward, ndone = v_step(states, action)
                alive = jnp.logical_not(done)
                total = total + reward * alive.astype(jnp.float32)
                steps = steps + alive.astype(jnp.int32)

                def keep(new, old):
                    mask = alive.reshape((-1,) + (1,) * (new.ndim - 1))
                    return jnp.where(mask, new, old)

                states_next = jax.tree_util.tree_map(keep, nstate, states)
                obs_next = keep(nobs, obs)
                done_next = done | ndone
            return (states_next, obs_next, done_next, total, steps), None

        init = (
            states0,
            obs0,
            jnp.zeros((n,), jnp.bool_),
            jnp.zeros((n,), jnp.float32),
            jnp.zeros((n,), jnp.int32),
        )
        (states, obs, done, total, steps), _ = jax.lax.scan(
            step_fn, init, None, length=horizon
        )
        with stage(ENV):
            bc = v_behavior(states, obs).astype(jnp.float32)
        return RolloutResult(total_reward=total, bc=bc, steps=steps)

    return rollout
