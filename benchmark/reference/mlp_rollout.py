"""Plain reference for an MLP-policy ES member: float32 ``jax.numpy`` at
``highest`` matmul precision, no engine, no chunking, no kernels.

It takes the centre ``theta`` (flat), reads each sampled member's noise from
the SAME table by the SAME offset, adds ``sigma * sign * eps`` explicitly
(full-rank table noise; a rank-1 configuration brings a reference file of its
own), unravels the vector by its own layout rule, and steps the environment in one plain
``lax.scan`` with the done mask of the rollout contract (reward counted
while alive, state frozen afterwards).  The environment object is the task
definition and is the program's; everything else here is independent of it.

Flat layout (flax ``Dense`` trees ravelled in key order): for each layer in
order ``dense_0 .. dense_{k-1}, head``: ``bias (n,)`` then ``kernel (m, n)``
row-major.

Keying contract mirrored from the engine (``parallel/engine.py``): with
``base = fold_in(state.key, generation)``, rollout keys come from
``split(fold_in(base, 1), rows)``; a mirrored population has one row per
antithetic pair (members ``2k`` and ``2k+1`` share offset and key, signs
``+1, -1``), an unmirrored one has one row per member.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark import costs


def layer_shapes(config: dict) -> tuple[tuple[int, int], ...]:
    dims = [config["obs_dim"], *config["hidden"], config["action_dim"]]
    return tuple(zip(dims[:-1], dims[1:]))


def describe(config: dict) -> dict:
    """What the harness needs to know of this policy family, from the
    configuration's own keys (``obs_dim``, ``hidden``, ``action_dim``): the
    length of the flat parameter vector, and the multiply-adds one member
    needs for one environment step, whatever the noise path does
    (``costs.matmul_flops`` over the policy's matmuls)."""
    shapes = layer_shapes(config)
    return {"layers": shapes,
            "param_dim": sum(m * n + n for m, n in shapes),
            "flops_per_member_step": costs.matmul_flops(shapes)}


def unravel_mlp(flat, shapes):
    """[(bias, kernel), ...] from the flat vector; ``shapes`` is
    ``[(m, n), ...]`` in layer order."""
    layers, at = [], 0
    for m, n in shapes:
        bias = flat[at:at + n]
        at += n
        kernel = flat[at:at + m * n].reshape(m, n)
        at += m * n
        layers.append((bias, kernel))
    if at != flat.shape[0]:
        raise ValueError(f"layout covers {at} of {flat.shape[0]} parameters")
    return layers


def init_theta(key, config):
    """Seeded initial weights in the flat layout, made on the device in one
    jitted call: kernels normal with standard deviation 1/sqrt(fan_in)
    (flax's ``Dense`` default without the truncation), biases zero."""
    return _init_theta(key, layer_shapes(config))


@jax.jit(static_argnums=1)
def _init_theta(key, shapes):
    keys = jax.random.split(key, len(shapes))
    parts = []
    for k, (m, n) in zip(keys, shapes):
        parts.append(jnp.zeros((n,), jnp.float32))
        parts.append(jax.random.normal(k, (m * n,), jnp.float32)
                     / jnp.sqrt(jnp.float32(m)))
    return jnp.concatenate(parts)


def normalize(obs, obs_stats, clip):
    count, mean, m2 = obs_stats
    var = jnp.maximum(m2 / count, 1e-8)
    return jnp.clip((obs - mean) / jnp.sqrt(var), -clip, clip)


def forward(layers, obs, action_scale):
    x = obs
    for bias, kernel in layers[:-1]:
        x = jnp.tanh(x @ kernel + bias)
    bias, kernel = layers[-1]
    return jnp.tanh(x @ kernel + bias) * action_scale


def member_keys(state_key, generation, rows):
    base = jax.random.fold_in(state_key, generation)
    return jax.random.split(jax.random.fold_in(base, 1), rows)


def make_reference(env, config, horizon, obs_clip=None):
    """``fitness(theta, table, offsets, signs, keys, sigma, obs_stats) ->
    (returns (k,), alive_steps (k,), behaviour (k, b))`` for ``k`` members,
    jitted once.  ``behaviour`` is the environment's own summary of the
    state the episode ended in (``env.behavior``: the torso's position for
    Humanoid2D), which the generation program reports per member too."""

    shapes = layer_shapes(config)
    action_scale = config["action_scale"]
    dim = describe(config)["param_dim"]

    def one(theta, table, off, sign, key, sigma, obs_stats):
        eps = jax.lax.dynamic_slice(table, (off,), (dim,))
        layers = unravel_mlp(theta + sigma * sign * eps, shapes)
        state0, obs0 = env.reset(key)

        def step(carry, _):
            state, obs, done, total, steps = carry
            x = obs.astype(jnp.float32)
            if obs_clip is not None:
                x = normalize(x, obs_stats, obs_clip)
            action = forward(layers, x, action_scale)
            nstate, nobs, reward, ndone = env.step(state, action)
            alive = jnp.logical_not(done)
            total = total + jnp.where(alive, reward, 0.0)
            steps = steps + alive.astype(jnp.int32)
            keep = lambda new, old: jnp.where(alive, new, old)
            state = jax.tree_util.tree_map(keep, nstate, state)
            return (state, keep(nobs, obs), done | ndone, total, steps), None

        init = (state0, obs0, jnp.bool_(False), jnp.float32(0.0),
                jnp.int32(0))
        (state, obs, _, total, steps), _ = jax.lax.scan(step, init, None,
                                                        length=horizon)
        return total, steps, env.behavior(state, obs).astype(jnp.float32)

    @jax.jit
    def fitness(theta, table, offsets, signs, keys, sigma, obs_stats):
        with jax.default_matmul_precision("highest"):
            return jax.vmap(one, in_axes=(None, None, 0, 0, 0, None, None))(
                theta, table, offsets, signs, keys, sigma, obs_stats)

    return fitness
