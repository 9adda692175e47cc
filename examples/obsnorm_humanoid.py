"""Does observation normalization move the Humanoid2D plateau?

Round 3 left the capstone at a standing-plus-drift population (600 gens,
mean 158.6, best 422 by gen 175) and obs_norm untried on it.  Walker2D's
obs_norm null came with an explanation — no variance spread to fix — so
step 0 here MEASURES Humanoid2D's per-dimension observation spread to
predict the outcome, then runs the A/B at a fixed budget: same recipe,
same seeds, only ``obs_norm`` differs.

Run:  python examples/obsnorm_humanoid.py [gens] [pop] [--spread-only]
"""

import json
import sys
import time

import numpy as np


def measure_spread(n_episodes=4, horizon=400):
    """Per-dim obs variance of a random policy on Humanoid2D: the scale
    spread obs_norm exists to fix (Walker2D measured ~flat → null)."""
    import jax
    import jax.numpy as jnp

    from estorch_tpu.envs import Humanoid2D

    env = Humanoid2D()

    def episode(key):
        def step(carry, _):
            state, k = carry
            k, ka = jax.random.split(k)
            act = jax.random.uniform(
                ka, (env.action_dim,), minval=-1.0, maxval=1.0
            )
            state, obs, _, _ = env.step(state, act)
            return (state, k), obs

        k0, k1 = jax.random.split(key)
        state, obs0 = env.reset(k0)
        _, obs = jax.lax.scan(step, (state, k1), None, length=horizon)
        return jnp.concatenate([obs0[None], obs], axis=0)

    keys = jax.random.split(jax.random.PRNGKey(0), n_episodes)
    obs = np.asarray(jax.vmap(episode)(keys)).reshape(-1, int(env.obs_dim))
    var = obs.var(axis=0)
    mean = obs.mean(axis=0)
    return {
        "obs_dim": int(env.obs_dim),
        "var_min": float(var.min()),
        "var_max": float(var.max()),
        "var_spread": float(var.max() / max(var.min(), 1e-12)),
        "n_dims_var_gt_1": int((var > 1.0).sum()),
        "n_dims_var_lt_0.1": int((var < 0.1).sum()),
        "max_abs_mean_over_std": float(
            (np.abs(mean) / np.sqrt(np.maximum(var, 1e-12))).max()
        ),
    }


def run(obs_norm: bool, seed: int, gens: int, pop: int):
    from estorch_tpu import configs

    es = configs.humanoid2d_device(
        population_size=pop, seed=seed, obs_norm=obs_norm,
    )
    t0 = time.perf_counter()
    es.train(gens, verbose=False)
    means = [r["reward_mean"] for r in es.history]
    return {
        "final_mean": round(means[-1], 1),
        "best": round(es.best_reward, 1),
        "auc": round(float(np.mean(means)), 1),
        "last10_mean": round(float(np.mean(means[-10:])), 1),
        "wall_s": round(time.perf_counter() - t0, 1),
    }


def main():
    from estorch_tpu.utils import enable_compilation_cache

    enable_compilation_cache()

    print(json.dumps({"spread": measure_spread()}), flush=True)
    if "--spread-only" in sys.argv:
        return
    args = [a for a in sys.argv[1:] if not a.startswith("--")]
    gens = int(args[0]) if args else 80
    pop = int(args[1]) if len(args) > 1 else 512
    for seed in (0, 1):
        for flag in (True, False):
            r = run(flag, seed, gens, pop)
            print(json.dumps({"seed": seed, "obs_norm": flag, "gens": gens,
                              "pop": pop, **r}), flush=True)


if __name__ == "__main__":
    main()
