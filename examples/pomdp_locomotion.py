"""Partially observable locomotion: does memory pay when the velocity
channels go dark?

`PositionOnly(Walker2D())` zeros every rate channel (torso velocity,
spin, joint rates).  Standing still is statically achievable blind (the
alive bonus rewards it), so the discriminating metric is forward
DISPLACEMENT — walking needs the rate feedback a memoryless policy
cannot see and a recurrent one can estimate from consecutive positions.

Run:  python examples/pomdp_locomotion.py [gens] [pop]
"""

import sys

import numpy as np


def run(recurrent: bool, seed: int, gens: int, pop: int):
    import optax

    from estorch_tpu import ES, JaxAgent, MLPPolicy, RecurrentPolicy
    from estorch_tpu.envs import PositionOnly, Walker2D

    if recurrent:
        policy, pk = RecurrentPolicy, {
            "action_dim": 6, "hidden": (64,), "gru_size": 32,
            "discrete": False,
        }
    else:
        policy, pk = MLPPolicy, {
            "action_dim": 6, "hidden": (64, 64), "discrete": False,
        }
    es = ES(
        policy=policy, agent=JaxAgent, optimizer=optax.adam,
        population_size=pop, sigma=0.05, policy_kwargs=pk,
        agent_kwargs={"env": PositionOnly(Walker2D()), "horizon": 200},
        optimizer_kwargs={"learning_rate": 2e-2}, seed=seed,
    )
    es.train(gens, verbose=False)
    # displacement of the center policy: mean final BC x over held-out
    # episodes (the locomotion BC is the torso's final (x, y))
    ev = es.evaluate_policy(n_episodes=16, seed=99, return_details=True)
    return {
        "final_mean": es.history[-1]["reward_mean"],
        "best": es.best_reward,
        "center_disp_x": float(ev["bc"][:, 0].mean()),
    }


def main():
    gens = int(sys.argv[1]) if len(sys.argv) > 1 else 60
    pop = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    for seed in (0, 1):
        for rec in (True, False):
            r = run(rec, seed, gens, pop)
            name = "recurrent" if rec else "memoryless"
            print(f"seed {seed} {name:10s} final_mean {r['final_mean']:7.1f}"
                  f"  best {r['best']:7.1f}"
                  f"  center displacement {r['center_disp_x']:6.2f} m",
                  flush=True)


if __name__ == "__main__":
    main()
