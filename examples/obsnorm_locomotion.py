"""Does observation normalization help locomotion?  A/B on Walker2D.

Walker2D observations mix bounded joint angles with unbounded velocity
channels whose variance dominates — the classic case running obs stats
exist for (OpenAI-ES normalizes MuJoCo observations for exactly this
reason; the reference has no such machinery).  Same recipe, same seeds,
only ``obs_norm`` differs.

Run:  python examples/obsnorm_locomotion.py [gens] [pop]
"""

import sys

import numpy as np


def run(obs_norm: bool, seed: int, gens: int, pop: int):
    from estorch_tpu import configs

    es = configs.walker2d_device(
        population_size=pop, seed=seed, obs_norm=obs_norm,
    )
    es.train(gens, verbose=False)
    means = [r["reward_mean"] for r in es.history]
    return {
        "final_mean": means[-1],
        "best": es.best_reward,
        "auc": float(np.mean(means)),  # area under the learning curve
    }


def main():
    gens = int(sys.argv[1]) if len(sys.argv) > 1 else 40
    pop = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    for seed in (0, 1):
        for flag in (True, False):
            r = run(flag, seed, gens, pop)
            print(f"seed {seed} obs_norm={str(flag):5s} "
                  f"final_mean {r['final_mean']:8.1f}  best {r['best']:8.1f}"
                  f"  auc {r['auc']:8.1f}", flush=True)


if __name__ == "__main__":
    main()
