"""obs_norm probe-count A/B: 1 vs 4 center episodes per generation.

Round-4 verdict weak #5: the device path's running obs stats come solely
from center-policy probe episodes (default 1/generation,
`EngineConfig.obs_probe_episodes`) — the one obs_norm default with no
A/B behind it.  Fixed generation budget on Humanoid2D; more probe
episodes converge the stats faster (and track the population's
distribution better through the center's neighborhood) at the cost of
extra probe FLOPs.  Compared at end-of-budget final/last-10 mean (the
round-4 lesson: obs_norm comparisons at end-of-budget, not AUC).

Run:  python examples/obsnorm_probe_ab.py [gens] [pop] [seeds]
"""

import json
import sys
import time

import numpy as np


def main():
    gens = int(sys.argv[1]) if len(sys.argv) > 1 else 150
    pop = int(sys.argv[2]) if len(sys.argv) > 2 else 512
    n_seeds = int(sys.argv[3]) if len(sys.argv) > 3 else 2

    from estorch_tpu import configs
    from estorch_tpu.utils import enable_compilation_cache

    enable_compilation_cache()

    for seed in range(n_seeds):
        for probes in (1, 4):
            t0 = time.perf_counter()
            es = configs.humanoid2d_device(
                population_size=pop, seed=seed,
                obs_probe_episodes=probes,
            )
            es.train(gens, verbose=False)
            means = [r["reward_mean"] for r in es.history]
            ev = es.evaluate_policy(n_episodes=16, seed=55)
            print(json.dumps({
                "arm": f"probe{probes}", "seed": seed, "gens": gens,
                "pop": pop,
                "final_mean": round(means[-1], 1),
                "last10_mean": round(float(np.mean(means[-10:])), 1),
                "auc_mean": round(float(np.mean(means)), 1),
                "heldout_mean_16ep": round(ev["mean"], 1),
                "obs_count": float(es.state.obs_stats[0]),
                "wall_s": round(time.perf_counter() - t0, 1),
            }), flush=True)


if __name__ == "__main__":
    main()
