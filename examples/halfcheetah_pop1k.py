"""BASELINE config 2 at its stated scale: HalfCheetah, population ~1k.

Every recorded HalfCheetah run so far used pop 64 (the obs_norm A/Bs);
the BASELINE config says population 1k.  This runs the pooled recipe —
real MuJoCo physics in gym.vector workers, device-batched policy
forwards, the OpenAI-ES obs_norm setup the round-3 A/B measured at
+30-43% held-out — at population 1024 with mirrored sampling, and
records the learning curve, throughput, and peak RSS.

Run:  python examples/halfcheetah_pop1k.py [gens] [pop] [seed]
"""

import json
import resource
import sys
import time


def main():
    gens = int(sys.argv[1]) if len(sys.argv) > 1 else 40
    pop = int(sys.argv[2]) if len(sys.argv) > 2 else 1024
    seed = int(sys.argv[3]) if len(sys.argv) > 3 else 0

    from estorch_tpu import configs
    from estorch_tpu.parallel.mesh import single_device_mesh
    from estorch_tpu.utils import enable_compilation_cache, force_cpu_backend

    # pooled path: physics steps on the host CPU and inference is a small
    # device-batched program.  One CPU device on purpose: the run is
    # host-bound, so it should not hold a chip it would leave idle.
    force_cpu_backend(1)
    enable_compilation_cache()

    es = configs.halfcheetah_pooled(
        population_size=pop, seed=seed, obs_norm=True,
        mesh=single_device_mesh(),
    )

    t0 = time.perf_counter()
    total_steps = 0

    def log(rec):
        nonlocal total_steps
        total_steps += rec["env_steps"]
        el = time.perf_counter() - t0
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
        print(json.dumps({
            "gen": rec["generation"],
            "reward_mean": round(rec["reward_mean"], 1),
            "reward_max": round(rec["reward_max"], 1),
            "env_steps": rec["env_steps"],
            "steps_per_s": round(total_steps / el, 1),
            "elapsed_s": round(el, 1),
            "peak_rss_gb": round(rss, 2),
        }), flush=True)

    es.train(gens, log_fn=log, verbose=False)

    ev = es.evaluate_policy(n_episodes=32, seed=1)
    print(json.dumps({
        "summary": "halfcheetah_pooled pop-%d obs_norm" % pop,
        "gens": gens, "seed": seed,
        "final_reward_mean": round(es.history[-1]["reward_mean"], 1),
        "best": round(es.best_reward, 1),
        "heldout_mean_32ep": round(ev["mean"], 1),
        "heldout_std": round(ev["std"], 1),
        "total_env_steps": total_steps,
        "wall_s": round(time.perf_counter() - t0, 1),
        "peak_rss_gb": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6, 2),
    }), flush=True)
    es.engine.pool.close()
    es.engine.center_pool.close()


if __name__ == "__main__":
    main()
