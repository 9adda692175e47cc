"""Real MuJoCo Humanoid training — BASELINE config 3's first evidence.

Round-4 verdict next #2: the Humanoid env (gymnasium Humanoid-v5, the
v3 lineage's current id) had never trained in this repo — the capstone
evidence is the in-tree planar Humanoid2D.  This runs the pooled recipe
(`configs.humanoid_pooled`: real physics in gym.vector workers,
device-batched 256×256 MLP forwards, obs_norm, mirrored sampling) at a
CPU-feasible population and records the learning curve, throughput, and
peak RSS — config 3's evidence trail starts here; the 10k population is
the chip's job.

Run:  python examples/humanoid_v3_pooled.py [gens] [pop] [seed]
"""

import json
import resource
import sys
import time


def main():
    # flags and positionals may come in any order: `... 40 512 0 --resume`
    # and `... --resume` both work
    pos = [a for a in sys.argv[1:] if not a.startswith("--")]
    resume = "--resume" in sys.argv
    gens = int(pos[0]) if len(pos) > 0 else 40
    pop = int(pos[1]) if len(pos) > 1 else 512
    seed = int(pos[2]) if len(pos) > 2 else 0

    from estorch_tpu import configs
    from estorch_tpu.parallel.mesh import single_device_mesh
    from estorch_tpu.utils import (PeriodicCheckpointer,
                                   enable_compilation_cache,
                                   force_cpu_backend, restore_checkpoint)

    # pooled path: MuJoCo steps on the host CPU and inference is a small
    # device-batched program.  One CPU device on purpose: the run is
    # host-bound, so it should not hold a chip it would leave idle.
    force_cpu_backend(1)
    enable_compilation_cache()

    es = configs.humanoid_pooled(
        population_size=pop, seed=seed, mesh=single_device_mesh(),
    )
    # checkpoint + periodic held-out evals: a wall-clock kill (the round-5
    # stage-2 run died 2 generations before its final eval) must not cost
    # the evidence — the latest checkpoint restores and every 10th
    # generation already carries a held-out row
    ck = PeriodicCheckpointer(es, f"runs/humanoid_v3_s{seed}/ckpts",
                              every=5, max_to_keep=2)
    resumed_at = 0
    if resume and ck.latest():
        restore_checkpoint(es, ck.latest())
        resumed_at = es.generation
        print(json.dumps({"resumed_at": resumed_at}), flush=True)

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
        ck.on_record(rec)
        if rec["generation"] % 10 == 0:
            ev10 = es.evaluate_policy(n_episodes=8, seed=1)
            print(json.dumps({
                "gen": rec["generation"],
                "heldout_mean_8ep": round(ev10["mean"], 1),
                "heldout_std": round(ev10["std"], 1),
            }), flush=True)

    remaining = gens - es.generation
    if remaining > 0:
        es.train(remaining, log_fn=log, verbose=False)
    ck.save(es.generation)
    ck.close()

    ev = es.evaluate_policy(n_episodes=32, seed=1)
    print(json.dumps({
        "summary": "humanoid_pooled pop-%d obs_norm (Humanoid-v5)" % pop,
        # history-derived totals so a resumed run reports the WHOLE run,
        # not just the post-resume session (the log rows' steps_per_s and
        # wall_s stay session-relative by design)
        "gens": es.generation, "seed": seed,
        "resumed_at": resumed_at or None,
        "final_reward_mean": round(es.history[-1]["reward_mean"], 1),
        "best": round(es.best_reward, 1),
        "heldout_mean_32ep": round(ev["mean"], 1),
        "heldout_std": round(ev["std"], 1),
        "total_env_steps": int(sum(r["env_steps"] for r in es.history)),
        "session_env_steps": total_steps,
        "session_wall_s": round(time.perf_counter() - t0, 1),
        "peak_rss_gb": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6, 2),
    }), flush=True)
    es.engine.pool.close()
    es.engine.center_pool.close()


if __name__ == "__main__":
    main()
