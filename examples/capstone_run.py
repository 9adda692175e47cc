"""Long-budget capstone driver for any shipped recipe, on whatever
backend JAX picks (the chip where there is one; JAX_PLATFORMS=cpu for a
CPU run).

Trains `configs.<name>` for a bounded generation budget with the full
evidence protocol the round-4/5 capstones used: a JSONL learning curve,
held-out evaluations (32 episodes, gait metrics included) every
`eval_every` generations, and periodic checkpoints so a killed run
keeps its endgame (the round-5 Humanoid-v5 lesson).

Run:  python examples/capstone_run.py [config] [gens] [eval_every] [seed]
      defaults: humanoid2d_device 1000 100 0
"""

import json
import resource
import sys
import time


def main():
    name = sys.argv[1] if len(sys.argv) > 1 else "humanoid2d_device"
    gens = int(sys.argv[2]) if len(sys.argv) > 2 else 1000
    eval_every = int(sys.argv[3]) if len(sys.argv) > 3 else 100
    seed = int(sys.argv[4]) if len(sys.argv) > 4 else 0

    from estorch_tpu import configs
    from estorch_tpu.utils import (PeriodicCheckpointer,
                                   enable_compilation_cache)

    enable_compilation_cache()

    es = configs.CONFIGS[name](seed=seed)
    ck = PeriodicCheckpointer(
        es, f"runs/capstone_{name}_s{seed}/ckpts", every=eval_every,
        max_to_keep=2)

    t0 = time.perf_counter()

    def log(rec):
        if rec["generation"] % 10:
            return
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
        print(json.dumps({
            "gen": rec["generation"],
            "reward_mean": round(rec["reward_mean"], 1),
            "reward_max": round(rec["reward_max"], 1),
            "elapsed_s": round(time.perf_counter() - t0, 1),
            "peak_rss_gb": round(rss, 2),
        }), flush=True)

    done = 0
    while done < gens:
        step = min(eval_every, gens - done)
        es.train(step, log_fn=lambda r: (log(r), ck.on_record(r)),
                 verbose=False)
        done += step
        ev = es.evaluate_policy(n_episodes=32, seed=1, return_details=True)
        g = ev.get("gait", {})  # per-episode arrays → report episode means
        print(json.dumps({
            "heldout_at_gen": es.generation,
            "mean": round(float(ev["mean"]), 1),
            "std": round(float(ev["std"]), 1),
            **{k: round(float(v.mean()), 3) for k, v in g.items()},
        }), flush=True)
    ck.save(es.generation)
    ck.close()
    print(json.dumps({
        "summary": f"capstone {name} seed {seed}",
        "gens": gens,
        "best_reward": round(float(es.best_reward), 1),
        "wall_s": round(time.perf_counter() - t0, 1),
    }), flush=True)


if __name__ == "__main__":
    main()
