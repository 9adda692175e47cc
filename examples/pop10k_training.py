"""humanoid2d_pop10k at its STATED population — a real training run.

Trains the exact shipped recipe (pop 10240, 256×256 policy, low_rank=1,
obs_norm, eval_chunk 1024, horizon 400) for a bounded number of
generations on whatever backend JAX picks — the chip where there is one
(`chip_smoke.py` runs three generations of this same configuration
there); set JAX_PLATFORMS=cpu for a CPU run — and records the learning
curve, per-generation wall time, and peak RSS.  A wall time is a fact
about the device it was taken on: say which when quoting one.

Run:  python examples/pop10k_training.py [gens] [seed]
"""

import json
import resource
import sys
import time


def main():
    gens = int(sys.argv[1]) if len(sys.argv) > 1 else 12
    seed = int(sys.argv[2]) if len(sys.argv) > 2 else 0

    from estorch_tpu import configs
    from estorch_tpu.utils import enable_compilation_cache

    enable_compilation_cache()

    es = configs.humanoid2d_pop10k(seed=seed)

    t0 = time.perf_counter()
    last = [t0]
    total_steps = 0

    def log(rec):
        nonlocal total_steps
        now = time.perf_counter()
        total_steps += rec["env_steps"]
        rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
        print(json.dumps({
            "gen": rec["generation"],
            "reward_mean": round(rec["reward_mean"], 1),
            "reward_max": round(rec["reward_max"], 1),
            "env_steps": rec["env_steps"],
            "gen_wall_s": round(now - last[0], 1),
            "elapsed_s": round(now - t0, 1),
            "peak_rss_gb": round(rss, 2),
        }), flush=True)
        last[0] = now

    es.train(gens, log_fn=log, verbose=False)

    ev = es.evaluate_policy(n_episodes=32, seed=1, return_details=True)
    g = ev.get("gait", {})
    print(json.dumps({
        "summary": "humanoid2d_pop10k STATED SCALE (pop 10240, low_rank=1, "
                   "obs_norm, 256x256, h400)",
        "gens": gens, "seed": seed,
        "first_reward_mean": round(es.history[0]["reward_mean"], 1),
        "final_reward_mean": round(es.history[-1]["reward_mean"], 1),
        "best": round(es.best_reward, 1),
        "heldout_mean_32ep": round(ev["mean"], 1),
        "heldout_std": round(ev["std"], 1),
        "fwd_vel_mps": round(float(g["forward_velocity_mps"].mean()), 3)
        if g else None,
        "upright_frac": round(float(g["upright_fraction"].mean()), 3)
        if g else None,
        "total_env_steps": total_steps,
        "wall_s": round(time.perf_counter() - t0, 1),
        "peak_rss_gb": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6, 2),
    }), flush=True)


if __name__ == "__main__":
    main()
