"""The IW-ES claim, completed: vanilla ES at its OWN best lr vs IW-ES.

The round-2 result (−25% env-steps to threshold) compared both at
lr 3e-3 — the small-step regime the reuse math requires (lr ≲ σ/√dim,
algo/iwes.py).  The open question an expert asks: does vanilla ES at
its own best lr beat IW-ES at its constrained lr on env-steps AND on
wall-clock?  This sweeps vanilla over a lr grid, picks the best by
median env-steps to the bar, and compares both currencies.

Run: python examples/iwes_vs_best_lr.py [--quick]
"""

import json
import sys
import time

import numpy as np
import optax

from estorch_tpu import ES, IW_ES, JaxAgent, MLPPolicy
from estorch_tpu.envs import CartPole

SIGMA, GENS, WINDOW, POP = 0.1, 150, 2, 128
REUSE_LR = 3e-3  # the lr the reuse math constrains IW-ES to (σ/√dim)
VANILLA_GRID = (3e-3, 1e-2, 3e-2)
BAR = 450


def run(algo, lr, seed, gens):
    kw = dict(
        policy=MLPPolicy, agent=JaxAgent, optimizer=optax.adam,
        population_size=POP, sigma=SIGMA,
        policy_kwargs={"action_dim": 2, "hidden": (16, 16)},
        agent_kwargs={"env": CartPole()},
        optimizer_kwargs={"learning_rate": lr}, seed=seed,
    )
    es = (IW_ES(reuse_window=WINDOW, ess_min=0.3, **kw)
          if algo == "iwes" else ES(**kw))
    t0 = time.perf_counter()
    es.train(gens, verbose=False)
    wall = time.perf_counter() - t0
    steps, steps_at, wall_at = 0, None, None
    for r in es.history:
        steps += r["env_steps"]
        if steps_at is None and r["reward_mean"] >= BAR:
            steps_at = steps
            # wall-clock attribution: fraction of generations used
            wall_at = wall * (r["generation"] + 1 - es.history[0]["generation"]) / len(es.history)
    return {
        "steps_to_bar": steps_at,
        "wall_to_bar_s": round(wall_at, 1) if wall_at else None,
        "final_mean": round(es.history[-1]["reward_mean"], 1),
        "wall_s": round(wall, 1),
    }


def median_or_inf(vals):
    """Median with never-reached seeds counted as INFINITY, not dropped —
    dropping them would crown an lr that fails most seeds on the strength
    of its one lucky run."""
    return float(np.median([float("inf") if v is None else v for v in vals]))


def main():
    from estorch_tpu.utils import enable_compilation_cache

    enable_compilation_cache()

    quick = "--quick" in sys.argv
    gens = 40 if quick else GENS
    seeds = (0,) if quick else (0, 1, 2)

    results = {}
    for lr in VANILLA_GRID:
        rows = [run("es", lr, s, gens) for s in seeds]
        results[lr] = rows
        print(json.dumps({"algo": "es", "lr": lr,
                          "rows": rows}), flush=True)
    best_lr = min(
        results,
        key=lambda lr: (
            median_or_inf([r["steps_to_bar"] for r in results[lr]]),
            -np.median([r["final_mean"] for r in results[lr]]),
        ),
    )

    iwes_rows = [run("iwes", REUSE_LR, s, gens) for s in seeds]
    print(json.dumps({"algo": "iwes", "lr": REUSE_LR,
                      "rows": iwes_rows}), flush=True)

    verdict = {
        "vanilla_best_lr": best_lr,
        "vanilla_median_steps_to_bar": median_or_inf(
            [r["steps_to_bar"] for r in results[best_lr]]),
        "vanilla_median_wall_to_bar_s": median_or_inf(
            [r["wall_to_bar_s"] for r in results[best_lr]]),
        "iwes_lr": REUSE_LR,
        "iwes_median_steps_to_bar": median_or_inf(
            [r["steps_to_bar"] for r in iwes_rows]),
        "iwes_median_wall_to_bar_s": median_or_inf(
            [r["wall_to_bar_s"] for r in iwes_rows]),
    }
    def winner(iwes_med, vanilla_med):
        # neither arm reached the bar → no evidence, no winner
        if np.isinf(iwes_med) and np.isinf(vanilla_med):
            return "none"
        return "iwes" if iwes_med < vanilla_med else "vanilla"

    verdict["env_steps_winner"] = winner(
        verdict["iwes_median_steps_to_bar"],
        verdict["vanilla_median_steps_to_bar"],
    )
    verdict["wall_clock_winner"] = winner(
        verdict["iwes_median_wall_to_bar_s"],
        verdict["vanilla_median_wall_to_bar_s"],
    )
    print(json.dumps({"verdict": verdict}), flush=True)


if __name__ == "__main__":
    main()
