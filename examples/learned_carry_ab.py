"""Does a LEARNED episode-start carry pay on POMDP locomotion?

A/B for the round-5 `learned_carry=True` extension (models/policies.py):
the episode-start carry becomes ordinary ``carry0_*`` params — perturbed
by ES noise, moved by the update — instead of zeros.  The hypothesis:
on a partially observable task the recurrent core spends its first
steps rebuilding rate estimates from positions; a learned start state
can encode that warm-up (a gait-phase prior), which a zeros start must
re-derive every episode.

Protocol mirrors examples/pomdp_locomotion.py: `PositionOnly(Walker2D())`
(all rate channels zeroed — walking requires memory), identical budget
and hypers for both arms, displacement as the discriminating metric.
Also reports the trained ‖carry0‖ so "the learned start moved away from
zeros" is itself a measurement, and an honest null stays publishable.

Run:  python examples/learned_carry_ab.py [gens] [pop] [seeds]
"""

import json
import sys
import time

import numpy as np


def run(learned: bool, seed: int, gens: int, pop: int):
    import optax

    from estorch_tpu import ES, JaxAgent, RecurrentPolicy

    from estorch_tpu.envs import PositionOnly, Walker2D

    pk = {"action_dim": 6, "hidden": (64,), "gru_size": 32,
          "discrete": False, "learned_carry": learned}
    es = ES(
        policy=RecurrentPolicy, agent=JaxAgent, optimizer=optax.adam,
        population_size=pop, sigma=0.05, policy_kwargs=pk,
        agent_kwargs={"env": PositionOnly(Walker2D()), "horizon": 200},
        optimizer_kwargs={"learning_rate": 2e-2}, seed=seed,
    )
    t0 = time.perf_counter()
    es.train(gens, verbose=False)
    ev = es.evaluate_policy(n_episodes=16, seed=99, return_details=True)
    out = {
        "arm": "learned" if learned else "zeros",
        "seed": seed,
        "final_mean": round(float(es.history[-1]["reward_mean"]), 1),
        "heldout_mean": round(float(ev["mean"]), 1),
        "center_disp_x": round(float(ev["bc"][:, 0].mean()), 2),
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    if learned:
        c0 = es._spec.unravel(es.state.params_flat)["carry0_0"]
        out["carry0_norm"] = round(float(np.linalg.norm(np.asarray(c0))), 3)
    return out


def main():
    gens = int(sys.argv[1]) if len(sys.argv) > 1 else 120
    pop = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    n_seeds = int(sys.argv[3]) if len(sys.argv) > 3 else 2

    from estorch_tpu.utils import enable_compilation_cache

    enable_compilation_cache()

    rows = []
    for seed in range(n_seeds):
        for learned in (False, True):
            r = run(learned, seed, gens, pop)
            rows.append(r)
            print(json.dumps(r), flush=True)

    def med(arm, k):
        return float(np.median([r[k] for r in rows if r["arm"] == arm]))

    print(json.dumps({"verdict": {
        "zeros_heldout_median": med("zeros", "heldout_mean"),
        "learned_heldout_median": med("learned", "heldout_mean"),
        "zeros_disp_median": med("zeros", "center_disp_x"),
        "learned_disp_median": med("learned", "center_disp_x"),
    }}), flush=True)


if __name__ == "__main__":
    main()
