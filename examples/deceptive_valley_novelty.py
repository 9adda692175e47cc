"""Novelty vs reward-only ES on a DECEPTIVE locomotion task.

Round-4 verdict next #5: the novelty family's only outright win was
MountainCarContinuous; its real-physics showing (HalfCheetah NSR-ES) was
a predicted loss because plain locomotion is not deceptive.  This study
runs the A/B on a task BUILT to be deceptive — `DeceptiveValley`
(envs/locomotion.py): a reward valley along the progress axis of a
planar runner, the 1-D equivalent of Conti et al.'s U-maze (PAPERS.md).
Reward-following ES should stall at the bait (a true local optimum
whose basin covers the greedy path); novelty search over the
final-position BC has no such barrier.

Substrate: Swimmer2D — no alive bonus and no termination, so the shaped
fitness telescopes EXACTLY to reward_scale·(φ(x_T) − φ(x_0)) − control
cost (no survival confound), and — decisive (round-5 calibration) —
displacement is entirely EARNED: a passive/random swimmer stays at
x ≈ 0.00 while trained undulation reaches ~8 units (the walker/cheetah
alternatives drift ~0.5-0.8 units passively, so a valley inside their
envelope gets crossed by accident, not locomotion).

Geometry is SCALE-RELATIVE to the measured [passive, trained] span:
phase 0 measures the untrained median final x (x_rand), the trained
reach X, and the episode noise of final x; the bait sits at
x_rand + 0.35·(X − x_rand) and the valley ends at x_rand + 0.75·(X −
x_rand) — inside demonstrated reach, above passive drift — and the
study aborts honestly unless the valley width clears 3 noise widths
AND the bait clears the passive envelope by 5 (otherwise "escape"
could be luck, not search).

Run:  python examples/deceptive_valley_novelty.py [gens] [pop] [seeds]
          [valley_end_frac]

`valley_end_frac` (default 0.75) is the task-difficulty knob: where the
valley's far wall sits as a fraction of the calibrated [passive,
trained] span.  The round-5 120-gen run at 0.75 measured NSRA's valley
penetration at ~0.36 units per 120 gens — enough to show the mechanism
(ES pinned AT the bait both seeds; novelty past it both seeds) but a
3.3-unit-wide valley needs a budget no CPU-mesh session has.  A
narrower valley (e.g. 0.55) is the same trap — a true local optimum
whose width still clears the 3-noise-width guard by two orders of
magnitude — sized so a full escape fits the generation budget.
"""

import json
import sys
import time

import numpy as np


def _final_x_stats(es, n_episodes=16, meta_index=None):
    ev = es.evaluate_policy(n_episodes=n_episodes, seed=101,
                            meta_index=meta_index, return_details=True)
    xs = ev["bc"][:, 0]
    return (float(np.median(xs)), float(np.std(xs)), float(ev["mean"]))


def main():
    gens = int(sys.argv[1]) if len(sys.argv) > 1 else 120
    pop = int(sys.argv[2]) if len(sys.argv) > 2 else 512
    n_seeds = int(sys.argv[3]) if len(sys.argv) > 3 else 2
    valley_end_frac = float(sys.argv[4]) if len(sys.argv) > 4 else 0.75
    seed_start = int(sys.argv[5]) if len(sys.argv) > 5 else 0

    import optax

    from estorch_tpu import ES, NSRA_ES, JaxAgent, MLPPolicy
    from estorch_tpu.envs import DeceptiveValley, Swimmer2D
    from estorch_tpu.utils import enable_compilation_cache

    enable_compilation_cache()

    base = Swimmer2D()
    common = dict(
        policy=MLPPolicy, agent=JaxAgent, optimizer=optax.adam,
        population_size=pop, sigma=0.08,
        policy_kwargs={"action_dim": base.action_dim, "hidden": (32, 32),
                       "discrete": False, "action_scale": 1.0},
        # the proven Swimmer2D recipe (locomotion_swimmer.py: full gait in
        # ~30 gens at pop 512 / lr 3e-2) — calibration AND both A/B arms
        # share it, so an ES stall at the bait is deception, not an
        # under-powered optimizer (the round-5 0.77-unit calibration abort
        # was pop 256 / lr 2e-2 under-training, not geometry)
        optimizer_kwargs={"learning_rate": 3e-2},
    )

    # phase 0: passive envelope (median AND spread), reachable
    # displacement, trained episode noise
    cal = ES(agent_kwargs={"env": base, "horizon": 400}, seed=0, **common)
    x_rand, x_rand_noise, _ = _final_x_stats(cal)
    cal.train(max(gens // 2, 30), verbose=False)
    x_reach, x_noise, _ = _final_x_stats(cal)
    print(json.dumps({"phase": "calibrate", "x_rand": round(x_rand, 3),
                      "x_rand_noise": round(x_rand_noise, 3),
                      "x_reach": round(x_reach, 3),
                      "final_x_noise": round(x_noise, 3),
                      "gens": max(gens // 2, 30)}), flush=True)

    span = x_reach - x_rand
    x_bait = x_rand + 0.35 * span
    x_valley = x_rand + valley_end_frac * span
    width = x_valley - x_bait
    # two distinct noise scales: the TRAINED policy's episode spread sizes
    # the valley width; the PASSIVE policy's spread sizes the bait's
    # clearance above where un-trained episodes land by luck
    noise = max(x_noise, 1e-3)
    p_noise = max(x_rand_noise, 1e-3)
    if span <= 0 or width < 3.0 * noise or x_bait < x_rand + 5.0 * p_noise:
        print(json.dumps({"error": "geometry not luck-proof: span %.3f, "
                          "width %.3f vs 3*trained-noise %.3f, bait margin "
                          "%.3f vs 5*passive-noise %.3f"
                          % (span, width, 3 * noise,
                             x_bait - x_rand, 5 * p_noise)}),
              flush=True)
        return
    env = DeceptiveValley(base, x_bait=x_bait, x_valley=x_valley,
                          valley_slope=1.5, rise_slope=4.0,
                          reward_scale=10.0)
    print(json.dumps({"phase": "geometry", "x_bait": round(x_bait, 3),
                      "x_valley": round(x_valley, 3),
                      "reward_scale": 10.0}), flush=True)

    from estorch_tpu import NS_ES

    results = []
    for seed in range(seed_start, seed_start + n_seeds):
        for arm in ("es", "nses", "nsra"):
            t0 = time.perf_counter()
            if arm == "es":
                algo = ES(agent_kwargs={"env": env, "horizon": 400},
                          seed=seed, **common)
            else:
                # nses = pure novelty (Conti's strongest escaper on
                # deceptive tasks); nsra = adaptive reward/novelty blend
                cls = NS_ES if arm == "nses" else NSRA_ES
                algo = cls(agent_kwargs={"env": env, "horizon": 400},
                           seed=seed, k=10, meta_population_size=3,
                           **common)
            algo.train(gens, verbose=False)
            if arm == "es":
                x_med, _, r_mean = _final_x_stats(algo)
                per_center = [round(x_med, 3)]
            else:
                centers = [
                    _final_x_stats(algo, meta_index=i)
                    for i in range(len(algo.meta_states))
                ]
                per_center = [round(x, 3) for x, _, _ in centers]
                best = max(centers, key=lambda c: c[0])
                x_med, r_mean = best[0], best[2]
            row = {
                "phase": "ab", "arm": arm, "seed": seed,
                "median_final_x": round(x_med, 3),
                "per_center_x": per_center,
                "escaped_valley": bool(x_med > x_valley),
                "past_bait": bool(x_med > x_bait + 3 * max(noise, p_noise)),
                "heldout_reward_mean": round(r_mean, 1),
                "wall_s": round(time.perf_counter() - t0, 1),
            }
            results.append(row)
            print(json.dumps(row), flush=True)

    def esc(a):
        return [r["escaped_valley"] for r in results if r["arm"] == a]

    es_esc = esc("es")
    nov_esc = esc("nses") + esc("nsra")
    print(json.dumps({
        "verdict": {
            "es_escapes": f"{sum(es_esc)}/{len(es_esc)}",
            "nses_escapes": f"{sum(esc('nses'))}/{len(esc('nses'))}",
            "nsra_escapes": f"{sum(esc('nsra'))}/{len(esc('nsra'))}",
            "deception_held_for_es": not any(es_esc),
            "novelty_won": sum(nov_esc) > 0 and not any(es_esc),
        }
    }), flush=True)


if __name__ == "__main__":
    main()
