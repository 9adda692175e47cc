"""BASELINE config 4 on real physics: NSR-ES on MuJoCo HalfCheetah.

The novelty family's end-to-end evidence so far is the deceptive
MountainCarContinuous; this runs NSR-ES — reward AND novelty, BC =
final x-position (Conti et al.'s locomotion characterization) — on real
MuJoCo through the pooled path, against a reward-only ES control at the
same budget, and checkpoints the archive mid-run to prove resume covers
the novelty state on this config.

Both arms share ONE hyperparameter dict (defined here, matching
configs.halfcheetah_nsres) so the A/B stays internally matched by
construction.

Run:  python examples/novelty_mujoco.py [gens] [pop] [seed]
"""

import json
import sys
import tempfile
import time


def shared_kw(pop, seed):
    """The config-4 recipe both arms share (mirrors halfcheetah_nsres)."""
    import optax

    from estorch_tpu import MLPPolicy, PooledAgent
    from estorch_tpu.parallel.mesh import single_device_mesh

    return dict(
        policy=MLPPolicy,
        agent=PooledAgent,
        optimizer=optax.adam,
        population_size=pop,
        sigma=0.02,
        seed=seed,
        policy_kwargs={"action_dim": 6, "hidden": (64, 64),
                       "discrete": False},
        agent_kwargs={
            "env_name": "gym:HalfCheetah-v5",
            "horizon": 1000,
            "env_kwargs": {
                "exclude_current_positions_from_observation": False},
            "bc_indices": (0,),
        },
        optimizer_kwargs={"learning_rate": 1e-2},
        weight_decay=0.005,
        mesh=single_device_mesh(),
    )


def close_pools(es):
    es.engine.pool.close()
    es.engine.center_pool.close()


def run_nsres(gens, pop, seed):
    from estorch_tpu import NSR_ES
    from estorch_tpu.utils import restore_checkpoint, save_checkpoint

    es = NSR_ES(k=10, meta_population_size=3, **shared_kw(pop, seed))
    t0 = time.perf_counter()

    def log(rec):
        print(json.dumps({
            "algo": "NSR_ES", "gen": rec["generation"],
            "reward_mean": round(rec["reward_mean"], 1),
            "reward_max": round(rec["reward_max"], 1),
            "novelty_mean": round(rec.get("novelty_mean", float("nan")), 3),
            "archive": len(es.archive),
            "elapsed_s": round(time.perf_counter() - t0, 1),
        }), flush=True)

    half = max(1, gens // 2)
    es.train(half, log_fn=log, verbose=False)

    # archive checkpoint/resume on THIS config (BASELINE config 4 asks for
    # a checkpointed archive): round-trip mid-run, then continue
    from estorch_tpu import NSR_ES as _NSR

    with tempfile.TemporaryDirectory() as d:
        save_checkpoint(es, d + "/ck")
        es2 = _NSR(k=10, meta_population_size=3, **shared_kw(pop, seed))
        try:
            restore_checkpoint(es2, d + "/ck")
            assert len(es2.archive) == len(es.archive), "archive must resume"
            print(json.dumps(
                {"archive_checkpoint_roundtrip": len(es2.archive)}),
                flush=True)
        finally:
            close_pools(es2)

    es.train(gens - half, log_fn=log, verbose=False)

    # final-x spread across the meta-population: what novelty bought
    xs = []
    for m in range(len(es.meta_states)):
        det = es.evaluate_policy(n_episodes=4, meta_index=m,
                                 return_details=True)
        xs.append(float(det["bc"][:, 0].mean()))
    out = {
        "summary": f"NSR_ES halfcheetah pop-{pop}", "gens": gens,
        "seed": seed,
        "final_reward_mean": round(es.history[-1]["reward_mean"], 1),
        "best": round(es.best_reward, 1),
        "archive_size": len(es.archive),
        "meta_final_x": [round(x, 2) for x in xs],
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    close_pools(es)
    return out


def run_es_control(gens, pop, seed):
    """Reward-only control: the SAME shared_kw, novelty machinery removed."""
    from estorch_tpu import ES

    es = ES(**shared_kw(pop, seed))
    t0 = time.perf_counter()

    def log(rec):
        print(json.dumps({
            "algo": "ES", "gen": rec["generation"],
            "reward_mean": round(rec["reward_mean"], 1),
            "reward_max": round(rec["reward_max"], 1),
            "elapsed_s": round(time.perf_counter() - t0, 1),
        }), flush=True)

    es.train(gens, log_fn=log, verbose=False)
    det = es.evaluate_policy(n_episodes=4, return_details=True)
    out = {
        "summary": f"ES control halfcheetah pop-{pop}", "gens": gens,
        "seed": seed,
        "final_reward_mean": round(es.history[-1]["reward_mean"], 1),
        "best": round(es.best_reward, 1),
        "final_x": round(float(det["bc"][:, 0].mean()), 2),
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    close_pools(es)
    return out


def main():
    gens = int(sys.argv[1]) if len(sys.argv) > 1 else 30
    pop = int(sys.argv[2]) if len(sys.argv) > 2 else 256
    seed = int(sys.argv[3]) if len(sys.argv) > 3 else 0

    from estorch_tpu.utils import enable_compilation_cache, force_cpu_backend

    # pooled path: MuJoCo steps on the host CPU; one CPU device on purpose
    # (host-bound — it should not hold a chip it would leave idle)
    force_cpu_backend(1)
    enable_compilation_cache()

    print(json.dumps(run_nsres(gens, pop, seed)), flush=True)
    print(json.dumps(run_es_control(gens, pop, seed)), flush=True)


if __name__ == "__main__":
    main()
