"""Worker entrypoint for the REAL multi-process validation test.

Launched by tests/test_multiprocess.py as ``python _mp_worker.py <pid>
<nprocs> <port> <outdir>``.  Each worker is one JAX process with 4 local
CPU devices; ``jax.distributed`` connects them over Gloo/TCP — the same
runtime layering a TPU pod uses over DCN (SURVEY.md §2 'Distributed
communication backend'), so collectives here genuinely cross process
boundaries instead of staying inside one XLA client.

Requests the CPU platform with 4 devices BEFORE any device use: these
workers simulate hosts, and must never reach for (or hold) a real chip.
"""

import pathlib
import sys

from estorch_tpu.utils.backend import force_cpu_backend

force_cpu_backend(4)


def main() -> None:
    pid, nprocs, port = int(sys.argv[1]), int(sys.argv[2]), int(sys.argv[3])
    outdir = pathlib.Path(sys.argv[4])
    algo = sys.argv[5] if len(sys.argv) > 5 else "es"

    import estorch_tpu.parallel.multihost as mh

    # Gloo CPU collectives: the default CPU client refuses any
    # cross-process psum ("Multiprocess computations aren't implemented")
    assert mh.initialize(f"localhost:{port}", num_processes=nprocs,
                         process_id=pid, cpu_collectives=True), \
        "distributed init did not happen"
    info = mh.process_info()
    assert info["process_count"] == nprocs
    assert info["global_devices"] == nprocs * 4

    import numpy as np
    import optax

    from estorch_tpu import ES, NSR_ES, JaxAgent, MLPPolicy
    from estorch_tpu.envs import CartPole

    kw = dict(
        policy=MLPPolicy,
        agent=JaxAgent,
        optimizer=optax.adam,
        population_size=16,
        sigma=0.1,
        policy_kwargs={"action_dim": 2, "hidden": (8,), "discrete": True},
        agent_kwargs={"env": CartPole(), "horizon": 64},
        optimizer_kwargs={"learning_rate": 1e-2},
        seed=7,
        mesh=mh.global_population_mesh(),
    )
    if algo == "nsr":
        # the novelty family keeps archive/meta-selection HOST-side on
        # every process, derived from replicated device results + the
        # seeded RNG — the claim under test is that all processes evolve
        # identical host state with zero communication
        es = NSR_ES(meta_population_size=2, k=3, **kw)
    else:
        es = ES(**kw)
    es.train(2, verbose=False)

    # leader_only must elect exactly one writer
    wrote = mh.leader_only(lambda: True)()

    extra = {}
    if algo == "nsr":
        extra = {
            "archive": np.asarray(es.archive.bcs, np.float64),
            "meta_sums": np.asarray(
                [np.asarray(s.params_flat, np.float64).sum()
                 for s in es.meta_states]
            ),
            "meta_indices": np.asarray(
                [r["meta_index"] for r in es.history], np.int64
            ),
        }
    np.savez(
        outdir / f"proc{pid}.npz",
        params=np.asarray(es.state.params_flat, np.float64),
        fitness=np.asarray(es.history[-1]["reward_mean"], np.float64),
        best=np.float64(es.best_reward),
        is_leader_writer=np.bool_(bool(wrote)),
        **extra,
    )
    print(f"proc {pid}: OK", flush=True)


if __name__ == "__main__":
    main()
