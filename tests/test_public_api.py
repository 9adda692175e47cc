"""The public API surface is a parity CONTRACT (SURVEY.md Appendix A) —
this test freezes it so refactors can't silently drop exports."""

import inspect


def test_reference_parity_imports():
    """The reference's import line, estorch_tpu edition."""
    from estorch_tpu import (  # noqa: F401
        ES,
        NS_ES,
        NSR_ES,
        NSRA_ES,
        VirtualBatchNorm,
    )


def test_extended_surface_imports():
    from estorch_tpu import (  # noqa: F401
        JaxAgent,
        MLPPolicy,
        NatureCNN,
        NoveltyArchive,
        PooledAgent,
    )
    from estorch_tpu.models import TorchVirtualBatchNorm  # noqa: F401
    from estorch_tpu.envs import (  # noqa: F401
        Acrobot,
        CartPole,
        MountainCar,
        MountainCarContinuous,
        Pendulum,
    )
    from estorch_tpu.parallel import (  # noqa: F401
        global_population_mesh,
        initialize_distributed,
        population_mesh,
    )
    from estorch_tpu.utils import (  # noqa: F401
        JsonlWriter,
        PeriodicCheckpointer,
        restore_checkpoint,
        save_checkpoint,
    )
    from estorch_tpu.obs import (  # noqa: F401
        FlightRecorder,
        Heartbeat,
        JsonlSink,
        MultiSink,
        Telemetry,
        read_heartbeat,
        summarize,
        write_manifest,
    )
    from estorch_tpu.resilience import (  # noqa: F401
        CHAOS_ENV,
        ChaosError,
        ChaosPlan,
        Supervisor,
        run_resilient,
    )
    from estorch_tpu.serve import (  # noqa: F401
        BatcherSaturated,
        Bundle,
        BundleError,
        CircuitBreaker,
        DynamicBatcher,
        Fleet,
        FleetError,
        PolicyServer,
        Router,
        ServeClient,
        export_bundle,
        load_bundle,
        load_fleet_config,
        validate_bundle,
    )
    from estorch_tpu.utils import latest_checkpoint  # noqa: F401


def test_es_constructor_signature_matches_reference():
    """Appendix A ctor args must all exist with these names."""
    from estorch_tpu import ES

    params = inspect.signature(ES.__init__).parameters
    for name in ("policy", "agent", "optimizer", "population_size", "sigma",
                 "device", "policy_kwargs", "agent_kwargs", "optimizer_kwargs"):
        assert name in params, f"reference ctor arg {name!r} missing"


def test_es_options_are_a_written_list():
    """Every keyword of ``ES.__init__``, in order.  Each one multiplies the
    configurations tests and benchmark cells have to cover: a new option
    is an edit to this list, visible in review, not a drive-by."""
    from estorch_tpu import ES

    options = [
        # the reference's
        "policy", "agent", "optimizer", "population_size", "sigma", "device",
        "policy_kwargs", "agent_kwargs", "optimizer_kwargs",
        # this implementation's
        "seed", "table_size", "eval_chunk", "grad_chunk", "weight_decay",
        "mesh", "vbn_batch", "compute_dtype", "sigma_decay", "sigma_min",
        "mirrored", "episodes_per_member", "worker_mode", "low_rank",
        "obs_norm", "obs_clip", "obs_probe_episodes", "obs_warmup_episodes",
        "telemetry", "shard_params", "model_shards", "partition_rules",
        "noise_mode", "scenarios",
    ]
    assert len(options) == 33
    params = list(inspect.signature(ES.__init__).parameters)
    assert params == ["self"] + options


def test_train_signature_matches_reference():
    from estorch_tpu import ES

    params = inspect.signature(ES.train).parameters
    assert "n_steps" in params
    assert "n_proc" in params


def test_novelty_ctor_extras_match_reference():
    """Appendix A: k, meta-population size; NSRA: weight, delta, patience."""
    from estorch_tpu import NS_ES, NSRA_ES

    ns = inspect.signature(NS_ES.__init__).parameters
    assert "k" in ns and "meta_population_size" in ns
    nsra = inspect.signature(NSRA_ES.__init__).parameters
    for name in ("weight", "weight_delta", "stagnation_patience"):
        assert name in nsra


def test_instance_attributes_exposed():
    """es.policy / es.best_policy / es.best_reward exist as the reference's."""
    from estorch_tpu import ES

    assert isinstance(ES.policy, property)
    assert isinstance(ES.best_policy, property)
