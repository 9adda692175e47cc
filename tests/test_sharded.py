"""Param-sharded hyperscale engine tests (docs/sharding.md).

The acceptance contract of the sharded path:

- partition rules resolve every leaf of the demo policies' trees, error
  on unmatched leaves, and round-trip through config serialization;
- a same-seed sharded run (table noise) matches the replicated fused
  path allclose at f32 (reduction order is the only licensed delta);
- program-mode noise is mesh-shape invariant (GSPMD value semantics);
- a policy whose replicated footprint exceeds the per-device budget
  trains ≥3 generations on the sharded path with per-device peak bytes
  (compile-ledger memory_analysis) under the replicated bound;
- generations are donated (in-place) and the in-program anomaly
  rollback preserves the deterministic re-run contract.
"""

import numpy as np
import optax
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from estorch_tpu.envs import CartPole, SyntheticEnv
from estorch_tpu.models import MLPPolicy, NatureCNN, RecurrentPolicy
from estorch_tpu.ops import make_noise_table, make_param_spec
from estorch_tpu.parallel import (
    DEFAULT_PARTITION_RULES,
    EngineConfig,
    ESEngine,
    MODEL_AXIS,
    ShardedESEngine,
    hyperscale_mesh,
    match_partition_rules,
    partition_rules_from_json,
    partition_rules_to_json,
    population_mesh,
)
from estorch_tpu.parallel.mesh import sharding_summary

from conftest import collectives


def _mlp_setup():
    def init_params(key):
        k1, k2 = jax.random.split(key)
        return {
            "dense_0": {"kernel": jax.random.normal(k1, (4, 16)) * 0.5,
                        "bias": jnp.zeros(16)},
            "head": {"kernel": jax.random.normal(k2, (16, 2)) * 0.5,
                     "bias": jnp.zeros(2)},
        }

    def apply(p, obs):
        h = jnp.tanh(obs @ p["dense_0"]["kernel"] + p["dense_0"]["bias"])
        return h @ p["head"]["kernel"] + p["head"]["bias"]

    params = init_params(jax.random.PRNGKey(0))
    flat, spec = make_param_spec(params)
    return flat, spec, apply


@pytest.fixture(scope="module")
def setup():
    flat, spec, apply = _mlp_setup()
    return dict(
        flat=flat, spec=spec, apply=apply, env=CartPole(),
        table=make_noise_table(1 << 18, seed=0), opt=optax.adam(3e-2),
        cfg=EngineConfig(population_size=32, sigma=0.1, horizon=50,
                         eval_chunk=8),
    )


def _sharded(s, mesh, noise_mode="program", cfg=None, table=None):
    return ShardedESEngine(
        s["env"], s["apply"], s["spec"],
        table if table is not None else (
            s["table"] if noise_mode == "table" else None),
        s["opt"], cfg or s["cfg"], mesh, noise_mode=noise_mode)


# ---------------------------------------------------------------------
# partition rules (satellite: matching, coverage error, serialization)
# ---------------------------------------------------------------------

class TestPartitionRules:
    def _demo_param_trees(self):
        """Shape trees of the bundled demo policies, via eval_shape (no
        compute)."""
        trees = {}
        mlp = MLPPolicy(action_dim=4, hidden=(64, 64))
        trees["mlp"] = jax.eval_shape(
            mlp.init, jax.random.PRNGKey(0), jnp.zeros((8,)))["params"]
        rec = RecurrentPolicy(action_dim=2, hidden=(32,), gru_size=16)
        trees["recurrent"] = jax.eval_shape(
            rec.init, jax.random.PRNGKey(0), jnp.zeros((8,)),
            rec.carry_init())["params"]
        cnn = NatureCNN(action_dim=6)
        trees["cnn"] = jax.eval_shape(
            cnn.init, jax.random.PRNGKey(0),
            jnp.zeros((84, 84, 4)))["params"]
        return trees

    def test_default_rules_cover_demo_policies(self, devices8):
        """Every leaf of every demo policy's tree resolves — the
        rule-coverage contract the engine builds on."""
        mesh = hyperscale_mesh(2, 4)
        for name, tree in self._demo_param_trees().items():
            sh = match_partition_rules(DEFAULT_PARTITION_RULES, tree, mesh)
            summary = sharding_summary(tree, sh)
            assert summary, name
            # at least the big kernels actually shard over model
            assert any(MODEL_AXIS in spec for spec in summary.values()), (
                name, summary)

    def test_unmatched_leaf_errors(self, devices8):
        mesh = hyperscale_mesh(2, 4)
        rules = ((r"kernel$", P(None, MODEL_AXIS)),)  # no catch-all
        tree = {"dense": {"kernel": jnp.zeros((8, 8)),
                          "bias": jnp.zeros((8,))}}
        with pytest.raises(ValueError, match="dense/bias"):
            match_partition_rules(rules, tree, mesh)

    def test_scalars_always_replicate(self, devices8):
        mesh = hyperscale_mesh(2, 4)
        # the sharding rule would be invalid for a scalar — the scalar
        # guard must win before any rule matches
        sh = match_partition_rules(
            ((r".*", P(MODEL_AXIS)),), {"count": jnp.float32(0.0)}, mesh)
        assert sh["count"].spec == P()

    def test_divisibility_fallback_replicates(self, devices8):
        """A dim the mesh axis cannot divide evenly falls back to
        replication for THAT dim (jax requires even shards; padding a
        parameter would change the optimization problem)."""
        mesh = hyperscale_mesh(2, 4)
        tree = {"head": {"kernel": jnp.zeros((16, 17)),
                         "bias": jnp.zeros((68,))}}
        sh = match_partition_rules(DEFAULT_PARTITION_RULES, tree, mesh)
        assert sh["head"]["kernel"].spec == P(None, None)  # 17 % 4 != 0
        assert sh["head"]["bias"].spec == P(MODEL_AXIS)  # 68 % 4 == 0

    def test_optimizer_state_resolves_through_same_rules(self, devices8):
        """adam's mu/nu embed param-shaped subtrees under the same leaf
        names; ONE rule set covers params and optimizer state."""
        mesh = hyperscale_mesh(2, 4)
        params = {"dense": {"kernel": jnp.zeros((8, 16)),
                            "bias": jnp.zeros((16,))}}
        opt_shape = jax.eval_shape(optax.adam(1e-2).init, params)
        sh = match_partition_rules(DEFAULT_PARTITION_RULES, opt_shape, mesh)
        leaves = jax.tree_util.tree_leaves(
            sh, is_leaf=lambda x: hasattr(x, "spec"))
        specs = {str(l.spec) for l in leaves}
        assert str(P(None, MODEL_AXIS)) in specs  # mu/nu kernels sharded
        assert str(P()) in specs  # count replicated

    def test_rules_round_trip_through_config_serialization(self):
        import json

        data = partition_rules_to_json(DEFAULT_PARTITION_RULES)
        # must be plain-JSON serializable (the manifest rides it)
        rebuilt = partition_rules_from_json(json.loads(json.dumps(data)))
        assert len(rebuilt) == len(DEFAULT_PARTITION_RULES)
        for (p0, s0), (p1, s1) in zip(DEFAULT_PARTITION_RULES, rebuilt):
            assert p0 == p1
            assert tuple(s0) == tuple(s1)


# ---------------------------------------------------------------------
# numerical contracts
# ---------------------------------------------------------------------

class TestShardedParity:
    def test_table_mode_matches_replicated_fused_path(self, setup, devices8):
        """THE numerical contract: same-seed sharded (table noise) vs the
        replicated fused engine, allclose at f32 over 3 generations.
        Reduction order is the licensed difference (model-sharded
        contractions psum in a different association), hence allclose,
        not bit-equality — docs/sharding.md."""
        eng = _sharded(setup, hyperscale_mesh(2, 4), noise_mode="table")
        rep = ESEngine(setup["env"], setup["apply"], setup["spec"],
                       setup["table"], setup["opt"], setup["cfg"],
                       population_mesh())
        s = eng.init_state(setup["flat"], jax.random.PRNGKey(7))
        sr = rep.init_state(setup["flat"], jax.random.PRNGKey(7))
        for gen in range(3):
            s, m = eng.generation_step(s)
            sr, mr = rep.generation_step(sr)
            np.testing.assert_allclose(
                np.asarray(m["fitness"]), np.asarray(mr["fitness"]),
                rtol=1e-5, atol=1e-5,
                err_msg=f"fitness diverged at gen {gen}")
            assert int(m["steps"]) == int(mr["steps"])
            np.testing.assert_allclose(
                np.asarray(s.params_flat), np.asarray(sr.params_flat),
                rtol=2e-4, atol=1e-5,
                err_msg=f"params diverged at gen {gen}")

    @pytest.mark.slow  # three engine builds; the (2,4) leg also runs
    # inside every non-slow test above, so tier-1 keeps 2-D coverage
    def test_program_mode_mesh_shape_invariance(self, setup, devices8):
        """GSPMD value semantics: the in-program noise keyed on
        (key, generation, row, leaf) gives the same run on ANY mesh
        shape, f32 reduction order aside."""
        results = []
        for shape in ((1, 8), (8, 1), (2, 4)):
            eng = _sharded(setup, hyperscale_mesh(*shape))
            s = eng.init_state(setup["flat"], jax.random.PRNGKey(3))
            for _ in range(2):
                s, m = eng.generation_step(s)
            results.append((shape, np.asarray(s.params_flat),
                            np.asarray(m["fitness"])))
        ref_shape, ref_p, ref_f = results[0]
        for shape, p, f in results[1:]:
            np.testing.assert_allclose(
                f, ref_f, rtol=1e-5, atol=1e-5,
                err_msg=f"fitness {shape} vs {ref_shape}")
            np.testing.assert_allclose(
                p, ref_p, rtol=5e-4, atol=1e-5,
                err_msg=f"params {shape} vs {ref_shape}")

    def test_member_reconstruction_matches_eval(self, setup, devices8):
        """member_params(i) (eager, off-mesh) must be exactly the θ the
        in-program path evaluated for member i — one keying contract."""
        from estorch_tpu.envs.rollout import make_rollout
        from estorch_tpu.parallel.engine import _gen_keys

        eng = _sharded(setup, hyperscale_mesh(2, 4))
        s0 = eng.init_state(setup["flat"], jax.random.PRNGKey(11))
        _, m = eng.generation_step(s0)
        # s0 was donated — rebuild an identical state for reconstruction
        s0 = eng.init_state(setup["flat"], jax.random.PRNGKey(11))
        theta5 = eng.member_params(s0, 5)
        # program mode runs under the PARTITIONABLE threefry impl
        # (docs/sharding.md): any host-side replay of its key derivations
        # and rollouts must enter the same scope or the streams differ
        with jax.threefry_partitionable(True):
            _, rkey = _gen_keys(s0)
            pair_keys = jax.random.split(rkey, 16)
            rollout = make_rollout(setup["env"], setup["apply"],
                                   setup["cfg"].horizon)
            res = rollout(setup["spec"].unravel(theta5), pair_keys[5 // 2])
            reward = float(res.total_reward)
        assert reward == pytest.approx(float(m["fitness"][5]), abs=1e-4)

    @pytest.mark.slow  # two engine builds; the replicated twin of this
    # regression (test_engine.py::test_indivisible_pairs_padded) and the
    # shared mesh.padded_count machinery stay in tier-1
    def test_arbitrary_population_padding(self, setup, devices8):
        """pop=10 over 8 pop-shards (the old divisibility error class):
        ghost-padded, matching the same run on a padding-free mesh."""
        cfg = EngineConfig(population_size=10, sigma=0.1, horizon=30)
        e_pad = _sharded(setup, hyperscale_mesh(8, 1), cfg=cfg)
        e_one = _sharded(setup, hyperscale_mesh(1, 8), cfg=cfg)
        sp = e_pad.init_state(setup["flat"], jax.random.PRNGKey(5))
        so = e_one.init_state(setup["flat"], jax.random.PRNGKey(5))
        for _ in range(2):
            sp, mp = e_pad.generation_step(sp)
            so, mo = e_one.generation_step(so)
        assert mp["fitness"].shape == (10,)
        np.testing.assert_allclose(np.asarray(mp["fitness"]),
                                   np.asarray(mo["fitness"]),
                                   rtol=1e-5, atol=1e-5)
        assert int(mp["steps"]) == int(mo["steps"])
        np.testing.assert_allclose(np.asarray(sp.params_flat),
                                   np.asarray(so.params_flat),
                                   rtol=5e-4, atol=1e-5)

    def test_low_rank_program_noise_trains(self, setup, devices8):
        """Factored in-program noise (A·Bᵀ/√r generated per row/leaf,
        update einsums the factors): trains finite, and the factored-leaf
        plan follows the (m+n)·r < m·n save-or-dense rule."""
        cfg = EngineConfig(population_size=16, sigma=0.1, horizon=30,
                           low_rank=2)
        eng = _sharded(setup, hyperscale_mesh(2, 4), cfg=cfg)
        # 4x16: 2·(4+16)=40 < 64 → factored;  16x2: 2·18=36 ≥ 32 → dense
        factored_shapes = {eng.leaf_shapes[i] for i in eng._factored}
        assert factored_shapes == {(4, 16)}
        s = eng.init_state(setup["flat"], jax.random.PRNGKey(1))
        for _ in range(2):
            s, m = eng.generation_step(s)
        assert bool(np.asarray(m["update_finite"]))
        assert int(m["n_valid"]) == 16


class TestDonationAndRollback:
    def test_generation_is_donated_in_place(self, setup, devices8):
        """donate_argnums actually took: the input state's buffers are
        deleted after the step (sample→eval→update ran in place)."""
        eng = _sharded(setup, hyperscale_mesh(2, 4))
        s0 = eng.init_state(setup["flat"], jax.random.PRNGKey(0))
        leaf0 = jax.tree_util.tree_leaves(s0.params)[0]
        s1, _ = eng.generation_step(s0)
        assert leaf0.is_deleted(), "input params survived — donation lost"
        jax.block_until_ready(jax.tree_util.tree_leaves(s1.params))

    def test_in_program_rollback_on_collapsed_population(self, devices8):
        """All-NaN fitness → n_valid 0 → the program emits the INPUT
        state unchanged (the donated path's in-program twin of ES.train's
        host-side restore): same generation, same params — so the
        deterministic re-run contract holds."""
        import dataclasses

        class NaNEnv:
            obs_dim = 4
            action_dim = 2
            discrete = False
            bc_dim = 1

            def reset(self, key):
                s = jax.random.normal(key, (4,))
                return s, s

            def step(self, state, action):
                return state, state, jnp.float32(jnp.nan), jnp.bool_(False)

            def behavior(self, state, obs):
                return state[:1]

        flat, spec, apply = _mlp_setup()
        cfg = EngineConfig(population_size=8, sigma=0.1, horizon=5)
        eng = ShardedESEngine(NaNEnv(), apply, spec, None, optax.adam(1e-2),
                              cfg, hyperscale_mesh(2, 4))
        s0 = eng.init_state(flat, jax.random.PRNGKey(0))
        before = np.asarray(s0.params_flat)  # host copy BEFORE donation
        s1, m = eng.generation_step(s0)
        assert int(m["n_valid"]) == 0
        assert int(np.asarray(s1.generation)) == 0  # NOT incremented
        np.testing.assert_array_equal(np.asarray(s1.params_flat), before)


# ---------------------------------------------------------------------
# THE memory acceptance: replicated footprint > per-device budget,
# sharded trains under it
# ---------------------------------------------------------------------

class TestBigPolicyMemory:
    def test_big_policy_trains_under_replicated_bound(self, devices8):
        """A ~900k-param policy (replicated state: params + adam moments
        on EVERY device) trains ≥3 generations on the sharded path with
        per-device peak bytes — XLA's memory_analysis of the compiled
        donated program, via the compile ledger — UNDER the replicated
        program's per-device peak (the 'replicated bound')."""
        from estorch_tpu.obs.profile.costmodel import compiled_cost_facts

        env = SyntheticEnv(obs_dim=376, action_dim=17)
        module = MLPPolicy(action_dim=17, hidden=(768, 768),
                           discrete=False, action_scale=1.0)
        variables = module.init(jax.random.PRNGKey(0),
                                jnp.zeros((376,), jnp.float32))
        flat, spec = make_param_spec(variables["params"])

        def apply(p, obs):
            return module.apply({"params": p}, obs)

        opt = optax.adam(1e-2)
        cfg = EngineConfig(population_size=16, sigma=0.05, horizon=20,
                           eval_chunk=8, grad_chunk=8)
        eng = ShardedESEngine(env, apply, spec, None, opt, cfg,
                              hyperscale_mesh(1, 8))
        s = eng.init_state(flat, jax.random.PRNGKey(1))
        eng.compile(s)
        shard_facts = eng.memory_facts()
        for _ in range(3):
            s, m = eng.generation_step(s)
        assert bool(np.asarray(m["update_finite"]))
        assert int(np.asarray(s.generation)) == 3

        table = make_noise_table(1 << 21, seed=0)
        rep = ESEngine(env, apply, spec, table, opt, cfg, population_mesh())
        sr = rep.init_state(flat, jax.random.PRNGKey(1))
        rep_facts = compiled_cost_facts(
            rep._generation_step.lower(sr).compile())
        assert shard_facts.get("peak_bytes"), shard_facts
        assert rep_facts.get("peak_bytes"), rep_facts
        # the replicated program's per-device peak EXCEEDS the per-device
        # budget this policy's sharded run fits in
        assert shard_facts["peak_bytes"] < rep_facts["peak_bytes"], (
            shard_facts, rep_facts)
        # and the replicated STATE alone (params + adam moments, what
        # every device must hold replicated) exceeds the sharded
        # program's resident state share: dim·12 bytes vs dim·12/8 + pad
        replicated_state_bytes = 3 * spec.dim * 4
        assert replicated_state_bytes > 10_000_000  # genuinely "big"


# ---------------------------------------------------------------------
# ES-level wiring + the sharded bench row
# ---------------------------------------------------------------------

class TestShardedES:
    @pytest.fixture(scope="class")
    def es_cls_common(self):
        import optax as _optax

        from estorch_tpu import ES, JaxAgent
        from estorch_tpu.envs import Pendulum

        return dict(
            policy=MLPPolicy, agent=JaxAgent, optimizer=_optax.adam,
            population_size=16, sigma=0.05,
            policy_kwargs={"action_dim": 1, "hidden": (32, 32),
                           "discrete": False, "action_scale": 2.0},
            agent_kwargs={"env": Pendulum(), "horizon": 60},
            optimizer_kwargs={"learning_rate": 1e-2}, seed=3,
            telemetry=True,
        )

    def test_es_sharded_end_to_end(self, es_cls_common, devices8):
        from estorch_tpu import ES

        es = ES(shard_params=True, **es_cls_common)
        assert es.table is None  # program mode allocates NO noise table
        es.train(2, verbose=False)
        assert len(es.history) == 2
        r = es.history[-1]
        assert r["sigma"] == pytest.approx(0.05)
        assert r["env_steps"] == 16 * 60
        # best-member snapshot via the in-program best_theta protocol
        assert es._best_flat is not None
        assert es._best_flat.shape == (es._spec.dim,)
        # inspection APIs work off the gathered flat
        out = es.predict(np.zeros(3, np.float32))
        assert np.asarray(out).shape == (1,)
        ev = es.evaluate_policy(n_episodes=2)
        assert np.isfinite(ev["mean"])
        # manifest records the sharded config incl. serialized rules
        cfg = es.run_manifest()["config"]
        assert cfg["shard_params"] is True
        assert cfg["noise_mode"] == "program"
        assert cfg["mesh_axes"] == {"pop": 1, "model": 8}
        rebuilt = partition_rules_from_json(cfg["partition_rules"])
        assert len(rebuilt) == len(DEFAULT_PARTITION_RULES)
        # shard-aware cost model rides telemetry
        cm = es.obs.cost_model
        assert cm["noise"] == "program"
        assert cm["sharding"]["model_shards"] == 8
        assert cm["sharding"]["per_device_flops_per_env_step"] == (
            cm["flops_per_env_step"] / 8)

    @pytest.mark.slow  # two full ES builds; the non-slow e2e test above
    # already exercises the best_theta snapshot path itself
    def test_es_sharded_best_theta_matches_member_params(
            self, es_cls_common, devices8):
        """The in-program best-θ (donated path) must equal the replicated
        engine's host-side member_params reconstruction at the same
        seed/table — the two best-tracking protocols cannot drift."""
        from estorch_tpu import ES

        es_t = ES(shard_params=True, noise_mode="table", **es_cls_common)
        es_r = ES(**es_cls_common)
        es_t.train(2, verbose=False)
        es_r.train(2, verbose=False)
        assert es_t._best_flat is not None and es_r._best_flat is not None
        np.testing.assert_allclose(es_t._best_flat, es_r._best_flat,
                                   rtol=2e-4, atol=1e-5)

    def test_option_validation(self, es_cls_common, devices8):
        from estorch_tpu import ES

        with pytest.raises(ValueError, match="shard_params=True"):
            ES(**{**es_cls_common, "model_shards": 4})
        # bfloat16 is no longer refused: an f32 centre, a bf16 forward
        es = ES(shard_params=True,
                **{**es_cls_common, "compute_dtype": "bfloat16"})
        es.train(1, verbose=False)
        assert np.isfinite(np.asarray(es.state.params_flat)).all()
        assert es.state.params_flat.dtype == jnp.float32
        with pytest.raises(ValueError, match="obs_norm"):
            ES(shard_params=True, **{**es_cls_common, "obs_norm": True})

    def test_bench_sharded_row_off_chip(self, devices8):
        """The sharded bench row on the CPU mesh (an explicit request):
        FLOPs come from the shard-aware cost model, and there is no MFU —
        a utilization exists only against a chip's published peak."""
        import os
        import sys

        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        try:
            import bench
        finally:
            sys.path.pop(0)
        row = bench.measure_one(
            {"env": "synthetic", "hidden": [16, 16], "population": 16,
             "horizon": 20, "gens": 1, "eval_chunk": 8, "shard": True,
             "telemetry": True}, force_cpu=True)
        assert row["mfu"] is None and row["mfu_basis"] is None
        assert row["platform"] == "cpu"
        assert row["dtype"] == "float32"
        shard = row["shard"]
        assert shard["mfu_from_cost_model"] is True
        assert shard["noise_mode"] == "program"
        assert shard["per_device_peak_bytes"]


class TestOneCompilePerProgram:
    """A state built on the host enters the mesh programs in the layout
    they return it in, so generation 1 reuses generation 0's executable:
    after generation 0 NOTHING is built (programs acquired == 0, cache
    hits included), on the replicated engine, the sharded engine, the
    novelty family's split path, and after a checkpoint restore."""

    @staticmethod
    def _common():
        import optax as _optax

        from estorch_tpu import JaxAgent
        from estorch_tpu.envs import CartPole

        return dict(
            policy=MLPPolicy, agent=JaxAgent, optimizer=_optax.adam,
            population_size=16, sigma=0.1, seed=0,
            policy_kwargs={"action_dim": 2, "hidden": (8,)},
            agent_kwargs={"env": CartPole(), "horizon": 10},
            optimizer_kwargs={"learning_rate": 1e-2}, table_size=1 << 14,
            telemetry=False,
        )

    @staticmethod
    def _programs_per_generation(es, gens=3):
        from estorch_tpu.utils.backend import (compile_event_counts,
                                               install_compile_event_counters)

        install_compile_event_counters()
        out = []
        for _ in range(gens):
            before = compile_event_counts()["programs"]
            es.train(1, verbose=False)
            out.append(compile_event_counts()["programs"] - before)
        return out

    def test_meshes_have_auto_axes(self, devices8):
        from jax.sharding import AxisType

        from estorch_tpu.parallel.mesh import (hyperscale_mesh,
                                               population_mesh,
                                               single_device_mesh)

        for mesh in (population_mesh(), single_device_mesh(),
                     hyperscale_mesh(2, 4)):
            assert all(t == AxisType.Auto for t in mesh.axis_types), mesh

    def test_replicated_and_sharded_build_nothing_after_generation_0(
            self, devices8):
        from estorch_tpu import ES

        for kw in ({"obs_norm": True, "low_rank": 1},
                   {"shard_params": True, "model_shards": 2}):
            es = ES(**{**self._common(), **kw})
            built = self._programs_per_generation(es)
            assert built[0] >= 1 and built[1:] == [0, 0], (kw, built)

    def test_split_path_and_restore_keep_the_layout(self, devices8,
                                                    tmp_path):
        from estorch_tpu import ES, NSR_ES
        from estorch_tpu.utils import restore_checkpoint, save_checkpoint

        ns = NSR_ES(meta_population_size=2, k=3, **self._common())
        built = self._programs_per_generation(ns)
        assert built[1:] == [0, 0], built

        es = ES(**{**self._common(), "obs_norm": True})
        es.train(1, verbose=False)
        save_checkpoint(es, str(tmp_path / "ck"))
        restore_checkpoint(es, str(tmp_path / "ck"))
        assert self._programs_per_generation(es, gens=2) == [0, 0]


class TestResilienceWithDonation:
    def test_run_resilient_rollback_survives_donated_state(self, devices8):
        """run_resilient's snapshot must deep-copy a SHARDED state: the
        donated generation deletes the live buffers, so a by-reference
        snapshot restores corpses ('buffer has been deleted or donated').
        A one-shot failure injected mid-train must roll back, re-run, and
        end bit-identical to the same run without the fault."""
        import optax as _optax

        from estorch_tpu import ES, JaxAgent
        from estorch_tpu.envs import Pendulum
        from estorch_tpu.resilience import run_resilient

        def build():
            return ES(
                policy=MLPPolicy, agent=JaxAgent, optimizer=_optax.adam,
                population_size=8, sigma=0.05,
                policy_kwargs={"action_dim": 1, "hidden": (16,),
                               "discrete": False, "action_scale": 2.0},
                agent_kwargs={"env": Pendulum(), "horizon": 30},
                optimizer_kwargs={"learning_rate": 1e-2}, seed=2,
                shard_params=True)

        es = build()
        fired = []

        def boom_once(record):
            if record["generation"] == 1 and not fired:
                fired.append(True)
                raise RuntimeError("injected post-generation fault")

        run_resilient(es, 3, log_fn=boom_once, verbose=False)
        assert fired, "fault never injected"
        assert es.generation == 3
        clean = build()
        clean.train(3, verbose=False)
        np.testing.assert_array_equal(
            np.asarray(es.state.params_flat),
            np.asarray(clean.state.params_flat))


# ---------------------------------------------------------------------
# the non-materialising form: a sequence model, low-rank rows from the
# table, factors batched over pairs, the centre un-batched (ISSUE 26)
# ---------------------------------------------------------------------

def _lm_es(devices, model_shards, **over):
    import lm_tiny
    from estorch_tpu import ES, JaxAgent
    from estorch_tpu.envs import TokenScoreEnv
    from estorch_tpu.models import HybridLM

    kw = dict(
        policy=HybridLM, agent=JaxAgent, optimizer=optax.adam,
        population_size=8, sigma=0.02, policy_kwargs=lm_tiny.TINY,
        agent_kwargs={"env": TokenScoreEnv(**lm_tiny.ENV)},
        optimizer_kwargs={"learning_rate": 1e-2}, shard_params=True,
        model_shards=model_shards, low_rank=1, noise_mode="table",
        table_size=1 << 18, device=list(devices))
    kw.update(over)
    return ES(**kw)


class TestCentreFormRule:
    """``centre_form_why``: the layout of the centre the perturbed form's
    forward reads, from what the engine observes."""

    @pytest.mark.parametrize(
        "form, model_shards, centre, held, chip, want, reason", [
            ("perturbed", 2, 1_900, 9_800, 16_000, "gathered",
             "fits a chip of 16000 bytes beside the 9800"),
            ("perturbed", 2, 1_900, 14_200, 16_000, "split",
             "1900 bytes, does not fit a chip of 16000 bytes beside the "
             "14200"),
            ("perturbed", 1, 1_900, 9_800, 16_000, "split",
             "model axis is 1"),
            ("materialised", 2, 1_900, 9_800, 16_000, "split",
             "the materialised form builds members' weights"),
            ("perturbed", 4, 10, 90, 100, "gathered", "10 bytes, fits"),
            ("perturbed", 4, 11, 90, 100, "split", "11 bytes, does not fit"),
        ], ids=["fits", "does-not-fit", "model-axis-1", "materialised",
                "to-the-byte", "a-byte-over"])
    def test_the_rule(self, form, model_shards, centre, held, chip, want,
                      reason):
        from estorch_tpu.parallel.sharded import centre_form_why

        got, why = centre_form_why(form, model_shards, centre, held, chip)
        assert got == want
        assert reason in why

    def test_what_the_engine_hands_the_rule(self, devices8, monkeypatch):
        """The whole centre in its compute dtypes against a chip's share of
        the float32 state (params and Adam's two moments), two best members
        and the activation budget."""
        from estorch_tpu.parallel import sharded

        es = _lm_es(devices8[:4], 2, compute_dtype="bfloat16")
        eng = es.engine
        assert eng.centre_form == "gathered"
        assert eng.centre_bytes == 2 * es._spec.dim
        assert eng.centre_bytes_per_chip == eng.centre_bytes
        held = 5 * eng.param_bytes_per_chip + 4  # Adam's count: one int32
        assert f"{eng.centre_bytes} bytes, fits" in eng.centre_form_why
        assert (f"beside the {held + sharded.ACTIVATION_BUDGET_BYTES} it "
                "holds") in eng.centre_form_why
        # one byte short of all of it: split, with the same numbers
        monkeypatch.setattr(
            sharded, "CHIP_MEMORY_BYTES",
            eng.centre_bytes + held + sharded.ACTIVATION_BUDGET_BYTES - 1)
        tight = _lm_es(devices8[:4], 2, compute_dtype="bfloat16").engine
        assert tight.centre_form == "split"
        assert "does not fit" in tight.centre_form_why
        assert tight.centre_bytes_per_chip < tight.centre_bytes
        # the materialised form has no centre to gather
        plain = _lm_es(devices8[:4], 2, low_rank=0, noise_mode="program")
        assert plain.engine.forward_form == "materialised"
        assert plain.engine.centre_form == "split"
        assert "materialised" in plain.engine.centre_form_why


class TestPerturbedForm:
    @pytest.fixture(scope="class")
    def one_device(self, devices8):
        es = _lm_es(devices8[:1], 1)
        offsets = np.asarray(es.engine.all_pair_offsets(es.state))
        es.train(2, verbose=False)
        return dict(fitness=[r["reward_mean"] for r in es.history],
                    params=np.asarray(es.state.params_flat), offsets=offsets)

    @pytest.mark.parametrize("pop, model", [(2, 2), (1, 4), (4, 1)])
    def test_mesh_shapes_match_one_device(self, one_device, devices8, pop,
                                          model, centre_form):
        """Fitness and updated parameters, allclose at f32: GSPMD's
        all-reduces reassociate float32 sums, nothing else differs (and in
        the gathered form only the update's)."""
        es = _lm_es(devices8[:4], model)
        assert es.engine.forward_form == "perturbed"
        # nothing to gather on a model axis of 1
        want_form = centre_form if model > 1 else "split"
        assert es.engine.centre_form == want_form
        assert (es.engine.pop_shards, es.engine.model_shards) == (pop, model)
        np.testing.assert_array_equal(
            es.engine.all_pair_offsets(es.state), one_device["offsets"])
        es.train(2, verbose=False)
        np.testing.assert_allclose(
            [r["reward_mean"] for r in es.history], one_device["fitness"],
            rtol=2e-6)
        np.testing.assert_allclose(np.asarray(es.state.params_flat),
                                   one_device["params"], atol=1e-5, rtol=0)
        cfg = es.run_manifest()["config"]
        assert cfg["forward_form"] == "perturbed"
        gauges = es.obs.counters
        assert gauges.get("forward_form") == "perturbed"
        assert gauges.get("mesh_shape") == f"{pop}x{model}"
        assert gauges.get("noise_rows_per_generation") == 4
        assert gauges.get("tokens_per_generation") == 8 * 21
        assert 0 < gauges.get("param_bytes_per_chip") <= 4 * es._spec.dim
        # which layout ran, why, and the centre's bytes a chip
        assert cfg["centre_form"] == gauges.get("centre_form") == want_form
        assert (cfg["centre_form_why"] == gauges.get("centre_form_why")
                == es.engine.centre_form_why)
        whole = 4 * es._spec.dim
        assert (cfg["centre_bytes_per_chip"]
                == gauges.get("centre_bytes_per_chip")
                == es.engine.centre_bytes_per_chip)
        if want_form == "gathered" or model == 1:
            assert cfg["centre_bytes_per_chip"] == whole
        else:
            assert whole / model <= cfg["centre_bytes_per_chip"] < whole
        assert es.engine.sharding_report()["centre_form"].startswith(
            f"{want_form}: ")

    def test_bfloat16_forward_over_a_float32_centre(self, one_device,
                                                    devices8, centre_form):
        es = _lm_es(devices8[:4], 2, compute_dtype="bfloat16")
        assert es.engine.centre_form == centre_form
        es.train(2, verbose=False)
        params = np.asarray(es.state.params_flat)
        assert params.dtype == np.float32 and np.isfinite(params).all()
        # Adam's steps are +-lr wherever the ranks agree; bf16 may swap
        # the ranks of near-equal members, so "close" is a few steps
        assert np.abs(params - one_device["params"]).max() <= 4 * 1e-2
        np.testing.assert_allclose(
            [r["reward_mean"] for r in es.history], one_device["fitness"],
            rtol=1e-3)
        assert all(leaf.dtype == jnp.float32 for leaf in
                   jax.tree_util.tree_leaves(es.state.opt_state)
                   if jnp.issubdtype(leaf.dtype, jnp.floating))

    def test_no_member_weights_and_no_whole_tree_gather(self, devices8,
                                                        centre_form):
        """The lowered program holds no ``[members | pairs, m, n]`` array
        for any factored leaf.  ``split``: the compiled one gathers no whole
        leaf.  ``gathered``: it gathers every factored leaf the rules split,
        once and in the compute dtype, and moves NO activation (an array
        with the sequence's positions in it) between chips."""
        import lm_tiny

        es = _lm_es(devices8[:4], 2, **(
            {"compute_dtype": "bfloat16"} if centre_form == "gathered"
            else {}))
        eng = es.engine
        assert eng.centre_form == centre_form
        lowered = eng._generation_step.lower(es.state, eng.table.data)
        text = lowered.as_text()
        batch = {eng.pair_chunk, eng.eval_chunk, eng.rows_padded,
                 eng.members_padded, 2}
        for _, m, n, _, _ in eng.lr_spec.lr_leaves:
            for lead in batch:
                for dims in (f"{lead}x{m}x{n}x", f"{lead}x2x{m}x{n}x"):
                    assert f"tensor<{dims}" not in text, dims
        moved = collectives(lowered.compile().as_text())
        whole = {tuple(s) for s in eng.leaf_shapes if len(s) == 2}
        gathered = [(dtype, shape) for kind, dtype, shape in moved
                    if kind == "all-gather"]
        if centre_form == "split":
            for _, shape in gathered:
                assert (shape not in whole
                        or shape[0] * shape[1] <= 4096), shape
            return
        # every leaf is cast to the compute dtype FIRST and constrained whole
        # after (XLA:CPU widens bfloat16 collectives, so the dtype a gather
        # carries is read off the TPU's compiler: test_trace_stages.py)
        import re

        whole_bf16 = re.findall(
            r"sdy\.sharding_constraint %\d+ <@mesh, \[(?:\{\}(?:, )?)+\]> : "
            r"tensor<([\dx]+)xbf16>", text)
        assert sorted(whole_bf16) == sorted(
            "x".join(str(d) for d in shape) for shape in eng.leaf_shapes)
        # no collective of any kind carries the sequence's positions
        positions = lm_tiny.ENV["seq_len"]
        assert positions not in {m for _, m, n, _, _ in eng.lr_spec.lr_leaves}
        carried = [(kind, shape) for kind, _, shape in moved
                   if positions in shape or positions - 1 in shape]
        assert not carried, carried

    @pytest.mark.parametrize("population", [6, 10])
    def test_ghost_rows_where_pairs_do_not_divide_the_devices(
            self, devices8, population):
        """3 and 5 pairs on four chips, the centre gathered: the rows are
        padded to the DEVICES (4 and 8), ghost rows repeat the last pair
        and weigh nothing, so the run is the one-device run."""
        one = _lm_es(devices8[:1], 1, population_size=population)
        es = _lm_es(devices8[:4], 2, population_size=population)
        eng = es.engine
        assert eng.centre_form == "gathered"
        pairs = population // 2
        assert eng.rows_global == pairs
        assert eng.rows_padded == -(-pairs // 4) * 4
        assert eng.pair_chunk % 4 == 0
        assert eng.members_padded == 2 * eng.rows_padded
        one.train(2, verbose=False)
        es.train(2, verbose=False)
        np.testing.assert_allclose(
            [r["reward_mean"] for r in es.history],
            [r["reward_mean"] for r in one.history], rtol=2e-6)
        np.testing.assert_allclose(np.asarray(es.state.params_flat),
                                   np.asarray(one.state.params_flat),
                                   atol=1e-5, rtol=0)

    def test_chunks_are_whole_rounds_of_the_devices(self, devices8,
                                                    monkeypatch):
        """Gathered: a member's activations are whole on its chip (no
        division by ``model``) and a chunk is a multiple of the devices."""
        from estorch_tpu.parallel import sharded

        gathered = _lm_es(devices8[:4], 2, population_size=16).engine
        monkeypatch.setattr(sharded, "CHIP_MEMORY_BYTES", 0)
        split = _lm_es(devices8[:4], 2, population_size=16).engine
        assert (gathered.centre_form, split.centre_form) == (
            "gathered", "split")
        assert gathered._widest_activation() > split._widest_activation()
        one = _lm_es(devices8[:1], 1, population_size=16).engine
        assert gathered._widest_activation() == one._widest_activation()
        # a budget of one pair's widest activations: a pair a chip a chunk
        monkeypatch.setattr(sharded, "ACTIVATION_BUDGET_BYTES",
                            2 * 4 * gathered._widest_activation())
        monkeypatch.setattr(sharded, "CHIP_MEMORY_BYTES", 16 * 10**9)
        tight = _lm_es(devices8[:4], 2, population_size=16).engine
        assert tight.centre_form == "gathered"
        assert (tight.pair_chunk, tight.n_pair_chunks, tight.eval_chunk) == (
            4, 2, 8)

    def test_a_one_by_one_mesh_traces_one_program(self, devices8,
                                                  monkeypatch):
        """Nothing to gather on one device: whatever the rule reads of the
        chip's memory, the lowered program is the same text."""
        def lowered():
            es = _lm_es(devices8[:1], 1, compute_dtype="bfloat16")
            assert es.engine.centre_form == "split"
            assert "model axis is 1" in es.engine.centre_form_why
            return es.engine._generation_step.lower(
                es.state, es.engine.table.data).as_text()

        from estorch_tpu.parallel import sharded

        roomy = lowered()
        monkeypatch.setattr(sharded, "CHIP_MEMORY_BYTES", 0)
        assert lowered() == roomy

    def test_best_member_stays_sharded_until_it_is_read(self, devices8):
        es = _lm_es(devices8[:4], 2)
        es.train(1, verbose=False)
        held = es._best
        assert not isinstance(held, np.ndarray)        # the sharded tree
        leaf = jax.tree_util.tree_leaves(held)[0]
        assert len(leaf.sharding.device_set) == 4
        flat = es._best_flat                            # read: gathered now
        assert isinstance(flat, np.ndarray) and flat.shape == (es._spec.dim,)
        assert isinstance(es._best, np.ndarray)

    def test_member_params_match_the_emitted_best_member(self, devices8,
                                                         centre_form):
        es = _lm_es(devices8[:4], 2)
        assert es.engine.centre_form == centre_form
        state0_params = np.asarray(es.state.params_flat)
        want = {i: np.asarray(es.engine.member_params(es.state, i))
                for i in range(8)}
        assert np.abs(want[0] - state0_params).max() > 0
        # members 2k and 2k+1 mirror each other around the centre
        np.testing.assert_allclose(want[2] + want[3], 2 * state0_params,
                                   atol=1e-6)
        es.train(1, verbose=False)
        got = es._best_flat
        assert min(np.abs(got - w).max() for w in want.values()) < 1e-6

    def test_low_rank_table_rows_need_a_perturbed_forward(self, setup,
                                                          devices8):
        mesh = hyperscale_mesh(2, 2, devices8[:4])
        cfg = EngineConfig(population_size=8, sigma=0.1, horizon=5,
                           low_rank=1)
        with pytest.raises(ValueError, match="perturbed forward"):
            _sharded(setup, mesh, noise_mode="table", cfg=cfg)

    def test_mlp_takes_the_perturbed_form_too(self, devices8):
        """Any policy with a perturbed forward: the MLP's is
        mlp_lowrank_apply on the same primitive."""
        from estorch_tpu import ES, JaxAgent

        es = ES(policy=MLPPolicy, agent=JaxAgent, optimizer=optax.adam,
                population_size=16, sigma=0.05,
                policy_kwargs={"action_dim": 2, "hidden": (16,)},
                agent_kwargs={"env": CartPole(), "horizon": 20},
                optimizer_kwargs={"learning_rate": 1e-2}, shard_params=True,
                model_shards=2, low_rank=1, noise_mode="table",
                table_size=1 << 16, device=list(devices8[:4]))
        assert es.engine.forward_form == "perturbed"
        es.train(2, verbose=False)
        assert np.isfinite(np.asarray(es.state.params_flat)).all()
        assert es.history[-1]["env_steps"] > 0


class TestCatchAllIsReported:
    def test_unmatched_leaves_are_named_with_their_bytes(self, devices8,
                                                         caplog):
        import logging

        from estorch_tpu.parallel.mesh import unmatched_leaves

        mesh = hyperscale_mesh(2, 2, devices8[:4])
        tree = {"dense_0": {"kernel": jnp.zeros((4, 8)), "bias": jnp.zeros(8)},
                "mixer": {"strange_matrix": jnp.zeros((512, 1024))},
                "scalar": jnp.zeros(())}
        assert unmatched_leaves(DEFAULT_PARTITION_RULES, tree) == {
            "mixer/strange_matrix": 512 * 1024 * 4}
        with caplog.at_level(logging.WARNING, "estorch_tpu.parallel.mesh"):
            sh = match_partition_rules(DEFAULT_PARTITION_RULES, tree, mesh)
        assert all(a is None for a in sh["mixer"]["strange_matrix"].spec)
        hits = [r for r in caplog.records if "catch-all" in r.getMessage()]
        assert len(hits) == 1
        assert "mixer/strange_matrix (2097152 B)" in hits[0].getMessage()
        report = sharding_summary(tree, sh, DEFAULT_PARTITION_RULES)
        assert "catch-all" in report["mixer/strange_matrix"]
        assert "2097152 bytes" in report["mixer/strange_matrix"]
        assert "catch-all" not in report["dense_0/kernel"]
        caplog.clear()
        with caplog.at_level(logging.WARNING, "estorch_tpu.parallel.mesh"):
            match_partition_rules(DEFAULT_PARTITION_RULES,
                                  {"dense_0": tree["dense_0"]}, mesh)
        assert not caplog.records
