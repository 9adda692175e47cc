"""The compressed-latent-attention cell end to end on ONE virtual CPU device
at the configuration's rehearsal size (8 query and 2 key-value heads of 16,
2 of 4 experts held, a router 16 wide, 128 positions, float32).  Not a chip
number: ``--rehearse`` is the only way past the TPU check, and it prints every
metric as ``rehearsal.<name>``.

The cell is added by files alone (configuration, reference, reader and costs
are new files; runner, traffic and ``run.py`` are untouched), comes out
``correct`` at three seeds, and comes out NOT ``correct`` in these
rehearsals: the reference given another seed, every projection's, the
head-mixing convolution's and every expert's input rounded to fp8, the value
shift left out, the q-k mean left out, the routing weight renormalised to 1,
the whole head rotated, the held experts taken for another rank's.  (``γ =
0`` comes out not ``correct`` at two rehearsal seeds in three: over two tiny
layers the state from below moves few routes.  The tier-1 test of the router
with and without the state from below holds it, and on the chip every member
of ``cca_tolerance.py``'s sixteen is over the behaviour limit sixfold.)"""

import json
import os

import pytest
from test_cells import (ROOT, cache, copy_of_the_benchmark,  # noqa: F401
                        result_of, run_cell)

CELL = "zaya1-es-8k-1chip"
CONFIG = "zaya1-8b-ep2"
SOURCE = "https://huggingface.co/Zyphra/ZAYA1-8B/blob/main/config.json"
HERE = os.path.dirname(os.path.abspath(__file__))
ARGS = ("--workload", CELL, "--seconds", "2", "--rehearse")
CCA_METRICS = [
    "cca.dense_share", "cca.mix_share", "cca.attn_share", "cca.rope_share",
    "cca.route_share", "cca.dispatch_share", "cca.expert_share",
    "cca.head_share", "cca.dense_flops_util", "cca.expert_flops_util",
    "cca.attn_flops_util", "cca.head_flops_util", "cca.mix_hbm_util"]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(ROOT, "benchmark/configs", CONFIG + ".json")) as f:
        return json.load(f)


def _catalog():
    """The catalog's row of this model, where the guides are installed."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        return None
    with open(path) as f:
        rows = [json.loads(line) for line in f]
    return next(r for r in rows if r["name"] == "ZAYA1-8B")


def test_the_cell_is_added_by_files_alone():
    """Names and lists, not positions among the entries (PERF.md §7, PR 39
    (h)): a later PR appends after these."""
    bench = _bench()
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert cell[0]["traffic"] == "train-lm" and cell[0]["config"] == CONFIG
    assert len(cell[0]["why"]) <= 200
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    config = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(config) == 1 and config[0]["source"] == SOURCE
    assert config[0]["reduced"] == ["num_hidden_layers", "num_experts",
                                    "vocab_size"]
    assert len(config[0]["why"]) <= 200
    assert [w["config"] for w in bench["workloads"]].count(CONFIG) == 1
    for path in (config[0]["file"], "benchmark/reference/cca_moe_lm.py",
                 "benchmark/layers/cca.py", "benchmark/costs_cca.py"):
        assert os.path.exists(os.path.join(ROOT, path)), path


def test_the_cca_metrics_name_this_cell_and_only_it():
    bench = _bench()
    ours = {m["name"]: m for m in bench["per_layer"]
            if m["name"].startswith("cca.")}
    assert sorted(ours) == sorted(CCA_METRICS)
    for m in ours.values():
        assert m["workloads"] == [CELL]
        assert m["moves"] == "steps_per_s_per_chip"
        assert m["layer"] == "policy forward" and m["unit"] == "share"
        assert m["source"] == "device_trace"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for name in CCA_METRICS:
        assert ours[name]["better"] == (
            "higher" if name.endswith("_util") else "lower")
    others = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", []) and m["name"] not in ours]
    assert others == []


def test_the_configuration_file_keeps_every_published_key():
    """Every key of the catalog's ``config`` at its published value but the
    three under ``reduced``; nested groups whole."""
    config = _config()
    published = dict(
        attention_bias=False, cca_time0=2, cca_time1=2, head_dim=128,
        hidden_act="silu", hidden_size=2048, layer_types=["hybrid"] * 40,
        lm_head_bias=False, max_position_embeddings=131072,
        model_type="zaya", moe_intermediate_size=2048, num_attention_heads=8,
        num_experts_per_tok=1, num_key_value_heads=2,
        partial_rotary_factor=0.5, rms_norm_eps=1e-05,
        rope_parameters={
            "hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                       "rope_type": "default"},
            "hybrid_sliding": {"partial_rotary_factor": 0.5,
                               "rope_theta": 10000, "rope_type": "default"},
            "rope_type": "default"},
        router_hidden_size=256, sliding_window=None,
        tie_word_embeddings=True)
    for key, value in published.items():
        assert config[key] == value, key
    row = _catalog()
    if row is not None:
        assert config["source"] == row["source_url"] == SOURCE
        assert set(row["config"]) - set(config["reduced"]) == set(published)
        for key, value in row["config"].items():
            if key not in config["reduced"]:
                assert config[key] == value, key
        for key in config["reduced"]:
            assert config["published"][key] == row["config"][key], key
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"]["num_hidden_layers"] == 40
    assert config["published"]["num_experts"] == 16
    assert config["published"]["vocab_size"] == 262272
    assert (config["num_hidden_layers"], config["vocab_size"]) in (
        (5, 32784), (4, 32784))
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    held = config["num_experts"]
    assert held == 8 and held * config["expert_group_size"] == 16
    assert config["deployment"]["expert_parallel_group"] == 2
    kwargs = config["build"]["kwargs"]
    policy = kwargs["policy_kwargs"]
    assert policy["layer_types"] == ["hybrid"] * config["num_hidden_layers"]
    for key in published:
        if key in policy and key != "layer_types":
            assert policy[key] == published[key], key
    assert policy["rope_theta"] == published["rope_parameters"]["hybrid"][
        "rope_theta"]
    assert (kwargs["model_shards"], kwargs["low_rank"], config["eval_chunk"],
            config["population_size"], config["horizon"]) == (
        1, 1, 0, 8, 8192)
    assert config["deployment"]["mesh"] == {"pop": 1, "model": 1}
    assert config["deployment"]["state_bytes_per_chip"] in (
        14 * 601_662_890, 14 * 494_759_048)
    assert {"assumed", "departures", "reference_tolerance", "deployment",
            "seeded_std", "seeded_scale", "seeded_orthogonal"} <= set(config)
    for said in ("order inside cca", "convolutions", "q-k mean",
                 "value shift", "scores", "rotation", "router", "experts",
                 "head", "initialisation", "sigma, optimizer", "low_rank",
                 "population_size", "corpus_seed and table_seed"):
        assert said in config["assumed"], said
    assert any("residual-scaled MoD" in d for d in config["departures"])
    tiny = config["rehearsal_kwargs"]["policy_kwargs"]
    assert (tiny["num_attention_heads"], tiny["num_key_value_heads"],
            tiny["head_dim"], tiny["num_experts"], tiny["expert_group_size"],
            tiny["router_hidden_size"]) == (8, 2, 16, 2, 2, 16)
    assert config["rehearsal_kwargs"]["compute_dtype"] == "float32"
    tol = config["reference_tolerance"]
    assert 0 < tol["rtol"] < 0.05 and 0 < tol["behaviour_atol"] < 0.5
    assert "fp8" in tol["why"]


def test_the_reader_finds_nothing_in_a_program_without_the_scope():
    """``layers/cca.py`` on a run that took no trace, one whose program names
    no stage, and ones of the other five sequence models (no ``es.mix``):
    nothing, no raise.  On this model's program: the thirteen metrics from
    the exact counts."""
    from benchmark.files import load_file_module

    reader = load_file_module(os.path.join(ROOT, "benchmark/layers/cca.py"))

    def run(stage_s, ops=None):
        staged = {"busiest": "d0", "devices": {"d0": {
            "stage_s": stage_s, "busy_s": sum(stage_s.values()) or 1.0,
            "ops": ops or {}}}}
        return {"stage_reduce": {"staged": staged}, "chips": 1,
                "steps_per_generation": 65536, "traced_generations": 1,
                "dense_flops_per_member_step": 52_428_800,
                "head_flops_per_member_step": 134_283_264,
                "peaks": {"peak_flops_per_s": 197e12,
                          "peak_hbm_bytes_per_s": 819e9}}

    assert reader.read({"stage_reduce": None}) == {}
    assert reader.read({"trace": None}) == {}
    assert reader.read(run({"unscoped": 0.2})) == {}
    assert reader.read(run({"policy": 0.1, "dense": 0.5, "attn": 0.1,
                            "ssm": 0.2, "head": 0.1})) == {}
    assert reader.read(run({"dense": 1.2, "attn": 0.7, "head": 0.3,
                            "rope": 0.1, "exit": 0.01})) == {}
    assert reader.read(run({"dense": 0.2, "attn": 0.5, "route": 0.05,
                            "dispatch": 0.06, "expert": 0.04})) == {}
    assert reader.read(run({"dense": 0.7, "attn": 0.4, "ssm": 0.12,
                            "gmu": 0.004, "diff": 0.01, "head": 0.06})) == {}
    assert reader.read(run({"dense": 0.1, "index": 0.5, "select": 0.8,
                            "attn": 0.6, "route": 0.01})) == {}
    stage_s = {"dense": 0.021, "mix": 0.038, "attn": 0.048, "rope": 0.005,
               "route": 0.021, "dispatch": 0.052, "expert": 0.001,
               "head": 0.054, "perturb": 0.079, "policy": 0.02,
               "update": 0.034, "unscoped": 0.05}
    ops = {"unscoped": {"ragged-dot-none.1": [0.045, 0, 0, ""],
                        "copy.3": [0.005, 0, 0, ""]}}
    got = reader.read(run(stage_s, ops))
    assert sorted(got) == sorted(CCA_METRICS)
    busy = sum(stage_s.values())
    assert abs(got["cca.mix_share"] - 0.038 / busy) < 1e-12
    assert abs(got["cca.route_share"] - 0.021 / busy) < 1e-12
    assert abs(got["cca.expert_share"] - 0.046 / busy) < 1e-12
    want = 52_428_800 * 65536 / 0.021 / 197e12
    assert abs(got["cca.dense_flops_util"] - want) < 1e-12 and want < 1.0
    want = 134_283_264 * 65536 / 0.054 / 197e12
    assert abs(got["cca.head_flops_util"] - want) < 1e-12 and want < 1.0
    config = _config()
    length, layers = config["horizon"], config["num_hidden_layers"]
    members = 65536 // length
    want = (length * (length + 1) // 2) * 4096 * layers * members / 0.048 / 197e12
    assert abs(got["cca.attn_flops_util"] - want) < 1e-12 and want < 1.0
    want = (65536 * layers * 0.5 * 2 * 3 * 2048 * 2048) / 0.046 / 197e12
    assert abs(got["cca.expert_flops_util"] - want) < 1e-12 and want < 1.0
    want = (2 * length * (10 + 12) * 128) * layers * members / 0.038 / 819e9
    assert abs(got["cca.mix_hbm_util"] - want) < 1e-12 and want < 1.0
    # without peaks: the shares alone
    run_without = run(stage_s, ops)
    run_without["peaks"] = None
    assert sorted(reader.read(run_without)) == sorted(CCA_METRICS[:8])


def test_the_costs_are_from_shapes():
    from benchmark import costs_cca

    assert costs_cca.causal_pairs(8192) == 33_558_528
    assert costs_cca.causal_pairs(3) == 6
    assert costs_cca.attention_flops_per_pair(8, 128) == 4096
    # q~ and k~ read (10 heads), q^, k^ and v written (12), bfloat16
    assert costs_cca.mix_bytes(8192, 8, 2, 128) == 46_137_344
    assert costs_cca.mix_bytes(1, 8, 2, 128, operand_bytes=4) == 4 * 22 * 128


@pytest.mark.parametrize("seed", ["3300000019", "7", "12"])
def test_the_cell_is_correct_on_one_virtual_device(cache, seed):
    trace = "1" if seed == "3300000019" else "0"
    p, lines = run_cell(cache, *ARGS, "--seed", seed, "--trace", trace)
    out = result_of(p, lines)
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["count"] == 1 and out["attempted"] >= 2
    got = out["metrics"]
    if trace == "0":
        assert set(got) == {"rehearsal.steps_per_s_per_chip",
                            "rehearsal.setup_s"}
        return
    assert got["rehearsal.rollout.alive_share"]["value"] == 1.0
    assert got["rehearsal.compile.programs_in_window"]["value"] == 0
    # a CPU trace has no device operation: the trace's readers (cca.*,
    # stage.*) find nothing and their metrics are left out
    assert not any(name.startswith(("rehearsal.cca.", "rehearsal.dsa.",
                                    "rehearsal.moe.")) for name in got)
    gauges = [ln for ln in lines if "gauges:" in ln]
    assert gauges and "'forward_form': 'perturbed'" in gauges[0]
    assert "'mesh_shape': '1x1'" in gauges[0]


def _with_policy(tmp_path, policy):
    bench = copy_of_the_benchmark(tmp_path)
    path = tmp_path / f"benchmark/configs/{CONFIG}.json"
    with open(path) as f:
        config = json.load(f)
    config["build"]["kwargs"]["policy"] = {"$import": policy}
    with open(path, "w") as f:
        json.dump(config, f)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)


def test_a_program_without_the_model_leaves_before_the_chips(cache, tmp_path):
    """What the parent commit does with this cell: the configuration names
    a module the program does not have, so the run ends non-zero with no
    result line, and says what is absent, before jax is asked for a
    device."""
    _with_policy(tmp_path, "estorch_tpu.models.NoSuchCCAMoELM")
    p, lines = run_cell(cache, *ARGS, "--seed", "1", "--trace", "1",
                        root=str(tmp_path), extra_env={"PYTHONPATH": ROOT})
    assert p.returncode != 0 and not any(ln.startswith("{") for ln in lines)
    assert "cannot run zaya1-8b-ep2" in p.stderr
    assert "has no estorch_tpu.models.NoSuchCCAMoELM" in p.stderr
    assert not any("device bring-up" in ln for ln in lines)


def test_a_reference_with_another_seed_is_not_correct(cache):
    out = result_of(*run_cell(cache, *ARGS, "--seed", "3", "--trace", "0",
                              "--reference-seed", "4"))
    assert out["correct"] is False


@pytest.mark.parametrize("policy", [
    "coarse_cca.Fp8Cca", "coarse_cca.NoValueShiftCca", "coarse_cca.NoMeanCca",
    "coarse_cca.RenormalisedCca", "coarse_cca.WholeRotationCca",
    "coarse_cca.OtherRankCca"])
def test_a_degraded_forward_is_not_correct(cache, tmp_path, policy):
    """The same configuration with every projection's, the head-mixing
    convolution's and every expert's input rounded to fp8, the value shift
    or the q-k mean left out, the routing weight renormalised, the whole
    head rotated or the held experts of another rank, against the same plain
    reference: ``correct`` comes out false."""
    _with_policy(tmp_path, policy)
    p, lines = run_cell(
        cache, *ARGS, "--seed", "3", "--trace", "0", root=str(tmp_path),
        extra_env={"PYTHONPATH": ROOT + os.pathsep + HERE})
    assert result_of(p, lines)["correct"] is False
    assert any("reference, the measured program" in ln and "MISMATCH" in ln
               for ln in lines)
