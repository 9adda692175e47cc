"""The sparse-expert cell end to end on ONE virtual CPU device at the
configuration's rehearsal size (tiny widths).  Not a chip number:
``--rehearse`` is the only way past the TPU check, and it prints every
metric as ``rehearsal.<name>``.

The cell is added by files alone (configuration, reference, reader and costs
are new files; runner, traffic and ``run.py`` are untouched), comes out
``correct`` at three seeds, and comes out NOT ``correct`` in these
rehearsals: the reference given another seed, every projection's and
expert's input rounded to fp8, one stacked expert leaf's rank-r correction
dropped, the MTP term left out.  Two degraded forms the limits do NOT
separate at this size are held by tier-1 tests instead and say so below: a
bfloat16 router and the selection bias in the weights."""

import json
import os

import pytest
from test_cells import (ROOT, cache, copy_of_the_benchmark,  # noqa: F401
                        result_of, run_cell)

CELL = "joyai-flash-es-4k-1chip"
CONFIG = "joyai-llm-flash-5layers"
SOURCE = ("https://huggingface.co/jdopensource/JoyAI-LLM-Flash/blob/main/"
          "config.json")
HERE = os.path.dirname(os.path.abspath(__file__))
ARGS = ("--workload", CELL, "--seconds", "2", "--rehearse")
MOE_METRICS = ["moe.route_share", "moe.dispatch_share", "moe.expert_share",
               "moe.attn_share", "moe.dense_share", "moe.rope_share",
               "moe.head_share", "moe.dense_flops_util",
               "moe.expert_flops_util", "moe.dispatch_hbm_util"]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config():
    with open(os.path.join(ROOT, "benchmark/configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_the_cell_is_added_by_files_alone():
    bench = _bench()
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert cell[0]["traffic"] == "train-lm" and cell[0]["config"] == CONFIG
    assert len(cell[0]["why"]) <= 200
    # the four-chip quota is spent on the granite cell: still exactly one
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(
        1, len(bench["workloads"]) // 4)
    config = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(config) == 1 and config[0]["source"] == SOURCE
    assert config[0]["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                    "vocab_size"]
    assert len(config[0]["why"]) <= 200
    assert [w["config"] for w in bench["workloads"]].count(CONFIG) == 1
    # the last entries of their lists: nothing was put in the middle
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG
    for path in (config[0]["file"], "benchmark/reference/moe_lm.py",
                 "benchmark/layers/moe.py", "benchmark/costs_moe.py"):
        assert os.path.exists(os.path.join(ROOT, path)), path


def test_the_moe_metrics_name_this_cell_and_only_it():
    bench = _bench()
    moe = {m["name"]: m for m in bench["per_layer"]
           if m["name"].startswith("moe.")}
    assert list(moe) == MOE_METRICS
    for m in moe.values():
        assert m["workloads"] == [CELL]
        assert m["moves"] == "steps_per_s_per_chip"
        assert m["layer"] == "policy forward" and m["unit"] == "share"
        assert m["source"] == "device_trace"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for name in ("moe.dense_flops_util", "moe.expert_flops_util",
                 "moe.dispatch_hbm_util"):
        assert moe[name]["better"] == "higher"
    others = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", []) and m["name"] not in moe]
    assert others == []
    assert [m["name"] for m in bench["per_layer"][-10:]] == MOE_METRICS


def test_the_configuration_file_keeps_every_published_width():
    config = _config()
    published = dict(
        attention_bias=False, ep_size=1, first_k_dense_replace=1, head_dim=64,
        hidden_act="silu", hidden_size=2048, intermediate_size=7168,
        kv_lora_rank=512, max_position_embeddings=131072,
        model_type="joyai_llm_flash", moe_intermediate_size=768,
        moe_layer_freq=1, n_group=1, n_shared_experts=1, norm_topk_prob=True,
        num_attention_heads=32, num_experts_per_tok=8,
        num_key_value_heads=32, num_nextn_predict_layers=1, q_lora_rank=1536,
        qk_head_dim=192, qk_nope_head_dim=128, qk_rope_head_dim=64,
        rms_norm_eps=1e-06, rope_interleave=True, rope_scaling=None,
        rope_theta=32000000, routed_scaling_factor=2.5,
        scoring_func="sigmoid", tie_word_embeddings=False, topk_group=1,
        topk_method="noaux_tc", v_head_dim=128)
    for key, value in published.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    assert config["published"]["num_hidden_layers"] == 40
    assert config["published"]["n_routed_experts"] == 256
    assert config["published"]["vocab_size"] == 129280
    assert (config["num_hidden_layers"], config["vocab_size"]) == (5, 16160)
    held = config["n_routed_experts"]
    assert held in (16, 8) and held * config["expert_group_size"] == 256
    assert config["deployment"]["expert_parallel_group"] == 256 // held
    assert config["layer_types"] == ["dense"] + ["moe"] * 39
    kwargs = config["build"]["kwargs"]
    policy = kwargs["policy_kwargs"]
    assert policy["layer_types"] == ["dense"] + ["moe"] * 4
    for key in published:
        if key in policy:
            assert policy[key] == published[key], key
    assert (policy["n_routed_experts"], policy["expert_group_size"],
            policy["expert_group_rank"], policy["vocab_size"]) == (
        held, 256 // held, 0, 16160)
    assert (kwargs["model_shards"], kwargs["low_rank"], config["eval_chunk"],
            config["population_size"], config["horizon"]) == (
        1, 1, 0, 16, 4096)
    assert config["deployment"]["mesh"] == {"pop": 1, "model": 1}
    assert {"assumed", "departures", "reference_tolerance",
            "deployment"} <= set(config)
    assert "layer_types" in config["assumed"]
    assert "selection bias b" in config["assumed"]
    tol = config["reference_tolerance"]
    assert 0 < tol["rtol"] < 0.05 and 0 < tol["behaviour_atol"] < 0.5
    assert "routes" in tol["why"]


def test_the_reader_finds_nothing_in_a_program_without_the_scopes():
    """``layers/moe.py`` on a run that took no trace, one whose program
    names no stage, and ones of the other two sequence models (no
    ``es.route``): nothing, no raise.  On an expert program: the ten
    metrics, the grouped matmuls' unscoped custom calls booked to the
    experts."""
    from benchmark.files import load_file_module

    reader = load_file_module(os.path.join(ROOT, "benchmark/layers/moe.py"))

    def run(stage_s, ops=None):
        staged = {"busiest": "d0", "devices": {"d0": {
            "stage_s": stage_s, "busy_s": sum(stage_s.values()) or 1.0,
            "ops": ops or {}}}}
        return {"stage_reduce": {"staged": staged}, "chips": 1,
                "steps_per_generation": 65536, "traced_generations": 1,
                "dense_flops_per_member_step": 468_189_184,
                "head_flops_per_member_step": 132_382_720,
                "peaks": {"peak_flops_per_s": 197e12,
                          "peak_hbm_bytes_per_s": 819e9}}

    assert reader.read({"stage_reduce": None}) == {}
    assert reader.read({"trace": None}) == {}
    assert reader.read(run({"unscoped": 0.2})) == {}
    assert reader.read(run({"policy": 0.1, "dense": 0.5, "attn": 0.1,
                            "ssm": 0.2, "head": 0.1})) == {}
    assert reader.read(run({"dense": 1.2, "attn": 0.7, "head": 0.3,
                            "rope": 0.1, "exit": 0.01})) == {}
    stage_s = {"attn": 0.53, "dense": 0.19, "route": 0.056, "dispatch": 0.063,
               "head": 0.054, "rope": 0.08, "perturb": 0.12, "unscoped": 0.08,
               "policy": 0.05, "update": 0.04}
    ops = {"unscoped": {"ragged-dot-none.12_f32_5120_768": [0.02, 0, 0, ""],
                        "ragged-dot-none_f32_5120_768": [0.019, 0, 0, ""],
                        "ragged-dot-metadata": [0.001, 0, 0, ""],
                        "copy.7_f32": [0.04, 0, 0, ""]}}
    got = reader.read(run(stage_s, ops))
    assert list(got) == MOE_METRICS
    busy = sum(stage_s.values())
    assert abs(got["moe.attn_share"] - 0.53 / busy) < 1e-12
    assert abs(got["moe.expert_share"] - 0.04 / busy) < 1e-12
    held = _config()["n_routed_experts"]
    pairs = 65536 * 5 * 8 * held / 256
    want = pairs * 2 * 3 * 2048 * 768 / 0.04 / 197e12
    assert abs(got["moe.expert_flops_util"] - want) < 1e-12 and want < 1.0
    want = pairs * 2048 * 16 / 0.063 / 819e9
    assert abs(got["moe.dispatch_hbm_util"] - want) < 1e-12 and want < 1.0
    want = (468_189_184 + 132_382_720) * 65536 / 0.244 / 197e12
    assert abs(got["moe.dense_flops_util"] - want) < 1e-12 and want < 1.0


def test_the_costs_are_from_shapes():
    from benchmark import costs_moe

    assert costs_moe.expected_pairs_per_token(8, 16, 256) == 0.5
    assert costs_moe.expected_pairs_per_token(8, 256, 256) == 8
    assert costs_moe.expert_flops_per_pair(2048, 768) == 2 * 3 * 2048 * 768
    # gather: a bf16 row read and written; combine: a float32 row read,
    # and the token's float32 row read and written
    assert costs_moe.dispatch_bytes_per_pair(2048) == 2048 * (2 + 2 + 12)


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
    # a CPU trace has no device operation: the trace's readers (moe.*,
    # stage.*) find nothing and their metrics are left out
    assert not any(name.startswith(("rehearsal.moe.", "rehearsal.loop.",
                                    "rehearsal.lm.")) for name in got)
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
    _with_policy(tmp_path, "estorch_tpu.models.NoSuchMoELM")
    p, lines = run_cell(cache, *ARGS, "--seed", "1", "--trace", "1",
                        root=str(tmp_path), extra_env={"PYTHONPATH": ROOT})
    assert p.returncode != 0 and not any(ln.startswith("{") for ln in lines)
    assert "cannot run joyai-llm-flash-5layers" in p.stderr
    assert "has no estorch_tpu.models.NoSuchMoELM" in p.stderr
    assert not any("device bring-up" in ln for ln in lines)


def test_a_reference_with_another_seed_is_not_correct(cache):
    out = result_of(*run_cell(cache, *ARGS, "--seed", "3", "--trace", "0",
                              "--reference-seed", "4"))
    assert out["correct"] is False


@pytest.mark.parametrize("policy", [
    "coarse_moe.Fp8Moe", "coarse_moe.DroppedExpertCorrectionMoe",
    "coarse_moe.NoMtpMoe"])
def test_a_degraded_forward_is_not_correct(cache, tmp_path, policy):
    """The same configuration with every projection's and expert's input
    rounded to fp8, with one stacked expert leaf's rank-r correction
    dropped, or with the MTP term left out, against the same plain
    reference: ``correct`` comes out false."""
    _with_policy(tmp_path, policy)
    p, lines = run_cell(
        cache, *ARGS, "--seed", "3", "--trace", "0", root=str(tmp_path),
        extra_env={"PYTHONPATH": ROOT + os.pathsep + HERE})
    assert result_of(p, lines)["correct"] is False
    assert any("reference, the measured program" in ln and "MISMATCH" in ln
               for ln in lines)


@pytest.mark.parametrize("policy", [
    "coarse_moe.Bf16RouterMoe", "coarse_moe.BiasInWeightsMoe"])
def test_what_the_limits_cannot_separate_still_runs(cache, tmp_path, policy):
    """A bfloat16 router moves a route only where the eighth and ninth
    scores lie within its rounding, and the selection bias is sigma-sized
    (it starts at 0), so in the weights it moves them by parts in a
    thousand: both stay inside the honest bfloat16 spread, here and on the
    chip (``reference_tolerance.why``).  They are held by tier-1 tests
    (``tests/test_moe_lm.py``: the router's leaves float32 in the engine's
    copy and its scores to the seventh digit; the bias in the choice only,
    case by case).  Here: the degraded forms run and the check reads them."""
    _with_policy(tmp_path, policy)
    p, lines = run_cell(
        cache, *ARGS, "--seed", "3", "--trace", "0", root=str(tmp_path),
        extra_env={"PYTHONPATH": ROOT + os.pathsep + HERE})
    out = result_of(p, lines)
    assert out["failed"] == 0
    assert any("reference, the measured program" in ln for ln in lines)
