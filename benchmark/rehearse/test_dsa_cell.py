"""The learned-sparse-attention cell end to end on ONE virtual CPU device at
the configuration's rehearsal size (tiny widths, ``topk`` 32 of 128
positions, so the selection bites).  Not a chip number: ``--rehearse`` is the
only way past the TPU check, and it prints every metric as
``rehearsal.<name>``.

The cell is added by files alone (configuration, reference, reader and costs
are new files; runner, traffic and ``run.py`` are untouched), comes out
``correct`` at three seeds, and comes out NOT ``correct`` in these
rehearsals: the reference given another seed, every projection's and
expert's input rounded to fp8, the selection ignored (full causal attention),
``topk`` halved, ``relu`` left out of the indexer, the indexer's ``w``
replaced by ones, sigmoid in place of softmax in the router, the held experts
taken for another rank's."""

import json
import os

import pytest
from test_cells import (ROOT, cache, copy_of_the_benchmark,  # noqa: F401
                        result_of, run_cell)

CELL = "keye-vl2-es-16k-1chip"
CONFIG = "keye-vl-2.0-30b-a3b-ep8"
SOURCE = ("https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/"
          "config.json")
HERE = os.path.dirname(os.path.abspath(__file__))
ARGS = ("--workload", CELL, "--seconds", "2", "--rehearse")
DSA_METRICS = [
    "dsa.dense_share", "dsa.index_share", "dsa.select_share",
    "dsa.attn_share", "dsa.rope_share", "dsa.route_share",
    "dsa.dispatch_share", "dsa.expert_share", "dsa.head_share",
    "dsa.dense_flops_util", "dsa.expert_flops_util", "dsa.attn_flops_util",
    "dsa.index_flops_util"]


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
    assert config[0]["reduced"] == ["num_hidden_layers", "num_experts",
                                    "vocab_size"]
    assert len(config[0]["why"]) <= 200
    assert [w["config"] for w in bench["workloads"]].count(CONFIG) == 1
    for path in (config[0]["file"], "benchmark/reference/indexed_moe_lm.py",
                 "benchmark/layers/dsa.py", "benchmark/costs_dsa.py"):
        assert os.path.exists(os.path.join(ROOT, path)), path


def test_the_dsa_metrics_name_this_cell_and_only_it():
    bench = _bench()
    ours = {m["name"]: m for m in bench["per_layer"]
            if m["name"].startswith("dsa.")}
    assert list(ours) == DSA_METRICS
    for m in ours.values():
        assert m["workloads"] == [CELL]
        assert m["moves"] == "steps_per_s_per_chip"
        assert m["layer"] == "policy forward" and m["unit"] == "share"
        assert m["source"] == "device_trace"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for name in DSA_METRICS[-4:]:
        assert ours[name]["better"] == "higher"
    others = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", []) and m["name"] not in ours]
    assert others == []


def test_the_configuration_file_keeps_every_published_key():
    """Every key of the catalog's ``config`` at its published value but the
    three under ``reduced``; nested groups whole."""
    config = _config()
    published = dict(
        attention_bias=False, decoder_sparse_step=1, head_dim=128,
        hidden_act="silu", hidden_size=2048, intermediate_size=6144,
        max_position_embeddings=262144, max_window_layers=48,
        mlp_only_layers=[], model_type="KeyeVL2", moe_intermediate_size=768,
        norm_topk_prob=True, num_attention_heads=32, num_experts_per_tok=8,
        num_key_value_heads=4, num_local_experts=128, rms_norm_eps=1e-06,
        rope_scaling={"mrope_section": [16, 24, 24], "rope_type": "default",
                      "type": "default"},
        rope_theta=10000000,
        sa_config={"indexer_head_dim": 64, "indexer_num_heads": 16,
                   "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                   "q_chunk_size": 512, "topk": 2048},
        sliding_window=None, tie_word_embeddings=False,
        use_sliding_window=False)
    for key, value in published.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"]["num_hidden_layers"] == 48
    assert config["published"]["num_experts"] == 128
    assert config["published"]["vocab_size"] == 151936
    assert (config["num_hidden_layers"], config["vocab_size"]) in (
        (5, 18992), (4, 18992))
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    held = config["num_experts"]
    assert held == 16 and held * config["expert_group_size"] == 128
    assert config["deployment"]["expert_parallel_group"] == 8
    assert config["layer_types"] == ["moe"] * 48
    kwargs = config["build"]["kwargs"]
    policy = kwargs["policy_kwargs"]
    assert policy["layer_types"] == ["moe"] * config["num_hidden_layers"]
    for key in published:
        if key in policy:
            assert policy[key] == published[key], key
    for key, value in published["sa_config"].items():
        if key in policy:
            assert policy[key] == value, key
    assert policy["mrope_section"] == [16, 24, 24]
    assert (kwargs["model_shards"], kwargs["low_rank"], config["eval_chunk"],
            config["population_size"], config["horizon"]) == (
        1, 1, 0, 4, 16384)
    assert config["deployment"]["mesh"] == {"pop": 1, "model": 1}
    assert config["deployment"]["state_bytes_per_chip"] in (
        14 * 562_290_560, 14 * 465_391_104)
    assert {"assumed", "departures", "reference_tolerance",
            "deployment"} <= set(config)
    for said in ("per-head q/k RMSNorm", "indexer", "router",
                 "q_chunk_size, kv_chunk_size", "initialisation",
                 "sigma, optimizer", "population_size",
                 "corpus_seed and table_seed"):
        assert said in config["assumed"], said
    assert any("vision tower is NOT built" in d
               for d in config["departures"])
    tiny = config["rehearsal_kwargs"]
    assert (tiny["policy_kwargs"]["topk"] * 4
            == tiny["agent_kwargs"]["env"]["kwargs"]["seq_len"])
    tol = config["reference_tolerance"]
    assert 0 < tol["rtol"] < 0.05 and 0 < tol["behaviour_atol"] < 0.5
    assert "fp8" in tol["why"]


def test_the_reader_finds_nothing_in_a_program_without_the_scopes():
    """``layers/dsa.py`` on a run that took no trace, one whose program names
    no stage, and ones of the other four sequence models (neither
    ``es.index`` nor ``es.select``): nothing, no raise.  On this model's
    program: the thirteen metrics from the exact pair counts."""
    from benchmark.files import load_file_module

    reader = load_file_module(os.path.join(ROOT, "benchmark/layers/dsa.py"))

    def run(stage_s, ops=None):
        staged = {"busiest": "d0", "devices": {"d0": {
            "stage_s": stage_s, "busy_s": sum(stage_s.values()) or 1.0,
            "ops": ops or {}}}}
        return {"stage_reduce": {"staged": staged}, "chips": 1,
                "steps_per_generation": 65536, "traced_generations": 1,
                "dense_flops_per_member_step": 188_743_680,
                "head_flops_per_member_step": 77_791_232,
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
    stage_s = {"dense": 0.1, "index": 0.5, "select": 0.8, "attn": 0.6,
               "rope": 0.02, "route": 0.01, "dispatch": 0.03, "expert": 0.02,
               "head": 0.03, "perturb": 0.1, "policy": 0.03, "update": 0.04,
               "unscoped": 0.05}
    ops = {"unscoped": {"ragged-dot-none.1": [0.04, 0, 0, ""],
                        "copy.3": [0.01, 0, 0, ""]}}
    got = reader.read(run(stage_s, ops))
    assert list(got) == DSA_METRICS
    busy = sum(stage_s.values())
    assert abs(got["dsa.index_share"] - 0.5 / busy) < 1e-12
    assert abs(got["dsa.select_share"] - 0.8 / busy) < 1e-12
    assert abs(got["dsa.expert_share"] - 0.06 / busy) < 1e-12
    want = (188_743_680 + 77_791_232) * 65536 / 0.13 / 197e12
    assert abs(got["dsa.dense_flops_util"] - want) < 1e-12 and want < 1.0
    config = _config()
    length, layers = config["horizon"], config["num_hidden_layers"]
    members = 65536 // length
    chosen = 2048 * 2049 // 2 + (length - 2048) * 2048
    want = chosen * 16384 * layers * members / 0.6 / 197e12
    assert abs(got["dsa.attn_flops_util"] - want) < 1e-12 and want < 1.0
    want = (length * (length + 1) // 2) * 2048 * layers * members / 1.3 / 197e12
    assert abs(got["dsa.index_flops_util"] - want) < 1e-12 and want < 1.0
    want = (65536 * layers * 1.0 * 2 * 3 * 2048 * 768) / 0.06 / 197e12
    assert abs(got["dsa.expert_flops_util"] - want) < 1e-12 and want < 1.0


def test_the_costs_are_from_shapes():
    from benchmark import costs_dsa

    assert costs_dsa.causal_pairs(16384) == 134_225_920
    assert costs_dsa.selected_pairs(16384, 2048) == 31_458_304
    assert costs_dsa.selected_pairs(5, 8) == 15
    assert costs_dsa.selected_pairs(4, 1) == 4
    for t, k in [(7, 3), (12, 5), (3, 3), (9, 20)]:
        assert costs_dsa.selected_pairs(t, k) == sum(
            min(q + 1, k) for q in range(t))
    assert costs_dsa.attention_flops_per_pair(32, 128) == 16384
    assert costs_dsa.index_flops_per_pair(16, 64) == 2048


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
    # a CPU trace has no device operation: the trace's readers (dsa.*,
    # stage.*) find nothing and their metrics are left out
    assert not any(name.startswith(("rehearsal.dsa.", "rehearsal.moe.",
                                    "rehearsal.sambay.")) for name in got)
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
    _with_policy(tmp_path, "estorch_tpu.models.NoSuchIndexedMoELM")
    p, lines = run_cell(cache, *ARGS, "--seed", "1", "--trace", "1",
                        root=str(tmp_path), extra_env={"PYTHONPATH": ROOT})
    assert p.returncode != 0 and not any(ln.startswith("{") for ln in lines)
    assert "cannot run keye-vl-2.0-30b-a3b-ep8" in p.stderr
    assert "has no estorch_tpu.models.NoSuchIndexedMoELM" in p.stderr
    assert not any("device bring-up" in ln for ln in lines)


def test_a_reference_with_another_seed_is_not_correct(cache):
    out = result_of(*run_cell(cache, *ARGS, "--seed", "3", "--trace", "0",
                              "--reference-seed", "4"))
    assert out["correct"] is False


@pytest.mark.parametrize("policy", [
    "coarse_dsa.Fp8Dsa", "coarse_dsa.IgnoredSelectionDsa",
    "coarse_dsa.HalfTopkDsa", "coarse_dsa.NoReluDsa",
    "coarse_dsa.OnesWeightsDsa", "coarse_dsa.SigmoidRouterDsa",
    "coarse_dsa.OtherRankDsa"])
def test_a_degraded_forward_is_not_correct(cache, tmp_path, policy):
    """The same configuration with every projection's and expert's input
    rounded to fp8, the selection ignored, ``topk`` halved, ``relu`` left
    out of the indexer, ``w`` replaced by ones, a sigmoid router or the held
    experts of another rank, against the same plain reference: ``correct``
    comes out false."""
    _with_policy(tmp_path, policy)
    p, lines = run_cell(
        cache, *ARGS, "--seed", "3", "--trace", "0", root=str(tmp_path),
        extra_env={"PYTHONPATH": ROOT + os.pathsep + HERE})
    assert result_of(p, lines)["correct"] is False
    assert any("reference, the measured program" in ln and "MISMATCH" in ln
               for ln in lines)
