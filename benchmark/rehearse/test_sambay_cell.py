"""The SambaY cell end to end on ONE virtual CPU device at the
configuration's rehearsal size (tiny widths).  Not a chip number:
``--rehearse`` is the only way past the TPU check, and it prints every
metric as ``rehearsal.<name>``.

The cell is added by files alone (configuration, reference, reader and costs
are new files; runner, traffic and ``run.py`` are untouched), comes out
``correct`` at three seeds, and comes out NOT ``correct`` in these
rehearsals: the reference given another seed, every projection's input
rounded to fp8, the rank-r correction left out of one projection, λ set to 0
(plain attention), the window ignored, ``m`` taken from the first Mamba layer
instead of the last, the cross layer given keys of its own."""

import json
import os

import pytest
from test_cells import (ROOT, cache, copy_of_the_benchmark,  # noqa: F401
                        result_of, run_cell)

CELL = "phi4-flash-es-8k-1chip"
CONFIG = "phi-4-mini-flash-6layers"
SOURCE = ("https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning/blob/"
          "main/config.json")
HERE = os.path.dirname(os.path.abspath(__file__))
ARGS = ("--workload", CELL, "--seconds", "2", "--rehearse")
SAMBAY_METRICS = [
    "sambay.dense_share", "sambay.ssm_share", "sambay.gmu_share",
    "sambay.window_attn_share", "sambay.full_attn_share",
    "sambay.diff_share", "sambay.head_share", "sambay.dense_flops_util",
    "sambay.attn_flops_util", "sambay.ssm_hbm_util"]


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
    assert config[0]["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert len(config[0]["why"]) <= 200
    assert [w["config"] for w in bench["workloads"]].count(CONFIG) == 1
    # the last entries of their lists: nothing was put in the middle
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == CONFIG
    for path in (config[0]["file"], "benchmark/reference/sambay_lm.py",
                 "benchmark/layers/sambay.py", "benchmark/costs_sambay.py"):
        assert os.path.exists(os.path.join(ROOT, path)), path


def test_the_sambay_metrics_name_this_cell_and_only_it():
    bench = _bench()
    ours = {m["name"]: m for m in bench["per_layer"]
            if m["name"].startswith("sambay.")}
    assert list(ours) == SAMBAY_METRICS
    for m in ours.values():
        assert m["workloads"] == [CELL]
        assert m["moves"] == "steps_per_s_per_chip"
        assert m["layer"] == "policy forward" and m["unit"] == "share"
        assert m["source"] == "device_trace"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for name in SAMBAY_METRICS[-3:]:
        assert ours[name]["better"] == "higher"
    others = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", []) and m["name"] not in ours]
    assert others == []
    assert [m["name"] for m in bench["per_layer"][-10:]] == SAMBAY_METRICS


def test_the_configuration_file_keeps_every_published_width():
    config = _config()
    published = dict(
        embd_pdrop=0, hidden_act="silu", hidden_size=2560,
        intermediate_size=10240, layer_norm_eps=1e-05,
        max_position_embeddings=262144, mb_per_layer=2,
        model_type="phi4flash", num_attention_heads=40,
        num_key_value_heads=20, resid_pdrop=0, sliding_window=512,
        tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False)
    for key, value in published.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert config["published"]["num_hidden_layers"] == 32
    assert config["published"]["vocab_size"] == 200064
    assert (config["num_hidden_layers"], config["vocab_size"]) == (6, 25008)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["layers_held"] == [0, 1, 16, 17, 18, 19]
    assert config["layer_types"] == ["mamba", "window", "mamba_mem",
                                     "full_kv", "gmu", "cross"]
    kwargs = config["build"]["kwargs"]
    policy = kwargs["policy_kwargs"]
    assert policy["layer_indices"] == config["layers_held"]
    for key in published:
        if key in policy:
            assert policy[key] == published[key], key
    assert (policy["mamba_d_state"], policy["mamba_d_conv"],
            policy["mamba_expand"], policy["mamba_dt_rank"]) == (
        16, 4, 2, 160)
    assert (kwargs["model_shards"], kwargs["low_rank"], config["eval_chunk"],
            config["population_size"], config["horizon"]) in (
        (1, 1, 0, 8, 8192), (1, 1, 0, 16, 4096))
    assert config["deployment"]["mesh"] == {"pop": 1, "model": 1}
    assert config["deployment"]["state_bytes_per_chip"] == 9_759_319_808
    assert {"assumed", "departures", "reference_tolerance",
            "deployment"} <= set(config)
    for said in ("mamba sizes", "pairing of heads", "initialisation",
                 "sigma, optimizer", "population_size",
                 "corpus_seed and table_seed"):
        assert said in config["assumed"], said
    tol = config["reference_tolerance"]
    assert 0 < tol["rtol"] < 0.05 and 0 < tol["behaviour_atol"] < 0.5
    assert "fp8" in tol["why"]


def test_the_reader_finds_nothing_in_a_program_without_the_scopes():
    """``layers/sambay.py`` on a run that took no trace, one whose program
    names no stage, and ones of the other three sequence models (no
    ``es.gmu``): nothing, no raise.  On a SambaY program: the ten metrics,
    the attention's seconds split by the part in each operation's name
    stack."""
    from benchmark.files import load_file_module

    reader = load_file_module(os.path.join(ROOT,
                                           "benchmark/layers/sambay.py"))

    def run(stage_s, ops=None):
        staged = {"busiest": "d0", "devices": {"d0": {
            "stage_s": stage_s, "busy_s": sum(stage_s.values()) or 1.0,
            "ops": ops or {}}}}
        return {"stage_reduce": {"staged": staged}, "chips": 1,
                "steps_per_generation": 65536, "traced_generations": 1,
                "dense_flops_per_member_step": 1_265_500_160,
                "head_flops_per_member_step": 128_040_960,
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
    stage_s = {"dense": 0.7, "attn": 0.4, "ssm": 0.12, "gmu": 0.004,
               "diff": 0.01, "head": 0.06, "perturb": 0.1, "policy": 0.03,
               "update": 0.04, "unscoped": 0.02}
    stack = "jit(f)/es.policy/vmap(es.attn)/vmap(of.{})/es.attn/dot_general"
    ops = {"attn": {"fusion.1": [0.03, 0, 0, stack.format("window")],
                    "fusion.2": [0.2, 0, 0, stack.format("full")],
                    "fusion.3": [0.15, 0, 0, stack.format("cross")],
                    "fusion.4": [0.02, 0, 0, "jit(f)/es.attn/reduce_max"]}}
    got = reader.read(run(stage_s, ops))
    assert list(got) == SAMBAY_METRICS
    busy = sum(stage_s.values())
    assert abs(got["sambay.ssm_share"] - 0.12 / busy) < 1e-12
    assert abs(got["sambay.window_attn_share"] - 0.03 / busy) < 1e-12
    assert abs(got["sambay.full_attn_share"] - 0.35 / busy) < 1e-12
    want = (1_265_500_160 + 128_040_960) * 65536 / 0.76 / 197e12
    assert abs(got["sambay.dense_flops_util"] - want) < 1e-12 and want < 1.0
    config = _config()
    length, members = config["horizon"], 65536 // config["horizon"]
    full = length * (length + 1) // 2
    banded = 512 * 513 // 2 + (length - 512) * 512
    want = (2 * full + banded) * 15360 * members / 0.4 / 197e12
    assert abs(got["sambay.attn_flops_util"] - want) < 1e-12 and want < 1.0
    want = (2 * 4 * length * (3 * 5120 + 2 * 16)) * members / 0.12 / 819e9
    assert abs(got["sambay.ssm_hbm_util"] - want) < 1e-12 and want < 1.0


def test_the_costs_are_from_shapes():
    from benchmark import costs_sambay

    assert costs_sambay.visible_pairs(8192) == 33_558_528
    assert costs_sambay.visible_pairs(8192, 512) == 4_063_488
    assert costs_sambay.visible_pairs(5, 8) == 15
    assert costs_sambay.visible_pairs(4, 1) == 4
    # brute force: query t sees the keys (t - w, t]
    for t, w in [(7, 3), (12, 5), (3, 3)]:
        assert costs_sambay.visible_pairs(t, w) == sum(
            1 for q in range(t) for k in range(t) if q - w < k <= q)
    assert costs_sambay.diff_attention_flops_per_pair(40, 64) == 15360
    kinds = ["mamba", "window", "mamba_mem", "full_kv", "gmu", "cross"]
    flops = costs_sambay.attention_flops_per_sequence(kinds, 8192, 512, 40,
                                                      64)
    assert flops == {"window": 4_063_488 * 15360,
                     "full": 2 * 33_558_528 * 15360}
    # 0.133 GFLOP of attention a token at 8,192
    assert abs(sum(flops.values()) / 8192 - 0.1335e9) < 1e6
    assert costs_sambay.scan_bytes_per_sequence(kinds, 8192, 5120, 16) == (
        2 * 4 * 8192 * (3 * 5120 + 32))


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
    # a CPU trace has no device operation: the trace's readers (sambay.*,
    # stage.*) find nothing and their metrics are left out
    assert not any(name.startswith(("rehearsal.sambay.", "rehearsal.moe.",
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
    _with_policy(tmp_path, "estorch_tpu.models.NoSuchSambaYLM")
    p, lines = run_cell(cache, *ARGS, "--seed", "1", "--trace", "1",
                        root=str(tmp_path), extra_env={"PYTHONPATH": ROOT})
    assert p.returncode != 0 and not any(ln.startswith("{") for ln in lines)
    assert "cannot run phi-4-mini-flash-6layers" in p.stderr
    assert "has no estorch_tpu.models.NoSuchSambaYLM" in p.stderr
    assert not any("device bring-up" in ln for ln in lines)


def test_a_reference_with_another_seed_is_not_correct(cache):
    out = result_of(*run_cell(cache, *ARGS, "--seed", "3", "--trace", "0",
                              "--reference-seed", "4"))
    assert out["correct"] is False


@pytest.mark.parametrize("policy", [
    "coarse_sambay.Fp8SambaY", "coarse_sambay.DroppedCorrectionSambaY",
    "coarse_sambay.PlainAttentionSambaY", "coarse_sambay.NoWindowSambaY",
    "coarse_sambay.FirstMemorySambaY", "coarse_sambay.OwnKeysCrossSambaY"])
def test_a_degraded_forward_is_not_correct(cache, tmp_path, policy):
    """The same configuration with every projection's input rounded to fp8,
    one projection's rank-r correction dropped, λ set to 0, the window
    ignored, ``m`` taken from the first Mamba layer or the cross layer given
    keys of its own, against the same plain reference: ``correct`` comes out
    false."""
    _with_policy(tmp_path, policy)
    p, lines = run_cell(
        cache, *ARGS, "--seed", "3", "--trace", "0", root=str(tmp_path),
        extra_env={"PYTHONPATH": ROOT + os.pathsep + HERE})
    assert result_of(p, lines)["correct"] is False
    assert any("reference, the measured program" in ln and "MISMATCH" in ln
               for ln in lines)
