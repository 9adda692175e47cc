"""The looped-model cell end to end on ONE virtual CPU device at the
configuration's rehearsal size (tiny widths).  Not a chip number:
``--rehearse`` is the only way past the TPU check, and it prints every
metric as ``rehearsal.<name>``.

The cell is added by files alone (configuration, reference and reader are
new files; runner, traffic and ``run.py`` are untouched), comes out
``correct``, and comes out NOT ``correct`` in six rehearsals: the reference
given another seed, three passes instead of four, the rotation left out of
the keys, the score read from the last pass alone, one leaf's rank-r
correction dropped, every projection's input rounded to fp8."""

import json
import os

import pytest
from test_cells import (ROOT, cache, copy_of_the_benchmark,  # noqa: F401
                        result_of, run_cell)

CELL = "ouro-2.6b-es-4k-1chip"
CONFIG = "ouro-2.6b-8layers"
HERE = os.path.dirname(os.path.abspath(__file__))
ARGS = ("--workload", CELL, "--seconds", "2", "--rehearse")
LOOP_METRICS = {"loop.dense_share", "loop.attn_share", "loop.head_share",
                "loop.rope_share", "loop.exit_share", "loop.dense_flops_util"}


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_cell_is_added_by_files_alone():
    bench = _bench()
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 1
    assert cell[0]["traffic"] == "train-lm" and cell[0]["config"] == CONFIG
    assert len(cell[0]["why"]) <= 200
    # the four-chip quota is spent on the granite cell
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(config) == 1 and config[0]["reduced"] == ["num_hidden_layers"]
    assert config[0]["source"] == ("https://huggingface.co/ByteDance/"
                                   "Ouro-2.6B/blob/main/config.json")
    assert [w["config"] for w in bench["workloads"]].count(CONFIG) == 1
    for path in (config[0]["file"], "benchmark/reference/looped_lm.py",
                 "benchmark/layers/loop.py"):
        assert os.path.exists(os.path.join(ROOT, path)), path


def test_the_loop_metrics_name_this_cell_and_only_it():
    bench = _bench()
    loop = {m["name"]: m for m in bench["per_layer"]
            if m["name"].startswith("loop.")}
    assert set(loop) == LOOP_METRICS
    for m in loop.values():
        assert m["workloads"] == [CELL]
        assert m["moves"] == "steps_per_s_per_chip"
        assert m["layer"] == "policy forward" and m["unit"] == "share"
        assert m["source"] == "device_trace"
    assert loop["loop.dense_flops_util"]["better"] == "higher"
    # no other metric lists it: the granite cell's stay the granite cell's
    others = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", []) and m["name"] not in loop]
    assert others == []
    # they are the last six entries: nothing was put in the middle
    assert [m["name"] for m in bench["per_layer"][-6:]] == [
        "loop.dense_share", "loop.attn_share", "loop.head_share",
        "loop.rope_share", "loop.exit_share", "loop.dense_flops_util"]


def test_the_configuration_file_keeps_every_published_width():
    with open(os.path.join(ROOT, "benchmark/configs", CONFIG + ".json")) as f:
        config = json.load(f)
    published = dict(
        head_dim=128, hidden_act="silu", hidden_size=2048,
        intermediate_size=5632, max_position_embeddings=65536,
        max_window_layers=48, model_type="ouro", num_attention_heads=16,
        num_key_value_heads=16, rms_norm_eps=1e-06, rope_scaling=None,
        rope_theta=1000000, sliding_window=None, tie_word_embeddings=False,
        total_ut_steps=4, early_exit_threshold=1, use_sliding_window=False,
        vocab_size=49152)
    for key, value in published.items():
        assert config[key] == value, key
    assert config["layer_types"] == ["full_attention"] * 48
    assert config["num_hidden_layers"] in (8, 6)
    assert config["reduced"] == ["num_hidden_layers"]
    kwargs = config["build"]["kwargs"]
    assert kwargs["policy_kwargs"]["layer_types"] == (
        ["full_attention"] * config["num_hidden_layers"])
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "num_key_value_heads", "head_dim", "vocab_size", "rope_theta",
                "rms_norm_eps", "total_ut_steps", "tie_word_embeddings"):
        assert kwargs["policy_kwargs"][key] == published[key], key
    assert (kwargs["model_shards"], kwargs["low_rank"], config["eval_chunk"],
            config["population_size"], config["horizon"]) == (1, 1, 0, 8, 4096)
    assert config["deployment"]["mesh"] == {"pop": 1, "model": 1}
    assert {"assumed", "departures", "reference_tolerance"} <= set(config)


def test_the_reader_finds_nothing_in_a_program_without_the_scopes():
    """``layers/loop.py`` on three traced runs: one that took no trace, one
    whose program names no stage at all, and one of another sequence model
    (dense, attn and head but no rope and no exit): nothing, no raise.  And
    on a looped program: the six metrics."""
    from benchmark.files import load_file_module

    reader = load_file_module(os.path.join(ROOT, "benchmark/layers/loop.py"))

    def run(stage_s):
        staged = {"busiest": "d0", "devices": {"d0": {
            "stage_s": stage_s, "busy_s": sum(stage_s.values()) or 1.0}}}
        return {"stage_reduce": {"staged": staged}, "chips": 1,
                "steps_per_generation": 32768, "traced_generations": 1,
                "dense_flops_per_member_step": 3_288_334_336,
                "head_flops_per_member_step": 805_306_368,
                "peaks": {"peak_flops_per_s": 197e12}}

    assert reader.read({"stage_reduce": None}) == {}
    assert reader.read({"trace": None}) == {}
    assert reader.read(run({"unscoped": 0.2})) == {}
    assert reader.read(run({"policy": 0.1, "dense": 0.5, "attn": 0.1,
                            "ssm": 0.2, "head": 0.1})) == {}
    got = reader.read(run({"dense": 1.2, "attn": 0.7, "head": 0.3,
                           "rope": 0.1, "exit": 0.01, "policy": 0.09}))
    assert set(got) == LOOP_METRICS
    assert abs(got["loop.dense_share"] - 0.5) < 1e-12
    assert abs(got["loop.rope_share"] - 0.1 / 2.4) < 1e-12
    want = (3_288_334_336 + 805_306_368) * 32768 / 1.5 / 197e12
    assert abs(got["loop.dense_flops_util"] - want) < 1e-12 and want < 1.0


def test_the_cell_is_correct_on_one_virtual_device(cache):
    p, lines = run_cell(cache, *ARGS, "--seed", "3100000019", "--trace", "1")
    out = result_of(p, lines)
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["count"] == 1 and out["attempted"] >= 2
    got = out["metrics"]
    assert got["rehearsal.rollout.alive_share"]["value"] == 1.0
    assert got["rehearsal.compile.programs_in_window"]["value"] == 0
    # a CPU trace has no device operation: the trace's readers (loop.*,
    # stage.*) find nothing and their metrics are left out
    assert not any(name.startswith(("rehearsal.loop.", "rehearsal.lm."))
                   for name in got)
    gauges = [ln for ln in lines if "gauges:" in ln]
    assert gauges and "'forward_form': 'perturbed'" in gauges[0]
    assert "'mesh_shape': '1x1'" in gauges[0]


def test_end_to_end_metrics_without_a_trace(cache):
    out = result_of(*run_cell(cache, *ARGS, "--seed", "7", "--trace", "0"))
    assert out["correct"] is True
    assert set(out["metrics"]) == {"rehearsal.steps_per_s_per_chip",
                                   "rehearsal.setup_s"}


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
    _with_policy(tmp_path, "estorch_tpu.models.NoSuchLoopedLM")
    p, lines = run_cell(cache, *ARGS, "--seed", "1", "--trace", "1",
                        root=str(tmp_path), extra_env={"PYTHONPATH": ROOT})
    assert p.returncode != 0 and not any(ln.startswith("{") for ln in lines)
    assert "cannot run ouro-2.6b-8layers" in p.stderr
    assert "has no estorch_tpu.models.NoSuchLoopedLM" in p.stderr
    assert not any("device bring-up" in ln for ln in lines)


def test_a_reference_with_another_seed_is_not_correct(cache):
    out = result_of(*run_cell(cache, *ARGS, "--seed", "3", "--trace", "0",
                              "--reference-seed", "4"))
    assert out["correct"] is False


@pytest.mark.parametrize("policy", [
    "coarse_loop.ThreePassLoop", "coarse_loop.UnrotatedKeysLoop",
    "coarse_loop.LastPassScoreLoop", "coarse_loop.DroppedCorrectionLoop",
    "coarse_loop.Fp8Loop"])
def test_a_degraded_forward_is_not_correct(cache, tmp_path, policy):
    """The same configuration with three passes instead of four, with the
    rotation left out of the keys, with the score read from the last pass
    alone, with one leaf's rank-r correction dropped, or with every
    projection's input rounded to fp8, against the same plain reference:
    ``correct`` comes out false."""
    _with_policy(tmp_path, policy)
    p, lines = run_cell(
        cache, *ARGS, "--seed", "3", "--trace", "0", root=str(tmp_path),
        extra_env={"PYTHONPATH": ROOT + os.pathsep + HERE})
    assert result_of(p, lines)["correct"] is False
    assert any("reference, the measured program" in ln and "MISMATCH" in ln
               for ln in lines)
