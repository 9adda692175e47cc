"""The sequence-model cell end to end on four virtual CPU devices at the
configuration's rehearsal size (tiny widths).  Not a chip number:
``--rehearse`` is the only way past the TPU check, and it prints every
metric as ``rehearsal.<name>``.

The cell is added by files alone (its runner, traffic, configuration,
reference and reader are new files; ``run.py`` is untouched), comes out
``correct``, and comes out NOT ``correct`` in three rehearsals: the
reference given another seed, the rank-r correction left out of one
projection, the FFN activations rounded to fp8."""

import json
import os

import pytest
from test_cells import (ROOT, cache, copy_of_the_benchmark,  # noqa: F401
                        result_of, run_cell)

CELL = "granite-h-micro-es-4k-4chip"
HERE = os.path.dirname(os.path.abspath(__file__))
ARGS = ("--workload", CELL, "--seconds", "2", "--rehearse")


def test_the_cell_is_added_by_files_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = [w for w in bench["workloads"] if w["name"] == CELL]
    assert len(cell) == 1 and cell[0]["chips"] == 4
    assert cell[0]["traffic"] == "train-lm"
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    config = [c for c in bench["configs"] if c["name"] == cell[0]["config"]]
    assert config[0]["reduced"] == ["num_hidden_layers"]
    for path in ("benchmark/train_lm_runner.py",
                 "benchmark/traffic/train-lm.json", config[0]["file"],
                 "benchmark/reference/hybrid_lm.py",
                 "benchmark/layers/lm.py"):
        assert os.path.exists(os.path.join(ROOT, path)), path
    named = {m["name"]: m for m in bench["per_layer"]
             if m.get("workloads") == [CELL]}
    assert set(named) == {"lm.dense_share", "lm.ssm_share", "lm.attn_share",
                          "lm.head_share", "lm.dense_flops_util",
                          "collective.time_share"}


def test_the_cell_is_correct_on_four_virtual_devices(cache):
    out = result_of(*run_cell(cache, *ARGS, "--seed", "3000000019",
                              "--trace", "1", devices=4))
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["count"] == 4 and out["attempted"] >= 2
    got = out["metrics"]
    assert got["rehearsal.rollout.alive_share"]["value"] == 1.0
    assert got["rehearsal.compile.programs_in_window"]["value"] == 0
    # a CPU trace has no device operation: the trace's readers (lm.*,
    # collective.*, stage.*) find nothing and their metrics are left out
    assert not any(name.startswith(("rehearsal.lm.", "rehearsal.collective."))
                   for name in got)


def test_end_to_end_metrics_without_a_trace(cache):
    out = result_of(*run_cell(cache, *ARGS, "--seed", "7", "--trace", "0",
                              devices=4))
    assert out["correct"] is True
    assert set(out["metrics"]) == {"rehearsal.steps_per_s_per_chip",
                                   "rehearsal.setup_s"}


def test_fewer_devices_than_four_is_refused(cache):
    p, lines = run_cell(cache, *ARGS, "--seed", "1", "--trace", "0",
                        devices=1)
    assert p.returncode != 0 and not any(ln.startswith("{") for ln in lines)
    assert "needs 4 chips" in p.stderr


def test_a_program_without_the_model_leaves_before_the_chips(cache, tmp_path):
    """What the parent commit does with this cell: the configuration names
    code the program does not have, so the run ends non-zero with no result
    line, and says what is absent, before jax is asked for a device."""
    bench = copy_of_the_benchmark(tmp_path)
    name = [w for w in bench["workloads"] if w["name"] == CELL][0]["config"]
    path = tmp_path / f"benchmark/configs/{name}.json"
    with open(path) as f:
        config = json.load(f)
    config["build"]["kwargs"]["policy"] = {
        "$import": "estorch_tpu.models.NoSuchLM"}
    with open(path, "w") as f:
        json.dump(config, f)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    p, lines = run_cell(cache, *ARGS, "--seed", "1", "--trace", "1",
                        devices=4, root=str(tmp_path),
                        extra_env={"PYTHONPATH": ROOT})
    assert p.returncode != 0 and not any(ln.startswith("{") for ln in lines)
    assert "has no estorch_tpu.models.NoSuchLM" in p.stderr
    assert not any("device bring-up" in ln for ln in lines)


def test_a_reference_with_another_seed_is_not_correct(cache):
    out = result_of(*run_cell(cache, *ARGS, "--seed", "3", "--trace", "0",
                              "--reference-seed", "4", devices=4))
    assert out["correct"] is False


@pytest.mark.parametrize("policy", ["coarse_lm.DroppedCorrectionLM",
                                    "coarse_lm.Fp8LM"])
def test_a_degraded_forward_is_not_correct(cache, tmp_path, policy):
    """The same configuration with one projection's rank-r correction left
    out, or with the FFN activations rounded to fp8, against the same
    plain reference: ``correct`` comes out false."""
    bench = copy_of_the_benchmark(tmp_path)
    name = [w for w in bench["workloads"] if w["name"] == CELL][0]["config"]
    path = tmp_path / f"benchmark/configs/{name}.json"
    with open(path) as f:
        config = json.load(f)
    config["build"]["kwargs"]["policy"] = {"$import": policy}
    with open(path, "w") as f:
        json.dump(config, f)
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    p, lines = run_cell(
        cache, *ARGS, "--seed", "3", "--trace", "0", devices=4,
        root=str(tmp_path),
        extra_env={"PYTHONPATH": ROOT + os.pathsep + HERE})
    assert result_of(p, lines)["correct"] is False
    assert any("reference, the measured program" in ln and "MISMATCH" in ln
               for ln in lines)
