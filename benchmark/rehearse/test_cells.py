"""Each cell end to end on the CPU at a tiny size.  Not a chip number:
``--rehearse`` is the only way past the TPU check, and it prints every
metric as ``rehearsal.<name>``."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(cache, *argv, root=ROOT, devices=1, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.update(extra_env or {})
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"), *argv],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    return p, lines


@pytest.fixture(scope="session")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("xla_cache")


def result_of(p, lines):
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    out = json.loads(lines[-1])
    assert KEYS <= set(out) <= KEYS | {"breakdown"}
    for name, m in out["metrics"].items():
        assert name.startswith("rehearsal."), name
        if name.endswith(("_share", "_util")):
            assert 0.0 <= m["value"] <= 1.0 and m["unit"] == "share"
    # every earlier line names the device
    assert all(ln.startswith("[cpu cpu x") for ln in lines[:-1])
    return out


def test_a_cpu_is_refused_without_the_rehearsal_flag(cache):
    p, lines = run_cell(cache, "--workload", "synth376-train-1chip",
                        "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert not any(ln.startswith("{") for ln in lines)
    assert "no TPU" in p.stderr


@pytest.mark.parametrize("cell", ["humanoid2d-train-1chip",
                                  "synth376-train-1chip"])
def test_one_chip_cells(cache, cell):
    out = result_of(*run_cell(cache, "--workload", cell, "--seed", "3",
                              "--seconds", "2", "--trace", "0", "--rehearse"))
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"rehearsal.steps_per_s_per_chip",
                                   "rehearsal.setup_s"}
    assert out["attempted"] >= 2


def test_traced_run_reports_the_per_layer_metrics(cache):
    out = result_of(*run_cell(cache, "--workload", "synth376-train-1chip",
                              "--seed", "4", "--seconds", "2", "--trace", "1",
                              "--rehearse"))
    got = set(out["metrics"])
    # records, clock and counters are read on any backend; a CPU trace has
    # no device operation, so its readers return nothing and are left out
    assert {"rehearsal.entry.gen_s_p50", "rehearsal.host.stall_share",
            "rehearsal.compile.programs_in_window",
            "rehearsal.rollout.alive_share"} <= got
    assert "rehearsal.gen.device_s" not in got
    assert out["metrics"]["rehearsal.compile.programs_in_window"]["value"] == 0
    assert out["metrics"]["rehearsal.rollout.alive_share"]["value"] == 1.0


def test_a_reference_with_another_seed_is_not_correct(cache):
    out = result_of(*run_cell(cache, "--workload", "synth376-train-1chip",
                              "--seed", "3", "--seconds", "1", "--trace", "0",
                              "--rehearse", "--reference-seed", "4"))
    assert out["correct"] is False


def copy_of_the_benchmark(tmp_path):
    """A temporary copy of the benchmark's own files, and its manifest."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def add_cell(tmp_path, bench, config, traffic="train"):
    """A throwaway configuration file and a cell on it: new files and new
    entries, no edit to a file that is there."""
    name = config["name"]
    with open(tmp_path / f"benchmark/configs/{name}.json", "w") as f:
        json.dump(config, f)
    bench["configs"].append({"name": name, "source": "none",
                             "file": f"benchmark/configs/{name}.json",
                             "reduced": config["reduced"], "why": "test"})
    bench["workloads"].append({"name": name + "-cell", "config": name,
                               "traffic": traffic, "chips": 1, "why": "test"})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return name + "-cell"


def four_chip_cell(tmp_path):
    """The cell a later PR adds across chips, in a temporary copy: an entry
    in ``workloads`` on the mix kept for it, and the collectives' metric
    for the reader that is there.  No cell of ``BENCHMARK.json`` takes four
    chips today: the contract's memory floor refused this one (PERF.md)."""
    bench = copy_of_the_benchmark(tmp_path)
    bench["workloads"].append({"name": "mesh-cell",
                               "config": "humanoid2d-mlp256",
                               "traffic": "train-mesh4", "chips": 4,
                               "why": "test"})
    bench["per_layer"].append({"name": "collective.time_share",
                               "unit": "share", "better": "lower",
                               "source": "device_trace",
                               "layer": "collectives",
                               "moves": "steps_per_s_per_chip",
                               "workloads": ["mesh-cell"]})
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return "mesh-cell"


def test_four_chip_cell_on_four_virtual_devices(cache, tmp_path):
    out = result_of(*run_cell(
        cache, "--workload", four_chip_cell(tmp_path), "--seed", "5",
        "--seconds", "2", "--trace", "1", "--rehearse", devices=4,
        root=str(tmp_path), extra_env={"PYTHONPATH": ROOT}))
    assert out["correct"] is True and out["device"]["count"] == 4
    # a CPU trace has no device operation: the collectives' reader finds
    # nothing and its metric is left out
    assert "rehearsal.collective.time_share" not in out["metrics"]
    assert "rehearsal.host.stall_share" in out["metrics"]


def test_fewer_devices_than_the_cell_asks_for_is_refused(cache, tmp_path):
    p, lines = run_cell(
        cache, "--workload", four_chip_cell(tmp_path), "--seed", "5",
        "--seconds", "1", "--trace", "0", "--rehearse", devices=1,
        root=str(tmp_path), extra_env={"PYTHONPATH": ROOT})
    assert p.returncode != 0 and not any(ln.startswith("{") for ln in lines)


WIDTHS_REFERENCE = '''
"""A reference of another policy family, as a later PR would bring it: it
reads its sizes from a key of its own (``widths``), not the MLP's keys."""
import os
from benchmark.files import load_file_module

mlp = load_file_module(os.path.join(os.path.dirname(__file__),
                                    "mlp_rollout.py"))
member_keys = mlp.member_keys


def as_mlp(config):
    w = config["widths"]
    return {**config, "obs_dim": w[0], "hidden": w[1:-1],
            "action_dim": w[-1]}


def describe(config):
    return mlp.describe(as_mlp(config))


def init_theta(key, config):
    return mlp.init_theta(key, as_mlp(config))


def make_reference(env, config, horizon, obs_clip=None):
    return mlp.make_reference(env, as_mlp(config), horizon, obs_clip)
'''


def test_a_cell_is_added_by_files_alone(cache, tmp_path):
    """A throwaway configuration with a reference module of its own, a
    traffic mix, a cell and a per-layer reader in a temporary copy: new
    files and new entries, no edit to a file that is there.  The
    configuration has none of the MLP reference's keys, so the runner
    takes nothing about the policy family but what the module says."""
    bench = copy_of_the_benchmark(tmp_path)
    with open(tmp_path / "benchmark/configs/synth376-mlp256.json") as f:
        config = json.load(f)
    for key in ("obs_dim", "hidden", "action_dim"):
        del config[key]
    config.update(name="throwaway", widths=[376, 32, 17],
                  reference="widths_rollout")
    config["build"]["kwargs"]["policy_kwargs"]["hidden"] = {"$tuple": [32]}
    with open(tmp_path / "benchmark/reference/widths_rollout.py", "w") as f:
        f.write(WIDTHS_REFERENCE)
    with open(tmp_path / "benchmark/traffic/train-short.json", "w") as f:
        json.dump({"kind": "train", "warmup_generations": 1,
                   "generations_per_call": 1, "trace_generations": 1}, f)
    with open(tmp_path / "benchmark/layers/throwaway.py", "w") as f:
        f.write("def read(run):\n"
                "    return {'throwaway.readings': len(run['records'])}\n")
    bench["per_layer"].append({"name": "throwaway.readings", "unit": "count",
                               "better": "higher", "source": "host_clock",
                               "layer": "throwaway",
                               "moves": "steps_per_s_per_chip",
                               "workloads": ["throwaway-cell"]})
    cell = add_cell(tmp_path, bench, config, traffic="train-short")
    out = result_of(*run_cell(
        cache, "--workload", cell, "--seed", "1", "--seconds",
        "1", "--trace", "1", "--rehearse", root=str(tmp_path),
        extra_env={"PYTHONPATH": ROOT}))
    assert out["correct"] is True
    assert out["metrics"]["rehearsal.throwaway.readings"]["value"] >= 1
    assert "rehearsal.policy.flops_util" not in out["metrics"]  # no trace
    assert "rehearsal.collective.time_share" not in out["metrics"]
    assert out["metrics"]["rehearsal.setup.bring_up_s"]["value"] >= 0


COARSE = '''
import jax.numpy as jnp


def fp8_tanh(x):
    """tanh rounded to float8_e4m3 (3 bits of mantissa): the hidden
    activations of a lower-precision forward; the weights stay as they
    are, so this is the mildest form of one."""
    return jnp.tanh(x).astype(jnp.float8_e4m3fn).astype(x.dtype)
'''


@pytest.mark.parametrize("name, failing_check", [
    ("humanoid2d-mlp256", "first steps"),
    ("synth376-mlp256", "the measured program")])
def test_a_lower_precision_forward_is_not_correct(cache, tmp_path, name,
                                                  failing_check):
    """The same configuration with its hidden activations rounded to fp8,
    against the same plain reference: ``correct`` comes out false, by the
    check the configuration relies on for precision."""
    bench = copy_of_the_benchmark(tmp_path)
    with open(tmp_path / f"benchmark/configs/{name}.json") as f:
        config = json.load(f)
    config["name"] = "coarse"
    config["build"]["kwargs"]["policy_kwargs"] = {
        "action_dim": config["action_dim"],
        "hidden": {"$tuple": config["hidden"]}, "discrete": False,
        "action_scale": config["action_scale"],
        "activation": {"$import": "coarse.fp8_tanh"}}
    with open(tmp_path / "coarse.py", "w") as f:
        f.write(COARSE)
    cell = add_cell(tmp_path, bench, config)
    p, lines = run_cell(
        cache, "--workload", cell, "--seed", "3", "--seconds", "1",
        "--trace", "0", "--rehearse", root=str(tmp_path),
        extra_env={"PYTHONPATH": ROOT + os.pathsep + str(tmp_path)})
    assert result_of(p, lines)["correct"] is False
    verdicts = [ln for ln in lines if "reference, " in ln
                and ("MISMATCH" in ln or ": ok" in ln)]
    assert any(f"reference, {failing_check}:" in ln and "MISMATCH" in ln
               for ln in verdicts), verdicts


def test_without_the_program_there_is_no_result(cache, tmp_path):
    """In a directory that holds only BENCHMARK.json and the benchmark's
    own files, the run fails and prints no result."""
    copy_of_the_benchmark(tmp_path)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p, lines = run_cell(cache, "--workload", "synth376-train-1chip", "--seed",
                        "1", "--seconds", "1", "--trace", "0", "--rehearse",
                        root=str(tmp_path), extra_env={"PYTHONPATH": ""})
    assert p.returncode != 0 and not any(ln.startswith("{") for ln in lines)
