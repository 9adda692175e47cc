"""The cell of the decoder whose router reads the layer's input ahead of
attention, end to end on ONE virtual CPU device at the configuration's
rehearsal size (tiny widths, a global and a window layer, a band of 32 over
128 positions, so the band bites).  Not a chip number: ``--rehearse`` is the
only way past the TPU check, and it prints every metric as
``rehearsal.<name>``.

The cell is added by files alone (configuration, reference, reader and costs
are new files; runner, traffic and ``run.py`` are untouched), comes out
``correct`` at three seeds, and comes out NOT ``correct`` in these
rehearsals: the reference given another seed, every projection's and
expert's input rounded to fp8, the float32 parts in bfloat16, a band of half
and of twice the width, rotation on the global layer, SiLU for ReLU, the
routes taken after attention, the held experts taken for another rank's."""

import json
import os

import pytest
from test_cells import (ROOT, cache, copy_of_the_benchmark,  # noqa: F401
                        result_of, run_cell)

CELL = "smallthinker-es-16k-1chip"
CONFIG = "smallthinker-21b-a3b-ep4"
SOURCE = ("https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct/"
          "blob/main/config.json")
HERE = os.path.dirname(os.path.abspath(__file__))
ARGS = ("--workload", CELL, "--seconds", "2", "--rehearse")
SWA_METRICS = [
    "swa.dense_share", "swa.window_attn_share", "swa.global_attn_share",
    "swa.rope_share", "swa.route_share", "swa.dispatch_share",
    "swa.expert_share", "swa.head_share", "swa.dense_flops_util",
    "swa.expert_flops_util", "swa.window_attn_flops_util",
    "swa.global_attn_flops_util", "swa.head_flops_util"]


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
    config = [c for c in bench["configs"] if c["name"] == CONFIG]
    assert len(config) == 1 and config[0]["source"] == SOURCE
    assert config[0]["reduced"] == ["num_hidden_layers",
                                    "moe_num_primary_experts", "vocab_size"]
    assert len(config[0]["why"]) <= 200
    assert [w["config"] for w in bench["workloads"]].count(CONFIG) == 1
    # the last of their lists: nothing that was there moved
    assert bench["workloads"][-1] is cell[0]
    assert bench["configs"][-1] is config[0]
    for path in (config[0]["file"], "benchmark/reference/window_moe_lm.py",
                 "benchmark/layers/swa.py", "benchmark/costs_swa.py"):
        assert os.path.exists(os.path.join(ROOT, path)), path


def test_the_swa_metrics_name_this_cell_and_only_it():
    bench = _bench()
    ours = {m["name"]: m for m in bench["per_layer"]
            if m["name"].startswith("swa.")}
    assert list(ours) == SWA_METRICS
    assert [m["name"] for m in bench["per_layer"]][-13:] == SWA_METRICS
    for m in ours.values():
        assert m["workloads"] == [CELL]
        assert m["moves"] == "steps_per_s_per_chip"
        assert m["layer"] == "policy forward" and m["unit"] == "share"
        assert m["source"] == "device_trace"
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["better"] == ("higher" if m["name"].endswith("_util")
                               else "lower")
    others = [m["name"] for m in bench["per_layer"]
              if CELL in m.get("workloads", []) and m["name"] not in ours]
    assert others == []


def test_the_configuration_file_keeps_every_published_key():
    """Every key of the catalog's ``config`` at its published value but the
    three under ``reduced``; the two layouts whole."""
    config = _config()
    layout = [int(i % 4 != 0) for i in range(52)]
    published = dict(
        head_dim=128, hidden_size=2560, max_position_embeddings=16384,
        model_name="smallthinker_21b_instruct", moe_ffn_hidden_size=768,
        moe_num_active_primary_experts=6,
        moe_primary_router_apply_softmax=True, norm_topk_prob=True,
        num_attention_heads=28, num_key_value_heads=4, rms_norm_eps=1e-06,
        rope_layout=layout, rope_scaling=None, rope_theta=1500000,
        sliding_window_layout=layout, sliding_window_size=4096,
        tie_word_embeddings=False)
    for key, value in published.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers",
                                 "moe_num_primary_experts", "vocab_size"]
    assert config["published"]["num_hidden_layers"] == 52
    assert config["published"]["moe_num_primary_experts"] == 64
    assert config["published"]["vocab_size"] == 151936
    assert (config["num_hidden_layers"], config["vocab_size"]) == (4, 37984)
    assert config["vocab_size"] * 4 == config["published"]["vocab_size"]
    held = config["moe_num_primary_experts"]
    assert held == 16 and held * config["expert_group_size"] == 64
    assert config["deployment"]["expert_parallel_group"] == 4
    assert config["layer_types"] == ["window" if b else "global"
                                     for b in layout]
    kwargs = config["build"]["kwargs"]
    policy = kwargs["policy_kwargs"]
    assert policy["layer_types"] == ["global", "window", "window", "window"]
    for key in published:
        if key in policy:
            assert policy[key] == published[key], key
    assert (kwargs["model_shards"], kwargs["low_rank"], config["eval_chunk"],
            config["population_size"], config["horizon"]) == (
        1, 1, 0, 4, 16384)
    assert config["deployment"]["mesh"] == {"pop": 1, "model": 1}
    assert config["deployment"]["state_bytes_per_chip"] == 14 * 656_529_920
    assert {"assumed", "departures", "reference_tolerance",
            "deployment"} <= set(config)
    for said in ("layer_types", "no q/k norm, no bias", "rotation", "window",
                 "global layers", "router", "experts", "initialisation",
                 "sigma, optimizer", "population_size",
                 "corpus_seed and table_seed"):
        assert said in config["assumed"], said
    tiny = config["rehearsal_kwargs"]
    assert (tiny["policy_kwargs"]["sliding_window_size"] * 4
            == tiny["agent_kwargs"]["env"]["kwargs"]["seq_len"])
    assert tiny["policy_kwargs"]["layer_types"] == ["global", "window"]
    tol = config["reference_tolerance"]
    assert 0 < tol["rtol"] < 0.05 and 0 < tol["behaviour_atol"] < 0.5
    assert "fp8" in tol["why"]


def test_the_reader_finds_nothing_in_a_program_without_the_scopes():
    """``layers/swa.py`` on a run that took no trace, one whose program names
    no stage, and ones of the other sequence models (no part ``of.global``
    under ``es.attn``: the SambaY decoder names ``of.window`` too): nothing,
    no raise.  On this model's program: the thirteen metrics from the exact
    pair counts and the run's own routed rows."""
    from benchmark.files import load_file_module

    reader = load_file_module(os.path.join(ROOT, "benchmark/layers/swa.py"))

    def run(stage_s, ops=None, records=()):
        staged = {"busiest": "d0", "devices": {"d0": {
            "stage_s": stage_s, "busy_s": sum(stage_s.values()) or 1.0,
            "ops": ops or {}}}}
        return {"stage_reduce": {"staged": staged}, "chips": 1,
                "steps_per_generation": 65536, "traced_generations": 1,
                "dense_flops_per_member_step": 167_772_160,
                "head_flops_per_member_step": 194_478_080,
                "records": list(records),
                "peaks": {"peak_flops_per_s": 197e12,
                          "peak_hbm_bytes_per_s": 819e9}}

    def attn(**parts):
        return {"attn": {f"fusion.{i}": [s, 0, 0,
                                         f"jit(f)/es.policy/es.attn/of.{p}/x"]
                         for i, (p, s) in enumerate(parts.items())}}

    assert reader.read({"stage_reduce": None}) == {}
    assert reader.read({"trace": None}) == {}
    assert reader.read(run({"unscoped": 0.2})) == {}
    assert reader.read(run({"policy": 0.1, "dense": 0.5, "attn": 0.1,
                            "ssm": 0.2, "head": 0.1})) == {}
    # the sparse-expert models: routes, experts, attention without parts
    assert reader.read(run({"dense": 0.2, "attn": 0.5, "route": 0.05,
                            "dispatch": 0.06, "expert": 0.04})) == {}
    # the decoder over a selection: es.attn in the part of.selected
    assert reader.read(run({"dense": 0.2, "attn": 0.5, "index": 0.3},
                           attn(selected=0.5))) == {}
    # the SambaY decoder: of.window beside of.full and of.cross
    assert reader.read(run({"dense": 0.7, "attn": 0.4, "ssm": 0.12,
                            "diff": 0.01, "head": 0.06},
                           attn(window=0.1, full=0.2, cross=0.1))) == {}
    stage_s = {"dense": 0.12, "attn": 0.6, "rope": 0.02, "route": 0.01,
               "dispatch": 0.05, "expert": 0.03, "head": 0.08,
               "perturb": 0.1, "policy": 0.03, "update": 0.04,
               "unscoped": 0.05}
    ops = {"unscoped": {"ragged-dot-none.1": [0.04, 0, 0, ""],
                        "copy.3": [0.01, 0, 0, ""]},
           **attn(window=0.42, **{"global": 0.18})}
    records = [{"routed_pairs": 393_000}, {"routed_pairs": 394_000}]
    got = reader.read(run(stage_s, ops, records))
    assert sorted(got) == sorted(SWA_METRICS)
    busy = sum(stage_s.values())
    assert abs(got["swa.window_attn_share"] - 0.42 / busy) < 1e-12
    assert abs(got["swa.global_attn_share"] - 0.18 / busy) < 1e-12
    assert abs(got["swa.expert_share"] - 0.07 / busy) < 1e-12
    want = 167_772_160 * 65536 / 0.12 / 197e12
    assert abs(got["swa.dense_flops_util"] - want) < 1e-12 and want < 1.0
    want = 194_478_080 * 65536 / 0.08 / 197e12
    assert abs(got["swa.head_flops_util"] - want) < 1e-12 and want < 1.0
    members = 65536 // 16384
    banded = 4096 * 4097 // 2 + (16384 - 4096) * 4096
    want = 3 * banded * 14336 * members / 0.42 / 197e12
    assert abs(got["swa.window_attn_flops_util"] - want) < 1e-12 and want < 1
    want = (16384 * 16385 // 2) * 14336 * members / 0.18 / 197e12
    assert abs(got["swa.global_attn_flops_util"] - want) < 1e-12 and want < 1
    # the rows the run routed, not a uniform router's 393,216
    want = 393_500 * 2 * 3 * 2560 * 768 / 0.07 / 197e12
    assert abs(got["swa.expert_flops_util"] - want) < 1e-12 and want < 1.0
    expected = reader.read(run(stage_s, ops))
    want = 65536 * 4 * 1.5 * 2 * 3 * 2560 * 768 / 0.07 / 197e12
    assert abs(expected["swa.expert_flops_util"] - want) < 1e-12


def test_the_costs_are_from_shapes():
    """``costs_swa`` against a count of the mask, pair by pair."""
    from benchmark import costs_swa

    assert costs_swa.visible_pairs(16384) == 134_225_920
    assert costs_swa.visible_pairs(16384, 4096) == 58_722_304
    for t, w in [(7, 3), (12, 5), (3, 3), (9, 20), (40, 1), (33, 32)]:
        brute = sum(1 for q in range(t) for s in range(t)
                    if q - w < s <= q)
        assert costs_swa.visible_pairs(t, w) == brute
        assert costs_swa.visible_pairs(t) == sum(
            1 for q in range(t) for s in range(t) if s <= q)
    assert costs_swa.attention_flops_per_pair(28, 128) == 14336
    got = costs_swa.attention_flops_per_sequence(
        ["global", "window", "window", "window"], 16384, 4096, 28, 128)
    assert got == {"window": 3 * 58_722_304 * 14336,
                   "global": 134_225_920 * 14336}
    # three quarters of the attention's visible pairs are under the band
    assert 0.56 < got["window"] / (got["window"] + got["global"]) < 0.57


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
    # a CPU trace has no device operation: the trace's readers (swa.*,
    # stage.*) find nothing and their metrics are left out
    assert not any(name.startswith(("rehearsal.swa.", "rehearsal.moe.",
                                    "rehearsal.dsa.")) for name in got)
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
    _with_policy(tmp_path, "estorch_tpu.models.NoSuchWindowMoELM")
    p, lines = run_cell(cache, *ARGS, "--seed", "1", "--trace", "1",
                        root=str(tmp_path), extra_env={"PYTHONPATH": ROOT})
    assert p.returncode != 0 and not any(ln.startswith("{") for ln in lines)
    assert "cannot run smallthinker-21b-a3b-ep4" in p.stderr
    assert "has no estorch_tpu.models.NoSuchWindowMoELM" in p.stderr
    assert not any("device bring-up" in ln for ln in lines)


def test_a_reference_with_another_seed_is_not_correct(cache):
    out = result_of(*run_cell(cache, *ARGS, "--seed", "3", "--trace", "0",
                              "--reference-seed", "4"))
    assert out["correct"] is False


@pytest.mark.parametrize("policy", [
    "coarse_swa.Fp8Swa", "coarse_swa.AllBf16Swa", "coarse_swa.HalfWindowSwa",
    "coarse_swa.DoubleWindowSwa", "coarse_swa.RotatedGlobalSwa",
    "coarse_swa.SiluSwa", "coarse_swa.RoutesAfterSwa",
    "coarse_swa.OtherRankSwa"])
def test_a_degraded_forward_is_not_correct(cache, tmp_path, policy):
    """The same configuration with every projection's and expert's input
    rounded to fp8, the float32 parts in bfloat16, a band of half or twice
    the width, the global layer rotated, SiLU for ReLU, the routes taken
    after attention or the held experts of another rank, against the same
    plain reference: ``correct`` comes out false."""
    _with_policy(tmp_path, policy)
    p, lines = run_cell(
        cache, *ARGS, "--seed", "3", "--trace", "0", root=str(tmp_path),
        extra_env={"PYTHONPATH": ROOT + os.pathsep + HERE})
    assert result_of(p, lines)["correct"] is False
    assert any("reference, the measured program" in ln and "MISMATCH" in ln
               for ln in lines)
