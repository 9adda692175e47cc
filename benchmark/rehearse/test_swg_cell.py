"""The cell of the decoder whose two kinds of attention layer differ in head
count, band and rotation, with a gate a head, end to end on ONE virtual CPU
device at the configuration's rehearsal size (tiny widths, the dense layer
under full attention and a sparse sliding layer, a band of 32 over 128
positions, so the band bites).  Not a chip number: ``--rehearse`` is the only
way past the TPU check, and it prints every metric as ``rehearsal.<name>``.

The cell is added by files alone (configuration, reference, reader and costs
are new files; runner, traffic and ``run.py`` are untouched), comes out
``correct`` at three seeds, and comes out NOT ``correct`` in these
rehearsals: the reference given another seed, every projection's and
expert's input rounded to fp8, the float32 parts in bfloat16, the gate left
out, the full layer rotated over the whole head or under plain rope, the band
dropped or widened by a block, the full layers' grouping in the sliding layer,
a softmax router, the 2.5 left out, the shared expert left out, the held
experts taken for another rank's."""

import json
import os

import pytest
from test_cells import (ROOT, cache, copy_of_the_benchmark,  # noqa: F401
                        result_of, run_cell)

CELL = "laguna-xs2-es-16k-1chip"
CONFIG = "laguna-xs.2-33b-a3b-ep16"
SOURCE = "https://huggingface.co/poolside/Laguna-XS.2/blob/main/config.json"
HERE = os.path.dirname(os.path.abspath(__file__))
ARGS = ("--workload", CELL, "--seconds", "2", "--rehearse")
# what the reader computes; BENCHMARK.json had room for ONE of them (127 of
# its 128 per-layer entries were taken)
SWG_METRICS = [
    "swg.dense_share", "swg.sliding_attn_share", "swg.full_attn_share",
    "swg.gate_share", "swg.rope_share", "swg.route_share",
    "swg.dispatch_share", "swg.expert_share", "swg.head_share",
    "swg.dense_flops_util", "swg.sliding_attn_flops_util",
    "swg.full_attn_flops_util", "swg.expert_flops_util",
    "swg.head_flops_util"]
LISTED = ["swg.sliding_attn_share"]
FULL, SLIDING = "full_attention", "sliding_attention"
PUBLISHED = {
    "model_type": "laguna", "hidden_size": 2048, "intermediate_size": 8192,
    "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
    "max_position_embeddings": 262144, "attention_bias": False,
    "rms_norm_eps": 1e-06, "num_experts_per_tok": 8,
    "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
    "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
    "rope_parameters": {
        FULL: {"rope_theta": 500000, "rope_type": "yarn", "factor": 64,
               "original_max_position_embeddings": 4096, "beta_slow": 1,
               "beta_fast": 64, "attention_factor": 1.4158883083359672,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096},
    "layer_types": [FULL if i % 4 == 0 else SLIDING for i in range(40)],
    "moe_apply_router_weight_on_input": False, "partial_rotary_factor": 0.5,
    "mlp_layer_types": ["dense"] + ["sparse"] * 39,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [48 if i % 4 == 0 else 64
                                      for i in range(40)]}


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
    assert config[0]["reduced"] == ["num_hidden_layers", "num_experts",
                                    "vocab_size"]
    assert len(config[0]["why"]) <= 200
    assert [w["config"] for w in bench["workloads"]].count(CONFIG) == 1
    # appended where the lists ended, the eleventh of each: nothing that
    # was there moved (later cells follow)
    assert bench["workloads"][10] is cell[0]
    assert bench["configs"][10] is config[0]
    for path in (config[0]["file"],
                 "benchmark/reference/gated_window_moe_lm.py",
                 "benchmark/layers/swg.py", "benchmark/costs_swg.py"):
        assert os.path.exists(os.path.join(ROOT, path)), path


def test_the_swg_metric_names_this_cell_and_only_it():
    bench = _bench()
    ours = {m["name"]: m for m in bench["per_layer"]
            if m["name"].startswith("swg.")}
    assert list(ours) == LISTED and set(LISTED) <= set(SWG_METRICS)
    # appended after the 127 that were there, and the list is full
    assert [m["name"] for m in bench["per_layer"]][127:128] == LISTED
    assert len(bench["per_layer"]) <= 128
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
    three under ``reduced``; the three per-layer lists and the rope groups
    whole."""
    config = _config()
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"]["num_hidden_layers"] == 40
    assert config["published"]["num_experts"] == 256
    assert config["published"]["vocab_size"] == 100352
    assert (config["num_hidden_layers"], config["vocab_size"],
            config["num_experts"]) == (5, 12544, 16)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["num_experts"] * config["expert_group_size"] == 256
    assert config["deployment"]["expert_parallel_group"] == 16
    kwargs = config["build"]["kwargs"]
    policy = kwargs["policy_kwargs"]
    assert policy["layer_types"] == [FULL, SLIDING, SLIDING, SLIDING, FULL]
    # the lists the build is handed ARE the published ones, whole
    for key in ("mlp_layer_types", "num_attention_heads_per_layer",
                "rope_parameters"):
        assert policy[key] == config[key] == PUBLISHED[key], key
    for key in PUBLISHED:
        if key in policy and key != "layer_types":
            assert policy[key] == PUBLISHED[key], key
    assert (kwargs["model_shards"], kwargs["low_rank"], config["eval_chunk"],
            config["population_size"], config["horizon"]) == (
        1, 1, 0, 4, 16384)
    env = kwargs["agent_kwargs"]["env"]["kwargs"]
    assert env == {"vocab_size": 12544, "seq_len": 16384,
                   "corpus_sequences": 64, "seed": 0}
    assert config["deployment"]["mesh"] == {"pop": 1, "model": 1}
    assert config["deployment"]["state_bytes_per_chip"] == 14 * 490_297_344
    assert {"assumed", "departures", "reference_tolerance",
            "deployment"} <= set(config)
    for said in ("gate", "router", "norms, q/k norm, bias", "rotation",
                 "window", "experts", "initialisation", "sigma, optimizer",
                 "low_rank", "population_size", "corpus_seed and table_seed",
                 "table_size"):
        assert said in config["assumed"], said
    tiny = config["rehearsal_kwargs"]
    assert (tiny["policy_kwargs"]["sliding_window"] * 4
            == tiny["agent_kwargs"]["env"]["kwargs"]["seq_len"])
    assert tiny["policy_kwargs"]["layer_types"] == [FULL, SLIDING]
    assert tiny["policy_kwargs"]["mlp_layer_types"] == ["dense", "sparse"]
    tol = config["reference_tolerance"]
    assert 0 < tol["rtol"] < 0.05 and 0 < tol["behaviour_atol"] < 0.5
    assert "fp8" in tol["why"]


def test_the_reader_finds_nothing_in_a_program_without_the_scopes():
    """``layers/swg.py`` on a run that took no trace, one whose program names
    no stage, and ones of the other sequence models (no part ``of.sliding``
    under ``es.attn``): nothing, no raise.  On this model's program: the
    fourteen metrics from the exact pair counts, each kind's own heads, and
    the run's own routed rows; the gate's part leaves ``es.dense``."""
    from benchmark.files import load_file_module

    reader = load_file_module(os.path.join(ROOT, "benchmark/layers/swg.py"))

    def run(stage_s, ops=None, records=()):
        staged = {"busiest": "d0", "devices": {"d0": {
            "stage_s": stage_s, "busy_s": sum(stage_s.values()) or 1.0,
            "ops": ops or {}}}}
        return {"stage_reduce": {"staged": staged}, "chips": 1,
                "steps_per_generation": 65536, "traced_generations": 1,
                "dense_flops_per_member_step": 470_941_696,
                "head_flops_per_member_step": 51_380_224,
                "records": list(records),
                "peaks": {"peak_flops_per_s": 197e12,
                          "peak_hbm_bytes_per_s": 819e9}}

    def parts(stage, **named):
        return {stage: {f"fusion.{stage}.{i}": [
            s, 0, 0, f"jit(f)/es.policy/es.{stage}/of.{p}/x"]
            for i, (p, s) in enumerate(named.items())}}

    assert reader.read({"stage_reduce": None}) == {}
    assert reader.read({"trace": None}) == {}
    assert reader.read(run({"unscoped": 0.2})) == {}
    # the sparse-expert models: routes, experts, attention without parts
    assert reader.read(run({"dense": 0.2, "attn": 0.5, "route": 0.05,
                            "dispatch": 0.06, "expert": 0.04})) == {}
    # the router-ahead decoder: of.window and of.global
    assert reader.read(run({"dense": 0.2, "attn": 0.5, "route": 0.03},
                           parts("attn", window=0.3, **{"global": 0.2}))
                       ) == {}
    # the SambaY decoder: of.window beside of.full and of.cross
    assert reader.read(run({"dense": 0.7, "attn": 0.4, "ssm": 0.12},
                           parts("attn", window=0.1, full=0.2, cross=0.1))
                       ) == {}
    stage_s = {"dense": 0.25, "attn": 0.33, "rope": 0.02, "route": 0.01,
               "dispatch": 0.03, "expert": 0.03, "head": 0.02,
               "perturb": 0.1, "policy": 0.03, "update": 0.04,
               "unscoped": 0.05}
    ops = {"unscoped": {"ragged-dot-none.1": [0.04, 0, 0, ""],
                        "copy.3": [0.01, 0, 0, ""]},
           **parts("attn", sliding=0.11, full=0.22),
           **parts("dense", head_gate=0.015, q=0.1, o=0.135)}
    records = [{"routed_pairs": 131_000}, {"routed_pairs": 131_400}]
    got = reader.read(run(stage_s, ops, records))
    assert sorted(got) == sorted(SWG_METRICS)
    busy = sum(stage_s.values())
    assert abs(got["swg.sliding_attn_share"] - 0.11 / busy) < 1e-12
    assert abs(got["swg.full_attn_share"] - 0.22 / busy) < 1e-12
    assert abs(got["swg.gate_share"] - 0.015 / busy) < 1e-12
    assert abs(got["swg.dense_share"] - 0.235 / busy) < 1e-12
    assert abs(got["swg.expert_share"] - 0.07 / busy) < 1e-12
    want = 470_941_696 * 65536 / 0.235 / 197e12
    assert abs(got["swg.dense_flops_util"] - want) < 1e-12 and want < 1.0
    want = 51_380_224 * 65536 / 0.02 / 197e12
    assert abs(got["swg.head_flops_util"] - want) < 1e-12 and want < 1.0
    members = 65536 // 16384
    banded = 512 * 513 // 2 + (16384 - 512) * 512
    assert banded == 8_257_792
    want = 3 * banded * (2 * 64 * 256) * members / 0.11 / 197e12
    assert abs(got["swg.sliding_attn_flops_util"] - want) < 1e-12 and want < 1
    want = 2 * (16384 * 16385 // 2) * (2 * 48 * 256) * members / 0.22 / 197e12
    assert abs(got["swg.full_attn_flops_util"] - want) < 1e-12 and want < 1
    # the rows the run routed, not a uniform router's 131,072
    want = 131_200 * 2 * 3 * 2048 * 512 / 0.07 / 197e12
    assert abs(got["swg.expert_flops_util"] - want) < 1e-12 and want < 1.0
    expected = reader.read(run(stage_s, ops))
    want = 65536 * 4 * 0.5 * 2 * 3 * 2048 * 512 / 0.07 / 197e12
    assert abs(expected["swg.expert_flops_util"] - want) < 1e-12


def test_the_costs_are_from_shapes():
    """``costs_swg`` against a count of the mask, pair by pair, and each
    kind's own heads."""
    from benchmark import costs_swg
    from benchmark.costs_swa import visible_pairs

    assert visible_pairs(16384) == 134_225_920
    assert visible_pairs(16384, 512) == 8_257_792
    for t, w in [(7, 3), (12, 5), (3, 3), (9, 20), (40, 1), (33, 32)]:
        brute = sum(1 for q in range(t) for s in range(t)
                    if q - w < s <= q)
        assert visible_pairs(t, w) == brute
    assert costs_swg.attention_flops_per_pair(64, 128) == 32_768
    assert costs_swg.attention_flops_per_pair(48, 128) == 24_576
    kinds = [FULL, SLIDING, SLIDING, SLIDING, FULL]
    got = costs_swg.attention_flops_per_sequence(
        kinds, [48, 64, 64, 64, 48], 16384, 512, 128)
    assert got == {"sliding": 3 * 8_257_792 * 32_768,
                   "full": 2 * 134_225_920 * 24_576}
    # the three banded layers are an eighth of the attention's visible work
    assert 0.10 < got["sliding"] / (got["sliding"] + got["full"]) < 0.12
    # heads are a layer's own: the same kinds with other counts
    other = costs_swg.attention_flops_per_sequence(
        kinds, [48, 48, 48, 48, 48], 16384, 512, 128)
    assert other["sliding"] * 64 == got["sliding"] * 48
    assert other["full"] == got["full"]


@pytest.mark.parametrize("seed", ["3300000019", "7", "12"])
def test_the_cell_is_correct_on_one_virtual_device(cache, seed):
    trace = "1" if seed == "3300000019" else "0"
    p, lines = run_cell(cache, *ARGS, "--seed", seed, "--trace", trace)
    # ``layers/boot.py`` (PR 50) prints its ``[boot]`` lines without the
    # device's prefix, which ``result_of`` refuses in every cell's traced
    # rehearsal since; they are taken out before it reads the rest
    out = result_of(p, [ln for ln in lines if not ln.startswith("[boot]")])
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["count"] == 1 and out["attempted"] >= 2
    got = out["metrics"]
    if trace == "0":
        assert set(got) == {"rehearsal.steps_per_s_per_chip",
                            "rehearsal.setup_s"}
        return
    assert got["rehearsal.rollout.alive_share"]["value"] == 1.0
    assert got["rehearsal.compile.programs_in_window"]["value"] == 0
    # a CPU trace has no device operation: the trace's readers (swg.*,
    # stage.*) find nothing and their metrics are left out
    assert not any(name.startswith(("rehearsal.swg.", "rehearsal.swa.",
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
    _with_policy(tmp_path, "estorch_tpu.models.NoSuchGatedWindowMoELM")
    p, lines = run_cell(cache, *ARGS, "--seed", "1", "--trace", "1",
                        root=str(tmp_path), extra_env={"PYTHONPATH": ROOT})
    assert p.returncode != 0 and not any(ln.startswith("{") for ln in lines)
    assert "cannot run laguna-xs.2-33b-a3b-ep16" in p.stderr
    assert "has no estorch_tpu.models.NoSuchGatedWindowMoELM" in p.stderr
    assert not any("device bring-up" in ln for ln in lines)


def test_a_reference_with_another_seed_is_not_correct(cache):
    out = result_of(*run_cell(cache, *ARGS, "--seed", "3", "--trace", "0",
                              "--reference-seed", "4"))
    assert out["correct"] is False


@pytest.mark.parametrize("policy", [
    "coarse_swg.Fp8Swg", "coarse_swg.AllBf16Swg", "coarse_swg.NoGateSwg",
    "coarse_swg.WholeHeadRotationSwg", "coarse_swg.PlainRopeSwg",
    "coarse_swg.NoBandSwg", "coarse_swg.WiderBandSwg",
    "coarse_swg.FullGroupingSwg", "coarse_swg.SoftmaxRouterSwg",
    "coarse_swg.UnscaledRouterSwg", "coarse_swg.NoSharedSwg",
    "coarse_swg.OtherRankSwg"])
def test_a_degraded_forward_is_not_correct(cache, tmp_path, policy):
    """The same configuration with one degraded form as its policy, against
    the same plain reference: ``correct`` comes out false."""
    _with_policy(tmp_path, policy)
    p, lines = run_cell(
        cache, *ARGS, "--seed", "3", "--trace", "0", root=str(tmp_path),
        extra_env={"PYTHONPATH": ROOT + os.pathsep + HERE})
    assert result_of(p, lines)["correct"] is False
    assert any("reference, the measured program" in ln and "MISMATCH" in ln
               for ln in lines)
