"""The cell of the decoder of gated-delta-rule layers beside a gated
full-attention layer, end to end on ONE virtual CPU device at the
configuration's rehearsal size (tiny widths, a linear and a full layer, 128
positions in two chunks of 64).  Not a chip number: ``--rehearse`` is the only
way past the TPU check, and it prints every metric as ``rehearsal.<name>``.

The cell is added by files alone (configuration, reference, reader and costs
are new files; runner, traffic and ``run.py`` are untouched), comes out
``correct`` at three seeds, and comes out NOT ``correct`` in these
rehearsals: the reference given another seed, every projection's and
expert's input rounded to fp8, the float32 parts in bfloat16, the correction
or the decay dropped, the state reset at chunk boundaries, the conv or its
SiLU left out, q and k not normalised, the gated norm's gate, the attention's
output gate or the shared expert's sigmoid left out, the rotation over the
whole head."""

import json
import os

import pytest
from test_cells import (ROOT, cache, copy_of_the_benchmark,  # noqa: F401
                        result_of, run_cell)

CELL = "qwen3next-es-16k-1chip"
CONFIG = "qwen3-next-80b-a3b-ep16"
SOURCE = ("https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/"
          "blob/main/config.json")
HERE = os.path.dirname(os.path.abspath(__file__))
ARGS = ("--workload", CELL, "--seconds", "2", "--rehearse")
GDN_METRICS = [
    "gdn.dense_share", "gdn.delta_share", "gdn.conv_share",
    "gdn.solve_share", "gdn.carry_share", "gdn.attn_share", "gdn.rope_share",
    "gdn.route_share", "gdn.dispatch_share", "gdn.expert_share",
    "gdn.head_share", "gdn.dense_flops_util", "gdn.expert_flops_util",
    "gdn.attn_flops_util", "gdn.head_flops_util", "gdn.delta_flops_util",
    "gdn.delta_hbm_util"]
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts_per_tok": 10,
    "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False}


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
    # appended where the lists ended, the tenth of each: nothing that was
    # there moved (later cells follow)
    assert bench["workloads"][9] is cell[0]
    assert bench["configs"][9] is config[0]
    for path in (config[0]["file"], "benchmark/reference/delta_moe_lm.py",
                 "benchmark/layers/gdn.py", "benchmark/costs_gdn.py"):
        assert os.path.exists(os.path.join(ROOT, path)), path


def test_the_gdn_metrics_name_this_cell_and_only_it():
    bench = _bench()
    ours = {m["name"]: m for m in bench["per_layer"]
            if m["name"].startswith("gdn.")}
    assert list(ours) == GDN_METRICS
    # appended after the 110 that were there (later metrics follow)
    assert [m["name"] for m in bench["per_layer"]][110:127] == GDN_METRICS
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
    three under ``reduced``."""
    config = _config()
    for key, value in PUBLISHED.items():
        assert config[key] == value, key
    assert config["reduced"] == ["num_hidden_layers", "num_experts",
                                 "vocab_size"]
    assert config["published"]["num_hidden_layers"] == 48
    assert config["published"]["num_experts"] == 512
    assert config["published"]["vocab_size"] == 151936
    assert (config["num_hidden_layers"], config["vocab_size"],
            config["num_experts"]) == (4, 18992, 32)
    assert config["vocab_size"] * 8 == config["published"]["vocab_size"]
    assert config["num_experts"] * config["expert_group_size"] == 512
    assert config["deployment"]["expert_parallel_group"] == 16
    assert config["layer_types"] == [
        "full" if (i + 1) % 4 == 0 else "linear" for i in range(48)]
    kwargs = config["build"]["kwargs"]
    policy = kwargs["policy_kwargs"]
    assert policy["layer_types"] == ["linear", "linear", "linear", "full"]
    for key in PUBLISHED:
        if key in policy:
            assert policy[key] == PUBLISHED[key], key
    assert (kwargs["model_shards"], kwargs["low_rank"], config["eval_chunk"],
            config["population_size"], config["horizon"]) == (
        1, 1, 0, 4, 16384)
    assert config["deployment"]["mesh"] == {"pop": 1, "model": 1}
    assert config["deployment"]["state_bytes_per_chip"] == 14 * 625_667_136
    assert {"assumed", "departures", "reference_tolerance",
            "deployment"} <= set(config)
    for said in ("layer_types", "zero-centred norms", "W_qkvz column order",
                 "conv", "delta rule", "full attention", "router",
                 "initialisation", "sigma, optimizer", "population_size",
                 "corpus_seed and table_seed"):
        assert said in config["assumed"], said
    assert any("MTP" in d for d in config["departures"])
    tiny = config["rehearsal_kwargs"]
    assert tiny["policy_kwargs"]["layer_types"] == ["linear", "full"]
    assert (tiny["policy_kwargs"]["delta_chunk"] * 2
            == tiny["agent_kwargs"]["env"]["kwargs"]["seq_len"])
    tol = config["reference_tolerance"]
    assert 0 < tol["rtol"] < 0.05 and 0 < tol["behaviour_atol"] < 0.5
    assert "fp8" in tol["why"]


def test_the_reader_finds_nothing_in_a_program_without_the_scopes():
    """``layers/gdn.py`` on a run that took no trace, one whose program names
    no stage, and ones of the other sequence models (no part ``of.solve``
    under ``es.ssm``: Mamba-2's and Mamba-1's scans name no part): nothing,
    no raise.  On this model's program: the seventeen metrics from the exact
    counts and the run's own routed rows."""
    from benchmark import costs_gdn
    from benchmark.files import load_file_module

    reader = load_file_module(os.path.join(ROOT, "benchmark/layers/gdn.py"))

    def run(stage_s, ops=None, records=()):
        staged = {"busiest": "d0", "devices": {"d0": {
            "stage_s": stage_s, "busy_s": sum(stage_s.values()) or 1.0,
            "ops": ops or {}}}}
        return {"stage_reduce": {"staged": staged}, "chips": 1,
                "steps_per_generation": 65536, "traced_generations": 1,
                "dense_flops_per_member_step": 281_821_184,
                "head_flops_per_member_step": 77_791_232,
                "records": list(records),
                "peaks": {"peak_flops_per_s": 197e12,
                          "peak_hbm_bytes_per_s": 819e9}}

    def ssm(**parts):
        return {"ssm": {f"fusion.{i}": [s, 0, 0,
                                        f"jit(f)/es.policy/es.ssm/of.{p}/x"]
                        for i, (p, s) in enumerate(parts.items())}}

    assert reader.read({"stage_reduce": None}) == {}
    assert reader.read({"trace": None}) == {}
    assert reader.read(run({"unscoped": 0.2})) == {}
    # the Mamba-2 hybrid and the SambaY decoder: es.ssm without a part
    assert reader.read(run({"policy": 0.1, "dense": 0.5, "attn": 0.1,
                            "ssm": 0.2, "head": 0.1},
                           {"ssm": {"fusion.1": [
                               0.2, 0, 0, "jit(f)/es.policy/es.ssm/x"]}})
                       ) == {}
    # the sparse-expert models: routes, experts, no scan at all
    assert reader.read(run({"dense": 0.2, "attn": 0.5, "route": 0.05,
                            "dispatch": 0.06, "expert": 0.04})) == {}
    stage_s = {"dense": 0.22, "ssm": 0.31, "attn": 0.09, "rope": 0.01,
               "route": 0.02, "dispatch": 0.03, "expert": 0.02, "head": 0.05,
               "perturb": 0.1, "policy": 0.03, "update": 0.04,
               "unscoped": 0.05}
    ops = {"unscoped": {"ragged-dot-none.1": [0.04, 0, 0, ""],
                        "copy.3": [0.01, 0, 0, ""]},
           **ssm(conv=0.04, decay=0.03, solve=0.08, carry=0.12, gate=0.04)}
    records = [{"routed_pairs": 163_000}, {"routed_pairs": 164_000}]
    got = reader.read(run(stage_s, ops, records))
    assert sorted(got) == sorted(GDN_METRICS)
    busy = sum(stage_s.values())
    assert abs(got["gdn.delta_share"] - 0.31 / busy) < 1e-12
    assert abs(got["gdn.solve_share"] - 0.08 / busy) < 1e-12
    assert abs(got["gdn.carry_share"] - 0.12 / busy) < 1e-12
    assert abs(got["gdn.conv_share"] - 0.04 / busy) < 1e-12
    assert abs(got["gdn.expert_share"] - 0.06 / busy) < 1e-12
    want = 281_821_184 * 65536 / 0.22 / 197e12
    assert abs(got["gdn.dense_flops_util"] - want) < 1e-12 and want < 1.0
    want = 77_791_232 * 65536 / 0.05 / 197e12
    assert abs(got["gdn.head_flops_util"] - want) < 1e-12 and want < 1.0
    members = 65536 // 16384
    want = 134_225_920 * 2 * 16 * 512 * members / 0.09 / 197e12
    assert abs(got["gdn.attn_flops_util"] - want) < 1e-12 and want < 1
    rule = costs_gdn.delta_rule_flops_per_sequence(
        ["linear"] * 3 + ["full"], 16384, 64, 16, 32, 128, 128)
    want = rule * members / 0.20 / 197e12
    assert abs(got["gdn.delta_flops_util"] - want) < 1e-12 and want < 1
    want = 3 * 4 * 16384 * (2 * 2048 + 2 * 4096 + 64) * members / 0.20 / 819e9
    assert abs(got["gdn.delta_hbm_util"] - want) < 1e-12 and want < 1
    # the rows the run routed, not a uniform router's 163,840
    want = 163_500 * 2 * 3 * 2048 * 512 / 0.06 / 197e12
    assert abs(got["gdn.expert_flops_util"] - want) < 1e-12 and want < 1.0
    expected = reader.read(run(stage_s, ops))
    want = 65536 * 4 * 0.625 * 2 * 3 * 2048 * 512 / 0.06 / 197e12
    assert abs(expected["gdn.expert_flops_util"] - want) < 1e-12


def test_the_costs_are_from_shapes():
    """``costs_gdn`` against a brute-force count, multiply-add by
    multiply-add, of the chunked rule's terms and of the causal pairs."""
    from benchmark import costs_gdn

    assert costs_gdn.visible_pairs(16384) == 134_225_920
    assert costs_gdn.attention_flops_per_pair(16, 256) == 16384
    assert costs_gdn.attention_flops_per_sequence(
        ["linear", "linear", "linear", "full"], 16384, 16, 256) == (
        134_225_920 * 16384)
    assert costs_gdn.triangular_inverse_flops(64) == 87_360
    for rows, nk, nv, dk, dv in [(4, 1, 2, 3, 5), (7, 2, 4, 2, 3),
                                 (1, 1, 1, 2, 2)]:
        count = dict.fromkeys(("k_kt", "q_kt", "inverse", "w", "u", "v_new",
                               "out_state", "state", "out_inside"), 0)
        for i in range(rows):
            for j in range(rows):
                if j < i:
                    count["k_kt"] += nk * dk        # K K^T below the diagonal
                    count["inverse"] += nv * (i - j)    # X_ij by substitution
                if j <= i:
                    count["q_kt"] += nk * dk
                    count["w"] += nv * dk           # T (beta K e^gamma)
                    count["u"] += nv * dv           # T (beta V)
                    count["out_inside"] += nv * dv  # (Q K^T o decay) V'
            for name in ("v_new", "out_state", "state"):
                count[name] += nv * dk * dv         # against the state
        assert costs_gdn.delta_rule_terms(rows, nk, nv, dk, dv) == {
            name: 2 * n for name, n in count.items()}
    one = sum(costs_gdn.delta_rule_terms(64, 16, 32, 128, 128).values())
    # about 4.25 MFLOP a token, three quarters of it against the state
    assert 4.1e6 < one / 64 < 4.4e6
    assert costs_gdn.delta_rule_flops_per_sequence(
        ["linear"] * 3 + ["full"], 16384, 64, 16, 32, 128, 128) == (
        3 * 256 * one)
    # a last short chunk is counted at its own length
    short = sum(costs_gdn.delta_rule_terms(36, 16, 32, 128, 128).values())
    assert costs_gdn.delta_rule_flops_per_sequence(
        ["linear"], 100, 64, 16, 32, 128, 128) == one + short
    assert costs_gdn.delta_rule_bytes_per_sequence(
        ["linear"] * 3 + ["full"], 16384, 16, 32, 128, 128) == (
        3 * 4 * 16384 * (2048 + 2048 + 4096 + 4096 + 32 + 32))


@pytest.mark.parametrize("seed", ["3300000019", "7", "12"])
def test_the_cell_is_correct_on_one_virtual_device(cache, seed):
    trace = "1" if seed == "3300000019" else "0"
    p, lines = run_cell(cache, *ARGS, "--seed", seed, "--trace", trace)
    # (a traced run's readers say what they read under their own names)
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
    # a CPU trace has no device operation: the trace's readers (gdn.*,
    # stage.*) find nothing and their metrics are left out
    assert not any(name.startswith(("rehearsal.gdn.", "rehearsal.swa.",
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
    _with_policy(tmp_path, "estorch_tpu.models.NoSuchDeltaMoELM")
    p, lines = run_cell(cache, *ARGS, "--seed", "1", "--trace", "1",
                        root=str(tmp_path), extra_env={"PYTHONPATH": ROOT})
    assert p.returncode != 0 and not any(ln.startswith("{") for ln in lines)
    assert "cannot run qwen3-next-80b-a3b-ep16" in p.stderr
    assert "has no estorch_tpu.models.NoSuchDeltaMoELM" in p.stderr
    assert not any("device bring-up" in ln for ln in lines)


def test_a_reference_with_another_seed_is_not_correct(cache):
    out = result_of(*run_cell(cache, *ARGS, "--seed", "3", "--trace", "0",
                              "--reference-seed", "4"))
    assert out["correct"] is False


@pytest.mark.parametrize("policy", [
    "coarse_gdn.Fp8Gdn", "coarse_gdn.AllBf16Gdn",
    "coarse_gdn.NoCorrectionGdn", "coarse_gdn.NoDecayGdn",
    "coarse_gdn.ChunkResetGdn", "coarse_gdn.NoConvGdn",
    "coarse_gdn.NoConvSiluGdn", "coarse_gdn.NoQkNormGdn",
    "coarse_gdn.NoNormGateGdn", "coarse_gdn.NoOutputGateGdn",
    "coarse_gdn.WholeHeadRotationGdn", "coarse_gdn.NoSharedSigmoidGdn"])
def test_a_degraded_forward_is_not_correct(cache, tmp_path, policy):
    """The same configuration with a degraded forward against the same
    plain reference: ``correct`` comes out false."""
    _with_policy(tmp_path, policy)
    p, lines = run_cell(
        cache, *ARGS, "--seed", "3", "--trace", "0", root=str(tmp_path),
        extra_env={"PYTHONPATH": ROOT + os.pathsep + HERE})
    assert result_of(p, lines)["correct"] is False
    assert any("reference, the measured program" in ln and "MISMATCH" in ln
               for ln in lines)
