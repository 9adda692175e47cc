"""``costs_parts.py`` against the reference modules' own counts, and the
``part.*`` reader (``layers/part.py``) on hand-made stage tables: a program
that names its parts, one whose name was lost to a re-fusion, one served from
a compile-cache entry written without parts, and one without sequence
stages."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import costs_parts  # noqa: E402
from benchmark.files import load_file_module, load_json  # noqa: E402

CONFIGS = ("granite-4.0-h-micro-1period", "ouro-2.6b-8layers",
           "joyai-llm-flash-5layers")
PEAKS = load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
TOKENS = 1000


def config_of(name):
    return load_json(os.path.join(ROOT, "benchmark", "configs",
                                  name + ".json"))


def reader():
    return load_file_module(os.path.join(ROOT, "benchmark", "layers",
                                         "part.py"))


@pytest.mark.parametrize("name", CONFIGS)
def test_parts_add_up_to_the_references_count(name):
    config = config_of(name)
    ref = load_file_module(os.path.join(
        ROOT, "benchmark", "reference", config["reference"] + ".py"))
    about = ref.describe(config)
    assert costs_parts.counted_by_reference(config) == (
        about["dense_flops_per_member_step"]
        + about["head_flops_per_member_step"])
    parts = costs_parts.parts(config)
    # every part is in at most one group, and the groups leave only the
    # parts that are nobody's (eh), the routers, the gate and the experts
    outside = {p for p in parts if costs_parts.group_of(p) is None}
    assert outside <= {"eh", "router", "exit_gate", "experts.gate",
                       "experts.up", "experts.down"}
    if "expert_flops_per_member_step" in about:
        assert sum(parts[f"experts.{n}"][0] for n in costs_parts.FFN) == (
            pytest.approx(about["expert_flops_per_member_step"]))


def test_attention_is_the_exact_causal_count():
    # ouro: 32 layer-applications of 16 heads, 128 scored and 128 summed,
    # 4096 · 4097 / 2 visible pairs a sequence
    config = config_of("ouro-2.6b-8layers")
    pairs = sum(t + 1 for t in range(4096))
    assert costs_parts.attention_flops_per_token(config) * 4096 == (
        32 * 16 * 2 * (128 + 128) * pairs)
    # latent attention scores 128 + 64 wide and sums 128 wide
    config = config_of("joyai-llm-flash-5layers")
    assert costs_parts.attention_flops_per_token(config) * 4096 == (
        6 * 32 * 2 * (192 + 128) * pairs)


# ------------------------------------------------ the reader on made-up runs

def run_of(ops, config="ouro-2.6b-8layers", chips=1):
    """A traced run's facts around a hand-made per-operation table:
    ``ops[stage][label] = [seconds, bytes, FLOPs, name stack]``."""
    stage_s = {stage: sum(rec[0] for rec in labels.values())
               for stage, labels in ops.items()}
    device = {"stage_s": stage_s, "ops": ops, "busy_s": sum(stage_s.values()),
              "scoped_ops": 1}
    ref = load_file_module(os.path.join(
        ROOT, "benchmark", "reference",
        config_of(config)["reference"] + ".py"))
    about = ref.describe(config_of(config))
    return {"stage_reduce": {"staged": {"devices": {"tpu0": device},
                                        "busiest": "tpu0"}, "spans": []},
            "peaks": PEAKS, "chips": chips, "steps_per_generation": TOKENS,
            "traced_generations": 1,
            "dense_flops_per_member_step":
                about["dense_flops_per_member_step"],
            "head_flops_per_member_step": about["head_flops_per_member_step"]}


def named_ouro_table(seconds=1e-3):
    """ouro's parts, each operation carrying XLA's FLOPs of exactly the
    counted ones for ``TOKENS`` tokens, ``seconds`` each."""
    config = config_of("ouro-2.6b-8layers")
    parts = costs_parts.parts(config)
    dense = {f"fusion.{i}": [seconds, 10 ** 6, flops * TOKENS,
                             f"jit(gen)/es.policy/es.dense/of.{name}/dot"]
             for i, (name, (flops, _)) in enumerate(parts.items())
             if name not in ("head", "exit_gate")}
    return {
        "dense": dense,
        "head": {"fusion.90": [seconds, 10 ** 6, parts["head"][0] * TOKENS,
                               "jit(gen)/es.policy/es.head/of.head/dot"]},
        "attn": {"causal_attention.1": [
            seconds, 10 ** 6,
            1.25 * costs_parts.attention_flops_per_token(config) * TOKENS,
            "jit(gen)/es.policy/es.attn/pallas_call"]},
        "perturb": {
            # an unfused correction beneath a part, a logits copy beneath
            # the head, and a perturbation that is nobody's correction
            "fusion.95": [seconds / 2, 10 ** 5, 10 ** 3,
                          "jit(gen)/es.policy/es.dense/vmap(of.gate)/"
                          "es.perturb/mul"],
            "copy.1": [seconds / 2, 10 ** 5, 0,
                       "jit(gen)/es.policy/es.head/of.head/es.perturb/add"],
            "fusion.96": [seconds, 10 ** 5, 0, "jit(gen)/es.perturb/add"]},
        "policy": {"fusion.97": [seconds, 10 ** 5, 0,
                                 "jit(gen)/es.policy/of.embed/gather"]},
    }


def test_part_of_a_name_stack():
    part_of = reader().part_of
    assert part_of("jit(g)/es.policy/es.dense/of.gate/dot_general") == "gate"
    assert part_of("jit(g)/es.policy/of.shared/es.dense/vmap(of.gate)/"
                   "es.perturb/mul") == "shared.gate"
    assert part_of("jit(g)/es.policy/es.dense/soft.gate/roof.up/dot") == ""
    assert part_of("") == part_of(None) == ""


def test_reader_on_a_program_that_names_its_parts(capsys):
    ops = named_ouro_table()
    run = run_of(ops)
    values = reader().read(run)
    assert set(values) == {
        "part.named_share", "part.correction_share", "part.ffn_flops_util",
        "part.mixer_flops_util", "part.head_flops_util",
        "part.attn_flops_util"}
    assert values["part.named_share"] == 1.0
    busy = run["stage_reduce"]["staged"]["devices"]["tpu0"]["busy_s"]
    # half a millisecond of correction sits under es.perturb beneath a
    # part; the logits' layout copy beside it is no correction, and the
    # third perturbation is nobody's
    assert values["part.correction_share"] == pytest.approx(0.5e-3 / busy)
    assert "0.000500 s of layout copies" in capsys.readouterr().out
    parts = costs_parts.parts(config_of("ouro-2.6b-8layers"))
    peak = PEAKS["peak_flops_per_s"]
    ffn = sum(parts[n][0] for n in ("gate", "up", "down")) * TOKENS
    # gate's unfused correction is among the FFN's seconds
    assert values["part.ffn_flops_util"] == pytest.approx(
        ffn / 3.5e-3 / peak)
    mixer = sum(parts[n][0] for n in ("q", "k", "v", "o")) * TOKENS
    assert values["part.mixer_flops_util"] == pytest.approx(
        mixer / 4e-3 / peak)
    # the head's seconds hold the copy booked to es.perturb beneath it
    assert values["part.head_flops_util"] == pytest.approx(
        parts["head"][0] * TOKENS / 1.5e-3 / peak)
    assert values["part.attn_flops_util"] == pytest.approx(
        costs_parts.attention_flops_per_token(
            config_of("ouro-2.6b-8layers")) * TOKENS / 1e-3 / peak)
    reader().read(run_of(named_ouro_table()))
    said = capsys.readouterr().out
    assert "over the exact causal count 1.2500" in said
    assert "part gate:" in said and "of the ridge" in said
    dense_s = sum(rec[0] for rec in ops["dense"].values())
    assert f"sum to {dense_s:.9f} s, the stage's are {dense_s:.9f} s" in said
    # a run whose facts no listed configuration counts: the coverage and
    # the corrections still read, no utilisation
    odd = {**run_of(named_ouro_table()), "head_flops_per_member_step": 1}
    assert set(reader().read(odd)) == {"part.named_share",
                                       "part.correction_share"}
    assert "no configuration of" in capsys.readouterr().out


def test_a_lost_name_leaves_its_utilisation_out_and_says_so(capsys):
    ops = named_ouro_table()
    # XLA re-fused ``up`` under a name stack of its own: its seconds and
    # FLOPs are in es.dense, but under no part
    lost = next(label for label, rec in ops["dense"].items()
                if rec[3].endswith("of.up/dot"))
    ops["dense"][lost][3] = "jit(gen)/es.policy/es.dense/dot"
    values = reader().read(run_of(ops))
    assert "part.ffn_flops_util" not in values
    assert {"part.mixer_flops_util", "part.head_flops_util",
            "part.attn_flops_util"} <= set(values)
    # 7 ms under es.dense, 1 under es.head, 1 of corrections beneath them
    assert values["part.named_share"] == pytest.approx(1 - 1e-3 / 9e-3)
    said = capsys.readouterr().out
    assert "part.ffn_flops_util left out" in said
    assert "(no part)" in said


def test_a_stale_cache_entry_reads_no_part_and_says_so(capsys):
    ops = named_ouro_table()
    for labels in ops.values():
        for rec in labels.values():
            rec[3] = "/".join(c for c in rec[3].split("/") if "of." not in c)
    values = reader().read(run_of(ops))
    assert len(values) == 6 and set(values.values()) == {0.0}
    assert values["part.named_share"] == 0.0
    assert "compile-cache entry written without the parts" in (
        capsys.readouterr().out)


def test_a_program_without_sequence_stages_reads_nothing():
    ops = {"policy": {"fusion.1": [1e-3, 10, 10, "jit(gen)/es.policy/dot"]},
           "env": {"fusion.2": [1e-3, 10, 10, "jit(gen)/es.env/sin"]}}
    assert reader().read(run_of(ops)) == {}
    assert reader().read({"trace": None}) == {}


def test_a_four_chip_run_counts_a_chips_share():
    # every chip does a quarter of the counted work: the same table with a
    # quarter of the FLOPs an operation reads the same utilisation
    ops = named_ouro_table()
    for labels in ops.values():
        for rec in labels.values():
            rec[2] /= 4
    one = reader().read(run_of(named_ouro_table()))
    four = reader().read(run_of(ops, chips=4))
    assert four["part.ffn_flops_util"] == pytest.approx(
        one["part.ffn_flops_util"] / 4)
