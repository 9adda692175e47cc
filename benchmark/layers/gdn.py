"""Layers inside the policy forward of a sparse-expert decoder whose token
mixers are gated-delta-rule layers beside gated full-attention ones
(``estorch_tpu/models/delta_moe_lm.py``), by the stage scopes and parts the
model names itself with inside ``es.policy`` (``estorch_tpu/obs/trace.py``):
``es.dense`` (both mixers' projections, the output gate, the shared expert),
``es.ssm`` with the parts ``of.conv`` (the causal conv over q, k, v and its
SiLU), ``of.decay`` (beta, g, the L2 norms of q and k), ``of.solve`` (``K
Kᵀ``, the triangular inverse, ``W``, ``U``), ``of.carry`` (the chain over the
chunks: ``V'``, ``O``, ``S'``) and ``of.gate`` (the norm gated by
``silu(z)``), ``es.attn``, ``es.rope``, ``es.route``, ``es.dispatch``,
``es.expert`` and ``es.head``.  Source: the device trace reduced by
``stage_reduce.py``, as ``layers/swa.py`` reads it: seconds of the busiest
chip's leaf operations booked to each stage (the INNERMOST scope of an
operation's name stack; a fusion to its root's), as shares of that chip's busy
seconds in the traced window.  ``gdn.delta_share`` is ALL of ``es.ssm``;
``gdn.expert_share`` adds the unscoped ``ragged-dot*`` operations, as
``layers/moe.py`` does and for its reason.

``gdn.dense_flops_util``: the reference's matmul count of what runs under
``es.dense`` x the traced generations' tokens / seconds of ``es.dense`` /
(chips x the bf16 peak).  ``gdn.head_flops_util``: 2 x hidden x vocabulary a
token / seconds of ``es.head`` / peak.  ``gdn.expert_flops_util``: the rows
the run ROUTED to its held experts (``routed_pairs`` of the window's
generation records; the EXPECTED count where the records have none) x 2 x 3 x
hidden x expert width / seconds of ``es.expert`` / peak.
``gdn.attn_flops_util``: the EXACT count of causal pairs x 2 x heads x (head
width + value width) x the full layers x sequences / seconds of ``es.attn`` /
peak.  The delta rule's two rooflines read the same WORK whatever implements
it later (``costs_gdn.py``): ``gdn.delta_flops_util``, the chunked rule at the
configuration's chunk counted from shapes x sequences / seconds of ``of.solve``
+ ``of.carry`` / the bf16 peak, and ``gdn.delta_hbm_util``, q, k, v, g, beta
read and o written ONCE a token and head / the same seconds / the HBM peak:
what a kernel that keeps the state in VMEM is judged by.

The sizes come from the configuration file of the cell that lists these
metrics in ``BENCHMARK.json`` (the run's facts do not carry them).  A program
whose ``es.ssm`` names no part ``of.solve`` runs no such model (a program
without the scopes, or another sequence model: Mamba-2's and Mamba-1's scans
name no part): the reader returns nothing and does not raise.
"""

import os
import re

from benchmark import costs_gdn, costs_moe, stage_reduce
from benchmark.files import load_file_module, load_json

# metric: its stage, and for es.ssm the part of it
SHARES = {"gdn.dense_share": ("dense", None),
          "gdn.delta_share": ("ssm", None),
          "gdn.conv_share": ("ssm", "conv"),
          "gdn.solve_share": ("ssm", "solve"),
          "gdn.carry_share": ("ssm", "carry"),
          "gdn.attn_share": ("attn", None),
          "gdn.rope_share": ("rope", None),
          "gdn.route_share": ("route", None),
          "gdn.dispatch_share": ("dispatch", None),
          "gdn.expert_share": ("expert", None),
          "gdn.head_share": ("head", None)}
# the part of es.ssm only this model names: it marks its program
OWN_PART = "solve"
# what XLA:TPU names the custom calls it rewrites jax.lax.ragged_dot into
GROUPED_MATMUL = "ragged-dot"
# the part scope of obs/trace.py, bare or under jax's transforms
PART = re.compile(r"(?:^|/)(?:\w+\()*of\.([A-Za-z0-9_.]+?)\)*(?=/|$)")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def model_sizes():
    """``(sizes, describe)`` of the configuration the ``gdn.*`` metrics'
    cell runs, from its reference module; ``None`` where ``BENCHMARK.json``
    names none."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = [m.get("workloads", []) for m in bench["per_layer"]
             if m["name"] == "gdn.delta_flops_util"]
    if not cells or not cells[0]:
        return None
    cell = next(w for w in bench["workloads"] if w["name"] == cells[0][0])
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, entry["file"]))
    ref = load_file_module(os.path.join(
        os.path.dirname(HERE), "reference", config["reference"] + ".py"))
    return ref.sizes(config), ref.describe(config)


def scan_seconds(device: dict) -> dict:
    """Seconds booked to ``es.ssm`` by the part in each operation's name
    stack (``conv``, ``decay``, ``solve``, ``carry``, ``gate``; ``""``
    without one)."""
    out: dict[str, float] = {}
    for s, _, _, tf_op in device["ops"].get("ssm", {}).values():
        found = PART.findall(tf_op or "")
        name = found[-1] if found else ""
        out[name] = out.get(name, 0.0) + s
    return out


def of_records(run, key: str) -> list:
    """``key`` of the window's generation records that carry it."""
    return [r[key] for r in run.get("records", []) if key in r]


def read(run):
    out = stage_reduce.of_run(run)
    if not out:
        return {}
    d = stage_reduce.busiest_device(out["staged"])
    seconds, busy = dict(d["stage_s"]), d["busy_s"]
    by_part = scan_seconds(d)
    if OWN_PART not in by_part:
        return {}
    seconds["expert"] = seconds.get("expert", 0.0) + sum(
        rec[0] for label, rec in d["ops"].get(stage_reduce.UNSCOPED,
                                              {}).items()
        if label.startswith(GROUPED_MATMUL))
    values = {metric: (seconds.get(stage, 0.0) if of is None
                       else by_part.get(of, 0.0)) / busy
              for metric, (stage, of) in SHARES.items()}
    print("[gdn] es.ssm by part: " + "; ".join(
        f"{name or '(no part)'} {s:.6f} s"
        for name, s in sorted(by_part.items())), flush=True)
    peaks = run.get("peaks")
    if not peaks:
        return values
    tokens = run["steps_per_generation"] * run["traced_generations"]
    chip_flops = run["chips"] * peaks["peak_flops_per_s"]

    def util(name, work, spent, peak=chip_flops):
        if spent > 0 and work:
            values[name] = work / spent / peak

    util("gdn.dense_flops_util",
         run.get("dense_flops_per_member_step", 0) * tokens,
         seconds.get("dense", 0.0))
    util("gdn.head_flops_util",
         run.get("head_flops_per_member_step", 0) * tokens,
         seconds.get("head", 0.0))
    found = model_sizes()
    if not found:
        return values
    s, about = found
    length = s["seq_len"]
    sequences = tokens / length
    expected = (run["steps_per_generation"] * about["expert_layers"]
                * about["expected_pairs_per_token_and_layer"])
    counts = of_records(run, "routed_pairs")
    counted = sum(counts) / len(counts) if counts else None
    fullest = of_records(run, "expert_load_max_over_mean")
    routed = (expected if counted is None else counted) * run[
        "traced_generations"]
    heads = (s["linear_num_key_heads"], s["linear_num_value_heads"],
             s["linear_key_head_dim"], s["linear_value_head_dim"])
    chunk = s.get("delta_chunk", 64)
    attn = costs_gdn.attention_flops_per_sequence(
        s["layer_types"], length, s["num_attention_heads"], s["head_dim"])
    rule = costs_gdn.delta_rule_flops_per_sequence(
        s["layer_types"], length, chunk, *heads)
    rule_bytes = costs_gdn.delta_rule_bytes_per_sequence(
        s["layer_types"], length, *heads)
    print(f"[gdn] counted a sequence of {length}: attention {attn} FLOP "
          f"full causal (visible pairs only); the delta rule {rule} FLOP in "
          f"chunks of {chunk} and {rule_bytes} bytes at the least; "
          f"{sequences:.0f} sequences traced; rows routed to the held "
          f"experts a generation: "
          f"{'not in the records' if counted is None else f'{counted:.0f}'}"
          f" (a uniform router's {expected:.0f}: the held experts' share of "
          f"the pairs is {(counted or expected) / expected:.4f} of theirs); "
          f"the fullest held expert over their mean "
          f"{f'{min(fullest):.4f} to {max(fullest):.4f}' if fullest else 'not in the records'}"
          f" over the window's generations", flush=True)
    util("gdn.expert_flops_util",
         routed * costs_moe.expert_flops_per_pair(
             s["hidden_size"], s["moe_intermediate_size"]),
         seconds.get("expert", 0.0))
    util("gdn.attn_flops_util", attn * sequences, seconds.get("attn", 0.0))
    rule_s = by_part.get("solve", 0.0) + by_part.get("carry", 0.0)
    util("gdn.delta_flops_util", rule * sequences, rule_s)
    util("gdn.delta_hbm_util", rule_bytes * sequences, rule_s,
         run["chips"] * peaks["peak_hbm_bytes_per_s"])
    return values
