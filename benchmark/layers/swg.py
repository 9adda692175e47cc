"""Layers inside the policy forward of a sparse-expert decoder whose two
kinds of attention layer differ in head count, band and rotation, with a gate
a head (``estorch_tpu/models/gated_window_moe_lm.py``), by the stage scopes
and parts the model names itself with inside ``es.policy``
(``estorch_tpu/obs/trace.py``): ``es.dense`` (the attention's projections,
the dense FFN, the shared experts) WITHOUT its part ``of.head_gate`` (the
gate's projection, sigmoid and product: ``swg.gate_share``), ``es.rope``
(both kinds' tables and rotations), ``es.attn`` with the parts ``of.sliding``
(scores, softmax, ``P.V`` of the layers banded to 512 keys) and ``of.full``
(of the full causal ones), ``es.route``, ``es.dispatch``, ``es.expert`` and
``es.head``.  Source: the device trace reduced by ``stage_reduce.py``, as
``layers/swa.py`` reads it: seconds of the busiest chip's leaf operations
booked to each stage (the INNERMOST scope of an operation's name stack; a
fusion to its root's), as shares of that chip's busy seconds in the traced
window.  ``swg.expert_share`` adds the unscoped ``ragged-dot*`` operations,
as ``layers/moe.py`` does and for its reason.

``swg.dense_flops_util``: the reference's matmul count of what runs under
``es.dense`` x the traced generations' tokens / seconds of ``es.dense`` /
(chips x the bf16 peak).  ``swg.head_flops_util``: 2 x hidden x vocabulary a
token / seconds of ``es.head`` / peak.  ``swg.expert_flops_util``: the rows
the run ROUTED to its held experts (``routed_pairs`` of the window's
generation records; the EXPECTED count where the records have none) x 2 x 3
x hidden x expert width / seconds of ``es.expert`` / peak.  The attention's
shares of the peak, which read the same WORK whatever implements it later
(``costs_swg.py``): the EXACT count of visible pairs, band and causal, x 2 x
THAT KIND's heads x (head width + value width) x that kind's layers x
sequences / seconds of that kind's part of ``es.attn`` / peak:
``swg.sliding_attn_flops_util`` (what a sub-block band inside a kernel will
be judged by) and ``swg.full_attn_flops_util``.

The reader returns all fourteen; ``run.py`` puts on the result line those
``BENCHMARK.json`` lists (its ``per_layer`` held 127 of 128 entries when
this file was added: PERF.md section 7), and every one is printed in the
run's log.  The sizes come from the configuration file of the cell that
lists a ``swg.*`` metric (the run's facts do not carry them).  A program
whose ``es.attn`` names no part ``of.sliding`` runs no such model (a program
without the scopes, or another sequence model): the reader returns nothing
and does not raise.
"""

import os
import re

from benchmark import costs_moe, costs_swg, stage_reduce
from benchmark.files import load_file_module, load_json

# metric: its stage, and the part of it (None: the whole stage)
SHARES = {"swg.dense_share": ("dense", None),
          "swg.sliding_attn_share": ("attn", "sliding"),
          "swg.full_attn_share": ("attn", "full"),
          "swg.gate_share": ("dense", "head_gate"),
          "swg.rope_share": ("rope", None),
          "swg.route_share": ("route", None),
          "swg.dispatch_share": ("dispatch", None),
          "swg.expert_share": ("expert", None),
          "swg.head_share": ("head", None)}
# the part of es.attn only this model names: it marks its program
OWN_PART = "sliding"
GATE_PART = "head_gate"
# what XLA:TPU names the custom calls it rewrites jax.lax.ragged_dot into
GROUPED_MATMUL = "ragged-dot"
# the part scope of obs/trace.py, bare or under jax's transforms
PART = re.compile(r"(?:^|/)(?:\w+\()*of\.([A-Za-z0-9_.]+?)\)*(?=/|$)")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def model_sizes():
    """``(sizes, describe)`` of the configuration the ``swg.*`` metrics'
    cell runs, from its reference module; ``None`` where ``BENCHMARK.json``
    names none."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = [m.get("workloads", []) for m in bench["per_layer"]
             if m["name"].startswith("swg.")]
    if not cells or not cells[0]:
        return None
    cell = next(w for w in bench["workloads"] if w["name"] == cells[0][0])
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, entry["file"]))
    ref = load_file_module(os.path.join(
        os.path.dirname(HERE), "reference", config["reference"] + ".py"))
    return ref.sizes(config), ref.describe(config)


def seconds_by_part(device: dict, stage: str) -> dict:
    """Seconds booked to ``es.<stage>`` by the innermost part in each
    operation's name stack (``""`` without one)."""
    out: dict[str, float] = {}
    for s, _, _, tf_op in device["ops"].get(stage, {}).values():
        found = PART.findall(tf_op or "")
        name = found[-1] if found else ""
        out[name] = out.get(name, 0.0) + s
    return out


def of_records(run, key: str) -> list:
    """``key`` of the window's generation records that carry it:
    ``routed_pairs`` (the program's own count of the rows its held experts
    took, summed over the layers), ``expert_load_max_over_mean``."""
    return [r[key] for r in run.get("records", []) if key in r]


def read(run):
    out = stage_reduce.of_run(run)
    if not out:
        return {}
    d = stage_reduce.busiest_device(out["staged"])
    seconds, busy = dict(d["stage_s"]), d["busy_s"]
    by_part = {"attn": seconds_by_part(d, "attn"),
               "dense": seconds_by_part(d, "dense")}
    if OWN_PART not in by_part["attn"]:
        return {}
    gate_s = by_part["dense"].get(GATE_PART, 0.0)
    # the gate is a share of its own: es.dense without it
    seconds["dense"] = seconds.get("dense", 0.0) - gate_s
    seconds["expert"] = seconds.get("expert", 0.0) + sum(
        rec[0] for label, rec in d["ops"].get(stage_reduce.UNSCOPED,
                                              {}).items()
        if label.startswith(GROUPED_MATMUL))
    values = {metric: (seconds.get(stage, 0.0) if of is None
                       else by_part[stage].get(of, 0.0)) / busy
              for metric, (stage, of) in SHARES.items()}
    print("[swg] es.attn by part: " + "; ".join(
        f"{name or '(no part)'} {s:.6f} s"
        for name, s in sorted(by_part["attn"].items()))
        + f"; es.dense/of.{GATE_PART} {gate_s:.6f} s", flush=True)
    peaks = run.get("peaks")
    if peaks:
        _utils(run, values, seconds, by_part["attn"],
               run["chips"] * peaks["peak_flops_per_s"])
    print("[swg] " + "; ".join(f"{k} {v:.6g}" for k, v in values.items()),
          flush=True)
    return values


def _utils(run, values, seconds, attn_s, chip_flops):
    """The five shares of the MXU's peak, into ``values``."""
    tokens = run["steps_per_generation"] * run["traced_generations"]

    def util(name, work, spent):
        if spent > 0 and work:
            values[name] = work / spent / chip_flops

    util("swg.dense_flops_util",
         run.get("dense_flops_per_member_step", 0) * tokens,
         seconds.get("dense", 0.0))
    util("swg.head_flops_util",
         run.get("head_flops_per_member_step", 0) * tokens,
         seconds.get("head", 0.0))
    found = model_sizes()
    if not found:
        return
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
    attn = costs_swg.attention_flops_per_sequence(
        s["layer_types"], s["heads"], length, s["sliding_window"],
        s["head_dim"])
    print(f"[swg] counted a sequence of {length}: attention "
          f"{attn['sliding']} FLOP banded ({s['sliding_window']} keys) + "
          f"{attn['full']} FLOP full causal (visible pairs only, each "
          f"kind's own heads); {sequences:.0f} sequences traced; rows "
          f"routed to the held experts a generation: "
          f"{'not in the records' if counted is None else f'{counted:.0f}'}"
          f" (a uniform router's {expected:.0f}: the held experts' share of "
          f"the pairs is {(counted or expected) / expected:.4f} of theirs); "
          f"the fullest held expert over their mean "
          f"{f'{min(fullest):.4f} to {max(fullest):.4f}' if fullest else 'not in the records'}"
          f" over the window's generations", flush=True)
    util("swg.expert_flops_util",
         routed * costs_moe.expert_flops_per_pair(
             s["hidden_size"], s["moe_intermediate_size"]),
         seconds.get("expert", 0.0))
    util("swg.sliding_attn_flops_util", attn["sliding"] * sequences,
         attn_s.get("sliding", 0.0))
    util("swg.full_attn_flops_util", attn["full"] * sequences,
         attn_s.get("full", 0.0))
