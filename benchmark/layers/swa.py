"""Layers inside the policy forward of a sparse-expert decoder whose router
reads the layer's input AHEAD of attention and whose attention layers are of
two kinds (``estorch_tpu/models/window_moe_lm.py``), by the stage scopes and
parts the model names itself with inside ``es.policy``
(``estorch_tpu/obs/trace.py``): ``es.route`` (the router's matmul, softmax,
top-k, renormalisation: ahead of ``es.attn``), ``es.dispatch``, ``es.expert``
(the grouped matmuls and the ReLU gate), ``es.dense`` (the attention's four
projections), ``es.rope`` (the window layers' rotation), ``es.attn`` with the
parts ``of.window`` (scores, softmax, ``P.V`` of the banded rotary layers) and
``of.global`` (of the position-free full-causal ones) and ``es.head``.
Source: the device trace reduced by ``stage_reduce.py``, as
``layers/sambay.py`` reads it: seconds of the busiest chip's leaf operations
booked to each stage (the INNERMOST scope of an operation's name stack; a
fusion to its root's), as shares of that chip's busy seconds in the traced
window.  ``swa.expert_share`` adds the unscoped ``ragged-dot*`` operations, as
``layers/moe.py`` does and for its reason.

``swa.dense_flops_util``: the reference's matmul count of the attention's
projections x the traced generations' tokens / seconds of ``es.dense`` /
(chips x the bf16 peak).  ``swa.head_flops_util``: 2 x hidden x vocabulary a
token / seconds of ``es.head`` / peak.  ``swa.expert_flops_util``: the rows
the run ROUTED to its held experts (``routed_pairs`` of the window's
generation records, their mean a generation, printed beside a uniform
router's count with the records' ``expert_load_max_over_mean``; the EXPECTED
count where the records have none) x 2 x 3 x hidden x expert width / seconds
of ``es.expert`` / peak.  The attention's shares of its roofline, which read
the same WORK whatever implements it later (``costs_swa.py``): the EXACT
count of visible pairs, band and causal, x 2 x heads x (head width + value
width) x that kind's layers x sequences / seconds of that kind's part of
``es.attn`` / peak: ``swa.window_attn_flops_util`` (what a band inside the
kernel's tiles will be judged by) and ``swa.global_attn_flops_util``.

The sizes come from the configuration file of the cell that lists these
metrics in ``BENCHMARK.json`` (the run's facts do not carry them).  A program
whose ``es.attn`` names no part ``of.global`` runs no such model (a program
without the scopes, or another sequence model): the reader returns nothing
and does not raise.
"""

import os
import re

from benchmark import costs_moe, costs_swa, stage_reduce
from benchmark.files import load_file_module, load_json

# metric: its stage, and for es.attn the part of it
SHARES = {"swa.dense_share": ("dense", None),
          "swa.window_attn_share": ("attn", "window"),
          "swa.global_attn_share": ("attn", "global"),
          "swa.rope_share": ("rope", None),
          "swa.route_share": ("route", None),
          "swa.dispatch_share": ("dispatch", None),
          "swa.expert_share": ("expert", None),
          "swa.head_share": ("head", None)}
# the part of es.attn only this model names: it marks its program
OWN_PART = "global"
# what XLA:TPU names the custom calls it rewrites jax.lax.ragged_dot into
GROUPED_MATMUL = "ragged-dot"
# the part scope of obs/trace.py, bare or under jax's transforms
PART = re.compile(r"(?:^|/)(?:\w+\()*of\.([A-Za-z0-9_.]+?)\)*(?=/|$)")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def model_sizes():
    """``(sizes, describe)`` of the configuration the ``swa.*`` metrics'
    cell runs, from its reference module; ``None`` where ``BENCHMARK.json``
    names none."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = [m.get("workloads", []) for m in bench["per_layer"]
             if m["name"] == "swa.window_attn_flops_util"]
    if not cells or not cells[0]:
        return None
    cell = next(w for w in bench["workloads"] if w["name"] == cells[0][0])
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, entry["file"]))
    ref = load_file_module(os.path.join(
        os.path.dirname(HERE), "reference", config["reference"] + ".py"))
    return ref.sizes(config), ref.describe(config)


def attention_seconds(device: dict) -> dict:
    """Seconds booked to ``es.attn`` by the part in each operation's name
    stack (``window``, ``global``; ``""`` without one)."""
    out: dict[str, float] = {}
    for s, _, _, tf_op in device["ops"].get("attn", {}).values():
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
    by_part = attention_seconds(d)
    if OWN_PART not in by_part:
        return {}
    seconds["expert"] = seconds.get("expert", 0.0) + sum(
        rec[0] for label, rec in d["ops"].get(stage_reduce.UNSCOPED,
                                              {}).items()
        if label.startswith(GROUPED_MATMUL))
    values = {metric: (seconds.get(stage, 0.0) if of is None
                       else by_part.get(of, 0.0)) / busy
              for metric, (stage, of) in SHARES.items()}
    print("[swa] es.attn by part: " + "; ".join(
        f"{name or '(no part)'} {s:.6f} s"
        for name, s in sorted(by_part.items())), flush=True)
    peaks = run.get("peaks")
    if not peaks:
        return values
    tokens = run["steps_per_generation"] * run["traced_generations"]
    chip_flops = run["chips"] * peaks["peak_flops_per_s"]

    def util(name, work, spent):
        if spent > 0 and work:
            values[name] = work / spent / chip_flops

    util("swa.dense_flops_util",
         run.get("dense_flops_per_member_step", 0) * tokens,
         seconds.get("dense", 0.0))
    util("swa.head_flops_util",
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
    attn = costs_swa.attention_flops_per_sequence(
        s["layer_types"], length, s["sliding_window_size"],
        s["num_attention_heads"], s["head_dim"])
    print(f"[swa] counted a sequence of {length}: attention "
          f"{attn['window']} FLOP banded ({s['sliding_window_size']} keys) + "
          f"{attn['global']} FLOP full causal (visible pairs only); "
          f"{sequences:.0f} sequences traced; rows routed to the held "
          f"experts a generation: "
          f"{'not in the records' if counted is None else f'{counted:.0f}'}"
          f" (a uniform router's {expected:.0f}: the held experts' share of "
          f"the pairs is {(counted or expected) / expected:.4f} of theirs); "
          f"the fullest held expert over their mean "
          f"{f'{min(fullest):.4f} to {max(fullest):.4f}' if fullest else 'not in the records'}"
          f" over the window's generations", flush=True)
    util("swa.expert_flops_util",
         routed * costs_moe.expert_flops_per_pair(
             s["hidden_size"], s["moe_ffn_hidden_size"]),
         seconds.get("expert", 0.0))
    util("swa.window_attn_flops_util", attn["window"] * sequences,
         by_part.get("window", 0.0))
    util("swa.global_attn_flops_util", attn["global"] * sequences,
         by_part.get("global", 0.0))
    return values
