"""Layers inside the policy forward of a sparse-expert decoder whose
attention reads a LEARNED SELECTION of keys
(``estorch_tpu/models/indexed_moe_lm.py``), by the stage scopes the model
names itself with inside ``es.policy`` (``estorch_tpu/obs/trace.py``):
``es.index`` (the indexer's three projections, its key's norm, the score
product ``sum_j w_j relu(q_j . k)`` over every causal pair), ``es.select``
(the choice of the ``topk`` largest a query and the write of the selection),
``es.attn`` (scores, softmax and ``P.V`` under the selection), ``es.dense``
(the attention's four projections), ``es.rope``, ``es.route``,
``es.dispatch``, ``es.expert`` and ``es.head``.  Source: the device trace
reduced by ``stage_reduce.py``, as ``layers/sambay.py`` reads it: seconds of
the busiest chip's leaf operations booked to each stage (the INNERMOST scope
of an operation's name stack; a fusion to its root's), as shares of that
chip's busy seconds in the traced window.  ``dsa.expert_share`` adds the
unscoped ``ragged-dot*`` operations, as ``layers/moe.py`` does and for its
reason.

``dsa.dense_flops_util``: the reference's matmul count of the attention's
projections and the head x the traced generations' tokens / seconds of
``es.dense`` + ``es.head`` / (chips x the bf16 peak).
``dsa.expert_flops_util``: as ``moe.expert_flops_util``, the EXPECTED routed
pairs of a uniform router.  The new mechanism's shares of its roofline, which
read the same WORK whatever implements it later (``costs_dsa.py``):
``dsa.attn_flops_util`` = the pairs the queries SELECTED x 2 x heads x (head
width + value width) x layers x sequences / seconds of ``es.attn`` / peak (a
kernel that multiplies every visible pair and masks reads about a quarter of
what it would on full attention at 16,384 positions and ``topk`` 2,048: that
is what is left to win); ``dsa.index_flops_util`` = the exact causal pairs x
2 x index heads x index width x layers x sequences / seconds of ``es.index``
+ ``es.select`` / peak.

The sizes come from the configuration file of the cell that lists these
metrics in ``BENCHMARK.json`` (the run's facts do not carry them).  A program
whose forward names neither ``es.index`` nor ``es.select`` runs no such model
(a program without the scopes, or another sequence model): the reader
returns nothing and does not raise.
"""

import os

from benchmark import costs_dsa, costs_moe, stage_reduce
from benchmark.files import load_file_module, load_json

SHARES = {"dsa.dense_share": "dense", "dsa.index_share": "index",
          "dsa.select_share": "select", "dsa.attn_share": "attn",
          "dsa.rope_share": "rope", "dsa.route_share": "route",
          "dsa.dispatch_share": "dispatch", "dsa.expert_share": "expert",
          "dsa.head_share": "head"}
# the stages only this model names: one of them marks its program
OWN_STAGES = ("index", "select")
# what XLA:TPU names the custom calls it rewrites jax.lax.ragged_dot into
GROUPED_MATMUL = "ragged-dot"
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def model_sizes():
    """``(sizes, describe)`` of the configuration the ``dsa.*`` metrics'
    cell runs, from its reference module; ``None`` where ``BENCHMARK.json``
    names none."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = [m.get("workloads", []) for m in bench["per_layer"]
             if m["name"] == "dsa.index_flops_util"]
    if not cells or not cells[0]:
        return None
    cell = next(w for w in bench["workloads"] if w["name"] == cells[0][0])
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, entry["file"]))
    ref = load_file_module(os.path.join(
        os.path.dirname(HERE), "reference", config["reference"] + ".py"))
    return ref.sizes(config), ref.describe(config)


def read(run):
    out = stage_reduce.of_run(run)
    if not out:
        return {}
    d = stage_reduce.busiest_device(out["staged"])
    seconds, busy = dict(d["stage_s"]), d["busy_s"]
    if not any(stage in seconds for stage in OWN_STAGES):
        return {}
    seconds["expert"] = seconds.get("expert", 0.0) + sum(
        rec[0] for label, rec in d["ops"].get(stage_reduce.UNSCOPED,
                                              {}).items()
        if label.startswith(GROUPED_MATMUL))
    values = {metric: seconds.get(stage, 0.0) / busy
              for metric, stage in SHARES.items()}
    peaks = run.get("peaks")
    if not peaks:
        return values
    tokens = run["steps_per_generation"] * run["traced_generations"]
    chip_flops = run["chips"] * peaks["peak_flops_per_s"]
    matmul_s = seconds.get("dense", 0.0) + seconds.get("head", 0.0)
    flops = (run.get("dense_flops_per_member_step", 0)
             + run.get("head_flops_per_member_step", 0))
    if matmul_s > 0 and flops:
        values["dsa.dense_flops_util"] = (
            flops * tokens / matmul_s / chip_flops)
    found = model_sizes()
    if not found:
        return values
    s, about = found
    length, layers = s["seq_len"], len(s["layer_types"])
    sequences = tokens / length
    routed = (tokens * about["expert_layers"]
              * about["expected_pairs_per_token_and_layer"])
    chosen = costs_dsa.selected_pairs(length, s["topk"])
    visible = costs_dsa.causal_pairs(length)
    print(f"[dsa] counted a sequence of {length} and a layer: {chosen} "
          f"selected pairs of {visible} causal ones (topk {s['topk']}); "
          f"{layers} layers, {sequences:.0f} sequences traced; the experts' "
          f"EXPECTED routed pairs of a uniform router {routed:.0f}",
          flush=True)
    if seconds.get("expert", 0.0) > 0:
        values["dsa.expert_flops_util"] = (
            routed * costs_moe.expert_flops_per_pair(
                s["hidden_size"], s["moe_intermediate_size"])
            / seconds["expert"] / chip_flops)
    if seconds.get("attn", 0.0) > 0:
        values["dsa.attn_flops_util"] = (
            chosen * costs_dsa.attention_flops_per_pair(
                s["num_attention_heads"], s["head_dim"])
            * layers * sequences / seconds["attn"] / chip_flops)
    index_s = seconds.get("index", 0.0) + seconds.get("select", 0.0)
    if index_s > 0:
        values["dsa.index_flops_util"] = (
            visible * costs_dsa.index_flops_per_pair(
                s["indexer_num_heads"], s["indexer_head_dim"])
            * layers * sequences / index_s / chip_flops)
    return values
