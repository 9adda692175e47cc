"""Layers inside the policy forward of a SPARSE-EXPERT sequence model, by the
stage scopes the model names itself with inside ``es.policy``
(``estorch_tpu/obs/trace.py``): ``es.route`` (the router's matmul, sigmoid,
selection bias, top-k, renormalisation), ``es.dispatch`` (sorting the
(token, k) pairs by held expert, the gather into expert order, the weighted
combine back), ``es.expert`` (the grouped matmuls over the routed rows and
the experts' gated activation), ``es.attn`` (scores, softmax, ``P.V``),
``es.dense`` (latent attention's projections, the dense FFN, the shared
experts, the MTP's projection), ``es.rope`` and ``es.head`` (both heads).
Source: the device trace reduced by ``stage_reduce.py``, as ``layers/loop.py``
reads it: seconds of the busiest chip's leaf operations booked to each stage
(the INNERMOST scope of an operation's name stack; a fusion to its root's),
as shares of that chip's busy seconds in the traced window.

``moe.dense_flops_util``: the multiply-adds outside the experts and the
routers plus the two heads' for the traced generations' tokens (the
reference module counts them from the widths) over the seconds booked to
``es.dense`` + ``es.head`` and the chips' bf16 peak.

``moe.expert_share`` adds, to what is booked to ``es.expert``, the operations
named ``ragged-dot*`` that carry NO stage: on the TPU ``jax.lax.ragged_dot``
is rewritten into a custom call (``ragged-dot-none``, with a small
``ragged-dot-metadata`` beside it) whose name stack is the rewrite's own, so
the grouped matmuls themselves reach the trace unscoped (they stay in
``stage.unscoped_share`` too, whose reader knows nothing of this).  The
experts' gated activation fuses into the rank-r corrections around it and is
booked with them to ``es.perturb``.

``moe.expert_flops_util``: 2 x 3 x hidden x expert width x routed pairs
(``costs_moe.py``) over the seconds booked to ``es.expert`` and the bf16
peak: the grouped matmul's share of its compute roofline.  The pairs are
the EXPECTED ``tokens x top-k x held / total`` an expert layer under a
router that spreads evenly, times the expert layers, not what the run
routed (a run's own count is ``routed_pairs`` in its generation records,
which the harness does not hand a reader).

``moe.dispatch_hbm_util``: the bytes ``costs_moe.py`` reckons a routed row
costs in gather and combine, for the same expected pairs, over the seconds
booked to ``es.dispatch`` and the HBM peak.

The sizes come from the configuration file of the cell that lists these
metrics in ``BENCHMARK.json`` (the run's facts do not carry them).  A
program whose forward names no ``es.route`` runs no expert layer (a program
without the scopes, or another sequence model): the reader returns nothing
and does not raise.
"""

import os

from benchmark import costs_moe, stage_reduce
from benchmark.files import load_file_module, load_json

SHARES = {"moe.route_share": "route", "moe.dispatch_share": "dispatch",
          "moe.expert_share": "expert", "moe.attn_share": "attn",
          "moe.dense_share": "dense", "moe.rope_share": "rope",
          "moe.head_share": "head"}
# what XLA:TPU names the custom calls it rewrites jax.lax.ragged_dot into
GROUPED_MATMUL = "ragged-dot"
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def expert_sizes():
    """``(sizes, describe)`` of the configuration the ``moe.*`` metrics'
    cell runs, from its reference module; ``None`` where ``BENCHMARK.json``
    names none."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = [m.get("workloads", []) for m in bench["per_layer"]
             if m["name"] == "moe.expert_flops_util"]
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
    seconds, busy = d["stage_s"], d["busy_s"]
    if "route" not in seconds:
        return {}
    seconds = dict(seconds)
    seconds["expert"] = seconds.get("expert", 0.0) + sum(
        rec[0] for label, rec in d["ops"].get(stage_reduce.UNSCOPED,
                                              {}).items()
        if label.startswith(GROUPED_MATMUL))
    values = {metric: seconds.get(stage, 0.0) / busy
              for metric, stage in SHARES.items()}
    peaks = run.get("peaks")
    tokens = run["steps_per_generation"] * run["traced_generations"]
    matmul_s = seconds.get("dense", 0.0) + seconds.get("head", 0.0)
    flops = (run.get("dense_flops_per_member_step", 0)
             + run.get("head_flops_per_member_step", 0))
    if peaks and matmul_s > 0 and flops:
        values["moe.dense_flops_util"] = (
            flops * tokens / matmul_s
            / (run["chips"] * peaks["peak_flops_per_s"]))
    found = expert_sizes() if peaks else None
    if found:
        s, about = found
        pairs = (tokens * about["expert_layers"]
                 * about["expected_pairs_per_token_and_layer"])
        print(f"[moe] expert_flops_util and dispatch_hbm_util count the "
              f"EXPECTED routed pairs of a uniform router: {pairs:.0f} "
              f"({tokens} tokens x {about['expert_layers']} expert layers x "
              f"{about['expected_pairs_per_token_and_layer']} a token)",
              flush=True)
        if seconds.get("expert", 0.0) > 0:
            values["moe.expert_flops_util"] = (
                pairs * costs_moe.expert_flops_per_pair(
                    s["hidden_size"], s["moe_intermediate_size"])
                / seconds["expert"]
                / (run["chips"] * peaks["peak_flops_per_s"]))
        if seconds.get("dispatch", 0.0) > 0:
            values["moe.dispatch_hbm_util"] = (
                pairs * costs_moe.dispatch_bytes_per_pair(s["hidden_size"])
                / seconds["dispatch"]
                / (run["chips"] * peaks["peak_hbm_bytes_per_s"]))
    return values
