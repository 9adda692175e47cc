"""Layers inside the policy forward of a LOOPED sequence model, by the stage
scopes the model names itself with inside ``es.policy``
(``estorch_tpu/obs/trace.py``): ``es.dense`` (the shared ``x@W`` projections
and the gated FFN of every layer-application), ``es.attn`` (scores, softmax,
``P.V``), ``es.head`` (the untied head's logits, log-softmax and score, once
a pass), ``es.rope`` (cos/sin, rotating queries and keys) and ``es.exit``
(the exit gate, the exit distribution, the weighting of the per-pass
scores).  Source: the device trace reduced by ``stage_reduce.py``, as
``layers/lm.py`` reads it: seconds of the busiest chip's leaf operations
booked to each stage (the INNERMOST scope of an operation's name stack; a
fusion to its root's), as shares of that chip's busy seconds in the traced
window.

``loop.dense_flops_util``: the multiply-adds the projections and the heads
need in ALL passes for the traced generations' tokens (the reference module
counts them from the widths, ``costs.py``) over the seconds booked to
``es.dense`` + ``es.head`` and the chips' bf16 peak (``peaks.json``): the
dense stages' share of their compute roofline.  The rank-r corrections are
booked to ``es.perturb`` where they are not fused into a projection, and are
not in the count.

A program whose forward names no ``es.rope`` and no ``es.exit`` runs no
looped model (a program without the scopes, or another sequence model): the
reader returns nothing and does not raise.
"""

from benchmark import stage_reduce

SHARES = {"loop.dense_share": "dense", "loop.attn_share": "attn",
          "loop.head_share": "head", "loop.rope_share": "rope",
          "loop.exit_share": "exit"}
LOOP_ONLY = ("rope", "exit")


def read(run):
    out = stage_reduce.of_run(run)
    if not out:
        return {}
    d = stage_reduce.busiest_device(out["staged"])
    seconds, busy = d["stage_s"], d["busy_s"]
    if not any(stage in seconds for stage in LOOP_ONLY):
        return {}
    values = {metric: seconds.get(stage, 0.0) / busy
              for metric, stage in SHARES.items()}
    peaks = run.get("peaks")
    matmul_s = seconds.get("dense", 0.0) + seconds.get("head", 0.0)
    flops = (run.get("dense_flops_per_member_step", 0)
             + run.get("head_flops_per_member_step", 0))
    if peaks and matmul_s > 0 and flops:
        values["loop.dense_flops_util"] = (
            flops * run["steps_per_generation"] * run["traced_generations"]
            / matmul_s / (run["chips"] * peaks["peak_flops_per_s"]))
    return values
