"""Layers inside the policy forward of a sparse-expert decoder whose
attention is computed inside a COMPRESSED LATENT
(``estorch_tpu/models/cca_moe_lm.py``), by the stage scopes the model names
itself with inside ``es.policy`` (``estorch_tpu/obs/trace.py``): ``es.mix``
(both causal convolutions over q and k, the q-k mean, the value shift, the L2
scale under the temperature), ``es.dense`` (the four projections into and out
of the latent), ``es.attn`` (scores, softmax and ``P.V`` over the latent's
heads), ``es.rope`` (the rotation of half of each head), ``es.route`` (the
router's down-projection, the carried state, its MLP, the choice of ONE
expert), ``es.dispatch``, ``es.expert`` and ``es.head`` (the tied head).
Source: the device trace reduced by ``stage_reduce.py``, as ``layers/dsa.py``
reads it: seconds of the busiest chip's leaf operations booked to each stage
(the INNERMOST scope of an operation's name stack; a fusion to its root's),
as shares of that chip's busy seconds in the traced window.
``cca.expert_share`` adds the unscoped ``ragged-dot*`` operations, as
``layers/moe.py`` does and for its reason.

``cca.dense_flops_util``: the reference's matmul count of the attention's four
projections x the traced generations' tokens / seconds of ``es.dense`` / (chips
x the bf16 peak).  ``cca.head_flops_util``: the tied head's 2 x hidden x
vocabulary a token / seconds of ``es.head`` / peak.
``cca.expert_flops_util``: as ``moe.expert_flops_util``: the EXPECTED tokens
routed INTO HELD experts by a uniform router (tokens x held / total a layer) x
2 x 3 x hidden x expert width / seconds of ``es.expert`` / peak.
``cca.attn_flops_util``: the exact causal pairs x 2 x heads x (head width +
value width) x layers x sequences / seconds of ``es.attn`` / peak.  The new
mechanism's share of ITS roofline, which reads the same work whatever
implements it later (``costs_cca.py``): ``cca.mix_hbm_util`` = the bytes the
least pass over the latent moves (q~, k~ read once; q^, k^ and the shifted v
written once; compute dtype) x layers x sequences / seconds of ``es.mix`` /
(chips x the HBM peak).

The sizes come from the configuration file of the cell that lists these
metrics in ``BENCHMARK.json`` (the run's facts do not carry them).  A program
whose forward names no ``es.mix`` runs no such model (a program without the
scope, or another sequence model): the reader returns nothing and does not
raise.
"""

import os

from benchmark import costs_cca, costs_moe, stage_reduce
from benchmark.files import load_file_module, load_json

SHARES = {"cca.dense_share": "dense", "cca.mix_share": "mix",
          "cca.attn_share": "attn", "cca.rope_share": "rope",
          "cca.route_share": "route", "cca.dispatch_share": "dispatch",
          "cca.expert_share": "expert", "cca.head_share": "head"}
# the stage only this model names: it marks its program
OWN_STAGE = "mix"
# what XLA:TPU names the custom calls it rewrites jax.lax.ragged_dot into
GROUPED_MATMUL = "ragged-dot"
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def model_sizes():
    """``(sizes, describe)`` of the configuration the ``cca.*`` metrics'
    cell runs, from its reference module; ``None`` where ``BENCHMARK.json``
    names none."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = [m.get("workloads", []) for m in bench["per_layer"]
             if m["name"] == "cca.mix_hbm_util"]
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
    if OWN_STAGE not in seconds:
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

    def util(name, work, stage):
        if seconds.get(stage, 0.0) > 0 and work:
            values[name] = work / seconds[stage] / chip_flops

    util("cca.dense_flops_util",
         run.get("dense_flops_per_member_step", 0) * tokens, "dense")
    util("cca.head_flops_util",
         run.get("head_flops_per_member_step", 0) * tokens, "head")
    found = model_sizes()
    if not found:
        return values
    s, about = found
    length, layers = s["seq_len"], len(s["layer_types"])
    sequences = tokens / length
    routed = (tokens * about["expert_layers"]
              * about["expected_pairs_per_token_and_layer"])
    visible = costs_cca.causal_pairs(length)
    moved = costs_cca.mix_bytes(length, s["num_attention_heads"],
                                s["num_key_value_heads"], s["head_dim"])
    print(f"[cca] counted a sequence of {length} and a layer: {visible} "
          f"causal pairs, {moved} bytes through the latent's mixing; "
          f"{layers} layers, {sequences:.0f} sequences traced; the EXPECTED "
          f"tokens a uniform router sends the held experts {routed:.0f}",
          flush=True)
    util("cca.expert_flops_util",
         routed * costs_moe.expert_flops_per_pair(
             s["hidden_size"], s["moe_intermediate_size"]), "expert")
    util("cca.attn_flops_util",
         visible * costs_cca.attention_flops_per_pair(
             s["num_attention_heads"], s["head_dim"]) * layers * sequences,
         "attn")
    if seconds.get("mix", 0.0) > 0:
        values["cca.mix_hbm_util"] = (
            moved * layers * sequences / seconds["mix"]
            / (run["chips"] * peaks["peak_hbm_bytes_per_s"]))
    return values
