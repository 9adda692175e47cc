"""Layers inside the policy forward of a SambaY decoder with differential
attention (``estorch_tpu/models/sambay_lm.py``), by the stage scopes and
parts the model names itself with inside ``es.policy``
(``estorch_tpu/obs/trace.py``): ``es.dense`` (every projection and the gated
FFN), ``es.ssm`` (Mamba-1's conv, ``Δ``, the selective scan, the gate),
``es.gmu`` (a gated memory unit's gate product), ``es.attn`` with the parts
``of.window``, ``of.full`` and ``of.cross`` (scores, softmax, ``P.V`` of the
banded layer, of the layer whose keys and values are shared, of the layers
that read them), ``es.diff`` (``λ``, the subtraction of the two maps, the
norm over a head pair's values) and ``es.head``.  Source: the device trace
reduced by ``stage_reduce.py``, as ``layers/lm.py`` reads it: seconds of the
busiest chip's leaf operations booked to each stage (the INNERMOST scope of
an operation's name stack; a fusion to its root's), as shares of that chip's
busy seconds in the traced window.  ``sambay.full_attn_share`` is ``of.full``
+ ``of.cross``.

``sambay.dense_flops_util``: the reference's matmul count × the traced
generations' tokens ÷ seconds of ``es.dense`` + ``es.head`` ÷ (chips × the
bf16 peak).  ``sambay.attn_flops_util``: the EXACT banded and causal count of
``costs_sambay.py`` (visible pairs only) × sequences ÷ seconds of ``es.attn``
÷ (chips × peak).  ``sambay.ssm_hbm_util``: the scans' LEAST bytes
(``costs_sambay.scan_bytes_per_sequence``) × sequences ÷ seconds of
``es.ssm`` ÷ (chips × the HBM peak): the new mechanism's share of its
roofline, which reads the same work whatever implements the scan.

The sizes come from the configuration file of the cell that lists these
metrics in ``BENCHMARK.json`` (the run's facts do not carry them).  A program
whose forward names neither ``es.gmu`` nor ``es.diff`` runs no such model (a
program without the scopes, or another sequence model): the reader returns
nothing and does not
raise.  Where the compiler fuses a gated memory unit's gate product into
the operand of the projection that reads it, no operation's ROOT is booked to
``es.gmu`` and ``sambay.gmu_share`` reads 0 (said in the log): the model's
program is then known by ``es.diff``.
"""

import os
import re

from benchmark import costs_sambay, stage_reduce
from benchmark.files import load_file_module, load_json

# metric: its stage, and for es.attn the parts of it
SHARES = {"sambay.dense_share": ("dense", None),
          "sambay.ssm_share": ("ssm", None),
          "sambay.gmu_share": ("gmu", None),
          "sambay.window_attn_share": ("attn", ("window",)),
          "sambay.full_attn_share": ("attn", ("full", "cross")),
          "sambay.diff_share": ("diff", None),
          "sambay.head_share": ("head", None)}
# the stages only this model names: one of them marks its program
OWN_STAGES = ("gmu", "diff")
# the part scope of obs/trace.py, bare or under jax's transforms
PART = re.compile(r"(?:^|/)(?:\w+\()*of\.([A-Za-z0-9_.]+?)\)*(?=/|$)")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def model_sizes():
    """The reference's ``sizes`` of the configuration the ``sambay.*``
    metrics' cell runs; ``None`` where ``BENCHMARK.json`` names none."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = [m.get("workloads", []) for m in bench["per_layer"]
             if m["name"] == "sambay.ssm_hbm_util"]
    if not cells or not cells[0]:
        return None
    cell = next(w for w in bench["workloads"] if w["name"] == cells[0][0])
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, entry["file"]))
    ref = load_file_module(os.path.join(
        os.path.dirname(HERE), "reference", config["reference"] + ".py"))
    return ref.sizes(config)


def attention_seconds(device: dict) -> dict:
    """Seconds booked to ``es.attn`` by the part in each operation's name
    stack (``window``, ``full``, ``cross``; ``""`` without one)."""
    out: dict[str, float] = {}
    for s, _, _, tf_op in device["ops"].get("attn", {}).values():
        found = PART.findall(tf_op or "")
        name = found[-1] if found else ""
        out[name] = out.get(name, 0.0) + s
    return out


def read(run):
    out = stage_reduce.of_run(run)
    if not out:
        return {}
    d = stage_reduce.busiest_device(out["staged"])
    seconds, busy = d["stage_s"], d["busy_s"]
    if not any(stage in seconds for stage in OWN_STAGES):
        return {}
    by_part = attention_seconds(d)
    values = {metric: (seconds.get(stage, 0.0) if parts is None else sum(
        by_part.get(p, 0.0) for p in parts)) / busy
        for metric, (stage, parts) in SHARES.items()}
    if "gmu" not in seconds:
        print("[sambay] no operation's root is booked to es.gmu: the "
              "compiler fused the gate product silu(u W1) * m into a "
              "neighbour (a fusion is booked whole to its root's stage)",
              flush=True)
    print("[sambay] es.attn by part: " + "; ".join(
        f"{name or '(no part)'} {s:.6f} s"
        for name, s in sorted(by_part.items())), flush=True)
    peaks = run.get("peaks")
    if not peaks:
        return values
    tokens = run["steps_per_generation"] * run["traced_generations"]
    chip_flops = run["chips"] * peaks["peak_flops_per_s"]
    matmul_s = seconds.get("dense", 0.0) + seconds.get("head", 0.0)
    flops = (run.get("dense_flops_per_member_step", 0)
             + run.get("head_flops_per_member_step", 0))
    if matmul_s > 0 and flops:
        values["sambay.dense_flops_util"] = (
            flops * tokens / matmul_s / chip_flops)
    s = model_sizes()
    if not s:
        return values
    length = s["seq_len"]
    sequences = tokens / length
    attn = costs_sambay.attention_flops_per_sequence(
        s["layer_types"], length, s["sliding_window"],
        s["num_attention_heads"],
        s["hidden_size"] // s["num_attention_heads"])
    scan_bytes = costs_sambay.scan_bytes_per_sequence(
        s["layer_types"], length, s["mamba_expand"] * s["hidden_size"],
        s["mamba_d_state"])
    print(f"[sambay] counted a sequence of {length}: attention "
          f"{attn['window']} FLOP banded + {attn['full']} FLOP full causal "
          f"(visible pairs only), scans {scan_bytes} bytes at the least; "
          f"{sequences:.0f} sequences traced", flush=True)
    if seconds.get("attn", 0.0) > 0:
        values["sambay.attn_flops_util"] = (
            (attn["window"] + attn["full"]) * sequences / seconds["attn"]
            / chip_flops)
    if seconds.get("ssm", 0.0) > 0:
        values["sambay.ssm_hbm_util"] = (
            scan_bytes * sequences / seconds["ssm"]
            / (run["chips"] * peaks["peak_hbm_bytes_per_s"]))
    return values
