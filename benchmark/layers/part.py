"""The work beneath a stage, by the PARTS the program names inside its stages
(``estorch_tpu/obs/trace.py``: ``part``, the scope ``of.<leaf's key>`` around
every operation that multiplies that parameter leaf; nested parts read as a
path, ``of.shared/…/of.gate`` is ``shared.gate``).  Source: the device trace
reduced by ``stage_reduce.py``, whose per-operation rows (seconds, XLA's bytes
and FLOPs, name stack) are bucketed here by the part in the name stack; the
stage an operation books to is ``stage_reduce``'s and does not change.

- ``part.named_share``: of the busiest chip's seconds booked to ``es.dense``,
  ``es.head`` and to ``es.perturb`` beneath them, the share whose operation
  carries a part: the coverage check.  0 with those stages present means the
  executable came from a compile-cache entry written without the parts (the
  cache key leaves metadata out; PERF.md §3): the trace is reported as it
  reads, as ``layers/stage.py`` does: this share 0 and, with no second booked
  to any part, every other ``part.*`` value 0, and the log says why.  Trace
  on a cache directory of its own.
- ``part.ffn_flops_util``, ``part.mixer_flops_util``, ``part.head_flops_util``:
  the multiply-adds ``costs_parts.py`` counts for the group's parts (``gate up
  down``, the shared experts' too; the projections into and out of the token
  mixers; the heads' logits matmuls, under ``es.head``) × the traced
  generations' tokens ÷ ALL the seconds booked to those parts (an unfused
  correction's and a layout copy's too) ÷ (chips × the bf16 peak).
- ``part.attn_flops_util``: the exact causal count × tokens ÷ seconds booked
  to ``es.attn`` ÷ (chips × peak).
- ``part.correction_share``: busy seconds booked to ``es.perturb`` beneath a
  part ÷ busy seconds, XLA's plain ``copy`` operations left out (a layout
  copy whose root happens to be a correction's add does no arithmetic: the
  logits' copies are the large ones, printed per part): the rank-r
  corrections XLA did not fuse into their projection; what is left of
  ``stage.perturb_share`` is no correction.

A utilisation is reported only where XLA's own FLOPs of the operations booked
to the group reach ``COVERED`` of the counted ones: a name lost to a re-fusion
would shrink the seconds and read over the peak.  Else it is left out and the
log says which parts fell short.  (A Mosaic kernel's FLOPs are the ones it
declares in its ``cost_estimate``.)

The reader prints, per part: seconds, share of busy and how they split
(operations that multiply matrices, above ``MATMUL_RATE`` of the MXU's peak by
XLA's FLOPs, and their rate; XLA's collectives by name; corrections under
``es.perturb``; layout copies; the rest, e.g. a fusion that combines partial
sums across chips), XLA's FLOPs and bytes per second, FLOPs per byte by XLA's counts (which include on-chip reuse) and
by the shapes' least (``costs_parts.py``) against the chip's ridge, the
counted FLOPs' share of the peak, and XLA's (a kernel's declared) FLOPs over
the counted ones.

The widths come from the configuration file of whichever cell listed under
these metrics in ``BENCHMARK.json`` counts the run's own
``dense_flops_per_member_step + head_flops_per_member_step`` (the run's facts
name no configuration).  A program without ``part`` (the parent of the PR that
added it) names nothing: the reader returns nothing and does not raise.
"""

import os
import re

from benchmark import costs_parts, stage_reduce, trace_reduce
from benchmark.files import load_json

try:
    from estorch_tpu.obs.trace import PART_PREFIX
except ImportError:         # a program from before the parts
    PART_PREFIX = None

COVERED = 0.98
# an operation that multiplies matrices runs above this share of the MXU's
# peak by XLA's own FLOPs; a combine of partial sums, a copy, a norm do not
MATMUL_RATE = 0.1
MATMUL_STAGES = ("dense", "head")
PERTURB = "perturb"
ATTN = "attn"
NO_PART = "(no part)"
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def part_of(tf_op: str) -> str:
    """The part path of a name stack: its ``of.<name>`` components, bare or
    under the transforms jax wraps around them, joined by ``.``; ``""``
    without one."""
    found = re.findall(r"(?:^|/)(?:\w+\()*" + re.escape(PART_PREFIX)
                       + r"([A-Za-z0-9_.]+?)\)*(?=/|$)", tf_op or "")
    return ".".join(found)


def rows_of(device: dict) -> list[dict]:
    """One row per (stage, operation label) of ``stage_reduce.reduce``'s
    table for one chip: its part, the stages of its name stack, seconds,
    XLA's bytes and FLOPs."""
    return [{"stage": stage, "label": label, "part": part_of(tf_op),
             "stack": stage_reduce.SCOPE.findall(tf_op), "s": s,
             "bytes": nbytes, "flops": flops,
             # XLA's layout copy, as it names one; a fusion is none
             "copy": label.startswith("copy") and "fusion" not in label,
             "collective": bool(trace_reduce.COLLECTIVE.match(label))}
            for stage, ops in device["ops"].items()
            for label, (s, nbytes, flops, tf_op) in ops.items()]


def beneath_matmul(row: dict) -> bool:
    """Booked to ``es.dense`` or ``es.head``, or to ``es.perturb`` inside
    one of them."""
    return (row["stage"] in MATMUL_STAGES
            or (row["stage"] == PERTURB
                and any(s in MATMUL_STAGES for s in row["stack"])))


def cell_config(run: dict, say):
    """The configuration whose parts count what the run's facts count, among
    the cells ``BENCHMARK.json`` lists under these metrics; ``None`` (said
    why) where none does."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = next((m.get("workloads", []) for m in bench["per_layer"]
                  if m["name"] == "part.named_share"), [])
    want = (run.get("dense_flops_per_member_step", 0)
            + run.get("head_flops_per_member_step", 0))
    for name in cells:
        cell = next(w for w in bench["workloads"] if w["name"] == name)
        entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
        config = load_json(os.path.join(ROOT, entry["file"]))
        if costs_parts.counted_by_reference(config) == want:
            return config
    say(f"no configuration of {cells} counts the run's {want} FLOPs a "
        f"token: no utilisation")
    return None


def group_rows(rows: list[dict]) -> dict[str, list[dict]]:
    """The rows of each utilisation's group."""
    groups = {"ffn": [], "mixer": [], "head": [], "attn": []}
    for row in rows:
        if row["stage"] == ATTN:
            groups["attn"].append(row)
        group = costs_parts.group_of(row["part"]) if row["part"] else None
        if group == "head" and "head" not in row["stack"]:
            continue            # the embedding's lookup, not a tied head
        if group and beneath_matmul(row):
            groups[group].append(row)
    return groups


def describe(rows, counted, busy, per_chip_tokens, peaks, say) -> None:
    """The per-part table, largest first."""
    ridge = peaks["peak_flops_per_s"] / peaks["peak_hbm_bytes_per_s"]
    buckets: dict = {}
    for row in rows:
        if not (row["part"] or beneath_matmul(row)):
            continue
        b = buckets.setdefault(row["part"] or NO_PART,
                               {"s": 0.0, "perturb_s": 0.0, "copy_s": 0.0,
                                "collective_s": 0.0, "matmul_s": 0.0,
                                "matmul_flops": 0, "bytes": 0, "flops": 0,
                                "stages": set()})
        b["s"] += row["s"]
        b["bytes"] += row["bytes"]
        b["flops"] += row["flops"]
        b["stages"].add(row["stage"])
        if row["copy"]:
            b["copy_s"] += row["s"]
        elif row["collective"]:
            b["collective_s"] += row["s"]
        elif row["stage"] == PERTURB:
            b["perturb_s"] += row["s"]
        elif row["flops"] > MATMUL_RATE * peaks["peak_flops_per_s"] * row["s"]:
            b["matmul_s"] += row["s"]
            b["matmul_flops"] += row["flops"]
    say(f"parts of the busiest chip, largest first (ridge "
        f"{ridge:.0f} FLOP/B; XLA's bytes include on-chip reuse):")
    for name, b in sorted(buckets.items(), key=lambda kv: -kv[1]["s"]):
        s = max(b["s"], 1e-12)
        other = max(0.0, b["s"] - b["matmul_s"] - b["collective_s"]
                    - b["perturb_s"] - b["copy_s"])
        text = (f"part {name}: {b['s']:.6f} s, share {b['s'] / busy:.6f}, "
                f"of it {b['matmul_s']:.6f} s of matmuls at "
                f"{b['matmul_flops'] / max(b['matmul_s'], 1e-12) / 1e12:.1f} "
                f"TFLOP/s, {b['collective_s']:.6f} s of collectives, "
                f"{b['perturb_s']:.6f} s of corrections under es.perturb, "
                f"{b['copy_s']:.6f} s of layout copies and {other:.6f} s of "
                f"other operations under {MATMUL_RATE} of the peak; booked "
                f"to "
                f"{sorted(b['stages'])}; {b['flops'] / s / 1e12:.3f} "
                f"TFLOP/s and {b['bytes'] / s / 1e9:.1f} GB/s by XLA's "
                f"counts")
        if b["bytes"]:
            intensity = b["flops"] / b["bytes"]
            text += (f", {intensity:.1f} FLOP/B ("
                     f"{'left' if intensity < ridge else 'right'} of the "
                     f"ridge)")
        if name in counted:
            flops, least = counted[name]
            want = flops * per_chip_tokens
            text += (f"; XLA's FLOPs over counted {b['flops'] / want:.4f}, "
                     f"{flops / least:.1f} FLOP/B by the shapes' least bytes "
                     f"({'left' if flops / least < ridge else 'right'})")
            # seconds that do not hold the part's matmul say nothing of it
            # (a grouped matmul reaches the trace without its name stack)
            text += (f", counted {want / s / peaks['peak_flops_per_s']:.4f} "
                     f"of the peak" if b["flops"] >= COVERED * want
                     else "; its matmul is booked elsewhere")
        say(text)


def read(run, say=None):
    say = say or (lambda text: print("[part] " + text, flush=True))
    out = stage_reduce.of_run(run)
    if not out:
        return {}
    if PART_PREFIX is None:
        say("this program has no estorch_tpu.obs.trace.part: no operation "
            "names a part")
        return {}
    d = stage_reduce.busiest_device(out["staged"])
    rows, busy = rows_of(d), d["busy_s"]
    matmul_s = sum(r["s"] for r in rows if beneath_matmul(r))
    if matmul_s <= 0:
        return {}               # no sequence model's stages in this program
    named_s = sum(r["s"] for r in rows if beneath_matmul(r) and r["part"])
    values = {"part.named_share": named_s / matmul_s}
    if named_s == 0:
        say("es.dense / es.head are in the trace and no operation beneath "
            "them names a part: the executable came from a compile-cache "
            "entry written without the parts; the trace is reported as it "
            "reads, no second booked to any part.  Trace on a cache "
            "directory of its own")
        return {**values, **dict.fromkeys(
            ("part.correction_share", "part.ffn_flops_util",
             "part.mixer_flops_util", "part.head_flops_util",
             "part.attn_flops_util"), 0.0)}
    values["part.correction_share"] = sum(
        r["s"] for r in rows
        if r["stage"] == PERTURB and r["part"] and not r["copy"]) / busy
    dense_s = sum(r["s"] for r in rows if r["stage"] == "dense")
    say(f"the parts' seconds under es.dense ({NO_PART} among them) sum to "
        f"{dense_s:.9f} s, the stage's are "
        f"{d['stage_s'].get('dense', 0.0):.9f} s")
    peaks = run.get("peaks")
    config = cell_config(run, say) if peaks else None
    if not config:
        return values
    counted = costs_parts.parts(config)
    per_chip_tokens = (run["steps_per_generation"]
                       * run["traced_generations"] / run["chips"])
    describe(rows, counted, busy, per_chip_tokens, peaks, say)
    want = {"ffn": 0.0, "mixer": 0.0, "head": 0.0,
            "attn": costs_parts.attention_flops_per_token(config)}
    for name, (flops, _) in counted.items():
        group = costs_parts.group_of(name)
        if group:
            want[group] += flops
    for group, members in group_rows(rows).items():
        seconds = sum(r["s"] for r in members)
        flops = want[group] * per_chip_tokens
        if seconds <= 0 or flops <= 0:
            continue
        found = sum(r["flops"] for r in members)
        if group == "attn":
            say(f"es.attn: XLA's (a kernel's declared) FLOPs over the exact "
                f"causal count {found / flops:.4f}")
        if found < COVERED * flops:
            named = sorted({r["part"] or r["stage"] for r in members})
            say(f"part.{group}_flops_util left out: the operations booked to "
                f"{named} carry {found / flops:.4f} of the counted FLOPs "
                f"(under {COVERED}): a part lost its name to a re-fusion, or "
                f"an operation its cost")
            continue
        values[f"part.{group}_flops_util"] = (
            flops / seconds / peaks["peak_flops_per_s"])
    return values
