"""From a profiler trace to seconds per **stage** of the generation program,
and to the host's spans on the same clock.

The program names its stages with name-stack scopes ``es.<stage>``
(``estorch_tpu/obs/trace.py``); on a TPU each operation's event metadata
carries that stack as ``tf_op`` (``xplane_meta.py``).  An operation belongs
to the INNERMOST ``es.<stage>`` of its stack and to ``unscoped`` without
one; a fusion carries the stack of its root, so one that spans two stages
is booked whole to one.  Leaf operations are picked exactly as
``trace_reduce.reduce`` picks them (same plane, line, container rule and
window clipping), and each instant of the busy union is booked once, to
the operation that started first: the stages of a chip sum to its
``busy_s``.

The host's spans are the trace's own annotations: every
``Telemetry.phase`` enters a ``TraceAnnotation`` that carries the
generation, so they have true starts and ends and need no reconstruction.
The largest idle gap of a generation lies after its LAST operation (the
host wakes, syncs, records; PERF.md §5): ``tail_gaps`` measures it, from
the device plane to the runner's next fence annotation.

    python -m benchmark.stage_reduce <trace-dir-or-file>     # the table
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics
import sys
import time

from benchmark import trace_reduce, xplane_meta

try:
    from estorch_tpu.obs.trace import SCOPE_PREFIX, STAGES
except ImportError:     # a program from before the scopes: all is unscoped
    SCOPE_PREFIX, STAGES = "es.", ()

# one component of a name stack that IS a stage scope, bare or under the
# transforms jax wraps around it (``vmap(es.env)``)
SCOPE = re.compile(r"(?:^|/)(?:\w+\()*" + re.escape(SCOPE_PREFIX)
                   + "(" + "|".join(STAGES) + r")\)*(?=/|$)")
UNSCOPED = "unscoped"
# the spans ``trace_reduce.name_gaps`` has always named idle gaps by
GAP_NAMES = {"device": "inside_generation"}
BETWEEN = "between_generations"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def stage_of(tf_op: str | None) -> str:
    """Innermost stage scope of a name stack; a word after ``es.`` that is
    not one of ``STAGES`` names no stage."""
    found = SCOPE.findall(tf_op or "") if STAGES else ()
    return found[-1] if found else UNSCOPED


def reduce(pd, meta: dict, window: tuple[float, float] | None = None):
    """Per device plane: seconds per stage (summing to the busy union),
    per stage the seconds, bytes, FLOPs and name stack of each operation
    label, and the sorted end times of the leaf operations.  ``meta`` is
    ``xplane_meta.event_metadata`` of the same file.  ``None`` when the
    trace holds no device operation."""
    devices = {}
    for plane in pd.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        lines = [ln for ln in plane.lines if ln.name == trace_reduce.OPS_LINE]
        if not lines:
            continue
        plane_meta = meta.get(plane.name, {})
        kinds: dict[str, tuple | None] = {}
        leaves = []
        for e in lines[0].events:
            name = e.name
            if name not in kinds:
                ident = trace_reduce.op_id(name)
                stats = plane_meta.get(name, {})
                kinds[name] = (None if trace_reduce.CONTAINER.match(ident)
                               else (stage_of(stats.get("tf_op")),
                                     trace_reduce.op_label(name),
                                     stats.get("bytes_accessed") or 0,
                                     stats.get("flops") or 0,
                                     stats.get("tf_op") or ""))
            kind = kinds[name]
            if kind is None:
                continue
            a, b = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
            if window is not None:
                c = trace_reduce._clip(a, b, *window)
                if c is None:
                    continue
                a, b = c
            leaves.append((a, b, kind))
        if not leaves:
            continue
        leaves.sort(key=lambda x: x[:2])
        stage_s: dict[str, float] = {}
        ops: dict[str, dict[str, list]] = {}
        covered = leaves[0][0]
        for a, b, (stage, label, nbytes, flops, tf_op) in leaves:
            new = max(0.0, b - max(a, covered))
            covered = max(covered, b)
            stage_s[stage] = stage_s.get(stage, 0.0) + new
            rec = ops.setdefault(stage, {}).setdefault(
                label, [0.0, 0, 0, tf_op])
            rec[0] += new
            rec[1] += nbytes
            rec[2] += flops
        devices[plane.name] = {
            "stage_s": stage_s, "ops": ops,
            "busy_s": sum(stage_s.values()),
            "first_start": leaves[0][0],
            "ends": sorted(b for _, b, _ in leaves),
            "scoped_ops": sum(1 for k in kinds.values()
                              if k is not None and k[0] != UNSCOPED),
        }
    if not devices:
        return None
    busiest = max(devices, key=lambda k: devices[k]["busy_s"])
    return {"devices": devices, "busiest": busiest}


def host_spans(pd) -> list[tuple[str, float, float, int]]:
    """``(name, start_s, end_s, generation)`` of every annotation on a host
    plane that carries a generation: the program's ``Telemetry.phase``
    spans, sorted by start."""
    spans = []
    for plane in pd.planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                generation = dict(e.stats).get("generation")
                if generation is None:
                    continue
                a = e.start_ns * 1e-9
                spans.append((e.name, a, a + e.duration_ns * 1e-9,
                              int(generation)))
    return sorted(spans, key=lambda s: s[1])


def gap_spans(spans, window: tuple[float, float]):
    """The program's spans under the names ``trace_reduce.name_gaps`` has
    always used, innermost first, then ``between_generations`` for what
    no span covers inside ``window``."""
    named = sorted(((GAP_NAMES.get(n, n), a, b) for n, a, b, _ in spans),
                   key=lambda s: s[2] - s[1])
    covered = trace_reduce.union([(a, b) for _, a, b in named])
    edges = [window[0]] + [t for ab in covered for t in ab] + [window[1]]
    between = [(BETWEEN, edges[i], edges[i + 1])
               for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]
    return named + between


def tail_gaps(ends: list[float], marks: list[float]) -> list[float]:
    """Per traced generation (two consecutive fence annotations): seconds
    from the end of its last leaf operation to the fence that closes it.
    ``ends`` sorted; a generation without an operation gives nothing."""
    out = []
    for opened, closed in zip(marks, marks[1:]):
        i = bisect.bisect_right(ends, closed)
        if i and ends[i - 1] > opened:
            out.append(closed - ends[i - 1])
    return out


def newest_trace(root: str = ROOT) -> str | None:
    """The newest ``.xplane.pb`` a run of the benchmark left behind
    (``benchmark_out/<cell>/trace``: the runner empties the directory
    before each traced run)."""
    found = glob.glob(os.path.join(root, "benchmark_out", "*", "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def read_trace(path: str, gaps=None) -> dict | None:
    """Everything the readers and ``describe`` want of one trace file: the
    traced window (first to last fence annotation; the span of the leaf
    operations without fences), the stage table, the host's spans, the
    tail gaps and, given the idle ``gaps`` of ``trace_reduce.reduce``,
    their names from the annotations.  ``None`` without device
    operations."""
    pd = trace_reduce.load(path)
    marks = trace_reduce.fence_times(pd)
    window = (marks[0], marks[-1]) if len(marks) > 1 else None
    staged = reduce(pd, xplane_meta.event_metadata(path), window)
    if not staged:
        return None
    d = busiest_device(staged)
    window = window or (d["first_start"], d["ends"][-1])
    spans = host_spans(pd)
    return {"path": path, "window": window, "staged": staged, "spans": spans,
            "tail_gaps": tail_gaps(d["ends"], marks),
            "named_gaps": (trace_reduce.name_gaps(
                gaps, gap_spans(spans, window)) if spans and gaps else None)}


def of_run(run: dict, say=print) -> dict | None:
    """The traced run's ``read_trace``; computed once and kept in ``run``
    for the next reader.  ``None`` (said why) when the run took no trace
    or the trace found is not the one the runner reduced."""
    if "stage_reduce" in run:
        return run["stage_reduce"]
    run["stage_reduce"] = None
    reduced, path = run.get("trace"), newest_trace()
    if not reduced or not path:
        return None
    t = time.perf_counter()
    chip = trace_reduce.busiest_device(reduced)
    out = read_trace(path, chip["gaps"])
    if not out or abs(busiest_device(out["staged"])["busy_s"]
                      - chip["busy_s"]) > 1e-6:
        say(f"[stage_reduce] {path} is not the trace this run reduced "
            f"(busy_s {chip['busy_s']!r}): no stage metrics")
        return None
    run["stage_reduce"] = out
    for text in describe(out):
        say("[stage_reduce] " + text)
    say(f"[stage_reduce] read again and reduced by stage in "
        f"{time.perf_counter() - t:.2f} s")
    return out


def busiest_device(staged: dict) -> dict:
    return staged["devices"][staged["busiest"]]


def describe(out: dict, top: int = 4) -> list[str]:
    """The stage table of the busiest chip, the host's spans and the idle
    gaps named from them, as lines of text."""
    d = busiest_device(out["staged"])
    busy = d["busy_s"]
    lo, hi = out["window"]
    lines = [f"{out['path']}: {out['staged']['busiest']} busy_s {busy:.6f} "
             f"in the traced window of {hi - lo:.6f} s, first leaf operation "
             f"at +{d['first_start'] - lo:.6f}, last ends at "
             f"+{d['ends'][-1] - lo:.6f}; {d['scoped_ops']} operation names "
             f"carry a stage"]
    for stage, s in sorted(d["stage_s"].items(), key=lambda kv: -kv[1]):
        ops = d["ops"][stage]
        nbytes = sum(o[1] for o in ops.values())
        flops = sum(o[2] for o in ops.values())
        lines.append(
            f"stage {stage}: {s:.6f} s, share {s / busy:.6f}, "
            f"{nbytes / max(s, 1e-12) / 1e9:.1f} GB/s accessed, "
            f"{flops / max(s, 1e-12) / 1e12:.3f} TFLOP/s; " + "; ".join(
                f"{label} {o[0]:.6f} s"
                + (f" (name stack {o[3]!r})" if stage == UNSCOPED else "")
                for label, o in
                sorted(ops.items(), key=lambda kv: -kv[1][0])[:top]))
    if out["tail_gaps"]:
        lines.append(
            "tail gap (end of a generation's last leaf operation to the "
            f"fence that closes it): {out['tail_gaps']}, median "
            f"{statistics.median(out['tail_gaps']):.6f} s")
    spans = out["spans"]
    if not spans:
        lines.append("host spans: the trace holds no annotation that carries"
                     " a generation (a program whose phases are not trace "
                     "annotations)")
        return lines
    lines.append("host spans, from the trace's annotations: " + "; ".join(
        f"{n}[{g}] +{a - lo:.6f} for {b - a:.6f}"
        for n, a, b, g in spans[:12]))
    if out["named_gaps"] is not None:
        lines.append("idle gaps named from the trace's annotations: "
                     f"{out['named_gaps']}")
    return lines


if __name__ == "__main__":
    found = read_trace(trace_reduce.find_xplane(sys.argv[1]))
    print("\n".join(describe(found)) if found else "no device operation")
