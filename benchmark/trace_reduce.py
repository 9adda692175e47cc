"""From a profiler trace (``.xplane.pb``) to device busy time, time per
operation and idle gaps.  Read with nothing but JAX's own ``ProfileData``.

What a trace of this system looks like on a TPU v5e (looked at by hand,
PR 23): one plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` holds
one event for every execution of every HLO operation, those inside ``while``
bodies included; the event's name is the operation's HLO text
(``%fusion.458 = f32[...] fusion(...)``).  ``while``/``conditional``/``call``
events are containers that span their bodies, so they are left out of the
busy union and of the per-operation table: *busy* means a leaf operation was
running.  Host threads are lines of ``/host:CPU``; a ``TraceAnnotation`` made
by the runner is an event there under its own name, on the same clock.

    python benchmark/trace_reduce.py <trace-dir-or-file>     # describe it
"""

from __future__ import annotations

import glob
import os
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
CONTAINER = re.compile(r"^(while|conditional|call)([._]|$)")
COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|all-to-all|"
                        r"collective-permute)")
FENCE = "bench_fence"


def find_xplane(path: str) -> str:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    if os.path.isfile(path):
        return path
    found = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return max(found, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(find_xplane(path))


def op_id(event_name: str) -> str:
    """``%fusion.458 = f32[...] fusion(...)`` -> ``fusion.458``."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def op_label(event_name: str) -> str:
    """Operation id and result shape in the characters of a name, so that a
    reader of ``breakdown`` can tell a per-member weight fusion from a
    physics one."""
    head, _, rest = event_name.partition(" = ")
    shape = rest.split(" ", 1)[0] if rest else ""
    label = f"{head.strip().lstrip('%')}:{shape}" if shape else op_id(head)
    return re.sub(r"[^A-Za-z0-9_.\-]+", "_", label).strip("_")[:64]


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, disjoint union of closed intervals."""
    out: list[tuple[float, float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(a: float, b: float, lo: float, hi: float):
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def fence_times(pd) -> list[float]:
    """Start times (s, trace clock) of the runner's fence annotations."""
    times = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            times += [e.start_ns * 1e-9 for e in line.events
                      if e.name == FENCE]
    return sorted(times)


def reduce(pd, window: tuple[float, float] | None = None) -> dict | None:
    """Per device plane: busy seconds (union of leaf-operation intervals
    inside ``window``), seconds per operation label, seconds in
    collectives, and the idle gaps.  ``None`` when the trace holds no
    device operation (a CPU rehearsal)."""
    devices = {}
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
        if not lines:
            continue
        spans, per_op, collective_s = [], {}, 0.0
        kinds: dict[str, tuple | None] = {}   # event name -> (label, is_collective)
        for e in lines[0].events:
            name = e.name
            if name not in kinds:
                ident = op_id(name)
                kinds[name] = (None if CONTAINER.match(ident) else
                               (op_label(name), bool(COLLECTIVE.match(ident))))
            kind = kinds[name]
            if kind is None:
                continue
            a, b = e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9
            if window is not None:
                c = _clip(a, b, *window)
                if c is None:
                    continue
                a, b = c
            spans.append((a, b))
            per_op[kind[0]] = per_op.get(kind[0], 0.0) + (b - a)
            if kind[1]:
                collective_s += b - a
        if not spans:
            continue
        busy = union(spans)
        lo, hi = window if window is not None else (busy[0][0], busy[-1][1])
        edges = [lo] + [t for ab in busy for t in ab] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        devices[plane.name] = {
            "busy_s": sum(b - a for a, b in busy),
            "window_s": hi - lo,
            "events": len(spans),
            "per_op": per_op,
            "collective_s": collective_s,
            "gaps": gaps,
        }
    if not devices:
        return None
    busiest = max(devices, key=lambda k: devices[k]["busy_s"])
    return {
        "devices": devices,
        "busiest": busiest,
        "busy_s_mean": (sum(d["busy_s"] for d in devices.values())
                        / len(devices)),
        "window_s": devices[busiest]["window_s"],
    }


def busiest_device(reduced: dict) -> dict:
    return reduced["devices"][reduced["busiest"]]


def name_gaps(gaps: list[tuple[float, float]],
              spans: list[tuple[str, float, float]], top: int = 10):
    """The ``top`` longest gaps as ``[name, seconds]``: each named by the
    host span that covers its midpoint (``outside_spans`` if none does).
    Gaps under a microsecond, between back-to-back operations, are left
    out."""
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        if b - a < 1e-6:
            break
        mid = 0.5 * (a + b)
        name = next((n for n, s, e in spans if s <= mid <= e),
                    "outside_spans")
        out.append([name, b - a])
    return out


def top_ops(per_op: dict, top: int = 10):
    return [[k, v] for k, v in
            sorted(per_op.items(), key=lambda kv: -kv[1])[:top]]


def describe(pd, out=sys.stdout) -> None:
    """Planes, lines, event counts and the commonest names: what to look
    at by hand before trusting a reduction."""
    for plane in pd.planes:
        print(f"plane {plane.name!r}", file=out)
        for line in plane.lines:
            events = list(line.events)
            names: dict[str, list] = {}
            for e in events:
                rec = names.setdefault(e.name[:100], [0, 0.0])
                rec[0] += 1
                rec[1] += e.duration_ns * 1e-9
            print(f"  line {line.name!r}: {len(events)} events", file=out)
            for n, (c, s) in sorted(names.items(),
                                    key=lambda kv: -kv[1][1])[:12]:
                print(f"    {c:8d} x {s:10.6f} s  {n}", file=out)
            if events:
                e = events[0]
                try:
                    stats = dict(e.stats)
                except Exception as err:  # describing only
                    stats = {"<unreadable>": str(err)}
                print(f"    first event: start_ns {e.start_ns} duration_ns "
                      f"{e.duration_ns} stats {str(stats)[:400]}", file=out)


if __name__ == "__main__":
    describe(load(sys.argv[1]))
