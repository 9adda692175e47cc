"""Layer: host loop (algo/es.py), by the program's own spans.

``record_share`` (source ``program_span``): the seconds the window's
generations spent in their ``record`` phase (best-member tracking, which
can dispatch a device program), over the window.  ``tail_gap_s`` (source
``device_trace``): from the end of a traced generation's LAST leaf
operation on the busiest chip to the runner's fence annotation that closes
the generation, both on the trace's clock; the median over the traced
generations.  It is the largest idle gap of a generation in both cells (PR
24): the host wakes from ``block_until_ready``, runs ``host_sync``, some
Python that no span covers, ``record`` and the ``log_fn`` (the run's log
places each from the trace's annotations; PERF.md §5).  The host's and the
device's planes agree to a millisecond or two, so that is its resolution.
"""

import statistics

from benchmark import stage_reduce


def read(run):
    out = {}
    fences = run["fences"]
    if len(fences) > 1:
        out["span.record_share"] = sum(
            (r.get("phases") or {}).get("record", 0.0)
            for r in run["records"]) / (fences[-1] - fences[0])
    traced = stage_reduce.of_run(run)
    if traced and traced["tail_gaps"]:
        out["span.tail_gap_s"] = statistics.median(traced["tail_gaps"])
    return out
