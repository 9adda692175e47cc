"""Layer: device.  ``idle_share``: 1 - busy / window on the busiest chip,
busy being the union of the intervals in which a leaf operation ran
(``trace_reduce.py``).  ``peak_hbm_gib``: ``memory_stats()
["peak_bytes_in_use"]``, the fullest chip."""

from benchmark import trace_reduce


def read(run):
    out = {}
    t = run.get("trace")
    if t:
        d = trace_reduce.busiest_device(t)
        out["device.idle_share"] = min(1.0, max(
            0.0, 1.0 - d["busy_s"] / d["window_s"]))
    if run.get("memory_peak_bytes"):
        out["device.peak_hbm_gib"] = run["memory_peak_bytes"] / 2**30
    return out
