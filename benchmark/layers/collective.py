"""Layer: collectives.  Source: the device trace: seconds of ``all-gather*``
/ ``all-reduce*`` operations (XLA's own names) on the busiest chip over the
traced window.  A one-chip program has none, and the reader returns
nothing."""

from benchmark import trace_reduce


def read(run):
    t = run.get("trace")
    if not t or run["chips"] < 2:
        return {}
    d = trace_reduce.busiest_device(t)
    return {"collective.time_share": d["collective_s"] / d["window_s"]}
