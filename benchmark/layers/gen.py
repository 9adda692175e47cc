"""Layer: generation program.  Source: the device trace: busy seconds of
the busiest chip in the traced window over the generations traced."""

from benchmark import trace_reduce


def read(run):
    t = run.get("trace")
    if not t:
        return {}
    busy = trace_reduce.busiest_device(t)["busy_s"]
    return {"gen.device_s": busy / run["traced_generations"]}
