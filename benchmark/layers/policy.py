"""Layer: policy forward.  An end-to-end utilisation, NOT a kernel's
roofline share: the multiply-adds the policy needs for every scanned
member-step of a generation (the configuration's reference module counts
them from the widths, ``costs.py``) over the device seconds a generation
takes (trace) and the chips' bf16 peak (``peaks.json``, keyed by
``device_kind``)."""

from benchmark import trace_reduce


def read(run):
    t, peaks = run.get("trace"), run.get("peaks")
    if not t or not peaks:
        return {}
    device_s = (trace_reduce.busiest_device(t)["busy_s"]
                / run["traced_generations"])
    flops = run["policy_flops_per_member_step"] * run["steps_per_generation"]
    return {"policy.flops_util":
            flops / device_s / (run["chips"] * peaks["peak_flops_per_s"])}
