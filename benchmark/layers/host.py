"""Layer: host loop (algo/es.py).  Source: the benchmark's fences and the
records' ``wall_time_s``.  ``stall_share`` is what the median interval does
not account for: one slow generation in fifty moves it and leaves
``steps_per_s_per_chip`` alone."""

from benchmark import window


def read(run):
    walls = [r["wall_time_s"] for r in run["records"]]
    return {"host.between_gen_share":
            window.between_share(run["fences"], walls),
            "host.stall_share": window.stall_share(run["fences"])}
