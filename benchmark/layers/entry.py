"""Layer: entry (ES.train).  Source: the record's ``wall_time_s`` (host
clock around a ``block_until_ready`` fence) of the window's generations."""

import statistics

from benchmark import window


def read(run):
    walls = [r["wall_time_s"] for r in run["records"]]
    return {"entry.gen_s_p50": statistics.median(walls),
            "entry.gen_s_mean_over_p50": window.mean_over_median(walls)}
