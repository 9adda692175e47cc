"""One run of one cell of ``BENCHMARK.json``.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Driven by data: the cell names a configuration (``benchmark/configs/``) and
a traffic mix (``benchmark/traffic/<name>.json``); the mix's ``kind`` names
the runner (``benchmark/<kind>_runner.py``); each per-layer metric
``<group>.<x>`` is read by ``benchmark/layers/<group>.py``.  A new cell,
configuration, mix or per-layer metric is new files and new entries; no file
that is there needs an edit.

It fails (non-zero, no result line) when JAX finds no TPU or fewer chips
than the cell asks for.  ``--rehearse`` is the one way to run on a CPU: it
applies the configuration's ``rehearsal_kwargs`` (a tiny population) and
prints every metric as ``rehearsal.<name>``, never under a device metric's
name.  Every line printed names platform, device kind and device count; the
last line is the result object.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def process_age_s() -> float:
    """Seconds since this process was started, as the kernel has it, so
    that the interpreter's own start-up is inside ``setup_s``."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


AGE_AT_START = process_age_s()


class Say:
    """print, with the device named on every line."""

    prefix = "[device not asked yet]"

    def __call__(self, text: str) -> None:
        print(f"{self.prefix} {text}", flush=True)


def one(entries, name, what):
    found = [e for e in entries if e["name"] == name]
    if len(found) != 1:
        raise SystemExit(f"{what} {name!r}: {len(found)} entries in "
                         f"BENCHMARK.json")
    return found[0]


def in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="allow a CPU, at the configuration's rehearsal "
                         "size; metrics are printed as rehearsal.<name>")
    ap.add_argument("--reference-seed", type=int, default=None,
                    help="rehearsal only: give the reference another seed "
                         "(correct must come out false)")
    args = ap.parse_args()
    if args.reference_seed is not None and not args.rehearse:
        raise SystemExit("--reference-seed is for rehearsals")

    from benchmark.files import load_file_module, load_json

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = one(bench["workloads"], args.workload, "workload")
    config_entry = one(bench["configs"], cell["config"], "config")
    config = load_json(os.path.join(ROOT, config_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    out_dir = os.path.join(ROOT, "benchmark_out", cell["name"])
    os.makedirs(out_dir, exist_ok=True)

    runner = load_file_module(os.path.join(
        HERE, traffic["kind"] + "_runner.py"))
    say = Say()

    def setup_clock(t: float) -> float:
        return AGE_AT_START + (t - T_START)

    result = runner.run(cell, config, traffic, args, out_dir, say,
                        setup_clock)

    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    if args.trace:
        values = {}
        for group in sorted({m["name"].split(".")[0]
                             for m in bench["per_layer"]
                             if in_cell(m, cell["name"])}):
            reader = load_file_module(os.path.join(HERE, "layers",
                                                   group + ".py"))
            values.update(reader.read(result["run_facts"]))
        wanted = [m["name"] for m in bench["per_layer"]
                  if in_cell(m, cell["name"])]
    else:
        values = result["end_to_end"]
        wanted = [m["name"] for m in bench["end_to_end"]
                  if in_cell(m, cell["name"])]
    prefix = "rehearsal." if args.rehearse else ""
    metrics = {prefix + name: {"value": values[name], "unit": units[name]}
               for name in wanted if name in values}
    for name, m in metrics.items():
        if name.endswith(("_share", "_util")) and not 0.0 <= m["value"] <= 1.0:
            raise SystemExit(f"{name} = {m['value']} is not a fraction")
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": metrics,
            "device": result["device"]}
    if args.trace and result["breakdown"]:
        line["breakdown"] = result["breakdown"]
    say(f"run took {time.perf_counter() - T_START:.2f} s")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
