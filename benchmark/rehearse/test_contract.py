"""``BENCHMARK.json`` against the limits of the benchmark's contract that
can be checked without a chip, and against the files it names."""

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH_KEY = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|"
                       r"head)")


def load():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_names_and_units():
    b = load()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    ends = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in ends
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in ends
        if m["name"].endswith(("_share", "_util")):
            assert m["unit"] == "share"
    names = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(names) == len(set(names))


def test_cells_configs_and_files():
    b = load()
    configs = {c["name"]: c for c in b["configs"]}
    cells = b["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({c["name"] for c in cells}) == len(cells)
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    assert {c["config"] for c in cells} == set(configs)
    four = [c for c in cells if c["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    for c in cells:
        assert NAME.match(c["name"]) and c["chips"] in (1, 4)
        assert 1 <= len(c["why"]) <= 200
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "traffic", c["traffic"] + ".json"))
    for c in configs.values():
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        with open(os.path.join(ROOT, c["file"])) as f:
            body = json.load(f)
        assert body["reduced"] == c["reduced"]
        assert not any(WIDTH_KEY.search(k) for k in c["reduced"])
        assert 1 <= len(c["source"]) <= 200
    for m in b["per_layer"]:
        group = m["name"].split(".")[0]
        assert os.path.exists(os.path.join(ROOT, "benchmark", "layers",
                                           group + ".py")), m["name"]
        for w in m.get("workloads", []):
            assert w in {c["name"] for c in cells}


def test_run_seconds_fits_a_full_check_of_24_cells():
    rs = load()["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
