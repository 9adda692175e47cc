"""Export layer (estorch_tpu/obs/export/): Prometheus exposition +
metrics sidecar, Perfetto trace-event export, the `obs regress` perf
gate, atomic flight-recorder dumps — and THE e2e acceptance demo: a
supervised training run killed mid-flight stays scrapeable from the
sidecar throughout, with counter totals monotone across the restart.
"""

import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from estorch_tpu.obs import FlightRecorder, Heartbeat, read_heartbeat
from estorch_tpu.obs.__main__ import main as obs_main
from estorch_tpu.obs.export.prometheus import (is_gauge, metric_name,
                                               parse_exposition,
                                               render_exposition,
                                               samples_by_name)
from estorch_tpu.obs.export.regress import (compare, compare_files,
                                            load_measurement)
from estorch_tpu.obs.export.regress import selfcheck as regress_selfcheck
from estorch_tpu.obs.export.sidecar import (MetricsSidecar, compose_totals,
                                            publish_counters,
                                            read_published_counters)
from estorch_tpu.obs.export.traceevent import (export_trace, validate_trace,
                                               write_trace)

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


# ---------------------------------------------------------------------
# Prometheus text exposition
# ---------------------------------------------------------------------

class TestPrometheus:
    def test_render_parse_round_trip(self):
        body = render_exposition(
            {"env_steps": 1234, "recompiles": 3, "peak_rss_mb": 512.5},
            {"ts": time.time(), "age_s": 1.0, "pid": 42,
             "phase": "eval", "generation": 7},
        )
        vals = samples_by_name(parse_exposition(body))
        assert vals["estorch_env_steps"] == 1234
        assert vals["estorch_recompiles"] == 3
        assert vals["estorch_peak_rss_mb"] == 512.5
        assert vals["estorch_heartbeat_generation"] == 7
        assert vals["estorch_heartbeat_stale"] == 0
        assert vals["estorch_up"] == 1

    def test_counter_vs_gauge_classification(self):
        assert not is_gauge("env_steps")
        assert not is_gauge("requests_total")
        assert is_gauge("peak_rss_mb")
        assert is_gauge("compile_time_s")
        assert is_gauge("queue_depth")
        assert is_gauge("batch_size_last")
        body = render_exposition({"env_steps": 1, "queue_depth": 2})
        assert "# TYPE estorch_env_steps counter" in body
        assert "# TYPE estorch_queue_depth gauge" in body

    def test_stale_heartbeat_reads_down(self):
        body = render_exposition(
            {}, {"ts": 0.0, "age_s": 9999.0, "pid": 1, "phase": "device",
                 "generation": 3},
            stale_after_s=120.0)
        vals = samples_by_name(parse_exposition(body))
        assert vals["estorch_heartbeat_stale"] == 1
        assert vals["estorch_up"] == 0

    def test_no_heartbeat_up_override(self):
        """The serve server IS the scraped process: up=True without any
        heartbeat file; a run-dir sidecar with no heartbeat reads down."""
        assert samples_by_name(parse_exposition(
            render_exposition({}, None)))["estorch_up"] == 0
        assert samples_by_name(parse_exposition(
            render_exposition({}, None, up=True)))["estorch_up"] == 1

    def test_name_sanitization_and_label_escape(self):
        assert metric_name("serve.requests-total") == \
            "estorch_serve_requests_total"
        body = render_exposition(
            {}, {"ts": 0.0, "age_s": 0.0, "pid": 9,
                 "phase": 'ev"al\nx\\y', "generation": 0})
        samples = parse_exposition(body)
        labels = [lab for name, lab, _ in samples
                  if name == "estorch_heartbeat_info"][0]
        assert labels["pid"] == "9"

    def test_non_numeric_registry_values_skipped(self):
        body = render_exposition({"env_steps": 5, "note": "hello",
                                  "flag": True})
        vals = samples_by_name(parse_exposition(body))
        assert vals["estorch_env_steps"] == 5
        assert "estorch_note" not in vals
        assert "estorch_flag" not in vals

    def test_extra_gauge_shadows_registry_entry(self):
        """The serve server's live queue-depth read and the batcher's
        registry gauge share a name — the point-in-time extra must
        SHADOW the registry entry, not duplicate its TYPE (a duplicate
        is exactly what the validating parser rejects)."""
        body = render_exposition({"queue_depth": 7, "env_steps": 1},
                                 extra_gauges={"queue_depth": 3})
        vals = samples_by_name(parse_exposition(body))  # parses: no dup
        assert vals["estorch_queue_depth"] == 3  # the fresher read wins
        assert vals["estorch_env_steps"] == 1

    def test_parser_rejects_garbage(self):
        with pytest.raises(ValueError):
            parse_exposition("this is not an exposition line\n")
        with pytest.raises(ValueError):
            parse_exposition("estorch_x notanumber\n")
        with pytest.raises(ValueError):
            parse_exposition("# TYPE estorch_x counter\n"
                             "# TYPE estorch_x gauge\n")
        # garbage INSIDE a label block must not be blessed just because
        # one well-formed pair is also present — a real scraper rejects
        # the whole scrape
        with pytest.raises(ValueError):
            parse_exposition('estorch_x{phase="eval" JUNK==,} 1\n')


# ---------------------------------------------------------------------
# sidecar: publish/compose + live loopback scrape
# ---------------------------------------------------------------------

class TestSidecarComposition:
    def test_publish_read_round_trip(self, tmp_path):
        d = str(tmp_path)
        publish_counters(d, {"env_steps": 100, "note": "skip-me"},
                         through_ts=123.0, extra={"restart_count": 2})
        back = read_published_counters(d)
        assert back["counters"] == {"env_steps": 100}
        assert back["through_ts"] == 123.0
        assert back["restart_count"] == 2
        assert not os.path.exists(os.path.join(d, "counters.json.tmp"))

    def test_corrupt_or_missing_published_is_none(self, tmp_path):
        assert read_published_counters(str(tmp_path)) is None
        (tmp_path / "counters.json").write_text("{half")
        assert read_published_counters(str(tmp_path)) is None
        (tmp_path / "counters.json").write_text(
            json.dumps({"schema": 999, "counters": {}}))
        assert read_published_counters(str(tmp_path)) is None

    def test_compose_skips_already_folded_beat(self):
        """The cross-restart double-count guard: a dead child's final
        beat (ts == through_ts) is already inside the published totals —
        only a NEWER beat (the next child) adds on top."""
        published = {"through_ts": 100.0, "counters": {"env_steps": 50}}
        dead = {"ts": 100.0, "counters": {"env_steps": 50}}
        live = {"ts": 101.0, "counters": {"env_steps": 7}}
        assert compose_totals(published, dead) == {"env_steps": 50}
        assert compose_totals(published, live) == {"env_steps": 57}
        assert compose_totals(None, live) == {"env_steps": 7}
        assert compose_totals(published, None) == {"env_steps": 50}

    def test_loopback_scrape_and_health(self, tmp_path):
        d = str(tmp_path)
        Heartbeat(os.path.join(d, "heartbeat.json")).beat(
            "eval", 3, {"env_steps": 11})
        publish_counters(d, {"env_steps": 31}, through_ts=1.0,
                         extra={"restart_count": 1})
        sc = MetricsSidecar(d, port=0)
        sc.start_background()
        try:
            with urllib.request.urlopen(
                    f"http://{sc.host}:{sc.port}/metrics", timeout=10) as r:
                assert r.status == 200
                assert "text/plain" in r.headers["Content-Type"]
                vals = samples_by_name(
                    parse_exposition(r.read().decode()))
            assert vals["estorch_env_steps"] == 42  # 31 published + 11 live
            assert vals["estorch_supervisor_restarts"] == 1
            assert vals["estorch_up"] == 1
            assert "estorch_run_completed" not in vals  # still running
            with urllib.request.urlopen(
                    f"http://{sc.host}:{sc.port}/healthz", timeout=10) as r:
                h = json.load(r)
            assert h["ok"] and h["generation"] == 3
        finally:
            sc.close()

    def test_completed_verdict_distinguishes_done_from_dead(self,
                                                            tmp_path):
        """After a run ends its heartbeat goes stale and estorch_up
        drops either way — the published completion verdict is what
        tells an alert 'done' from 'dead'."""
        d = str(tmp_path)
        publish_counters(d, {"env_steps": 9}, through_ts=1.0,
                         extra={"restart_count": 0, "completed": True})
        sc = MetricsSidecar(d, port=0)
        vals = samples_by_name(parse_exposition(sc.scrape()))
        sc.close()
        assert vals["estorch_up"] == 0  # no fresh heartbeat
        assert vals["estorch_run_completed"] == 1

    def test_health_503_without_heartbeat(self, tmp_path):
        sc = MetricsSidecar(str(tmp_path), port=0)
        sc.start_background()
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://{sc.host}:{sc.port}/healthz", timeout=10)
            assert ei.value.code == 503
            # /metrics still answers — the sidecar outlives the run
            with urllib.request.urlopen(
                    f"http://{sc.host}:{sc.port}/metrics", timeout=10) as r:
                vals = samples_by_name(parse_exposition(r.read().decode()))
            assert vals["estorch_up"] == 0
        finally:
            sc.close()

    def test_file_run_never_imports_package_or_jax(self, tmp_path):
        """The wedged-host contract: the sidecar must serve a scrape when
        run AS A FILE, without the estorch_tpu package init (and hence
        without jax) ever loading — same discipline as bench.py."""
        Heartbeat(str(tmp_path / "heartbeat.json")).beat("eval", 1, {})
        src = os.path.join(REPO, "estorch_tpu", "obs", "export",
                           "sidecar.py")
        probe = (
            "import json, sys, threading, urllib.request\n"
            "import importlib.util\n"
            f"spec = importlib.util.spec_from_file_location('sc', {src!r})\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(m)\n"
            "assert 'jax' not in sys.modules, 'sidecar imported jax'\n"
            "assert 'estorch_tpu' not in sys.modules, 'package init ran'\n"
            f"sc = m.MetricsSidecar({str(tmp_path)!r}, port=0)\n"
            "sc.start_background()\n"
            "url = f'http://{sc.host}:{sc.port}/metrics'\n"
            "body = urllib.request.urlopen(url, timeout=10).read().decode()\n"
            "assert 'estorch_up 1' in body, body\n"
            "sc.close()\n"
        )
        r = subprocess.run([sys.executable, "-c", probe],
                           capture_output=True, text=True, timeout=120)
        assert r.returncode == 0, r.stderr


# ---------------------------------------------------------------------
# flight recorder: atomic dump (satellite)
# ---------------------------------------------------------------------

class TestAtomicDump:
    def test_dump_appends_and_leaves_no_tmp(self, tmp_path):
        path = str(tmp_path / "ring.jsonl")
        r = FlightRecorder(capacity=4)
        r.add("event", "first")
        r.dump_jsonl(path)
        r2 = FlightRecorder(capacity=4)
        r2.add("event", "second")
        r2.dump_jsonl(path)
        names = [json.loads(ln)["name"] for ln in open(path)]
        assert names == ["first", "second"]
        assert not os.path.exists(path + ".tmp")

    def test_dump_drops_truncated_tail(self, tmp_path):
        """A pre-existing truncated file (crash during a non-atomic-era
        dump, or a torn copy) loses only the partial line: keeping it
        would either glue the new first event onto it or park malformed
        JSON mid-file, where tolerant readers rightly raise."""
        from estorch_tpu.obs.summarize import load_records_tolerant

        path = str(tmp_path / "ring.jsonl")
        with open(path, "w") as f:
            f.write('{"kind": "event", "name": "old"}\n{"kind": "ev')
        r = FlightRecorder(capacity=4)
        r.add("event", "new")
        r.dump_jsonl(path)
        rows = [json.loads(ln) for ln in open(path)]  # every line parses
        assert [row["name"] for row in rows] == ["old", "new"]
        records, dropped = load_records_tolerant(path)
        assert dropped == 0 and len(records) == 2


# ---------------------------------------------------------------------
# trace-event export
# ---------------------------------------------------------------------

def _run_records(gens, rate=1000.0, phases=None):
    recs = []
    for g in gens:
        rec = {"generation": g, "wall_time_s": 1.0, "env_steps": 1000,
               "env_steps_per_sec": rate, "reward_mean": 0.0,
               "reward_max": 0.0, "best_reward": 0.0, "n_failed": 0}
        if phases is not None:
            rec["phases"] = dict(phases)
        recs.append(rec)
    return recs


class TestTraceEvent:
    def test_single_run_lanes_and_nesting(self):
        recs = _run_records(range(3), phases={
            "eval": 0.6, "eval/sample": 0.2, "update": 0.3})
        trace = export_trace(recs)
        assert validate_trace(trace) == []
        evs = trace["traceEvents"]
        gens = [e for e in evs if e.get("cat") == "generation"]
        assert [e["name"] for e in gens] == ["gen 0", "gen 1", "gen 2"]
        # generations laid end to end on the synthesized clock
        assert [e["ts"] for e in gens] == [0.0, 1e6, 2e6]
        child = [e for e in evs if e["name"] == "eval/sample"][0]
        parent = [e for e in evs if e["name"] == "eval"][0]
        assert parent["ts"] <= child["ts"]
        assert child["dur"] <= parent["dur"]
        assert trace["otherData"]["segments"] == 1
        assert trace["otherData"]["restart_markers"] == 0

    def test_restart_becomes_segment_and_marker(self):
        """A supervised run whose child died at gen 5 and resumed from
        the gen-3 checkpoint replays gens 4..: the exporter must split
        lanes at the replay boundary and mark the restart with the
        manifest's provenance."""
        recs = _run_records(range(5)) + _run_records(range(4, 8))
        manifest = {"pid": 111, "resilience": {"restarts": [
            {"reason": "child died with exit code -9",
             "heartbeat": {"pid": 222, "generation": 4}},
        ]}}
        trace = export_trace(recs, manifest=manifest)
        assert validate_trace(trace) == []
        markers = [e for e in trace["traceEvents"]
                   if e["name"] == "supervisor restart"]
        assert len(markers) == 1
        assert "exit code -9" in markers[0]["args"]["reason"]
        assert trace["otherData"]["segments"] == 2
        # the dead child's lane is keyed by its heartbeat pid
        names = {e["args"]["name"] for e in trace["traceEvents"]
                 if e.get("ph") == "M" and e["name"] == "process_name"}
        assert any("pid 222" in n for n in names)

    def test_flight_recorder_events_get_wall_clock_lane(self):
        recs = _run_records(range(2))
        events = [{"ts": 1000.0, "kind": "event", "name": "compile"},
                  {"ts": 1001.5, "kind": "note", "name": "init"}]
        hb = {"ts": 1002.0, "pid": 1, "phase": "eval", "generation": 1}
        trace = export_trace(recs, events=events, heartbeat=hb)
        assert validate_trace(trace) == []
        wall = [e for e in trace["traceEvents"] if e.get("pid") == 0
                and e.get("ph") == "i"]
        assert [e["ts"] for e in wall] == [0.0, 1.5e6, 2e6]  # rebased
        assert wall[-1]["name"] == "last heartbeat"

    def test_heartbeat_without_numeric_ts_does_not_crash(self):
        """A hand-edited or foreign heartbeat (ts missing or a string)
        cannot be placed on the wall-clock lane — the export must skip
        it, not die on min() of an empty sequence."""
        recs = _run_records(range(2))
        for hb in ({"phase": "eval", "pid": 1},
                   {"ts": "not-a-number", "phase": "eval", "pid": 1}):
            trace = export_trace(recs, heartbeat=hb)
            assert validate_trace(trace) == []
            assert not [e for e in trace["traceEvents"]
                        if e.get("pid") == 0]  # no wall-clock lane

    def test_records_without_phases_still_render(self):
        trace = export_trace(_run_records(range(3)))
        assert validate_trace(trace) == []
        assert len([e for e in trace["traceEvents"]
                    if e.get("cat") == "generation"]) == 3
        assert not [e for e in trace["traceEvents"]
                    if e.get("cat") == "phase"]

    def test_validator_catches_malformed_events(self):
        assert validate_trace([]) != []
        assert validate_trace({"traceEvents": None}) != []
        bad = {"traceEvents": [
            {"ph": "Z", "name": "x", "pid": 1, "tid": 1, "ts": 0},
            {"ph": "X", "name": "", "pid": 1, "tid": 1, "ts": 0, "dur": 1},
            {"ph": "X", "name": "ok", "pid": 1, "tid": 1, "ts": -5,
             "dur": 1},
            {"ph": "X", "name": "ok", "pid": 1, "tid": 1, "ts": 0},
        ]}
        problems = validate_trace(bad)
        assert len(problems) == 4

    def test_write_trace_is_atomic(self, tmp_path):
        path = str(tmp_path / "trace.json")
        write_trace(export_trace(_run_records(range(2))), path)
        assert validate_trace(json.load(open(path))) == []
        assert not os.path.exists(path + ".tmp")


# ---------------------------------------------------------------------
# degenerate inputs: summarize + trace CLIs (satellite)
# ---------------------------------------------------------------------

class TestDegenerateInputs:
    def test_empty_jsonl(self, tmp_path, capsys):
        path = tmp_path / "run.jsonl"
        path.write_text("")
        assert obs_main(["summarize", str(path)]) == 0
        assert obs_main(["trace", str(path),
                         "-o", str(tmp_path / "t.json")]) == 0
        capsys.readouterr()
        trace = json.load(open(tmp_path / "t.json"))
        assert validate_trace(trace) == []
        assert trace["otherData"]["generations"] == 0

    def test_truncated_final_line_dropped_with_note(self, tmp_path,
                                                    capsys):
        """A SIGKILLed writer legitimately leaves a partial last line —
        the post-mortem tools exist for exactly those runs."""
        path = tmp_path / "run.jsonl"
        with open(path, "w") as f:
            for rec in _run_records(range(3)):
                f.write(json.dumps(rec) + "\n")
            f.write('{"generation": 3, "env_ste')
        assert obs_main(["summarize", str(path), "--json"]) == 0
        out = capsys.readouterr()
        assert json.loads(out.out)["generations"] == 3
        assert "truncated final line" in out.err
        assert obs_main(["trace", str(path),
                         "-o", str(tmp_path / "t.json")]) == 0
        capsys.readouterr()
        assert json.load(open(
            tmp_path / "t.json"))["otherData"]["generations"] == 3

    def test_garbage_mid_file_still_raises(self, tmp_path, capsys):
        path = tmp_path / "run.jsonl"
        with open(path, "w") as f:
            f.write('{"generation": 0}\nGARBAGE\n{"generation": 1}\n')
        assert obs_main(["summarize", str(path)]) == 1
        assert obs_main(["trace", str(path)]) == 1
        assert "cannot read" in capsys.readouterr().err

    def test_wrong_file_with_one_malformed_line_is_error(self, tmp_path,
                                                         capsys):
        """A torn tail is tolerated only BEHIND valid records: pointing
        the tools at the wrong file (one malformed line, zero records)
        must error, not exit 0 with an empty result."""
        path = tmp_path / "notes.txt"
        path.write_text("this is not a run JSONL\n")
        assert obs_main(["summarize", str(path)]) == 1
        assert obs_main(["trace", str(path)]) == 1
        assert "cannot read" in capsys.readouterr().err

    def test_records_missing_phases(self, tmp_path, capsys):
        path = tmp_path / "run.jsonl"
        with open(path, "w") as f:
            for rec in _run_records(range(4)):
                f.write(json.dumps(rec) + "\n")
        assert obs_main(["summarize", str(path)]) == 0
        assert obs_main(["trace", str(path),
                         "-o", str(tmp_path / "t.json")]) == 0
        capsys.readouterr()

    def test_heartbeat_only_run_dir(self, tmp_path, capsys):
        """A run that wedged before logging a single generation still has
        a story: its heartbeat."""
        hb = tmp_path / "heartbeat.json"
        Heartbeat(str(hb)).beat("device", 2, {"env_steps": 5})
        assert obs_main(["summarize", "--heartbeat", str(hb)]) == 0
        assert "device" in capsys.readouterr().out


# ---------------------------------------------------------------------
# obs regress
# ---------------------------------------------------------------------

class TestRegress:
    def test_selfcheck_clean(self):
        assert regress_selfcheck() == []

    def test_verdict_math(self):
        base = [100.0] * 12
        assert compare([100.0] * 12, base)["verdict"] == "pass"
        slow = compare([60.0] * 12, base)
        assert slow["verdict"] == "regress" and slow["drop_pct"] == 40.0
        fast = compare([140.0] * 12, base)
        assert fast["verdict"] == "pass" and fast["improved"]

    def test_noisy_sample_widens_band(self):
        """A sample whose own scatter exceeds the floor must not flag a
        same-distribution rerun: the band is learned, not assumed."""
        base = [100.0, 80.0, 120.0, 95.0, 105.0, 70.0, 130.0, 100.0]
        shifted = [x * 0.85 for x in base]  # well inside the ~22% MAD band
        v = compare(shifted, base)
        assert v["band_pct"] > 15.0
        assert v["verdict"] == "pass"

    def test_load_measurement_shapes(self, tmp_path):
        bench_path = tmp_path / "BENCH_x.json"
        bench_path.write_text(json.dumps(
            {"parsed": {"metric": "env_steps_per_sec_per_chip",
                        "value": 123.0}}))
        samples, metric = load_measurement(str(bench_path))
        assert samples == [123.0]
        assert metric == "env_steps_per_sec_per_chip"
        ab_path = tmp_path / "ab.jsonl"
        with open(ab_path, "w") as f:
            for lab, rate in (("on", 10.0), ("off", 20.0), ("on", 12.0)):
                f.write(json.dumps({"label": lab, "rate": rate}) + "\n")
        samples, _ = load_measurement(str(ab_path), label="on")
        assert samples == [10.0, 12.0]

    def test_cli_exit_codes_and_verdict_json(self, tmp_path, capsys):
        base = tmp_path / "BENCH_base.json"
        base.write_text(json.dumps({"parsed": {
            "metric": "env_steps_per_sec", "value": 1000.0}}))
        run = tmp_path / "run.jsonl"
        with open(run, "w") as f:
            for rec in _run_records(range(8), rate=990.0):
                f.write(json.dumps(rec) + "\n")
        assert obs_main(["regress", str(run), "--baseline", str(base),
                         "--json"]) == 0
        assert json.loads(capsys.readouterr().out)["verdict"] == "pass"
        slow = tmp_path / "slow.jsonl"
        with open(slow, "w") as f:
            for rec in _run_records(range(8), rate=600.0):
                f.write(json.dumps(rec) + "\n")
        assert obs_main(["regress", str(slow), "--baseline", str(base),
                         "--json"]) == 1
        v = json.loads(capsys.readouterr().out)
        assert v["verdict"] == "regress" and v["drop_pct"] == 40.0

    def test_cli_unusable_input_is_error(self, tmp_path, capsys):
        empty = tmp_path / "empty.jsonl"
        empty.write_text("")
        base = tmp_path / "b.json"
        base.write_text(json.dumps({"parsed": {"metric": "m",
                                               "value": 1.0}}))
        assert obs_main(["regress", str(empty), "--baseline",
                         str(base)]) == 1
        assert "regress:" in capsys.readouterr().err


class TestMixedSchemaBaselines:
    """BENCH_r06+ artifacts embed phase_rows/tail_rows; older baselines
    predate them.  The gates must consume the new schema and DEGRADE
    with a one-line diagnosis — never a traceback or a bogus verdict —
    on the old one."""

    def _run_jsonl(self, path, n=8):
        with open(path, "w") as f:
            for g in range(n):
                f.write(json.dumps({
                    "generation": g, "env_steps_per_sec": 1000.0,
                    "wall_time_s": 0.10,
                    "phases": {"eval": 0.08, "update": 0.02}}) + "\n")

    def _r06(self, path, eval_s=0.08):
        with open(path, "w") as f:
            json.dump({
                "n": 3, "platform": "cpu",
                "parsed": {"metric": "env_steps_per_sec_per_chip",
                           "value": 1000.0, "unit": "x (cpu)"},
                "phase_rows": [
                    {"generation": g, "env_steps_per_sec": 1000.0,
                     "wall_time_s": eval_s + 0.02,
                     "phases": {"eval": eval_s, "update": 0.02}}
                    for g in range(8)],
            }, f)

    def test_r06_schema_feeds_phase_and_tail_gates(self, tmp_path):
        from estorch_tpu.obs.export.regress import (compare_phase_files,
                                                    compare_tail_files)

        cur = str(tmp_path / "cur.jsonl")
        self._run_jsonl(cur)
        base = str(tmp_path / "BENCH_r06.json")
        self._r06(base)
        v = compare_phase_files(cur, base)
        assert v["verdict"] == "pass"
        assert set(v["phases"]) == {"eval", "update"}
        t = compare_tail_files(cur, base)
        assert t["verdict"] == "pass"
        assert "eval" in t["groups"] and "wall_time_s" in t["groups"]

    def test_r06_baseline_catches_phase_slowdown(self, tmp_path):
        from estorch_tpu.obs.export.regress import compare_phase_files

        cur = str(tmp_path / "cur.jsonl")
        self._run_jsonl(cur)
        base = str(tmp_path / "BENCH_r06.json")
        self._r06(base, eval_s=0.05)  # baseline 37% faster at eval
        v = compare_phase_files(cur, base)
        print("REGRESS_VERDICT", v)
        assert v["verdict"] == "regress"
        assert v["regressed_phases"] == ["eval"]

    def test_pre_r06_baseline_degrades_one_line(self, tmp_path, capsys):
        from estorch_tpu.obs.export.regress import (compare_phase_files,
                                                    compare_tail_files)

        cur = str(tmp_path / "cur.jsonl")
        self._run_jsonl(cur)
        old = str(tmp_path / "BENCH_r05.json")
        with open(old, "w") as f:
            json.dump({"n": 5, "parsed": {
                "metric": "env_steps_per_sec_per_chip",
                "value": 62791.4, "unit": "env-steps/s/chip (cpu)"}}, f)
        for fn, what in ((compare_phase_files, "per-phase"),
                         (compare_tail_files, "tail")):
            with pytest.raises(ValueError) as ei:
                fn(cur, old)
            msg = str(ei.value)
            assert "\n" not in msg, msg  # ONE line
            assert "baseline" in msg and f"no {what} rows" in msg
            assert "capture-baseline" in msg  # says how to fix it
        # the CLI prints it as a one-line error, exit 1, no traceback
        rc = obs_main(["regress", cur, "--baseline", old, "--phases"])
        assert rc == 1
        err = capsys.readouterr().err
        assert err.startswith("regress:") and err.count("\n") == 1

    def test_empty_current_names_the_current_side(self, tmp_path):
        from estorch_tpu.obs.export.regress import compare_phase_files

        base = str(tmp_path / "BENCH_r06.json")
        self._r06(base)
        bare = str(tmp_path / "bare.jsonl")
        with open(bare, "w") as f:
            f.write(json.dumps({"generation": 0,
                                "env_steps_per_sec": 5.0}) + "\n")
        with pytest.raises(ValueError) as ei:
            compare_phase_files(bare, base)
        assert "current measurement carries no per-phase rows" \
            in str(ei.value)

    def test_embedded_repeats_are_distinct_samples_not_replays(self):
        """Baseline phase_rows carry a 'repeat' stamp: generation g of
        repeat 0 and of repeat 1 are different measurements and must
        BOTH survive; a replayed generation within one repeat (same
        (repeat, generation)) still dedupes keeping the last."""
        from estorch_tpu.obs.export.regress import (extract_phase_samples,
                                                    extract_tail_groups)

        rows = [{"phase_rows": [
            {"repeat": r, "generation": g, "wall_time_s": 1.0 + r,
             "phases": {"eval": 0.5 + r}}
            for r in range(3) for g in range(4)]}]
        phases = extract_phase_samples(rows)
        assert len(phases["eval"]) == 12
        assert sorted(set(phases["eval"])) == [0.5, 1.5, 2.5]
        groups = extract_tail_groups(rows)
        assert len(groups["wall_time_s"]) == 12
        # replay within one repeat: last occurrence wins, no double count
        rows[0]["phase_rows"].append(
            {"repeat": 0, "generation": 0, "wall_time_s": 9.0,
             "phases": {"eval": 9.0}})
        phases = extract_phase_samples(rows)
        assert len(phases["eval"]) == 12 and 9.0 in phases["eval"] \
            and phases["eval"].count(0.5) == 3

    def test_committed_r06_artifact_carries_what_the_gates_need(self):
        """The REAL committed baseline (satellite: the trajectory no
        longer ends at r05): embedded phase rows, a tail headline, and
        the typed device probe."""
        path = os.path.join(REPO, "BENCH_r06.json")
        with open(path) as f:
            art = json.load(f)
        assert art["phase_rows"] and all(
            isinstance(r.get("phases"), dict) for r in art["phase_rows"])
        assert art["extras"]["phases_headline"]
        assert art["extras"]["tail_headline"]["wall_time_s"]["p99_s"] > 0
        # the tail baseline must be STEADY STATE: a warm-up/compile
        # generation left in phase_rows becomes the p99 (nearest-rank
        # over ~35 samples is the max) and would wave a real 100x
        # dispatch-tail regression through
        walls = [r["wall_time_s"] for r in art["phase_rows"]]
        assert max(walls) < 3 * sorted(walls)[len(walls) // 2], (
            "compile-spike rows leaked into the committed tail baseline")
        assert art["extras"]["device_probe"]["status"] in (
            "ok", "failed")
        from estorch_tpu.obs.export.regress import (
            extract_phase_samples, extract_tail_groups, load_rows,
            measurement_platform)

        rows = load_rows(path)
        assert measurement_platform(rows) in ("cpu", "tpu")
        phases = extract_phase_samples(rows)
        # every repeat's every generation is a sample (n repeats ×
        # gens-per-repeat == the embedded row count — nothing collapsed)
        assert phases and all(len(v) == len(art["phase_rows"])
                              for v in phases.values())
        assert "wall_time_s" in extract_tail_groups(rows)

    def test_committed_r07_artifact_carries_the_elastic_row(self):
        """The round-19 committed baseline (ISSUE 15 satellite: fresh
        committed history for this round's gates): same steady-state
        phase-row contract as r06 PLUS the elastic multi-host row —
        sync-SPMD vs elastic-fold gps under the shared straggle_host
        plan, with the fold actually exercised and the accounting
        invariant intact at capture time."""
        path = os.path.join(REPO, "BENCH_r07.json")
        with open(path) as f:
            art = json.load(f)
        assert art["phase_rows"] and all(
            isinstance(r.get("phases"), dict) for r in art["phase_rows"])
        walls = [r["wall_time_s"] for r in art["phase_rows"]]
        assert max(walls) < 3 * sorted(walls)[len(walls) // 2], (
            "compile-spike rows leaked into the committed tail baseline")
        el = art["extras"]["elastic"]
        assert el["ratio"] >= 1.25
        assert el["elastic_gps"] > el["sync_gps"]
        assert el["results_folded"] > 0
        assert el["accounting_ok"] is True


# ---------------------------------------------------------------------
# THE e2e acceptance demo
# ---------------------------------------------------------------------

def _demo_factory():
    """Supervisor child factory (spawned: fresh interpreter — pin the
    backend to CPU before anything touches this image's default)."""
    import torch

    from estorch_tpu import ES
    from estorch_tpu.utils import force_cpu_backend

    force_cpu_backend(1)

    class TinyMLP(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.net = torch.nn.Linear(4, 2)

        def forward(self, x):
            return self.net(x)

    class QuadAgent:
        def rollout(self, policy):
            with torch.no_grad():
                vec = torch.nn.utils.parameters_to_vector(
                    policy.parameters())
                reward = -float((vec ** 2).sum())
            self.last_episode_steps = 1
            return reward

    return ES(TinyMLP, QuadAgent, torch.optim.Adam, population_size=8,
              sigma=0.05, seed=11, table_size=1 << 12)


class TestExportE2E:
    def test_supervised_run_scrapeable_throughout(self, tmp_path,
                                                  monkeypatch, capsys):
        """ISSUE 5 acceptance: SIGKILL a supervised training run
        mid-flight; the metrics sidecar keeps answering /metrics scrapes
        throughout with counter totals MONOTONE across the restart; the
        finished run's `obs trace` validates with a restart-boundary
        marker; `obs regress` passes the clean baseline and flags the
        injected-slowdown one."""
        from estorch_tpu.resilience import CHAOS_ENV, Supervisor
        from estorch_tpu.resilience import chaos as chaos_mod

        root = tmp_path / "run"
        plan = {"events": [{"kind": "die", "gen": 5}],
                "ledger": str(tmp_path / "chaos_ledger")}
        monkeypatch.setenv(CHAOS_ENV, json.dumps(plan))
        chaos_mod.reset_cache()

        sc = MetricsSidecar(str(root.absolute()), port=0)
        os.makedirs(root, exist_ok=True)
        sc.start_background()
        url = f"http://{sc.host}:{sc.port}/metrics"
        series: list[dict] = []
        scrape_errors: list[str] = []
        stop = threading.Event()

        # the sidecar listens from its constructor on (bind + listen), so
        # the first scrape needs no wait; a scrape may be slow on a host
        # that six test workers and their children share, never unanswered
        def scraper():
            while not stop.is_set():
                try:
                    with urllib.request.urlopen(url, timeout=120) as r:
                        body = r.read().decode()
                    series.append(samples_by_name(parse_exposition(body)))
                except Exception as e:  # noqa: BLE001 — collected and
                    scrape_errors.append(repr(e))  # asserted empty below
                stop.wait(0.2)

        t = threading.Thread(target=scraper, daemon=True)
        t.start()
        try:
            sup = Supervisor(_demo_factory, str(root),
                             target_generation=8, every=2,
                             max_restarts=2, backoff_s=0.1, poll_s=0.25,
                             startup_grace_s=300.0)
            res = sup.run()
            # one last scrape AFTER the final publish: the post-run truth
            with urllib.request.urlopen(url, timeout=120) as r:
                series.append(samples_by_name(
                    parse_exposition(r.read().decode())))
        finally:
            stop.set()
            t.join(timeout=130)
            sc.close()
        assert not t.is_alive(), "the scraper never came back"
        assert res["ok"], f"supervisor failed: {res}"
        assert len(res["restarts"]) == 1  # exactly the gen-5 SIGKILL

        # (a) scrapeable throughout: every scrape answered and parsed,
        # spanning both children, and env_steps totals never went
        # backwards — the published+live composition did not double count
        # or lose the dead child's totals
        assert not scrape_errors, scrape_errors
        assert len(series) >= 5
        steps = [s["estorch_env_steps"] for s in series
                 if "estorch_env_steps" in s]
        assert steps, "no scrape ever saw counters"
        assert steps == sorted(steps), f"totals went backwards: {steps}"
        # totals are "through each child's last beat" (a heartbeat cannot
        # see past itself, so each child's final generation lags one
        # beat): > 40 proves child2's live counters rode ON TOP of
        # child1's published totals (child1 alone could reach at most
        # 5 gens x 8 steps), and the final scrape must equal the
        # manifest's cross-restart totals exactly
        assert steps[-1] > 5 * 8
        manifest = json.load(open(root / "manifest.json"))
        assert steps[-1] == manifest["resilience"]["counters"]["env_steps"]
        final = series[-1]
        assert final["estorch_supervisor_restarts"] == 1

        # (b) the finished run's trace validates, with the restart marked
        out_path = str(tmp_path / "trace.json")
        assert obs_main(["trace", str(root / "run.jsonl"),
                         "-o", out_path]) == 0
        capsys.readouterr()
        trace = json.load(open(out_path))
        assert validate_trace(trace) == []
        markers = [e for e in trace["traceEvents"]
                   if e["name"] == "supervisor restart"]
        assert len(markers) == 1
        assert trace["otherData"]["segments"] == 2

        # (c) regress: clean baseline passes, injected slowdown flagged
        rates, _ = load_measurement(str(root / "run.jsonl"))
        med = sorted(rates)[len(rates) // 2]
        clean = tmp_path / "BENCH_clean.json"
        clean.write_text(json.dumps({"parsed": {
            "metric": "env_steps_per_sec", "value": med}}))

        def verdict(baseline):
            code = obs_main(["regress", str(root / "run.jsonl"),
                             "--baseline", str(baseline), "--json"])
            out = capsys.readouterr().out.strip().splitlines()[-1]
            return code, json.loads(out)

        code, v = verdict(clean)
        assert code == 0 and v["verdict"] == "pass", v
        # the slowdown is injected RELATIVE to the band the gate learned
        # from this run's eight millisecond generations (1.48 MAD /
        # median).  On a host shared with five other test workers that
        # band has read 24% in one run and 98% in the next (rates of one
        # run spread over a decade), so a fixed "2.5x the measured rate =
        # a 60% drop" was flagged in one and swallowed in the other: the
        # flake of ROADMAP D10.  A baseline whose drop lies halfway
        # between the band and 100% is beyond ANY band under 100%
        band = v["band_pct"]
        slow = tmp_path / "BENCH_slow.json"
        if band < 99.0:
            drop = (band + 100.0) / 2.0
            slow.write_text(json.dumps({"parsed": {
                "metric": "env_steps_per_sec",
                "value": med / (1.0 - drop / 100.0)}}))
            code, v = verdict(slow)
            assert code == 1 and v["verdict"] == "regress", v
            assert v["drop_pct"] > v["band_pct"] == band
        else:
            # a run too noisy to learn anything from: the gate must not
            # manufacture an alarm out of it either
            slow.write_text(json.dumps({"parsed": {
                "metric": "env_steps_per_sec", "value": med * 2.0}}))
            code, v = verdict(slow)
            assert code == 0 and v["verdict"] == "pass", v
