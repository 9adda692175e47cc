"""Observability subsystem (estorch_tpu/obs/): spans, counters, flight
recorder + heartbeat, manifest round-trip, summarize CLI, and the
record-schema contract against REAL training records.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from estorch_tpu.obs import (Counters, FlightRecorder, Heartbeat,
                             JsonlSink, Telemetry, collect_manifest,
                             load_manifest, read_heartbeat,
                             resolve_telemetry, summarize, validate_record,
                             write_manifest)
from estorch_tpu.obs.recorder import STALE_AFTER_S
from estorch_tpu.obs.summarize import GOLDEN_RECORD, selfcheck


# ---------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------

class TestSpans:
    def test_basic_phase_accumulation(self):
        t = Telemetry()
        with t.phase("eval"):
            time.sleep(0.005)
        with t.phase("eval"):
            time.sleep(0.005)
        with t.phase("update"):
            pass
        ph = t.take_phases()
        assert set(ph) == {"eval", "update"}
        assert ph["eval"] >= 0.01
        # take_phases flushes: the next generation starts clean
        assert t.take_phases() == {}

    def test_nesting_records_parent_and_child(self):
        t = Telemetry()
        with t.phase("update"):
            with t.phase("obsnorm_merge"):
                time.sleep(0.005)
        ph = t.take_phases()
        assert set(ph) == {"update", "update/obsnorm_merge"}
        # the parent's time includes the child's
        assert ph["update"] >= ph["update/obsnorm_merge"]

    def test_fence_runs_inside_the_clock(self):
        t = Telemetry()
        fenced = []

        def fence():
            fenced.append(time.perf_counter())
            time.sleep(0.01)

        with t.phase("device", fence=fence):
            pass
        ph = t.take_phases()
        assert fenced, "fence must be invoked"
        assert ph["device"] >= 0.01, "fence time must land in the span"

    def test_generation_advances_and_counters_ride(self):
        t = Telemetry()
        with t.phase("eval"):
            pass
        t.take_phases()
        with t.phase("eval"):
            pass
        t.take_phases()
        assert t.generation == 2
        snap = t.counters.snapshot()
        assert snap["generations"] == 2
        assert snap["peak_rss_mb"] > 0

    def test_disabled_is_inert(self):
        t = Telemetry(enabled=False)
        with t.phase("eval"):
            pass
        assert t.take_phases() == {}
        assert len(t.recorder) == 0

    def test_overhead_is_small(self):
        """10k enabled spans in well under a second — the 'low-overhead'
        claim, with enormous CI headroom (the real budget is <2% of a
        bench generation; see bench.py --obs-ab)."""
        t = Telemetry()
        t0 = time.perf_counter()
        for _ in range(10_000):
            with t.phase("eval"):
                pass
        enabled = time.perf_counter() - t0
        assert enabled < 1.0, f"10k spans took {enabled:.3f}s"

    def test_resolve_telemetry_contract(self):
        assert resolve_telemetry(False).enabled is False
        assert resolve_telemetry(True).enabled is True
        t = Telemetry()
        assert resolve_telemetry(t) is t
        assert resolve_telemetry(None).enabled is True  # default-on
        with pytest.raises(TypeError):
            resolve_telemetry("yes")

    def test_env_disable(self, monkeypatch):
        monkeypatch.setenv("ESTORCH_OBS", "0")
        assert resolve_telemetry(None).enabled is False

    def test_aborted_generation_spans_are_discardable(self):
        """A generation that raises mid-phase leaves partial spans; train
        loops discard them on (re-)entry so they never pollute the next
        successful record — but the flight recorder keeps them."""
        t = Telemetry()
        with pytest.raises(RuntimeError):
            with t.phase("eval"):
                raise RuntimeError("dead env")
        assert "eval" in t._acc  # partial span recorded
        t.discard_phases()
        assert t.take_phases() == {}
        assert any(e["name"] == "eval" for e in t.recorder.events())


# ---------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------

class TestCounters:
    def test_inc_gauge_snapshot(self):
        c = Counters()
        c.inc("env_steps", 100)
        c.inc("env_steps", 50)
        c.gauge("compile_time_s", 3.5)
        c.gauge("compile_time_s", 4.5)  # gauges overwrite
        snap = c.snapshot()
        assert snap == {"env_steps": 150, "compile_time_s": 4.5}
        snap["env_steps"] = 0  # snapshot is a copy
        assert c.get("env_steps") == 150

    def test_disabled_telemetry_counters_are_inert(self):
        """Engines inc counters unconditionally, so a disabled hub — in
        particular the process-wide NULL_TELEMETRY every engine defaults
        to — must swallow writes instead of aggregating cross-run state."""
        from estorch_tpu.obs import NULL_TELEMETRY

        t = Telemetry(enabled=False)
        t.counters.inc("recompiles")
        t.counters.gauge("compile_time_s", 9.9)
        assert t.counters.snapshot() == {}
        NULL_TELEMETRY.counters.inc("recompiles")
        assert NULL_TELEMETRY.counters.snapshot() == {}

    def test_thread_safety(self):
        import threading

        c = Counters()

        def worker():
            for _ in range(1000):
                c.inc("n")

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        assert c.get("n") == 8000


# ---------------------------------------------------------------------
# flight recorder + heartbeat
# ---------------------------------------------------------------------

class TestFlightRecorder:
    def test_ring_eviction_keeps_newest(self):
        r = FlightRecorder(capacity=4)
        for i in range(10):
            r.add("span", f"phase{i}", generation=i)
        assert len(r) == 4
        names = [e["name"] for e in r.events()]
        assert names == ["phase6", "phase7", "phase8", "phase9"]
        assert r.last()["name"] == "phase9"

    def test_capacity_validated(self):
        with pytest.raises(ValueError):
            FlightRecorder(capacity=0)

    def test_dump_jsonl(self, tmp_path):
        r = FlightRecorder(capacity=8)
        r.add("event", "compile", dur_s=1.5)
        path = str(tmp_path / "ring.jsonl")
        r.dump_jsonl(path)
        rows = [json.loads(ln) for ln in open(path)]
        assert rows[0]["name"] == "compile" and rows[0]["kind"] == "event"


class TestBenchStaysJaxFree:
    def test_bench_import_does_not_pull_jax(self):
        """bench.py's heartbeat helpers must load WITHOUT the estorch_tpu
        package init: importing jax in the bench driver would touch the
        possibly-wedged device runtime before the stage protocol's
        subprocess isolation can protect it (the round-1 lesson)."""
        repo = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
        r = subprocess.run(
            [sys.executable, "-c",
             "import sys; import bench; "
             "assert 'jax' not in sys.modules, 'bench imported jax'; "
             "assert 'estorch_tpu' not in sys.modules, "
             "'bench ran the package __init__'; "
             "assert callable(bench.describe_heartbeat)"],
            capture_output=True, text=True, cwd=repo, timeout=60,
        )
        assert r.returncode == 0, r.stderr


class TestHeartbeat:
    def test_beat_and_read(self, tmp_path):
        path = str(tmp_path / "hb.json")
        Heartbeat(path).beat("eval", 3, {"env_steps": 10})
        hb = read_heartbeat(path)
        assert hb["phase"] == "eval"
        assert hb["generation"] == 3
        assert hb["counters"] == {"env_steps": 10}
        assert 0 <= hb["age_s"] < STALE_AFTER_S

    def test_staleness_from_old_timestamp(self, tmp_path):
        path = str(tmp_path / "hb.json")
        with open(path, "w") as f:
            json.dump({"ts": time.time() - 10 * STALE_AFTER_S,
                       "pid": 1, "phase": "device", "generation": 7}, f)
        hb = read_heartbeat(path)
        assert hb["age_s"] > STALE_AFTER_S

    def test_missing_and_corrupt_return_none(self, tmp_path):
        assert read_heartbeat(str(tmp_path / "nope.json")) is None
        bad = tmp_path / "bad.json"
        bad.write_text("{half a rec")
        assert read_heartbeat(str(bad)) is None

    def test_telemetry_beats_on_phase_entry(self, tmp_path):
        """A wedge INSIDE a phase must leave that phase's name behind —
        the beat happens at entry, not exit."""
        path = str(tmp_path / "hb.json")
        t = Telemetry(heartbeat_path=path)
        try:
            with t.phase("eval"):
                mid = read_heartbeat(path)
                raise RuntimeError("wedge stand-in")
        except RuntimeError:
            pass
        assert mid["phase"] == "eval"


# ---------------------------------------------------------------------
# manifest
# ---------------------------------------------------------------------

class TestManifest:
    def test_round_trip(self, tmp_path):
        man = collect_manifest(config={"population_size": 64},
                               extra={"run_id": "r1"})
        path = str(tmp_path / "runs" / "manifest.json")
        write_manifest(path, man)
        back = load_manifest(path)
        assert back["config"] == {"population_size": 64}
        assert back["run_id"] == "r1"
        assert back["jax"] is not None
        assert back["python"] == sys.version.split()[0]
        # this repo IS a git checkout — the sha must resolve here
        assert isinstance(back["git_sha"], str) and len(back["git_sha"]) == 40

    def test_schema_mismatch_raises(self, tmp_path):
        path = str(tmp_path / "m.json")
        with open(path, "w") as f:
            json.dump({"schema": 999}, f)
        with pytest.raises(ValueError):
            load_manifest(path)

    def test_es_manifest_carries_device_topology(self, small_device_es):
        man = small_device_es.run_manifest()
        assert man["config"]["algorithm"] == "ES"
        assert man["config"]["backend"] == "device"
        assert len(man["devices"]) == 8  # the 8-virtual-device CPU mesh
        assert man["devices"][0]["platform"] == "cpu"


# ---------------------------------------------------------------------
# records from a REAL run + summarize
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_device_es():
    import optax

    from estorch_tpu import ES, JaxAgent, MLPPolicy
    from estorch_tpu.envs import CartPole

    return ES(
        MLPPolicy, JaxAgent, optax.adam,
        population_size=16, sigma=0.1, seed=0,
        policy_kwargs={"action_dim": 2, "hidden": (8,), "discrete": True},
        agent_kwargs={"env": CartPole(), "horizon": 25},
        optimizer_kwargs={"learning_rate": 0.05},
    )


class TestRealRecords:
    def test_device_records_pass_schema_and_carry_phases(
            self, small_device_es, tmp_path):
        """The contract the selfcheck golden pins must hold for records an
        actual ES produces — this is the test that catches a one-sided
        edit of _base_record vs RECORD_SCHEMA/GOLDEN_RECORD."""
        path = str(tmp_path / "run.jsonl")
        sink = JsonlSink(path)
        small_device_es.train(3, verbose=False, log_fn=sink)
        sink.close()
        recs = JsonlSink.read(path)
        assert len(recs) == 3
        for rec in recs:
            assert validate_record(rec) == [], validate_record(rec)
        # the fused device path's honest span names
        assert {"dispatch", "device", "host_sync"} <= set(recs[-1]["phases"])
        s = summarize(recs)
        assert s["generations"] == 3
        assert s["env_steps"] == sum(r["env_steps"] for r in recs)
        assert "device" in s["phase_share"]

    def test_golden_matches_schema(self):
        assert validate_record(GOLDEN_RECORD) == []

    def test_selfcheck_clean(self):
        assert selfcheck() == []


def _synthetic_records(n=8, stall_at=None):
    recs = []
    for g in range(n):
        wall = 2.0 if g != stall_at else 40.0
        recs.append(dict(
            GOLDEN_RECORD, generation=g, wall_time_s=wall,
            env_steps=1000, env_steps_per_sec=1000 / wall,
            phases={"sample": 0.05, "eval": 1.5, "update": 0.4,
                    "update/obsnorm_merge": 0.1},
        ))
    return recs


class TestSummarize:
    def test_phase_share_and_nesting(self):
        s = summarize(_synthetic_records())
        share = s["phase_share"]
        assert set(share) == {"sample", "eval", "update"}
        assert share["eval"]["share"] > share["update"]["share"]
        assert "obsnorm_merge" in share["update"]["children"]
        total = sum(row["share"] for row in share.values())
        assert abs(total - 1.0) < 1e-3  # shares are rounded to 4 decimals

    def test_stall_detection(self):
        s = summarize(_synthetic_records(stall_at=5))
        assert [st["generation"] for st in s["stalls"]] == [5]
        assert "took" in s["diagnosis"]

    def test_stale_heartbeat_in_diagnosis(self, tmp_path):
        hb = tmp_path / "heartbeat.json"
        hb.write_text(json.dumps(
            {"ts": time.time() - 10 * STALE_AFTER_S, "pid": 1,
             "phase": "device", "generation": 4}))
        s = summarize(_synthetic_records(), heartbeat_path=str(hb))
        assert "STALE" in s["diagnosis"]
        assert "phase=device" in s["diagnosis"]

    def test_empty_run(self):
        assert summarize([])["generations"] == 0


class TestCLI:
    def _run(self, args, cwd=None):
        return subprocess.run(
            [sys.executable, "-m", "estorch_tpu.obs", *args],
            capture_output=True, text=True, timeout=120, cwd=cwd,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )

    def test_selfcheck_exits_zero(self):
        r = self._run(["summarize", "--selfcheck"])
        assert r.returncode == 0, r.stderr
        assert "OK" in r.stdout

    def test_summarize_human_output(self, tmp_path):
        path = tmp_path / "run.jsonl"
        with open(path, "w") as f:
            for rec in _synthetic_records(stall_at=3):
                f.write(json.dumps(rec) + "\n")
        r = self._run(["summarize", str(path)])
        assert r.returncode == 0, r.stderr
        for needle in ("sample", "eval", "update", "env steps/s",
                       "diagnosis"):
            assert needle in r.stdout
        # auto-discovers a heartbeat.json beside the JSONL
        hb = tmp_path / "heartbeat.json"
        hb.write_text(json.dumps(
            {"ts": time.time() - 10 * STALE_AFTER_S, "pid": 1,
             "phase": "eval", "generation": 2}))
        r2 = self._run(["summarize", str(path)])
        assert "STALE" in r2.stdout

    def test_summarize_json_output(self, tmp_path):
        path = tmp_path / "run.jsonl"
        with open(path, "w") as f:
            for rec in _synthetic_records():
                f.write(json.dumps(rec) + "\n")
        r = self._run(["summarize", str(path), "--json"])
        s = json.loads(r.stdout)
        assert s["generations"] == 8
        assert s["phase_share"]["eval"]["seconds"] > 0

    def test_missing_file_is_error_not_traceback(self, tmp_path):
        r = self._run(["summarize", str(tmp_path / "nope.jsonl")])
        assert r.returncode == 1
        assert "cannot read" in r.stderr


# ---------------------------------------------------------------------
# ES integration: telemetry kwarg + heartbeat env protocol
# ---------------------------------------------------------------------

class TestESIntegration:
    def test_telemetry_disabled_records_empty_phases(self, monkeypatch):
        import torch

        from estorch_tpu import ES

        class MLP(torch.nn.Module):
            def __init__(self):
                super().__init__()
                self.net = torch.nn.Linear(4, 2)

            def forward(self, x):
                return self.net(x)

        class Agent:
            def rollout(self, policy):
                self.last_episode_steps = 1
                with torch.no_grad():
                    v = torch.nn.utils.parameters_to_vector(
                        policy.parameters())
                    return -float((v ** 2).sum())

        recs = []
        es = ES(MLP, Agent, torch.optim.Adam, population_size=8,
                sigma=0.05, table_size=1 << 12, telemetry=False)
        es.train(1, verbose=False, log_fn=recs.append)
        assert recs[0]["phases"] == {}

        # default-on: the host backend emits the canonical span names and
        # the heartbeat env var is honored end to end
        hb_path = os.path.join(
            os.environ.get("TMPDIR", "/tmp"), f"hb_{os.getpid()}.json")
        monkeypatch.setenv("ESTORCH_OBS_HEARTBEAT", hb_path)
        try:
            recs2 = []
            es2 = ES(MLP, Agent, torch.optim.Adam, population_size=8,
                     sigma=0.05, table_size=1 << 12)
            es2.train(2, verbose=False, log_fn=recs2.append)
            assert {"sample", "eval", "update"} <= set(recs2[0]["phases"])
            hb = read_heartbeat(hb_path)
            assert hb is not None and hb["generation"] == 2
            assert es2.obs.counters.get("env_steps") == sum(
                r["env_steps"] for r in recs2)
        finally:
            try:
                os.remove(hb_path)
            except OSError:
                pass
