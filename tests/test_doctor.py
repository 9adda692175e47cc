"""Environment doctor (estorch_tpu/doctor.py).

The device probe itself runs a REAL subprocess against whatever backend
this machine has, so the tests pin the classifier's behavior on
controlled child processes and the report's shape, not the machine's
health.
"""

import pytest

import json
import sys

from estorch_tpu import doctor


def _stub_subprocess_probes(monkeypatch):
    """report() tests whose subject is NOT the mesh / scenario / elastic
    probes stub them (each is a jax-importing child, seconds apiece, with
    a test class of its own below)."""
    for name, timeout in (("check_mesh", 90.0), ("check_scenarios", 90.0),
                          ("check_elastic", 120.0)):
        monkeypatch.setattr(
            doctor, name, lambda _t=timeout, **kw: {
                "status": "ok", "elapsed_s": 0.1, "timeout_s": _t})


class TestProbeClassifier:
    def test_healthy_parse(self, monkeypatch):
        """A child that prints PROBE_OK is classified healthy with fields."""
        monkeypatch.setattr(doctor, "_PROBE", "print('PROBE_OK cpu 8')")
        out = doctor.probe_device(timeout_s=60)
        assert out == {"status": "healthy", "platform": "cpu",
                       "n_devices": 8}

    @pytest.mark.slow
    def test_wedge_detected_by_timeout_with_stderr_clue(self, monkeypatch):
        """A child that hangs past the timeout is classified wedged, and
        whatever it wrote to stderr before hanging survives in the report
        (the only clue about WHERE the runtime hung)."""
        monkeypatch.setattr(doctor, "_PROBE", (
            "import sys, time\n"
            "sys.stderr.write('initializing device plugin...')\n"
            "sys.stderr.flush()\n"
            "time.sleep(60)\n"
        ))
        # the stub child imports nothing: a few seconds cover interpreter
        # startup even on a loaded host
        out = doctor.probe_device(timeout_s=5)
        assert out["status"] == "wedged"
        assert out["timeout_s"] == 5
        assert "initializing device plugin" in out["stderr_tail"]

    def test_fast_failure_is_error_not_wedge(self, monkeypatch):
        """A child that raises quickly is an init error with stderr tail."""
        monkeypatch.setattr(doctor, "_PROBE",
                            "raise RuntimeError('backend exploded')")
        out = doctor.probe_device(timeout_s=60)
        assert out["status"] == "error"
        assert "backend exploded" in out["stderr_tail"]


class TestCheckDevice:
    """The typed staged probe (check_device): reason-code classification on
    the pure classifier, hang classification on controlled children that
    wedge at a KNOWN stage, and the healthy path against this image's
    CPU backend."""

    def test_classifier_reason_codes(self):
        c = doctor.classify_device_probe
        ok = "PROBE_START\nPROBE_JAX_OK\nPROBE_DEVICES_OK cpu 1\n" \
             "PROBE_COMPILE_OK\nPROBE_EXEC_OK\n"
        assert c(ok, False, 0) == ("ok", None)
        assert c("", True, None) == ("failed", "init-hang")
        assert c("PROBE_START\nPROBE_JAX_OK\n", True, None) == \
            ("failed", "init-hang")
        assert c("PROBE_START\nPROBE_JAX_OK\nPROBE_DEVICES_OK cpu 1\n",
                 True, None) == ("failed", "compile-hang")
        assert c("PROBE_START\nPROBE_JAX_OK\nPROBE_DEVICES_OK cpu 1\n"
                 "PROBE_COMPILE_OK\n", True, None) == \
            ("failed", "exec-hang")
        # failed FAST before device init: the backend said no — not a wedge
        assert c("PROBE_START\nPROBE_JAX_OK\n", False, 1) == \
            ("failed", "no-device")
        # failed fast AFTER devices existed: error, read the stderr
        assert c("PROBE_START\nPROBE_JAX_OK\nPROBE_DEVICES_OK cpu 1\n",
                 False, 1) == ("failed", "error")

    def test_healthy_cpu_probe_is_fast_and_typed(self):
        """On this image's CPU backend the full staged probe (import →
        devices → compile → execute) must come back ok in seconds — the
        <30s platform-decision contract bench.py builds on."""
        out = doctor.check_device(timeout_s=60.0)
        assert out["status"] == "ok"
        assert out["platform"] == "cpu"
        assert out["n_devices"] >= 1
        assert "reason" not in out
        assert out["elapsed_s"] < 30.0

    def test_compile_hang_classified(self, monkeypatch):
        monkeypatch.setattr(doctor, "_STAGED_PROBE", (
            'print("PROBE_START", flush=True)\n'
            'print("PROBE_JAX_OK", flush=True)\n'
            'print("PROBE_DEVICES_OK cpu 1", flush=True)\n'
            "import time; time.sleep(60)\n"))
        out = doctor.check_device(timeout_s=1.0)
        assert out["status"] == "failed"
        assert out["reason"] == "compile-hang"
        assert out["platform"] == "cpu"  # the layer that DID answer

    def test_init_hang_classified(self, monkeypatch):
        monkeypatch.setattr(doctor, "_STAGED_PROBE", (
            'print("PROBE_START", flush=True)\n'
            "import time; time.sleep(60)\n"))
        out = doctor.check_device(timeout_s=1.0)
        assert out["status"] == "failed"
        assert out["reason"] == "init-hang"

    def test_no_device_failure_is_fast(self, monkeypatch):
        monkeypatch.setattr(doctor, "_STAGED_PROBE", (
            'print("PROBE_START", flush=True)\n'
            'import sys\n'
            'print("no backend here", file=sys.stderr)\n'
            "sys.exit(1)\n"))
        out = doctor.check_device(timeout_s=30.0)
        assert out["status"] == "failed"
        assert out["reason"] == "no-device"
        assert "no backend here" in out["stderr_tail"]
        assert out["elapsed_s"] < 10.0

    def test_platform_pin_reaches_child(self, monkeypatch):
        monkeypatch.setattr(doctor, "_STAGED_PROBE", (
            "import os, sys\n"
            'plat = os.environ.get("JAX_PLATFORMS", "unset")\n'
            'print("PROBE_DEVICES_OK", plat, 1, flush=True)\n'
            "sys.exit(1)\n"))
        out = doctor.check_device(timeout_s=30.0, platform="tpu")
        # the stub echoes the env pin back through the DEVICES marker
        assert out["requested_platform"] == "tpu"
        assert out["platform"] == "tpu"

    def test_healthy_backend_of_another_platform_is_not_the_chip(
            self, monkeypatch):
        """A caller that asks for the chip must not be told "ok" by a
        probe that came up healthy on the CPU."""
        monkeypatch.setattr(doctor, "_STAGED_PROBE", (
            'print("PROBE_JAX_OK", flush=True)\n'
            'print("PROBE_DEVICES_OK cpu 8", flush=True)\n'
            'print("PROBE_COMPILE_OK", flush=True)\n'
            'print("PROBE_EXEC_OK", flush=True)\n'))
        out = doctor.check_device(timeout_s=30.0, platform="tpu")
        assert out["status"] == "failed"
        assert out["reason"] == "wrong-platform"
        assert out["platform"] == "cpu"  # what it found, reported
        # the same probe with no platform asked for is a healthy backend
        assert doctor.check_device(timeout_s=30.0)["status"] == "ok"

    def test_report_gains_device_probe_row(self, monkeypatch):
        monkeypatch.setattr(
            doctor, "check_device",
            lambda timeout_s=20.0, platform=None: {
                "status": "failed", "reason": "init-hang",
                "elapsed_s": timeout_s, "timeout_s": timeout_s})
        _stub_subprocess_probes(monkeypatch)
        rep = doctor.report(timeout_s=5)
        assert rep["device_probe"]["reason"] == "init-hang"
        # ONE staged probe serves both rows: the legacy device summary
        # is derived from the same verdict (a *-hang reason = wedged),
        # so a wedged host pays one timeout, not two serial ones
        assert rep["device"]["status"] == "wedged"
        assert rep["device"]["timeout_s"] == 5


class TestMeshCheck:
    """The param-sharded mesh probe (check_mesh): can the 2-D virtual
    CPU mesh build, the default partition rules resolve, and one donated
    sharded program compile+execute here?  (docs/sharding.md)"""

    def test_classifier_reason_codes(self):
        c = doctor.classify_mesh_probe
        ok = ("MESH_START\nMESH_BUILD_OK 8\nMESH_RULES_OK\n"
              "MESH_COMPILE_OK\nMESH_EXEC_OK\n")
        assert c(ok, False, 0) == ("ok", None)
        assert c("MESH_START\n", True, None) == ("failed", "mesh-build")
        assert c("MESH_START\nMESH_BUILD_OK 8\n", False, 1) == \
            ("failed", "partition-rules")
        assert c("MESH_START\nMESH_BUILD_OK 8\nMESH_RULES_OK\n",
                 True, None) == ("failed", "sharded-compile")
        assert c("MESH_START\nMESH_BUILD_OK 8\nMESH_RULES_OK\n"
                 "MESH_COMPILE_OK\n", False, 1) == \
            ("failed", "sharded-exec")

    def test_healthy_mesh_probe(self):
        out = doctor.check_mesh(timeout_s=120.0)
        assert out["status"] == "ok", out
        assert "failed_stage" not in out

    def test_failing_stage_named(self, monkeypatch):
        monkeypatch.setattr(doctor, "_MESH_PROBE", (
            'print("MESH_START", flush=True)\n'
            'print("MESH_BUILD_OK 8", flush=True)\n'
            'raise RuntimeError("no rules for you")\n'))
        out = doctor.check_mesh(timeout_s=30.0)
        assert out["status"] == "failed"
        assert out["failed_stage"] == "partition-rules"
        assert "no rules for you" in out["stderr_tail"]

    def test_report_gains_mesh_row(self, monkeypatch):
        """report() carries the mesh verdict without re-running the
        heavy probe here (stubbed like the device row's test)."""
        _stub_subprocess_probes(monkeypatch)
        monkeypatch.setattr(doctor, "check_mesh",
                            lambda **kw: {"status": "ok", "elapsed_s": 0.1,
                                          "timeout_s": 90.0})
        monkeypatch.setattr(doctor, "check_device",
                            lambda timeout_s=20.0, platform=None: {
                                "status": "ok", "platform": "cpu",
                                "n_devices": 8, "elapsed_s": 0.1,
                                "timeout_s": timeout_s})
        monkeypatch.setattr(doctor, "check_elastic",
                            lambda **kw: {"status": "ok",
                                          "elapsed_s": 0.1,
                                          "timeout_s": 120.0})
        rep = doctor.report(timeout_s=5.0)
        assert rep["mesh"]["status"] == "ok"


class TestScenariosCheck:
    """The scenario-suite probe (check_scenarios): deterministic
    distribution draws + one tiny traced-operand rollout across 3
    variants (docs/scenarios.md), findings-not-tracebacks on failure."""

    def test_classifier_reason_codes(self):
        c = doctor.classify_scenario_probe
        ok = "SCEN_START\nSCEN_DRAW_OK\nSCEN_ROLLOUT_OK\n"
        assert c(ok, False, 0) == ("ok", None)
        assert c("SCEN_START\n", True, None) == \
            ("failed", "draw-determinism")
        assert c("SCEN_START\nSCEN_DRAW_OK\n", False, 1) == \
            ("failed", "traced-rollout")
        # all markers but a dirty exit: the last stage takes the blame
        assert c(ok, False, 1) == ("failed", "traced-rollout")

    def test_healthy_scenario_probe(self):
        out = doctor.check_scenarios(timeout_s=120.0)
        assert out["status"] == "ok", out
        assert "failed_stage" not in out

    def test_failing_stage_named_not_raised(self, monkeypatch):
        monkeypatch.setattr(doctor, "_SCENARIO_PROBE", (
            'print("SCEN_START", flush=True)\n'
            'print("SCEN_DRAW_OK", flush=True)\n'
            'raise RuntimeError("variant rollout exploded")\n'))
        out = doctor.check_scenarios(timeout_s=30.0)
        assert out["status"] == "failed"
        assert out["failed_stage"] == "traced-rollout"
        assert "variant rollout exploded" in out["stderr_tail"]

    def test_report_gains_scenarios_row(self, monkeypatch):
        _stub_subprocess_probes(monkeypatch)
        monkeypatch.setattr(doctor, "check_scenarios",
                            lambda **kw: {"status": "ok", "elapsed_s": 0.1,
                                          "timeout_s": 90.0})
        monkeypatch.setattr(doctor, "check_mesh",
                            lambda **kw: {"status": "ok", "elapsed_s": 0.1,
                                          "timeout_s": 90.0})
        monkeypatch.setattr(doctor, "check_device",
                            lambda timeout_s=20.0, platform=None: {
                                "status": "ok", "platform": "cpu",
                                "n_devices": 8, "elapsed_s": 0.1,
                                "timeout_s": timeout_s})
        monkeypatch.setattr(doctor, "check_elastic",
                            lambda **kw: {"status": "ok",
                                          "elapsed_s": 0.1,
                                          "timeout_s": 120.0})
        rep = doctor.report(timeout_s=5.0)
        assert rep["scenarios"]["status"] == "ok"


class TestElasticCheck:
    """The elastic multi-host probe (check_elastic): staged subprocess —
    2-process jax.distributed bring-up over loopback (Gloo CPU
    collectives) → cross-process mesh → one cross-process psum →
    the jax-free coordinator TCP round-trip (docs/multihost.md);
    findings-not-tracebacks, the first missing marker names the layer."""

    def test_classifier_reason_codes(self):
        c = doctor.classify_elastic_probe
        ok = ("ELASTIC_START\nELASTIC_INIT_OK\nELASTIC_MESH_OK\n"
              "ELASTIC_PSUM_OK\nELASTIC_COORD_OK\n")
        assert c(ok, False, 0) == ("ok", None)
        assert c("ELASTIC_START\n", True, None) == \
            ("failed", "distributed-init")
        assert c("ELASTIC_START\nELASTIC_INIT_OK\n", False, 1) == \
            ("failed", "mesh-build")
        assert c("ELASTIC_START\nELASTIC_INIT_OK\nELASTIC_MESH_OK\n",
                 False, 1) == ("failed", "cross-process-psum")
        # all markers but a dirty exit: the last stage takes the blame
        assert c(ok, False, 1) == ("failed", "coordinator-roundtrip")

    def test_healthy_elastic_probe(self):
        out = doctor.check_elastic(timeout_s=120.0)
        assert out["status"] == "ok", out
        assert "failed_stage" not in out

    def test_failing_stage_named_not_raised(self, monkeypatch):
        monkeypatch.setattr(doctor, "_ELASTIC_PROBE", (
            'print("ELASTIC_START", flush=True)\n'
            'print("ELASTIC_INIT_OK", flush=True)\n'
            'raise RuntimeError("no cross-process mesh here")\n'))
        out = doctor.check_elastic(timeout_s=30.0)
        assert out["status"] == "failed"
        assert out["failed_stage"] == "mesh-build"
        assert "no cross-process mesh here" in out["stderr_tail"]

    def test_report_gains_elastic_row(self, monkeypatch):
        _stub_subprocess_probes(monkeypatch)
        monkeypatch.setattr(doctor, "check_elastic",
                            lambda **kw: {"status": "failed",
                                          "failed_stage": "distributed-init",
                                          "elapsed_s": 0.1,
                                          "timeout_s": 120.0})
        monkeypatch.setattr(doctor, "check_mesh",
                            lambda **kw: {"status": "ok"})
        monkeypatch.setattr(doctor, "check_scenarios",
                            lambda **kw: {"status": "ok"})
        monkeypatch.setattr(doctor, "check_device",
                            lambda timeout_s=20.0, platform=None: {
                                "status": "ok", "platform": "cpu",
                                "n_devices": 8, "elapsed_s": 0.1,
                                "timeout_s": timeout_s})
        rep = doctor.report(timeout_s=5.0)
        assert rep["elastic"]["failed_stage"] == "distributed-init"


class TestOptionalDeps:
    def test_missing_parent_package_never_crashes(self, monkeypatch):
        """find_spec('pkg.sub') raises ModuleNotFoundError when pkg itself
        is absent; the report must say unavailable, not traceback."""
        import importlib.util as ilu

        real = ilu.find_spec

        def raising(name, *a, **k):
            if name.startswith("mujoco"):
                raise ModuleNotFoundError("No module named 'mujoco'")
            return real(name, *a, **k)

        monkeypatch.setattr(ilu, "find_spec", raising)
        out = doctor.check_optional_deps()
        assert out["mujoco.mjx"]["available"] is False
        assert out["mujoco"]["available"] is False
        assert out["gymnasium"]["available"] is True


class TestObsCheck:
    def test_trace_dir_and_tensorboard_reported(self, tmp_path,
                                                monkeypatch):
        monkeypatch.setenv("ESTORCH_OBS_DIR", str(tmp_path))
        out = doctor.check_obs()
        assert out["trace_dir"]["path"] == str(tmp_path)
        assert out["trace_dir"]["writable"] is True
        assert isinstance(out["tensorboard"]["available"], bool)
        assert "heartbeat" not in out  # no run dir given

    def test_unwritable_trace_dir_never_crashes(self, tmp_path,
                                                monkeypatch):
        monkeypatch.setenv("ESTORCH_OBS_DIR",
                           str(tmp_path / "does" / "not" / "exist"))
        out = doctor.check_obs()
        assert out["trace_dir"]["writable"] is False
        assert "error" in out["trace_dir"]

    def test_heartbeat_fresh_vs_stale_vs_missing(self, tmp_path):
        import time

        from estorch_tpu.obs import Heartbeat
        from estorch_tpu.obs.recorder import STALE_AFTER_S

        out = doctor.check_obs(str(tmp_path))
        assert out["heartbeat"]["found"] is False
        assert "hint" in out["heartbeat"]

        Heartbeat(str(tmp_path / "heartbeat.json")).beat("eval", 5)
        out = doctor.check_obs(str(tmp_path))
        hb = out["heartbeat"]
        assert hb["found"] is True and hb["stale"] is False
        assert hb["phase"] == "eval" and hb["generation"] == 5

        with open(tmp_path / "heartbeat.json", "w") as f:
            json.dump({"ts": time.time() - 10 * STALE_AFTER_S,
                       "pid": 1, "phase": "device", "generation": 2}, f)
        out = doctor.check_obs(str(tmp_path))
        assert out["heartbeat"]["stale"] is True
        assert out["heartbeat"]["age_s"] > STALE_AFTER_S

    def test_export_probe_scrapes_and_parses(self):
        """The export probe: loopback-scrape the metrics sidecar over a
        synthetic temp run-dir and validate the exposition parses, with
        the published+live counter composition checked end to end."""
        out = doctor.check_obs()
        probe = out["export"]
        assert probe["ok"] is True, probe
        assert probe["samples"] > 0

    def test_export_probe_failure_is_reported_not_raised(self,
                                                         monkeypatch):
        """A diagnostic tool never crashes the report — a broken sidecar
        surfaces as ok=False with the error."""
        from estorch_tpu.obs.export import sidecar as sidecar_mod

        def boom(*a, **k):
            raise RuntimeError("bind refused")

        monkeypatch.setattr(sidecar_mod.MetricsSidecar, "__init__", boom)
        probe = doctor.check_obs()["export"]
        assert probe["ok"] is False
        assert "bind refused" in probe["error"]


class TestCollectorCheck:
    def test_collector_probe_end_to_end(self):
        """check_collector: synthetic sidecar target + dead port under a
        real collector for one tick — stored sample, rules evaluation
        (dead fires, live doesn't), /alerts and /metrics parse."""
        out = doctor.check_collector()
        assert out["ok"] is True, out

    def test_refused_port_never_crashes_the_report(self, monkeypatch):
        """The ISSUE's explicit hazard: a host that cannot bind loopback
        must get a finding, not a traceback."""
        from estorch_tpu.obs.agg import collector as collector_mod

        def boom(*a, **k):
            raise OSError("port refused")

        monkeypatch.setattr(collector_mod.Collector, "__init__", boom)
        out = doctor.check_collector()
        assert out["ok"] is False
        assert "port refused" in out["error"]

    def test_report_gains_collector_row(self, monkeypatch):
        """report() carries the collector verdict (heavy probes stubbed
        like the device/mesh row tests)."""
        _stub_subprocess_probes(monkeypatch)
        monkeypatch.setattr(doctor, "check_mesh",
                            lambda **kw: {"status": "ok"})
        monkeypatch.setattr(doctor, "check_device",
                            lambda timeout_s=20.0, platform=None: {
                                "status": "ok", "platform": "cpu",
                                "n_devices": 8, "elapsed_s": 0.1,
                                "timeout_s": timeout_s})
        monkeypatch.setattr(doctor, "check_collector",
                            lambda: {"ok": True})
        monkeypatch.setattr(doctor, "check_elastic",
                            lambda **kw: {"status": "ok",
                                          "elapsed_s": 0.1,
                                          "timeout_s": 120.0})
        rep = doctor.report(timeout_s=5.0)
        assert rep["collector"] == {"ok": True}


class TestRouterCheck:
    def test_router_probe_failover_end_to_end(self):
        """check_router: a 2-replica toy fleet behind a real Router —
        kill one replica, the next requests must still answer (retry on
        the survivor) and /metrics must parse with the per-replica
        breaker gauges."""
        out = doctor.check_router()
        assert out["ok"] is True, out
        assert out["retries"] >= 1  # the probe's health is STALE by
        # design, so failover HAD to go through the retry budget
        assert out["breakers"]["ra"] == "open"
        assert out["breakers"]["rb"] == "closed"

    def test_router_probe_never_crashes_the_report(self, monkeypatch):
        from estorch_tpu.serve import router as router_mod

        def boom(*a, **k):
            raise OSError("no loopback")

        monkeypatch.setattr(router_mod.Router, "__init__", boom)
        out = doctor.check_router()
        assert out["ok"] is False
        assert "no loopback" in out["error"]

    def test_report_gains_router_row(self, monkeypatch):
        _stub_subprocess_probes(monkeypatch)
        monkeypatch.setattr(doctor, "check_mesh",
                            lambda **kw: {"status": "ok"})
        monkeypatch.setattr(doctor, "check_device",
                            lambda timeout_s=20.0, platform=None: {
                                "status": "ok", "platform": "cpu",
                                "n_devices": 8, "elapsed_s": 0.1,
                                "timeout_s": timeout_s})
        monkeypatch.setattr(doctor, "check_collector",
                            lambda: {"ok": True})
        monkeypatch.setattr(doctor, "check_router",
                            lambda: {"ok": True, "retries": 1})
        monkeypatch.setattr(doctor, "check_elastic",
                            lambda **kw: {"status": "ok",
                                          "elapsed_s": 0.1,
                                          "timeout_s": 120.0})
        rep = doctor.report(timeout_s=5.0)
        assert rep["router"] == {"ok": True, "retries": 1}


class TestTracingCheck:
    def test_tracing_probe_assembles_across_processes(self):
        """check_tracing: one forced-sampled request through a real
        Router to a tracer-equipped toy replica must assemble into a
        single trace spanning both processes, with a cross-process hop
        and a schema-clean Perfetto export."""
        out = doctor.check_tracing()
        assert out["ok"] is True, out
        assert out["procs"] == ["router", "replica"]
        assert out["segments"] >= 3  # route + upstream leg + request
        assert out["cross_hops"] >= 1
        assert out["sampled"] == "forced"

    def test_tracing_probe_never_crashes_the_report(self, monkeypatch):
        from estorch_tpu.serve import router as router_mod

        def boom(*a, **k):
            raise OSError("no loopback")

        monkeypatch.setattr(router_mod.Router, "__init__", boom)
        out = doctor.check_tracing()
        assert out["ok"] is False
        assert "no loopback" in out["error"]

    def test_report_gains_tracing_row(self, monkeypatch):
        _stub_subprocess_probes(monkeypatch)
        monkeypatch.setattr(doctor, "check_mesh",
                            lambda **kw: {"status": "ok"})
        monkeypatch.setattr(doctor, "check_device",
                            lambda timeout_s=20.0, platform=None: {
                                "status": "ok", "platform": "cpu",
                                "n_devices": 8, "elapsed_s": 0.1,
                                "timeout_s": timeout_s})
        monkeypatch.setattr(doctor, "check_collector",
                            lambda: {"ok": True})
        monkeypatch.setattr(doctor, "check_router",
                            lambda: {"ok": True})
        monkeypatch.setattr(doctor, "check_tracing",
                            lambda: {"ok": True, "cross_hops": 1})
        monkeypatch.setattr(doctor, "check_elastic",
                            lambda **kw: {"status": "ok",
                                          "elapsed_s": 0.1,
                                          "timeout_s": 120.0})
        rep = doctor.report(timeout_s=5.0)
        assert rep["tracing"] == {"ok": True, "cross_hops": 1}


class TestResilienceCheck:
    def test_config_checks_without_probe(self, tmp_path, monkeypatch):
        monkeypatch.setenv("ESTORCH_CKPT_ROOT", str(tmp_path))
        out = doctor.check_resilience()
        assert out["ckpt_root"]["path"] == str(tmp_path)
        assert out["ckpt_root"]["writable"] is True
        assert "roundtrip" not in out  # probe is opt-in (subprocess cost)
        assert out["fork"]["available"] is True  # this CI image is posix
        assert out["heartbeat_watchdog"]["telemetry_enabled"] in (True, False)

    def test_unwritable_ckpt_root_never_crashes(self, tmp_path):
        out = doctor.check_resilience(
            ckpt_root=str(tmp_path / "missing" / "deep"))
        assert out["ckpt_root"]["writable"] is False
        assert "error" in out["ckpt_root"]

    def test_watchdog_warns_on_heartbeat_with_telemetry_off(
            self, tmp_path, monkeypatch):
        """The config trap the sanity check exists for: a heartbeat path
        with ESTORCH_OBS=0 means no beats ever — a staleness watchdog
        would kill perfectly healthy runs."""
        monkeypatch.setenv("ESTORCH_OBS_HEARTBEAT",
                           str(tmp_path / "hb.json"))
        monkeypatch.setenv("ESTORCH_OBS", "0")
        out = doctor.check_resilience(ckpt_root=str(tmp_path))
        wd = out["heartbeat_watchdog"]
        assert wd["heartbeat_env_set"] is True
        assert wd["telemetry_enabled"] is False
        assert "warning" in wd
        assert wd["heartbeat_dir_writable"] is True

    def test_roundtrip_probe_classifier(self, tmp_path, monkeypatch):
        """Probe protocol pinned on controlled children (the real probe
        builds a tiny ES — exercised once in test_resilience.py's
        supervisor flow, not per doctor test)."""
        monkeypatch.setattr(doctor, "_RESILIENCE_PROBE",
                            "print('RESILIENCE_PROBE_OK')")
        out = doctor.check_resilience(ckpt_root=str(tmp_path), probe=True)
        assert out["roundtrip"] == {"status": "ok"}

        monkeypatch.setattr(doctor, "_RESILIENCE_PROBE",
                            "raise RuntimeError('orbax exploded')")
        out = doctor.check_resilience(ckpt_root=str(tmp_path), probe=True)
        assert out["roundtrip"]["status"] == "error"
        assert "orbax exploded" in out["roundtrip"]["stderr_tail"]

    @pytest.mark.slow
    def test_roundtrip_probe_wedge_detected(self, tmp_path, monkeypatch):
        monkeypatch.setattr(doctor, "_RESILIENCE_PROBE",
                            "import time; time.sleep(60)")
        out = doctor.check_resilience(ckpt_root=str(tmp_path), probe=True,
                                      probe_timeout_s=8)
        assert out["roundtrip"]["status"] == "wedged"


class TestServeCheck:
    def test_loopback_and_batcher_smoke(self):
        out = doctor.check_serve()
        assert out["loopback"]["bindable"] is True
        assert out["batcher"]["ok"] is True
        # the numpy-only smoke compiles nothing, but the accounting must
        # still bound "recompiles" by the ladder it reports
        assert out["batcher"]["recompiles"] <= len(out["batcher"]["buckets"])
        assert "bundle" not in out  # no bundle given

    def test_bundle_validation_without_jax_import(self, tmp_path):
        """A structurally-broken bundle is diagnosed (not crashed on),
        and validation never needs the policy module to be importable."""
        out = doctor.check_serve(bundle=str(tmp_path / "missing"))
        assert out["bundle"]["valid"] is False
        assert "error" in out["bundle"]

        import json

        bdir = tmp_path / "b"
        bdir.mkdir()
        (bdir / "arrays.npz").write_bytes(b"junk")
        (bdir / "MANIFEST.json").write_text(json.dumps({
            "schema": 1, "version": "x",
            "module": {"import": "not.importable:Ghost", "kwargs": {}},
            "obs_shape": [3], "param_dim": 7,
            "sha256": {"arrays.npz": "0" * 64},
        }))
        out = doctor.check_serve(bundle=str(bdir))
        assert out["bundle"]["valid"] is False
        assert "checksum" in out["bundle"]["error"]

    def test_valid_bundle_reported(self, tmp_path):
        import hashlib
        import json

        import numpy as np

        bdir = tmp_path / "b"
        bdir.mkdir()
        arrays = bdir / "arrays.npz"
        with open(arrays, "wb") as f:
            np.savez(f, params_flat=np.zeros(7, np.float32))
        sha = hashlib.sha256(arrays.read_bytes()).hexdigest()
        (bdir / "MANIFEST.json").write_text(json.dumps({
            "schema": 1, "version": "v9",
            "module": {"import": "whatever:NotImported", "kwargs": {}},
            "obs_shape": [3], "param_dim": 7, "obs_norm": False,
            "sha256": {"arrays.npz": sha},
        }))
        out = doctor.check_serve(bundle=str(bdir))
        assert out["bundle"]["valid"] is True
        assert out["bundle"]["version"] == "v9"
        assert out["bundle"]["param_dim"] == 7
        assert out["bundle"]["warm"] == {"present": False}

    @staticmethod
    def _warm_bundle(tmp_path, jax_version, **warm_over):
        """Hand-crafted warm bundle — the probe must stay jax-free, so
        the fixture is raw files + checksums, no export machinery."""
        import hashlib
        import json

        import numpy as np

        bdir = tmp_path / "wb"
        bdir.mkdir()
        arrays = bdir / "arrays.npz"
        with open(arrays, "wb") as f:
            np.savez(f, params_flat=np.zeros(7, np.float32))
        (bdir / "warm").mkdir()
        entry = bdir / "warm" / "jit_one-abc123-cache"
        entry.write_bytes(b"fake executable bytes")
        sha = {
            "arrays.npz": hashlib.sha256(arrays.read_bytes()).hexdigest(),
            "warm/jit_one-abc123-cache": hashlib.sha256(
                entry.read_bytes()).hexdigest(),
        }
        warm = {
            "format": "xla_cache", "max_batch": 4,
            "buckets": [2, 4], "buckets_excluded": [],
            "dtypes": ["f32"],
            "entries": {"jit_one-abc123-cache": entry.stat().st_size},
            "jax_version": jax_version, "platform": "cpu",
            "device_count": 8,
        }
        warm.update(warm_over)
        (bdir / "MANIFEST.json").write_text(json.dumps({
            "schema": 1, "version": "v9",
            "module": {"import": "whatever:NotImported", "kwargs": {}},
            "obs_shape": [3], "param_dim": 7, "obs_norm": False,
            "sha256": sha, "warm": warm,
        }))
        return bdir

    def test_warm_probe_compatible(self, tmp_path):
        from importlib.metadata import version

        bdir = self._warm_bundle(tmp_path, version("jax"))
        out = doctor.check_serve(bundle=str(bdir))
        warm = out["bundle"]["warm"]
        assert warm["present"] and warm["compatible"] is True
        assert warm["entries"] == 1
        assert "finding" not in warm

    def test_warm_probe_version_mismatch_is_finding(self, tmp_path):
        """The satellite contract: stale warmth (built under another jax)
        is a structured FINDING naming the fix, never a traceback — and
        the bundle itself still validates."""
        bdir = self._warm_bundle(tmp_path, "0.0.0")
        out = doctor.check_serve(bundle=str(bdir))
        assert out["bundle"]["valid"] is True
        warm = out["bundle"]["warm"]
        assert warm["compatible"] is False
        assert "0.0.0" in warm["finding"]
        assert "re-export" in warm["finding"]

    def test_warm_probe_ladder_incomplete_rejected(self, tmp_path):
        """Structural breakage IS an error: a warm block whose buckets
        don't cover its own max_batch ladder can't be trusted."""
        bdir = self._warm_bundle(tmp_path, "0.0.0", buckets=[2])
        out = doctor.check_serve(bundle=str(bdir))
        assert out["bundle"]["valid"] is False
        assert "ladder incomplete" in out["bundle"]["error"]


class TestReport:
    def test_report_shape_and_hints(self, monkeypatch):
        monkeypatch.setattr(
            doctor, "check_device",
            lambda timeout_s=20.0, platform=None: {
                "status": "failed", "reason": "init-hang",
                "elapsed_s": timeout_s, "timeout_s": timeout_s,
                "stderr_tail": ""})
        _stub_subprocess_probes(monkeypatch)
        rep = doctor.report()
        assert rep["device"]["status"] == "wedged"
        # the answer to a dead chip is to free the chip, not the CPU mesh
        assert "one process at a time" in rep["hint"]
        assert "cpu" not in rep["hint"].lower()
        assert isinstance(rep["native"]["cpp_pool"], bool)
        assert rep["optional"]["gymnasium"]["available"] is True
        assert rep["obs"]["trace_dir"]["writable"] in (True, False)
        # resilience config checks ride every report (probe is opt-in)
        assert rep["resilience"]["fork"]["available"] is True
        assert "ckpt_root" in rep["resilience"]
        # serving readiness rides every report too (bundle is opt-in)
        assert rep["serve"]["loopback"]["bindable"] is True
        assert rep["serve"]["batcher"]["ok"] is True

    def test_report_run_dir_flows_to_obs_check(self, tmp_path,
                                               monkeypatch):
        from estorch_tpu.obs import Heartbeat

        monkeypatch.setattr(
            doctor, "check_device",
            lambda timeout_s=20.0, platform=None: {
                "status": "ok", "platform": "cpu", "n_devices": 8,
                "elapsed_s": 1.0, "timeout_s": timeout_s})
        Heartbeat(str(tmp_path / "heartbeat.json")).beat("update", 11)
        _stub_subprocess_probes(monkeypatch)
        rep = doctor.report(run_dir=str(tmp_path))
        assert rep["obs"]["heartbeat"]["generation"] == 11

    def test_cli_json_and_exit_code(self, monkeypatch, capsys):
        monkeypatch.setattr(
            doctor, "check_device",
            lambda timeout_s=20.0, platform=None: {
                "status": "ok", "platform": "cpu", "n_devices": 8,
                "elapsed_s": 1.0, "timeout_s": timeout_s})
        _stub_subprocess_probes(monkeypatch)
        rc = doctor.main(["--timeout", "5"])
        rep = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert rep["device"]["platform"] == "cpu"
        assert "hint" not in rep
