"""Performance attribution (estorch_tpu/obs/profile/): cost model,
roofline, compile ledger, `obs profile` CLI, the phase-localized regress
gate, and bench.py's probe-gated platform decision.

The acceptance contract (ISSUE 6): a run with known per-step FLOPs
produces exactly the expected MFU; ledger entries round-trip the
Prometheus exposition parser; degenerate inputs degrade to a note
(never a crash); an injected 30% eval-phase slowdown is flagged NAMING
the eval phase; and bench decides its platform from the typed device
probe instead of a 480s timeout.
"""

import json
import os
import subprocess
import sys

import pytest

from estorch_tpu.obs.__main__ import main as obs_main
from estorch_tpu.obs.export import regress
from estorch_tpu.obs.export.prometheus import (is_gauge, parse_exposition,
                                               render_exposition,
                                               samples_by_name)
from estorch_tpu.obs.profile import (CompileLedger, collect_compile_events,
                                     find_cost_model, format_profile,
                                     generation_cost, ledger_counters,
                                     device_roofline, measure_cpu_roofline,
                                     phase_cost_for, platform_roofline,
                                     profile_records)
from estorch_tpu.obs.profile.report import selfcheck as profile_selfcheck
from estorch_tpu.obs.spans import Telemetry


# ---------------------------------------------------------------------
# cost model
# ---------------------------------------------------------------------

class TestCostModel:
    SHAPES = [(3, 64), (64, 64), (64, 1)]

    def test_generation_cost_known_math(self):
        kernels = sum(m * n for m, n in self.SHAPES)
        param_dim = kernels + 64 + 64 + 1
        m = generation_cost(population=4096, matmul_shapes=self.SHAPES,
                            param_dim=param_dim, horizon=200)
        assert m["flops_per_env_step"] == 2 * kernels
        assert m["bytes_per_env_step"] == param_dim * 4
        assert m["env_steps_per_generation"] == 4096 * 200
        # mirrored: one table row per antithetic pair
        assert m["noise_dim"] == param_dim
        assert m["per_generation"]["sample"]["flops"] == \
            2 * 4096 * param_dim
        assert m["per_generation"]["update"]["flops"] == \
            2 * (4096 // 2) * param_dim
        assert m["per_generation"]["eval"]["flops"] == \
            4096 * 200 * 2 * kernels

    def test_low_rank_shrinks_noise_dim(self):
        kernels = sum(m * n for m, n in self.SHAPES)
        param_dim = kernels + 129
        m = generation_cost(population=256, matmul_shapes=self.SHAPES,
                            param_dim=param_dim, horizon=10, low_rank=2)
        factors = sum((a + b) * 2 for a, b in self.SHAPES)
        assert m["noise_dim"] == factors + 129  # factored kernels + dense rest
        assert m["noise_dim"] < param_dim
        # the factored apply adds the per-step reconstruction term
        assert m["flops_per_env_step"] > 2 * kernels

    def test_phase_cost_fused_device_is_the_sum(self):
        m = generation_cost(population=64, matmul_shapes=self.SHAPES,
                            param_dim=4481, horizon=10)
        steps = 64 * 10 * 3  # 3 generations' recorded env steps
        parts = [phase_cost_for(m, p, env_steps=steps, n_generations=3)
                 for p in ("sample", "eval", "update")]
        fused = phase_cost_for(m, "device", env_steps=steps,
                               n_generations=3)
        assert fused["flops"] == sum(p["flops"] for p in parts)
        assert fused["bytes"] == sum(p["bytes"] for p in parts)
        # host bookkeeping phases carry no modeled cost, by design
        assert phase_cost_for(m, "dispatch", env_steps=steps,
                              n_generations=3) is None

    def test_horizonless_model_omits_eval(self):
        m = generation_cost(population=16, matmul_shapes=self.SHAPES,
                            param_dim=4481, horizon=None)
        assert "eval" not in m["per_generation"]
        # eval cost still derivable from recorded env_steps
        c = phase_cost_for(m, "eval", env_steps=100, n_generations=1)
        assert c["flops"] == 100 * m["flops_per_env_step"]


class TestRoofline:
    def test_cpu_calibration_measures_positive_peaks(self):
        cal = measure_cpu_roofline(budget_s=0.05, gemm_n=128, copy_mb=4)
        assert cal["peak_flops_per_s"] > 0
        assert cal["peak_bytes_per_s"] > 0
        assert cal["basis"] == "cpu_calibrated"

    def test_tpu_roofline_is_keyed_by_device_kind_with_its_source(self):
        r = device_roofline("TPU v5 lite")  # the string the chip reports
        assert r["peak_flops_per_s"] == 197e12
        assert r["peak_bytes_per_s"] == 819e9
        assert r["basis"] == "tpu_v5e_bf16_peak"
        assert "TPU v5e" in r["source"]

    def test_unknown_device_kind_raises(self):
        """A chip that is not in the table is an error, never a default —
        and "tpu" is a platform, not a kind."""
        with pytest.raises(ValueError, match="TPU v9"):
            device_roofline("TPU v9")
        with pytest.raises(ValueError, match="device_kind"):
            platform_roofline("tpu")

    def test_unmeasured_cpu_roofline_keeps_the_tag(self):
        r = platform_roofline("cpu", measure=False)
        assert r["peak_flops_per_s"] is None
        assert r["basis"] == "cpu_calibrated"

    def test_unknown_platform_gets_no_denominator(self):
        """A gpu (or anything that isn't tpu/cpu) must NOT inherit the
        host CPU's measured GEMM ceiling as its peak — None-peaks and no
        basis, so MFU honestly stays null there."""
        r = platform_roofline("gpu")
        assert r["peak_flops_per_s"] is None
        assert r["peak_bytes_per_s"] is None
        assert r["basis"] is None
        assert r["platform"] == "gpu"


# ---------------------------------------------------------------------
# compile ledger + exposition round trip
# ---------------------------------------------------------------------

class TestCompileLedger:
    def test_take_new_cursor(self):
        led = CompileLedger()
        led.record("a", 1.0, generation=0)
        led.record("b", 2.0, generation=0, xla_flops=5e9)
        first = led.take_new()
        assert [e["program"] for e in first] == ["a", "b"]
        assert led.take_new() == []
        led.record("c", 3.0, generation=1)
        assert [e["program"] for e in led.take_new()] == ["c"]
        assert len(led) == 3

    def test_ledger_rides_exposition_and_parses_back(self):
        """Satellite 3: compile-ledger entries round-trip through the
        validating Prometheus parser."""
        entries = [{"program": "generation_step", "compile_s": 12.5,
                    "generation": 0, "xla_flops": 7.25e9,
                    "peak_bytes": 2.5e9}]
        folded = ledger_counters(entries)
        assert folded["compile_s_generation_step"] == 12.5
        assert folded["compile_xla_flops_generation_step"] == 7.25e9
        body = render_exposition(folded, up=True)
        vals = samples_by_name(parse_exposition(body))
        assert vals["estorch_compile_s_generation_step"] == 12.5
        assert vals["estorch_compile_peak_bytes_generation_step"] == 2.5e9
        # ledger facts are gauges (last-write-wins per program)
        assert is_gauge("compile_s_generation_step")
        assert is_gauge("compile_peak_bytes_generation_step")
        assert not is_gauge("recompiles")
        assert "# TYPE estorch_compile_s_generation_step gauge" in body

    def test_telemetry_compile_event_feeds_counters_and_flush(self):
        t = Telemetry()
        t.compile_event("prog_a", 1.5, first_call=True)
        t.compile_event("prog_b", 0.5, count_recompiles=0)
        snap = t.counters.snapshot()
        assert snap["recompiles"] == 1  # count_recompiles=0 respected
        assert snap["compile_time_s"] == 2.0  # cumulative over the ledger
        assert snap["compile_s_prog_a"] == 1.5
        evs = t.take_compile_events()
        assert [e["program"] for e in evs] == ["prog_a", "prog_b"]
        assert evs[0]["first_call"] is True
        assert t.take_compile_events() == []

    def test_disabled_telemetry_is_inert(self):
        t = Telemetry(enabled=False)
        assert t.compile_event("x", 1.0) is None
        assert t.take_compile_events() == []
        t.set_cost_model({"schema": 1})
        assert t.cost_model is None

    def test_collect_compile_events_skips_garbage(self):
        recs = [{"compile_events": [{"program": "a", "compile_s": 1.0},
                                    "not-a-dict"]},
                {"compile_events": "nope"}, {}, "junk"]
        assert collect_compile_events(recs) == \
            [{"program": "a", "compile_s": 1.0}]


# ---------------------------------------------------------------------
# profile_records: known math + the tolerance contract
# ---------------------------------------------------------------------

def _synth_run(eval_s=1.0, n=6, with_model=True, with_compiles=True):
    shapes = [(3, 64), (64, 64), (64, 1)]
    kernels = sum(m * n for m, n in shapes)
    model = generation_cost(population=512, matmul_shapes=shapes,
                            param_dim=kernels + 129, horizon=50)
    recs = []
    for g in range(n):
        rec = {"generation": g, "env_steps": 512 * 50,
               "env_steps_per_sec": 512 * 50 / (eval_s + 0.1),
               "wall_time_s": eval_s + 0.1, "reward_mean": 0.0,
               "reward_max": 0.0, "best_reward": 0.0,
               "phases": {"sample": 0.02, "eval": eval_s, "update": 0.08}}
        if g == 0:
            if with_model:
                rec["cost_model"] = model
            if with_compiles:
                rec["compile_events"] = [
                    {"program": "generation_step", "compile_s": 4.0,
                     "generation": 0,
                     "xla_flops": float(512 * 50 * 2 * kernels)}]
        recs.append(json.loads(json.dumps(rec)))
    return recs, model, kernels


class TestProfileRecords:
    ROOF = {"platform": "synthetic", "basis": "selfcheck",
            "peak_flops_per_s": 1e12, "peak_bytes_per_s": 1e11}

    def test_known_flops_exact_mfu(self):
        recs, model, kernels = _synth_run()
        p = profile_records(recs, self.ROOF)
        eval_row = p["phases"]["eval"]
        n = len(recs)
        want = (n * 512 * 50 * 2 * kernels) / (n * 1.0) / 1e12
        assert eval_row["mfu"] == pytest.approx(want, abs=0, rel=1e-12)
        assert eval_row["bound"] == "memory"  # GEMV regime vs ridge 10
        assert p["compile"]["n_events"] == 1
        # the fused program's XLA estimate vs the analytic per-gen total:
        # eval dominates, so the ratio lands near (eval+sample+update)/eval
        assert 0.9 < p["compile"]["model_vs_xla_flops_ratio"] < 1.5
        assert "eval" in format_profile(p)

    def test_phaseless_records_degrade_to_a_note(self):
        recs = [{"generation": g, "env_steps": 10,
                 "env_steps_per_sec": 1.0, "wall_time_s": 10.0,
                 "reward_mean": 0, "reward_max": 0, "best_reward": 0}
                for g in range(3)]
        p = profile_records(recs, self.ROOF)
        assert any("no phase spans" in n for n in p["notes"])
        assert any("no cost_model" in n for n in p["notes"])
        assert any("no compile events" in n for n in p["notes"])
        assert format_profile(p)  # renders, never raises

    def test_empty_and_modelless_runs(self):
        assert profile_records([], self.ROOF)["generations"] == 0
        recs, _, _ = _synth_run(with_model=False, with_compiles=False)
        p = profile_records(recs, self.ROOF)
        assert p["has_cost_model"] is False
        # time shares still reported without a model
        assert p["phases"]["eval"]["share"] > 0.8
        assert "mfu" not in p["phases"]["eval"]

    def test_uncalibrated_roofline_is_rates_only(self):
        recs, _, _ = _synth_run()
        p = profile_records(recs, {"platform": "cpu",
                                   "basis": "cpu_calibrated",
                                   "peak_flops_per_s": None,
                                   "peak_bytes_per_s": None})
        assert "flops_per_s" in p["phases"]["eval"]
        assert "mfu" not in p["phases"]["eval"]

    def test_replayed_generations_deduped(self):
        recs, _, _ = _synth_run(n=4)
        slow_replay = json.loads(json.dumps(recs[1]))
        slow_replay["phases"]["eval"] = 99.0
        recs_replayed = recs + [slow_replay]  # gen 1 replayed, last wins
        p = profile_records(recs_replayed, self.ROOF)
        assert p["generations"] == 4
        assert p["phases"]["eval"]["seconds"] == pytest.approx(
            3 * 1.0 + 99.0)

    def test_find_cost_model(self):
        recs, model, _ = _synth_run()
        assert find_cost_model(recs) == model
        assert find_cost_model([{"a": 1}]) is None

    def test_selfcheck_clean(self):
        assert profile_selfcheck() == []


# ---------------------------------------------------------------------
# phase-localized regress
# ---------------------------------------------------------------------

class TestPhaseRegress:
    def test_identical_runs_pass(self):
        recs, _, _ = _synth_run()
        v = regress.compare_phases(recs, recs)
        assert v["verdict"] == "pass"
        assert v["regressed_phases"] == []

    def test_eval_slowdown_flagged_naming_eval_only(self):
        """THE acceptance check: a 30% eval-phase slowdown is flagged
        naming the eval phase — and only it."""
        base, _, _ = _synth_run()
        slow, _, _ = _synth_run(eval_s=1.3)
        v = regress.compare_phases(slow, base)
        assert v["verdict"] == "regress"
        assert v["regressed_phases"] == ["eval"]
        assert v["phases"]["sample"]["verdict"] == "pass"
        assert v["phases"]["eval"]["slowdown_pct"] == pytest.approx(30, abs=1)

    def test_no_phase_rows_is_a_one_line_error(self):
        """Phase-less records degrade to the mixed-schema diagnosis (one
        line, names the side lacking rows) — never a bogus verdict."""
        with pytest.raises(ValueError,
                           match="carries no per-phase rows") as ei:
            regress.compare_phases([{"generation": 0}], [{"generation": 0}])
        assert "\n" not in str(ei.value)

    def test_disjoint_phase_names_is_an_error(self):
        with pytest.raises(ValueError, match="no shared top-level phases"):
            regress.compare_phases(
                [{"generation": 0, "phases": {"eval": 1.0}}],
                [{"generation": 0, "phases": {"update": 1.0}}])

    def test_cli_phases_exit_codes(self, tmp_path, capsys):
        base, _, _ = _synth_run()
        slow, _, _ = _synth_run(eval_s=1.3)
        bp, sp = tmp_path / "base.jsonl", tmp_path / "slow.jsonl"
        for path, recs in ((bp, base), (sp, slow)):
            with open(path, "w") as f:
                for r in recs:
                    f.write(json.dumps(r) + "\n")
        rc = obs_main(["regress", str(bp), "--baseline", str(bp),
                       "--phases"])
        assert rc == 0
        rc = obs_main(["regress", str(sp), "--baseline", str(bp),
                       "--phases"])
        assert rc == 1
        out = capsys.readouterr().out
        assert "eval" in out and "REGRESSION" in out

    def test_cli_phases_rejects_label(self, tmp_path, capsys):
        """--label filters bench A/B rows; phase records carry no labels.
        Combining them is a usage error (exit 3), not a silently
        unfiltered verdict."""
        base, _, _ = _synth_run()
        bp = tmp_path / "base.jsonl"
        with open(bp, "w") as f:
            for r in base:
                f.write(json.dumps(r) + "\n")
        rc = obs_main(["regress", str(bp), "--baseline", str(bp),
                       "--phases", "--label", "headline"])
        assert rc == 3
        assert "cannot combine" in capsys.readouterr().err


class TestPlatformGuard:
    def test_cpu_fallback_vs_tpu_baseline_is_an_error(self, tmp_path):
        """Satellite 1: a cpu-fallback artifact against a TPU baseline is
        a platform-mismatch ERROR, never a bogus verdict."""
        tpu = tmp_path / "BENCH_tpu.json"
        with open(tpu, "w") as f:
            json.dump({"parsed": {"metric": "m", "value": 5e6,
                                  "unit": "env-steps/s/chip (x, tpu)"}}, f)
        cpu = tmp_path / "BENCH_cpu.json"
        with open(cpu, "w") as f:
            json.dump({"parsed": {"metric": "m", "value": 4e4},
                       "extras": {"device_probe": {
                           "status": "failed", "reason": "init-hang",
                           "platform": "cpu", "cpu_fallback": True}}}, f)
        with pytest.raises(ValueError, match="platform mismatch"):
            regress.compare_files(str(cpu), str(tpu))
        rc = obs_main(["regress", str(cpu), "--baseline", str(tpu)])
        assert rc == 1

    def test_legacy_fallback_prose_reads_as_cpu(self):
        row = {"parsed": {"metric": "m", "value": 1.0,
                          "unit": "env-steps/s/chip (Pendulum, cpu, "
                                  "TPU-PATH-FAILED cpu fallback — see "
                                  "stderr)"}}
        assert regress.measurement_platform([row]) == "cpu"

    def test_same_platform_still_verdicts(self, tmp_path):
        a = tmp_path / "a.json"
        with open(a, "w") as f:
            json.dump({"parsed": {"metric": "m", "value": 100.0},
                       "platform": "cpu"}, f)
        v = regress.compare_files(str(a), str(a))
        assert v["verdict"] == "pass"
        assert v["platform"] == "cpu"


# ---------------------------------------------------------------------
# a REAL run end to end: cost model + ledger ride the records
# ---------------------------------------------------------------------

@pytest.fixture(scope="module")
def profiled_run(tmp_path_factory):
    import optax

    from estorch_tpu import ES, JaxAgent, MLPPolicy
    from estorch_tpu.envs import CartPole
    from estorch_tpu.obs import JsonlSink

    es = ES(
        MLPPolicy, JaxAgent, optax.adam,
        population_size=16, sigma=0.1, seed=0,
        policy_kwargs={"action_dim": 2, "hidden": (8,), "discrete": True},
        agent_kwargs={"env": CartPole(), "horizon": 25},
        optimizer_kwargs={"learning_rate": 0.05},
    )
    d = tmp_path_factory.mktemp("profiled_run")
    path = str(d / "run.jsonl")
    sink = JsonlSink(path)
    es.train(3, verbose=False, log_fn=sink)
    sink.close()
    return es, path


class TestRealRun:
    def test_cost_model_and_ledger_ride_the_records(self, profiled_run):
        es, path = profiled_run
        from estorch_tpu.obs import JsonlSink

        recs = JsonlSink.read(path)
        model = find_cost_model(recs)
        assert model is not None
        assert model["population"] == 16
        # CartPole MLP 4 -> 8 -> 2: kernels (4,8) and (8,2)
        assert sorted(map(tuple, model["matmul_shapes"])) == \
            [(4, 8), (8, 2)]
        events = collect_compile_events(recs)
        assert any(e["program"] == "generation_step" for e in events)
        assert all(e["compile_s"] >= 0 for e in events)
        # the model rides ONCE (first record), not every record
        assert sum(1 for r in recs if "cost_model" in r) == 1

    def test_profile_cli_on_real_run(self, profiled_run, capsys):
        _, path = profiled_run
        assert obs_main(["profile", path]) == 0
        out = capsys.readouterr().out
        assert "cpu_calibrated" in out
        assert "compiles" in out
        assert obs_main(["profile", path, "--json"]) == 0
        p = json.loads(capsys.readouterr().out)
        assert p["has_cost_model"] is True
        assert p["compile"]["n_events"] >= 1
        assert p["phases"]["device"]["mfu"] > 0

    def test_profile_cli_tolerates_truncated_tail(self, profiled_run,
                                                  tmp_path, capsys):
        _, path = profiled_run
        clone = tmp_path / "truncated.jsonl"
        with open(path) as f:
            text = f.read()
        with open(clone, "w") as f:
            f.write(text + '{"generation": 99, "env_ste')
        assert obs_main(["profile", str(clone)]) == 0
        err = capsys.readouterr().err
        assert "truncated" in err

    def test_profile_reads_real_manifest_device_list(self, profiled_run,
                                                     tmp_path, capsys):
        """The manifest schema (obs/manifest.py) stores ``devices`` as a
        LIST of per-device dicts — platform auto-detection must read it
        (a real manifest beside the jsonl used to crash the CLI)."""
        import shutil

        _, path = profiled_run
        d = tmp_path / "run_with_manifest"
        d.mkdir()
        shutil.copy(path, d / "run.jsonl")
        with open(d / "manifest.json", "w") as f:
            json.dump({"devices": [
                {"id": 0, "platform": "tpu", "kind": "TPU v5 lite",
                 "process_index": 0}]}, f)
        assert obs_main(["profile", str(d / "run.jsonl"), "--json"]) == 0
        p = json.loads(capsys.readouterr().out)
        assert p["platform"] == "tpu"
        assert p["basis"] == "tpu_v5e_bf16_peak"
        # cpu manifest keeps the measured-host basis
        with open(d / "manifest.json", "w") as f:
            json.dump({"devices": [
                {"id": 0, "platform": "cpu", "kind": "cpu",
                 "process_index": 0}]}, f)
        assert obs_main(["profile", str(d / "run.jsonl"), "--json"]) == 0
        p = json.loads(capsys.readouterr().out)
        assert p["platform"] == "cpu"
        assert p["basis"] == "cpu_calibrated"

    def test_trace_renders_compiles_lane(self, profiled_run):
        from estorch_tpu.obs import JsonlSink
        from estorch_tpu.obs.export.traceevent import (export_trace,
                                                       validate_trace)

        _, path = profiled_run
        recs = JsonlSink.read(path)
        trace = export_trace(recs)
        assert validate_trace(trace) == []
        compiles = [e for e in trace["traceEvents"]
                    if e.get("cat") == "compile"]
        assert any(e["name"] == "compile:generation_step"
                   for e in compiles)
        assert all(e["tid"] == 3 for e in compiles)

    def test_disabled_telemetry_skips_model_build(self, monkeypatch):
        """telemetry=False must not pay for the model at all — building
        it unravels the device param tree to host only for set_cost_model
        to discard it."""
        import torch

        from estorch_tpu import ES
        from estorch_tpu.algo import es as es_mod

        def boom(self):
            raise AssertionError("_build_cost_model called with "
                                 "telemetry disabled")

        monkeypatch.setattr(es_mod.ES, "_build_cost_model", boom)

        class P(torch.nn.Module):
            def __init__(self):
                super().__init__()
                self.lin = torch.nn.Linear(4, 2)

            def forward(self, x):
                return self.lin(x)

        class A:
            def rollout(self, policy):
                self.last_episode_steps = 1
                return 0.0

        es = ES(P, A, torch.optim.Adam, population_size=4, sigma=0.1,
                seed=0, optimizer_kwargs={"lr": 1e-2},
                table_size=1 << 10, telemetry=False)
        assert es.obs.cost_model is None

    def test_host_backend_cost_model(self):
        """The third engine family: torch policies get their matmul model
        from the live parameter tensors; horizon stays unknown (host
        agents own their rollout length) and no XLA compile events
        exist — `obs profile` notes both instead of crashing."""
        import torch

        from estorch_tpu import ES

        class P(torch.nn.Module):
            def __init__(self):
                super().__init__()
                self.net = torch.nn.Sequential(
                    torch.nn.Linear(4, 8), torch.nn.Tanh(),
                    torch.nn.Linear(8, 2))

            def forward(self, x):
                return self.net(x)

        class A:
            def rollout(self, policy):
                with torch.no_grad():
                    v = torch.nn.utils.parameters_to_vector(
                        policy.parameters())
                self.last_episode_steps = 1
                return -float((v ** 2).sum())

        es = ES(P, A, torch.optim.Adam, population_size=4, sigma=0.1,
                seed=0, optimizer_kwargs={"lr": 1e-2}, table_size=1 << 10)
        m = es.obs.cost_model
        assert sorted(map(tuple, m["matmul_shapes"])) == [(2, 8), (8, 4)]
        assert "env_steps_per_generation" not in m
        es.train(2, verbose=False)
        assert "cost_model" in es.history[0]
        assert "compile_events" not in es.history[0]
        p = profile_records(es.history, platform_roofline("cpu"))
        assert any("no compile events" in n for n in p["notes"])
        assert "eval" in p["phases"]
        es.engine.close()

    def test_sequence_policy_is_not_modelled(self):
        """A policy that states sequence facts gets NO cost model: "every
        2-D leaf is a matmul of one env-step" counts an untied embedding
        as a matmul and leaves out attention; `obs profile` then says it
        has no model instead of printing a wrong MFU."""
        import jax
        import loop_tiny
        import optax

        from estorch_tpu import ES, JaxAgent
        from estorch_tpu.envs import TokenScoreEnv
        from estorch_tpu.models import LoopedLM

        es = ES(policy=LoopedLM, agent=JaxAgent, optimizer=optax.adam,
                population_size=8, sigma=0.02, policy_kwargs=loop_tiny.TINY,
                agent_kwargs={"env": TokenScoreEnv(**loop_tiny.ENV)},
                optimizer_kwargs={"learning_rate": 1e-2}, shard_params=True,
                model_shards=1, low_rank=1, noise_mode="table",
                table_size=1 << 18, device=jax.devices()[:1])
        assert es._sequence_facts()["tokens_per_generation"] > 0
        assert es._build_cost_model() is None
        assert es.obs.cost_model is None
        es.train(1, verbose=False)
        assert "cost_model" not in es.history[0]
        p = profile_records(es.history, platform_roofline("cpu"))
        assert p["has_cost_model"] is False
        assert any("no cost_model" in n for n in p["notes"])

    def test_ledger_gauges_reach_the_registry(self, profiled_run):
        es, _ = profiled_run
        snap = es.obs.counters.snapshot()
        assert snap["compile_s_generation_step"] > 0
        assert snap["compile_time_s"] > 0
        # and they render as gauges in the exposition
        body = render_exposition(snap)
        assert "# TYPE estorch_compile_s_generation_step gauge" in body
        parse_exposition(body)  # must stay parseable with ledger gauges


# ---------------------------------------------------------------------
# bench.py: probe-gated platform decision + scratch hygiene (jax-free)
# ---------------------------------------------------------------------

@pytest.fixture()
def bench_mod():
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        import bench
    finally:
        sys.path.pop(0)
    return bench


def _fake_row(platform="tpu"):
    return {"rate": 1000.0, "platform": platform,
            "device_kind": "TPU v5 lite", "dtype": "bfloat16",
            "mfu": 2.5e-05, "mfu_basis": "tpu_v5e_bf16_peak",
            "phases": {"device": {"share": 0.5, "seconds": 1.0,
                                  "mfu": 2.5e-05}},
            "compile": {"n_events": 1},
            "peak_hbm_gb": 1.0, "peak_rss_gb": 1.0, "cfg": {}}


class _FakeDoctor:
    def __init__(self, verdict):
        self.verdict = verdict
        self.calls = []

    def check_device(self, timeout_s=20.0, platform=None):
        self.calls.append(platform)
        return dict(self.verdict)


_NO_TPU = {"status": "failed", "reason": "no-device", "elapsed_s": 2.0,
           "timeout_s": 60.0}
_TPU_OK = {"status": "ok", "platform": "tpu", "n_devices": 1,
           "elapsed_s": 2.0, "timeout_s": 60.0}


class TestBenchPlatformDecision:
    """The measured path has no fallback: no TPU, or a failed stage, is a
    non-zero exit with one line and no device metric; --cpu is the only
    way onto the CPU mesh."""

    def _patch(self, bench_mod, monkeypatch, probe, stage_result):
        calls = {"run_stage": 0}

        def fake_run_stage(cfg, timeout_s=480, force_cpu=False):
            calls["run_stage"] += 1
            assert not force_cpu  # the default bench never asks for cpu
            return stage_result

        doctor = _FakeDoctor(probe)
        monkeypatch.setattr(bench_mod, "_lock_or_warn", lambda *a, **k: None)
        monkeypatch.setattr(bench_mod, "_load_doctor", lambda: doctor)
        monkeypatch.setattr(bench_mod, "run_stage", fake_run_stage)
        monkeypatch.setattr(bench_mod, "measure_reference_style_baseline",
                            lambda budget_s=6.0: 100.0)
        return doctor, calls

    def test_no_tpu_exits_nonzero_and_prints_no_device_metric(
            self, bench_mod, monkeypatch, capsys):
        doctor, calls = self._patch(bench_mod, monkeypatch, _NO_TPU,
                                    _fake_row())
        with pytest.raises(SystemExit) as ei:
            bench_mod.main()
        assert ei.value.code not in (0, None)
        cap = capsys.readouterr()
        assert doctor.calls == ["tpu"]  # it asked for the CHIP
        assert calls["run_stage"] == 0  # nothing measured anywhere
        assert not [ln for ln in cap.out.splitlines() if ln.startswith("{")]
        assert "per_chip" not in cap.out + cap.err
        assert "mfu" not in cap.out
        why = [ln for ln in cap.err.splitlines() if "FAILED" in ln]
        assert len(why) == 1 and "no TPU" in why[0]

    def test_a_failed_stage_fails_the_run(self, bench_mod, monkeypatch,
                                          capsys):
        """Probe ok but a stage died: exit non-zero, no JSON line — a
        null row in an exit-0 artifact is how failures used to hide."""
        _, calls = self._patch(bench_mod, monkeypatch, _TPU_OK, None)
        with pytest.raises(SystemExit) as ei:
            bench_mod.main()
        assert ei.value.code not in (0, None)
        assert calls["run_stage"] == 1
        cap = capsys.readouterr()
        assert not [ln for ln in cap.out.splitlines() if ln.startswith("{")]
        assert "stage failed" in cap.err

    def test_on_the_chip_the_headline_names_its_device(self, bench_mod,
                                                       monkeypatch, capsys):
        self._patch(bench_mod, monkeypatch, _TPU_OK, _fake_row())
        bench_mod.main()
        out = capsys.readouterr().out
        row = json.loads([ln for ln in out.splitlines()
                          if ln.startswith("{")][-1])
        assert row["metric"] == "env_steps_per_sec_per_chip"
        assert row["platform"] == "tpu"
        assert row["extras"]["device_kind"] == "TPU v5 lite"
        assert row["extras"]["device_probe"]["status"] == "ok"
        assert "cpu_fallback" not in row["extras"]["device_probe"]

    def test_stage_drivers_cpu_is_explicit_only(self, bench_mod,
                                                monkeypatch):
        """--regress/--stage-ab/--obs-ab/--capture-baseline share one
        decision: --cpu skips the probe entirely, anything else needs the
        chip (a healthy CPU backend is "wrong-platform", not ok)."""
        bad = _FakeDoctor({"status": "failed", "reason": "wrong-platform",
                           "platform": "cpu", "elapsed_s": 3.0,
                           "timeout_s": 60.0})
        monkeypatch.setattr(bench_mod, "_load_doctor", lambda: bad)
        got = bench_mod._require_tpu_unless(True)
        assert got["requested_platform"] == "cpu" and bad.calls == []
        with pytest.raises(SystemExit) as ei:
            bench_mod._require_tpu_unless(False)
        assert ei.value.code not in (0, None) and bad.calls == ["tpu"]
        ok = _FakeDoctor(_TPU_OK)
        monkeypatch.setattr(bench_mod, "_load_doctor", lambda: ok)
        assert bench_mod._require_tpu_unless(False)["platform"] == "tpu"

    def test_cpu_request_still_measures_without_device_metrics(
            self, bench_mod):
        """--cpu (what the CPU tests use) measures on the CPU mesh; the
        row has no MFU and is never named per-chip.  Without --cpu the
        same call refuses instead of measuring the wrong silicon."""
        cfg = {"env": "pendulum", "hidden": [8, 8], "population": 16,
               "horizon": 5, "gens": 1, "telemetry": False}
        with pytest.raises(bench_mod.NoTpuError, match="'cpu'"):
            bench_mod.measure_one(cfg)
        row = bench_mod.measure_one(cfg, force_cpu=True)
        assert row["platform"] == "cpu" and row["rate"] > 0
        assert row["mfu"] is None and row["mfu_basis"] is None
        assert bench_mod._rate_metric(row["platform"]) \
            == "env_steps_per_sec_cpu_mesh"
        assert bench_mod._rate_metric("tpu") == "env_steps_per_sec_per_chip"


class TestBenchScratchHygiene:
    def test_stale_dirs_and_legacy_buffers_swept(self, bench_mod,
                                                 monkeypatch, tmp_path):
        """Satellite 2: scratch from CRASHED prior runs (per-pid workdirs
        with dead owners, legacy flat bench_stderr_/bench_hb_ files) is
        swept; the live process's scratch survives."""
        import tempfile as _tempfile

        monkeypatch.setattr(_tempfile, "gettempdir", lambda: str(tmp_path))
        root = tmp_path / "estorch_bench"
        monkeypatch.setattr(bench_mod, "_BENCH_TMP_ROOT", str(root))
        dead = subprocess.Popen(["sleep", "0"])
        dead.wait()
        os.makedirs(root / str(dead.pid))
        (root / str(dead.pid) / "fallback_stderr.log").write_text("boom")
        os.makedirs(root / str(os.getpid()))
        (tmp_path / f"bench_stderr_{dead.pid}.log").write_text("old")
        (tmp_path / f"bench_hb_{dead.pid}_123.json").write_text("{}")
        (tmp_path / f"bench_stderr_{os.getpid()}.log").write_text("live")
        bench_mod._sweep_stale_bench_dirs()
        assert not (root / str(dead.pid)).exists()
        assert (root / str(os.getpid())).exists()
        assert not (tmp_path / f"bench_stderr_{dead.pid}.log").exists()
        assert not (tmp_path / f"bench_hb_{dead.pid}_123.json").exists()
        assert (tmp_path / f"bench_stderr_{os.getpid()}.log").exists()

    def test_workdir_created_and_cleaned(self, bench_mod, monkeypatch,
                                         tmp_path):
        monkeypatch.setattr(bench_mod, "_BENCH_TMP_ROOT",
                            str(tmp_path / "estorch_bench"))
        d = bench_mod._bench_workdir()
        assert os.path.isdir(d)
        assert os.path.basename(d) == str(os.getpid())
        bench_mod._cleanup_bench_workdir()
        assert not os.path.isdir(d)
