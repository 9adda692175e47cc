"""Resilience subsystem (estorch_tpu/resilience, docs/resilience.md).

The headline claim under test: recovery is not merely "doesn't crash" —
it is *bit-exact*.  Because the noise stream is keyed on
``(key, generation)`` and every recovery path either restores full
population participation (worker respawn + same-generation slice retry)
or re-runs the generation from the pre-fault state (rejection, skip,
checkpoint resume), a run that survived worker SIGKILLs, NaN bursts, a
checkpoint-write crash, and a SIGKILL of the whole process must end with
``params_flat`` IDENTICAL to an uninterrupted run of the same seed.

Chaos events are scheduled (resilience/chaos.py), never raced, so every
test here is deterministic.
"""

import json
import os
import signal
import time

import numpy as np
import pytest
import torch

from estorch_tpu import ES
from estorch_tpu.resilience import CHAOS_ENV, ChaosPlan, Supervisor, run_resilient
from estorch_tpu.resilience import chaos as chaos_mod


class TinyMLP(torch.nn.Module):
    def __init__(self):
        super().__init__()
        self.net = torch.nn.Sequential(
            torch.nn.Linear(4, 8), torch.nn.Tanh(), torch.nn.Linear(8, 2)
        )

    def forward(self, x):
        return self.net(x)


class QuadAgent:
    """Deterministic fitness — recovery bit-exactness needs an oracle."""

    target = 0.1

    def rollout(self, policy):
        with torch.no_grad():
            vec = torch.nn.utils.parameters_to_vector(policy.parameters())
            reward = -float(((vec - self.target) ** 2).sum())
        self.last_episode_steps = 1
        return reward


class AlwaysDeadAgent:
    def rollout(self, policy):
        raise RuntimeError("env permanently dead")


def _make_es(worker_mode="process", agent=QuadAgent):
    return ES(TinyMLP, agent, torch.optim.Adam, population_size=8,
              sigma=0.05, seed=3, optimizer_kwargs={"lr": 0.05},
              table_size=1 << 12, worker_mode=worker_mode)


def _child_factory():
    """Supervisor child factory (spawned: a FRESH interpreter whose jax
    backend is not yet initialized — request the CPU before anything can
    reach for a chip)."""
    from estorch_tpu.utils import force_cpu_backend

    force_cpu_backend(1)
    return _make_es("process")


# ---------------------------------------------------------------------
# ChaosPlan mechanics
# ---------------------------------------------------------------------

class TestChaosPlan:
    def test_parse_roundtrip_and_indexing(self):
        plan = ChaosPlan.parse(json.dumps({"events": [
            {"kind": "kill_worker", "gen": 5, "worker": 1},
            {"kind": "nan_fitness", "gen": 9, "member": "all"},
        ]}))
        assert [e["kind"] for e in plan.events_at(5)] == ["kill_worker"]
        assert plan.events_at(9, "nan_fitness")
        assert plan.events_at(9, "kill_worker") == []
        again = ChaosPlan.parse(plan.to_json())
        assert [e["kind"] for e in again.events] == \
            [e["kind"] for e in plan.events]

    def test_unknown_kind_rejected(self):
        with pytest.raises(ValueError, match="unknown chaos event kind"):
            ChaosPlan([{"kind": "meteor", "gen": 1}])

    def test_fire_once_in_memory(self):
        plan = ChaosPlan([{"kind": "die", "gen": 1}])
        (ev,) = plan.events_at(1)
        assert plan.fire(ev) is True
        assert plan.fire(ev) is False

    def test_ledger_survives_process_restart(self, tmp_path):
        """A second plan instance (a restarted process) must see events
        the first instance fired — the property that stops a supervisor
        restart from replaying the SIGKILL that caused it forever."""
        ledger = str(tmp_path / "ledger")
        text = json.dumps({"events": [{"kind": "die", "gen": 12}],
                           "ledger": ledger})
        first = ChaosPlan.parse(text)
        assert first.fire(first.events_at(12)[0]) is True
        second = ChaosPlan.parse(text)  # "restarted" process
        assert second.fire(second.events_at(12)[0]) is False

    def test_generate_is_deterministic_in_seed(self):
        a = ChaosPlan.generate(seed=7, n_generations=50, kill_every=10,
                               n_workers=4, p_rollout_exc=0.2,
                               population_size=16)
        b = ChaosPlan.generate(seed=7, n_generations=50, kill_every=10,
                               n_workers=4, p_rollout_exc=0.2,
                               population_size=16)
        assert a.to_json() == b.to_json()
        assert len(a.events) >= 5  # the kills alone


# ---------------------------------------------------------------------
# ProcessPool: detection race, same-generation retry, respawn, close
# ---------------------------------------------------------------------

class TestProcessPoolRecovery:
    def test_dead_worker_bails_fast_and_slice_is_retried(self):
        """The satellite race: a worker that dies leaves nothing on its
        pipe — collection must notice in poll slices and retry its slice
        on the survivor, NOT block out the full generation timeout."""
        es = _make_es()
        try:
            es.train(1, n_proc=2, verbose=False)  # builds the pool
            pool = es.engine._proc_pool
            os.kill(pool._procs[0].pid, signal.SIGKILL)
            time.sleep(0.3)  # let the kill land
            offs = es.engine._pair_offsets(es.state)
            t0 = time.monotonic()
            fitness, _bc, _steps = pool.evaluate(
                es.state.params_flat, es.engine.sigma, offs,
                timeout_s=120.0, generation=int(es.state.generation))
            elapsed = time.monotonic() - t0
            # 120s timeout, dead pipe: the old code would sit out the full
            # timeout; slice-polling + retry must finish in seconds
            assert elapsed < 20.0
            # the survivor covered the dead worker's members: FULL
            # participation, and the values are the analytic truth
            assert np.isfinite(fitness).all()
            expected = np.array(
                [-float(((es.engine.member_theta(es.state, i) - 0.1) ** 2)
                        .sum()) for i in range(8)], np.float32)
            np.testing.assert_allclose(fitness, expected, rtol=1e-4,
                                       atol=1e-5)
        finally:
            es.engine.close()

    def test_chaos_kill_recovers_and_respawns_bit_exact(self, monkeypatch):
        """Worker kill at gen 1: the generation retries the slice (full
        participation, n_failed 0), the next generation respawns the
        worker, and the trained parameters equal a run never faulted."""
        clean = _make_es()
        try:
            clean.train(3, n_proc=2, verbose=False)
            clean_params = np.asarray(clean.state.params_flat).copy()
        finally:
            clean.engine.close()

        monkeypatch.setenv(CHAOS_ENV, json.dumps({"events": [
            {"kind": "kill_worker", "gen": 1, "worker": 0}]}))
        chaos_mod.reset_cache()
        es = _make_es()
        try:
            es.train(3, n_proc=2, verbose=False)
            assert [r["n_failed"] for r in es.history] == [0, 0, 0]
            pool = es.engine._proc_pool
            assert all(p.is_alive() for p in pool._procs)  # respawned
            assert es.obs.counters.get("workers_respawned") >= 1
            assert es.obs.counters.get("chaos_worker_kills") == 1
            assert es.obs.counters.get("members_retried") == 4
            np.testing.assert_array_equal(
                np.asarray(es.state.params_flat), clean_params)
        finally:
            es.engine.close()

    def test_close_reclaims_dead_worker_pipes_and_joins_respawned(
            self, monkeypatch):
        monkeypatch.setenv(CHAOS_ENV, json.dumps({"events": [
            {"kind": "kill_worker", "gen": 0, "worker": 1}]}))
        chaos_mod.reset_cache()
        es = _make_es()
        es.train(2, n_proc=2, verbose=False)  # gen 0 kill, gen 1 respawn
        pool = es.engine._proc_pool
        assert pool._retired, "respawn should have parked the corpse"
        everything = [*pool._procs, *pool._retired]
        pool.close()
        assert all(c.closed for c in pool._conns)
        assert all(not p.is_alive() for p in everything)
        assert pool._retired == []
        es.engine.close()

    def test_rollout_exc_in_fork_worker_is_nan_not_crash(self, monkeypatch):
        monkeypatch.setenv(CHAOS_ENV, json.dumps({"events": [
            {"kind": "rollout_exc", "gen": 0, "member": 5}]}))
        chaos_mod.reset_cache()
        es = _make_es()
        try:
            es.train(1, n_proc=2, verbose=False)
            assert es.history[0]["n_failed"] == 1
        finally:
            es.engine.close()


# ---------------------------------------------------------------------
# update anomaly guards (ES.train rejection policy)
# ---------------------------------------------------------------------

class TestAnomalyGuards:
    def test_nan_update_rejected_then_bit_exact(self, monkeypatch):
        """An injected non-finite update is rejected — previous state
        restored, counted, flight-recorded — and the re-run proceeds from
        the pre-fault state, ending bit-identical to a clean run."""
        clean = _make_es("thread")
        clean.train(4, verbose=False)
        clean_params = np.asarray(clean.state.params_flat).copy()

        monkeypatch.setenv(CHAOS_ENV, json.dumps({"events": [
            {"kind": "nan_update", "gen": 2}]}))
        chaos_mod.reset_cache()
        es = _make_es("thread")
        es.train(4, verbose=False)
        assert es.generation == 4  # the rejected attempt did not count
        assert es.obs.counters.get("generations_rejected") == 1
        assert any(e["name"] == "generation_rejected"
                   for e in es.obs.recorder.events())
        assert np.isfinite(np.asarray(es.state.params_flat)).all()
        np.testing.assert_array_equal(
            np.asarray(es.state.params_flat), clean_params)

    def test_nan_fitness_burst_rejected_then_bit_exact(self, monkeypatch):
        """A full-population NaN burst collapses the generation (<2
        valid); rejection + deterministic re-run keeps the trajectory."""
        clean = _make_es("thread")
        clean.train(3, verbose=False)
        clean_params = np.asarray(clean.state.params_flat).copy()

        monkeypatch.setenv(CHAOS_ENV, json.dumps({"events": [
            {"kind": "nan_fitness", "gen": 1, "member": "all"}]}))
        chaos_mod.reset_cache()
        es = _make_es("thread")
        es.train(3, verbose=False)
        assert es.generation == 3
        assert es.obs.counters.get("generations_rejected") == 1
        np.testing.assert_array_equal(
            np.asarray(es.state.params_flat), clean_params)

    def test_persistent_collapse_raises_with_state_intact(self):
        es = _make_es("thread", agent=AlwaysDeadAgent)
        before = np.asarray(es.state.params_flat).copy()
        with pytest.raises(RuntimeError, match="valid fitness"):
            es.train(1, verbose=False)
        # bounded retries: default cap rejected 4 attempts, then raised
        assert es.obs.counters.get("generations_rejected") == 4
        assert es.generation == 0
        np.testing.assert_array_equal(
            np.asarray(es.state.params_flat), before)


# ---------------------------------------------------------------------
# run_resilient: in-process skip/rollback
# ---------------------------------------------------------------------

class TestRunResilient:
    def test_checkpoint_write_crash_skipped_and_bit_exact(
            self, tmp_path, monkeypatch):
        """A crash INSIDE a checkpoint save rolls the finished generation
        back (it re-runs deterministically and re-saves); the crashed
        directory is not restorable and latest() skips past it."""
        from estorch_tpu.utils.checkpoint import PeriodicCheckpointer

        clean = _make_es("thread")
        clean.train(4, verbose=False)
        clean_params = np.asarray(clean.state.params_flat).copy()

        # every=2 saves after record gens 1 and 3 (es.generation 2 and 4);
        # the crash fires during the first of those saves
        monkeypatch.setenv(CHAOS_ENV, json.dumps({"events": [
            {"kind": "ckpt_crash", "gen": 2}]}))
        chaos_mod.reset_cache()
        es = _make_es("thread")
        ck = PeriodicCheckpointer(es, str(tmp_path / "cks"), every=2)
        run_resilient(es, 4, checkpointer=ck)
        assert es.generation == 4
        assert es.obs.counters.get("generations_skipped") == 1
        assert any(e["name"] == "generation_skipped"
                   for e in es.obs.recorder.events())
        np.testing.assert_array_equal(
            np.asarray(es.state.params_flat), clean_params)
        # the re-run re-saved the same directory, now finalized
        latest = ck.latest()
        assert latest is not None and latest.endswith("gen_00000003")
        assert os.path.isdir(os.path.join(str(tmp_path / "cks"),
                                          "gen_00000001", "state"))
        # exactly 4 records, no duplicate from the rolled-back attempt
        assert [r["generation"] for r in es.history] == [0, 1, 2, 3]

    def test_persistent_failure_reraises(self):
        es = _make_es("thread", agent=AlwaysDeadAgent)
        with pytest.raises(RuntimeError, match="valid fitness"):
            run_resilient(es, 2, max_consecutive_skips=1)


# ---------------------------------------------------------------------
# Supervisor: the end-to-end chaos demo (acceptance criterion)
# ---------------------------------------------------------------------

class TestSupervisor:
    def test_chaos_run_supervised_to_bit_exact_completion(
            self, tmp_path, monkeypatch, capsys):
        """THE deterministic chaos demo: worker SIGKILL at gen 5, a full
        NaN-fitness burst at gen 9, a checkpoint-write crash at gen 8's
        save, and SIGKILL of the whole training process at gen 12 — the
        Supervisor drives the run to generation 16, and the final
        params_flat is BIT-IDENTICAL to an uninterrupted run of the same
        seed on the host backend."""
        clean = _make_es("process")
        try:
            clean.train(16, n_proc=2, verbose=False)
            clean_params = np.asarray(clean.state.params_flat).copy()
        finally:
            clean.engine.close()

        root = tmp_path / "run"
        plan = {"events": [
            {"kind": "kill_worker", "gen": 5, "worker": 0},
            {"kind": "ckpt_crash", "gen": 8},
            {"kind": "nan_fitness", "gen": 9, "member": "all"},
            {"kind": "die", "gen": 12},
        ], "ledger": str(tmp_path / "chaos_ledger")}
        monkeypatch.setenv(CHAOS_ENV, json.dumps(plan))
        chaos_mod.reset_cache()

        sup = Supervisor(_child_factory, str(root), target_generation=16,
                         every=4, n_proc=2, max_restarts=3,
                         backoff_s=0.1, poll_s=0.25,
                         startup_grace_s=300.0)
        res = sup.run()
        assert res["ok"], f"supervisor failed: {res}"
        assert len(res["restarts"]) == 1  # exactly the gen-12 SIGKILL
        assert res["restarts"][0]["exitcode"] == -signal.SIGKILL

        # resume is bit-exact: restore the final checkpoint and compare
        from estorch_tpu.utils.checkpoint import restore_checkpoint

        es = _make_es("process")
        try:
            restore_checkpoint(es, res["checkpoint"])
            assert es.generation == 16
            np.testing.assert_array_equal(
                np.asarray(es.state.params_flat), clean_params)
        finally:
            es.engine.close()

        # restart provenance + cross-restart counters in the manifest:
        # the SIGKILLed child's rejected/skipped counters survive via its
        # last heartbeat
        with open(root / "manifest.json") as f:
            manifest = json.load(f)
        resil = manifest["resilience"]
        assert resil["completed"] is True
        assert resil["restart_count"] == 1
        assert resil["counters"]["generations_rejected"] >= 1  # NaN burst
        assert resil["counters"]["generations_skipped"] >= 1  # ckpt crash
        assert resil["counters"]["workers_respawned"] >= 1  # gen-5 kill

        # every trained generation logged exactly once across both child
        # processes (the rolled-back attempts never reached the sink)
        from estorch_tpu.obs.summarize import load_records

        records = load_records(str(root / "run.jsonl"))
        assert [r["generation"] for r in records] == list(range(16))
        assert all(r["n_failed"] == 0 for r in records)  # full participation

        # `python -m estorch_tpu.obs summarize` surfaces the chaos run's
        # rejection + restart counters (acceptance criterion)
        from estorch_tpu.obs.__main__ import main as obs_main

        rc = obs_main(["summarize", str(root / "run.jsonl")])
        out = capsys.readouterr().out
        assert rc == 0
        assert "generations_rejected" in out
        assert "restarts         1" in out

    @pytest.mark.slow
    def test_wedged_child_killed_by_heartbeat_watchdog_and_resumed(
            self, tmp_path, monkeypatch):
        """A child that stops beating (chaos wedge: a long silent sleep)
        is killed by the staleness watchdog and the run resumes from the
        last checkpoint to the same final parameters.  Slow-marked: two
        child spawns + the staleness detection window (~80s); the
        non-slow acceptance test above already exercises the supervisor's
        death-detection restart path."""
        clean = _make_es("process")
        try:
            clean.train(4, n_proc=2, verbose=False)
            clean_params = np.asarray(clean.state.params_flat).copy()
        finally:
            clean.engine.close()

        root = tmp_path / "run"
        plan = {"events": [
            {"kind": "wedge", "gen": 2, "sleep_s": 300.0},
        ], "ledger": str(tmp_path / "chaos_ledger")}
        monkeypatch.setenv(CHAOS_ENV, json.dumps(plan))
        chaos_mod.reset_cache()

        # stale_after must exceed the slowest legitimate inter-beat gap
        # (child-side setup IO on this loaded 1-core box) while staying
        # far below the 300s wedge sleep it exists to catch
        sup = Supervisor(_child_factory, str(root), target_generation=4,
                         every=1, n_proc=2, max_restarts=2,
                         backoff_s=0.1, poll_s=0.25,
                         stale_after_s=10.0, startup_grace_s=300.0)
        res = sup.run()
        assert res["ok"], f"supervisor failed: {res}"
        assert len(res["restarts"]) == 1
        assert "stale" in res["restarts"][0]["reason"]

        from estorch_tpu.utils.checkpoint import restore_checkpoint

        es = _make_es("process")
        try:
            restore_checkpoint(es, res["checkpoint"])
            assert es.generation == 4
            np.testing.assert_array_equal(
                np.asarray(es.state.params_flat), clean_params)
        finally:
            es.engine.close()


# ---------------------------------------------------------------------
# deterministic interleaving harness (resilience/interleave.py)
# ---------------------------------------------------------------------

class _Counter:
    """Shared state with a deliberately torn read-modify-write."""

    def __init__(self):
        self.n = 0


def _racy_workers(box, per_worker=20):
    def worker():
        for _ in range(per_worker):
            cur = box.n
            cur = cur + 1
            box.n = cur
    return [worker, worker]


class TestInterleaver:
    def test_same_seed_is_bit_identical(self):
        """The acceptance criterion: a seeded run replays exactly —
        same schedule, same switches, same final (racy) state."""
        from estorch_tpu.resilience import run_interleaved

        runs = []
        for _ in range(2):
            box = _Counter()
            runs.append((run_interleaved(_racy_workers(box), seed=1234),
                         box.n))
        (r1, n1), (r2, n2) = runs
        assert r1.replays(r2)
        assert r1.schedule == r2.schedule
        assert r1.switches == r2.switches
        assert n1 == n2

    def test_a_seed_exists_that_loses_updates(self):
        """The harness's reason to exist: some seed interleaves the
        read-modify-write so updates vanish — deterministically."""
        from estorch_tpu.resilience import run_interleaved

        losing = None
        for seed in range(32):
            box = _Counter()
            run_interleaved(_racy_workers(box), seed=seed)
            if box.n < 40:
                losing = seed
                break
        assert losing is not None, "no seed exposed the race"
        # the losing seed is a reproducer: same seed, same loss
        box_a, box_b = _Counter(), _Counter()
        ra = run_interleaved(_racy_workers(box_a), seed=losing)
        rb = run_interleaved(_racy_workers(box_b), seed=losing)
        assert ra.replays(rb)
        assert box_a.n == box_b.n < 40

    def test_different_seeds_differ(self):
        from estorch_tpu.resilience import run_interleaved

        schedules = set()
        for seed in range(6):
            box = _Counter()
            schedules.add(
                run_interleaved(_racy_workers(box), seed=seed).schedule)
        assert len(schedules) > 1

    def test_cooplock_fixes_every_seed(self):
        """The fix side: the SAME seeds that lose updates bare are
        correct under CoopLock, and stay deterministic."""
        from estorch_tpu.resilience import CoopLock, Interleaver

        for seed in range(8):
            box = _Counter()
            holder = []

            def worker():
                for _ in range(20):
                    with holder[0]:
                        cur = box.n
                        cur = cur + 1
                        box.n = cur

            itl = Interleaver([worker, worker], seed=seed)
            holder.append(CoopLock(itl))
            itl.run()
            assert box.n == 40, f"seed {seed} lost updates under lock"

    def test_values_and_errors_propagate(self):
        from estorch_tpu.resilience import run_interleaved

        res = run_interleaved([lambda: "a", lambda: "b"], seed=0)
        assert res.values == ("a", "b")

        def boom():
            raise ValueError("torn")

        with pytest.raises(ValueError, match="torn"):
            run_interleaved([boom, lambda: None], seed=0)

    def test_runaway_loop_fails_fast(self):
        from estorch_tpu.resilience import DeadlockError, run_interleaved

        def spin():
            while True:
                pass

        with pytest.raises(DeadlockError):
            run_interleaved([spin, spin], seed=0, max_steps=200)
