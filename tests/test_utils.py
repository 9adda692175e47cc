"""Aux subsystems: checkpoint/resume exactness, metrics writers, fault
tolerance, profiler timing (SURVEY.md §5)."""

import os

import numpy as np
import optax
import pytest
import torch

from estorch_tpu import ES, NSRA_ES, JaxAgent, MLPPolicy
from estorch_tpu.envs import CartPole
from estorch_tpu.utils import (
    JsonlWriter,
    MultiWriter,
    PeriodicCheckpointer,
    mask_and_renormalize,
    rank_weights_with_failures,
    restore_checkpoint,
    save_checkpoint,
    valid_mask,
)


def _device_es(**over):
    kw = dict(
        policy=MLPPolicy,
        agent=JaxAgent,
        optimizer=optax.adam,
        population_size=16,
        sigma=0.1,
        seed=3,
        policy_kwargs={"action_dim": 2, "hidden": (8,)},
        agent_kwargs={"env": CartPole(), "horizon": 50},
        optimizer_kwargs={"learning_rate": 1e-2},
        table_size=1 << 16,
    )
    kw.update(over)
    cls = kw.pop("cls", ES)
    return cls(**kw)


class TestCheckpointDevice:
    def test_resume_is_exact(self, tmp_path):
        """Train 4; checkpoint at 2; restore into a fresh object; resume 2
        more — params must be IDENTICAL to the uninterrupted run."""
        ref = _device_es()
        ref.train(4, verbose=False)

        a = _device_es()
        a.train(2, verbose=False)
        save_checkpoint(a, str(tmp_path / "ck"))

        b = _device_es()
        restore_checkpoint(b, str(tmp_path / "ck"))
        assert b.generation == 2
        b.train(2, verbose=False)

        np.testing.assert_array_equal(
            np.asarray(ref.state.params_flat), np.asarray(b.state.params_flat)
        )
        assert int(b.state.generation) == 4

    def test_history_survives_resume(self, tmp_path):
        """Per-generation records must come back (ADVICE round 1): a resumed
        run's logs continue from the interruption point, not from scratch."""
        a = _device_es()
        a.train(3, verbose=False)
        save_checkpoint(a, str(tmp_path / "ck"))
        b = _device_es()
        restore_checkpoint(b, str(tmp_path / "ck"))
        assert len(b.history) == 3
        assert [r["generation"] for r in b.history] == [0, 1, 2]
        assert b.history[2]["reward_max"] == a.history[2]["reward_max"]
        b.train(1, verbose=False)
        assert [r["generation"] for r in b.history] == [0, 1, 2, 3]

    def test_best_snapshot_restored(self, tmp_path):
        a = _device_es()
        a.train(3, verbose=False)
        save_checkpoint(a, str(tmp_path / "ck"))
        b = _device_es()
        restore_checkpoint(b, str(tmp_path / "ck"))
        assert b.best_reward == a.best_reward
        np.testing.assert_array_equal(b._best_flat, a._best_flat)

    def test_nsra_archive_and_weight_restored(self, tmp_path):
        a = _device_es(cls=NSRA_ES, meta_population_size=2, k=3, weight=0.6)
        a.train(3, verbose=False)
        save_checkpoint(a, str(tmp_path / "ck"))

        b = _device_es(cls=NSRA_ES, meta_population_size=2, k=3, weight=0.6)
        restore_checkpoint(b, str(tmp_path / "ck"))
        assert len(b.archive) == len(a.archive)
        np.testing.assert_allclose(b.archive.bcs, a.archive.bcs)
        assert b.weight == a.weight
        assert b._stagnation == a._stagnation
        for sa, sb in zip(a.meta_states, b.meta_states):
            np.testing.assert_array_equal(
                np.asarray(sa.params_flat), np.asarray(sb.params_flat)
            )

    @pytest.mark.slow
    def test_novelty_resume_is_exact(self, tmp_path):
        """Regression: the meta-selection RNG position must be checkpointed —
        without it the resumed run picks different meta-individuals."""
        def mk():
            return _device_es(cls=NSRA_ES, meta_population_size=2, k=3, weight=0.8)

        ref = mk()
        ref.train(5, verbose=False)

        a = mk()
        a.train(3, verbose=False)
        save_checkpoint(a, str(tmp_path / "ck"))
        b = mk()
        restore_checkpoint(b, str(tmp_path / "ck"))
        b.train(2, verbose=False)

        np.testing.assert_array_equal(
            np.asarray(ref.state.params_flat), np.asarray(b.state.params_flat)
        )
        # history is restored too, so b's records 3: are the post-resume ones
        assert [r["meta_index"] for r in ref.history[3:]] == [
            r["meta_index"] for r in b.history[3:]
        ]

    def test_backend_mismatch_rejected(self, tmp_path):
        a = _device_es()
        a.train(1, verbose=False)
        save_checkpoint(a, str(tmp_path / "ck"))

        class P(torch.nn.Module):
            def __init__(self):
                super().__init__()
                self.l = torch.nn.Linear(4, 2)

            def forward(self, x):
                return self.l(x)

        class A:
            def rollout(self, policy):
                return 0.0

        host = ES(P, A, torch.optim.Adam, population_size=16,
                  optimizer_kwargs={"lr": 1e-2}, table_size=1 << 14)
        with pytest.raises(Exception):
            restore_checkpoint(host, str(tmp_path / "ck"))


class TestCheckpointHost:
    def _host_es(self):
        class P(torch.nn.Module):
            def __init__(self):
                super().__init__()
                self.l = torch.nn.Linear(4, 2)

            def forward(self, x):
                return self.l(x)

        class A:
            def rollout(self, policy):
                with torch.no_grad():
                    v = torch.nn.utils.parameters_to_vector(policy.parameters())
                    return -float(((v - 0.1) ** 2).sum())

        return ES(P, A, torch.optim.Adam, population_size=16, sigma=0.05,
                  seed=1, optimizer_kwargs={"lr": 0.05}, table_size=1 << 14)

    def test_host_resume_is_exact(self, tmp_path):
        ref = self._host_es()
        ref.train(4, verbose=False)

        a = self._host_es()
        a.train(2, verbose=False)
        save_checkpoint(a, str(tmp_path / "ck"))
        b = self._host_es()
        restore_checkpoint(b, str(tmp_path / "ck"))
        b.train(2, verbose=False)
        np.testing.assert_allclose(
            ref.state.params_flat, b.state.params_flat, rtol=1e-6, atol=1e-7
        )


class TestCheckpointPooled:
    def test_pooled_resume_is_exact(self, tmp_path):
        from estorch_tpu import PooledAgent

        def mk():
            return _device_es(
                agent=PooledAgent,
                agent_kwargs={"env_name": "cartpole", "horizon": 40},
                seed=2,
                table_size=1 << 14,
            )

        a = mk()
        a.train(2, verbose=False)
        save_checkpoint(a, str(tmp_path / "ck"))
        b = mk()
        restore_checkpoint(b, str(tmp_path / "ck"))
        assert b.generation == 2
        np.testing.assert_array_equal(
            np.asarray(a.state.params_flat), np.asarray(b.state.params_flat)
        )
        b.train(1, verbose=False)  # must run cleanly from the restored state
        assert b.generation == 3


class TestPeriodicCheckpointer:
    def test_every_k_and_gc(self, tmp_path):
        es = _device_es()
        ck = PeriodicCheckpointer(es, str(tmp_path / "cks"), every=2, max_to_keep=2)
        es.train(6, log_fn=ck.on_record)
        kept = sorted(os.listdir(tmp_path / "cks"))
        assert len(kept) == 2  # gens 1,3,5 saved; oldest GC'd
        assert ck.latest().endswith(kept[-1])


class TestMetricsWriters:
    def test_jsonl_roundtrip(self, tmp_path):
        path = str(tmp_path / "log.jsonl")
        w = JsonlWriter(path)
        es = _device_es()
        es.train(3, log_fn=w)
        w.close()
        recs = JsonlWriter.read(path)
        assert len(recs) == 3
        assert recs[0]["generation"] == 0
        assert "env_steps_per_sec" in recs[-1]

    def test_multi_writer_fans_out(self, tmp_path):
        seen = []
        w = MultiWriter([seen.append, JsonlWriter(str(tmp_path / "l.jsonl"))])
        w({"generation": 0, "reward_max": 1.0, "reward_mean": 0.5,
           "env_steps_per_sec": 100.0})
        assert len(seen) == 1
        w.close()


class TestFaultTolerance:
    def test_valid_mask(self):
        f = np.array([1.0, np.nan, 3.0, np.inf])
        np.testing.assert_array_equal(valid_mask(f), [True, False, True, False])

    def test_mask_and_renormalize_unbiased_scale(self):
        w = np.array([0.5, -0.5, 0.25, -0.25], np.float32)
        valid = np.array([True, True, True, False])
        out = mask_and_renormalize(w, valid)
        assert out[3] == 0.0
        np.testing.assert_allclose(out[:3], w[:3] * (4 / 3), rtol=1e-6)

    def test_too_few_survivors_raises(self):
        with pytest.raises(RuntimeError, match="valid fitness"):
            mask_and_renormalize(np.ones(4, np.float32), np.array([True] + [False] * 3))

    def test_rank_weights_with_failures(self):
        f = np.array([3.0, np.nan, 1.0, 2.0], np.float32)
        w = rank_weights_with_failures(f)
        assert w[1] == 0.0
        # valid members ranked among themselves, renormalized by 4/3
        from estorch_tpu.ops import centered_rank_np

        expected = np.zeros(4, np.float32)
        expected[[0, 2, 3]] = centered_rank_np(f[[0, 2, 3]]) * (4 / 3)
        np.testing.assert_allclose(w, expected, rtol=1e-6)

    def test_host_engine_survives_worker_exception(self):
        class P(torch.nn.Module):
            def __init__(self):
                super().__init__()
                self.l = torch.nn.Linear(2, 1)

            def forward(self, x):
                return self.l(x)

        class FlakyAgent:
            calls = 0

            def rollout(self, policy):
                FlakyAgent.calls += 1
                if FlakyAgent.calls % 5 == 0:
                    raise RuntimeError("env crashed")
                with torch.no_grad():
                    v = torch.nn.utils.parameters_to_vector(policy.parameters())
                    return -float((v**2).sum())

        es = ES(P, FlakyAgent, torch.optim.Adam, population_size=16,
                optimizer_kwargs={"lr": 1e-2}, table_size=1 << 12)
        es.train(2, verbose=False)  # must not raise
        assert len(es.history) == 2
        # failed members are NaN-masked: stats stay finite, failures counted,
        # and best tracking still works
        rec = es.history[-1]
        assert np.isfinite(rec["reward_mean"])
        assert np.isfinite(rec["reward_max"])
        assert rec["n_failed"] > 0
        assert np.isfinite(es.best_reward)
        assert es._best_flat is not None

    def test_novelty_weights_drop_failed_members(self):
        """A NaN-fitness member must get zero weight, not the top rank."""
        from estorch_tpu import NS_ES, JaxAgent, MLPPolicy
        from estorch_tpu.envs import CartPole
        import optax

        es = NS_ES(
            MLPPolicy, JaxAgent, optax.adam, population_size=16, sigma=0.1,
            seed=0, meta_population_size=2, k=3,
            policy_kwargs={"action_dim": 2, "hidden": (8,)},
            agent_kwargs={"env": CartPole(), "horizon": 20},
            optimizer_kwargs={"learning_rate": 1e-2}, table_size=1 << 14,
        )
        fitness = np.array([1.0, np.nan, 3.0, 2.0] * 4, np.float32)
        novelty = np.linspace(0, 1, 16).astype(np.float32)
        w = es._weights_with_failures(fitness, novelty)
        failed = np.isnan(fitness)
        assert np.all(w[failed] == 0.0)
        assert np.isfinite(w).all()
        assert abs(float(w.sum())) < 1e-4  # renormalized centered ranks still ~sum 0


class TestProfiler:
    @pytest.mark.slow
    def test_trace_writes_profile(self, tmp_path):
        from estorch_tpu.utils import annotate, trace

        es = _device_es()
        es.train(1, verbose=False)  # compile outside the trace
        with trace(str(tmp_path / "prof")):
            with annotate("generation"):
                es.train(1, verbose=False)
        written = list((tmp_path / "prof").rglob("*"))
        assert any(p.is_file() for p in written), "no trace files emitted"


class TestCompilationCache:
    """One compile cache, placeable: JAX_COMPILATION_CACHE_DIR wins and no
    code replaces it; unset, the default is one fixed directory in the
    checkout.  Each case runs in a child — the variable is read when jax
    is imported, and this process's cache must stay where conftest put it.
    """

    _CHILD = r"""
import json, os, sys
import jax, jax.numpy as jnp
from estorch_tpu.utils import backend, enable_compilation_cache
from estorch_tpu.utils.backend import (current_compilation_cache_dir,
                                       default_compilation_cache_dir)
explicit = sys.argv[1] or None
if sys.argv[3]:  # stand-in for the in-checkout default: keep the suite's
    backend.default_compilation_cache_dir = lambda: sys.argv[3]  # run out
got = enable_compilation_cache(explicit, min_compile_time_s=0.0)
jax.jit(lambda x: (x @ x.T).sum())(jnp.ones((64, 64))).block_until_ready()
# a bundle that packs warmth installs into the SAME directory
from estorch_tpu.serve.warm import install_warmth
warm = {"format": "xla_cache", "jax_version": jax.__version__,
        "platform": jax.default_backend(),
        "device_count": len(jax.devices()), "entries": {}}
status = install_warmth(sys.argv[2], {"warm": warm})
print(json.dumps({"returned": got, "live": current_compilation_cache_dir(),
                  "default": default_compilation_cache_dir(),
                  "warm_dir": status["cache_dir"],
                  "entries": len(os.listdir(got))}))
"""

    def _child(self, tmp_path, explicit="", default="", **env_over):
        import json
        import os
        import subprocess
        import sys

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": repo}
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        env.update(env_over)
        r = subprocess.run(
            [sys.executable, "-c", self._CHILD, explicit, str(tmp_path),
             default],
            capture_output=True, text=True, env=env, cwd=str(tmp_path),
            timeout=120)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    def test_environment_places_the_cache_and_code_leaves_it(self, tmp_path):
        placed = str(tmp_path / "placed")
        out = self._child(tmp_path, explicit=str(tmp_path / "ignored"),
                          JAX_COMPILATION_CACHE_DIR=placed)
        assert out["returned"] == out["live"] == out["warm_dir"] == placed
        assert out["entries"] > 0, "no cache entries written"
        assert not (tmp_path / "ignored").exists()

    def test_explicit_directory_when_the_environment_is_silent(
            self, tmp_path):
        want = str(tmp_path / "xla")
        out = self._child(tmp_path, explicit=want)
        assert out["returned"] == out["live"] == out["warm_dir"] == want
        assert out["entries"] > 0

    def test_default_is_one_fixed_git_ignored_path_in_the_checkout(
            self, tmp_path):
        import os

        from estorch_tpu.utils.backend import default_compilation_cache_dir

        # unset and nothing passed: the default directory, whatever it is
        stand_in = str(tmp_path / "default")
        out = self._child(tmp_path, default=stand_in)
        assert out["returned"] == out["live"] == out["warm_dir"] == stand_in

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        d = default_compilation_cache_dir()
        assert d == os.path.join(repo, ".xla_cache")
        with open(os.path.join(repo, ".gitignore")) as f:
            assert ".xla_cache/" in f.read().split()
        # nothing on the warm-install path makes a directory of its own
        with open(os.path.join(repo, "estorch_tpu", "serve",
                               "warm.py")) as f:
            assert "mkdtemp" not in f.read()


class TestAsyncCheckpoint:
    @pytest.mark.slow
    def test_async_save_restores_bit_exact(self, tmp_path):
        import optax

        from estorch_tpu import ES, JaxAgent, MLPPolicy
        from estorch_tpu.envs import CartPole
        from estorch_tpu.utils import restore_checkpoint, save_checkpoint

        def build():
            return ES(
                policy=MLPPolicy, agent=JaxAgent, optimizer=optax.adam,
                population_size=16, sigma=0.1,
                policy_kwargs={"action_dim": 2, "hidden": (8,),
                               "discrete": True},
                agent_kwargs={"env": CartPole(), "horizon": 32},
                optimizer_kwargs={"learning_rate": 1e-2}, seed=3,
            )

        es = build()
        es.train(2, verbose=False)
        handle = save_checkpoint(es, tmp_path / "ck", asynchronous=True)
        # training continues while the write drains in the background —
        # the save must snapshot the state AT save time, not pick up these
        # later updates
        es.train(2, verbose=False)
        handle.wait()
        handle.wait()  # idempotent

        es2 = build()
        restore_checkpoint(es2, tmp_path / "ck")
        assert es2.generation == 2
        es_ref = build()
        es_ref.train(2, verbose=False)
        np.testing.assert_array_equal(
            np.asarray(es2.state.params_flat),
            np.asarray(es_ref.state.params_flat),
        )

    def test_periodic_async_resume_exact(self, tmp_path):
        from estorch_tpu.utils import PeriodicCheckpointer, restore_checkpoint

        es = _device_es()
        ck = PeriodicCheckpointer(es, str(tmp_path / "cks"), every=2,
                                  max_to_keep=2, asynchronous=True)
        es.train(4, log_fn=ck.on_record)
        ck.wait()
        b = _device_es()
        restore_checkpoint(b, ck.latest())
        assert b.generation == 4
        np.testing.assert_array_equal(
            np.asarray(es.state.params_flat), np.asarray(b.state.params_flat)
        )

    def test_restore_unfinalized_dir_clear_error(self, tmp_path):
        """restore_checkpoint on an in-flight/crash-truncated async save
        (meta.json present, no finalized state/) must raise a clear
        'not finalized' error BEFORE handing the path to Orbax
        (round-3 ADVICE #2)."""
        import shutil

        import pytest

        from estorch_tpu.utils import restore_checkpoint, save_checkpoint

        es = _device_es()
        es.train(1, verbose=False)
        save_checkpoint(es, str(tmp_path / "ck"))
        # simulate the crash-truncated async save: meta/history written,
        # Orbax payload never finalized
        shutil.rmtree(tmp_path / "ck" / "state")
        b = _device_es()
        with pytest.raises(ValueError, match="no finalized state"):
            restore_checkpoint(b, str(tmp_path / "ck"))

    def test_latest_skips_unfinalized_dir(self, tmp_path):
        """A crash mid-async-drain leaves meta.json without a finalized
        Orbax state/ — latest() must fall back to the older restorable
        checkpoint instead of handing restore a partial one."""
        from estorch_tpu.utils import PeriodicCheckpointer

        es = _device_es()
        es.train(2, verbose=False)
        ck = PeriodicCheckpointer(es, str(tmp_path / "cks"), every=1)
        good = ck.save(1)
        # simulate the partial newer checkpoint
        partial = os.path.join(str(tmp_path / "cks"), "gen_00000099")
        os.makedirs(partial)
        open(os.path.join(partial, "meta.json"), "w").write("{}")
        assert ck.latest() == good

    def test_async_gc_deferred_until_durable(self, tmp_path):
        """With max_to_keep=1 the old checkpoint must survive until the
        new async save has drained (GC runs in wait(), not at launch)."""
        from estorch_tpu.utils import PeriodicCheckpointer

        es = _device_es()
        es.train(1, verbose=False)
        ck = PeriodicCheckpointer(es, str(tmp_path / "cks"), every=1,
                                  max_to_keep=1, asynchronous=True)
        ck.save(0)
        ck.wait()
        first = ck.latest()
        assert first is not None
        ck.save(1)
        # in-flight: the only durable checkpoint must still exist
        assert os.path.isdir(os.path.join(first, "state"))
        ck.close()
        kept = sorted(os.listdir(tmp_path / "cks"))
        assert kept == ["gen_00000001"]
