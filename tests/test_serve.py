"""Serving vertical (estorch_tpu/serve, docs/serving.md).

The headline contract under test is BIT-EXACTNESS end to end: an
exported bundle — loaded in a fresh process, served through the dynamic
micro-batcher over HTTP, coalesced with unrelated concurrent requests —
must answer with the SAME float32 bits the exporting run's
``ES.predict`` computes.  Plus the artifact hygiene around it
(atomic commit, corruption rejection), the batcher's bucket/backpressure
mechanics, and THE acceptance demo: a trained pendulum policy served to
concurrent clients at ≥3x the batch-size-1 throughput with a clean
SIGTERM drain.
"""

import json
import os
import signal
import subprocess
import sys
import threading
import time

import flax.linen as flax_nn
import jax
import numpy as np
import optax
import pytest

from estorch_tpu import ES, JaxAgent, MLPPolicy, RecurrentPolicy
from estorch_tpu.envs import RecallEnv
from estorch_tpu.envs.pendulum import Pendulum
from estorch_tpu.obs.spans import Telemetry
from estorch_tpu.serve import (BatcherClosed, BatcherSaturated, Bundle,
                               BundleError, DynamicBatcher, ServeClient,
                               ServeError, bucket_sizes, export_bundle,
                               load_bundle, validate_bundle)
from estorch_tpu.serve.batcher import verify_stable_buckets

SMALL_PK = {"action_dim": 1, "hidden": (24, 24), "discrete": False,
            "action_scale": 2.0}


def _make_small_es(**over):
    kw = dict(
        policy=MLPPolicy,
        agent=JaxAgent,
        optimizer=optax.adam,
        population_size=8,
        sigma=0.05,
        policy_kwargs=dict(SMALL_PK),
        agent_kwargs={"env": Pendulum(), "horizon": 20},
        optimizer_kwargs={"learning_rate": 1e-2},
        seed=0,
        table_size=1 << 14,
        obs_norm=True,
        device=jax.devices()[0],
    )
    kw.update(over)
    return ES(**kw)


@pytest.fixture(scope="module")
def small_es():
    es = _make_small_es()
    es.train(1, verbose=False)
    return es


@pytest.fixture(scope="module")
def small_bundle(small_es, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("bundles") / "pendulum")
    small_es.export_bundle(path, version="test-v1")
    return path


# =====================================================================
# serving-parity predict (serve/predictor.py wired into ES.predict)
# =====================================================================

class TestPredictParity:
    def test_jitted_predict_matches_eager_composition(self, small_es):
        """ES.predict now runs the shared jitted serving program.  For a
        plain policy that is bit-identical to the eager apply it replaced
        (the batch-1 GEMV family is jit/eager-stable); with obs_norm the
        jit FUSES normalize into the forward and may differ in the last
        ulp — numerically equivalent, and the serving stack inherits
        exactly the jitted value (the bit contract that matters, pinned
        by the bundle tests below)."""
        from estorch_tpu.parallel.engine import normalize_obs

        obs = np.random.default_rng(0).standard_normal(3).astype(np.float32)
        got = np.asarray(small_es.predict(obs))
        import jax.numpy as jnp

        norm = normalize_obs(jnp.asarray(obs), small_es.state.obs_stats,
                             small_es._obs_clip)
        want = np.asarray(small_es._policy_apply(small_es.policy, norm))
        np.testing.assert_allclose(got, want, rtol=1e-6)

        es = _make_small_es(obs_norm=False)  # untrained center is fine
        got = np.asarray(es.predict(obs))
        want = np.asarray(es._policy_apply(es.policy, jnp.asarray(obs)))
        assert got.tobytes() == want.tobytes()

    def test_predict_accepts_batched_obs(self, small_es):
        obs = np.random.default_rng(1).standard_normal((5, 3)).astype(
            np.float32)
        out = np.asarray(small_es.predict(obs))
        assert out.shape == (5, 1)


# =====================================================================
# bundle round trip (satellite: export → load → bit-equal predict)
# =====================================================================

class TestBundleRoundTrip:
    def test_manifest_is_self_describing(self, small_bundle):
        man = validate_bundle(small_bundle)
        assert man["version"] == "test-v1"
        assert man["module"]["import"].endswith(":MLPPolicy")
        assert man["obs_shape"] == [3]
        assert man["obs_norm"] is True
        assert man["source"]["algorithm"] == "ES"
        assert man["source"]["generation"] == 1
        # the regression-hunt facts ride along (obs/manifest.py)
        assert "jax" in man["runtime"]
        assert "git_sha" in man["runtime"]

    def test_predict_bit_equal_single_and_batch(self, small_es,
                                                small_bundle):
        b = load_bundle(small_bundle)
        rng = np.random.default_rng(2)
        one = rng.standard_normal(3).astype(np.float32)
        batch = rng.standard_normal((6, 3)).astype(np.float32)
        assert (np.asarray(b.predict(one)).tobytes()
                == np.asarray(small_es.predict(one)).tobytes())
        assert (np.asarray(b.predict(batch)).tobytes()
                == np.asarray(small_es.predict(batch)).tobytes())

    def test_batched_fn_matches_es_predict_at_same_shape(self, small_es,
                                                         small_bundle):
        """The link that anchors served bits to ES.predict: at one batch
        shape, the serving program (jit·vmap) and ES.predict's direct
        jitted apply agree bit-for-bit.  Combined with the batcher's
        bucket-vs-anchor verification, every served response chains back
        to an ES.predict value (docs/serving.md)."""
        b = load_bundle(small_bundle)
        fn = b.batched_predict_fn()
        batch = np.random.default_rng(9).standard_normal((8, 3)).astype(
            np.float32)
        assert (fn(batch).tobytes()
                == np.asarray(small_es.predict(batch)).tobytes())

    def test_use_best_snapshot_roundtrip(self, small_es, small_bundle,
                                         tmp_path):
        path = str(tmp_path / "best")
        small_es.export_bundle(path, use_best=True)
        b = load_bundle(path)
        obs = np.random.default_rng(3).standard_normal(3).astype(np.float32)
        assert (np.asarray(b.predict(obs)).tobytes()
                == np.asarray(small_es.predict(obs,
                                               use_best=True)).tobytes())

    @pytest.mark.slow  # fresh interpreter: ~15s of import/compile; the
    # non-slow serving demo exercises the same cross-process contract
    # end-to-end through the server
    def test_fresh_process_bit_equal(self, small_es, small_bundle,
                                     tmp_path):
        """THE bundle contract: a process that never saw the ES — only
        the artifact — reproduces es.predict bit for bit.  The fresh
        process pins the same host compute configuration (8 virtual CPU
        devices, matching conftest) because bit-parity is only promised
        within one configuration (docs/serving.md)."""
        rng = np.random.default_rng(4)
        obs = rng.standard_normal((8, 3)).astype(np.float32)
        np.save(tmp_path / "obs.npy", obs)
        script = (
            "import sys, numpy as np\n"
            "from estorch_tpu.utils import force_cpu_backend\n"
            "force_cpu_backend(8)\n"
            "from estorch_tpu.serve import load_bundle\n"
            "b = load_bundle(sys.argv[1])\n"
            "obs = np.load(sys.argv[2])\n"
            "batch = np.asarray(b.predict(obs))\n"
            "single = np.asarray(b.predict(obs[0]))\n"
            "print(batch.tobytes().hex())\n"
            "print(single.tobytes().hex())\n"
        )
        r = subprocess.run(
            [sys.executable, "-c", script, small_bundle,
             str(tmp_path / "obs.npy")],
            capture_output=True, text=True, timeout=300,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert r.returncode == 0, r.stderr[-2000:]
        batch_hex, single_hex = r.stdout.strip().splitlines()[-2:]
        assert batch_hex == np.asarray(small_es.predict(obs)).tobytes().hex()
        assert single_hex == np.asarray(
            small_es.predict(obs[0])).tobytes().hex()

    def test_recurrent_bundle_roundtrip(self, tmp_path):
        # no training needed: the round-trip contract is about the
        # artifact, and the freshly-initialized center is a real policy
        es = ES(RecurrentPolicy, JaxAgent, optax.adam, population_size=8,
                sigma=0.1, seed=0, table_size=1 << 14,
                policy_kwargs={"action_dim": 1, "hidden": (8,),
                               "gru_size": 8, "discrete": False},
                agent_kwargs={"env": RecallEnv(), "horizon": 8},
                optimizer_kwargs={"learning_rate": 5e-2},
                device=jax.devices()[0])
        path = str(tmp_path / "rec")
        es.export_bundle(path)
        b = load_bundle(path)
        assert b.recurrent
        obs = np.random.default_rng(5).standard_normal(1).astype(np.float32)
        o_es, h_es = es.predict(obs)
        o_b, h_b = b.predict(obs)
        assert np.asarray(o_es).tobytes() == np.asarray(o_b).tobytes()
        # threaded carry continues bit-equal
        o_es2, _ = es.predict(obs, carry=h_es)
        o_b2, _ = b.predict(obs, carry=h_b)
        assert np.asarray(o_es2).tobytes() == np.asarray(o_b2).tobytes()
        # sessionless coalescing of carries is refused, not fudged
        with pytest.raises(BundleError, match="recurrent"):
            b.batched_predict_fn()

    def test_host_backend_is_not_bundleable(self, tmp_path):
        import torch

        class P(torch.nn.Module):
            def __init__(self):
                super().__init__()
                self.l = torch.nn.Linear(2, 1)

            def forward(self, x):
                return self.l(x)

        class A:
            def rollout(self, policy):
                self.last_episode_steps = 1
                return 0.0

        es = ES(P, A, torch.optim.Adam, population_size=4, sigma=0.1,
                seed=0, table_size=1 << 12)
        with pytest.raises(NotImplementedError, match="torch"):
            es.export_bundle(str(tmp_path / "nope"))


class TestBundleRejection:
    """Corrupt/partial artifacts must be rejected loudly (satellite)."""

    def _copy(self, src, dst):
        import shutil

        shutil.copytree(src, dst)
        return str(dst)

    def test_missing_manifest_means_uncommitted(self, small_bundle,
                                                tmp_path):
        p = self._copy(small_bundle, tmp_path / "b")
        os.remove(os.path.join(p, "MANIFEST.json"))
        with pytest.raises(BundleError, match="never\\s+committed"):
            load_bundle(p)

    def test_corrupt_payload_fails_checksum(self, small_bundle, tmp_path):
        p = self._copy(small_bundle, tmp_path / "b")
        arrays = os.path.join(p, "arrays.npz")
        data = bytearray(open(arrays, "rb").read())
        data[len(data) // 2] ^= 0xFF
        with open(arrays, "wb") as f:
            f.write(bytes(data))
        with pytest.raises(BundleError, match="checksum"):
            load_bundle(p)

    def test_unsupported_schema_rejected(self, small_bundle, tmp_path):
        p = self._copy(small_bundle, tmp_path / "b")
        mp = os.path.join(p, "MANIFEST.json")
        man = json.load(open(mp))
        man["schema"] = 99
        json.dump(man, open(mp, "w"))
        with pytest.raises(BundleError, match="schema"):
            load_bundle(p)

    def test_param_count_drift_rejected(self, small_bundle, tmp_path):
        p = self._copy(small_bundle, tmp_path / "b")
        mp = os.path.join(p, "MANIFEST.json")
        man = json.load(open(mp))
        man["param_dim"] = int(man["param_dim"]) + 1
        json.dump(man, open(mp, "w"))
        with pytest.raises(BundleError, match="param"):
            load_bundle(p)

    def test_unimportable_module_rejected(self, small_bundle, tmp_path):
        p = self._copy(small_bundle, tmp_path / "b")
        mp = os.path.join(p, "MANIFEST.json")
        man = json.load(open(mp))
        man["module"]["import"] = "estorch_tpu.nonexistent:Ghost"
        json.dump(man, open(mp, "w"))
        with pytest.raises(BundleError, match="importable|import"):
            load_bundle(p)

    def test_reexport_over_existing_bundle(self, small_es, tmp_path):
        path = str(tmp_path / "b")
        small_es.export_bundle(path, version="a")
        small_es.export_bundle(path, version="b")
        assert load_bundle(path).version == "b"


# =====================================================================
# dynamic batcher (satellite: bucketing, recompiles, shed) — jax-free
# =====================================================================

class TestBucketLadder:
    def test_ladder_shapes(self):
        assert bucket_sizes(1) == (1,)
        assert bucket_sizes(2) == (2,)
        assert bucket_sizes(32) == (2, 4, 8, 16, 32)

    def test_non_power_of_two_rejected(self):
        with pytest.raises(ValueError, match="power of two"):
            bucket_sizes(12)


class TestDynamicBatcher:
    def _batcher(self, fn=None, **kw):
        tel = Telemetry(enabled=True)
        shapes = []

        def batch_fn(arr):
            shapes.append(arr.shape)
            return (fn or (lambda a: a.sum(axis=1, keepdims=True)))(arr)

        kw.setdefault("max_batch", 8)
        kw.setdefault("max_wait_ms", 5.0)
        b = DynamicBatcher(batch_fn, (3,), telemetry=tel, **kw)
        shapes.clear()  # drop the construction-time verification shapes
        return b, shapes, tel

    def test_batches_pad_to_ladder_buckets(self):
        b, shapes, _ = self._batcher()
        outs = [b.submit(np.full(3, i, np.float32)) for i in range(5)]
        for o in outs:
            assert o.event.wait(10)
        b.close()
        assert shapes, "no batches dispatched"
        for s in shapes:
            assert s[0] in b.buckets, f"dispatched shape {s} off-ladder"
        # results map back to the right requests
        for i, o in enumerate(outs):
            assert o.result[0] == pytest.approx(3.0 * i)

    def test_recompiles_bounded_under_mixed_load(self):
        b, shapes, tel = self._batcher(max_batch=16, max_wait_ms=2.0)
        n_ladder = len(b.buckets) + len(b.buckets_excluded)

        def client(seed):
            rng = np.random.default_rng(seed)
            for _ in range(40):
                item = b.submit(rng.standard_normal(3).astype(np.float32))
                assert item.event.wait(10)
                if rng.random() < 0.3:
                    time.sleep(0.001)

        threads = [threading.Thread(target=client, args=(s,))
                   for s in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        b.close()
        assert tel.counters.get("recompiles") <= n_ladder
        assert tel.counters.get("requests_total") == 240
        assert tel.counters.get("batched_requests_total") == 240

    def test_full_queue_sheds_with_backpressure(self):
        gate = threading.Event()

        def slow(arr):
            gate.wait(10)
            return arr

        tel = Telemetry(enabled=True)
        b = DynamicBatcher(slow, (3,), max_batch=2, max_wait_ms=1.0,
                           max_queue=4, telemetry=tel, verify=False)
        first = b.submit(np.zeros(3, np.float32))
        time.sleep(0.1)  # worker picks `first` up and blocks in slow()
        for _ in range(4):
            b.submit(np.zeros(3, np.float32))
        with pytest.raises(BatcherSaturated):
            b.submit(np.zeros(3, np.float32))
        assert tel.counters.get("shed_total") == 1
        gate.set()
        assert first.event.wait(10)
        b.close()

    def test_close_drains_queued_requests(self):
        def slowish(arr):
            time.sleep(0.02)
            return arr

        b = DynamicBatcher(slowish, (3,), max_batch=2, max_wait_ms=1.0,
                           verify=False)
        items = [b.submit(np.full(3, i, np.float32)) for i in range(10)]
        b.close(drain=True)
        for i, item in enumerate(items):
            assert item.event.is_set()
            assert item.error is None
            assert item.result[0] == pytest.approx(float(i))
        with pytest.raises(BatcherClosed):
            b.submit(np.zeros(3, np.float32))

    def test_batch_fn_error_propagates_to_waiters(self):
        def boom(arr):
            raise RuntimeError("model exploded")

        tel = Telemetry(enabled=True)
        b = DynamicBatcher(boom, (3,), max_batch=2, telemetry=tel,
                           verify=False)
        item = b.submit(np.zeros(3, np.float32))
        assert item.event.wait(10)
        assert isinstance(item.error, RuntimeError)
        assert tel.counters.get("batch_errors_total") == 1
        b.close()

    def test_obs_shape_mismatch_rejected(self):
        b, _, _ = self._batcher()
        with pytest.raises(ValueError, match="obs_shape"):
            b.submit(np.zeros(4, np.float32))
        b.close()


class TestBucketVerification:
    """The measured bit-determinism gate: XLA's cross-batch-shape row
    stability is checked per policy, never assumed (the B=2 lowering
    genuinely deviates by 1 ulp for some trained parameters)."""

    def test_unstable_bucket_excluded(self):
        def fn(arr):
            out = arr.sum(axis=1, keepdims=True)
            if arr.shape[0] == 2:  # model a shape-dependent lowering
                out = out + np.float32(1e-6)
            return out

        stable, excluded = verify_stable_buckets(fn, (3,), (2, 4, 8))
        assert excluded == (2,)
        assert stable == (4, 8)

    def test_batcher_routes_around_excluded_bucket(self):
        shapes = []

        def fn(arr):
            shapes.append(arr.shape[0])
            out = arr.sum(axis=1, keepdims=True)
            if arr.shape[0] == 2:
                out = out + np.float32(1e-6)
            return out

        b = DynamicBatcher(fn, (3,), max_batch=8, max_wait_ms=1.0)
        assert b.buckets_excluded == (2,)
        item = b.submit(np.ones(3, np.float32))
        assert item.event.wait(10)
        b.close()
        assert shapes[-1] == 4  # a lone request pads past the bad bucket

    def test_batcher_routes_around_excluded_interior_bucket(self):
        """An INTERIOR ladder shape failing verification must be padded
        past too — doubling from the smallest bucket would land exactly
        on the excluded (bit-unstable) shape."""
        shapes = []

        def fn(arr):
            shapes.append(arr.shape[0])
            out = arr.sum(axis=1, keepdims=True)
            if arr.shape[0] == 4:  # interior shape deviates
                out = out + np.float32(1e-6)
            return out

        b = DynamicBatcher(fn, (3,), max_batch=8, max_wait_ms=20.0)
        assert b.buckets_excluded == (4,)
        assert b.buckets == (2, 8)
        # the routing rule itself: sizes above the gap pad PAST it
        assert [b._bucket(n) for n in (1, 2, 3, 4, 5, 8)] == [
            2, 2, 8, 8, 8, 8]
        shapes.clear()
        items = [b.submit(np.ones(3, np.float32)) for _ in range(3)]
        for it in items:
            assert it.event.wait(10)
        b.close()
        assert 4 not in shapes  # the unstable shape is never dispatched

    def test_slot_dependent_anchor_is_fatal(self):
        def fn(arr):
            out = arr.sum(axis=1, keepdims=True)
            out[0] += np.float32(1e-6)  # slot 0 special-cased
            return out

        with pytest.raises(ValueError, match="slot-dependent"):
            verify_stable_buckets(fn, (3,), (2, 4))

    def test_stable_fn_keeps_whole_ladder(self):
        stable, excluded = verify_stable_buckets(
            lambda a: a.sum(axis=1, keepdims=True), (3,), (2, 4, 8))
        assert stable == (2, 4, 8)
        assert excluded == ()


# =====================================================================
# server endpoints (in-process PolicyServer)
# =====================================================================

@pytest.fixture(scope="module")
def live_server(small_bundle):
    from estorch_tpu.serve import PolicyServer

    srv = PolicyServer(small_bundle, port=0, max_batch=8, max_wait_ms=2.0,
                       telemetry=Telemetry(enabled=True))
    srv.start_background()
    yield srv
    srv.shutdown(drain=True)


def _anchor_ref(es, obs, anchor):
    """The bit-sound reference for a lone served request: the batcher
    pads into a VERIFIED bucket whose rows equal the anchor bucket's, and
    the anchor shape is where es.predict's direct program and the serving
    vmap agree (pinned by test_batched_fn_matches_es_predict_at_same_shape)
    — so reference = es.predict on an anchor-sized zero-padded batch."""
    pad = np.zeros((anchor,) + np.shape(obs), np.float32)
    pad[0] = obs
    return np.asarray(es.predict(pad))[0]


class TestServerEndpoints:
    def test_predict_health_stats(self, small_es, live_server):
        with ServeClient(f"{live_server.host}:{live_server.port}") as c:
            h = c.health()
            assert h["ok"] and h["version"] == "test-v1"
            obs = np.random.default_rng(6).standard_normal(3).astype(
                np.float32)
            action = np.asarray(c.predict(obs), np.float32)
            s = c.stats()
            ref = _anchor_ref(small_es, obs, max(s["buckets"]))
            assert action.tobytes() == ref.tobytes()
            assert s["requests_total"] >= 1
            assert s["recompiles"] <= len(s["buckets"]) + len(
                s["buckets_excluded"])

    def test_metrics_exposition_scrapeable(self, live_server):
        """GET /metrics speaks Prometheus text exposition: the serving
        counters as estorch_-prefixed samples, validated by the parser
        that did not write them (obs/export/prometheus.py)."""
        import urllib.request

        from estorch_tpu.obs.export.prometheus import (parse_exposition,
                                                       samples_by_name)

        with ServeClient(f"{live_server.host}:{live_server.port}") as c:
            obs = np.zeros(3, np.float32)
            c.predict(obs)  # at least one served request on the counters
        url = f"http://{live_server.host}:{live_server.port}/metrics"
        with urllib.request.urlopen(url, timeout=30) as r:
            assert r.status == 200
            assert "text/plain" in r.headers["Content-Type"]
            body = r.read().decode()
        vals = samples_by_name(parse_exposition(body))
        assert vals["estorch_requests_total"] >= 1
        assert vals["estorch_up"] == 1  # serving and not draining
        assert vals["estorch_uptime_seconds"] >= 0
        assert "estorch_queue_depth" in vals
        assert "# TYPE estorch_requests_total counter" in body
        assert "# TYPE estorch_queue_depth gauge" in body

    def test_bad_requests_are_4xx(self, live_server):
        with ServeClient(f"{live_server.host}:{live_server.port}") as c:
            with pytest.raises(ServeError) as ei:
                c.predict([1.0, 2.0])  # wrong obs shape
            assert ei.value.status == 400
            with pytest.raises(ServeError) as ei:
                c._request("POST", "/predict", {"not_obs": 1})
            assert ei.value.status == 400
            with pytest.raises(ServeError) as ei:
                c._request("GET", "/nope")
            assert ei.value.status == 404

    def test_hot_reload_swaps_atomically(self, small_es, live_server,
                                         tmp_path):
        v2 = str(tmp_path / "v2")
        small_es.export_bundle(v2, version="test-v2")
        addr = f"{live_server.host}:{live_server.port}"
        with ServeClient(addr) as c:
            assert c.reload(v2)["version"] == "test-v2"
            assert c.health()["version"] == "test-v2"
            # a bad reload is a 409 and the old bundle keeps serving
            with pytest.raises(ServeError) as ei:
                c.reload(str(tmp_path / "missing"))
            assert ei.value.status == 409
            assert c.health()["version"] == "test-v2"
            obs = np.random.default_rng(7).standard_normal(3).astype(
                np.float32)
            got = np.asarray(c.predict(obs), np.float32)
            ref = _anchor_ref(small_es, obs, max(c.stats()["buckets"]))
            assert got.tobytes() == ref.tobytes()


# =====================================================================
# tail-latency truth: quantile honesty + trace ids (docs/observability.md
# "Tails & traces")
# =====================================================================

class TestQuantileHonesty:
    def test_loadgen_offline_vs_server_histogram_quantiles(
            self, small_bundle):
        """Quantile honesty: the loadgen's OFFLINE p50/p95/p99 (exact
        nearest-rank over every client-measured latency) and
        histogram-derived quantiles must agree within the bucket ladder's
        documented error bound.  What is compared reads no clock twice:

        (a) ONE recorded list, two computations: the client's latencies
            through the server's own ladder (bucket path, no exact list)
            against their exact nearest rank;
        (b) an ordering no scheduler can break: a request's server span
            (submit to done, ``serve/request_s``) lies INSIDE the client's
            measurement of it, so each order statistic of the server's
            histogram is at most the client's, the ladder's bound apart.

        Until PR 56 (b) also had a LOWER bound with 10 ms of slack, to find
        a stall between the server's spans and the wire (until PR 43 a
        reply's body waited ~40 ms for the ACK of its headers: two sends
        under Nagle's algorithm).  It compared two clocks' views of one
        run, and under six test workers the client's threads wait for a
        core longer than that.  What it guarded is asserted as what it is:
        the handlers set ``disable_nagle_algorithm``."""
        import math

        from estorch_tpu.obs.hist import Histogram
        from estorch_tpu.serve import PolicyServer
        from estorch_tpu.serve.loadgen import _percentile, run_load

        srv = PolicyServer(small_bundle, port=0, max_batch=8,
                           max_wait_ms=2.0,
                           telemetry=Telemetry(enabled=True))
        srv.start_background()
        try:
            assert srv._httpd.RequestHandlerClass.disable_nagle_algorithm
            res = run_load(f"{srv.host}:{srv.port}", conns=8, total=400,
                           duration_s=60.0, obs=[0.0, 0.0, 0.0],
                           collect_latencies=True)
            assert res["requests"] == 400 and not res["errors"]
            offline = sorted(res["latencies_s"])
            hist = srv.obs.hists.get("serve/request_s")
            assert hist is not None and hist.count == 400
            bound = hist.quantile_error_bound()
            ladder = Histogram(lo=hist.lo, decades=hist.n // hist.per_decade,
                               per_decade=hist.per_decade, exact_cap=0)
            for latency in offline:
                ladder.observe(latency)
            for q in (0.50, 0.95, 0.99):
                off = _percentile(offline, q)
                # (a) the ladder answers at rank ceil(q n), the loadgen one
                # rank above it: within the bound of the one, never over
                # the other by more
                at_rank = offline[max(1, math.ceil(q * len(offline))) - 1]
                assert abs(ladder.quantile(q) - at_rank) <= bound * at_rank, (
                    f"p{q * 100:g}: ladder {ladder.quantile(q)} vs exact "
                    f"{at_rank}")
                assert at_rank <= off
                # (b) the server's histogram, of spans inside the client's
                assert hist.quantile(q) <= off * (1 + bound), (
                    f"p{q * 100:g}: hist {hist.quantile(q)} vs offline {off}")
            # lifecycle legs all populated on a real HTTP run
            names = srv.obs.hists.names()
            for name in ("serve/queue_wait_s", "serve/coalesce_wait_s",
                         "serve/compute_s", "serve/request_s",
                         "serve/write_s"):
                assert name in names, names
            # /stats surfaces histogram-derived request quantiles
            assert srv.stats()["request_ms"]["p50"] > 0
        finally:
            srv.shutdown(drain=True)

    def test_predict_response_carries_trace_id(self, live_server):
        import urllib.request

        body = json.dumps({"obs": [0.0, 0.0, 0.0]}).encode()
        req = urllib.request.Request(
            f"http://{live_server.host}:{live_server.port}/predict",
            data=body, headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200
            trace = r.headers.get("X-Trace-Id")
        assert trace and trace.startswith("r")
        # the same id is recorded in the batcher's dispatch event — the
        # causal link from an HTTP answer back to its coalesced batch
        evs = [e for e in live_server.obs.recorder.events()
               if e["name"] == "batch_dispatch"]
        assert any(trace in e.get("traces", []) for e in evs)


# =====================================================================
# supervised serving (resilience integration)
# =====================================================================

def _beat_then_wedge(root, marker):
    """Supervised child: first incarnation beats then wedges (watchdog
    food); later incarnations exit clean."""
    from estorch_tpu.obs.recorder import HEARTBEAT_ENV, Heartbeat

    hb = Heartbeat(os.environ[HEARTBEAT_ENV])
    if os.path.exists(marker):
        hb.beat("serving", 1)
        return
    with open(marker, "w") as f:
        f.write("seen")
    for _ in range(3):
        hb.beat("serving", 0)
        time.sleep(0.1)
    time.sleep(600)  # silent wedge: alive but beatless


class TestSupervisedServe:
    def test_generic_child_watchdog_restart(self, tmp_path):
        """The PR-3 watchdog babysits a NON-training child (the serving
        recipe): heartbeat staleness kills the wedged incarnation, the
        restart completes, provenance lands in the manifest."""
        from estorch_tpu.resilience import Supervisor

        marker = str(tmp_path / "marker")
        sup = Supervisor(
            ckpt_root=str(tmp_path / "root"),
            child_target=_beat_then_wedge,
            child_args=(marker,),
            stale_after_s=2.0,
            startup_grace_s=60.0,
            backoff_s=0.1,
            max_restarts=2,
            poll_s=0.2,
        )
        result = sup.run()
        assert result["ok"], result
        assert len(result["restarts"]) == 1
        assert "stale" in result["restarts"][0]["reason"]

    def test_exactly_one_child_mode_required(self, tmp_path):
        from estorch_tpu.resilience import Supervisor

        with pytest.raises(ValueError, match="exactly one"):
            Supervisor(ckpt_root=str(tmp_path))
        with pytest.raises(ValueError, match="exactly one"):
            Supervisor(es_factory=lambda: None, child_target=_beat_then_wedge,
                       ckpt_root=str(tmp_path))

    @pytest.mark.slow  # supervisor + spawned jax server child: ~15s; the
    # non-slow watchdog-restart test above covers the Supervisor's
    # generic-child mechanics
    def test_supervised_serve_end_to_end(self, small_bundle, tmp_path):
        """``serve --supervised``: the server answers under the watchdog,
        and SIGTERM to the SUPERVISOR forwards to the child, which drains
        — the supervisor reports clean completion (ok, exit 0)."""
        from estorch_tpu.serve.server import find_free_port

        port = find_free_port()
        env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        proc = subprocess.Popen(
            [sys.executable, "-m", "estorch_tpu.serve", "--bundle",
             small_bundle, "--supervised", "--supervise-root",
             str(tmp_path / "root"), "--port", str(port),
             "--cpu-devices", "8", "--max-batch", "8",
             "--beat-interval", "0.5", "--stale-after-s", "30"],
            stdout=subprocess.PIPE, text=True, env=env,
        )
        try:
            deadline = time.time() + 120
            health = None
            while time.time() < deadline:
                try:
                    with ServeClient(f"127.0.0.1:{port}",
                                     timeout_s=2) as c:
                        health = c.health()
                    break
                except OSError:
                    time.sleep(0.5)
            assert health is not None and health["ok"], health
            proc.send_signal(signal.SIGTERM)
            out, _ = proc.communicate(timeout=60)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
        assert proc.returncode == 0, out[-1000:]
        last = json.loads(out.strip().splitlines()[-1])
        assert last == {"supervised": True, "ok": True, "restarts": 0,
                        "reason": None}


# =====================================================================
# THE acceptance demo (tier-1): trained pendulum policy, real server
# subprocesses, concurrent load, bit-exactness + >=3x + clean drain
# =====================================================================

DEMO_HIDDEN = 6144  # big enough that one request's GEMV is memory-bound:
# the batching win being measured is one weight-stream amortized over the
# whole bucket — the 2206.08888 batched-inference effect, not a cache toy


@pytest.fixture(scope="module")
def demo_bundle(tmp_path_factory):
    es = _make_small_es(
        policy_kwargs=dict(SMALL_PK, hidden=(DEMO_HIDDEN, DEMO_HIDDEN)),
        agent_kwargs={"env": Pendulum(), "horizon": 8},
        population_size=4,
        table_size=1 << 26,
        obs_norm=False,
    )
    es.train(1, verbose=False)
    path = str(tmp_path_factory.mktemp("demo") / "pendulum_big")
    es.export_bundle(path, version="demo")
    return es, path


def _spawn_server(bundle, max_batch, extra_env=None, max_wait_ms=4.0,
                  extra_args=()):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(extra_env or {})}
    proc = subprocess.Popen(
        [sys.executable, "-m", "estorch_tpu.serve", "--bundle", bundle,
         "--port", "0", "--cpu-devices", "8",
         "--max-batch", str(max_batch), "--max-wait-ms", str(max_wait_ms),
         "--beat-interval", "0.5", *extra_args],
        stdout=subprocess.PIPE, text=True, env=env,
    )
    ready = json.loads(proc.stdout.readline())
    return proc, ready


def _finish(proc, timeout=60):
    proc.send_signal(signal.SIGTERM)
    out, _ = proc.communicate(timeout=timeout)
    return proc.returncode, json.loads(out.strip().splitlines()[-1])


class TestServingDemo:
    def test_serving_demo(self, demo_bundle):
        """Acceptance: (a) responses bit-equal to direct ES.predict,
        (b) dynamic batching >=3x the batch-size-1 throughput on this
        host, (c) recompiles <= n_buckets under mixed concurrent load,
        (d) SIGTERM drains in-flight requests cleanly — no shed, real
        answers, exit 0."""
        from estorch_tpu.serve.loadgen import run_load

        es, bundle = demo_bundle
        rng = np.random.default_rng(8)
        # exactly anchor-many obs: the reference es.predict batch IS the
        # anchor shape, where the direct program and the serving vmap are
        # asserted bit-equal in-process before anything goes on the wire
        check_obs = rng.standard_normal((64, 3)).astype(np.float32)
        ref = np.asarray(es.predict(check_obs))
        b = load_bundle(bundle)
        assert b.batched_predict_fn()(check_obs).tobytes() == ref.tobytes()

        # ---- dynamic-batching leg --------------------------------------
        proc, ready = _spawn_server(bundle, max_batch=64)
        addr = ready["url"]
        try:
            # (a) correctness under CONCURRENT load: 32 distinct obs ride
            # mixed buckets; every response must be bit-equal to the
            # exporting run's es.predict rows (same 8-virtual-device host
            # config on both sides)
            chk = run_load(addr, conns=6, total=len(check_obs),
                           duration_s=120.0,
                           obs_list=[o.tolist() for o in check_obs],
                           collect_responses=True)
            assert chk["errors"] == 0 and chk["shed"] == 0
            got = np.asarray([r["action"] for r in chk["responses"]],
                             np.float32)
            assert got.tobytes() == ref.tobytes(), (
                "served responses are not bit-equal to ES.predict")

            dyn = run_load(addr, conns=48, duration_s=2.5,
                           obs=[0.1, 0.2, 0.3])
            assert dyn["errors"] == 0

            with ServeClient(addr) as c:
                stats = c.stats()
            # (c) bucket ladder held: one compile per ladder shape, no
            # recompile churn under mixed batch sizes
            n_ladder = len(stats["buckets"]) + len(stats["buckets_excluded"])
            assert stats["recompiles"] <= n_ladder
            assert stats["shed_total"] == 0

            # (d) SIGTERM lands while 12 requests are in flight (the
            # batched forward takes tens of ms at this size, so firing
            # right after the clients guarantees work is mid-pipeline);
            # every one of them must get a REAL answer, nothing shed
            results: list = [None] * 12
            errors: list = []
            host_port = addr.split("://", 1)[1]
            # connections are ESTABLISHED (via a health round trip) before
            # the signal: in-flight means accepted work, not a racing
            # connect against the closing listener
            clients = [ServeClient(host_port, timeout_s=60)
                       for _ in range(12)]
            for c in clients:
                c.health()

            def client(i):
                try:
                    results[i] = clients[i].predict([0.1 * i, 0.2, 0.3])
                except Exception as e:  # asserted empty below
                    errors.append((i, repr(e)))
                finally:
                    clients[i].close()

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(12)]
            for t in threads:
                t.start()
            time.sleep(0.05)
            proc.send_signal(signal.SIGTERM)
            for t in threads:
                t.join(timeout=60)
            out, _ = proc.communicate(timeout=60)
            final = json.loads(out.strip().splitlines()[-1])
            assert not errors, errors
            assert all(r is not None for r in results)
            assert proc.returncode == 0
            assert final["clean"]
            assert final["counters"].get("shed_total", 0) == 0
            # drained responses are REAL answers: reference at the anchor
            # shape, zero-padded the same way the batcher pads
            pad = np.zeros((64, 3), np.float32)
            pad[:12] = np.asarray(
                [[0.1 * i, 0.2, 0.3] for i in range(12)], np.float32)
            drain_ref = np.asarray(es.predict(pad))[:12]
            assert np.asarray(results,
                              np.float32).tobytes() == drain_ref.tobytes()
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)

        # ---- batch-size-1 baseline leg ---------------------------------
        proc, ready = _spawn_server(bundle, max_batch=1)
        try:
            b1 = run_load(ready["url"], conns=8, duration_s=2.5,
                          obs=[0.1, 0.2, 0.3])
            assert b1["errors"] == 0
            code, final = _finish(proc)
            assert code == 0 and final["clean"]
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)

        # (b) the batching win: one weight-stream amortized per bucket.
        # Steady-state headroom is ~4x on this 2-core host; a transient
        # external load spike during one 2.5s leg can crater either
        # number, so a sub-3x first reading gets ONE full re-measurement
        # (both legs, fresh servers) before the gate decides.
        def measure_legs():
            p_dyn, r_dyn = _spawn_server(bundle, max_batch=64)
            try:
                d = run_load(r_dyn["url"], conns=48, duration_s=2.5,
                             obs=[0.1, 0.2, 0.3])
                _finish(p_dyn)
            finally:
                if p_dyn.poll() is None:
                    p_dyn.kill()
                    p_dyn.wait(timeout=30)
            p_b1, r_b1 = _spawn_server(bundle, max_batch=1)
            try:
                s = run_load(r_b1["url"], conns=8, duration_s=2.5,
                             obs=[0.1, 0.2, 0.3])
                _finish(p_b1)
            finally:
                if p_b1.poll() is None:
                    p_b1.kill()
                    p_b1.wait(timeout=30)
            return d["throughput_rps"], s["throughput_rps"]

        dyn_rps, b1_rps = dyn["throughput_rps"], b1["throughput_rps"]
        ratio = dyn_rps / b1_rps
        if ratio < 3.0:
            dyn_rps, b1_rps = measure_legs()
            ratio = dyn_rps / b1_rps
        print(f"\nserving demo: dyn={dyn_rps} rps "
              f"(p50 {dyn['latency_ms']['p50']}ms) vs b1={b1_rps} rps "
              f"-> {ratio:.2f}x")
        assert ratio >= 3.0, (
            f"dynamic batching {dyn_rps} rps vs batch-1 {b1_rps} rps = "
            f"{ratio:.2f}x < 3x")


# =====================================================================
# warm-start bundles (serve/warm.py, docs/serving.md "Cold start &
# quantized serving")
# =====================================================================

@pytest.fixture(scope="module")
def warm_bundle_path(small_es, tmp_path_factory):
    path = str(tmp_path_factory.mktemp("warm") / "pendulum_warm")
    small_es.export_bundle(path, version="warm-v1", warm=True,
                           warm_max_batch=4, serve_bf16=True)
    return path


class TestWarmBundle:
    def test_warm_block_packed_and_checksummed(self, warm_bundle_path):
        man = validate_bundle(warm_bundle_path)
        warm = man["warm"]
        assert warm["format"] == "xla_cache"
        assert warm["entries"], "warm export packed no cache entries"
        sha = man["sha256"]
        for fname in warm["entries"]:
            assert f"warm/{fname}" in sha
            assert os.path.exists(
                os.path.join(warm_bundle_path, "warm", fname))
        # ladder complete: warmed + verification-excluded covers exactly
        # the bucket ladder of the recorded max_batch
        covered = set(warm["buckets"]) | set(warm["buckets_excluded"])
        assert covered == set(bucket_sizes(warm["max_batch"]))
        assert warm["dtypes"] == ["f32", "bf16"]
        assert warm["jax_version"] == jax.__version__
        assert warm["platform"] == "cpu"

    def test_warm_corruption_rejected(self, warm_bundle_path, tmp_path):
        import shutil

        dst = str(tmp_path / "tampered")
        shutil.copytree(warm_bundle_path, dst)
        man = validate_bundle(warm_bundle_path)
        fname = sorted(man["warm"]["entries"])[0]
        victim = os.path.join(dst, "warm", fname)
        with open(victim, "r+b") as f:
            f.seek(0)
            b = f.read(1)
            f.seek(0)
            f.write(bytes([b[0] ^ 0xFF]))
        with pytest.raises(BundleError, match="checksum"):
            validate_bundle(dst)
        os.remove(victim)
        with pytest.raises(BundleError, match="missing"):
            validate_bundle(dst)

    def test_version_mismatch_is_finding_not_error(self, warm_bundle_path,
                                                   tmp_path):
        """Warmth built under another jax version must be IGNORED with a
        structured reason (load still succeeds, serving still works) —
        and the doctor's warm probe reports the same finding."""
        import shutil

        dst = str(tmp_path / "stale_warm")
        shutil.copytree(warm_bundle_path, dst)
        man_path = os.path.join(dst, "MANIFEST.json")
        with open(man_path) as f:
            man = json.load(f)
        man["warm"]["jax_version"] = "0.0.0"
        with open(man_path, "w") as f:
            json.dump(man, f)
        b = load_bundle(dst, install_warm=True)
        assert b.warm_status["installed"] is False
        assert "0.0.0" in b.warm_status["reason"]
        # still a perfectly servable bundle
        out = b.batched_predict_fn()(np.zeros((2, 3), np.float32))
        assert out.shape == (2, 1)
        from estorch_tpu.doctor import check_serve

        probe = check_serve(bundle=dst)["bundle"]["warm"]
        assert probe["present"] and probe["compatible"] is False
        assert "re-export" in probe["finding"]

    def test_cold_bundle_reports_no_warmth(self, small_bundle):
        b = load_bundle(small_bundle, install_warm=True)
        assert b.warm_status["installed"] is False
        assert "no warmth" in b.warm_status["reason"]
        from estorch_tpu.doctor import check_serve

        probe = check_serve(bundle=small_bundle)["bundle"]["warm"]
        assert probe == {"present": False}

    def test_reexport_without_warm_clears_stale_entries(self, small_es,
                                                        tmp_path):
        path = str(tmp_path / "re")
        small_es.export_bundle(path, warm=True, warm_max_batch=4)
        assert os.path.isdir(os.path.join(path, "warm"))
        small_es.export_bundle(path)  # cold re-export over the same dir
        man = validate_bundle(path)
        assert "warm" not in man
        assert not os.path.isdir(os.path.join(path, "warm"))

    def test_warm_roundtrip_fresh_process_zero_fresh_builds(
            self, small_es, warm_bundle_path, tmp_path):
        """THE warm-bundle acceptance: a fresh --cpu-devices-pinned
        process loads the warm bundle and serves its first request with
        ZERO fresh XLA builds (every program a persistent-cache hit, per
        the compile ledger's bundle_load accounting), answers bit-equal
        to the exporting run, and leaves the bundle's checksums intact.
        The --no-warm control leg on the SAME bundle pays the JIT storm,
        proving the A/B is real."""
        proc, ready = _spawn_server(warm_bundle_path, max_batch=4)
        try:
            cold = ready["cold_start"]
            assert cold["warm"]["installed"] is True
            assert cold["compiles_at_load"] == 0, (
                f"warm load paid {cold['compiles_at_load']} fresh builds")
            assert cold["warm_cache_hits"] > 0
            obs = np.random.default_rng(11).standard_normal(3).astype(
                np.float32)
            with ServeClient(ready["url"].split("://")[1]) as c:
                got = np.asarray(c.predict(obs), np.float32)
                stats = c.stats()
            ref = _anchor_ref(small_es, obs, max(stats["buckets"]))
            assert got.tobytes() == ref.tobytes()
            assert stats["cold_start"]["first_request_s"] is not None
            assert stats["cold_start"]["startup_s"] is not None
            code, final = _finish(proc)
            assert code == 0 and final["clean"]
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
        # serving never wrote into the bundle: checksums still hold
        validate_bundle(warm_bundle_path)

        # control leg: same bundle, warmth ignored -> the JIT storm.  The
        # warm leg installed the bundle's entries into the compile cache
        # its process was given; the control gets an empty one of its own
        proc, ready = _spawn_server(
            warm_bundle_path, max_batch=4, extra_args=["--no-warm"],
            extra_env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")})
        try:
            cold = ready["cold_start"]
            assert cold["warm"]["installed"] is False
            assert cold["compiles_at_load"] > 0
            assert cold["warm_cache_hits"] == 0
            code, final = _finish(proc)
            assert code == 0 and final["clean"]
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)


# =====================================================================
# quantized serving: divergence measurement + bucket exclusion
# (serve/batcher.py, jax-free) and the bf16 path (serve/predictor.py)
# =====================================================================

class TestQuantBatcher:
    obs_shape = (3,)

    @staticmethod
    def _f32(arr):
        return arr.sum(axis=1, keepdims=True).astype(np.float32)

    def test_drifting_bucket_excluded_f32_fallback_answers(self):
        """A quantized path that drifts at ONE bucket keeps serving:
        that bucket is excluded (measured, counted) and dispatches the
        exact f32 program at the same shape, while within-bound buckets
        ride the quantized fast path."""
        def quant(arr):
            out = self._f32(arr) + 0.01  # inside the bound
            if arr.shape[0] == 4:
                out = out + 1e3  # engineered drift at bucket 4
            return out

        tel = Telemetry(enabled=True)
        b = DynamicBatcher(self._f32, self.obs_shape, max_batch=8,
                           max_wait_ms=40.0, telemetry=tel,
                           quant_fn=quant, quant_bound=0.05)
        try:
            assert b.quant_buckets_excluded == (4,)
            assert set(b.quant_buckets) == {2, 8}
            assert b.quant_divergence[4] > 0.05
            assert int(tel.counters.get("quant_buckets_excluded")) == 1
            # one lone request pads to bucket 2 -> quantized value
            got = b.predict([1.0, 2.0, 3.0], timeout=10.0)
            assert got[0] == np.float32(6.0) + np.float32(0.01)
            # three coalesced requests pad to bucket 4 -> EXCLUDED from
            # the quant ladder -> exact f32 values
            items = [b.submit([float(i), 1.0, 1.0]) for i in range(3)]
            for i, it in enumerate(items):
                assert it.event.wait(10.0)
                assert it.result[0] == np.float32(i + 2.0)
            stats = b.stats()
            assert stats["quant"]["excluded"] == [4]
            assert stats["quant"]["batches_total"] >= 1
        finally:
            b.close()

    def test_anchor_drift_refused(self):
        with pytest.raises(ValueError, match="anchor"):
            DynamicBatcher(self._f32, self.obs_shape, max_batch=4,
                           max_wait_ms=1.0,
                           quant_fn=lambda a: self._f32(a) + 1e3,
                           quant_bound=0.05)

    def test_quant_needs_bound_and_verification(self):
        with pytest.raises(ValueError, match="quant_bound"):
            DynamicBatcher(self._f32, self.obs_shape, max_batch=4,
                           quant_fn=self._f32)
        with pytest.raises(ValueError, match="verification"):
            DynamicBatcher(self._f32, self.obs_shape, max_batch=4,
                           verify=False, quant_fn=self._f32,
                           quant_bound=0.05)

    def test_nonfinite_quant_output_is_infinite_divergence(self):
        from estorch_tpu.serve.batcher import measure_quant_divergence

        def quant(arr):
            out = self._f32(arr)
            out[0] = np.nan
            return out

        div = measure_quant_divergence(quant, self._f32, self.obs_shape,
                                       [2, 4])
        assert div[2] == float("inf") and div[4] == float("inf")

    def test_batch1_ladder_measures_divergence_too(self):
        """max_batch=1 (the GEMV baseline) still gets the accuracy
        contract: divergence measured at bucket 1, refused past bound."""
        b = DynamicBatcher(self._f32, self.obs_shape, max_batch=1,
                           max_wait_ms=1.0,
                           quant_fn=lambda a: self._f32(a) + 0.001,
                           quant_bound=0.05)
        try:
            assert b.quant_buckets == (1,)
            assert 1 in b.quant_divergence
        finally:
            b.close()
        with pytest.raises(ValueError, match="anchor"):
            DynamicBatcher(self._f32, self.obs_shape, max_batch=1,
                           max_wait_ms=1.0,
                           quant_fn=lambda a: self._f32(a) + 1e3,
                           quant_bound=0.05)


class DriftPolicy(flax_nn.Module):
    """bf16-hostile by construction: the +4096/-4096 round trip keeps
    the (tiny) signal in f32 but destroys it at bf16's 8 mantissa bits
    — the policy-exceeds-the-bound refusal case."""

    @flax_nn.compact
    def __call__(self, x):
        # weak-typed python literals follow the computation dtype: in
        # bf16 the +4096 absorbs the whole signal (8 mantissa bits), in
        # f32 it survives — a jnp.float32 constant would instead promote
        # the bf16 activations back to f32 and defeat the engineering
        h = flax_nn.Dense(1)(x) * 0.01
        return (h + 4096.0) - 4096.0


class TestBf16Serving:
    def test_bf16_refused_without_opt_in(self, small_bundle):
        b = load_bundle(small_bundle)
        with pytest.raises(BundleError, match="did not opt into"):
            b.batched_predict_fn(dtype="bf16")

    def test_bf16_server_serves_within_measured_bound(self, small_es,
                                                      warm_bundle_path):
        """An opted-in policy serves bf16 with per-bucket divergence
        MEASURED at load and every answer inside the documented bound of
        the f32 reference."""
        from estorch_tpu.serve import PolicyServer
        from estorch_tpu.serve.warm import BF16_DIVERGENCE_BOUND

        srv = PolicyServer(warm_bundle_path, port=0, max_batch=4,
                           max_wait_ms=2.0, dtype="bf16",
                           telemetry=Telemetry(enabled=True))
        srv.start_background()
        try:
            obs = np.random.default_rng(12).standard_normal(3).astype(
                np.float32)
            with ServeClient(f"{srv.host}:{srv.port}") as c:
                got = np.asarray(c.predict(obs), np.float32)
                stats = c.stats()
            quant = stats["quant"]
            assert quant["dtype"] == "bf16"
            assert quant["bound"] == BF16_DIVERGENCE_BOUND
            for b_, d in quant["divergence"].items():
                if int(b_) in quant["buckets"]:
                    assert d <= BF16_DIVERGENCE_BOUND
            assert stats["dtype"] == "bf16"
            ref = _anchor_ref(small_es, obs, max(stats["buckets"]))
            scale = max(abs(float(ref[0])), 1e-6)
            assert abs(float(got[0]) - float(ref[0])) <= (
                BF16_DIVERGENCE_BOUND * max(scale, 2.0))
        finally:
            srv.shutdown(drain=True)

    def test_drift_policy_refused_as_bundle_error(self, tmp_path):
        """A policy whose bf16 divergence exceeds the bound at the
        anchor is REFUSED (the server's 409 / CLI exit 2), never served
        quantized-but-wrong; the same bundle serves f32 fine."""
        es = _make_small_es(policy=DriftPolicy, policy_kwargs={},
                            obs_norm=False)
        path = str(tmp_path / "drift")
        es.export_bundle(path, serve_bf16=True)
        from estorch_tpu.serve import PolicyServer
        from estorch_tpu.serve.warm import build_serving_batcher

        with pytest.raises(BundleError, match="divergence bound"):
            build_serving_batcher(load_bundle(path), max_batch=4,
                                  dtype="bf16")
        # the exact path still answers: f32 serving of the same bundle
        srv = PolicyServer(path, port=0, max_batch=4, dtype="f32")
        srv.start_background()
        try:
            with ServeClient(f"{srv.host}:{srv.port}") as c:
                out = c.predict([0.1, 0.2, 0.3])
            assert np.isfinite(np.asarray(out, np.float32)).all()
        finally:
            srv.shutdown(drain=True)

    def test_warm_export_fails_loudly_on_drift_policy(self, tmp_path):
        """warm=True + serve_bf16=True REPLAYS the bf16 verification at
        export: a drifting policy fails the export with the diagnosis
        instead of shipping a bundle every server will 409."""
        es = _make_small_es(policy=DriftPolicy, policy_kwargs={},
                            obs_norm=False)
        with pytest.raises(BundleError, match="divergence bound"):
            es.export_bundle(str(tmp_path / "drift_warm"), warm=True,
                             warm_max_batch=4, serve_bf16=True)
