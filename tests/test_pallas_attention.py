"""The attention kernel (ops/pallas_attention.py) against the two forms it
must equal: the XLA block-causal form of ``lm_blocks.causal_attention``
(what every CPU program runs) and a plain masked softmax in float32.

On CPU the kernel runs in interpret mode (``interpret=True`` is passed
here, never derived from the backend); ``tests/test_trace_stages.py``
lowers the SAME code through Mosaic for a described v5e, and the looped
cell's reference check judges it on the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import lm_tiny
import loop_tiny
from estorch_tpu.models import HybridLM, LoopedLM, lm_blocks
from estorch_tpu.ops import pallas_attention
from estorch_tpu.ops.pallas_attention import (attention_form,
                                              causal_attention, kernel_block,
                                              kernel_scope, scoped_interpret)

HD = 8  # a tiny head: only Mosaic needs 128 lanes, the interpreter none
# float32 on both sides, sums in another order: measured up to 5e-7
F32_TOL = 1e-5
# bfloat16 operands and probabilities: measured up to 9e-3 on values of
# magnitude 1 (one bfloat16 ulp there is 8e-3)
BF16_TOL = 3e-2


def _qkv(t, nq, nkv, dtype, seed=0, spread=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(
        (spread * jax.random.normal(k, (t, n * HD), jnp.float32)).astype(dtype)
        for k, n in zip(ks, (nq, nkv, nkv)))


def _plain(q, k, v, nq, nkv, scale):
    """Full masked softmax per head, float32 ``highest``."""
    t, f32, hi = q.shape[0], jnp.float32, "highest"
    qh = q.astype(f32).reshape(t, nq, HD)
    kh = jnp.repeat(k.astype(f32).reshape(t, nkv, HD), nq // nkv, axis=1)
    vh = jnp.repeat(v.astype(f32).reshape(t, nkv, HD), nq // nkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", qh, kh, precision=hi) * scale
    s = jnp.where(jnp.arange(t)[None, :] <= jnp.arange(t)[:, None], s,
                  -jnp.inf)
    return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), vh,
                      precision=hi).reshape(t, nq * HD)


def _through_lm_blocks(q, k, v, nq, nkv, scale, block):
    """``lm_blocks.causal_attention`` with projections that hand q, k, v
    through: its core alone, in whichever form the open scope selects."""
    given = {"q": q, "k": k, "v": v}

    def dense(p, noise, c, name, x):
        return (x if name == "o" else given[name]).astype(jnp.float32)

    return lm_blocks.causal_attention(
        dense, None, None, 0.0, q, num_heads=nq, num_kv_heads=nkv,
        head_dim=HD, scale=scale, block=block)


def _kernel(q, k, v, nq, nkv, scale, block_q, block_k=None):
    return causal_attention(
        q, k, v, num_heads=nq, num_kv_heads=nkv, head_dim=HD, scale=scale,
        block_q=block_q, block_k=block_k or block_q, interpret=True)


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


class TestKernelAgainstBothForms:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("blocks", [1, 2, 4])
    @pytest.mark.parametrize("nq, nkv", [(4, 4), (4, 1)])
    def test_kernel_is_the_masked_softmax(self, nq, nkv, blocks, dtype):
        block, scale = 8, HD ** -0.5
        q, k, v = _qkv(block * blocks, nq, nkv, dtype, seed=blocks)
        got = _kernel(q, k, v, nq, nkv, scale, block)
        assert got.shape == q.shape and got.dtype == q.dtype
        tol = F32_TOL if dtype == jnp.float32 else BF16_TOL
        np.testing.assert_allclose(
            _f32(got), _f32(_plain(q, k, v, nq, nkv, scale)), atol=tol)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("blocks", [1, 2, 4])
    @pytest.mark.parametrize("nq, nkv", [(4, 4), (4, 1)])
    def test_kernel_is_the_xla_block_causal_form(self, nq, nkv, blocks,
                                                 dtype):
        """The two forms ``lm_blocks.causal_attention`` dispatches between:
        outside a scope the XLA form, inside one the kernel."""
        block, scale = 8, 0.3
        q, k, v = _qkv(block * blocks, nq, nkv, dtype, seed=10 + blocks)
        xla = _through_lm_blocks(q, k, v, nq, nkv, scale, block)
        tol = F32_TOL if dtype == jnp.float32 else BF16_TOL
        np.testing.assert_allclose(
            _f32(_kernel(q, k, v, nq, nkv, scale, block)), _f32(xla),
            atol=tol)
        with kernel_scope(interpret=True):
            scoped = _through_lm_blocks(q, k, v, nq, nkv, scale, block)
        np.testing.assert_allclose(_f32(scoped), _f32(xla), atol=tol)

    @pytest.mark.parametrize("block_q, block_k", [
        (16, 8), (8, 16), (32, 8), (8, 32), (32, 32)])
    def test_unequal_blocks(self, block_q, block_k):
        """Key blocks the diagonal crosses part-way, rows that a visible
        block masks whole, and the clamp of the index map."""
        q, k, v = _qkv(32, 4, 2, jnp.float32, seed=3)
        np.testing.assert_allclose(
            _f32(_kernel(q, k, v, 4, 2, 0.25, block_q, block_k)),
            _f32(_plain(q, k, v, 4, 2, 0.25)), atol=F32_TOL)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_under_the_engines_nesting(self, dtype):
        """``vmap`` over pairs and signs inside a ``lax.scan`` over chunks:
        the batching rule of ``pallas_call``."""
        nq, nkv, t = 4, 2, 16
        q, k, v = (jnp.stack([jnp.stack([jnp.stack(
            [_qkv(t, nq, nkv, dtype, seed=100 * c + 10 * p + s)[i]
             for s in range(2)]) for p in range(2)]) for c in range(3)])
            for i in range(3))

        def chunk(_, xs):
            return 0, jax.vmap(jax.vmap(
                lambda q, k, v: _kernel(q, k, v, nq, nkv, 0.25, 8)))(*xs)

        _, got = jax.lax.scan(chunk, 0, (q, k, v))
        want = jax.vmap(jax.vmap(jax.vmap(
            lambda q, k, v: _plain(q, k, v, nq, nkv, 0.25))))(q, k, v)
        np.testing.assert_allclose(
            _f32(got), _f32(want),
            atol=F32_TOL if dtype == jnp.float32 else BF16_TOL)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_first_row_sees_one_key_and_the_last_row_all(self, dtype):
        nq, nkv, t = 4, 2, 32
        q, k, v = _qkv(t, nq, nkv, dtype, seed=7)
        got = _f32(_kernel(q, k, v, nq, nkv, 0.25, 8))
        # one visible key: its probability is 1 and the context IS v[0]
        first = np.repeat(_f32(v)[0].reshape(nkv, HD), nq // nkv, axis=0)
        np.testing.assert_array_equal(got[0], first.reshape(-1))
        # the last row: a whole softmax over every key, no mask
        f32 = jnp.float32
        qh = q.astype(f32).reshape(t, nq, HD)[-1]
        kh = jnp.repeat(k.astype(f32).reshape(t, nkv, HD), 2, axis=1)
        vh = jnp.repeat(v.astype(f32).reshape(t, nkv, HD), 2, axis=1)
        p = jax.nn.softmax(jnp.einsum("hd,khd->hk", qh, kh) * 0.25, -1)
        np.testing.assert_allclose(
            got[-1], np.asarray(jnp.einsum("hk,khd->hd", p, vh)).reshape(-1),
            atol=F32_TOL if dtype == jnp.float32 else BF16_TOL)

    @pytest.mark.parametrize("spread", [1.0, 30.0, 300.0])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_no_nan_from_the_initial_max(self, dtype, spread):
        """The running max starts at -inf and masked scores are -inf:
        neither meets the other in a subtraction, whatever the scores'
        size (at spread 300 one key takes all the weight)."""
        q, k, v = _qkv(32, 4, 4, dtype, seed=11, spread=spread)
        got = _f32(_kernel(q, k, v, 4, 4, HD ** -0.5, 8))
        assert np.isfinite(got).all()
        # scores of magnitude spread² · 3: a last-bit difference in one
        # moves its probability by that much, hence the relative part
        np.testing.assert_allclose(
            got, _f32(_plain(q, k, v, 4, 4, HD ** -0.5)),
            rtol=1e-3 if dtype == jnp.float32 else 5e-2,
            atol=(F32_TOL if dtype == jnp.float32 else BF16_TOL) * spread)

    def test_default_blocks_are_the_kernels_own(self, monkeypatch):
        monkeypatch.setattr(pallas_attention, "BLOCKS", (16, 8))
        q, k, v = _qkv(24, 4, 2, jnp.float32, seed=5)  # 24 = 3 blocks of 8
        got = causal_attention(q, k, v, num_heads=4, num_kv_heads=2,
                               head_dim=HD, scale=0.25, interpret=True)
        np.testing.assert_allclose(
            _f32(got), _f32(_plain(q, k, v, 4, 2, 0.25)), atol=F32_TOL)

    @pytest.mark.parametrize("case, match", [
        ("ragged", "whole number"), ("heads", "multiple of key/value"),
        ("shape", "are not")])
    def test_sizes_are_validated(self, case, match):
        q, k, v = _qkv(24, 4, 2, jnp.float32)
        kw = dict(num_heads=4, num_kv_heads=2, head_dim=HD, scale=1.0,
                  block_q=8, block_k=8, interpret=True)
        if case == "ragged":
            kw["block_q"] = 16
        elif case == "heads":
            kw["num_kv_heads"] = 3
        else:
            k = k[:, :HD]
        with pytest.raises(ValueError, match=match):
            causal_attention(q, k, v, **kw)


# --------------------------------------------------------------- the rule

class TestTheRule:
    @pytest.mark.parametrize("platform, devices, head_dim, length, form", [
        ("tpu", 1, 128, 4096, "kernel"),   # ouro-2.6b-es-4k-1chip
        ("tpu", 4, 64, 4096, "xla"),       # granite-h-micro-es-4k-4chip
        ("tpu", 4, 128, 4096, "xla"),      # operands sharded: needs shard_map
        ("tpu", 1, 64, 4096, "xla"),       # a head is half a lane tile
        ("tpu", 1, 192, 4096, "xla"),
        ("tpu", 1, 256, 4096, "kernel"),
        ("tpu", 1, 128, 4000, "xla"),      # no block divides the sequence
        ("tpu", 1, 128, 384, "kernel"),    # three blocks of 128
        ("tpu", 1, 128, 21, "xla"),
        ("tpu", 2, 128, 4096, "xla"),
        ("cpu", 1, 128, 4096, "xla"),      # every CPU mesh
        ("cpu", 8, 128, 4096, "xla"),
        ("gpu", 1, 128, 4096, "xla"),
    ])
    def test_form_from_what_the_engine_observes(self, platform, devices,
                                                head_dim, length, form):
        assert attention_form(platform, devices, head_dim, length) == form

    @pytest.mark.parametrize("length, block", [
        (4096, 1024), (1024, 1024), (1536, 512), (768, 256), (384, 128),
        (128, 128), (4000, None), (64, None), (21, None)])
    def test_the_kernels_block(self, length, block):
        assert kernel_block(length) == block

    def test_published_shapes_are_what_the_rows_say(self):
        ouro, granite = loop_tiny.published(), lm_tiny.published()
        assert (ouro["head_dim"], ouro["horizon"]) == (128, 4096)
        assert granite["horizon"] == 4096
        assert granite["hidden_size"] // granite["num_attention_heads"] == 64

    @pytest.mark.parametrize("model", ["looped", "hybrid"])
    def test_a_call_outside_an_engine_takes_the_xla_form(self, model):
        """``apply`` on the default device: no scope is open, so the
        program holds no ``pallas_call``; inside a scope it does."""
        lm = (LoopedLM(**loop_tiny.TINY) if model == "looped"
              else HybridLM(**lm_tiny.TINY))
        tokens = jnp.arange(16) % 64
        variables = lm.init(jax.random.PRNGKey(0), tokens)
        assert scoped_interpret() is None
        assert "pallas_call" not in str(jax.make_jaxpr(lm.apply)(
            variables, tokens))
        with kernel_scope(interpret=True):
            assert scoped_interpret() is True
            inside = str(jax.make_jaxpr(lm.apply)(variables, tokens))
        assert scoped_interpret() is None
        assert "pallas_call" in inside

    def test_models_have_the_head_size_es_hands_the_engine(self):
        assert LoopedLM(**loop_tiny.TINY).head_dim == 8
        assert HybridLM(**lm_tiny.TINY).head_dim == 8


# ----------------------------------------------------- through the engine

def _lm_es(devices, model_shards=1, policy=LoopedLM, **over):
    from estorch_tpu import ES, JaxAgent
    from estorch_tpu.envs import TokenScoreEnv

    tiny, env = ((loop_tiny.TINY, loop_tiny.ENV) if policy is LoopedLM
                 else (lm_tiny.TINY, lm_tiny.ENV))
    kw = dict(
        policy=policy, agent=JaxAgent, optimizer=optax.adam,
        population_size=8, sigma=0.02, policy_kwargs=tiny,
        agent_kwargs={"env": TokenScoreEnv(**{**env, "seq_len": 16})},
        optimizer_kwargs={"learning_rate": 1e-2}, shard_params=True,
        model_shards=model_shards, low_rank=1, noise_mode="table",
        table_size=1 << 18, device=list(devices))
    kw.update(over)
    return ES(**kw)


@pytest.fixture
def kernel_attention(monkeypatch):
    """``with kernel_attention():`` — sharded engines built inside resolve
    ``attention_form == "kernel"`` on the suite's CPU mesh, where the rule
    says "xla"; ``_pallas_interpret`` comes from the mesh, so the kernel
    runs under the Pallas interpreter.  A fake substituted by the test:
    nothing in the package reads it."""
    import contextlib

    from estorch_tpu.parallel.sharded import ShardedESEngine

    @contextlib.contextmanager
    def forced():
        with monkeypatch.context() as m:
            m.setattr(ShardedESEngine, "_resolve_attention_form",
                      lambda self, head_dim: "kernel")
            yield

    return forced


class TestThroughTheShardedEngine:
    @pytest.mark.parametrize("policy", [LoopedLM, HybridLM])
    @pytest.mark.parametrize("n_devices, model_shards", [(1, 1), (4, 2)])
    def test_every_cpu_mesh_resolves_xla(self, devices8, policy, n_devices,
                                         model_shards):
        es = _lm_es(devices8[:n_devices], model_shards, policy=policy)
        assert es.engine.attention_form == "xla"
        assert es.run_manifest()["config"]["attention_form"] == "xla"
        assert es.obs.counters.snapshot()["attention_form"] == "xla"

    def test_a_policy_without_attention_has_no_form(self, devices8):
        from estorch_tpu import ES, JaxAgent, MLPPolicy
        from estorch_tpu.envs import CartPole

        es = ES(policy=MLPPolicy, agent=JaxAgent, optimizer=optax.adam,
                population_size=8, sigma=0.05,
                policy_kwargs={"action_dim": 2, "hidden": (8,)},
                agent_kwargs={"env": CartPole(), "horizon": 5},
                optimizer_kwargs={"learning_rate": 1e-2},
                shard_params=True, device=list(devices8[:1]))
        assert es.engine.attention_form is None
        assert es.run_manifest()["config"]["attention_form"] is None
        assert "attention_form" not in es.obs.counters.snapshot()

    @pytest.mark.parametrize("policy", [LoopedLM, HybridLM])
    def test_forced_kernel_runs_the_generation_the_xla_form_runs(
            self, devices8, kernel_attention, policy):
        """Two generations through ``ES.train`` on one device, the policy's
        attention once in each form: the same members' fitness and the
        same trained parameters, to the order of float32 sums."""
        ref = _lm_es(devices8[:1], policy=policy)
        with kernel_attention():
            kern = _lm_es(devices8[:1], policy=policy)
        assert (ref.engine.attention_form, kern.engine.attention_form) \
            == ("xla", "kernel")
        assert kern.run_manifest()["config"]["attention_form"] == "kernel"
        assert kern.obs.counters.snapshot()["attention_form"] == "kernel"
        programs = [str(jax.make_jaxpr(es.engine._generation_step)(
            es.state, es.table.data)) for es in (ref, kern)]
        assert ["pallas_call" in text for text in programs] == [False, True]
        ref.train(2, verbose=False)
        kern.train(2, verbose=False)
        np.testing.assert_allclose(
            [r["reward_mean"] for r in kern.history],
            [r["reward_mean"] for r in ref.history], rtol=1e-5)
        np.testing.assert_allclose(np.asarray(kern.state.params_flat),
                                   np.asarray(ref.state.params_flat),
                                   atol=1e-4, rtol=0)

    def test_forced_kernel_in_bfloat16_agrees_to_bfloat16(
            self, devices8, kernel_attention):
        ref = _lm_es(devices8[:1], compute_dtype="bfloat16")
        with kernel_attention():
            kern = _lm_es(devices8[:1], compute_dtype="bfloat16")
        ref.state, want = ref.engine.generation_step(ref.state)
        kern.state, got = kern.engine.generation_step(kern.state)
        # fitness is a mean log p of about -log(64) = -4.16
        np.testing.assert_allclose(got["fitness"], want["fitness"],
                                   atol=2e-2)
        assert np.isfinite(np.asarray(got["fitness"])).all()
