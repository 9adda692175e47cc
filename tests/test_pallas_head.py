"""The next-token head's kernel (ops/pallas_head.py) against the form it
must equal: the XLA form of ``lm_blocks.score_next_tokens`` (what every CPU
program runs: float32 logits a block, ``logsumexp`` and a gather).

On CPU the kernel runs in interpret mode (``interpret=True`` is passed
here, or comes from the ``kernel_scope`` a test opens; never derived from
the backend); ``tests/test_trace_stages.py`` lowers the SAME code through
Mosaic for a described v5e, and the sequence cells' reference checks judge
it on the chip.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import lm_tiny
import loop_tiny
from pallas_costs import declared_costs, pallas_calls
from estorch_tpu.models import HybridLM, LoopedLM, MoELM, lm_blocks
from estorch_tpu.models.perturbed import perturbed_dense
from estorch_tpu.ops.pallas_attention import kernel_scope
from estorch_tpu.ops.pallas_head import (fits, head_cost, head_facts,
                                         head_form_why, score_rows)

# float32 on both sides, sums in another order: measured up to 2e-6 on
# scores of magnitude 7
F32_TOL = 2e-5
# the smallest shapes the rule takes: hidden of one 128-lane block, one row
# tile of 512 positions
HIDDEN, LENGTH = 128, 512
SIGNS = jnp.asarray([1.0, -1.0], jnp.float32)


def _head(vocab, transposed, rank, pairs=2, hidden=HIDDEN, length=LENGTH,
          dtype=jnp.float32, seed=0):
    """``(h [pairs, 2, T, hidden], w, noise | None, tokens [pairs, T])``:
    a pair's two signs read ONE factor pair, as the engine hands them."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    h = jax.random.normal(ks[0], (pairs, 2, length, hidden)).astype(dtype)
    w = (0.3 * jax.random.normal(
        ks[1], (vocab, hidden) if transposed else (hidden, vocab))
    ).astype(dtype)
    noise = None if not rank else (
        jax.random.normal(ks[2], (pairs, w.shape[0], rank)),
        jax.random.normal(ks[3], (pairs, w.shape[1], rank)))
    # targets in the first and in the last (masked) vocabulary tile
    tokens = jax.random.randint(ks[4], (pairs, length), 0, vocab)
    tokens = tokens.at[:, 1].set(0).at[:, 2].set(vocab - 1)
    return h, w, noise, tokens


def _nested(score, w, noise):
    """``score(h [T, hidden], tokens, w, noise, c)`` under the engine's
    nesting: a ``vmap`` over pairs around one over a pair's two signs;
    ``w`` is batched by neither, the factors by the pairs, ``c`` by the
    signs."""
    def pairs(h, tokens, factors):
        def pair(hp, tp, fp):
            return jax.vmap(lambda hs, s: score(hs, tp, w, fp, 0.3 * s))(
                hp, SIGNS)
        return jax.vmap(pair)(h, tokens, factors)

    return lambda h, tokens: pairs(h, tokens, noise)


def _through_blocks(transposed, scaling, block=96):
    """``lm_blocks.score_next_tokens``' scores, in whichever form the open
    scope selects; ``block`` does not divide 512: the XLA form pads."""
    def score(h, tokens, w, noise, c):
        return lm_blocks.score_next_tokens(
            h, tokens, w, noise, c, block, scaling, leaf="head",
            transposed=transposed)[0]
    return score


def _direct(transposed, scaling, block_rows, block_vocab):
    """The kernel called as ``score_next_tokens`` calls it, at tiles of the
    test's own (under the interpreter any sizes run)."""
    def score(h, tokens, w, noise, c):
        xs = bt = None
        if noise is not None:
            a, b = (noise[1], noise[0]) if transposed else noise
            xs = (h @ a) * (c / np.sqrt(a.shape[-1]))
            bt = b.T
        return score_rows(
            h, w, jnp.pad(tokens[1:], (0, 1)), xs, bt, transposed=transposed,
            logits_scaling=scaling, interpret=True, block_rows=block_rows,
            block_vocab=block_vocab)[:-1]
    return score


class TestKernelAgainstTheXlaForm:
    @pytest.mark.parametrize("scaling", [None, 8.0], ids=["unscaled", "div8"])
    @pytest.mark.parametrize("vocab", [2048, 2020], ids=["whole", "tail"])
    @pytest.mark.parametrize("rank", [0, 1, 4], ids=["centre", "r1", "r4"])
    @pytest.mark.parametrize("transposed", [False, True],
                             ids=["untied", "tied"])
    def test_inside_a_scope_score_next_tokens_is_the_kernel(
            self, transposed, rank, vocab, scaling):
        """Both head layouts, the centre alone and corrections of rank 1
        and 4, a vocabulary of whole tiles and one with a masked tail
        (16,160 / 8), with and without ``logits_scaling``, under the
        engine's pair x sign nesting: the scores and the last position's
        logits of the XLA form (whose block of 96 pads the 512 rows)."""
        h, w, noise, tokens = _head(vocab, transposed, rank)

        def score():    # a new function a trace: jit caches by function
            return _nested(_through_blocks(transposed, scaling), w, noise)

        want = jax.jit(score())(h, tokens)
        assert not pallas_calls(score(), h, tokens)
        with kernel_scope(interpret=True):
            calls = pallas_calls(score(), h, tokens)
            got = jax.jit(score())(h, tokens)
        assert len(calls) == 1      # all four members' rows in ONE call
        assert got.shape == want.shape == (2, 2, LENGTH - 1)
        np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)
        # the targets the test put in the first and the last tile
        assert float(jnp.abs(got[..., :2] - want[..., :2]).max()) < F32_TOL
        if rank:    # the two signs differ by the correction alone
            assert float(jnp.abs(got[:, 0] - got[:, 1]).max()) > 1e-2

    def test_the_last_logits_are_the_xla_matmul_in_both_forms(self):
        h, w, noise, tokens = _head(2020, False, 1, pairs=1)
        args = (h[0, 0], tokens[0], w, (noise[0][0], noise[1][0]), 0.3, 96)
        want = lm_blocks.score_next_tokens(*args, leaf="head")[1]
        with kernel_scope(interpret=True):
            got = lm_blocks.score_next_tokens(*args, leaf="head")[1]
        np.testing.assert_array_equal(got, want)
        np.testing.assert_allclose(want, perturbed_dense(
            h[0, 0, -1:], w, (noise[0][0], noise[1][0]), 0.3)[0], atol=1e-6)

    @pytest.mark.parametrize("transposed", [False, True],
                             ids=["untied", "tied"])
    def test_bfloat16_operands_agree_to_bfloat16(self, transposed):
        """Operands in bfloat16, everything after the product in float32:
        both forms round the same operands, so they differ by the order of
        the float32 sums alone."""
        h, w, noise, tokens = _head(2020, transposed, 1, dtype=jnp.bfloat16)
        want = jax.jit(_nested(_through_blocks(transposed, None), w, noise))(
            h, tokens)
        with kernel_scope(interpret=True):
            assert len(pallas_calls(_nested(_through_blocks(
                transposed, None), w, noise), h, tokens)) == 1
            got = jax.jit(_nested(_through_blocks(transposed, None), w,
                                  noise))(h, tokens)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=0)

    @pytest.mark.parametrize("dtype, tol", [(jnp.float32, F32_TOL),
                                            (jnp.bfloat16, 2e-4)],
                             ids=["f32", "bf16"])
    @pytest.mark.parametrize("vocab", [4096, 4112], ids=["whole", "tail"])
    def test_the_tied_layout_at_the_first_cells_width_that_runs_it(
            self, vocab, dtype, tol):
        """``zaya1-es-8k-1chip`` is the first cell whose head is the TIED
        layout in the kernel: a ``[vocabulary, 2048]`` table read transposed
        under rank-1 factors whose ``A`` is the table's rows' and ``B`` the
        hidden width's.  Hidden 2,048 as in the cell, a vocabulary of whole
        4,096-row tiles and one with a short last tile (the cell's 32,784
        rows end in one), one pair's two signs over 512 positions: the XLA
        form's scores."""
        h, w, noise, tokens = _head(vocab, True, 1, pairs=1, hidden=2048,
                                    dtype=dtype, seed=3)
        w = (w.astype(jnp.float32) / 8).astype(dtype)   # logits of spread 4

        def score():    # a new function a trace: jit caches by function
            return _nested(_through_blocks(True, None), w, noise)

        want = jax.jit(score())(h, tokens)
        with kernel_scope(interpret=True):
            assert len(pallas_calls(score(), h, tokens)) == 1
            got = jax.jit(score())(h, tokens)
        assert got.shape == want.shape == (1, 2, LENGTH - 1)
        assert got.dtype == jnp.float32
        # (scores down to -190 under the rank-1 correction: the float32
        # sums' order shows in the seventh digit)
        np.testing.assert_allclose(got, want, atol=tol, rtol=1e-6)
        # the two signs differ by the correction alone
        assert float(jnp.abs(got[:, 0] - got[:, 1]).max()) > 1e-2

    @pytest.mark.parametrize("rank", [1, 6], ids=["r1", "r6"])
    @pytest.mark.parametrize("block_rows, block_vocab", [(16, 32), (32, 128)])
    def test_several_row_tiles_a_member_read_that_members_factor(
            self, block_rows, block_vocab, rank):
        """Tiles of the test's own: a member spans several row tiles and
        the vocabulary several tiles with a short last one; every row tile
        reads the ``Bᵀ`` of ITS member (rank 6: the correction as a dot)."""
        h, w, noise, tokens = _head(200, False, rank, pairs=3, hidden=16,
                                    length=64, seed=2)
        want = jax.jit(_nested(_through_blocks(False, None, 24), w, noise))(
            h, tokens)
        got = jax.jit(_nested(_direct(False, None, block_rows, block_vocab),
                              w, noise))(h, tokens)
        np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)

    def test_members_with_their_own_weights_go_one_by_one(self):
        """The materialised form hands every member its own ``W``: nothing
        to share, so the call is batched by ``pallas_call``'s own rule."""
        h, w, _, tokens = _head(200, False, 0, pairs=3, hidden=16, length=32)
        ws = w[None] * jnp.arange(1.0, 4.0)[:, None, None]

        def scores(score):
            return jax.vmap(lambda hp, tp, wp: score(hp, tp, wp, None, 0.0))(
                h[:, 0], tokens, ws)

        want = scores(_through_blocks(False, None, 8))
        got = scores(_direct(False, None, 16, 128))
        np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)

    def test_no_nan_from_the_initial_max_or_the_masked_tail(self):
        """Logits of magnitude 60 and a last tile of ONE valid entry: the
        running max starts at ``-inf`` and the tail is ``-inf`` too."""
        h, w, _, tokens = _head(129, True, 0, pairs=1, hidden=16, length=32)
        got = _direct(True, None, 16, 128)(20.0 * h[0, 0], tokens[0], w,
                                           None, 0.0)
        want = _through_blocks(True, None, 8)(20.0 * h[0, 0], tokens[0], w,
                                              None, 0.0)
        assert bool(jnp.isfinite(got).all())
        np.testing.assert_allclose(got, want, atol=1e-3, rtol=1e-5)

    @pytest.mark.parametrize("case, match", [
        ("half a correction", "xs AND bt"),
        ("rows", "whole number of row tiles"),
        ("contraction", "does not contract"),
    ])
    def test_sizes_are_validated(self, case, match):
        h, w, _, tokens = _head(64, False, 0, pairs=1, hidden=16, length=32)
        h, tokens, kw = h[0, 0], tokens[0], dict(interpret=True)
        if case == "half a correction":
            kw["xs"] = jnp.zeros((32, 1))
        elif case == "rows":
            kw["block_rows"] = 24
        else:
            kw["transposed"] = True
        with pytest.raises(ValueError, match=match):
            score_rows(h, w, tokens, **kw)


class TestTheRule:
    @pytest.mark.parametrize("traced, hidden, length, itemsize, form", [
        (True, 2048, 4096, 2, "kernel"),
        (True, 128, 512, 4, "kernel"),
        (True, 8192, 4096, 2, "kernel"),
        (False, 2048, 4096, 2, "xla"),  # no scope is opened: any other mesh
        (False, 128, 512, 4, "xla"),
        (True, 2000, 4096, 2, "xla"),   # not whole 128-lane blocks
        (True, 32, 512, 4, "xla"),
        (True, 8192, 4096, 4, "xla"),   # too wide to contract whole
        (True, 16384, 4096, 2, "xla"),
        (True, 2048, 4000, 2, "xla"),   # not whole row tiles of 512
        (True, 2048, 1536, 2, "kernel"),
        (True, 2048, 256, 2, "xla"),
    ])
    def test_form_from_what_the_engine_observes(self, traced, hidden,
                                                length, itemsize, form):
        """The head's own rule: whether Mosaic kernels may be traced, and
        the head's shapes; the attention's form is no part of it."""
        assert head_form_why((traced, "where"), hidden, length,
                             itemsize)[0] == form
        assert fits(hidden, length, itemsize) == (
            form == "kernel" or not traced)

    @pytest.mark.parametrize("traced, shapes, form, says", [
        ((True, "one TPU device"), (2048, 4096, 2), "kernel",
         "one TPU device; a hidden width of whole 128-lane blocks"),
        ((False, "the devices are 'cpu', not TPUs"), (2048, 4096, 2), "xla",
         "the devices are 'cpu', not TPUs"),
        ((True, "one TPU device"), (2000, 4096, 2), "xla",
         "hidden states 2000 wide in 2-byte operands over 4096 positions"),
        ((True, "one TPU device"), (2048, 4000, 2), "xla",
         "over whole row tiles of 512"),
    ])
    def test_the_rule_names_what_decided(self, traced, shapes, form, says):
        got, why = head_form_why(traced, *shapes)
        assert got == form and says in why


    def test_outside_a_scope_or_past_the_shapes_the_xla_form(self):
        """``score_next_tokens`` itself: no scope, a hidden width that is
        not whole lane blocks, a sequence no row tile divides or a dense
        noise array each leave the program without a ``pallas_call``."""
        def program(hidden, length, dense=False):
            h, w, noise, tokens = _head(64, False, 1, pairs=1, hidden=hidden,
                                        length=length)
            noise = (jnp.zeros(w.shape) if dense
                     else (noise[0][0], noise[1][0]))
            return pallas_calls(
                lambda: lm_blocks.score_next_tokens(
                    h[0, 0], tokens[0], w, noise, 0.3, 64, leaf="head"))

        assert not program(HIDDEN, LENGTH)
        with kernel_scope(interpret=True):
            assert len(program(HIDDEN, LENGTH)) == 1
            assert not program(HIDDEN, LENGTH, dense=True)
            assert not program(96, LENGTH)
            assert not program(HIDDEN, 500)

    def test_the_models_say_the_width_es_hands_the_engine(self):
        import lm_tiny
        import moe_tiny

        for lm in (LoopedLM(**loop_tiny.TINY), HybridLM(**lm_tiny.TINY),
                   MoELM(**moe_tiny.TINY)):
            assert dict(lm.declaration().kernels)[head_facts] == (32,)


class TestTheDeclaredCost:
    @pytest.mark.parametrize("rank", [0, 1, 4])
    @pytest.mark.parametrize("vocab", [2048, 2020])
    def test_the_call_declares_the_pure_functions_cost(self, vocab, rank):
        """What the ``pallas_call`` under the engine's nesting hands XLA is
        ``head_cost`` of the MERGED rows: the counted matmul ``2 · rows ·
        hidden · vocab`` at least (what ``benchmark/layers/part.py`` asks
        of the operations under ``es.head``), ``W`` once per row tile."""
        h, w, noise, tokens = _head(vocab, False, rank)
        with kernel_scope(interpret=True):
            (cost,) = declared_costs(
                _nested(_through_blocks(False, None), w, noise), h, tokens)
        rows = 2 * 2 * LENGTH
        want = head_cost(rows, HIDDEN, vocab, rank, 512, 1024, 4)
        assert (cost.flops, cost.transcendentals, cost.bytes_accessed) == (
            want.flops, want.transcendentals, want.bytes_accessed)
        assert cost.flops == 2 * rows * vocab * (HIDDEN + rank)
        assert cost.flops >= 2 * rows * HIDDEN * vocab
        # four row tiles, each streaming the whole of W past it
        assert cost.bytes_accessed >= 4 * HIDDEN * vocab * 4

    def test_the_published_heads(self):
        """The looped cell's call: 2 members x 4,096 rows against
        ``[2048, 49152]``: 1.649 TFLOP, ``W`` read 16 times."""
        cost = head_cost(8192, 2048, 49152, 1, 512, 1024, 2)
        assert cost.flops == 2 * 8192 * 49152 * 2049
        assert cost.transcendentals == 8192 * (49152 + 48 + 1)
        assert 16 * 2048 * 49152 * 2 < cost.bytes_accessed < 1.03 * (
            16 * 2048 * 49152 * 2)


class TestWhatTheProgramHolds:
    def test_w_enters_once_and_is_never_broadcast(self):
        """Under the pair x sign ``vmap``s the ONE call reads ``W`` as the
        un-batched array it is, and no ``broadcast_in_dim`` of the program
        makes a stack of ``W``s (a copy a member)."""
        h, w, noise, tokens = _head(2020, False, 1)
        score = _nested(_through_blocks(False, None), w, noise)
        with kernel_scope(interpret=True):
            jaxpr = jax.make_jaxpr(score)(h, tokens)
            (call,) = pallas_calls(score, h, tokens)
        assert [v.aval.shape for v in call.invars][:3] == [
            (4 * LENGTH, HIDDEN), (HIDDEN, 2020), (4 * LENGTH, 1)]

        def broadcasts(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    continue    # its body's tiles live in VMEM
                if eqn.primitive.name == "broadcast_in_dim":
                    yield eqn.outvars[0].aval
                for value in eqn.params.values():
                    inner = getattr(value, "jaxpr", value)
                    if hasattr(inner, "eqns"):
                        yield from broadcasts(inner)

        made = [aval.shape for aval in broadcasts(jaxpr.jaxpr)]
        assert made and all(shape[-2:] != w.shape for shape in made), made

    @pytest.mark.parametrize("leaf, transposed", [("head", False),
                                                  ("embed", True)])
    def test_the_call_sits_under_the_heads_stage_and_part(self, leaf,
                                                          transposed):
        """The call's name stack carries ``es.head/of.<leaf>`` through both
        ``vmap``s, and ``h A`` of the correction ``es.perturb`` beneath
        them: a trace books the kernel to the part the XLA form's logits
        were booked to."""
        h, w, noise, tokens = _head(2020, transposed, 1)

        def score(h, tokens, w, noise, c):
            return lm_blocks.score_next_tokens(
                h, tokens, w, noise, c, 96, leaf=leaf,
                transposed=transposed)[0]

        with kernel_scope(interpret=True):
            text = jax.jit(_nested(score, w, noise)).lower(
                h, tokens).as_text(debug_info=True)
        # (the vmaps wrap the outermost scope: "vmap(vmap(es.head))/of.…")
        names = re.findall(r'loc\("(jit\([^"]*)"', text)
        under = rf"es\.head\)*/of\.{leaf}/"
        assert any(re.search(under + "next_token_scores/pallas_call", n)
                   for n in names), names[:5]
        assert any(re.search(under + r"es\.perturb/dot_general", n)
                   for n in names)


# ----------------------------------------------------- through the engine

# the smallest models whose head fits the rule, by the head's layout:
# (model, its tiny sizes, what is changed of them)
HEADS = {
    # an untied [hidden, vocab] kernel, no scaling
    "untied": (LoopedLM, loop_tiny, {
        "layer_types": ("full_attention",), "total_ut_steps": 2}),
    # granite's: the embedding read transposed, the logits divided by 8,
    # and heads of 64 with values of 64, which the attention's kernel
    # turns away
    "tied_scaled": (HybridLM, lm_tiny, {
        "layer_types": ("attention", "mamba"), "mamba_chunk_size": 64,
        "num_attention_heads": 2, "num_key_value_heads": 1}),
    "tied": (HybridLM, lm_tiny, {
        "layer_types": ("attention", "mamba"), "mamba_chunk_size": 64,
        "num_attention_heads": 2, "num_key_value_heads": 1,
        "logits_scaling": 1.0}),
}


def _lm_es(devices, model_shards=1, head="untied", population_size=4,
           **policy):
    from estorch_tpu import ES, JaxAgent
    from estorch_tpu.envs import TokenScoreEnv

    model, tiny, sizes = HEADS[head]
    return ES(
        policy=model, agent=JaxAgent, optimizer=optax.adam,
        population_size=population_size, sigma=0.02,
        policy_kwargs={**tiny.TINY, "hidden_size": HIDDEN,
                       "attention_block": 128, "head_block": 96, **sizes,
                       **policy},
        agent_kwargs={"env": TokenScoreEnv(
            **{**tiny.ENV, "seq_len": LENGTH})},
        optimizer_kwargs={"learning_rate": 1e-2}, shard_params=True,
        model_shards=model_shards, low_rank=1, noise_mode="table",
        table_size=1 << 18, device=list(devices))


def _program(es):
    return lambda: es.engine._generation_step(es.state, es.table.data)


class TestThroughTheShardedEngine:
    @pytest.mark.parametrize("n_devices, model_shards", [(1, 1), (4, 2),
                                                         (4, 1)])
    def test_every_cpu_mesh_resolves_xla(self, devices8, n_devices,
                                         model_shards):
        es = _lm_es(devices8[:n_devices], model_shards)
        assert es.engine.kernel_facts["head_form"] == "xla"
        assert es.run_manifest()["config"]["head_form"] == "xla"
        assert es.obs.counters.snapshot()["head_form"] == "xla"

    def test_a_policy_without_a_head_has_no_form(self, devices8):
        from estorch_tpu import ES, JaxAgent, MLPPolicy
        from estorch_tpu.envs import CartPole

        es = ES(policy=MLPPolicy, agent=JaxAgent, optimizer=optax.adam,
                population_size=8, sigma=0.05,
                policy_kwargs={"action_dim": 2, "hidden": (8,)},
                agent_kwargs={"env": CartPole(), "horizon": 5},
                optimizer_kwargs={"learning_rate": 1e-2},
                shard_params=True, device=list(devices8[:1]))
        assert "head_form" not in es.engine.kernel_facts
        assert es.run_manifest()["config"]["head_form"] is None
        assert "head_form" not in es.obs.counters.snapshot()

    def test_inside_the_engines_scope_the_head_follows_its_shapes(
            self, devices8, kernel_attention):
        """One device, the scope opened: a hidden width of 128 over 512
        positions takes the head's kernel, a hidden width of 96 keeps the
        XLA form beside the attention's kernel; the gauge, the manifest and
        the traced program say the same."""
        with kernel_attention():
            fit = _lm_es(devices8[:1])
            narrow = _lm_es(devices8[:1], hidden_size=96)
        for es, form in ((fit, "kernel"), (narrow, "xla")):
            assert es.engine.kernel_facts["attention_form"] == "kernel"
            assert es.engine.kernel_facts["head_form"] == form
            assert es.run_manifest()["config"]["head_form"] == form
            assert es.obs.counters.snapshot()["head_form"] == form
            text = str(jax.make_jaxpr(es.engine._generation_step)(
                es.state, es.table.data))
            assert "causal_attention" in text
            assert ("next_token_scores" in text) == (form == "kernel")

    def test_the_generation_the_xla_form_runs(self, devices8,
                                              kernel_attention):
        """Two generations through ``ES.train`` on one device, the head
        (and the attention) once in each form: the same members' fitness
        and the same trained parameters, to the order of float32 sums."""
        ref = _lm_es(devices8[:1])
        with kernel_attention():
            kern = _lm_es(devices8[:1])
        assert (ref.engine.kernel_facts["head_form"],
                kern.engine.kernel_facts["head_form"]) == (
            "xla", "kernel")
        ref.train(2, verbose=False)
        kern.train(2, verbose=False)
        np.testing.assert_allclose(
            [r["reward_mean"] for r in kern.history],
            [r["reward_mean"] for r in ref.history], rtol=1e-5)
        np.testing.assert_allclose(np.asarray(kern.state.params_flat),
                                   np.asarray(ref.state.params_flat),
                                   atol=1e-4, rtol=0)


class TestOnAMeshOfSeveralDevices:
    """The rule "TPU devices and whole members on a chip", and the
    partition that makes it true: on a (2, 2) mesh whose centre is
    gathered the engine evaluates a chunk's pairs under a ``shard_map``, so
    the head's kernel runs a chip's OWN members.  ``as_tpu`` lets the
    suite's CPU mesh resolve the rules as TPU devices would; the kernels
    run under the interpreter."""

    @pytest.mark.parametrize("head", sorted(HEADS))
    def test_every_members_fitness_is_the_xla_heads(self, devices8, as_tpu,
                                                    head):
        """(2, 2), gathered, one pair a chip: the fitness of all 8 members
        and the update, head in the kernel against head in the XLA form,
        to the order of float32 sums."""
        ref = _lm_es(devices8[:4], 2, head, population_size=8)
        with as_tpu():
            kern = _lm_es(devices8[:4], 2, head, population_size=8)
        assert (ref.engine.centre_form, kern.engine.centre_form) == (
            "gathered", "gathered")
        assert (ref.engine.kernel_facts["head_form"],
                kern.engine.kernel_facts["head_form"]) == (
            "xla", "kernel")
        assert [len(pallas_calls(_program(es))) for es in (ref, kern)] == [
            0, 1]
        ref.state, want = ref.engine.generation_step(ref.state)
        kern.state, got = kern.engine.generation_step(kern.state)
        assert np.isfinite(np.asarray(got["fitness"])).all()
        # a fitness is a mean log p of about -log(64) = -4.16
        np.testing.assert_allclose(got["fitness"], want["fitness"],
                                   atol=F32_TOL, rtol=0)
        np.testing.assert_allclose(np.asarray(kern.state.params_flat),
                                   np.asarray(ref.state.params_flat),
                                   atol=1e-4, rtol=0)

    @pytest.mark.parametrize("population_size, pairs_a_chip", [(8, 1),
                                                               (16, 2)])
    def test_a_chips_call_holds_its_own_members_rows(
            self, devices8, as_tpu, population_size, pairs_a_chip):
        """The call is partitioned, not replicated: its rows are the pairs
        of ONE chip x 2 signs x T, whatever the population, and ``W``
        enters it whole (a replicated call would hold every pair's rows)."""
        with as_tpu():
            es = _lm_es(devices8[:4], 2, "tied_scaled",
                        population_size=population_size)
        assert es.engine.pair_chunk == population_size // 2
        call, = pallas_calls(_program(es))
        h, w, *_ = call.invars
        assert h.aval.shape == (pairs_a_chip * 2 * LENGTH, HIDDEN)
        assert w.aval.shape == (64, HIDDEN)
        assert [v.aval.shape for v in call.outvars] == [
            (pairs_a_chip * 2 * LENGTH, 1)]

    @pytest.mark.parametrize(
        "n_devices, model_shards, centre, head, attention, form, says", [
            (1, 1, "gathered", "untied", "kernel", "kernel",
             "one TPU device"),
            (4, 2, "gathered", "untied", "kernel", "kernel",
             "4 TPU devices, whole members on each"),
            (4, 2, "split", "untied", "xla", "xla",
             "a member's operands are not whole on a chip"),
            # a model axis of 1 gathers nothing: the pairs over ``pop``
            # alone, left to GSPMD
            (4, 1, "gathered", "untied", "xla", "xla",
             "4 devices on the mesh and the centre split"),
            # heads of 64 with values of 64: the attention in the XLA
            # form, the head beside it in its kernel, in one program
            (1, 1, "gathered", "tied_scaled", "xla", "kernel",
             "one TPU device"),
            (4, 2, "gathered", "tied_scaled", "xla", "kernel",
             "4 TPU devices, whole members on each"),
        ])
    def test_the_rule_as_the_engine_resolves_it(
            self, devices8, as_tpu, monkeypatch, n_devices, model_shards,
            centre, head, attention, form, says):
        from estorch_tpu.parallel import sharded

        if centre == "split":   # a chip with no room for the centre
            monkeypatch.setattr(sharded, "CHIP_MEMORY_BYTES", 0)
        with as_tpu():
            # heads of 128 where the attention's kernel is to fit
            es = _lm_es(devices8[:n_devices], model_shards, head,
                        **({"head_dim": 128, "num_key_value_heads": 1}
                           if attention == "kernel" or head == "untied"
                           else {}))
        engine = es.engine
        assert (engine.kernel_facts["attention_form"],
                engine.kernel_facts["head_form"]) == (attention, form)
        assert says in engine.kernel_facts["head_form_why"]
        assert es.run_manifest()["config"]["head_form_why"] == (
            engine.kernel_facts["head_form_why"])
        assert "head_form_why" not in es.obs.counters.snapshot()
        names = " ".join(eqn.params["name"]
                         for eqn in pallas_calls(_program(es)))
        assert ("causal_attention" in names) == (attention == "kernel")
        assert ("next_token_scores" in names) == (form == "kernel")

    def test_a_cpu_mesh_without_the_fixture_traces_no_kernel(self, devices8):
        es = _lm_es(devices8[:4], 2, "tied_scaled", population_size=8)
        assert es.engine.centre_form == "gathered"
        assert (es.engine.kernels_traced,
                es.engine.kernel_facts["head_form"]) == (
            False, "xla")
        assert es.engine.kernel_facts["head_form_why"] == (
            "the devices are 'cpu', not TPUs")
        assert pallas_calls(_program(es)) == []
