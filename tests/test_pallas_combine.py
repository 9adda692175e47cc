"""The expert layer's combine kernel (ops/pallas_combine.py) against the form
it must equal: the scatter-add that closes a pass of
``lm_blocks._experts_of_members`` (what every CPU program runs), on the
same operands.

Both forms add a token's float32 terms in the pass's row order, so where
every product ``w · row`` is exact (weights that are powers of two) they
agree to the LAST BIT; with any weights the interpreter's program may
contract the kernel's multiply-add into one rounding where the scatter-add
rounds the product first (XLA:CPU; the v5e has no fused multiply-add), and
the two agree to float32 rounding, a term at a time.

On CPU the kernel runs in interpret mode (``interpret=True`` is passed
here, or comes from the ``kernel_scope`` a test opens; never derived from
the backend); ``tests/test_trace_stages.py`` lowers the SAME code through
Mosaic for a described v5e, and the four expert cells' reference checks
judge it on the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pallas_costs import declared_costs, pallas_calls
from estorch_tpu.models import lm_blocks
from estorch_tpu.ops import pallas_combine
from estorch_tpu.ops.pallas_attention import kernel_scope
from estorch_tpu.ops.pallas_combine import (TOKEN_TILE, chunk_rows,
                                            combine_cost, combine_form,
                                            combine_rows, fits)

HIDDEN = 128
# one rounding of a term of magnitude up to 16 (|w| < 2, |row| < 8), over
# the few terms a token's row takes: float32's 2^-24 relative, a term
F32_TOL = 2e-5


def _routes(rng, tokens, k, total, among=None):
    """``[tokens, k]`` experts, no expert twice a token, drawn from
    ``among`` (all ``total`` where not given)."""
    among = np.arange(total) if among is None else np.asarray(among)
    return np.stack([rng.choice(among, k, replace=False)
                     for _ in range(tokens)]).astype(np.int32)


def _passes(rng, experts, held, cap, exact):
    """The passes ``_experts_of_members`` makes of ``experts`` (the held
    ones are ``0 … held - 1``), each as the operands of its combine:
    ``(rows, w, token, valid, key)``."""
    tokens, k = experts.shape
    pairs = tokens * k
    local = experts.reshape(pairs)
    group = np.where(local < held, local, held).astype(np.int32)
    order = np.argsort(group, kind="stable").astype(np.int32)
    routed = int((group < held).sum())
    order_p = np.concatenate([order, np.full(cap, pairs, np.int32)])
    group_p = np.concatenate([group[order], np.full(cap, held, np.int32)])
    weights = (2.0 ** rng.integers(-2, 2, pairs) if exact
               else rng.uniform(-2, 2, pairs)).astype(np.float32)
    out = []
    for start in range(0, max(routed, 1), cap):
        pair = order_p[start:start + cap]
        row_expert = np.minimum(group_p[start:start + cap], held - 1)
        valid = start + np.arange(cap) < routed
        token = np.minimum(pair // k, tokens - 1).astype(np.int32)
        w = np.where(valid, weights[np.minimum(pair, pairs - 1)], 0.0)
        key = np.where(valid, row_expert * tokens + token, held * tokens)
        rows = rng.standard_normal((cap, HIDDEN)).astype(np.float32)
        out.append(tuple(map(jnp.asarray, (
            rows, w.astype(np.float32), token, valid,
            key.astype(np.int32)))))
    return out, routed


# one compile a set of shapes and blocks, whatever the case
_kernel = jax.jit(combine_rows,
                  static_argnames=("held", "interpret", "tile", "chunk"))


def _both_forms(passes, tokens, held, **blocks):
    y = yk = jnp.zeros((tokens, HIDDEN), jnp.float32)
    for n, (rows, w, token, valid, key) in enumerate(passes):
        y = y.at[jnp.where(valid, token, tokens)].add(
            rows * w[:, None], mode="drop")
        yk = _kernel(yk, rows, w, token, key, n == 0, held=held,
                     interpret=True, **blocks)
    return y, yk


# tokens, k, total, held, rows of a pass, tile, chunk, the experts drawn from
CASES = {
    # 64 rows, 48 at a time: two passes
    "one expert a token, every expert held":
        (64, 1, 4, 4, 48, 16, 8, None),
    "six a token, half held: a token with several held experts":
        (32, 6, 8, 4, 128, 32, 16, None),
    "eight a token": (32, 8, 16, 8, 168, 8, None, None),
    "a held expert nobody chose": (64, 2, 8, 4, 48, 16, 8, [0, 2, 3, 5, 6]),
    "runs longer than a chunk, one tile": (64, 2, 4, 2, 80, 64, 8, None),
    # every token to the held experts: 192 routed rows, 40 at a time, so a
    # pass ends inside an expert's segment and four passes add into what
    # the one before left; the last has 8 rows past the routed ones
    "five passes of a skewed router": (64, 3, 8, 4, 40, 16, 8, [0, 1, 2, 3]),
    "the same, 48 at a time": (64, 3, 8, 4, 48, 16, 8, [0, 1, 2, 3]),
}


class TestAgainstTheScatterAdd:
    @pytest.mark.parametrize("case, exact", [
        *((case, True) for case in sorted(CASES)),
        *((case, False) for case in sorted(CASES)
          if case.startswith(("six", "the same")))])
    def test_every_pass_adds_what_the_scatter_adds(self, case, exact):
        tokens, k, total, held, cap, tile, chunk, among = CASES[case]
        rng = np.random.default_rng(sorted(CASES).index(case))
        experts = _routes(rng, tokens, k, total, among)
        passes, routed = _passes(rng, experts, held, cap, exact)
        assert len(passes) == -(-routed // cap)
        if "five passes" in case:
            assert (len(passes), routed) == (5, 192)
            assert int(passes[-1][3].sum()) == 32           # 8 rows past them
        if "nobody chose" in case:
            assert not (experts == 1).any()
        want, got = _both_forms(passes, tokens, held, tile=tile, chunk=chunk)
        assert float(jnp.abs(want).max()) > 1.0
        if exact:
            np.testing.assert_array_equal(got, want)
        else:
            np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)

    def test_the_order_of_a_tokens_terms_is_the_passes(self):
        """Terms of magnitudes 2^24, 1 and -2^24 in one token's row: the
        sum depends on their order, and the kernel's is the scatter-add's
        (expert order)."""
        experts = np.array([[0, 1, 2]] * 8, np.int32)
        passes, _ = _passes(np.random.default_rng(0), experts, 3, 24, True)
        rows, w, token, valid, key = passes[0]
        big = jnp.where(key[:, None] // 8 == 1, 1.0,
                        jnp.where(key[:, None] // 8 == 0, 2.0 ** 24,
                                  -2.0 ** 24))
        rows, w = jnp.broadcast_to(big, rows.shape), jnp.ones_like(w)
        want, got = _both_forms([(rows, w, token, valid, key)], 8, 3,
                                tile=8, chunk=8)
        np.testing.assert_array_equal(got, want)
        assert float(want[0, 0]) == 0.0          # (2^24 + 1) - 2^24, rounded

    def test_a_first_pass_does_not_read_y(self):
        """``first``: what ``y`` held is not added to (the loop's zeros are
        never read); a later pass adds into it."""
        experts = _routes(np.random.default_rng(1), 16, 2, 4)
        rows, w, token, _, key = _passes(np.random.default_rng(1), experts,
                                         4, 32, True)[0][0]
        y = jnp.full((16, HIDDEN), 3.0)
        first, later = (_kernel(y, rows, w, token, key, flag, held=4,
                                interpret=True, tile=8)
                        for flag in (True, False))
        np.testing.assert_array_equal(
            first, jnp.zeros_like(y).at[token].add(rows * w[:, None]))
        np.testing.assert_array_equal(
            later, y.at[token].add(rows * w[:, None]))

    def test_shapes_that_are_no_whole_tiles_are_refused(self):
        y = jnp.zeros((20, HIDDEN))
        rows = jnp.zeros((8, HIDDEN))
        with pytest.raises(ValueError, match="whole tiles"):
            combine_rows(y, rows, jnp.zeros(8), jnp.zeros(8, jnp.int32),
                         jnp.zeros(8, jnp.int32), True, held=2,
                         interpret=True, tile=8)


# ------------------------------------------------ through the expert layer

@pytest.fixture
def tiles_of_eight(monkeypatch):
    """The kernel's token tile at 8 tokens, so that layers of a few dozen
    tokens fit its rule and run under the interpreter quickly.  A fake
    substituted by the test: nothing in the package reads it."""
    monkeypatch.setattr(pallas_combine, "TOKEN_TILE", 8)


def _layer(held, width=16, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"gate": jax.random.normal(ks[0], (held, HIDDEN, width)) * 0.2,
            "up": jax.random.normal(ks[1], (held, HIDDEN, width)) * 0.2,
            "down": jax.random.normal(ks[2], (held, width, HIDDEN)) * 0.2}


def _experts_layer(p, noise, c, u, experts, weights, total):
    # a closure a trace: ``jit`` and ``make_jaxpr`` cache by function
    return lm_blocks.routed_experts(p, noise, c, u, experts, weights,
                                    first_held=0, total=total)


class TestTheExpertLayerTakesIt:
    @pytest.mark.parametrize("k, total, held, among", [
        (6, 8, 4, None),
        (8, 16, 8, range(8)),              # skewed: several passes
    ])
    def test_inside_a_scope_the_layer_is_the_xla_layer(
            self, tiles_of_eight, k, total, held, among):
        rng = np.random.default_rng(k)
        tokens = 32
        u = jax.random.normal(jax.random.PRNGKey(k), (tokens, HIDDEN))
        experts = jnp.asarray(_routes(rng, tokens, k, total, among))
        weights = jnp.asarray(rng.uniform(0.1, 1, (tokens, k)), jnp.float32)
        p = _layer(held)
        want, want_load = _experts_layer(p, None, 0.0, u, experts, weights,
                                         total)
        with kernel_scope(interpret=True):
            (call,) = pallas_calls(
                lambda *a: _experts_layer(p, None, 0.0, *a, total), u,
                experts, weights)
            got, load = _experts_layer(p, None, 0.0, u, experts, weights,
                                       total)
        assert call.params["name"] == "combine_rows"
        np.testing.assert_array_equal(load, want_load)
        np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)
        if among is not None:
            assert lm_blocks.expert_capacity(tokens * k, held, total) < (
                tokens * k)

    def test_members_under_a_vmap_become_rows_of_one_call(
            self, tiles_of_eight):
        """The ``custom_vmap`` rule merges the members (their noise batched,
        the centre not): ONE call over ``members · tokens`` rows of ``y``,
        each member's rows its own; with the centre batched too (the
        materialised form) the members go one by one through the
        scatter-add."""
        rng = np.random.default_rng(3)
        members, tokens, k, total, held = 3, 16, 2, 4, 4
        u = jax.random.normal(jax.random.PRNGKey(0), (members, tokens,
                                                      HIDDEN))
        experts = jnp.asarray(np.stack(
            [_routes(rng, tokens, k, total) for _ in range(members)]))
        weights = jnp.asarray(rng.uniform(0.1, 1, (members, tokens, k)),
                              jnp.float32)
        p = _layer(held)
        noise = {n: (jax.random.normal(jax.random.PRNGKey(i),
                                       (members, held, p[n].shape[1], 2)),
                     jax.random.normal(jax.random.PRNGKey(9 + i),
                                       (members, held, p[n].shape[2], 2)))
                 for i, n in enumerate(("gate", "up", "down"))}

        def merged(u, experts, weights, noise):
            return jax.vmap(lambda u, e, w, n: _experts_layer(
                p, n, 0.1, u, e, w, total))(u, experts, weights, noise)

        def one_by_one(u, experts, weights, stack):
            return jax.vmap(lambda u, e, w, p_m: _experts_layer(
                p_m, None, 0.0, u, e, w, total))(u, experts, weights, stack)

        want, want_load = merged(u, experts, weights, noise)
        stack = jax.tree_util.tree_map(
            lambda x: jnp.stack([x] * members), p)
        with kernel_scope(interpret=True):
            (call,) = pallas_calls(merged, u, experts, weights, noise)
            got, load = merged(u, experts, weights, noise)
            assert pallas_calls(one_by_one, u, experts, weights, stack) == []
        assert call.outvars[0].aval.shape == (members * tokens, HIDDEN)
        np.testing.assert_array_equal(load, want_load)
        np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)

    @pytest.mark.parametrize("tokens, hidden, scoped", [
        (32, HIDDEN, False),               # no scope
        (36, HIDDEN, True),                # no whole tiles
        (32, 96, True),                    # no whole lane blocks
    ])
    def test_anywhere_else_it_is_the_scatter_add(self, tiles_of_eight,
                                                 tokens, hidden, scoped):
        rng = np.random.default_rng(0)
        u = jnp.zeros((tokens, hidden))
        experts = jnp.asarray(_routes(rng, tokens, 2, 4))
        weights = jnp.ones((tokens, 2))
        p = {"gate": jnp.zeros((4, hidden, 8)),
             "up": jnp.zeros((4, hidden, 8)),
             "down": jnp.zeros((4, 8, hidden))}

        def layer(*a):
            return _experts_layer(p, None, 0.0, *a, 4)

        if scoped:
            with kernel_scope(interpret=True):
                calls = pallas_calls(layer, u, experts, weights)
        else:
            calls = pallas_calls(layer, u, experts, weights)
        assert calls == []


# ------------------------------------------------------------------ the rule

class TestTheRule:
    @pytest.mark.parametrize("hidden, tokens, want", [
        (2560, 16384, True),               # smallthinker's cell
        (2048, 8192, True),                # zaya1's, a member
        (2048, 4096, True),                # joyai's, a member
        (128, TOKEN_TILE, True),           # the least
        (4096, 512, True),                 # the widest row
        (4224, 512, False),                # wider than VMEM takes twice
        (2560, 16384 + 8, False),          # no whole tiles
        (2560, 21, False),
        (2500, 16384, False),              # no whole 128-lane blocks
        (32, 512, False),
        (0, 512, False), (128, 0, False),
    ])
    def test_fits(self, hidden, tokens, want):
        assert fits(hidden, tokens) is want

    @pytest.mark.parametrize("traced, shapes, want", [
        (True, (2560, 16384), "kernel"),
        (False, (2560, 16384), "xla"),     # no scope: another mesh
        (True, (32, 21), "xla"),           # the suite's tiny models
        (True, (2560, 16000), "xla"),
    ])
    def test_the_form(self, traced, shapes, want):
        """The combine's own rule: whether Mosaic kernels may be traced,
        and its shapes; no other kernel's form is part of it."""
        assert combine_form(traced, *shapes) == want

    @pytest.mark.parametrize("rows, held, tiles, want", [
        (30720, 16, 32, 72),               # smallthinker: runs of 48 rows
        (20480, 8, 64, 48),                # zaya1: 32
        (20480, 16, 32, 48),               # keye: 32
        (40960, 16, 128, 32),              # joyai: 16
        (64, 16, 32, 16),                  # never under two sublane tiles
        (8, 1, 1, 8),                      # nor over the pass
        (65536, 2, 4, 128),                # nor over the longest chunk
    ])
    def test_the_chunk_follows_the_runs(self, rows, held, tiles, want):
        assert chunk_rows(rows, held, tiles) == want

    def test_nothing_reads_the_backend(self):
        source = open(pallas_combine.__file__).read()
        assert "default_backend" not in source
        assert "os.environ" not in source


class TestTheDeclaredCost:
    def test_the_call_declares_its_least_bytes(self):
        """What the ``pallas_call`` hands XLA is ``combine_cost``, from the
        shapes: one chunk of float32 rows an expert and tile, every
        token's row written once, the tokens, the weights and the bounds;
        a multiply and an add a float of a pass's row."""
        tokens, held, cap, tile, chunk = 64, 4, 48, 16, 8
        experts = _routes(np.random.default_rng(0), tokens, 2, 8)
        rows, w, token, _, key = _passes(np.random.default_rng(0), experts,
                                         held, cap, True)[0][0]

        def kernel(y, *o):
            return combine_rows(y, *o, True, held=held, interpret=True,
                                tile=tile, chunk=chunk)

        (cost,) = declared_costs(kernel, jnp.zeros((tokens, HIDDEN)), rows,
                                 w, token, key)
        want = combine_cost(cap, tokens, HIDDEN, held, tile, chunk)
        assert (cost.flops, cost.transcendentals, cost.bytes_accessed) == (
            want.flops, 0, want.bytes_accessed)
        tiles = tokens // tile
        assert cost.flops == 2 * cap * HIDDEN
        assert cost.bytes_accessed == (
            4 * HIDDEN * (held * tiles * chunk + tokens)
            + 4 * 2 * cap + 4 * (held * tiles + 2))

    def test_the_cells_pass_moves_a_fifteenth_of_the_scatters_time(self):
        """smallthinker's pass: 30,720 rows of 2,560 floats into 16,384
        tokens.  Declared 545 MB, 0.67 ms at the v5e's 819 GB/s, where
        the scatter-add takes 9.2 ms (PERF.md §5)."""
        cost = combine_cost(30720, 16384, 2560, 16, TOKEN_TILE, 72)
        assert cost.bytes_accessed == (
            16 * 32 * 72 * 2560 * 4 + 16384 * 2560 * 4 + 2 * 30720 * 4
            + (16 * 32 + 2) * 4)
        assert 0.6e-3 < cost.bytes_accessed / 819e9 < 0.7e-3


# ------------------------------------------------ which form an engine takes

def _sequence_es(policy, tiny, devices, seq_len=None, **policy_over):
    import optax

    from estorch_tpu import ES, JaxAgent
    from estorch_tpu.envs import TokenScoreEnv

    env = {**tiny.ENV, **({} if seq_len is None else {"seq_len": seq_len})}
    return ES(policy=policy, agent=JaxAgent, optimizer=optax.adam,
              population_size=4, sigma=0.02,
              policy_kwargs={**tiny.TINY, **policy_over},
              agent_kwargs={"env": TokenScoreEnv(**env)},
              optimizer_kwargs={"learning_rate": 1e-2}, shard_params=True,
              model_shards=1, low_rank=1, noise_mode="table",
              table_size=1 << 18, device=list(devices))


def _kernel_names(es) -> list:
    return [call.params["name"] for call in pallas_calls(
        es.engine._generation_step, es.state, es.table.data)]


class TestWhichFormAnEngineTakes:
    def test_a_scope_and_shapes_that_fit_the_kernel_a_layer(
            self, devices8, kernel_attention, tiles_of_eight):
        """``window_moe_tiny`` at a hidden width of one lane block over 24
        tokens, the engine's scope open around its trace (and the kernel's
        tile at 8 tokens): ``combine_form`` "kernel" in the engine, the
        gauge and the manifest, ONE ``combine_rows`` call an expert layer
        in the generation program (the loop's passes all go through it)."""
        import window_moe_tiny
        from estorch_tpu.models import WindowMoELM

        wide = dict(seq_len=24, hidden_size=HIDDEN, attention_block=8)
        xla = _sequence_es(WindowMoELM, window_moe_tiny, devices8[:1],
                           **wide)
        with kernel_attention():
            kern = _sequence_es(WindowMoELM, window_moe_tiny, devices8[:1],
                                **wide)
        assert (xla.engine.kernel_facts["combine_form"],
                kern.engine.kernel_facts["combine_form"]) == (
            "xla", "kernel")
        for es, form in ((xla, "xla"), (kern, "kernel")):
            assert es.obs.counters.get("combine_form") == form
            assert es.run_manifest()["config"]["combine_form"] == form
        assert _kernel_names(xla) == []
        layers = len(window_moe_tiny.TINY["layer_types"])
        assert _kernel_names(kern).count("combine_rows") == layers

    def test_a_scope_and_shapes_that_do_not_fit_the_scatter_add(
            self, devices8, kernel_attention):
        """The suite's tiny model as it is (32 floats a row over 21
        tokens) inside the scope: the attention takes its kernel, the
        combine does not, and the engine says so."""
        import window_moe_tiny
        from estorch_tpu.models import WindowMoELM

        with kernel_attention():
            es = _sequence_es(WindowMoELM, window_moe_tiny, devices8[:1])
        assert (es.engine.kernel_facts["attention_form"],
                es.engine.kernel_facts["combine_form"]) == (
            "kernel", "xla")
        assert es.run_manifest()["config"]["combine_form"] == "xla"
        names = _kernel_names(es)
        assert names and "combine_rows" not in names

    @pytest.mark.parametrize("model", ["hybrid", "looped", "sambay"])
    def test_a_model_without_an_expert_layer_has_no_such_form(
            self, model, devices8, kernel_attention):
        """``None`` in the engine and the manifest, no gauge, and no
        ``combine_rows`` call in the generation program, scope or not."""
        import lm_tiny
        import loop_tiny
        import sambay_tiny
        from estorch_tpu.models import HybridLM, LoopedLM, SambaYLM

        policy, tiny = {"hybrid": (HybridLM, lm_tiny),
                        "looped": (LoopedLM, loop_tiny),
                        "sambay": (SambaYLM, sambay_tiny)}[model]
        with kernel_attention():
            es = _sequence_es(policy, tiny, devices8[:1])
        assert es.engine.kernels_traced
        assert "combine_form" not in es.engine.kernel_facts
        assert es.obs.counters.get("combine_form", None) is None
        assert es.run_manifest()["config"]["combine_form"] is None
        assert "combine_rows" not in _kernel_names(es)

    def test_the_mlp_has_none_either(self, devices8):
        import optax

        from estorch_tpu import ES, JaxAgent, MLPPolicy
        from estorch_tpu.envs import CartPole

        es = ES(policy=MLPPolicy, agent=JaxAgent, optimizer=optax.adam,
                population_size=8, sigma=0.1,
                policy_kwargs={"action_dim": 2, "hidden": (16, 16)},
                agent_kwargs={"env": CartPole(), "horizon": 20},
                optimizer_kwargs={"learning_rate": 1e-2},
                device=devices8[:2], shard_params=True, model_shards=2)
        assert "combine_form" not in es.engine.kernel_facts
        assert "combine_form" in es.run_manifest()["config"]
        assert es.run_manifest()["config"]["combine_form"] is None
        assert es.obs.counters.get("combine_form", None) is None
