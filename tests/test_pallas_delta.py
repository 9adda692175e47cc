"""The gated delta rule's kernels (ops/pallas_delta.py) against what they
must equal: the STEP recurrence of the benchmark's reference
(benchmark/reference/delta_moe_lm.py::delta_recurrence, one position after
the other) at the tolerance the XLA form is held to, and the XLA form of
``delta_moe_lm.gated_delta_rule`` on the same inputs.

On CPU the kernels run in interpret mode (``interpret=True`` is passed
here, or comes from the ``kernel_scope`` a test opens; never derived from
the backend); ``tests/test_trace_stages.py`` lowers the SAME code through
Mosaic for a described v5e, and the Qwen3-Next cell's reference check
judges it on the chip.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import delta_moe_tiny
from pallas_costs import declared_costs, pallas_calls
from estorch_tpu.models.delta_moe_lm import (INVERSE_BASE, DeltaMoELM,
                                             gated_delta_rule)
from estorch_tpu.ops import pallas_delta
from estorch_tpu.ops.pallas_attention import kernel_scope
from estorch_tpu.ops.pallas_delta import (BLOCK_ROWS, TILE, block_rows,
                                          chain_cost, delta_form, fits,
                                          inverse_products, solve_cost)

# float32 on both sides at ``HIGHEST``: the tolerance of
# tests/test_delta_moe_lm.py::test_the_chunked_rule_is_the_step_recurrence
STEP_TOL = 2e-5


def _rule_inputs(length, decay, seed=0, nk=1, nv=2, dk=128, dv=128):
    """test_delta_moe_lm.py's inputs at heads of 128: unit keys, queries
    scaled, a step's decay about ``decay``, a head's and a position's
    own."""
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = DeltaMoELM._unit
    q = unit(jax.random.normal(k[0], (length, nk, dk))) / math.sqrt(dk)
    key = unit(jax.random.normal(k[1], (length, nk, dk)))
    v = jax.random.normal(k[2], (length, nv, dv))
    g = math.log(decay) * jnp.exp(0.3 * jax.random.normal(k[3], (length, nv)))
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (length, nv)))
    return q, key, v, g, beta


def _in_kernels(chunk):
    """The rule as a program inside an engine's scope traces it (a new
    closure a call: ``jax.jit`` caches a trace by function)."""
    def rule(*xs):
        with kernel_scope(interpret=True):
            return gated_delta_rule(*xs, chunk)

    return jax.jit(rule)


def _in_xla(chunk):
    return jax.jit(lambda *xs: gated_delta_rule(*xs, chunk))


@pytest.fixture(scope="module")
def stepwise():
    return jax.jit(delta_moe_tiny.reference().delta_recurrence)


class TestAgainstTheStepRecurrence:
    @pytest.mark.parametrize("length, chunk", [
        (128, 64),      # whole chunks: one tile of two
        (100, 64),      # a short last chunk
        (7, 64),        # shorter than a chunk
        (1, 64),
        (640, 64),      # two grid steps: the state crosses a block boundary
        (96, 32),       # four chunks a tile, one of them padding
        (48, 16),
        (200, 128),     # a chunk a tile: no chunk is masked from another
    ])
    @pytest.mark.parametrize("decay", [0.5, 0.9, 0.999, 0.9999])
    def test_the_kernels_are_the_step_recurrence(self, stepwise, length,
                                                 chunk, decay):
        """The parent's lengths (whole chunks, a short last one, shorter
        than one) and decays (0.5: the state forgets within a few
        positions; 0.9999: nothing forgotten) at heads of 128, and every
        chunk the kernels take: the step recurrence at the XLA form's
        tolerance, and the XLA form on the same inputs."""
        xs = _rule_inputs(length, decay, seed=length)
        want = stepwise(*xs)
        got = _in_kernels(chunk)(*xs)
        assert got.shape == want.shape == (length, 2, 128)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, atol=STEP_TOL, rtol=0)
        np.testing.assert_allclose(got, _in_xla(chunk)(*xs), atol=STEP_TOL,
                                   rtol=0)
        assert length < 3 or float(jnp.abs(want).max()) > 0.02

    @pytest.mark.parametrize("dk, dv", [(256, 128), (128, 256)])
    def test_heads_of_two_lane_blocks(self, stepwise, dk, dv):
        """The widest heads the rule takes, a key head's or a value
        head's: a column block of two lane blocks, a state of twice the
        rows or the lanes."""
        xs = _rule_inputs(192, 0.99, seed=dk, dk=dk, dv=dv)
        np.testing.assert_allclose(_in_kernels(64)(*xs), stepwise(*xs),
                                   atol=STEP_TOL, rtol=0)

    def test_a_value_head_reads_its_key_head(self, stepwise):
        """Value head ``j`` reads key head ``j // rep`` and nothing of the
        others: every (key head, value head) pair alone gives the head's
        column of the whole call (the state is zeroed at every head's first
        block: head 1 inherits nothing of head 0's)."""
        q, k, v, g, beta = _rule_inputs(160, 0.99, seed=3, nk=2, nv=4)
        whole = _in_kernels(64)(q, k, v, g, beta)
        alone = _in_kernels(64)
        for j in range(4):
            h = j // 2
            got = alone(q[:, h:h + 1], k[:, h:h + 1], v[:, j:j + 1],
                        g[:, j:j + 1], beta[:, j:j + 1])
            np.testing.assert_allclose(whole[:, j], got[:, 0], atol=1e-6,
                                       rtol=0)
        np.testing.assert_allclose(whole, stepwise(q, k, v, g, beta),
                                   atol=STEP_TOL, rtol=0)

    def test_members_under_vmap_start_from_a_zero_state(self, stepwise):
        """Two members in front of the grid, two blocks a head: each is
        its own evaluation (the second member's first block zeroes the
        state the first one's last block left)."""
        length = BLOCK_ROWS + TILE
        members = [_rule_inputs(length, 0.999, seed=s) for s in (11, 12)]
        stacked = [jnp.stack(x) for x in zip(*members)]

        def rule(*xs):
            with kernel_scope(interpret=True):
                return jax.vmap(lambda *m: gated_delta_rule(*m, 64))(*xs)

        got = jax.jit(rule)(*stacked)
        for member, xs in zip(got, members):
            np.testing.assert_allclose(member, stepwise(*xs), atol=STEP_TOL,
                                       rtol=0)


class TestTheTriangularSystem:
    def test_keys_that_are_all_alike(self):
        """test_the_inverse_survives_keys_that_are_all_alike's inputs
        through the solve kernel: every key the same, ``β = 1``, no decay,
        so ``A`` is all ones below the diagonal inside a chunk, whose powers
        reach 1e17 in 64 rows; in blocks of 8 merged in pairs ``T`` is 1 on
        the diagonal and -1 below it, so ``U`` is ``v_i - v_{i-1}`` and
        ``W`` the key in a chunk's first row and 0 in the others."""
        length, chunk = 256, 64
        key = jnp.broadcast_to(DeltaMoELM._unit(jnp.ones((1, 1, 128))),
                               (length, 1, 128))
        v = jax.random.normal(jax.random.PRNGKey(0), (length, 1, 128))
        rows = pallas_delta.decay_rows(
            jnp.zeros((length, 1)), jnp.ones((length, 1)), 1, chunk)
        w, u = pallas_delta.solve_chunks(key, v, rows, chunk=chunk,
                                         interpret=True)
        first = (jnp.arange(length) % chunk == 0)[:, None]
        before = jnp.where(first, 0.0, jnp.roll(v[:, 0], 1, axis=0))
        np.testing.assert_allclose(u, v[:, 0] - before, atol=1e-4, rtol=0)
        np.testing.assert_allclose(w, jnp.where(first, key[:, 0], 0.0),
                                   atol=1e-4, rtol=0)

    @pytest.mark.parametrize("chunk, want", [
        (64, 10), (128, 12), (32, 8), (16, 6)])
    def test_the_products_of_an_inverse(self, chunk, want):
        """Both forms invert blocks of the same base."""
        assert pallas_delta.INVERSE_BASE == INVERSE_BASE == 8
        assert inverse_products(chunk) == want

    def test_padding_writes_nothing(self):
        """A sequence that ends inside a chunk, a tile and a block: the
        rows past it come back 0 from the solve (``β = 0``, ``k = 0``) and
        the positions before it are what the whole chunk's would be."""
        q, k, v, g, beta = _rule_inputs(70, 0.9, seed=5)
        rows = pallas_delta.decay_rows(g, beta, 1, 64)
        assert rows.shape == (1, 1, 4, TILE)
        # γ stands still past the end (g = 0) and β is 0 there
        np.testing.assert_array_equal(
            rows[0, 0, :2, 70:], jnp.broadcast_to(rows[0, 0, :2, 69:70],
                                                  (2, TILE - 70)))
        np.testing.assert_array_equal(rows[0, 0, 2:, 70:], 0.0)
        w, u = pallas_delta.solve_chunks(k, v, rows, chunk=64,
                                         interpret=True)
        assert w.shape == u.shape == (TILE, 256)
        np.testing.assert_array_equal(w[70:], 0.0)
        np.testing.assert_array_equal(u[70:], 0.0)


class TestTheRule:
    @pytest.mark.parametrize("key_dim, value_dim, chunk, length, want", [
        (128, 128, 64, 16384, True),          # the cell's
        (128, 128, 64, 100, True),            # a padded last chunk
        (128, 128, 64, 1, True),
        (256, 128, 64, 4096, True),
        (128, 256, 128, 4096, True),
        (128, 128, 16, 4096, True),
        (128, 128, 64, 0, False),
        (128, 128, 8, 4096, False),           # the inverse's base alone
        (128, 128, 48, 4096, False),          # not a power of two of blocks
        (128, 128, 256, 4096, False),         # wider than a tile
        (64, 128, 64, 4096, False),           # half a lane block
        (128, 192, 64, 4096, False),
        (384, 128, 64, 4096, False),          # states past the sized VMEM
        (8, 8, 8, 21, False),                 # the suite's tiny model
        (32, 32, 64, 2048, False),            # the benchmark's rehearsal
    ])
    def test_fits(self, key_dim, value_dim, chunk, length, want):
        assert fits(key_dim, value_dim, chunk, length) is want

    @pytest.mark.parametrize("traced, shapes, want", [
        (True, (128, 128, 64, 16384), "kernel"),
        (False, (128, 128, 64, 16384), "xla"),    # no scope: another mesh
        (True, (8, 8, 8, 21), "xla"),
        (False, (8, 8, 8, 21), "xla"),
        (True, (128, 128, 64, 100), "kernel"),
        (True, (128, 128, 40, 16384), "xla"),
    ])
    def test_the_form(self, traced, shapes, want):
        """The rule's own: whether Mosaic kernels may be traced, and its
        shapes; no other kernel's form is part of it."""
        assert delta_form(traced, *shapes) == want

    @pytest.mark.parametrize("length, want", [
        (16384, BLOCK_ROWS), (BLOCK_ROWS, BLOCK_ROWS), (100, TILE),
        (1, TILE), (300, 3 * TILE)])
    def test_the_rows_of_a_grid_step(self, length, want):
        assert block_rows(length) == want

    def test_nothing_reads_the_backend(self):
        source = open(pallas_delta.__file__).read()
        assert "default_backend" not in source
        assert "os.environ" not in source


class TestWhatTheKernelsDeclare:
    def test_one_tile_by_hand(self):
        """One tile of 128 positions (two chunks of 64), one key head, one
        value head of 128 x 128, dense tiles: the solve's ``K Kᵀ`` 2·128³,
        ten products of the inverse 2·128³ each, ``W`` and ``U`` 2·128³
        each; the chain's ``Q Kᵀ`` 2·128³ and, a chunk, three products
        against the state 2·64·128² each and one inside the chunk
        2·64²·128."""
        cube = 2 * 128 ** 3
        solve = solve_cost(128, 64, 1, 1, 128, 128)
        assert solve.flops == cube + 10 * cube + 2 * cube == 54_525_952
        assert solve.transcendentals == 128 * 128 + 128
        # k, v read; W, U written; γ and β read
        assert solve.bytes_accessed == 4 * 128 * (4 * 128 + 2)
        chain = chain_cost(128, 64, 1, 1, 128, 128)
        assert chain.flops == cube + 2 * (
            3 * 2 * 64 * 128 * 128 + 2 * 64 * 64 * 128) == 18_874_368
        assert chain.transcendentals == 128 * 128 + 2 * 128 + 2
        # q, k, W, U read; o written; γ and β read
        assert chain.bytes_accessed == 4 * 128 * (5 * 128 + 2)

    def test_the_calls_declare_the_tiles_they_compute(self):
        """The two ``pallas_call``s of a traced rule carry the cost of the
        WHOLE blocks the grid computes (a sequence of 600 is two blocks of
        512: 1,024 positions), and a chunk of 128 two more products an
        inverse."""
        xs = _rule_inputs(600, 0.9, nk=2, nv=4)

        def rule(*o):
            with kernel_scope(interpret=True):
                return gated_delta_rule(*o, 64)

        assert declared_costs(rule, *xs) == [
            solve_cost(600, 64, 2, 4, 128, 128),
            chain_cost(600, 64, 2, 4, 128, 128)]
        assert solve_cost(600, 64, 2, 4, 128, 128) == solve_cost(
            1024, 64, 2, 4, 128, 128)
        assert (solve_cost(1024, 128, 2, 4, 128, 128).flops
                - solve_cost(1024, 64, 2, 4, 128, 128).flops
                == 8 * 4 * 2 * 2 * 128 ** 3)


class TestWhichFormAProgramTakes:
    """``delta_moe_lm.gated_delta_rule`` asks the scope, then its shapes."""

    def _calls(self, xs, chunk, scoped):
        def rule(*o):                      # a closure a trace: no jit cache
            return gated_delta_rule(*o, chunk)

        if scoped:
            with kernel_scope(interpret=True):
                return pallas_calls(rule, *xs)
        return pallas_calls(rule, *xs)

    def test_inside_a_scope_shapes_that_fit_take_the_kernels(self):
        solve, chain = self._calls(_rule_inputs(128, 0.9), 64, scoped=True)
        assert solve.params["name"] == "delta_solve"
        assert chain.params["name"] == "delta_chain"
        # q, k, v and o stay [T, heads · dim]: no head-major copy
        assert [v.aval.shape for v in solve.invars[:2]] == [
            (128, 128), (128, 256)]
        assert [v.aval.shape for v in chain.outvars] == [(128, 256)]

    @pytest.mark.parametrize("sizes, chunk", [
        (dict(dk=8, dv=8), 8),              # the suite's tiny heads
        (dict(dk=32, dv=32), 64),           # the benchmark's rehearsal
        (dict(dk=128, dv=128), 8),          # a chunk the kernels refuse
        (dict(dk=128, dv=64), 64),
    ])
    def test_inside_a_scope_other_shapes_stay_in_xla(self, sizes, chunk):
        assert self._calls(_rule_inputs(64, 0.9, **sizes), chunk,
                           scoped=True) == []

    def test_outside_a_scope_nothing_takes_them(self):
        assert self._calls(_rule_inputs(128, 0.9), 64, scoped=False) == []
