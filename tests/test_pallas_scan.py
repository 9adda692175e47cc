"""The selective scan's kernel (ops/pallas_scan.py) against the form it must
equal: the ``lax.scan`` of ``sambay_lm.selective_scan`` (what every CPU
program runs), to float32 rounding.

On CPU the kernel runs in interpret mode (``interpret=True`` is passed
here, or comes from the ``kernel_scope`` a test opens; never derived from
the backend); ``tests/test_trace_stages.py`` lowers the SAME code through
Mosaic for a described v5e, and the SambaY cell's reference check judges it
on the chip.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import sambay_tiny
from pallas_costs import declared_costs, pallas_calls
from estorch_tpu.models.sambay_lm import selective_scan
from estorch_tpu.ops import pallas_scan
from estorch_tpu.ops.pallas_attention import kernel_scope
from estorch_tpu.ops.pallas_scan import (TIME_CHUNK, channel_block, fits,
                                         scan_cost, scan_form)

# float32 on both sides; what differs is the order of the d_state terms of
# y_t and the exponential (the interpreter's exp2 of a pre-scaled argument
# against exp): measured 2e-6 to 4e-6 on outputs of magnitude 10 to 30
F32_TOL = 3e-5


def _operands(length, d_inner, d_state, seed=0, step=-2.0):
    """``(x, Δ, A, B, C)`` as the mixer hands them: ``Δ`` after a softplus
    (about e^step), ``A`` negative, around Mamba-1's ``-(1 … d_state)``."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return (jax.random.normal(ks[0], (length, d_inner)),
            jax.nn.softplus(jax.random.normal(ks[1], (length, d_inner))
                            + step),
            -jnp.exp(jnp.log(jnp.arange(1.0, d_state + 1))[None, :]
                     + 0.3 * jax.random.normal(ks[2], (d_inner, d_state))),
            jax.random.normal(ks[3], (length, d_state)),
            jax.random.normal(ks[4], (length, d_state)))


def _xla(*operands):
    return selective_scan(*operands, 8)       # outside any scope


class TestAgainstTheLaxScan:
    @pytest.mark.parametrize("length, d_inner, d_state, blocks", [
        (64, 128, 16, dict(chunk=16)),                  # four time chunks
        (64, 384, 16, dict(chunk=32, block_d=128)),     # three channel blocks
        (48, 256, 8, dict(chunk=16, unroll=4)),         # eight states
        (32, 1024, 16, dict(chunk=16)),                 # a whole register
        (32, 256, 3, dict(chunk=8, unroll=2)),          # any few states
        (512, 128, 16, {}),                             # the shipped blocks
    ])
    def test_blocks_chunks_and_states(self, length, d_inner, d_state,
                                      blocks):
        operands = _operands(length, d_inner, d_state)
        got = pallas_scan.selective_scan(*operands, interpret=True, **blocks)
        want = _xla(*operands)
        assert got.shape == (length, d_inner) and got.dtype == jnp.float32
        np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)

    def test_the_state_survives_the_chunk_boundaries(self):
        """A decay near 1 (``Δ·A`` about -1e-3): what the first chunk's
        steps put into the state is still most of ``y`` in the last chunk,
        so a state zeroed, or dropped, at a boundary would show."""
        x, delta, a, b, c = _operands(96, 128, 16, step=-7.0)
        b, c = jnp.abs(b), jnp.abs(c)
        x = jnp.where(jnp.arange(96)[:, None] < 16, jnp.abs(x) + 1.0, 0.0)
        got = pallas_scan.selective_scan(x, delta, a, b, c, interpret=True,
                                         chunk=16)
        want = _xla(x, delta, a, b, c)
        np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=1e-5)
        # the input ended in the first chunk; the last chunk still reads it
        assert float(jnp.abs(want[80:]).min()) > 1e-4
        # and each channel block its own state: two blocks, not one's twice
        two = pallas_scan.selective_scan(
            jnp.tile(x, (1, 2)) * jnp.repeat(jnp.array([1.0, -2.0]), 128),
            jnp.tile(delta, (1, 2)), jnp.tile(a, (2, 1)), b, c,
            interpret=True, chunk=16, block_d=128)
        np.testing.assert_allclose(two[:, 128:], -2.0 * two[:, :128],
                                   rtol=1e-6)

    @pytest.mark.parametrize("a_batched", [True, False])
    def test_members_enter_through_vmap(self, a_batched):
        """The engine's nesting (pairs around signs): ``A = -exp(A_log)``
        is a perturbed leaf, so batched where the noise is; one ``vmap``
        with ``A`` not batched is the centre's."""
        x, delta, a, b, c = _operands(32, 128, 16)
        scale = jnp.array([[1.0, 0.5], [2.0, -1.0]])[..., None, None]
        xs, deltas = x * scale, delta * jnp.abs(scale)
        a_s = (a * jnp.array([[1.0, 1.1], [0.9, 1.3]])[..., None, None]
               if a_batched else a)
        axes = (0, 0, 0 if a_batched else None, None, None)

        def nested(scan):
            return jax.vmap(jax.vmap(scan, in_axes=axes), in_axes=axes)

        def kernel(*operands):
            return pallas_scan.selective_scan(*operands, interpret=True,
                                              chunk=16)

        got = nested(kernel)(xs, deltas, a_s, b, c)
        want = nested(_xla)(xs, deltas, a_s, b, c)
        assert got.shape == (2, 2, 32, 128)
        np.testing.assert_allclose(got, want, atol=F32_TOL, rtol=0)
        (call,) = pallas_calls(nested(kernel), xs, deltas, a_s, b, c)
        # members in front of the grid, the call's own grid behind them
        assert tuple(call.params["grid_mapping"].grid) == (2, 2, 1, 2)

    def test_operands_in_another_dtype_are_scanned_in_float32(self):
        operands = _operands(32, 128, 8)
        low = tuple(v.astype(jnp.bfloat16) for v in operands)
        got = pallas_scan.selective_scan(*low, interpret=True, chunk=16)
        assert got.dtype == jnp.float32
        np.testing.assert_allclose(
            got, _xla(*(v.astype(jnp.float32) for v in low)), atol=F32_TOL,
            rtol=0)

    @pytest.mark.parametrize("blocks", [
        dict(block_d=96), dict(block_d=256), dict(chunk=24),
        dict(chunk=16, unroll=3)])
    def test_blocks_that_do_not_divide_are_refused(self, blocks):
        with pytest.raises(ValueError, match="whole blocks"):
            pallas_scan.selective_scan(*_operands(64, 384, 4),
                                       interpret=True, **blocks)

    def test_shapes_that_disagree_are_refused(self):
        x, delta, a, b, c = _operands(32, 128, 4)
        with pytest.raises(ValueError, match=r"not \[T, D\]"):
            pallas_scan.selective_scan(x, delta, a, b[:16], c,
                                       interpret=True)


class TestTheRule:
    @pytest.mark.parametrize("d_inner, d_state, length, want", [
        (5120, 16, 8192, True),           # the published widths
        (128, 8, 256, True),
        (5120, 16, 8000, False),          # ragged T
        (5120, 16, 0, False),
        (5000, 16, 8192, False),          # not whole 128-lane blocks
        (64, 16, 8192, False),
        (5120, 32, 8192, False),          # more states than registers
        (5120, 0, 8192, False),
    ])
    def test_fits(self, d_inner, d_state, length, want):
        assert fits(d_inner, d_state, length) is want

    @pytest.mark.parametrize("d_inner, want", [
        (5120, 1024), (1536, 512), (768, 256), (640, 128), (96, None)])
    def test_the_channel_block(self, d_inner, want):
        assert channel_block(d_inner) == want

    @pytest.mark.parametrize("traced, shapes, want", [
        (True, (5120, 16, 8192), "kernel"),
        (False, (5120, 16, 8192), "xla"),     # no scope: another mesh
        (False, (128, 4, 256), "xla"),
        (True, (5120, 16, 8191), "xla"),
        (True, (5121, 16, 8192), "xla"),
    ])
    def test_the_form(self, traced, shapes, want):
        """The scan's own rule: whether Mosaic kernels may be traced, and
        the scan's shapes; the attention's form is no part of it."""
        assert scan_form(traced, *shapes) == want

    def test_nothing_reads_the_backend(self):
        source = open(pallas_scan.__file__).read()
        assert "default_backend" not in source
        assert "os.environ" not in source


class TestWhichFormAProgramTakes:
    """``sambay_lm.selective_scan`` asks the scope, then its shapes."""

    def _calls(self, operands, scoped):
        def scan(*o):                      # a closure a trace: no jit cache
            return selective_scan(*o, 8)

        if scoped:
            with kernel_scope(interpret=True):
                return pallas_calls(scan, *operands)
        return pallas_calls(scan, *operands)

    def test_inside_a_scope_shapes_that_fit_take_the_kernel(self):
        operands = _operands(TIME_CHUNK, 128, 16)
        (call,) = self._calls(operands, scoped=True)
        assert call.params["name"] == "selective_scan"
        with kernel_scope(interpret=True):
            got = selective_scan(*operands, 8)
        np.testing.assert_allclose(got, _xla(*operands), atol=F32_TOL,
                                   rtol=0)
        assert float(jnp.abs(got - _xla(*operands)).max()) > 0.0

    @pytest.mark.parametrize("length, d_inner, d_state", [
        (TIME_CHUNK + 8, 128, 16),          # ragged T
        (TIME_CHUNK, 192, 16),              # d_inner not a multiple of 128
        (TIME_CHUNK, 128, 24),              # too many states
    ])
    def test_inside_a_scope_shapes_the_rule_refuses_take_the_xla_form(
            self, length, d_inner, d_state):
        operands = _operands(length, d_inner, d_state)
        assert self._calls(operands, scoped=True) == []
        with kernel_scope(interpret=True):
            got = selective_scan(*operands, 8)
        np.testing.assert_array_equal(got, _xla(*operands))

    def test_outside_a_scope_the_program_is_the_one_it_was(self):
        """No ``pallas_call``, and the jaxpr is the ``lax.scan``'s: the
        text of the same function traced on the parent's lines."""
        operands = _operands(TIME_CHUNK, 128, 16)
        assert self._calls(operands, scoped=False) == []
        text = str(jax.make_jaxpr(lambda *o: selective_scan(*o, 8))(
            *operands))
        assert "pallas_call" not in text and "scan[" in text
        assert text.count("exp") == 1


class TestTheDeclaredCost:
    @pytest.mark.parametrize("members", [1, 2])
    def test_the_call_declares_the_scans_least_bytes(self, members):
        """What the ``pallas_call`` hands XLA is ``scan_cost``: ``Δ``, ``x``,
        ``y`` and ``B``, ``C`` once each in float32, ``4 · T · (3 · d_inner
        + 2 · d_state)``: what ``benchmark/costs_sambay.py`` counts a scan
        (``vmap`` scales the declaration by the members in front of the
        grid)."""
        length, d_inner, d_state = 64, 256, 16
        operands = _operands(length, d_inner, d_state)

        def kernel(*o):
            return pallas_scan.selective_scan(*o, interpret=True, chunk=16)

        f = kernel if members == 1 else jax.vmap(
            kernel, in_axes=(0, None, None, None, None))
        if members > 1:
            operands = (jnp.stack([operands[0]] * members),) + operands[1:]
        (cost,) = declared_costs(f, *operands)
        want = scan_cost(length, d_inner, d_state)
        assert (cost.flops, cost.transcendentals, cost.bytes_accessed) == (
            members * want.flops, members * want.transcendentals,
            members * want.bytes_accessed)
        assert cost.bytes_accessed == members * 4 * length * (
            3 * d_inner + 2 * d_state)
        assert cost.transcendentals == members * length * d_inner * d_state

    def test_the_published_scan_is_what_the_benchmark_counts(self):
        """One scan of the cell: 8,192 x 5,120 x 16.  The benchmark's own
        count of a sequence's scans (``costs_sambay.py``, loaded as the
        reference loads it) is this declaration times the Mamba layers."""
        sambay_tiny.reference()                  # puts the root on sys.path
        from benchmark.costs_sambay import scan_bytes_per_sequence

        cost = scan_cost(8192, 5120, 16)
        assert cost.bytes_accessed == 4 * 8192 * (3 * 5120 + 2 * 16)
        kinds = ("mamba", "window", "mamba_mem", "full_kv", "gmu", "cross")
        assert scan_bytes_per_sequence(kinds, 8192, 5120, 16) == (
            2 * cost.bytes_accessed)
        assert cost.transcendentals == 8192 * 5120 * 16
        assert cost.flops == 8192 * 5120 * (6 * 16 + 1)
