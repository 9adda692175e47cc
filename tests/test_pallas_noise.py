"""The Pallas row kernels vs their pure-JAX twins (interpret mode).

The kernels must be bit-compatible REORDERINGS of existing math:
- gather_noise_rows ≡ vmap(NoiseTable.slice).astype(dtype), bit for bit
- weighted_noise_sum ≡ ops/gradient.py::rank_weighted_noise_sum

On CPU they run in interpret mode (``interpret=True`` is passed here, never
derived from the backend); ``chip_smoke.py`` lowers the SAME code through
Mosaic on the chip and compares it with the same references.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from estorch_tpu.ops import make_noise_table, make_param_spec, rank_weighted_noise_sum
from estorch_tpu.ops.pallas_noise import (
    gather_noise_rows,
    rows_fit_dma,
    weighted_noise_sum,
)

TABLE = make_noise_table(1 << 16, seed=3)


def _slice_rows(offs, dim, dtype):
    """The oracle: today's form of the gather."""
    return jax.vmap(lambda o: TABLE.slice(o, dim))(offs).astype(dtype)


def _assert_same_bits(got, want):
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_array_equal(
        np.asarray(got.astype(jnp.float32)),
        np.asarray(want.astype(jnp.float32)))


class TestGatherNoiseRows:
    """The evaluation's pass over the table: rows by DMA of the aligned
    window, realigned and cast in VMEM, equal to the slice form's bit for
    bit whatever the offset's phase, the dim or the blocking."""

    # lane shifts (offset % 128), sublane shifts (offset // 128 % 8), both,
    # the table's first and last legal offsets (the last clamps the window)
    @pytest.mark.parametrize("offset", [0, 1, 64, 127, 128, 129, 384, 896,
                                        1023, 1024, 1025, 2047, 5000,
                                        "last-1024", "last-1", "last"])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_every_shift_class_is_the_slice(self, offset, dtype):
        dim = 300
        last = TABLE.size - dim
        if isinstance(offset, str):
            offset = last + int(offset[4:] or 0)
        offs = jnp.array([offset, 777], jnp.int32)
        got = gather_noise_rows(TABLE.data, offs, dim=dim, dtype=dtype,
                                interpret=True)
        _assert_same_bits(got, _slice_rows(offs, dim, dtype))

    # dims that are and are not multiples of 128 (and of the 1024 tile);
    # one row, fewer rows than window buffers, more
    @pytest.mark.parametrize("n,dim", [
        (1, 8), (1, 128), (2, 33), (3, 256), (13, 1024), (5, 3000),
        (16, 257), (9, 2048), (4, 640)])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dims_and_row_counts(self, n, dim, dtype):
        key = jax.random.key(n * 1000 + dim)
        offs = jax.random.randint(key, (n,), 0, TABLE.size - dim + 1,
                                  dtype=jnp.int32)
        got = gather_noise_rows(TABLE.data, offs, dim=dim, dtype=dtype,
                                interpret=True)
        _assert_same_bits(got, _slice_rows(offs, dim, dtype))

    def test_no_rows(self):
        got = gather_noise_rows(TABLE.data, jnp.zeros((0,), jnp.int32),
                                dim=8, dtype=jnp.bfloat16, interpret=True)
        assert got.shape == (0, 8) and got.dtype == jnp.bfloat16

    @pytest.mark.parametrize("size,dim,dtype,fits", [
        (1 << 16, 300, jnp.float32, True),
        (1 << 16, 300, jnp.bfloat16, False),  # the windows are f32 tiles
        (1000, 8, jnp.float32, False),  # not whole (8, 128) tiles
        (4096, 3000, jnp.float32, False),  # no room for one window
        (8192, 3000, jnp.float32, True)])
    def test_which_tables_the_kernels_serve(self, size, dim, dtype, fits):
        assert rows_fit_dma(jnp.zeros((size,), dtype), dim) is fits
        if fits:  # and what it admits does run, at the table's very end
            table = jax.random.normal(jax.random.key(0), (size,))
            offs = jnp.array([size - dim, 0], jnp.int32)
            got = gather_noise_rows(table, offs, dim=dim, dtype=dtype,
                                    interpret=True)
            np.testing.assert_array_equal(
                np.asarray(got[0]), np.asarray(table[size - dim:]))


class TestWeightedNoiseSum:
    @pytest.mark.parametrize("n,dim", [(1, 8), (7, 33), (64, 128), (33, 257),
                                       (5, 3000)])
    def test_matches_pure_jax(self, n, dim):
        key = jax.random.key(n * 1000 + dim)
        offs = jax.random.randint(key, (n,), 0, TABLE.size - dim, dtype=jnp.int32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (n,))
        got = weighted_noise_sum(TABLE.data, offs, w, dim=dim, interpret=True)
        want = rank_weighted_noise_sum(TABLE, offs, w, dim=dim)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)

    # the FMA is f32 on the VPU: the yardstick is the reduction at the
    # highest matmul precision
    @pytest.mark.parametrize("n,dim", [(1, 8), (2, 33), (64, 128), (33, 257),
                                       (5, 3000), (12, 1024)])
    def test_matches_highest_precision(self, n, dim):
        key = jax.random.key(n * 1000 + dim)
        offs = jax.random.randint(key, (n,), 0, TABLE.size - dim + 1,
                                  dtype=jnp.int32)
        w = jax.random.normal(jax.random.fold_in(key, 1), (n,))
        got = weighted_noise_sum(TABLE.data, offs, w, dim=dim,
                                 interpret=True)
        with jax.default_matmul_precision("highest"):
            want = rank_weighted_noise_sum(TABLE, offs, w, dim=dim)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=2e-6)

    def test_zero_weights_zero_sum(self):
        offs = jnp.array([5, 10, 15], jnp.int32)
        got = weighted_noise_sum(TABLE.data, offs, jnp.zeros(3), dim=16, interpret=True)
        np.testing.assert_array_equal(np.asarray(got), np.zeros(16, np.float32))

    def test_single_row_is_scaled_slice(self):
        got = weighted_noise_sum(
            TABLE.data, jnp.array([42], jnp.int32), jnp.array([2.5]), dim=64,
            interpret=True,
        )
        want = 2.5 * np.asarray(TABLE.data[42:106])
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6)

    def test_empty_input(self):
        got = weighted_noise_sum(
            TABLE.data, jnp.zeros((0,), jnp.int32), jnp.zeros((0,)), dim=8,
            interpret=True,
        )
        np.testing.assert_array_equal(np.asarray(got), np.zeros(8, np.float32))

    def test_every_alignment_and_the_table_end(self):
        """Rows are DMA'd as ALIGNED windows and realigned in VMEM: every
        lane/sublane phase of the start offset, and the last legal offset
        (where the window is clamped to the table), must give the slice."""
        dim = 300
        last = TABLE.size - dim
        offs = jnp.array([0, 1, 127, 128, 129, 1023, 1024, 1025, 2047,
                          last - 1024, last - 1, last], jnp.int32)
        for k, o in enumerate(np.asarray(offs)):
            w = jnp.zeros(offs.shape[0]).at[k].set(1.0)
            got = weighted_noise_sum(TABLE.data, offs, w, dim=dim,
                                     interpret=True)
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(TABLE.data[o:o + dim]),
                err_msg=f"offset {o}")

    def test_table_must_tile(self):
        with pytest.raises(ValueError, match="multiple of 1024"):
            weighted_noise_sum(jnp.zeros(1000), jnp.zeros((1,), jnp.int32),
                               jnp.ones(1), dim=8, interpret=True)


class TestEngineNoiseKernel:
    """The DMA form of the row gather must reproduce the chunked pure-JAX
    update inside the real sharded generation program (8 virtual devices,
    interpret mode; ``dma_gather``, tests/conftest.py, in place of a TPU)."""

    def _engines(self, mirrored, dma_gather):
        import optax

        from estorch_tpu.envs import CartPole
        from estorch_tpu.parallel import EngineConfig, ESEngine, population_mesh

        def apply(p, obs):
            return jnp.tanh(obs @ p["w1"] + p["b1"]) @ p["w2"] + p["b2"]

        params = {
            "w1": jax.random.normal(jax.random.key(0), (4, 16)) * 0.5,
            "b1": jnp.zeros(16),
            "w2": jax.random.normal(jax.random.key(1), (16, 2)) * 0.5,
            "b2": jnp.zeros(2),
        }
        flat, spec = make_param_spec(params)
        cfg = EngineConfig(
            population_size=32, sigma=0.1, horizon=30, mirrored=mirrored)

        def engine():
            return ESEngine(CartPole(), apply, spec, TABLE,
                            optax.adam(1e-2), cfg, population_mesh())

        ref = engine()
        with dma_gather():
            kern = engine()
        assert (ref.noise_gather_form, kern.noise_gather_form) \
            == ("slice", "dma")
        return (ref, kern), flat

    @pytest.mark.parametrize("mirrored", [True, False])
    def test_kernel_update_matches_pure_jax(self, mirrored, devices8,
                                            dma_gather):
        (ref, kern), flat = self._engines(mirrored, dma_gather)
        s_ref = ref.init_state(flat, jax.random.PRNGKey(5))
        s_k = kern.init_state(flat, jax.random.PRNGKey(5))
        for gen in range(2):
            s_ref, m_ref = ref.generation_step(s_ref)
            s_k, m_k = kern.generation_step(s_k)
            np.testing.assert_array_equal(
                np.asarray(m_ref["fitness"]), np.asarray(m_k["fitness"]),
                err_msg=f"gen {gen}",
            )
            np.testing.assert_allclose(
                np.asarray(s_ref.params_flat), np.asarray(s_k.params_flat),
                rtol=1e-5, atol=1e-6, err_msg=f"gen {gen}",
            )


class TestNoiseGatherForm:
    """``ESEngine.noise_gather_form``: resolved once at build, reported in
    the manifest and the gauges, and — forced by ``dma_gather``
    (tests/conftest.py), interpreted — the same generation as the slice
    form.  The rule on a TPU mesh: tests/test_trace_stages.py."""

    @staticmethod
    def _es(**over):
        import optax

        from estorch_tpu import ES, JaxAgent, MLPPolicy
        from estorch_tpu.envs import CartPole

        kw = dict(
            population_size=32, sigma=0.1, seed=0,
            policy_kwargs={"action_dim": 2, "hidden": (16,)},
            agent_kwargs={"env": CartPole(), "horizon": 30},
            optimizer_kwargs={"learning_rate": 3e-2}, table_size=1 << 16)
        kw.update(over)
        return ES(MLPPolicy, JaxAgent, optax.adam, **kw)

    @pytest.mark.parametrize("name,over,form", [
        # a CPU mesh cannot run a Mosaic kernel: the engine keeps the slice
        ("cpu_mesh", {}, "slice"),
        ("cpu_mesh_bf16", {"compute_dtype": "bfloat16"}, "slice"),
        ("cpu_mesh_unmirrored", {"mirrored": False}, "slice"),
        ("low_rank", {"low_rank": 1}, "slice"),
    ])
    def test_resolved_and_reported(self, name, over, form):
        es = self._es(**over)
        assert es.engine.noise_gather_form == form
        assert es.run_manifest()["config"]["noise_gather_form"] == form
        assert es.obs.counters.snapshot()["noise_gather_form"] == form

    def test_update_only_engines_follow_the_rule(self, dma_gather):
        """The pooled path's update program calls ``_local_grad`` too."""
        import optax

        from estorch_tpu import ES, MLPPolicy, PooledAgent

        def mk(**over):
            return ES(MLPPolicy, PooledAgent, optax.adam, population_size=8,
                      sigma=0.1,
                      policy_kwargs={"action_dim": 2, "hidden": (8,)},
                      agent_kwargs={"env_name": "cartpole", "horizon": 10},
                      optimizer_kwargs={"learning_rate": 1e-2},
                      table_size=1 << 14, **over)

        assert mk().engine.core.noise_gather_form == "slice"
        with dma_gather():  # what the core engine resolves is what it reports
            assert mk().engine.core.noise_gather_form == "dma"

    @pytest.mark.parametrize("over", [
        {}, {"compute_dtype": "bfloat16"}, {"episodes_per_member": 2},
        {"population_size": 36},  # ghost pairs on the 8-device mesh
        {"obs_norm": True}, {"mirrored": False},
    ], ids=["f32", "bf16", "episodes2", "padded", "obs_norm", "unmirrored"])
    def test_forced_dma_generation_equals_slice(self, over, devices8,
                                                dma_gather):
        """Pair-shared (and, unmirrored, materialised) generations: the
        gathered rows are the slice form's bits, so the fitness is EQUAL;
        the update's f32 FMA against the slice form's matmul agrees to f32
        tolerance."""
        ref = self._es(**over)
        with dma_gather():
            dma = self._es(**over)
        want = "materialised" if over.get("mirrored") is False \
            else "pair_shared"
        assert ref.engine.forward_form == dma.engine.forward_form == want
        assert (ref.engine.noise_gather_form, dma.engine.noise_gather_form) \
            == ("slice", "dma")
        s_ref, s_dma = ref.state, dma.state
        for gen in range(2):
            s_ref, m_ref = ref.engine.generation_step(s_ref)
            s_dma, m_dma = dma.engine.generation_step(s_dma)
            np.testing.assert_array_equal(
                np.asarray(m_ref["fitness"]), np.asarray(m_dma["fitness"]),
                err_msg=f"gen {gen}")
            np.testing.assert_allclose(
                np.asarray(s_ref.params_flat), np.asarray(s_dma.params_flat),
                rtol=1e-5, atol=1e-6, err_msg=f"gen {gen}")


def test_dims_past_the_vmem_budget_resolve_slice():
    """>1M params: ``weighted_noise_sum``'s 3·dim f32 would not fit VMEM
    (parallel/engine.py::NOISE_KERNEL_MAX_DIM), so where a chip would run
    the kernels the rule keeps the chunked pure-JAX forms for such a run."""
    import optax

    from estorch_tpu import ES, JaxAgent, MLPPolicy
    from estorch_tpu.envs import SyntheticEnv
    from estorch_tpu.parallel.engine import NOISE_KERNEL_MAX_DIM

    env = SyntheticEnv()  # obs 376: hidden 1024x1024 → ~1.45M params

    def resolved_on_a_chip(hidden):
        engine = ES(
            policy=MLPPolicy,
            agent=JaxAgent,
            optimizer=optax.adam,
            population_size=8,
            policy_kwargs={"action_dim": env.action_dim,
                           "hidden": hidden, "discrete": False},
            agent_kwargs={"env": env, "horizon": 10},
            optimizer_kwargs={"learning_rate": 1e-2},
            table_size=1 << 21,
        ).engine
        assert engine.noise_gather_form == "slice"  # a CPU mesh
        engine._pallas_interpret = False  # what a TPU mesh makes it
        return engine.spec.dim, engine._resolve_noise_gather_form()

    dim, form = resolved_on_a_chip((1024, 1024))
    assert dim > NOISE_KERNEL_MAX_DIM and form == "slice"
    dim, form = resolved_on_a_chip((8,))
    assert dim <= NOISE_KERNEL_MAX_DIM and form == "dma"


class TestTheDeclaredCost:
    """``row_kernel_cost``: what the row kernels tell XLA they move, against
    a count made row by row, and what ``pallas_call`` is handed."""

    @pytest.mark.parametrize("n, dim, dtype", [
        (5, 300, jnp.float32), (3, 1000, jnp.bfloat16), (1, 128, jnp.float32)])
    def test_gather_against_a_count_row_by_row(self, n, dim, dtype):
        from pallas_costs import declared_costs

        from estorch_tpu.ops import pallas_noise

        sublanes = 8 * 4 // jnp.dtype(dtype).itemsize
        rows_out = -(-(-(-dim // 128)) // sublanes) * sublanes
        window_rows = rows_out + 8
        read = written = 0
        for _ in range(n):
            read += window_rows * 128 * 4           # the aligned f32 window
            written += rows_out * 128 * jnp.dtype(dtype).itemsize
        want = pallas_noise.row_kernel_cost(
            n, window_rows, rows_out, jnp.dtype(dtype).itemsize, summed=False)
        assert (want.flops, want.transcendentals, want.bytes_accessed) == (
            0, 0, read + written)
        offs = jnp.arange(n, dtype=jnp.int32) * 7
        got, = declared_costs(
            lambda o: gather_noise_rows(TABLE.data, o, dim, dtype, True), offs)
        assert got == want

    @pytest.mark.parametrize("n, dim", [(5, 300), (2, 4096)])
    def test_sum_against_a_count_row_by_row(self, n, dim):
        from pallas_costs import declared_costs

        from estorch_tpu.ops import pallas_noise

        rows_out = -(-(-(-dim // 128)) // 8) * 8
        window_rows = rows_out + 8
        flops = read = 0
        for _ in range(n):
            read += window_rows * 128 * 4
            flops += 2 * rows_out * 128             # a multiply and an add
        want = pallas_noise.row_kernel_cost(n, window_rows, rows_out, 4,
                                            summed=True)
        assert (want.flops, want.bytes_accessed) == (
            flops, read + rows_out * 128 * 4)
        offs = jnp.arange(n, dtype=jnp.int32) * 7
        w = jnp.ones((n,), jnp.float32)
        got, = declared_costs(
            lambda o, w: weighted_noise_sum(TABLE.data, o, w, dim, True),
            offs, w)
        assert got == want

    def test_under_vmap_each_members_call_declares_its_own(self):
        # offsets are scalar-prefetched: pallas_call's batching rule runs
        # the kernel once a member in a loop, so a trace sums one
        # declaration per call (the attention kernel's members go in front
        # of its grid instead, and its declaration is scaled)
        from pallas_costs import declared_costs

        offs = jnp.arange(6, dtype=jnp.int32).reshape(2, 3) * 11

        def call(o):
            return gather_noise_rows(TABLE.data, o, 300, jnp.float32, True)

        one, = declared_costs(call, offs[0])
        looped, = declared_costs(jax.vmap(call), offs)
        assert looped == one
