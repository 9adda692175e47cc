"""SambaYLM (models/sambay_lm.py) against the plain reference the benchmark
judges its cell by (benchmark/reference/sambay_lm.py): float32, ``highest``,
Python loops over the layers, the recurrence a sequential scan of the
``[d_inner, d_state]`` state, two masked softmaxes a diff-head, every
perturbed leaf materialised."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.flatten_util import ravel_pytree

import sambay_tiny
from estorch_tpu.envs import TokenScoreEnv
from estorch_tpu.models import SambaYLM, lm_blocks
from estorch_tpu.models.sambay_lm import (lambda_init, layer_kinds,
                                          selective_scan)
from estorch_tpu.ops.lowrank import (lowrank_tree_noise,
                                     lowrank_tree_weighted_sum,
                                     make_lowrank_tree_spec)
from estorch_tpu.ops.pallas_attention import (attention_facts,
                                              attention_form,
                                              attention_form_why, call_form,
                                              heads_in_pairs, kernel_scope)
from estorch_tpu.parallel.mesh import unmatched_leaves

# the models here are tiny (heads of 8, sequences of 16): inside a
# ``kernel_scope`` their attention calls take the kernel all the same
# (conftest.py::tiny_widths fakes the call's own rule,
# ``pallas_attention.fits``, which the interpreter does not need)
pytestmark = pytest.mark.usefixtures("tiny_widths")

# float32 on both sides; what differs is the ORDER of float32 sums (blocked
# softmax against whole, one call of the core against two softmaxes a head)
# on values of magnitude 1: measured 2e-6 to 6e-6.  1e-4 would still catch
# bfloat16 anywhere (1e-2)
TOL = 1e-4


@pytest.fixture(scope="module")
def ref():
    return sambay_tiny.reference()


def _spread(ref, cfg, key):
    """Seeded weights with every matrix eight times its initial spread and
    biases that are not zero, so that logits, both softmax maps, the
    window and every bias matter."""
    s = ref.sizes(cfg)
    theta = np.array(ref.init_theta(key, cfg))
    rng = np.random.default_rng(0)
    for path, (off, shape) in ref.param_offsets(s).items():
        name = path.rsplit("/", 1)[-1]
        if name.endswith("bias") and name not in ("conv_bias", "dt_bias"):
            theta[off:off + shape[0]] = 0.3 * rng.normal(size=shape)
        elif len(shape) == 2 and name != "A_log":
            theta[off:off + math.prod(shape)] *= 8.0
    return jnp.asarray(theta)


def _built(ref, rank=2, **policy):
    cfg = sambay_tiny.config(rank=rank, policy=policy)
    lm = SambaYLM(**{**sambay_tiny.TINY, **policy})
    theta = _spread(ref, cfg, jax.random.PRNGKey(3))
    shapes = lm.param_shapes()
    _, unravel = ravel_pytree(jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, x.dtype), shapes))
    spec = make_lowrank_tree_spec(shapes, rank, dense=lm.dense_noise_leaves)
    noise = jax.random.normal(jax.random.PRNGKey(5), (spec.noise_dim,))
    return dict(cfg=cfg, s=ref.sizes(cfg), lm=lm, theta=theta,
                unravel=unravel, params=unravel(theta), spec=spec,
                noise=noise)


@pytest.fixture(scope="module")
def tiny(ref):
    return _built(ref)


def _tokens(length, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (length,), 0, 64)


# -------------------------------------------- (a) against the reference

@pytest.mark.parametrize("length", [21, 16, 5])
@pytest.mark.parametrize("sign", [0.0, 1.0, -1.0])
def test_logits_match_the_reference(ref, tiny, sign, length):
    """The whole logits and the policy output: the centre (sign 0) and both
    members of a pair from ONE factor read; logits, not tokens."""
    tokens, c = _tokens(length, length), 0.05 * sign
    noise = None if sign == 0.0 else tiny["spec"].unpack(tiny["noise"])
    member = ref.Member(tiny["s"], tiny["theta"],
                        None if sign == 0.0 else tiny["noise"], c)
    want = ref.logits(tiny["s"], member, tokens)
    got = tiny["lm"].logits(tiny["params"], tokens, noise, c)
    assert got.shape == want.shape == (length, 64)
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    assert float(jnp.abs(want).max()) > 1.0         # the logits spread
    logp, last = tiny["lm"].perturbed_apply(tiny["params"], noise, c, tokens)
    want_logp, want_last = ref.forward(tiny["s"], member, tokens,
                                       head_block=8)
    assert logp.shape == (length - 1,) and last.shape == (64,)
    np.testing.assert_allclose(logp, want_logp, atol=TOL, rtol=0)
    np.testing.assert_allclose(last, want_last, atol=TOL, rtol=0)
    if sign:
        centre = ref.logits(tiny["s"], ref.Member(
            tiny["s"], tiny["theta"], None, 0.0), tokens)
        assert float(jnp.abs(want - centre).max()) > 0.05


def test_apply_is_the_centre_alone(tiny):
    tokens = _tokens(21)
    got = tiny["lm"].apply({"params": tiny["params"]}, tokens)
    want = tiny["lm"].perturbed_apply(tiny["params"], None, 0.0, tokens)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    np.testing.assert_array_equal(
        tiny["lm"].apply({"params": tiny["params"]}, tokens, method="logits"),
        tiny["lm"].logits(tiny["params"], tokens))


@pytest.mark.parametrize("sign", [0.0, 1.0])
def test_what_the_boundary_layers_hand_on_matches_the_reference(ref, tiny,
                                                                sign):
    """``m`` is the last Mamba layer's scan output with the ``D`` term and
    before the gate; ``(K, V)`` the full layer's keys and value pairs."""
    tokens, c = _tokens(21, 4), 0.05 * sign
    noise = None if sign == 0.0 else tiny["spec"].unpack(tiny["noise"])
    member = ref.Member(tiny["s"], tiny["theta"],
                        None if sign == 0.0 else tiny["noise"], c)
    want_m, (want_k, want_v) = ref.carried(tiny["s"], member, tokens)
    carried = {}
    tiny["lm"].hidden(tiny["params"], noise, c, tokens, carried)
    np.testing.assert_allclose(carried["memory"], want_m, atol=TOL, rtol=0)
    k, v = carried["kv"]
    np.testing.assert_allclose(k, want_k, atol=TOL, rtol=0)
    # ONE block [T, 2 · 4] a value pair, as published: both of its maps
    # read it where it lies (no copy a map is carried)
    assert v.shape == (21, 2 * 8)
    np.testing.assert_allclose(v, want_v, atol=TOL, rtol=0)


@pytest.mark.parametrize("unroll", [1, 4, 21, 64])
def test_the_scan_is_the_recurrence_whatever_is_unrolled(unroll):
    t, d, n = 21, 6, 3
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    x, b, c = (jax.random.normal(k[i], shape) for i, shape in
               enumerate([(t, d), (t, n), (t, n)]))
    delta = jax.nn.softplus(jax.random.normal(k[3], (t, d)))
    a = -jnp.exp(jax.random.normal(k[4], (d, n)))
    h, want = np.zeros((d, n)), []
    for i in range(t):
        h = (np.exp(np.asarray(delta[i])[:, None] * np.asarray(a)) * h
             + np.asarray(delta[i] * x[i])[:, None] * np.asarray(b[i])[None])
        want.append(h @ np.asarray(c[i]))
    np.testing.assert_allclose(selective_scan(x, delta, a, b, c, unroll),
                               np.stack(want), atol=1e-5, rtol=0)


# ---------------------------------------------- (b) the layer pattern

def test_the_published_pattern():
    kinds = layer_kinds(32)
    assert kinds == (("mamba", "window") * 8 + ("mamba_mem", "full_kv")
                     + ("gmu", "cross") * 7)
    assert len(kinds) == 32
    with pytest.raises(ValueError, match="mb_per_layer 2"):
        layer_kinds(32, 4)
    with pytest.raises(ValueError, match="multiple of 4"):
        layer_kinds(30)


def test_the_six_layer_cut_keeps_the_published_indices():
    published = sambay_tiny.published()
    kwargs = published["build"]["kwargs"]["policy_kwargs"]
    lm = SambaYLM(**kwargs)
    assert lm.layer_indices == (0, 1, 16, 17, 18, 19)
    assert lm.layer_types == ("mamba", "window", "mamba_mem", "full_kv",
                              "gmu", "cross")
    assert list(lm.layer_types) == published["layer_types"]
    assert published["published_layer_types"] == list(layer_kinds(32))
    assert published["layers_held"] == list(lm.layer_indices)
    assert (lm.kv_shared_by, lm.memory_shared_by) == (1, 1)
    assert dict(lm.declaration().kernels)[attention_facts][2] == (
        ("window", 512), ("full_kv", None), ("cross", None))
    assert dict(SambaYLM(**{**kwargs, "layer_indices": (16, 17)}
                         ).declaration().kernels)[attention_facts][2] == (
        ("full_kv", None),)
    for index, want in [(1, 0.3555), (17, 0.7963), (19, 0.7980)]:
        assert lambda_init(index) == pytest.approx(
            0.8 - 0.6 * math.exp(-0.3 * index))
        assert lambda_init(index) == pytest.approx(want, abs=1e-4)
    whole = SambaYLM(**{**kwargs, "layer_indices": None})
    assert whole.layer_indices == tuple(range(32))
    assert (whole.kv_shared_by, whole.memory_shared_by) == (7, 7)


def test_each_layer_takes_the_lambda_of_its_published_index(ref):
    """The same six layers read as the layers of a deeper published model
    give other logits: λ_init comes from the published index, not from the
    position in the cut."""
    near = _built(ref)
    far = _built(ref, published_layers=16, layer_indices=(0, 1, 8, 9, 10, 11))
    assert near["lm"].layer_types == far["lm"].layer_types
    tokens = _tokens(21)
    a = near["lm"].logits(near["params"], tokens)
    b = far["lm"].logits(far["params"], tokens)
    assert float(jnp.abs(a - b).max()) > 1e-3
    member = ref.Member(far["s"], far["theta"], None, 0.0)
    np.testing.assert_allclose(b, ref.logits(far["s"], member, tokens),
                               atol=TOL, rtol=0)


@pytest.mark.parametrize("indices, missing", [
    ((0, 1, 6, 7), "mamba_mem"), ((0, 1, 4, 7), "full_kv"),
    ((6, 7), "mamba_mem"), ((4, 7), "full_kv")])
def test_a_cut_holds_what_its_layers_read(indices, missing):
    with pytest.raises(ValueError, match=missing):
        SambaYLM(**{**sambay_tiny.TINY, "layer_indices": indices})


def test_layer_indices_are_ascending_published_indices():
    for bad in [(1, 0), (0, 0, 1), (0, 8), ()]:
        with pytest.raises(ValueError, match="ascending"):
            SambaYLM(**{**sambay_tiny.TINY, "layer_indices": bad})
    with pytest.raises(ValueError, match="pairs adjacent heads"):
        SambaYLM(**{**sambay_tiny.TINY, "num_key_value_heads": 1})


# --------------------------------------- (c) the window in the shared core

def _core_case(length, heads=4, kv_heads=2, width=4, value=6, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k[0], (length, heads * width)),
            jax.random.normal(k[1], (length, kv_heads * width)),
            jax.random.normal(k[2], (length, kv_heads * value)))


def _dense_masked_softmax(q, k, v, heads, kv_heads, scale, window):
    t = q.shape[0]
    qh = np.asarray(q, np.float64).reshape(t, heads, -1)
    kh = np.asarray(k, np.float64).reshape(t, kv_heads, -1)
    vh = np.asarray(v, np.float64).reshape(t, kv_heads, -1)
    at = np.arange(t)
    mask = at[None, :] <= at[:, None]
    if window is not None:
        mask &= at[None, :] > at[:, None] - window
    out = []
    for h in range(heads):
        g = h // (heads // kv_heads)
        s = np.where(mask, qh[:, h] @ kh[:, g].T * scale, -np.inf)
        p = np.exp(s - s.max(axis=-1, keepdims=True))
        out.append(p / p.sum(axis=-1, keepdims=True) @ vh[:, g])
    return np.stack(out, axis=1).reshape(t, -1)


@pytest.mark.parametrize("window", [1, 3, 8, 9, 16, 21, 40])
@pytest.mark.parametrize("length, block", [(21, 8), (16, 8), (5, 8),
                                            (21, 32)])
def test_a_window_matches_a_dense_masked_softmax(length, block, window):
    """Windows under, at and over the block and the sequence."""
    q, k, v = _core_case(length)
    got = lm_blocks.attention_core(q, k, v, num_heads=4, num_kv_heads=2,
                                   scale=0.5, block=block, window=window)
    want = _dense_masked_softmax(q, k, v, 4, 2, 0.5, window)
    np.testing.assert_allclose(got, want, atol=2e-6, rtol=0)
    full = lm_blocks.attention_core(q, k, v, num_heads=4, num_kv_heads=2,
                                    scale=0.5, block=block)
    if window >= length:
        np.testing.assert_allclose(got, full, atol=2e-6, rtol=0)
    else:
        assert float(jnp.abs(got - full).max()) > 1e-3


def _core_equations(window, length=32, block=8):
    q, k, v = _core_case(length)

    def core(q, k, v):
        return lm_blocks.attention_core(
            q, k, v, num_heads=4, num_kv_heads=2, scale=0.5, block=block,
            window=window)

    return jax.make_jaxpr(core)(q, k, v)


def test_a_window_skips_the_key_blocks_outside_the_band():
    """A window of one block: every query block but the first is scored
    against TWO key blocks, however long the sequence; the full form's
    query block ``i`` against ``i + 1``."""
    def score_widths(jaxpr):
        # a score matmul contracts the head width 4 with a batch axis (the
        # key heads); its keys are the operand without the group axis
        out = []
        for eq in jaxpr.jaxpr.eqns:
            if eq.primitive.name != "dot_general":
                continue
            contract, batch = eq.params["dimension_numbers"]
            if not batch[0] or eq.invars[0].aval.shape[contract[0][0]] != 4:
                continue
            side = 0 if eq.invars[0].aval.ndim == 3 else 1
            keys = eq.invars[side].aval.shape
            out += [n for axis, n in enumerate(keys)
                    if axis not in contract[side] + batch[side]]
        return out

    banded = score_widths(_core_equations(8))
    full = score_widths(_core_equations(None))
    assert sorted(set(full)) == [8, 16, 24, 32]
    assert sorted(set(banded)) == [8, 16]
    assert banded.count(16) == 3 and full.count(32) == 1


def test_no_window_is_the_program_it_was():
    """``window=None`` traces to the equations a core without the argument
    traced to: the key slice, the mask and nothing else (granite's, ouro's
    and joyai's programs do not move)."""
    none = _core_equations(None)
    names = [eq.primitive.name for eq in none.jaxpr.eqns]
    assert "and" not in names and names.count("gt") == 0
    huge = _core_equations(10 ** 6)
    assert [eq.primitive.name for eq in huge.jaxpr.eqns].count("and") == 4


def test_a_call_with_a_window_is_the_xla_form_inside_a_kernel_scope():
    """A band narrower than the kernel's block (4 keys of 16 positions;
    the published 512 of 8,192): inside an engine's scope the call with
    that ``window`` traces to the XLA form's equations (and so gives its
    result), the call without one to the kernel
    (``pallas_attention.call_form``)."""
    q, k, v = _core_case(16)

    def traced(window, scoped):
        def core(q, k, v):     # a new closure a trace: jit caches by function
            return lm_blocks.attention_core(
                q, k, v, num_heads=4, num_kv_heads=2, scale=0.5, block=8,
                window=window)

        if not scoped:
            return jax.make_jaxpr(core)(q, k, v), core(q, k, v)
        with kernel_scope(True):
            return jax.make_jaxpr(core)(q, k, v), core(q, k, v)

    (banded, got), (outside, want) = traced(4, True), traced(4, False)
    assert "pallas_call" not in str(banded)
    assert str(banded) == str(outside)
    np.testing.assert_array_equal(got, want)
    full, _ = traced(None, True)
    assert "pallas_call" in str(full)


@pytest.mark.parametrize("widths, length, window, kv_heads, form, why", [
    # the published model: a pair is one lane block; the band is the call's
    ((64, 0, 128), 8192, 512, 20, "kernel",
     "one TPU device, two score heads a column block, whole row blocks; "
     "layers with a window of 512 in the XLA form"),
    ((64, 0, 128), 8192, None, 20, "kernel",
     "one TPU device, two score heads a column block, whole row blocks"),
    ((64, 0, 128), 8192, 512, 5, "xla", "over 5 key heads"),
    ((64, 0, 128), 8192, 512, None, "xla", "64 wide and its values 128"),
    # grouped heads of 64 with 64-wide values: half a block of context
    ((64, 0, 64), 8192, None, 20, "xla", "64 wide and its values 64"),
    # values of two blocks are no pair's ONE block
    ((64, 0, 256), 8192, None, 20, "xla", "64 wide and its values 256"),
    ((128, 0, 128), 8192, 512, 8, "kernel",
     "whole column blocks, whole row blocks; layers with a window of 512"),
    ((128, 0, 128), 8192, None, 8, "kernel",
     "one TPU device, whole column blocks, whole row blocks"),
    ((64, 0, 128), 8000, 512, 20, "xla", "divides 8000"),
    (128, 200, None, None, "xla", "divides 200"),
])
def test_the_attention_rule_takes_pairs_and_leaves_the_band_to_the_call(
        widths, length, window, kv_heads, form, why):
    assert attention_form("tpu", 1, widths, length, window,
                          kv_heads) == form
    got, reason = attention_form_why("tpu", 1, widths, length, window,
                                     kv_heads)
    assert got == form and why in reason
    assert attention_form_why("cpu", 1, widths, length, window,
                              kv_heads)[1].startswith("the devices are 'cpu'")
    assert "4 devices" in attention_form_why("tpu", 4, widths, length,
                                             window, kv_heads)[1]
    # which form a CALL takes in a program of that form: half a block of
    # band is the block itself over whole column blocks, never over pairs
    paired = heads_in_pairs(widths, kv_heads)
    assert paired == (widths == (64, 0, 128) and (kv_heads or 1) % 2 == 0)
    assert call_form(form, window, length, paired) == (
        "kernel" if form == "kernel" and (window is None or not paired)
        else "xla")
    assert ("in the kernel" in reason) == (
        form == "kernel" and window is not None and not paired)
    assert call_form(form, None, length, paired) == form


# ------------------------------------------- (d) differential attention

def test_the_differential_combine_against_its_formula():
    t, pairs, group, width = 5, 2, 2, 6
    ctx = jax.random.normal(jax.random.PRNGKey(0),
                            (t, pairs * 2 * group * width))
    gamma = jax.random.normal(jax.random.PRNGKey(1), (width,))
    got = lm_blocks.differential_combine(
        ctx, 0.7, gamma, pairs=pairs, group=group, lambda_init=0.3, eps=1e-5)
    maps = np.asarray(ctx).reshape(t, pairs, 2, group, width)
    o = maps[:, :, 0] - 0.7 * maps[:, :, 1]
    o = o / np.sqrt((o * o).mean(-1, keepdims=True) + 1e-5) * np.asarray(gamma)
    np.testing.assert_allclose(got, (o * 0.7).reshape(t, -1), atol=1e-6)


def test_both_maps_go_through_one_call_of_the_core(tiny):
    """Each attention layer is ONE call of the shared core (8 score heads
    over 4 key heads in pairs, ONE block of 8 values a key pair), not two
    attentions."""
    calls = []
    honest = lm_blocks.attention_core

    def counting(q, k, v, **kw):
        assert kw["paired"]
        calls.append((q.size // 21, k.size // 21, v.size // 21,
                      kw["num_heads"], kw["num_kv_heads"], kw["window"]))
        return honest(q, k, v, **kw)

    lm_blocks.attention_core = counting
    try:
        tiny["lm"].hidden(tiny["params"], None, 0.0, _tokens(21))
    finally:
        lm_blocks.attention_core = honest
    assert calls == [(32, 16, 16, 8, 4, 5), (32, 16, 16, 8, 4, None),
                     (32, 16, 16, 8, 4, None)]


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, TOL),
                                        (jnp.bfloat16, 0.1)])
@pytest.mark.parametrize("length", [21, 16])
def test_forced_through_the_interpreted_kernel_it_agrees(tiny, dtype, tol,
                                                         length):
    """The whole model inside a ``kernel_scope``, the windowed layer
    present: the two full-causal differential layers run the Pallas kernel
    on their pairs where they lie (interpreted here), the call with the
    window takes the XLA form and raises nothing; scores and logits agree
    with the XLA form's to the order of float32 sums, or to bfloat16's
    rounding of the probabilities."""
    tokens = _tokens(length, 4)
    params = jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if x.ndim == 2 else x, tiny["params"])
    factors = tiny["spec"].unpack(tiny["noise"])
    lm = tiny["lm"]
    want = lm.perturbed_apply(params, factors, 0.05, tokens)
    with kernel_scope(interpret=True):
        program = str(jax.make_jaxpr(
            lambda p, f: lm.perturbed_apply(p, f, 0.05, tokens))(
                params, factors))
        got = lm.perturbed_apply(params, factors, 0.05, tokens)
    # full_kv and cross; the window's call is not among them
    assert program.count("jaxpr=causal_attention") == 2
    for g, w in zip(got, want):
        assert g.dtype == jnp.float32 and bool(jnp.isfinite(g).all())
        np.testing.assert_allclose(g, w, atol=tol, rtol=0)
    assert float(jnp.abs(got[0] - want[0]).max()) > 0.0  # another program


def test_the_heads_kernel_is_reached_through_the_same_scope(ref):
    """A hidden width of one 128-lane block over 512 positions fits the
    head's rule (ops/pallas_head.py): inside a ``kernel_scope`` the tied
    head scores its next tokens in the head's kernel (interpreted here)
    beside the attention's two, and scores and last logits agree to the
    order of float32 sums."""
    lm = SambaYLM(**{**sambay_tiny.TINY, "hidden_size": 128,
                     "intermediate_size": 64, "attention_block": 128,
                     "head_block": 96, "sliding_window": 100,
                     "scan_chunk": 16})
    tokens = _tokens(512, 4)
    params = jax.tree_util.tree_map(
        lambda x: 3.0 * x if x.ndim == 2 and x.shape != (256, 4) else x,
        lm.init(jax.random.PRNGKey(2))["params"])
    spec = make_lowrank_tree_spec(lm.param_shapes(), 1,
                                  dense=lm.dense_noise_leaves)
    factors = spec.unpack(
        jax.random.normal(jax.random.PRNGKey(5), (spec.noise_dim,)))
    want = lm.perturbed_apply(params, factors, 0.05, tokens)
    with kernel_scope(interpret=True):
        program = str(jax.make_jaxpr(
            lambda p, f: lm.perturbed_apply(p, f, 0.05, tokens))(
                params, factors))
        got = lm.perturbed_apply(params, factors, 0.05, tokens)
    assert "next_token_scores" in program
    assert program.count("jaxpr=causal_attention") == 2
    for g, w in zip(got, want):
        assert g.shape == w.shape and bool(jnp.isfinite(g).all())
        np.testing.assert_allclose(g, w, atol=TOL, rtol=0)
    assert float(jnp.abs(got[0] - want[0]).max()) > 0.0  # another program


# ------------------------------------- (e) members under the engine's vmaps

def test_what_is_handed_on_is_per_member_under_vmap(tiny):
    """Two members with different noise get different ``m`` and ``(K, V)``,
    and each member's output under the pair x sign ``vmap``s equals its own
    evaluation."""
    lm, spec, tokens = tiny["lm"], tiny["spec"], _tokens(21, 9)
    rows = jax.random.normal(jax.random.PRNGKey(7), (3, spec.noise_dim))
    signs = jnp.asarray([0.05, -0.05])

    def member(row, c):
        carried = {}
        h = lm.hidden(tiny["params"], spec.unpack(row), c, tokens, carried)
        return h, carried["memory"], carried["kv"][0], carried["kv"][1]

    h, m, k, v = jax.vmap(lambda row: jax.vmap(
        lambda c: member(row, c))(signs))(rows)
    assert m.shape == (3, 2, 21, 64) and k.shape == (3, 2, 21, 16)
    assert v.shape == (3, 2, 21, 2 * 8)
    for i in range(3):
        for j in range(2):
            own = member(rows[i], signs[j])
            for got, want in zip((h, m, k, v), own):
                np.testing.assert_allclose(got[i, j], want, atol=1e-5,
                                           rtol=0)
    for x in (m, k, v):
        flat = np.asarray(x).reshape(6, -1)
        for i in range(6):
            for j in range(i):
                assert np.abs(flat[i] - flat[j]).max() > 1e-3


# ----------------------------------- (f) the tree, its noise, its sharding

def test_init_draws_the_declared_tree(tiny):
    lm = tiny["lm"]
    params = lm.init(jax.random.PRNGKey(0))["params"]
    shapes = lm.param_shapes()
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(shapes))
    for p, s in zip(jax.tree_util.tree_leaves(params),
                    jax.tree_util.tree_leaves(shapes)):
        assert p.shape == s.shape and p.dtype == jnp.float32
    mamba = params["layer_00"]["mamba"]
    np.testing.assert_allclose(
        mamba["A_log"], np.broadcast_to(np.log(np.arange(1, 5)), (64, 4)),
        rtol=1e-6)
    assert float(mamba["D"].min()) == 1.0
    dt = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert 1e-3 * 0.99 <= dt.min() and dt.max() <= 1e-1 * 1.01
    attn = params["layer_01"]["attn"]
    assert float(jnp.abs(attn["qkv_bias"]).max()) == 0.0
    assert float(attn["subln"].min()) == 1.0
    assert 0.02 < float(jnp.std(attn["lambda_q1"])) < 0.3
    assert set(params["layer_05"]["attn"]) == {
        "q", "q_bias", "o", "o_bias", "subln", "lambda_q1", "lambda_k1",
        "lambda_q2", "lambda_k2"}
    assert set(params["layer_04"]["gmu"]) == {"gmu_in", "gmu_out"}


def test_published_sizes_and_layouts(ref):
    """The configuration file's counts, recomputed from the built tree and
    from the reference's layout."""
    published = sambay_tiny.published()
    lm = SambaYLM(**published["build"]["kwargs"]["policy_kwargs"])
    shapes = lm.param_shapes()
    count = lambda tree: sum(  # noqa: E731
        math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == published["parameters"] == 697_094_272
    assert published["deployment"]["state_bytes_per_chip"] == 14 * count(
        shapes)
    assert [count(shapes[f"layer_{i:02d}"]) for i in range(6)] == [
        119_895_040, 98_322_304, 119_895_040, 98_322_304, 104_867_840,
        91_766_144]
    per = published["published"]["per_layer_parameters"]
    layer = lambda i, key: count(shapes[f"layer_{i:02d}"][key])  # noqa: E731
    assert (layer(0, "mamba"), layer(1, "attn"), layer(4, "gmu"),
            layer(5, "attn"), layer(0, "mlp")) == (
        per["mamba"], per["attention"], per["gmu"], per["cross_attention"],
        per["ffn"])
    assert count(shapes["embed"]) == 64_020_480
    about = ref.describe(published)
    assert about["param_dim"] == count(shapes)
    spec = make_lowrank_tree_spec(shapes, 1, dense=lm.dense_noise_leaves)
    assert about["noise_dim"] == spec.noise_dim
    # 2 x the matmul weights a token passes: 1.27 GFLOP in the layers, 0.13
    # in the head
    assert about["dense_flops_per_member_step"] == 2 * 632_750_080
    assert about["head_flops_per_member_step"] == 2 * 2560 * 25008
    whole = SambaYLM(**{**published["build"]["kwargs"]["policy_kwargs"],
                        "layer_indices": None, "vocab_size": 200064})
    assert count(whole.param_shapes()) == 3_852_562_944
    # the flat vector is the reference's layout, leaf for leaf
    paths = ["/".join(str(k.key) for k in path) for path, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    layout = ref.system_layout(ref.sizes(published))
    assert paths == [p for p, _ in layout]
    assert [tuple(x.shape) for x in jax.tree_util.tree_leaves(shapes)] == [
        s for _, s in layout]


def test_a_log_takes_dense_noise_and_the_matrices_factored(tiny):
    lm, spec = tiny["lm"], tiny["spec"]
    paths = ["/".join(str(k.key) for k in path) for path, _ in
             jax.tree_util.tree_flatten_with_path(lm.param_shapes())[0]]
    dense = {paths[i] for i, *_ in spec.dense_leaves}
    factored = {paths[i] for i, *_ in spec.lr_leaves}
    assert set(lm.dense_noise_leaves) == {
        "layer_00/mamba/A_log", "layer_02/mamba/A_log"} <= dense
    assert {"layer_00/mamba/in_proj", "layer_00/mamba/x_proj",
            "layer_00/mamba/dt_proj", "layer_00/mamba/out_proj",
            "layer_01/attn/qkv", "layer_01/attn/o", "layer_05/attn/q",
            "layer_04/gmu/gmu_in", "layer_04/gmu/gmu_out",
            "embed/embedding"} <= factored
    assert "layer_00/mamba/conv_kernel" in dense
    # left to the rule, A_log [64, 4] would have been factored
    assert {paths[i] for i, *_ in make_lowrank_tree_spec(
        lm.param_shapes(), 2).lr_leaves} - factored == set(
            lm.dense_noise_leaves)
    # the update's contraction gives A_log the weighted sum of dense rows
    rows = jax.random.normal(jax.random.PRNGKey(1), (3, spec.noise_dim))
    w = jnp.asarray([0.5, -1.0, 2.0])
    got = lowrank_tree_weighted_sum(spec, rows, w)
    want = sum(wi * lowrank_tree_noise(spec, r)["layer_00"]["mamba"]["A_log"]
               for wi, r in zip(w, rows))
    np.testing.assert_allclose(got["layer_00"]["mamba"]["A_log"], want,
                               atol=1e-5)


def test_every_leaf_has_a_partition_rule(tiny):
    assert unmatched_leaves(tiny["lm"].declaration().partition_rules,
                            tiny["lm"].param_shapes()) == {}


# ------------------------------------------- (g) through ES, over meshes

def _sambay_es(devices, model_shards, **over):
    from estorch_tpu import ES, JaxAgent

    kw = dict(
        policy=SambaYLM, agent=JaxAgent, optimizer=optax.adam,
        population_size=8, sigma=0.02, policy_kwargs=sambay_tiny.TINY,
        agent_kwargs={"env": TokenScoreEnv(**sambay_tiny.ENV)},
        optimizer_kwargs={"learning_rate": 1e-2}, shard_params=True,
        model_shards=model_shards, low_rank=1, noise_mode="table",
        table_size=1 << 18, device=list(devices))
    kw.update(over)
    return ES(**kw)


class TestThroughTheShardedEngine:
    @pytest.fixture(scope="class")
    def one_device(self, devices8):
        es = _sambay_es(devices8[:1], 1)
        offsets = np.asarray(es.engine.all_pair_offsets(es.state))
        es.train(2, verbose=False)
        return dict(es=es, fitness=[r["reward_mean"] for r in es.history],
                    params=np.asarray(es.state.params_flat), offsets=offsets)

    @pytest.mark.parametrize("pop, model", [(2, 4), (1, 2)])
    def test_mesh_shapes_match_one_device(self, one_device, devices8, pop,
                                          model, centre_form):
        es = _sambay_es(devices8[:pop * model], model)
        assert es.engine.forward_form == "perturbed"
        assert (es.engine.pop_shards, es.engine.model_shards) == (pop, model)
        # both layouts of the centre; nothing to gather on a model axis of 1
        assert es.engine.centre_form == (
            centre_form if model > 1 else "split")
        report = es.engine.sharding_report()
        assert report["layer_00/mamba/in_proj"] == (
            "PartitionSpec(None, 'model')")
        assert report["layer_00/mamba/A_log"] == (
            "PartitionSpec('model', None)")
        assert report["layer_04/gmu/gmu_out"] == (
            "PartitionSpec('model', None)")
        assert not any("catch-all" in v for v in report.values())
        np.testing.assert_array_equal(
            es.engine.all_pair_offsets(es.state), one_device["offsets"])
        es.train(2, verbose=False)
        np.testing.assert_allclose(
            [r["reward_mean"] for r in es.history], one_device["fitness"],
            rtol=2e-6)
        np.testing.assert_allclose(np.asarray(es.state.params_flat),
                                   one_device["params"], atol=1e-5, rtol=0)

    def test_one_device_run_its_gauges_and_its_manifest(self, one_device):
        es = one_device["es"]
        assert es.engine.forward_form == "perturbed"
        assert (es.engine.kernel_facts["attention_form"],
                es.engine.kernel_facts["head_form"]) == (
                    "xla", "xla")
        assert [r["env_steps"] for r in es.history] == [8 * 21] * 2
        assert -4.6 < es.history[0]["reward_mean"] < -3.9   # about -log 64
        gauges = es.obs.counters
        assert gauges.get("tokens_per_generation") == 8 * 21
        assert gauges.get("layer_kinds") == (
            "mamba,window,mamba_mem,full_kv,gmu,cross")
        assert (gauges.get("window"), gauges.get("scan_chunk"),
                gauges.get("kv_shared_by"),
                gauges.get("memory_shared_by")) == (5, 4, 1, 1)
        assert gauges.get("attention_form") == "xla"
        # a CPU mesh: every kind of attention layer in the XLA form
        by_kind = "window:xla,full_kv:xla,cross:xla"
        assert es.engine.kernel_facts["attention_form_by_kind"] == by_kind
        assert gauges.get("attention_form_by_kind") == by_kind
        assert gauges.get("head_form") == "xla"
        # the scans too: no scope on a CPU mesh, whatever their shapes
        assert (es.engine.kernel_facts["scan_form"]
                == gauges.get("scan_form") == "xla")
        # no expert layer: no combine, no form of it
        assert "combine_form" not in es.engine.kernel_facts
        assert gauges.get("combine_form", None) is None
        assert gauges.get("experts_held", None) is None
        cfg = es.run_manifest()["config"]
        assert cfg["scan_form"] == "xla"
        assert cfg["combine_form"] is None
        assert cfg["layer_kinds"] == gauges.get("layer_kinds")
        assert (cfg["window"], cfg["kv_shared_by"],
                cfg["memory_shared_by"]) == (5, 1, 1)
        assert cfg["attention_form_why"].startswith("the devices are 'cpu'")
        assert cfg["attention_form_by_kind"] == by_kind

    @pytest.mark.parametrize("dtype, tol", [("float32", 1e-4),
                                            ("bfloat16", 2e-2)])
    def test_forced_kernel_runs_the_generation_the_xla_form_runs(
            self, devices8, kernel_attention, dtype, tol):
        """The generation program on one device, the engine's scope open
        around its trace: the two full-causal kinds of attention layer take
        the kernel (two ``pallas_call``s a forward), the windowed kind the
        XLA form, the gauge says which, and the members' fitness is the XLA
        form's to the order of float32 sums (bfloat16: of its rounding of
        the probabilities; fitness is a mean log p of about -4.2)."""
        ref_es = _sambay_es(devices8[:1], 1, compute_dtype=dtype)
        with kernel_attention():
            kern = _sambay_es(devices8[:1], 1, compute_dtype=dtype)
        assert (ref_es.engine.kernel_facts["attention_form"],
                kern.engine.kernel_facts["attention_form"]) == (
                    "xla", "kernel")
        by_kind = "window:xla,full_kv:kernel,cross:kernel"
        assert kern.engine.kernel_facts["attention_form_by_kind"] == by_kind
        assert kern.obs.counters.get("attention_form_by_kind") == by_kind
        assert kern.run_manifest()["config"][
            "attention_form_by_kind"] == by_kind
        programs = [str(jax.make_jaxpr(es.engine._generation_step)(
            es.state, es.table.data)) for es in (ref_es, kern)]
        assert [text.count("jaxpr=causal_attention")
                for text in programs] == [0, 2]
        ref_es.state, want = ref_es.engine.generation_step(ref_es.state)
        kern.state, got = kern.engine.generation_step(kern.state)
        np.testing.assert_allclose(got["fitness"], want["fitness"], atol=tol)
        assert np.isfinite(np.asarray(got["fitness"])).all()

    def test_forced_kernel_scans_both_mamba_layers_in_the_kernel(
            self, devices8, kernel_attention):
        """Shapes the scan's rule takes (``d_inner`` one 128-lane block,
        one time chunk of 256 steps): inside the engine's scope BOTH Mamba
        layers run the scan kernel (interpreted here) beside the
        attention's two, the engine says so where it says ``head_form``,
        and the members' fitness is the XLA form's to float32 rounding."""
        from pallas_costs import pallas_calls

        over = dict(
            policy_kwargs={**sambay_tiny.TINY, "hidden_size": 64},
            agent_kwargs={"env": TokenScoreEnv(
                **{**sambay_tiny.ENV, "seq_len": 256})})
        ref_es = _sambay_es(devices8[:1], 1, **over)
        with kernel_attention():
            kern = _sambay_es(devices8[:1], 1, **over)
        assert (ref_es.engine.kernel_facts["scan_form"],
                kern.engine.kernel_facts["scan_form"]) == (
            "xla", "kernel")
        assert kern.obs.counters.get("scan_form") == "kernel"
        assert kern.run_manifest()["config"]["scan_form"] == "kernel"
        names = [[call.params["name"] for call in pallas_calls(
            es.engine._generation_step, es.state, es.table.data)]
            for es in (ref_es, kern)]
        assert names[0] == []
        assert names[1].count("selective_scan") == 2
        assert names[1].count("causal_attention") == 2
        ref_es.state, want = ref_es.engine.generation_step(ref_es.state)
        kern.state, got = kern.engine.generation_step(kern.state)
        np.testing.assert_allclose(got["fitness"], want["fitness"],
                                   atol=1e-4)
        assert np.isfinite(np.asarray(got["fitness"])).all()

    def test_shapes_the_scans_rule_refuses_keep_the_xla_form_in_the_scope(
            self, devices8, kernel_attention):
        """The tiny model (``d_inner`` 64 over 21 positions) inside the
        forced scope: attention in the kernel, the scans in the
        ``lax.scan``, and the engine says which."""
        with kernel_attention():
            es = _sambay_es(devices8[:1], 1)
        assert (es.engine.kernel_facts["attention_form"],
                es.engine.kernel_facts["scan_form"]) == (
            "kernel", "xla")
        assert es.run_manifest()["config"]["scan_form"] == "xla"

    def test_another_model_states_none_of_it(self, devices8):
        import loop_tiny
        from estorch_tpu.models import LoopedLM

        es = _sambay_es(devices8[:1], 1, policy=LoopedLM,
                        policy_kwargs=loop_tiny.TINY,
                        agent_kwargs={"env": TokenScoreEnv(**loop_tiny.ENV)})
        assert es.obs.counters.get("layer_kinds", None) is None
        assert "kv_shared_by" not in es.run_manifest()["config"]
        assert dict(es.module.declaration().kernels)[
            attention_facts][2] is None     # no kinds stated: one, no band
        assert es.engine.kernel_facts["attention_form_by_kind"] == "causal:xla"
        # no scan stated: no form, no gauge, null in the manifest
        assert "scan_form" not in es.engine.kernel_facts
        assert es.obs.counters.get("scan_form", None) is None
        assert es.run_manifest()["config"]["scan_form"] is None

    def test_the_reference_scores_the_engines_members(self, ref, devices8):
        """Generation 0 of the engine against the reference through the
        keying contract the benchmark's runner relies on: same table, same
        offsets, same keys, both signs of every pair."""
        es = _sambay_es(devices8[:1], 1, sigma=0.05)
        s = ref.sizes(sambay_tiny.config(rank=1))
        theta = np.asarray(es.state.params_flat)
        key = jnp.asarray(np.asarray(es.state.key))
        offsets = np.asarray(es.engine.all_pair_offsets(es.state))
        es.state, metrics = es.engine.generation_step(es.state)
        members = np.arange(8)
        keys = ref.member_keys(key, 0, 4)[members // 2]
        want, want_bc = ref.score_members(
            s, theta, es.table.data, offsets[members // 2],
            np.where(members % 2 == 0, 1.0, -1.0), keys, 0.05, 32)
        np.testing.assert_allclose(metrics["fitness"], want, atol=TOL)
        np.testing.assert_allclose(metrics["bc"], want_bc, atol=TOL)
        assert np.ptp(want) > 1e-4

    def test_the_centre_copy_keeps_the_decay_float32(self, devices8):
        es = _sambay_es(devices8[:1], 1, compute_dtype="bfloat16")
        eng = es.engine
        dtypes = dict(zip(eng.leaf_paths, eng._leaf_dtypes))
        for name in ("A_log", "D", "dt_bias", "conv_kernel", "conv_bias"):
            assert dtypes[f"layer_02/mamba/{name}"] == jnp.float32
        assert dtypes["layer_03/attn/lambda_q1"] == jnp.float32
        assert dtypes["layer_00/mamba/in_proj"] == jnp.bfloat16
        assert dtypes["layer_01/attn/qkv"] == jnp.bfloat16
        assert dtypes["embed/embedding"] == jnp.bfloat16
        es.train(1, verbose=False)
        assert np.isfinite(es.history[0]["reward_mean"])

    def test_program_noise_keeps_a_log_dense_too(self, devices8):
        """The table-free engine draws by the same rule: ``A_log`` is no
        factored leaf there either."""
        es = _sambay_es(devices8[:1], 1, noise_mode="program")
        eng = es.engine
        factored = {eng.leaf_paths[i] for i in eng._factored}
        assert "layer_00/mamba/in_proj" in factored
        assert not {p for p in factored if p.endswith("A_log")}
        es.train(1, verbose=False)
        assert np.isfinite(es.history[0]["reward_mean"])


class TestChunkRule:
    def test_the_widest_activation_is_the_fused_projections(self, devices8):
        """The chunk rule reads the factored leaves' output widths: here
        the fused ``in_proj`` (2 · d_inner), wider than the FFN."""
        es = _sambay_es(devices8[:1], 1)
        assert es.engine._widest_activation() == 21 * 128

    def test_at_the_published_widths_one_member_is_over_the_budget(self):
        """``[8192, 10240]`` float32 is 335 MB a member: over the budget by
        itself, so the published cell evaluates a pair a chunk and the two
        signs of it in turn."""
        from estorch_tpu.parallel.sharded import ACTIVATION_BUDGET_BYTES

        published = sambay_tiny.published()
        lm = SambaYLM(**published["build"]["kwargs"]["policy_kwargs"])
        spec = make_lowrank_tree_spec(lm.param_shapes(), 1,
                                      dense=lm.dense_noise_leaves)
        widest = max(n for _, _, n, _, _ in spec.lr_leaves)
        assert widest == 10240
        per_member = 4 * published["horizon"] * widest
        assert published["horizon"] == 8192
        assert per_member > ACTIVATION_BUDGET_BYTES > per_member // 2

    def test_signs_in_turn_where_one_member_is_over_the_budget(
            self, devices8, monkeypatch):
        """The rule's last arm: a member's widest activation alone over the
        budget makes a chunk one pair whose signs go one after the other;
        the same fitness, behaviour and update as both signs at once."""
        from estorch_tpu.parallel import sharded

        both = _sambay_es(devices8[:1], 1)
        assert not both.engine.signs_in_turn
        assert (both.engine.pair_chunk, both.engine.eval_chunk,
                both.engine.n_eval_chunks) == (4, 8, 1)
        # 21 x 128 floats a member: a budget under one member's
        monkeypatch.setattr(sharded, "ACTIVATION_BUDGET_BYTES", 4 * 21 * 100)
        turn = _sambay_es(devices8[:1], 1)
        assert turn.engine.signs_in_turn
        assert (turn.engine.pair_chunk, turn.engine.eval_chunk,
                turn.engine.n_eval_chunks) == (1, 1, 8)
        # a budget of one member and a half: a pair a chunk, signs at once
        monkeypatch.setattr(sharded, "ACTIVATION_BUDGET_BYTES", 4 * 21 * 190)
        pair = _sambay_es(devices8[:1], 1)
        assert not pair.engine.signs_in_turn
        assert (pair.engine.pair_chunk, pair.engine.eval_chunk) == (1, 2)
        outs = []
        for es in (both, turn, pair):
            es.state, metrics = es.engine.generation_step(es.state)
            outs.append((np.asarray(metrics["fitness"]),
                         np.asarray(metrics["bc"]),
                         np.asarray(es.state.params_flat)))
        for got in outs[1:]:
            for g, w in zip(got, outs[0]):
                np.testing.assert_allclose(g, w, atol=2e-6, rtol=0)
        # a caller's own eval_chunk is taken as given
        own = _sambay_es(devices8[:1], 1, eval_chunk=2)
        assert not own.engine.signs_in_turn
