"""GatedWindowMoELM (models/gated_window_moe_lm.py) against the plain reference
the benchmark judges its cell by (benchmark/reference/gated_window_moe_lm.py):
float32, ``highest``, Python loops over layers and over the held experts, one
full masked softmax per head, YaRN's frequencies from the formula, every
perturbed leaf (and expert) materialised, routes of its own."""

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.flatten_util import ravel_pytree

import gated_window_moe_tiny as tiny_model
from pallas_costs import pallas_calls
from estorch_tpu.models import GatedWindowMoELM, MoELM, WindowMoELM, lm_blocks
from estorch_tpu.models.gated_window_moe_lm import FULL_LAYER, SLIDING_LAYER
from estorch_tpu.ops.lowrank import make_lowrank_tree_spec
from estorch_tpu.ops.pallas_attention import (attention_facts, call_form,
                                              kernel_scope)
from estorch_tpu.ops.pallas_combine import combine_facts
from estorch_tpu.ops.pallas_head import head_facts
from estorch_tpu.parallel.mesh import (DEFAULT_PARTITION_RULES,
                                       hyperscale_mesh, match_partition_rules,
                                       unmatched_leaves)

# the degraded forms the cell's reference check has to refuse
sys.path.insert(0, os.path.join(tiny_model.ROOT, "benchmark", "rehearse"))
import coarse_swg  # noqa: E402

# float32 on both sides; what differs is the ORDER of float32 sums (blocked
# softmax against whole, grouped matmul against a masked loop) on values of
# magnitude 1: measured 1.5e-6
TOL = 1e-5
TINY = tiny_model.TINY
FULL, SLIDING = FULL_LAYER, SLIDING_LAYER
# spreads at which logits, scores, gates and routes all matter at hidden 32
STD = {"embedding": 1.0, "q": 0.3, "k": 0.3, "router": 0.4, "o": 0.1,
       "experts/down": 0.4, "other": 0.18}
# the published numbers of the full layers' rope group
PUBLISHED_YARN = {
    "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
    "original_max_position_embeddings": 4096, "beta_slow": 1,
    "beta_fast": 64, "attention_factor": 1.4158883083359672,
    "partial_rotary_factor": 0.5}


@pytest.fixture(scope="module")
def ref():
    return tiny_model.reference()


def _built(ref, rank=2, **policy):
    cfg = {**tiny_model.config(rank=rank, policy=policy), "seeded_std": STD}
    lm = GatedWindowMoELM(**{**TINY, **policy})
    theta = jnp.asarray(ref.init_theta(jax.random.PRNGKey(3), cfg))
    shapes = lm.param_shapes()
    _, unravel = ravel_pytree(jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, x.dtype), shapes))
    spec = make_lowrank_tree_spec(shapes, rank, stacked=lm.stacked_leaves)
    noise = jax.random.normal(jax.random.PRNGKey(5), (spec.noise_dim,))
    return dict(cfg=cfg, s=ref.sizes(cfg), lm=lm, theta=theta,
                unravel=unravel, params=unravel(theta), spec=spec,
                noise=noise)


@pytest.fixture(scope="module")
def tiny(ref):
    return _built(ref)


def _tokens(length, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (length,), 0, 64)


class Tapped(GatedWindowMoELM):
    """The honest model, which also hands out every layer's output (read
    after an un-jitted call)."""

    def _layer(self, *a):
        x, load = GatedWindowMoELM._layer(self, *a)
        TAPS.append(x)
        return x, load


TAPS = []


def _tapped(lm, *args):
    del TAPS[:]
    out = Tapped(**dataclasses.asdict(lm)).perturbed_apply(*args)
    return out, list(TAPS)


# -------------------------------------------- (a) against the reference

@pytest.mark.parametrize("length", [21, 16, 5])
@pytest.mark.parametrize("sign", [0.0, 1.0, -1.0])
def test_the_forward_matches_the_reference(ref, tiny, sign, length):
    """Scores, the behaviour vector, EVERY layer's output and the pairs
    that landed on the held experts: the centre (sign 0) and both members of
    a pair from ONE factor read; the dense layer, both kinds of attention
    layer (4 and 6 heads), the band of 6 biting from the seventh position."""
    tokens, c = _tokens(length, length), 0.05 * sign
    noise = None if sign == 0.0 else tiny["spec"].unpack(tiny["noise"])
    member = ref.Member(tiny["s"], tiny["theta"],
                        None if sign == 0.0 else tiny["noise"], c)
    want = ref.forward(tiny["s"], member, tokens, head_block=8,
                       with_choices=True, with_layers=True)
    got, layers = _tapped(tiny["lm"], tiny["params"], noise, c, tokens)
    for g, w, shape in zip(got[:2], want[:2], [(length - 1,), (64,)]):
        assert g.shape == w.shape == shape and g.dtype == jnp.float32
        np.testing.assert_allclose(g, w, atol=TOL, rtol=0)
    assert len(layers) == len(want[3]) == 4
    for g, w in zip(layers, want[3]):
        np.testing.assert_allclose(g, w, atol=TOL, rtol=0)
    # three sparse layers route; the dense one none
    assert len(want[2]) == 3
    chosen = np.concatenate([np.asarray(r).reshape(-1) for r in want[2]])
    np.testing.assert_array_equal(
        got[2], [(chosen == 4 + k).sum() for k in range(4)])
    assert 0 < int(got[2].sum()) < chosen.size      # some held, not all
    assert float(jnp.abs(want[1]).max()) > 0.5      # the logits spread
    if sign:
        centre = ref.forward(tiny["s"], ref.Member(
            tiny["s"], tiny["theta"], None, 0.0), tokens, head_block=8)
        assert float(jnp.abs(want[0] - centre[0]).max()) > 0.05


@pytest.mark.parametrize("kinds, mlps, heads", [
    ((FULL,), ("dense",), (4,)), ((SLIDING,), ("sparse",), (6,)),
    ((FULL,), ("sparse",), (2,)), ((SLIDING,), ("dense",), (8,)),
    ((SLIDING, FULL), ("sparse", "dense"), (8, 2)),
    ((FULL, SLIDING, SLIDING, SLIDING, FULL),
     ("dense", "sparse", "sparse", "sparse", "sparse"), (4, 6, 6, 6, 4))],
    ids=["full-dense", "sliding-sparse", "full-sparse", "sliding-dense",
         "the-other-order", "the-cell's-five"])
@pytest.mark.parametrize("window", [1, 6, 8, 64])
def test_each_kind_alone_and_the_cells_five_layers(ref, kinds, mlps, heads,
                                                   window):
    """A stack of one layer of either kind with either FFN, the other
    order, and the cell's five (the dense layer, then one period), under
    bands of one key, under the attention's block (8), at it and over the
    sequence: the reference's scores, behaviour and every layer's output."""
    built = _built(ref, layer_types=kinds, mlp_layer_types=mlps,
                   num_attention_heads_per_layer=heads,
                   sliding_window=window)
    tokens = _tokens(21, 4)
    member = ref.Member(built["s"], built["theta"], built["noise"], 0.05)
    want = ref.forward(built["s"], member, tokens, head_block=8,
                       with_layers=True)
    got, layers = _tapped(built["lm"], built["params"],
                          built["spec"].unpack(built["noise"]), 0.05, tokens)
    for g, w in zip(list(got[:2]) + layers, list(want[:2]) + want[2]):
        np.testing.assert_allclose(g, w, atol=TOL, rtol=0)


# bfloat16: the embedding and every matrix rounded to 8 bits of mantissa
# under logits of spread 1.3; measured 0.012 (scores) and 0.009 (behaviour)
BF16_MEAN_LIMIT = 0.05


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, TOL),
                                        (jnp.bfloat16, BF16_MEAN_LIMIT)])
@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_both_forms_and_both_dtypes_match_the_reference(ref, tiny, form,
                                                        dtype, tol,
                                                        tiny_widths):
    """A perturbed member in the XLA form and inside a kernel scope under
    the interpreter, in float32 and in bfloat16 (the copy the engine's
    forward reads: routers float32): the reference's scores and behaviour
    to the dtype's rounding.  32 positions in blocks of 8; inside the scope
    the TWO full layers take the kernel (4 heads over 2) and the two sliding
    layers stay in the XLA form (a band of 6 keys spans no block of the
    kernel's: ``pallas_attention.call_form``)."""
    lm, tokens, c = tiny["lm"], _tokens(32, 7), 0.05
    keep = set(lm.float32_leaves)
    paths = ["/".join(str(k.key) for k in p) for p, _ in
             jax.tree_util.tree_flatten_with_path(tiny["params"])[0]]
    leaves, treedef = jax.tree_util.tree_flatten(tiny["params"])
    params = jax.tree_util.tree_unflatten(treedef, [
        x if path in keep else x.astype(dtype)
        for x, path in zip(leaves, paths)])
    want = ref.forward(tiny["s"], ref.Member(
        tiny["s"], tiny["theta"], tiny["noise"], c), tokens, head_block=8)

    def forward(p, f):
        return lm.perturbed_apply(p, f, c, tokens)

    factors = tiny["spec"].unpack(tiny["noise"])
    if form == "kernel":
        with kernel_scope(interpret=True):
            calls = pallas_calls(forward, params, factors)
            got = forward(params, factors)
        assert len(calls) == 2
    else:
        got = forward(params, factors)
    for g, w in zip(got[:2], want):
        assert g.dtype == jnp.float32 and bool(jnp.isfinite(g).all())
        if dtype == jnp.float32:
            np.testing.assert_allclose(g, w, atol=tol, rtol=0)
        else:
            assert float(jnp.mean(jnp.abs(g - w))) < tol
            assert float(jnp.std(w)) > 0.3


@pytest.mark.parametrize("kind, heads, window, length, kernels", [
    (FULL, 4, 100, 384, 1), (SLIDING, 6, 200, 384, 1),
    (SLIDING, 6, 128, 384, 1), (SLIDING, 6, 100, 384, 0),
    (SLIDING, 6, 128, 512, 1), (SLIDING, 4, 256, 512, 1),
    (SLIDING, 6, 192, 512, 0)])
def test_the_two_forms_agree_a_kind(ref, kind, heads, window, length,
                                    kernels, tiny_widths):
    """ONE layer of a kind over 384 positions (three of the kernel's blocks
    of 128), in the XLA form and inside a scope under the interpreter: a
    full layer takes the kernel whatever the band beside it, a sliding one
    where its band spans a block (200 keys, 128) and not under 100; over
    512 positions (ONE block) a band of 128 or 256 keys is the block itself,
    both key blocks in one grid step, and one of 192 stays in the XLA form;
    either way the two forms' scores and behaviour agree and are the
    float32 reference's."""
    built = _built(ref, layer_types=(kind,), mlp_layer_types=("sparse",),
                   num_attention_heads_per_layer=(heads,),
                   sliding_window=window, attention_block=64)
    lm, tokens, c = built["lm"], _tokens(length, 11), 0.05
    want = ref.forward(built["s"], ref.Member(
        built["s"], built["theta"], built["noise"], c), tokens, head_block=8)
    factors = built["spec"].unpack(built["noise"])

    def forward(p, f):
        return lm.perturbed_apply(p, f, c, tokens)

    xla = forward(built["params"], factors)
    with kernel_scope(interpret=True):
        calls = pallas_calls(forward, built["params"], factors)
        got = forward(built["params"], factors)
    assert len(calls) == kernels
    assert call_form("kernel", lm._band(kind), length) == (
        "kernel" if kernels else "xla")
    # the band as the block: no key axis on the grid
    assert [len(call.params["grid_mapping"].grid) for call in calls] == (
        [2 if kind == SLIDING and length == 512 else 3] * kernels)
    for g, x, w in zip(got[:2], xla[:2], want):
        np.testing.assert_allclose(g, x, atol=TOL, rtol=0)
        np.testing.assert_allclose(g, w, atol=TOL, rtol=0)


def test_apply_is_the_centre_alone(tiny):
    tokens = _tokens(21)
    got = tiny["lm"].apply({"params": tiny["params"]}, tokens)
    want = tiny["lm"].perturbed_apply(tiny["params"], None, 0.0, tokens)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_members_under_vmap_are_their_own_evaluations(tiny):
    """The engine's nesting (pairs, then signs) around the model: every
    member's output, its held experts' load among them, equals its own
    evaluation."""
    lm, spec, tokens = tiny["lm"], tiny["spec"], _tokens(21, 9)
    rows = jax.random.normal(jax.random.PRNGKey(7), (3, spec.noise_dim))
    signs = jnp.asarray([0.05, -0.05])

    def member(row, c):
        return lm.perturbed_apply(tiny["params"], spec.unpack(row), c, tokens)

    got = jax.vmap(lambda row: jax.vmap(lambda c: member(row, c))(signs))(
        rows)
    assert got[2].shape == (3, 2, 4)
    for i in range(3):
        for j in range(2):
            want = member(rows[i], signs[j])
            for g, w in zip(got[:2], want[:2]):
                np.testing.assert_allclose(g[i, j], w, atol=1e-5, rtol=0)
            np.testing.assert_array_equal(got[2][i, j], want[2])


# ------------------------------------- (b) each wrong forward is refused

WRONG = {"fp8_inputs": coarse_swg.Fp8Swg, "all_bf16": coarse_swg.AllBf16Swg,
         "no_gate": coarse_swg.NoGateSwg,
         "whole_head_rotation": coarse_swg.WholeHeadRotationSwg,
         "plain_rope": coarse_swg.PlainRopeSwg,
         "no_band": coarse_swg.NoBandSwg,
         "wider_band": coarse_swg.WiderBandSwg,
         "full_grouping": coarse_swg.FullGroupingSwg,
         "softmax_router": coarse_swg.SoftmaxRouterSwg,
         "unscaled_router": coarse_swg.UnscaledRouterSwg,
         "no_shared": coarse_swg.NoSharedSwg,
         "other_rank": coarse_swg.OtherRankSwg}


@pytest.mark.parametrize("name", list(WRONG))
def test_each_wrong_forward_fails_the_comparison(ref, tiny, name):
    """In float32, where the honest forward is the reference's to 2e-6: a
    forward in fp8 or with the float32 parts in bfloat16, the gate left out,
    the full layers rotated over the whole head or under plain rope, the
    band dropped or widened by a block, the full layers' grouping in a
    sliding layer, a softmax router, the 2.5 left out, the shared expert
    left out and another share's experts each move the scores AND the
    behaviour vector by three hundred times the tolerance and more."""
    wrong = WRONG[name](**TINY)
    assert (dataclasses.asdict(wrong) == dataclasses.asdict(tiny["lm"])
            and wrong.declaration() == tiny["lm"].declaration())
    tokens, c = _tokens(21, 3), 0.05
    want = ref.forward(tiny["s"], ref.Member(
        tiny["s"], tiny["theta"], tiny["noise"], c), tokens, head_block=8)
    factors = tiny["spec"].unpack(tiny["noise"])
    honest = tiny["lm"].perturbed_apply(tiny["params"], factors, c, tokens)
    got = wrong.perturbed_apply(tiny["params"], factors, c, tokens)
    for h, g, w in zip(honest[:2], got[:2], want):
        np.testing.assert_allclose(h, w, atol=TOL, rtol=0)
        assert float(jnp.abs(g - w).max()) > 300 * TOL, name


# ------------------------------------------------- (c) the rope scaling

def test_yarn_at_the_published_numbers_is_the_closed_form():
    """``lm_blocks.yarn_inv_freq`` over the 64 that turn in a full layer,
    with the published group: the ramp runs from pair 5 to pair 16
    (5.66 -> 5, 15.80 -> 16), the pairs below keep ``500000^(-2i/64)``, the
    pairs above are 64 times slower, those between blended; and the factor
    of cos and sin is ``0.1 ln 64 + 1``."""
    inv_freq, factor = lm_blocks.yarn_inv_freq(64, 500000, PUBLISHED_YARN)
    d, ln_theta = 64, math.log(500000)
    low = d * math.log(4096 / (64 * 2 * math.pi)) / (2 * ln_theta)
    high = d * math.log(4096 / (1 * 2 * math.pi)) / (2 * ln_theta)
    assert (round(low, 2), round(high, 2)) == (5.66, 15.80)
    assert (math.floor(low), math.ceil(high)) == (5, 16)
    assert inv_freq.shape == (32,) and inv_freq.dtype == jnp.float32
    f = [500000 ** (-2 * i / 64) for i in range(32)]
    # written out: an untouched pair, a blended one, a slowed one
    np.testing.assert_allclose(inv_freq[3], 0.2922278, rtol=1e-6)
    np.testing.assert_allclose(inv_freq[3], f[3], rtol=1e-6)
    r = (10 - 5) / (16 - 5)
    np.testing.assert_allclose(inv_freq[10], f[10] / 64 * r + f[10] * (1 - r),
                               rtol=1e-6)
    np.testing.assert_allclose(inv_freq[10], 0.009150584, rtol=1e-6)
    np.testing.assert_allclose(inv_freq[20], f[20] / 64, rtol=1e-6)
    np.testing.assert_allclose(inv_freq[20], 4.285128e-06, rtol=1e-6)
    for i in range(32):
        ramp = min(max((i - 5) / 11, 0.0), 1.0)
        np.testing.assert_allclose(
            inv_freq[i], f[i] / 64 * ramp + f[i] * (1 - ramp), rtol=2e-6)
    assert factor == 1.4158883083359672
    np.testing.assert_allclose(factor, 0.1 * math.log(64) + 1, rtol=1e-15)
    # a group without the factor gets the formula's
    bare = {k: v for k, v in PUBLISHED_YARN.items()
            if k != "attention_factor"}
    np.testing.assert_allclose(
        lm_blocks.yarn_inv_freq(64, 500000, bare)[1],
        0.1 * math.log(64) + 1, rtol=1e-15)


def test_the_reference_computes_the_same_frequencies_by_itself(ref):
    got, factor = ref.inv_freq(PUBLISHED_YARN, 64)
    want, want_factor = lm_blocks.yarn_inv_freq(64, 500000, PUBLISHED_YARN)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert factor == want_factor
    plain, one = ref.inv_freq({"rope_theta": 10000.0,
                               "rope_type": "default"}, 128)
    np.testing.assert_allclose(
        plain, 10000.0 ** (-np.arange(64) / 64.0), rtol=1e-12)
    assert one == 1.0


@pytest.mark.parametrize("scaling", [None, {"rope_type": "default"},
                                     {"rope_type": "default",
                                      "rope_theta": 10000.0,
                                      "partial_rotary_factor": 1}])
def test_no_scaling_is_the_old_tables_bit_for_bit(scaling):
    """``rope_scaling`` null (and ``rope_type`` default) is the path every
    other model takes: the tables of the older formula to the last bit."""
    cos, sin = lm_blocks.rotary_tables(40, 16, 10000.0, scaling=scaling)
    inv_freq = 1.0 / (10000.0 ** (
        jnp.arange(0, 16, 2, dtype=jnp.float32) / 16))
    angle = jnp.arange(40, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    np.testing.assert_array_equal(cos, jnp.cos(angle))
    np.testing.assert_array_equal(sin, jnp.sin(angle))
    np.testing.assert_array_equal(
        cos, lm_blocks.rotary_tables(40, 16, 10000.0)[0])


def test_yarn_tables_carry_the_blend_and_the_factor():
    cos, sin = lm_blocks.rotary_tables(300, 64, 500000,
                                       scaling=PUBLISHED_YARN)
    plain_cos, _ = lm_blocks.rotary_tables(300, 64, 500000)
    assert cos.shape == sin.shape == (300, 32)
    factor = PUBLISHED_YARN["attention_factor"]
    np.testing.assert_allclose(cos[0], factor, rtol=1e-6)   # angle 0
    np.testing.assert_allclose(cos * cos + sin * sin, factor ** 2, rtol=1e-5)
    # an untouched pair turns as under plain rope, a slowed one 64 times less
    np.testing.assert_allclose(cos[:, 2], factor * plain_cos[:, 2],
                               atol=1e-4)
    angle = np.arctan2(np.asarray(sin[:, 20]), np.asarray(cos[:, 20]))
    np.testing.assert_allclose(
        angle, np.arange(300) * 500000 ** (-40 / 64) / 64, rtol=1e-3,
        atol=1e-9)
    with pytest.raises(ValueError, match="not written"):
        lm_blocks.rotary_tables(8, 16, 1e4, scaling={"rope_type": "llama3"})


def test_the_kinds_turn_by_their_own_tables(tiny):
    """A full layer turns the first HALF of a head by YaRN's tables and
    passes the rest; a sliding layer turns the whole head by plain rope."""
    lm = tiny["lm"]
    assert (lm.rotary_dim(FULL), lm.rotary_dim(SLIDING)) == (4, 8)
    full, sliding = lm._tables(FULL, 21), lm._tables(SLIDING, 21)
    assert full[0].shape == (21, 2) and sliding[0].shape == (21, 4)
    np.testing.assert_allclose(full[0][0], 1.2079441541679836, rtol=1e-6)
    np.testing.assert_array_equal(sliding[0][0], 1.0)
    x = jax.random.normal(jax.random.PRNGKey(0), (21, 3, 8))
    y = lm_blocks.rotate(x, *full, rotary_dim=4)
    np.testing.assert_array_equal(y[..., 4:], x[..., 4:])
    assert float(jnp.abs(y[1:, :, :4] - x[1:, :, :4]).max()) > 0.1
    # tiny numbers: low 0, high 1, so pair 0 keeps theta^0 = 1 and pair 1
    # is 8 times slower
    freq, _ = lm_blocks.yarn_inv_freq(4, 500000.0, tiny_model.ROPE[FULL])
    np.testing.assert_allclose(freq, [1.0, 500000.0 ** -0.5 / 8], rtol=1e-6)


# ------------------------------------------------ (d) gate, band, router

def test_the_gate_is_one_number_a_head(ref, tiny):
    """``W_g`` is ``[hidden, heads of this layer]``; every element of a
    head's context is scaled by that head's sigmoid, so a layer whose gate
    columns are all far negative writes nothing to the residual."""
    lm, p = tiny["lm"], tiny["params"]
    assert p["layer_00"]["attn"]["head_gate"].shape == (32, 4)
    assert p["layer_01"]["attn"]["head_gate"].shape == (32, 6)
    u = jax.random.normal(jax.random.PRNGKey(0), (21, 32))
    attn = p["layer_01"]["attn"]
    rotary = lm._tables(SLIDING, 21)
    shut = {**attn, "head_gate": jnp.zeros((32, 6))}
    open_ = lm._attention(shut, None, 0.0, u, SLIDING, 6, rotary)
    half = coarse_swg.NoGateSwg(**TINY)._attention(
        shut, None, 0.0, u, SLIDING, 6, rotary)
    np.testing.assert_allclose(open_, 0.5 * half, atol=1e-6)   # sigmoid(0)
    # one head's column moved: only that head's 8 context columns change
    one = {**attn, "head_gate": attn["head_gate"].at[:, 2].add(0.5)}
    o_eye = {"o": jnp.eye(48, 32)}      # the first 32 context columns out
    a = lm._attention({**attn, **o_eye}, None, 0.0, u, SLIDING, 6, rotary)
    b = lm._attention({**one, **o_eye}, None, 0.0, u, SLIDING, 6, rotary)
    moved = np.abs(np.asarray(a - b)).max(axis=0) > 0
    np.testing.assert_array_equal(moved, np.arange(32) // 8 == 2)


@pytest.mark.parametrize("window", [1, 3, 6, 21, 40])
def test_the_window_counts_the_querys_own_position(ref, window):
    """Query t of a sliding layer sees exactly the ``min(t + 1, window)``
    keys up to and including its own: a change to token t - window moves
    nothing at position t, one to token t - window + 1 does."""
    built = _built(ref, layer_types=(SLIDING,), mlp_layer_types=("sparse",),
                   num_attention_heads_per_layer=(6,), sliding_window=window)
    lm, tokens = built["lm"], _tokens(21, 5)

    def hidden(toks):
        return _tapped(lm, built["params"], None, 0.0, toks)[1][0]

    base, t = hidden(tokens), 20
    for back, moves in ((window, False), (window - 1, True)):
        if t - back < 0:
            continue
        other = tokens.at[t - back].set((tokens[t - back] + 1) % 64)
        changed = float(jnp.abs(hidden(other)[t] - base[t]).max()) > 0
        assert changed == moves, (window, back)


def test_the_router_scores_by_sigmoid_and_builds_no_bias(ref, tiny):
    lm = tiny["lm"]
    assert set(lm.float32_leaves) == {
        f"layer_{i:02d}/moe/router" for i in (1, 2, 3)}
    moe = tiny["params"]["layer_01"]["moe"]
    assert set(moe) == {"router", "shared", "experts"}      # no router_bias
    b = jax.random.normal(jax.random.PRNGKey(0), (21, 32))
    experts, w = lm_blocks.route(moe, None, 0.0, b, top_k=3, scaling=2.5)
    with jax.default_matmul_precision("highest"):
        score = jax.nn.sigmoid(b @ moe["router"])
    np.testing.assert_array_equal(
        np.sort(experts, -1),
        np.sort(jnp.argsort(-score, axis=-1, stable=True)[:, :3], -1))
    picked = jnp.take_along_axis(score, experts, axis=-1)
    np.testing.assert_allclose(
        w, 2.5 * picked / picked.sum(axis=-1, keepdims=True), rtol=2e-6)
    np.testing.assert_allclose(w.sum(axis=-1), 2.5, rtol=1e-6)
    want_experts, want_w = ref.routes(tiny["s"], {"moe/router": moe["router"]},
                                      b)
    np.testing.assert_array_equal(experts, want_experts)
    np.testing.assert_allclose(w, want_w, rtol=2e-6)
    # the float32 router decides: in bfloat16 the weights move
    half = {**moe, "router": moe["router"].astype(jnp.bfloat16)}
    _, w16 = lm_blocks.route(half, None, 0.0, b, top_k=3, scaling=2.5)
    assert float(jnp.abs(w - w16).max()) > 1e-4
    # a model WITH a selection bias is routed as it was
    biased = {**moe, "router_bias": jnp.linspace(-1.0, 1.0, 16)}
    other, _ = lm_blocks.route(biased, None, 0.0, b, top_k=3, scaling=2.5)
    assert not np.array_equal(np.sort(other, -1), np.sort(experts, -1))


# ------------------------------------------- (e) the shares add up

def test_the_four_shares_add_up_to_the_uncut_layer(ref):
    """model-configs section 4: 16 tiny experts over 4 shares (the cell's 16
    shares of 16 in small): the shares' routed parts plus the shared expert
    counted ONCE equal the uncut reference's layer (and the uncut
    system's)."""
    cfgs = [_built(ref, num_experts=4, expert_group_size=4,
                   expert_group_rank=r) for r in range(4)]
    whole = _built(ref, num_experts=16, expert_group_size=1,
                   expert_group_rank=0)
    s, base = whole["s"], "layer_01"
    b = jax.random.normal(jax.random.PRNGKey(3), (21, 32))
    member = ref.Member(s, whole["theta"], None, 0.0)
    p_ref = member.layer(base, "sparse")
    chosen, w = ref.routes(s, p_ref, b)
    shared = ref.swiglu(b, p_ref["moe/shared/gate"], p_ref["moe/shared/up"],
                        p_ref["moe/shared/down"])
    want = shared + ref.held_experts(s, member.experts_of(base, "sparse"), b,
                                     chosen, w)
    moe = whole["params"][base]["moe"]

    def share(model, rank):
        cut = {**moe, "experts": {
            n: moe["experts"][n][4 * rank:4 * rank + 4]
            for n in ("gate", "up", "down")}}
        return model._routed(cut, None, 0.0, b, jnp.float32)

    parts = [share(c["lm"], r) for r, c in enumerate(cfgs)]
    ours = whole["lm"]._shared(moe, None, 0.0, b) + sum(y for y, _ in parts)
    np.testing.assert_allclose(ours, want, atol=TOL, rtol=0)
    uncut, load = whole["lm"]._routed(moe, None, 0.0, b, jnp.float32)
    np.testing.assert_allclose(
        whole["lm"]._shared(moe, None, 0.0, b) + uncut, want, atol=TOL,
        rtol=0)
    np.testing.assert_array_equal(
        np.concatenate([l for _, l in parts]), load)
    assert int(load.sum()) == 21 * 3                # every pair lands once
    # a share alone is NOT the layer, and the shared expert counted a share
    # (four times) is not either
    assert float(jnp.abs(parts[0][0] + shared - want).max()) > 0.01
    assert float(jnp.abs(sum(y for y, _ in parts) + 4 * shared
                         - want).max()) > 0.01
    assert [c["lm"].first_expert_held for c in cfgs] == [0, 4, 8, 12]
    assert all(c["lm"].experts_total == 16 for c in cfgs)


# ------------------------- (f) the other models' programs are what they were

@pytest.mark.parametrize("name", ["moe", "window"])
def test_the_models_that_refuse_a_scaling_still_refuse_it(name):
    import moe_tiny
    import window_moe_tiny

    if name == "window":
        with pytest.raises(ValueError, match="not written"):
            WindowMoELM(**{**window_moe_tiny.TINY,
                           "rope_scaling": PUBLISHED_YARN})
    else:
        assert "rope_scaling" not in {
            f.name for f in dataclasses.fields(MoELM)}
        MoELM(**moe_tiny.TINY)


# ------------------------------------ (g) every leaf's correction, alone

def _leaf_cases():
    lm = GatedWindowMoELM(**TINY)
    cases = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            lm.param_shapes())[0]:
        name = "/".join(str(k.key) for k in path)
        if "/experts/" in name:
            cases += [(name, k) for k in (0, 3)]
        elif not name.startswith(("layer_02", "layer_03")) or (
                "head_gate" in name):
            cases.append((name, None))
    return cases


CASES = _leaf_cases()


@pytest.fixture(scope="module")
def one_leaf_programs(tiny):
    lm, spec = tiny["lm"], tiny["spec"]
    perturbed = jax.jit(
        lambda p, n, c, t: lm.perturbed_apply(p, spec.unpack(n), c, t))
    plain = jax.jit(lambda p, t: lm.perturbed_apply(p, None, 0.0, t))
    return perturbed, plain


@pytest.mark.parametrize("path, expert", CASES)
def test_a_leafs_correction_is_the_materialised_sum(ref, tiny,
                                                    one_leaf_programs, path,
                                                    expert):
    """Noise on ONE leaf (one EXPERT of a stacked leaf): the perturbed
    forward equals the plain forward of the materialised ``theta + c·E``,
    the routes it takes included."""
    perturbed, plain = one_leaf_programs
    s, spec, c = tiny["s"], tiny["spec"], 0.3
    entry = ref.noise_layout(s)[path]
    shape = ref.param_offsets(s)[path][1]
    noise = np.zeros((spec.noise_dim,), np.float32)
    full = np.asarray(tiny["noise"])
    if entry[0] == "stacked":
        e, m, n = shape
        for off, width in ((entry[1], m * 2), (entry[2], n * 2)):
            at = off + expert * width
            noise[at:at + width] = full[at:at + width]
    else:
        n = sum(shape) * 2 if entry[0] == "lr" else math.prod(shape)
        noise[entry[1]:entry[1] + n] = full[entry[1]:entry[1] + n]
    noise, tokens = jnp.asarray(noise), _tokens(21, 2)
    member = ref.Member(s, tiny["theta"], noise, c)
    flat = jnp.concatenate([
        (jnp.stack([member.expert(p, k) for k in range(shp[0])])
         if "/experts/" in p else member.leaf(p)).reshape(-1)
        for p, shp in ref.system_layout(s)])
    got = perturbed(tiny["params"], noise, jnp.float32(c), tokens)
    want = plain(tiny["unravel"](flat), tokens)
    centre = plain(tiny["params"], tokens)
    for g, w in zip(got[:2], want[:2]):
        np.testing.assert_allclose(g, w, atol=5 * TOL, rtol=0)
    np.testing.assert_array_equal(got[2], want[2])
    moved = max(float(jnp.abs(w - x).max())
                for w, x in zip(want[:2], centre[:2]))
    if expert is not None:
        # an expert no token of this sequence chose moves nothing
        layer = int(path.split("/")[0][-2:])
        chosen = ref.forward(s, ref.Member(s, tiny["theta"], None, 0.0),
                             tokens, head_block=8,
                             with_choices=True)[2][layer - 1]
        if not bool((chosen == 4 + expert).any()):
            assert moved == 0.0
            return
    assert moved > 1e-4, (path, expert, moved)


# -------------------------------------------- (h) sizes, init, validation

@pytest.mark.parametrize("bad, match", [
    ({"gating": False}, "gating = False is not written"),
    ({"attention_bias": True}, "not written"),
    ({"moe_apply_router_weight_on_input": True}, "not written"),
    ({"tie_word_embeddings": True}, "not written"),
    ({"layer_types": (FULL, "window")}, "a layer is"),
    ({"layer_types": ()}, "a layer is"),
    ({"mlp_layer_types": ("dense", "sparse")}, "name 2 and 5 layers"),
    ({"num_attention_heads_per_layer": (4, 6, 6)}, "name 5 and 3 layers"),
    ({"mlp_layer_types": ("dense", "moe", "sparse", "sparse")},
     "a layer's FFN is"),
    # a sliding layer with the full layers' head count beside a 6-head one
    ({"num_attention_heads_per_layer": (4, 6, 4, 4)}, "one count a kind"),
    ({"num_attention_heads_per_layer": (4, 5, 5, 4)}, "one count a kind"),
    ({"num_attention_heads_per_layer": (4, 0, 0, 4)}, "one count a kind"),
    ({"rope_parameters": {FULL: tiny_model.ROPE[FULL]}}, "no group"),
    ({"rope_parameters": {**tiny_model.ROPE, SLIDING: {
        "rope_theta": 1e4, "partial_rotary_factor": 0.4}}}, "turns pairs"),
    ({"sliding_window": 0}, "sliding_window"),
    ({"expert_group_rank": 4}, "shares"),
    ({"num_experts_per_tok": 17}, "more experts"),
    ({"behaviour_positions": 0}, "behaviour_positions"),
])
def test_sizes_are_validated(bad, match):
    with pytest.raises(ValueError, match=match):
        GatedWindowMoELM(**{**TINY, **bad})


def test_a_layers_leaves_are_as_wide_as_its_own_heads(tiny):
    """``W_q``, ``W_o`` and ``W_g`` of a 6-head layer are 6 heads wide and
    those of a 4-head layer 4: a sliding layer handed the full layers'
    leaves does not run."""
    lm, p = tiny["lm"], tiny["params"]
    shapes = lm.param_shapes()
    for name, heads in zip(("layer_00", "layer_01", "layer_02", "layer_03"),
                           (4, 6, 6, 4)):
        attn = shapes[name]["attn"]
        assert attn["q"].shape == (32, heads * 8)
        assert attn["o"].shape == (heads * 8, 32)
        assert attn["head_gate"].shape == (32, heads)
        assert attn["k"].shape == attn["v"].shape == (32, 16)
    u = jax.random.normal(jax.random.PRNGKey(0), (21, 32))
    with pytest.raises((TypeError, ValueError)):
        lm._attention(p["layer_00"]["attn"], None, 0.0, u, SLIDING, 6,
                      lm._tables(SLIDING, 21))


def test_the_published_lists_are_read_by_their_first_entries(tiny):
    """The configuration file hands the three per-layer lists whole (40
    entries) and a stack of five reads five; what the class holds is what
    it was given, so the file describes what was built."""
    lm = tiny["lm"]
    assert lm.heads == (4, 6, 6, 4) and len(
        lm.num_attention_heads_per_layer) == 5
    assert lm.mlp_kinds == ("dense", "sparse", "sparse", "sparse")
    as_lists = GatedWindowMoELM(**{
        **TINY, "mlp_layer_types": list(TINY["mlp_layer_types"]),
        "num_attention_heads_per_layer": list(
            TINY["num_attention_heads_per_layer"])})
    built = dataclasses.asdict(as_lists)
    assert built["num_attention_heads_per_layer"] == [4, 6, 6, 4, 6]
    assert built["mlp_layer_types"] == list(TINY["mlp_layer_types"])
    assert built["rope_parameters"] == tiny_model.ROPE
    # hashable all the same (jit keys a bound method by its owner)
    assert hash(as_lists) == hash(GatedWindowMoELM(**{
        **TINY, "mlp_layer_types": list(TINY["mlp_layer_types"]),
        "num_attention_heads_per_layer": list(
            TINY["num_attention_heads_per_layer"])}))
    assert isinstance(hash(coarse_swg.NoGateSwg(**TINY)), int)
    assert lm.heads_of(FULL) == 4 and lm.heads_of(SLIDING) == 6


def test_init_draws_the_declared_tree(tiny):
    lm = tiny["lm"]
    params = lm.init(jax.random.PRNGKey(0), None)["params"]
    shapes = lm.param_shapes()
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(shapes))
    for got, want in zip(jax.tree_util.tree_leaves(params),
                         jax.tree_util.tree_leaves(shapes)):
        assert got.shape == want.shape and got.dtype == jnp.float32
    assert set(params["layer_00"]) == {"norm1", "norm2", "attn", "mlp"}
    assert set(params["layer_01"]) == {"norm1", "norm2", "attn", "moe"}
    layer = params["layer_01"]
    assert np.all(np.asarray(layer["norm1"]["scale"]) == 1.0)
    assert 0.01 < float(layer["moe"]["experts"]["gate"].std()) < 0.03
    assert layer["moe"]["router"].shape == (32, 16)
    assert set(layer["attn"]) == {"q", "k", "v", "o", "head_gate"}
    assert params["layer_00"]["mlp"]["gate"].shape == (32, 48)


def test_the_declaration(tiny):
    stated = tiny["lm"].declaration()
    # heads of 8 over 2 key heads; each kind of attention layer with its
    # band, in layer order; the head and the combine at the hidden width
    kernels = dict(stated.kernels)
    assert kernels[attention_facts] == (
        8, 2, (("sliding", 6), ("full", None)), (6, 4))
    assert (kernels[head_facts], kernels[combine_facts]) == ((32,), (32,))
    assert stated.leaf_rows == {"head/kernel": 8}
    assert stated.leaf_rows_per_token == dict.fromkeys(
        tiny["lm"].stacked_leaves, 3 * 1.25 / 4)
    assert len(stated.stacked_leaves) == 9 and stated.outputs == (
        "expert_load",)
    assert stated.facts == {
        "experts_held": 4, "experts_total": 16, "experts_per_token": 3,
        "mtp_depth": 0, "sliding_window": 6, "dense_layers": 1,
        "sliding_layers": 2, "full_layers": 2, "sliding_heads": 6,
        "full_heads": 4}
    # a stack of one kind states that kind alone
    one = GatedWindowMoELM(**{
        **TINY, "layer_types": (SLIDING,), "mlp_layer_types": ("sparse",),
        "num_attention_heads_per_layer": (6,)}).declaration()
    assert dict(one.kernels)[attention_facts][2:] == ((("sliding", 6),),
                                                      (6,))
    assert "full_heads" not in one.facts and one.facts["full_layers"] == 0


def test_published_sizes_and_counts(ref):
    """The configuration file at its published widths: the tree the class
    builds from it counts 490,297,344 parameters, each part what the file's
    ``published`` block says, and the benchmark's exact pair counts."""
    cfg = tiny_model.published()
    s = ref.sizes(cfg)
    lm = GatedWindowMoELM(**cfg["build"]["kwargs"]["policy_kwargs"])
    assert lm.heads == (48, 64, 64, 64, 48) == tuple(s["heads"])
    assert lm.mlp_kinds == ("dense",) + ("sparse",) * 4
    assert lm.layer_types == (FULL, SLIDING, SLIDING, SLIDING, FULL)
    shapes = lm.param_shapes()
    count = lambda tree: sum(  # noqa: E731
        math.prod(x.shape) for x in jax.tree_util.tree_leaves(tree))
    assert count(shapes) == 490_297_344 == ref.describe(cfg)["param_dim"]
    assert cfg["deployment"]["state_bytes_per_chip"] == 14 * 490_297_344
    per = cfg["published"]["per_layer_parameters"]
    assert count(shapes["layer_00"]["attn"]) == per["full_mixer_with_gate"]
    assert count(shapes["layer_01"]["attn"]) == per["sliding_mixer_with_gate"]
    assert count(shapes["layer_00"]["mlp"]) == per["dense_ffn"]
    moe = shapes["layer_01"]["moe"]
    assert count(moe["router"]) == per["router"]
    assert count(moe["shared"]) == per["shared_expert"]
    assert count(moe["experts"]) == 16 * per["one_expert"]
    assert count(shapes["layer_00"]) == 79_794_176
    assert count(shapes["layer_01"]) == 91_885_568
    assert count(shapes["layer_04"]) == 83_464_192
    assert count(shapes["embed"]) + count(shapes["head"]) == 51_380_224
    # the published model, one gate column a head: the row's "33.4B"
    total = (2 * 100352 * 2048 + 2048 + 10 * per["full_mixer_with_gate"]
             + 30 * per["sliding_mixer_with_gate"] + 40 * per["two_norms"]
             + per["dense_ffn"] + 39 * (per["router"] + per["shared_expert"]
                                        + 256 * per["one_expert"]))
    assert total == 33_442_596_864
    assert cfg["published"]["parameters"].startswith("33,442,596,864")
    assert lm.rotary_dim(FULL) == 64 and lm.rotary_dim(SLIDING) == 128
    assert dict(lm.declaration().kernels)[attention_facts][2] == (
        ("sliding", 512), ("full", None))
    # the system's flat layout is the reference's
    paths = ["/".join(str(k.key) for k in p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    assert paths == [p for p, _ in ref.system_layout(s)]
    about = ref.describe(cfg)
    assert about["head_flops_per_member_step"] == 2 * 2048 * 12544
    assert about["expected_pairs_per_token_and_layer"] == 0.5
    assert about["expert_layers"] == 4
    # the seeded spreads name leaves the model has
    names = {p.rsplit("/", 1)[1] for p in paths} | {
        "/".join(p.split("/")[-2:]) for p in paths}
    assert set(cfg["seeded_std"]) - {"other"} <= names


# ---------------------------------------------------- (i) partition rules

def test_no_leaf_falls_to_the_catch_all(tiny):
    """The model's leaves are named by the blocks' rules
    (models/lm_blocks.py) but for the gate's narrow projection, which the
    model's own table names."""
    shapes = tiny["lm"].param_shapes()
    blocks = (lm_blocks.DECODER_PARTITION_RULES
              + lm_blocks.EXPERT_PARTITION_RULES)
    assert set(unmatched_leaves(blocks, shapes)) == {
        f"layer_{i:02d}/attn/head_gate" for i in range(4)}
    assert unmatched_leaves(
        tiny["lm"].declaration().partition_rules, shapes) == {}


@pytest.mark.parametrize("pop, model", [(2, 4), (1, 2)])
def test_partition_rules_name_the_leaves(devices8, pop, model):
    mesh = hyperscale_mesh(pop, model, devices8[:pop * model])
    lm = GatedWindowMoELM(**TINY)
    shapes = lm.param_shapes()
    sh = match_partition_rules(
        lm.declaration().partition_rules + DEFAULT_PARTITION_RULES, shapes,
        mesh)

    def spec(*path):
        node = sh
        for k in path:
            node = node[k]
        return tuple(node.spec)

    for n in ("gate", "up", "down"):
        assert spec("layer_01", "moe", "experts", n) == ("model", None, None)
    assert spec("layer_01", "moe", "router") in ((), (None, None))
    assert spec("layer_01", "moe", "shared", "gate") == (None, "model")
    assert spec("layer_00", "mlp", "down") == ("model", None)
    assert spec("layer_00", "attn", "q") == (None, "model")
    assert spec("layer_01", "attn", "q") == (None, "model")
    assert spec("layer_01", "attn", "o") == ("model", None)
    assert spec("layer_01", "attn", "head_gate") in ((), (None, None))
    assert spec("head", "kernel") == (None, "model")
    assert spec("embed", "embedding") == ("model", None)


# ------------------------------------------- (j) through ES, over meshes

def _es(devices, model_shards, **over):
    from estorch_tpu import ES, JaxAgent
    from estorch_tpu.envs import TokenScoreEnv

    kw = dict(
        policy=GatedWindowMoELM, agent=JaxAgent, optimizer=optax.adam,
        population_size=8, sigma=0.02, policy_kwargs=TINY,
        agent_kwargs={"env": TokenScoreEnv(**tiny_model.ENV)},
        optimizer_kwargs={"learning_rate": 1e-2}, shard_params=True,
        model_shards=model_shards, low_rank=1, noise_mode="table",
        table_size=1 << 18, device=list(devices))
    kw.update(over)
    return ES(**kw)


class TestThroughTheShardedEngine:
    @pytest.fixture(scope="class")
    def one_device(self, devices8):
        es = _es(devices8[:1], 1)
        offsets = np.asarray(es.engine.all_pair_offsets(es.state))
        records = []
        es.train(2, verbose=False, log_fn=records.append)
        return dict(es=es, fitness=[r["reward_mean"] for r in es.history],
                    params=np.asarray(es.state.params_flat), offsets=offsets,
                    records=records)

    @pytest.mark.parametrize("pop, model", [(2, 4), (1, 2)])
    def test_mesh_shapes_match_one_device(self, one_device, devices8, pop,
                                          model, centre_form):
        """The same fitness, parameters and counts on (2, 4) and (1, 2)
        virtual meshes as on one device, in both forms of the centre."""
        es = _es(devices8[:pop * model], model)
        assert es.engine.forward_form == "perturbed"
        assert (es.engine.pop_shards, es.engine.model_shards) == (pop, model)
        assert es.engine.centre_form == centre_form
        assert es.engine.kernel_facts["attention_form"] == "xla"
        report = es.engine.sharding_report()
        assert report["layer_01/moe/experts/gate"].startswith(
            "PartitionSpec('model'")
        assert not any("catch-all" in v for v in report.values())
        np.testing.assert_array_equal(
            es.engine.all_pair_offsets(es.state), one_device["offsets"])
        records = []
        es.train(2, verbose=False, log_fn=records.append)
        np.testing.assert_allclose(
            [r["reward_mean"] for r in es.history], one_device["fitness"],
            rtol=2e-5)
        np.testing.assert_allclose(np.asarray(es.state.params_flat),
                                   one_device["params"], atol=2e-5, rtol=0)
        assert ([r["routed_pairs"] for r in records]
                == [r["routed_pairs"] for r in one_device["records"]])

    def test_one_device_run_its_gauges_and_its_counters(self, one_device):
        es = one_device["es"]
        assert es.engine.forward_form == "perturbed"
        assert (es.engine.kernel_facts["attention_form"],
                es.engine.kernel_facts["head_form"],
                es.engine.kernel_facts["combine_form"]) == ("xla", "xla",
                                                            "xla")
        assert [r["env_steps"] for r in es.history] == [8 * 21] * 2
        assert -4.6 < es.history[0]["reward_mean"] < -3.9   # about -log 64
        gauges = es.obs.counters
        assert gauges.get("tokens_per_generation") == 8 * 21
        assert (gauges.get("experts_held"), gauges.get("experts_total"),
                gauges.get("experts_per_token"),
                gauges.get("mtp_depth")) == (4, 16, 3, 0)
        assert (gauges.get("sliding_window"), gauges.get("sliding_layers"),
                gauges.get("full_layers"), gauges.get("dense_layers"),
                gauges.get("sliding_heads"), gauges.get("full_heads")) == (
            6, 2, 2, 1, 6, 4)
        assert gauges.get("attention_form_by_kind") == "sliding:xla,full:xla"
        cfg = es.run_manifest()["config"]
        assert (cfg["sliding_window"], cfg["sliding_heads"],
                cfg["full_heads"]) == (6, 6, 4)
        assert cfg["attention_form_by_kind"] == "sliding:xla,full:xla"
        for r in one_device["records"]:
            # 8 members x 21 tokens x 3 choices x 3 sparse layers, a
            # quarter held
            assert 250 < r["routed_pairs"] < 520
            assert 1.0 <= r["expert_load_max_over_mean"] < 2.5

    def test_the_reference_scores_the_engines_members(self, ref, devices8):
        """Generation 0 of the engine against the reference through the
        keying contract the benchmark's runner relies on: same table, same
        offsets, same keys, both signs of every pair."""
        es = _es(devices8[:1], 1, sigma=0.05)
        s = ref.sizes(tiny_model.config(rank=1))
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
        assert np.ptp(want) > 1e-5

    def test_the_centre_copy_keeps_the_routers_float32(self, devices8):
        es = _es(devices8[:1], 1, compute_dtype="bfloat16")
        eng = es.engine
        dtypes = dict(zip(eng.leaf_paths, eng._leaf_dtypes))
        assert dtypes["layer_01/moe/router"] == jnp.float32
        assert dtypes["layer_00/attn/q"] == jnp.bfloat16
        assert dtypes["layer_01/attn/head_gate"] == jnp.bfloat16
        assert dtypes["layer_01/moe/experts/gate"] == jnp.bfloat16
        es.train(1, verbose=False)
        assert np.isfinite(es.history[0]["reward_mean"])

    @pytest.mark.parametrize("dtype, tol", [("float32", 1e-4),
                                            ("bfloat16", 2e-2)])
    @pytest.mark.parametrize("length, band, sliding, kernels", [
        (32, 6, "xla", 2), (512, 128, "kernel", 4)])
    def test_forced_kernel_runs_the_generation_the_xla_form_runs(
            self, devices8, kernel_attention, dtype, tol, length, band,
            sliding, kernels):
        """The generation program on one device, the engine's scope open
        around its trace: the TWO full layers take the kernel, the two
        sliding layers stay in the XLA form under a band of 6 keys over 32
        positions and take it, the band as the block, under 128 keys over
        512; the gauge and the manifest say which kind took which, and the
        members' fitness is the XLA form's to the order of float32 sums."""
        from estorch_tpu.envs import TokenScoreEnv

        wide = {**TINY, "attention_block": min(length // 2, 64),
                "sliding_window": band}
        env = {"env": TokenScoreEnv(**{**tiny_model.ENV, "seq_len": length})}
        ref_es = _es(devices8[:1], 1, compute_dtype=dtype,
                     policy_kwargs=wide, agent_kwargs=env)
        with kernel_attention():
            kern = _es(devices8[:1], 1, compute_dtype=dtype,
                       policy_kwargs=wide, agent_kwargs=env)
        assert (ref_es.engine.kernel_facts["attention_form"],
                kern.engine.kernel_facts["attention_form"]) == (
                    "xla", "kernel")
        assert ref_es.engine.kernel_facts["attention_form_by_kind"] == (
            "sliding:xla,full:xla")
        assert kern.engine.kernel_facts["attention_form_by_kind"] == (
            f"sliding:{sliding},full:kernel")
        assert kern.run_manifest()["config"][
            "attention_form_by_kind"] == f"sliding:{sliding},full:kernel"
        assert [len(pallas_calls(es.engine._generation_step, es.state,
                                 es.table.data))
                for es in (ref_es, kern)] == [0, kernels]
        ref_es.state, want = ref_es.engine.generation_step(ref_es.state)
        kern.state, got = kern.engine.generation_step(kern.state)
        np.testing.assert_allclose(got["fitness"], want["fitness"], atol=tol)
        assert np.isfinite(np.asarray(got["fitness"])).all()


# ------------------- (k) the cell's own rehearsals that run no child process
# (benchmark/rehearse/test_swg_cell.py: pytest tests/ never collects that
# directory; the ones that run the cell in a child stay the benchmark's own)

import test_swg_cell as _cell  # noqa: E402

test_cell__is_added_by_files_alone = _cell.test_the_cell_is_added_by_files_alone
test_cell__metric_names_this_cell_and_only_it = (
    _cell.test_the_swg_metric_names_this_cell_and_only_it)
test_cell__configuration_keeps_every_published_key = (
    _cell.test_the_configuration_file_keeps_every_published_key)
test_cell__reader_finds_nothing_in_another_models_program = (
    _cell.test_the_reader_finds_nothing_in_a_program_without_the_scopes)
test_cell__costs_are_from_shapes = _cell.test_the_costs_are_from_shapes
