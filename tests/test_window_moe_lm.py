"""WindowMoELM (models/window_moe_lm.py) against the plain reference the
benchmark judges its cell by (benchmark/reference/window_moe_lm.py): float32,
``highest``, Python loops over layers and over the held experts, one full
masked softmax per head, the rotation written from the formula, every
perturbed leaf (and expert) materialised, routes of its own taken AHEAD of
attention."""

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

import window_moe_tiny as tiny_model
from pallas_costs import pallas_calls
from estorch_tpu.models import (CCAMoELM, HybridLM, IndexedMoELM, LoopedLM,
                                MoELM, SambaYLM, WindowMoELM, lm_blocks)
from estorch_tpu.ops import pallas_attention
from estorch_tpu.ops.lowrank import make_lowrank_tree_spec
from estorch_tpu.ops.pallas_attention import (attention_facts,
                                              attention_form_why, call_form,
                                              kernel_scope)
from estorch_tpu.ops.pallas_head import head_facts
from estorch_tpu.parallel.mesh import (DEFAULT_PARTITION_RULES,
                                       hyperscale_mesh, match_partition_rules,
                                       unmatched_leaves)

# the degraded forms the cell's reference check has to refuse
sys.path.insert(0, os.path.join(tiny_model.ROOT, "benchmark", "rehearse"))
import coarse_swa  # noqa: E402

# float32 on both sides; what differs is the ORDER of float32 sums (blocked
# softmax against whole, grouped matmul against a masked loop) on values of
# magnitude 1: measured 2e-6.  1e-4 would still catch bfloat16 anywhere
TOL = 1e-4
TINY = tiny_model.TINY
PERIOD = ("global", "window", "window", "window")


@pytest.fixture(scope="module")
def ref():
    return tiny_model.reference()


def _spread(ref, cfg, key):
    """Seeded weights with every matrix ten times its initial spread, so
    that logits, scores and routes all matter."""
    s = ref.sizes(cfg)
    theta = np.array(ref.init_theta(key, cfg))
    for path, (off, shape) in ref.param_offsets(s).items():
        if path.rsplit("/", 1)[-1] not in ("scale", "__dim__"):
            theta[off:off + math.prod(shape)] *= 10.0
    return jnp.asarray(theta)


def _built(ref, rank=2, **policy):
    cfg = tiny_model.config(rank=rank, policy=policy)
    lm = WindowMoELM(**{**TINY, **policy})
    theta = _spread(ref, cfg, jax.random.PRNGKey(3))
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


@dataclasses.dataclass(frozen=True)
class Tapped(WindowMoELM):
    """The honest model, which also hands out every layer's output and the
    routes it took (read after an un-jitted call)."""

    def _layer(self, *a):
        x, load = WindowMoELM._layer(self, *a)
        TAPS["layers"].append(x)
        return x, load

    def _routes(self, *a):
        experts, weights = WindowMoELM._routes(self, *a)
        TAPS["routes"].append(experts)
        return experts, weights


TAPS = {"layers": [], "routes": []}


def _tapped(lm, *args):
    TAPS["layers"], TAPS["routes"] = [], []
    out = Tapped(**dataclasses.asdict(lm)).perturbed_apply(*args)
    return out, list(TAPS["layers"]), list(TAPS["routes"])


# -------------------------------------------- (a) against the reference

@pytest.mark.parametrize("length", [21, 16, 5])
@pytest.mark.parametrize("sign", [0.0, 1.0, -1.0])
def test_the_forward_matches_the_reference(ref, tiny, sign, length):
    """Scores, the behaviour vector, EVERY layer's output, the routes and
    the pairs that landed on the held experts: the centre (sign 0) and both
    members of a pair from ONE factor read; the band of 6 bites from the
    seventh position on."""
    tokens, c = _tokens(length, length), 0.05 * sign
    noise = None if sign == 0.0 else tiny["spec"].unpack(tiny["noise"])
    member = ref.Member(tiny["s"], tiny["theta"],
                        None if sign == 0.0 else tiny["noise"], c)
    want = ref.forward(tiny["s"], member, tokens, head_block=8,
                       with_choices=True, with_layers=True)
    got, layers, routes = _tapped(tiny["lm"], tiny["params"], noise, c,
                                  tokens)
    for g, w, shape in zip(got[:2], want[:2], [(length - 1,), (64,)]):
        assert g.shape == w.shape == shape and g.dtype == jnp.float32
        np.testing.assert_allclose(g, w, atol=TOL, rtol=0)
    assert len(layers) == len(want[3]) == 3
    for g, w in zip(layers, want[3]):
        np.testing.assert_allclose(g, w, atol=TOL, rtol=0)
    for g, w in zip(routes, want[2]):
        np.testing.assert_array_equal(np.sort(g, -1), np.sort(w, -1))
    chosen = np.concatenate([np.asarray(r).reshape(-1) for r in want[2]])
    np.testing.assert_array_equal(
        got[2], [(chosen == 4 + k).sum() for k in range(4)])
    assert 0 < int(got[2].sum()) < chosen.size      # some held, not all
    assert float(jnp.abs(want[1]).max()) > 0.5      # the logits spread
    if sign:
        centre = ref.forward(tiny["s"], ref.Member(
            tiny["s"], tiny["theta"], None, 0.0), tokens, head_block=8)
        assert float(jnp.abs(want[0] - centre[0]).max()) > 0.05


@pytest.mark.parametrize("kinds", [("global",), ("window",), PERIOD,
                                   ("window", "global")],
                         ids=lambda k: "-".join(k))
@pytest.mark.parametrize("window", [1, 6, 8, 21, 64])
def test_both_kinds_of_layer_alone_and_in_the_published_period(ref, kinds,
                                                               window):
    """A stack of one kind, the published period of four and the other
    order, under bands of one key, under the attention's block (8), at it,
    at the sequence and over it: the reference's scores, behaviour and
    every layer's output."""
    built = _built(ref, layer_types=kinds, sliding_window_size=window)
    tokens = _tokens(21, 4)
    member = ref.Member(built["s"], built["theta"], built["noise"], 0.05)
    want = ref.forward(built["s"], member, tokens, head_block=8,
                       with_layers=True)
    got, layers, _ = _tapped(built["lm"], built["params"],
                             built["spec"].unpack(built["noise"]), 0.05,
                             tokens)
    for g, w in zip(list(got[:2]) + layers, list(want[:2]) + want[2]):
        np.testing.assert_allclose(g, w, atol=TOL, rtol=0)


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, TOL),
                                        (jnp.bfloat16, 0.1)])
@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_both_forms_and_both_dtypes_match_the_reference(ref, tiny, form,
                                                        dtype, tol,
                                                        tiny_widths):
    """A perturbed member in the XLA form and inside a kernel scope under
    the interpreter, in float32 and in bfloat16 (the copy the engine's
    forward reads: routers float32): the reference's scores and behaviour
    to the dtype's rounding.  32 positions in blocks of 8; inside the scope
    the ONE global layer takes the kernel and the two window layers stay in
    the XLA form (a band of 6 keys spans no block of the kernel's:
    ``pallas_attention.call_form``)."""
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
            program = str(jax.make_jaxpr(forward)(params, factors))
            got = forward(params, factors)
        assert program.count("pallas_call[") == 1
    else:
        got = forward(params, factors)
    for g, w in zip(got[:2], want):
        assert g.dtype == jnp.float32 and bool(jnp.isfinite(g).all())
        if dtype == jnp.float32:
            np.testing.assert_allclose(g, w, atol=tol, rtol=0)
        else:
            assert float(jnp.mean(jnp.abs(g - w))) < tol
            assert float(jnp.std(w)) > 0.3


@pytest.mark.parametrize("window, kernels", [(200, 3), (128, 3), (100, 1)])
def test_a_band_of_a_kernel_block_takes_the_kernel_and_matches_the_reference(
        ref, window, kernels, tiny_widths):
    """384 positions are three of the kernel's blocks of 128: inside a scope
    the two window layers take the kernel beside the global one where their
    band spans a block (200 keys: no multiple of it; 128: exactly one) and
    stay in the XLA form under 100 keys; either way a perturbed member's
    scores and behaviour are the float32 reference's."""
    built = _built(ref, sliding_window_size=window, attention_block=64)
    lm, tokens, c = built["lm"], _tokens(384, 11), 0.05
    want = ref.forward(built["s"], ref.Member(
        built["s"], built["theta"], built["noise"], c), tokens, head_block=8)
    factors = built["spec"].unpack(built["noise"])

    def forward(p, f):
        return lm.perturbed_apply(p, f, c, tokens)

    with kernel_scope(interpret=True):
        calls = pallas_calls(forward, built["params"], factors)
        got = forward(built["params"], factors)
    assert len(calls) == kernels
    for g, w in zip(got[:2], want):
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

WRONG = {"fp8_inputs": coarse_swa.Fp8Swa, "all_bf16": coarse_swa.AllBf16Swa,
         "half_window": coarse_swa.HalfWindowSwa,
         "double_window": coarse_swa.DoubleWindowSwa,
         "rotated_global": coarse_swa.RotatedGlobalSwa,
         "silu_for_relu": coarse_swa.SiluSwa,
         "routes_after_attention": coarse_swa.RoutesAfterSwa,
         "other_rank": coarse_swa.OtherRankSwa}


@pytest.mark.parametrize("name", list(WRONG))
def test_each_wrong_forward_fails_the_comparison(ref, tiny, name):
    """In float32, where the honest forward is the reference's to 2e-6: a
    forward in fp8 or with the float32 parts in bfloat16, a band of half or
    twice the width, rotation on the global layer, SiLU for ReLU, routes
    taken after attention and another share's experts each move the scores
    AND the behaviour vector by thirty times the tolerance and more."""
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
        assert float(jnp.abs(g - w).max()) > 30 * TOL, name


# ------------------------------------------ (c) the band and the rotation

@pytest.mark.parametrize("window", [1, 3, 6, 21, 40])
def test_the_window_counts_the_querys_own_position(ref, window):
    """Key s is visible to query t iff t - window < s <= t: ``min(t + 1,
    window)`` keys a query, in the reference's mask and, through the
    softmax over them, in the system's core."""
    mask = np.asarray(ref.visible(0, 21, 21, window))
    np.testing.assert_array_equal(
        mask.sum(axis=1), [min(t + 1, window) for t in range(21)])
    assert all(mask[t, t] for t in range(21))
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (21, 2 * 8))
               for i in range(3))
    got = lm_blocks.attention_core(q, k, v, num_heads=2, num_kv_heads=2,
                                   scale=1.0, block=8, window=window)
    s = jnp.einsum("qhd,shd->hqs", q.reshape(21, 2, 8), k.reshape(21, 2, 8))
    prob = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    want = jnp.einsum("hqs,shd->qhd", prob, v.reshape(21, 2, 8))
    np.testing.assert_allclose(got, want.reshape(21, 16), atol=1e-5, rtol=0)


def test_a_window_of_one_key_writes_the_tokens_own_values(tiny):
    """Band 1: every query sees itself alone, the softmax is 1 and the
    attention writes ``v W_o``, whatever the rotation did to q and k."""
    lm = dataclasses.replace(tiny["lm"], sliding_window_size=1)
    p = tiny["params"]["layer_01"]["attn"]
    u = jax.random.normal(jax.random.PRNGKey(0), (21, 32))
    rotary = lm_blocks.rotary_tables(21, 8, lm.rope_theta)
    got = lm._attention(p, None, 0.0, u, "window", rotary)
    v = (u @ p["v"]).reshape(21, 2, 1, 8)
    want = jnp.broadcast_to(v, (21, 2, 3, 8)).reshape(21, 48) @ p["o"]
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def _permuted(tokens):
    """The same tokens with the earlier ones in another order; the last two
    stay where they are."""
    head = np.asarray(tokens[:-2])
    return jnp.concatenate([jnp.asarray(head[::-1].copy()), tokens[-2:]])


@pytest.mark.parametrize("kinds, moves", [(("global",), False),
                                          (("window",), True)],
                         ids=["global", "window"])
def test_a_global_layer_has_no_position_term(ref, kinds, moves):
    """A global layer tells positions apart by the causal mask alone: what
    the last position predicts does not change when the EARLIER tokens are
    put in another order (ONE layer: a second one reads keys that each saw
    a prefix of their own).  A rotary window layer over the same keys (a
    band wider than the sequence) does change it."""
    built = _built(ref, layer_types=kinds, sliding_window_size=64)
    tokens = _tokens(21, 8)
    a = built["lm"].perturbed_apply(built["params"], None, 0.0, tokens)
    b = built["lm"].perturbed_apply(built["params"], None, 0.0,
                                    _permuted(tokens))
    gap = abs(float(a[0][-1] - b[0][-1]))
    assert (gap > 1e-3) if moves else (gap < 1e-5), gap
    # the reference agrees
    member = ref.Member(built["s"], built["theta"], None, 0.0)
    want = ref.forward(built["s"], member, _permuted(tokens), head_block=8)
    np.testing.assert_allclose(b[0], want[0], atol=TOL, rtol=0)


def test_only_window_layers_turn_their_queries_and_keys(tiny):
    """``es.rope`` holds the tables and the window layers' rotation: a
    stack of global layers alone traces none of it."""
    def text(kinds):
        lm = dataclasses.replace(tiny["lm"], layer_types=kinds)
        shapes = lm.param_shapes()
        return str(jax.make_jaxpr(
            lambda p, t: lm.perturbed_apply(p, None, 0.0, t))(
                jax.tree_util.tree_map(
                    lambda x: jnp.zeros(x.shape, x.dtype), shapes),
                _tokens(16)))
    assert "cos" not in text(("global", "global"))
    assert "cos" in text(("global", "window"))


# ---------------------------- (d) the router reads the LAYER'S INPUT

def test_the_routes_do_not_depend_on_the_attention_beside_them(ref, tiny):
    """A layer's routes are those of ``route`` on ``norm1(x)``, whatever
    the attention writes: with ``o`` zeroed (attention writes nothing) the
    same experts are chosen, bit for bit; the form that routes AFTER
    attention chooses otherwise."""
    tokens = _tokens(21, 5)
    params = tiny["params"]
    silent = jax.tree_util.tree_map(lambda x: x, params)
    for name in ("layer_00", "layer_01", "layer_02"):
        silent[name] = {**params[name], "attn": {
            **params[name]["attn"],
            "o": jnp.zeros_like(params[name]["attn"]["o"])}}
    _, _, routes = _tapped(tiny["lm"], params, None, 0.0, tokens)
    # layer 0's input is the embedding in both: the same routes exactly
    _, _, quiet = _tapped(tiny["lm"], silent, None, 0.0, tokens)
    np.testing.assert_array_equal(routes[0], quiet[0])
    x = jnp.take(params["embed"]["embedding"], tokens, axis=0)
    a = lm_blocks.rmsnorm(x, params["layer_00"]["norm1"]["scale"], 1e-6)
    want, _ = lm_blocks.route(params["layer_00"]["moe"], None, 0.0, a,
                              top_k=3, scaling=1.0, scoring="softmax")
    np.testing.assert_array_equal(routes[0], want)
    # routed after attention, layer 0's own choice is another
    h = x + tiny["lm"]._attention(params["layer_00"]["attn"], None, 0.0, a,
                                  "global", None)
    b = lm_blocks.rmsnorm(h, params["layer_00"]["norm2"]["scale"], 1e-6)
    after, _ = lm_blocks.route(params["layer_00"]["moe"], None, 0.0, b,
                               top_k=3, scaling=1.0, scoring="softmax")
    assert bool((jnp.sort(after, -1) != jnp.sort(want, -1)).any())
    chosen = ref.forward(tiny["s"], ref.Member(
        tiny["s"], tiny["theta"], None, 0.0), tokens, head_block=8,
        with_choices=True)[2]
    np.testing.assert_array_equal(np.sort(routes[0], -1),
                                  np.sort(chosen[0], -1))


def test_the_router_reads_float32(tiny):
    lm = tiny["lm"]
    assert set(lm.float32_leaves) == {
        f"layer_{i:02d}/moe/router" for i in range(3)}
    p = tiny["params"]["layer_01"]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(0), (21, 32))
    idx, w = lm._routes(p, None, 0.0, u)
    half = {**p, "router": p["router"].astype(jnp.bfloat16)}
    _, w16 = lm._routes(half, None, 0.0, u)
    assert w.dtype == w16.dtype == jnp.float32
    assert float(jnp.abs(w - w16).max()) > 1e-4
    with jax.default_matmul_precision("highest"):
        prob = jax.nn.softmax(u @ p["router"], axis=-1)
    picked = jnp.take_along_axis(prob, idx, axis=-1)
    np.testing.assert_allclose(
        w, picked / picked.sum(axis=-1, keepdims=True), rtol=2e-6)
    # a softmax over all experts before the choice, renormalised, IS the
    # softmax over the chosen logits
    logits = jnp.take_along_axis(u @ p["router"], idx, axis=-1)
    np.testing.assert_allclose(w, jax.nn.softmax(logits, axis=-1), rtol=1e-4)


# ------------------------------------------- (e) the shares add up

def test_the_four_shares_add_up_to_the_uncut_layer(ref):
    """model-configs section 4: 16 tiny experts over 4 shares (the cell's
    0-15, 16-31, 32-47, 48-63 of 64 in small), each through
    ``routed_experts`` under routes taken from ANOTHER state than the one
    the experts read; the four partial results equal the uncut reference's
    layer (and the uncut system's)."""
    cfgs = [_built(ref, moe_num_primary_experts=4, expert_group_size=4,
                   expert_group_rank=r) for r in range(4)]
    whole = _built(ref, moe_num_primary_experts=16, expert_group_size=1,
                   expert_group_rank=0)
    s, base = whole["s"], "layer_01"
    a = jax.random.normal(jax.random.PRNGKey(2), (21, 32))
    b = jax.random.normal(jax.random.PRNGKey(3), (21, 32))
    member = ref.Member(s, whole["theta"], None, 0.0)
    chosen, w = ref.routes(s, member.layer(base), a)
    want = ref.held_experts(s, member.experts_of(base), b, chosen, w)
    p = whole["params"][base]["moe"]
    experts, weights = lm_blocks.route(p, None, 0.0, a, top_k=3, scaling=1.0,
                                       scoring="softmax")
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(chosen, -1))

    def held(first, count):
        stack = {n: p["experts"][n][first:first + count]
                 for n in ("gate", "up", "down")}
        return lm_blocks.routed_experts(
            stack, None, 0.0, b, experts, weights, first_held=first,
            total=16, activation=jax.nn.relu)

    parts = [held(4 * r, 4) for r in range(4)]
    np.testing.assert_allclose(sum(y for y, _ in parts), want, atol=TOL,
                               rtol=0)
    uncut, load = held(0, 16)
    np.testing.assert_allclose(uncut, want, atol=TOL, rtol=0)
    np.testing.assert_array_equal(
        np.concatenate([l for _, l in parts]), load)
    assert int(load.sum()) == 21 * 3                # every pair lands once
    # a share alone is NOT the layer
    assert float(jnp.abs(parts[0][0] - want).max()) > 0.01
    # and the models built as shares hold what the slices hold
    assert [c["lm"].first_expert_held for c in cfgs] == [0, 4, 8, 12]
    assert all(c["lm"].experts_total == 16 for c in cfgs)
    shares = [c["lm"]._experts(
        {"experts": {n: p["experts"][n][4 * r:4 * r + 4]
                     for n in ("gate", "up", "down")}},
        None, 0.0, b, experts, weights) for r, c in enumerate(cfgs)]
    np.testing.assert_allclose(sum(y for y, _ in shares), want, atol=TOL,
                               rtol=0)


# --------------------------------- (f) the gate's activation is the model's

def _mlp_inputs():
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    p = {"gate": jax.random.normal(k[0], (32, 16)),
         "up": jax.random.normal(k[1], (32, 16)),
         "down": jax.random.normal(k[2], (16, 32))}
    return p, jax.random.normal(k[3], (21, 32))


def test_the_gated_ffn_takes_the_models_activation():
    p, u = _mlp_inputs()
    default = lm_blocks.gated_mlp(lm_blocks.dense, p, None, 0.0, u)
    silu = lm_blocks.gated_mlp(lm_blocks.dense, p, None, 0.0, u,
                               jax.nn.silu)
    relu = lm_blocks.gated_mlp(lm_blocks.dense, p, None, 0.0, u, jax.nn.relu)
    np.testing.assert_array_equal(default, silu)
    np.testing.assert_allclose(
        default, (jax.nn.silu(u @ p["gate"]) * (u @ p["up"])) @ p["down"],
        rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(
        relu, (jax.nn.relu(u @ p["gate"]) * (u @ p["up"])) @ p["down"],
        rtol=1e-5, atol=1e-4)
    assert float(jnp.abs(relu - default).max()) > 0.1


def test_the_expert_layer_takes_the_models_activation(ref):
    """``routed_experts`` with ReLU is the reference's ReGLU layer; the
    default is SiLU, bit for bit what every other expert model calls."""
    whole = _built(ref, moe_num_primary_experts=16, expert_group_size=1,
                   expert_group_rank=0)
    p = whole["params"]["layer_00"]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(4), (21, 32))
    experts, weights = lm_blocks.route(p, None, 0.0, u, top_k=3, scaling=1.0,
                                       scoring="softmax")

    def layer(**kw):
        return lm_blocks.routed_experts(p["experts"], None, 0.0, u, experts,
                                        weights, first_held=0, total=16,
                                        **kw)[0]

    np.testing.assert_array_equal(layer(), layer(activation=jax.nn.silu))
    want = jnp.zeros_like(u)
    for e in range(16):
        weight = jnp.sum(jnp.where(experts == e, weights, 0.0), axis=-1)
        hidden = (jax.nn.relu(u @ p["experts"]["gate"][e])
                  * (u @ p["experts"]["up"][e]))
        want = want + weight[:, None] * (hidden @ p["experts"]["down"][e])
    np.testing.assert_allclose(layer(activation=jax.nn.relu), want,
                               atol=TOL, rtol=0)
    assert float(jnp.abs(layer() - want).max()) > 0.01


def _other_models():
    import cca_moe_tiny
    import indexed_moe_tiny
    import lm_tiny
    import loop_tiny
    import moe_tiny
    import sambay_tiny

    return {"hybrid": (HybridLM, lm_tiny), "looped": (LoopedLM, loop_tiny),
            "moe": (MoELM, moe_tiny), "sambay": (SambaYLM, sambay_tiny),
            "indexed_moe": (IndexedMoELM, indexed_moe_tiny),
            "cca_moe": (CCAMoELM, cca_moe_tiny)}


@pytest.mark.parametrize("name", ["hybrid", "looped", "moe", "sambay",
                                  "indexed_moe", "cca_moe"])
def test_the_other_models_outputs_are_what_they_were(name, monkeypatch):
    """The activation argument leaves every other model as it was: its
    output with the default equals, BIT FOR BIT, its output with SiLU handed
    to the gated FFN and to the expert layer by name; handed ReLU there, it
    is another model's (the argument does reach the place)."""
    cls, module = _other_models()[name]
    lm = cls(**module.TINY)
    params = lm.init(jax.random.PRNGKey(1), None)["params"]
    params = jax.tree_util.tree_map(
        lambda x: x * (1.0 if x.ndim == 1 else 10.0), params)
    tokens = _tokens(16, 2)
    honest_mlp, honest_experts = lm_blocks.gated_mlp, lm_blocks.routed_experts

    def forward():
        return lm.perturbed_apply(params, None, 0.0, tokens)

    def handed(activation):
        with monkeypatch.context() as m:
            m.setattr(lm_blocks, "gated_mlp",
                      lambda *a: honest_mlp(*a, activation=activation))
            m.setattr(lm_blocks, "routed_experts",
                      lambda *a, **kw: honest_experts(
                          *a, **{**kw, "activation": activation}))
            return forward()

    default, silu, relu = forward(), handed(jax.nn.silu), handed(jax.nn.relu)
    for d, s in zip(default, silu):
        np.testing.assert_array_equal(d, s)
    assert float(jnp.abs(default[0] - relu[0]).max()) > 1e-4


# --------------------------- (g) every leaf's and every expert's correction

LEAVES = [path for path, _ in tiny_model.reference().system_layout(
    tiny_model.reference().sizes(tiny_model.config(rank=2)))]
CASES = [(p, None) for p in LEAVES if "/experts/" not in p] + [
    (p, k) for p in LEAVES if "/experts/" in p for k in range(4)]


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
        np.testing.assert_allclose(g, w, atol=TOL, rtol=0)
    np.testing.assert_array_equal(got[2], want[2])
    moved = max(float(jnp.abs(w - x).max())
                for w, x in zip(want[:2], centre[:2]))
    if expert is not None:
        # an expert no token of this sequence chose moves nothing
        layer = int(path.split("/")[0][-2:])
        chosen = ref.forward(s, ref.Member(s, tiny["theta"], None, 0.0),
                             tokens, head_block=8, with_choices=True)[2][layer]
        if not bool((chosen == 4 + expert).any()):
            assert moved == 0.0
            return
    assert moved > 1e-4, (path, expert, moved)


# -------------------------------------------- (h) sizes, init, validation

@pytest.mark.parametrize("bad, match", [
    ({"moe_primary_router_apply_softmax": False}, "is not written"),
    ({"norm_topk_prob": False}, "norm_topk_prob = False is not written"),
    ({"rope_scaling": {"type": "yarn"}}, "not written"),
    ({"tie_word_embeddings": True}, "not written"),
    ({"layer_types": ("global", "full")}, "a layer is"),
    ({"layer_types": ()}, "a layer is"),
    ({"num_key_value_heads": 4}, "multiple of key heads"),
    ({"head_dim": 7}, "even"),
    ({"sliding_window_size": 0}, "sliding_window_size"),
    ({"expert_group_rank": 4}, "shares"),
    ({"moe_num_active_primary_experts": 17}, "more experts"),
    ({"behaviour_positions": 0}, "behaviour_positions"),
])
def test_sizes_are_validated(bad, match):
    with pytest.raises(ValueError, match=match):
        WindowMoELM(**{**TINY, **bad})


def test_what_is_not_written_is_refused_in_the_other_models_voice():
    import moe_tiny

    with pytest.raises(ValueError) as ours:
        WindowMoELM(**{**TINY, "norm_topk_prob": False})
    with pytest.raises(ValueError) as theirs:
        MoELM(**{**moe_tiny.TINY, "norm_topk_prob": False})
    assert str(ours.value) == str(theirs.value)


def test_init_draws_the_declared_tree(tiny):
    lm = tiny["lm"]
    params = lm.init(jax.random.PRNGKey(0), None)["params"]
    shapes = lm.param_shapes()
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(shapes))
    for got, want in zip(jax.tree_util.tree_leaves(params),
                         jax.tree_util.tree_leaves(shapes)):
        assert got.shape == want.shape and got.dtype == jnp.float32
    layer = params["layer_01"]
    assert np.all(np.asarray(layer["norm1"]["scale"]) == 1.0)
    assert 0.01 < float(layer["moe"]["experts"]["gate"].std()) < 0.03
    assert layer["moe"]["router"].shape == (32, 16)
    assert set(layer["attn"]) == {"q", "k", "v", "o"}       # no norm, no bias
    assert set(layer["moe"]) == {"router", "experts"}       # no shared expert
    assert layer["attn"]["q"].shape == (32, 48)
    assert layer["attn"]["k"].shape == (32, 16)


def test_the_declaration(tiny):
    stated = tiny["lm"].declaration()
    # heads of 8 over 2 key heads, each kind of attention layer with its
    # band; the head at the hidden width
    kernels = dict(stated.kernels)
    assert kernels[attention_facts] == (
        8, 2, (("window", 6), ("global", None)), 6)
    assert kernels[head_facts] == (32,)
    assert stated.leaf_rows == {"head/kernel": 8}
    assert stated.leaf_rows_per_token == dict.fromkeys(
        tiny["lm"].stacked_leaves, 3 * 1.25 / 4)
    assert len(stated.stacked_leaves) == 9 and stated.outputs == (
        "expert_load",)
    assert stated.facts == {
        "experts_held": 4, "experts_total": 16, "experts_per_token": 3,
        "mtp_depth": 0, "sliding_window": 6, "window_layers": 2,
        "global_layers": 1}
    # a stack of one kind states that kind alone
    def kinds(layer_types):
        return dict(dataclasses.replace(
            tiny["lm"], layer_types=layer_types).declaration().kernels)[
                attention_facts][2]

    assert kinds(("global",)) == (("global", None),)
    assert kinds(("window",)) == (("window", 6),)


def test_published_sizes_and_layouts(ref):
    """The configuration file: the parameter count recomputed from the built
    tree, the published count from the published keys, the layer kinds from
    the two published layouts, the reference's layouts equal to the system's
    tree and noise spec, no leaf left to the catch-all partition rule, what
    the engine's rules read."""
    cfg = tiny_model.published()
    about = ref.describe(cfg)
    layers = cfg["num_hidden_layers"]
    attention = 2 * 2560 * 3584 + 2 * 2560 * 512
    outside = attention + 2 * 2560 + 2560 * 64
    assert (attention, outside) == (20_971_520, 21_140_480)
    expert = 3 * 2560 * 768
    want = layers * (outside + 16 * expert) + 2 * 37984 * 2560 + 2560
    assert about["param_dim"] == want == 656_529_920
    assert cfg["deployment"]["state_bytes_per_chip"] == 14 * want == (
        9_191_418_880)
    published = cfg["published"]
    assert 52 * (outside + 64 * expert) + 2 * 151936 * 2560 + 2560 == (
        21_506_562_560)
    assert "21,506,562,560" in published["parameters"]
    assert cfg["reduced"] == ["num_hidden_layers", "moe_num_primary_experts",
                              "vocab_size"]
    assert (published["num_hidden_layers"],
            published["moe_num_primary_experts"],
            published["vocab_size"]) == (52, 64, 151936)
    assert 4 * cfg["vocab_size"] == 151936 and (
        4 * cfg["moe_num_primary_experts"] == 64)
    # the kinds from the two published layouts, kept whole
    assert len(cfg["sliding_window_layout"]) == len(cfg["rope_layout"]) == 52
    assert cfg["sliding_window_layout"] == cfg["rope_layout"] == [
        int(i % 4 != 0) for i in range(52)]
    assert cfg["layer_types"] == [
        "window" if banded else "global"
        for banded in cfg["sliding_window_layout"]]
    kwargs = cfg["build"]["kwargs"]["policy_kwargs"]
    assert cfg["layer_types"][:layers] == kwargs["layer_types"] == list(
        PERIOD)
    lm = WindowMoELM(**kwargs)
    assert (lm.experts_total, lm.moe_num_active_primary_experts,
            lm.first_expert_held, lm.num_attention_heads,
            lm.num_key_value_heads, lm.head_dim, lm.sliding_window_size,
            lm.moe_ffn_hidden_size, lm.rope_theta) == (
        64, 6, 0, 28, 4, 128, 4096, 768, 1_500_000)
    # every published key the module has a field for holds what it builds
    fields = dataclasses.asdict(lm)
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "moe_ffn_hidden_size", "sliding_window_size",
                "moe_num_active_primary_experts", "moe_num_primary_experts",
                "moe_primary_router_apply_softmax", "norm_topk_prob",
                "rope_theta", "rope_scaling", "rms_norm_eps",
                "tie_word_embeddings", "vocab_size", "expert_group_size",
                "behaviour_positions"):
        assert fields[key] == cfg[key], key
    assert cfg["horizon"] == cfg["max_position_embeddings"] == 16384
    stated = lm.declaration()
    kernels = dict(stated.kernels)
    widths, kv_heads, windows, query_heads = kernels[attention_facts]
    assert (widths, kernels[head_facts], kv_heads, windows, query_heads) == (
        128, (2560,), 4, (("window", 4096), ("global", None)), 28)
    assert stated.leaf_rows_per_token == dict.fromkeys(
        lm.stacked_leaves, 6 * 1.25 / 4)
    # 28 query heads over 4 key heads of 128 at 16,384: whole column
    # blocks, so the global layer takes the kernel on one chip, and the
    # window layers too: their band is four of its blocks of 1,024
    form, why = attention_form_why("tpu", 1, widths, cfg["horizon"], 4096,
                                   kv_heads)
    assert form == "kernel" and why.endswith(
        "layers with a window of 4096 in the kernel")
    assert pallas_attention.fits(128, 0, 128, None, 16384)
    assert [call_form(form, band, cfg["horizon"])
            for _, band in windows] == ["kernel", "kernel"]
    shapes = lm.param_shapes()
    paths = ["/".join(str(k.key) for k in p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    s = ref.sizes(cfg)
    assert ([(p, tuple(x.shape)) for p, x in
             zip(paths, jax.tree_util.tree_leaves(shapes))]
            == ref.system_layout(s))
    spec = make_lowrank_tree_spec(shapes, 1, stacked=lm.stacked_leaves)
    layout = ref.noise_layout(s)
    assert spec.noise_dim == layout["__dim__"] == about["noise_dim"]
    for i, m, n, a_off, b_off in spec.lr_leaves:
        assert layout[paths[i]] == ("lr", a_off, b_off)
    for i, e, m, n, a_off, b_off in spec.stacked_leaves:
        assert layout[paths[i]] == ("stacked", a_off, b_off)
        assert e == 16
    for i, _, _, off in spec.dense_leaves:
        assert layout[paths[i]] == ("dense", off)
    assert len(spec.stacked_leaves) == 3 * layers
    dense = {paths[i].rsplit("/", 1)[1] for i, *_ in spec.dense_leaves}
    assert dense == {"scale"}
    assert unmatched_leaves(stated.partition_rules, shapes) == {}
    assert about["expert_flops_per_member_step"] == int(
        layers * 6 * 16 / 64 * 2 * 3 * 2560 * 768)
    assert about["dense_flops_per_member_step"] == layers * 2 * attention
    assert about["head_flops_per_member_step"] == 2 * 2560 * 37984
    # the seeded spreads name leaves the model has
    names = {p.rsplit("/", 1)[1] for p in paths}
    assert set(cfg["seeded_std"]) - {"other"} <= names


def test_no_leaf_falls_to_the_catch_all(tiny):
    """The model's leaves are all named by the blocks' rules
    (models/lm_blocks.py): q, k, v, o, the norms, embedding and head by
    the decoder's frame, the router and the stacked experts by the expert
    layer's; its own list is those two and nothing else."""
    shapes = tiny["lm"].param_shapes()
    own = tiny["lm"].declaration().partition_rules
    assert unmatched_leaves(own, shapes) == {}
    assert own == (lm_blocks.DECODER_PARTITION_RULES
                   + lm_blocks.EXPERT_PARTITION_RULES)
    assert unmatched_leaves(lm_blocks.DECODER_PARTITION_RULES, shapes) != {}


@pytest.mark.parametrize("pop, model", [(2, 4), (1, 2)])
def test_partition_rules_name_the_leaves(devices8, pop, model):
    mesh = hyperscale_mesh(pop, model, devices8[:pop * model])
    lm = WindowMoELM(**TINY)
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
    assert spec("layer_00", "attn", "q") == (None, "model")
    assert spec("layer_00", "attn", "k") == (None, "model")
    assert spec("layer_00", "attn", "o") == ("model", None)
    assert spec("layer_00", "norm1", "scale") in ((), (None,))
    assert spec("head", "kernel") == (None, "model")
    assert spec("embed", "embedding") == ("model", None)


# ------------------------------------------- (i) through ES, over meshes

def _es(devices, model_shards, **over):
    from estorch_tpu import ES, JaxAgent
    from estorch_tpu.envs import TokenScoreEnv

    kw = dict(
        policy=WindowMoELM, agent=JaxAgent, optimizer=optax.adam,
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
        virtual meshes as on one device, in the XLA form."""
        es = _es(devices8[:pop * model], model)
        assert es.engine.forward_form == "perturbed"
        assert (es.engine.pop_shards, es.engine.model_shards) == (pop, model)
        assert es.engine.centre_form == (
            centre_form if model > 1 else "split")
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
            rtol=2e-6)
        np.testing.assert_allclose(np.asarray(es.state.params_flat),
                                   one_device["params"], atol=1e-5, rtol=0)
        assert ([r["routed_pairs"] for r in records]
                == [r["routed_pairs"] for r in one_device["records"]])

    def test_one_device_run_its_gauges_and_its_counters(self, one_device):
        es = one_device["es"]
        assert es.engine.forward_form == "perturbed"
        assert (es.engine.kernel_facts["attention_form"],
                es.engine.kernel_facts["head_form"]) == (
                    "xla", "xla")
        # the expert layers' combine too: the scatter-add on a CPU mesh
        assert (es.engine.kernel_facts["combine_form"],
                es.obs.counters.get("combine_form"),
                es.run_manifest()["config"]["combine_form"]) == ("xla",) * 3
        assert [r["env_steps"] for r in es.history] == [8 * 21] * 2
        assert -4.6 < es.history[0]["reward_mean"] < -3.9   # about -log 64
        gauges = es.obs.counters
        assert gauges.get("tokens_per_generation") == 8 * 21
        assert (gauges.get("experts_held"), gauges.get("experts_total"),
                gauges.get("experts_per_token"),
                gauges.get("mtp_depth")) == (4, 16, 3, 0)
        assert (gauges.get("sliding_window"), gauges.get("window_layers"),
                gauges.get("global_layers")) == (6, 2, 1)
        assert gauges.get("attention_form_by_kind") == (
            "window:xla,global:xla")
        cfg = es.run_manifest()["config"]
        assert (cfg["sliding_window"], cfg["window_layers"],
                cfg["global_layers"]) == (6, 2, 1)
        assert cfg["attention_form_by_kind"] == "window:xla,global:xla"
        assert cfg["attention_form_why"] == "the devices are 'cpu', not TPUs"
        assert "selected_pairs" not in one_device["records"][0]
        for r in one_device["records"]:
            # 8 members x 21 tokens x 3 choices x 3 layers, a quarter held
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
        assert np.ptp(want) > 1e-4

    def test_the_centre_copy_keeps_the_routers_float32(self, devices8):
        es = _es(devices8[:1], 1, compute_dtype="bfloat16")
        eng = es.engine
        dtypes = dict(zip(eng.leaf_paths, eng._leaf_dtypes))
        assert dtypes["layer_01/moe/router"] == jnp.float32
        assert dtypes["layer_00/attn/q"] == jnp.bfloat16
        assert dtypes["layer_01/moe/experts/gate"] == jnp.bfloat16
        es.train(1, verbose=False)
        assert np.isfinite(es.history[0]["reward_mean"])

    @pytest.mark.parametrize("dtype, tol", [("float32", 1e-4),
                                            ("bfloat16", 2e-2)])
    @pytest.mark.parametrize("positions, window, by_kind, kernels", [
        (32, 6, "window:xla,global:kernel", 1),
        (384, 200, "window:kernel,global:kernel", 3)])
    def test_forced_kernel_runs_the_generation_the_xla_form_runs(
            self, devices8, kernel_attention, positions, window, by_kind,
            kernels, dtype, tol):
        """The generation program on one device, the engine's scope open
        around its trace: the ONE global layer takes the kernel; the two
        window layers stay in the XLA form under a band of 6 keys over 32
        positions and take the kernel under 200 keys over 384 (three of
        its blocks of 128: the band spans one); the gauge and the manifest
        say which kind took which, and the members' fitness is the XLA
        form's to the order of float32 sums (in bfloat16 over 384
        positions the rounding moves a route in a thousand of the layers
        above)."""
        from estorch_tpu.envs import TokenScoreEnv

        wide = {**TINY, "attention_block": 16,
                "sliding_window_size": window}
        env = {"env": TokenScoreEnv(**{**tiny_model.ENV,
                                       "seq_len": positions})}
        ref_es = _es(devices8[:1], 1, compute_dtype=dtype,
                     policy_kwargs=wide, agent_kwargs=env)
        with kernel_attention():
            kern = _es(devices8[:1], 1, compute_dtype=dtype,
                       policy_kwargs=wide, agent_kwargs=env)
        assert (ref_es.engine.kernel_facts["attention_form"],
                kern.engine.kernel_facts["attention_form"]) == (
                    "xla", "kernel")
        assert ref_es.engine.kernel_facts["attention_form_by_kind"] == (
            "window:xla,global:xla")
        assert kern.engine.kernel_facts["attention_form_by_kind"] == by_kind
        assert kern.run_manifest()["config"][
            "attention_form_by_kind"] == by_kind
        assert [len(pallas_calls(es.engine._generation_step, es.state,
                                 es.table.data))
                for es in (ref_es, kern)] == [0, kernels]
        ref_es.state, want = ref_es.engine.generation_step(ref_es.state)
        kern.state, got = kern.engine.generation_step(kern.state)
        np.testing.assert_allclose(got["fitness"], want["fitness"], atol=tol)
        moved = np.abs(np.asarray(got["expert_load"])
                       - np.asarray(want["expert_load"])).sum()
        assert moved <= (0 if (dtype, positions) != ("bfloat16", 384)
                         else 0.005 * np.asarray(want["expert_load"]).sum())
        assert np.isfinite(np.asarray(got["fitness"])).all()


# ------------------- (j) the cell's own rehearsals that run no child process
# (benchmark/rehearse/test_swa_cell.py: pytest tests/ never collects that
# directory; the ones that run the cell in a child stay the benchmark's own)

import test_swa_cell as _cell  # noqa: E402


def test_cell__is_added_by_files_alone(monkeypatch):
    """The rehearsal also asserts that the cell and its configuration are
    the LAST of their lists; cells that later PRs append follow them (PR
    52's did), so it is handed the lists up to its own entries."""
    bench = _cell._bench()

    def upto(entries, name):
        return entries[:[e["name"] for e in entries].index(name) + 1]

    monkeypatch.setattr(_cell, "_bench", lambda: {
        **bench, "workloads": upto(bench["workloads"], _cell.CELL),
        "configs": upto(bench["configs"], _cell.CONFIG)})
    _cell.test_the_cell_is_added_by_files_alone()


def test_cell__metrics_name_this_cell_and_only_it(monkeypatch):
    """The rehearsal also asserts that ``swa.*`` are the LAST per-layer
    entries; metrics that later PRs append follow them, as the contract
    asks of new entries (``boot.*`` since PR 50), and dropping that check is
    a ``benchmark`` PR's edit (PERF.md §7).  So it is handed the list up to
    its own entries, and what follows must name no cell list with this
    cell in it."""
    bench = _cell._bench()
    names = [m["name"] for m in bench["per_layer"]]
    end = max(i for i, n in enumerate(names) if n.startswith("swa.")) + 1
    assert not [m["name"] for m in bench["per_layer"][end:]
                if _cell.CELL in m.get("workloads", [])]
    monkeypatch.setattr(_cell, "_bench", lambda: {
        **bench, "per_layer": bench["per_layer"][:end]})
    _cell.test_the_swa_metrics_name_this_cell_and_only_it()


test_cell__configuration_keeps_every_published_key = (
    _cell.test_the_configuration_file_keeps_every_published_key)
test_cell__reader_finds_nothing_in_another_models_program = (
    _cell.test_the_reader_finds_nothing_in_a_program_without_the_scopes)
test_cell__costs_are_from_shapes = _cell.test_the_costs_are_from_shapes
