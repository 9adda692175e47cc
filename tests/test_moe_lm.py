"""MoELM (models/moe_lm.py) against the plain reference the benchmark judges
its cell by (benchmark/reference/moe_lm.py): float32, ``highest``, Python
loops over layers and over the held experts with a boolean mask each, one
full masked softmax per head, the rotation written from the formula, every
perturbed leaf (and expert) materialised, routes of its own."""

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.flatten_util import ravel_pytree

import moe_tiny
from estorch_tpu.models import LoopedLM, MoELM, lm_blocks
from estorch_tpu.models.perturbed import (leaf_columns, perturbed_dense,
                                          perturbed_grouped_dense)
from estorch_tpu.ops.lowrank import (lowrank_tree_noise,
                                     lowrank_tree_weighted_sum,
                                     make_lowrank_tree_spec)
from estorch_tpu.ops.pallas_attention import (attention_facts,
                                              attention_form)
from estorch_tpu.parallel.mesh import (DEFAULT_PARTITION_RULES,
                                       hyperscale_mesh, match_partition_rules,
                                       unmatched_leaves)

# the models here are tiny (heads of 8, sequences of 16): inside a
# ``kernel_scope`` their attention calls take the kernel all the same
# (conftest.py::tiny_widths fakes the call's own rule,
# ``pallas_attention.fits``, which the interpreter does not need)
pytestmark = pytest.mark.usefixtures("tiny_widths")

# float32 on both sides; what differs is the ORDER of float32 sums (blocked
# softmax against whole, grouped matmul against a masked loop) on values of
# magnitude 1: measured 1e-6 to 3e-6.  1e-4 would still catch bfloat16
# anywhere (1e-2)
TOL = 1e-4


@pytest.fixture(scope="module")
def ref():
    return moe_tiny.reference()


def _spread(ref, cfg, key):
    """Seeded weights with every matrix ten times its initial spread and
    selection biases of the size of a score gap, so that logits, routes and
    the bias all matter."""
    s = ref.sizes(cfg)
    theta = np.array(ref.init_theta(key, cfg))
    rng = np.random.default_rng(0)
    for path, (off, shape) in ref.param_offsets(s).items():
        name = path.rsplit("/", 1)[-1]
        if name == "router_bias":
            theta[off:off + shape[0]] = 0.05 * rng.normal(size=shape)
        elif name not in ("scale", "__dim__"):
            theta[off:off + math.prod(shape)] *= 10.0
    return jnp.asarray(theta)


def _built(ref, rank=2, **policy):
    cfg = moe_tiny.config(rank=rank, policy=policy)
    lm = MoELM(**{**moe_tiny.TINY, **policy})
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


# -------------------------------------------- (a) against the reference

@pytest.mark.parametrize("length", [21, 16, 5])
@pytest.mark.parametrize("sign", [0.0, 1.0, -1.0])
def test_heads_match_the_reference(ref, tiny, sign, length):
    """Main and MTP log-probabilities, the main head's last logits, the
    policy output and the pairs that landed on the held experts: the centre
    (sign 0) and both members of a pair from ONE factor read."""
    tokens, c = _tokens(length, length), 0.05 * sign
    noise = None if sign == 0.0 else tiny["spec"].unpack(tiny["noise"])
    member = ref.Member(tiny["s"], tiny["theta"],
                        None if sign == 0.0 else tiny["noise"], c)
    want = ref.heads(tiny["s"], member, tokens, head_block=8,
                     with_routes=True)
    got = tiny["lm"].heads(tiny["params"], noise, c, tokens)
    for g, w, shape in zip(got[:3], want[:3],
                           [(length - 1,), (length - 1,), (64,)]):
        assert g.shape == w.shape == shape and g.dtype == jnp.float32
        np.testing.assert_allclose(g, w, atol=TOL, rtol=0)
    # the system's load is the reference's routes counted by held expert
    chosen = np.concatenate([np.asarray(r).reshape(-1) for r in want[3]])
    np.testing.assert_array_equal(
        got[3], [(chosen == 4 + k).sum() for k in range(4)])
    assert 0 < int(got[3].sum()) < chosen.size      # some held, not all
    score, last, load = tiny["lm"].perturbed_apply(tiny["params"], noise, c,
                                                   tokens)
    want_score, want_last = ref.forward(tiny["s"], member, tokens,
                                        head_block=8)
    np.testing.assert_allclose(score, want_score, atol=TOL, rtol=0)
    np.testing.assert_allclose(last, want_last, atol=TOL, rtol=0)
    np.testing.assert_array_equal(load, got[3])
    assert float(jnp.abs(want[2]).max()) > 0.5      # the logits spread
    if sign:
        centre = ref.forward(tiny["s"], ref.Member(
            tiny["s"], tiny["theta"], None, 0.0), tokens, head_block=8)
        assert float(jnp.abs(want_score - centre[0]).max()) > 0.05


def test_apply_is_the_centre_alone(tiny):
    tokens = _tokens(21)
    got = tiny["lm"].apply({"params": tiny["params"]}, tokens)
    want = tiny["lm"].perturbed_apply(tiny["params"], None, 0.0, tokens)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_members_under_vmap_share_one_sort_and_one_grouped_matmul(tiny):
    """The engine's nesting (pairs, then signs) around the model: every
    member's output equals its own evaluation, and the jaxpr holds ONE sort
    and three grouped matmuls an expert layer however many members."""
    lm, spec, tokens = tiny["lm"], tiny["spec"], _tokens(21, 9)
    rows = jax.random.normal(jax.random.PRNGKey(7), (3, spec.noise_dim))
    signs = jnp.asarray([0.05, -0.05])

    def member(row, c):
        return lm.perturbed_apply(tiny["params"], spec.unpack(row), c, tokens)

    def all_members(rows):
        return jax.vmap(lambda row: jax.vmap(
            lambda c: member(row, c))(signs))(rows)

    got = jax.jit(all_members)(rows)
    for i in range(3):
        for j in range(2):
            want = member(rows[i], signs[j])
            for g, w in zip(got, want):
                np.testing.assert_allclose(g[i, j], w, atol=1e-5, rtol=0)
    text = str(jax.make_jaxpr(all_members)(rows))
    layers = 3                                  # two of the stack, the MTP's
    assert text.count("ragged_dot_general[") == 3 * layers
    assert len(re.findall(r"= argsort\b|name=argsort\b", text)) == layers


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for held in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(held)


def test_no_row_fetches_its_own_copy_of_a_factor(tiny):
    """The routed rows' corrections are products against the stacked
    factors: under the engine's nesting no ``gather`` of the expert layers
    reads a factor stack ``[members, held, m | n, r]`` (one that did would
    write a ``[rows, m | n, r]`` copy of it)."""
    lm, spec, tokens = tiny["lm"], tiny["spec"], _tokens(21, 9)
    rows = jax.random.normal(jax.random.PRNGKey(7), (3, spec.noise_dim))
    signs = jnp.asarray([0.05, -0.05])

    def all_members(rows):
        return jax.vmap(lambda row: jax.vmap(lambda c: lm.perturbed_apply(
            tiny["params"], spec.unpack(row), c, tokens))(signs))(rows)

    # a stacked leaf's factors [held, m | n, r]: 4 experts held, rank 2
    factors = {(4, moe_tiny.TINY[width], 2)
               for width in ("hidden_size", "moe_intermediate_size")}
    gathers = [e for e in _equations(jax.make_jaxpr(all_members)(rows).jaxpr)
               if e.primitive.name == "gather"]
    assert gathers                  # the tokens' rows are still gathered
    assert not [e for e in gathers
                if e.invars[0].aval.shape[-3:] in factors]


def test_each_member_its_own_weights_goes_member_by_member(tiny):
    """The materialised form hands every member its own tree: the expert
    layer then has no centre to share and evaluates the members one by
    one, to the same numbers."""
    lm, tokens = tiny["lm"], _tokens(21, 3)
    trees = jax.tree_util.tree_map(
        lambda x: jnp.stack([x, 1.5 * x]), tiny["params"])
    got = jax.vmap(lambda p: lm.apply({"params": p}, tokens))(trees)
    for i, scale in enumerate([1.0, 1.5]):
        want = lm.apply({"params": jax.tree_util.tree_map(
            lambda x: scale * x, tiny["params"])}, tokens)
        for g, w in zip(got, want):
            np.testing.assert_allclose(g[i], w, atol=1e-5, rtol=0)


# --------------------------- (b) every leaf's and every expert's correction

LEAVES = [path for path, _ in moe_tiny.reference().system_layout(
    moe_tiny.reference().sizes(moe_tiny.config(rank=2)))]
CASES = [(p, None) for p in LEAVES if "/experts/" not in p] + [
    (p, k) for p in LEAVES if "/experts/" in p for k in range(4)]


@pytest.fixture(scope="module")
def one_leaf_programs(tiny):
    lm, spec = tiny["lm"], tiny["spec"]
    perturbed = jax.jit(lambda p, n, c, t: lm.heads(p, spec.unpack(n), c, t))
    plain = jax.jit(lambda p, t: lm.heads(p, None, 0.0, t))
    return perturbed, plain


@pytest.mark.parametrize("path, expert", CASES)
def test_a_leafs_correction_is_the_materialised_sum(ref, tiny,
                                                    one_leaf_programs, path,
                                                    expert):
    """Noise on ONE leaf (one EXPERT of a stacked leaf): the perturbed
    forward equals the plain forward of the materialised ``theta + c·E``."""
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
    for g, w in zip(got[:3], want[:3]):
        np.testing.assert_allclose(g, w, atol=TOL, rtol=0)
    np.testing.assert_array_equal(got[3], want[3])
    moved = max(float(jnp.abs(w - x).max())
                for w, x in zip(want[:3], centre[:3]))
    if expert is not None:
        # an expert no token of this sequence chose moves nothing
        layer = ["layer_01", "layer_02", "mtp/layer"].index(
            path.split("/moe/")[0])
        chosen = ref.heads(s, ref.Member(s, tiny["theta"], None, 0.0),
                           tokens, head_block=8, with_routes=True)[3][layer]
        if not bool((chosen == 4 + expert).any()):
            assert moved == 0.0
            return
    assert moved > 1e-4, (path, expert, moved)


# ------------------------------------------- (c) the shares add up

def test_the_shares_add_up_to_the_uncut_layer(ref):
    """16 tiny experts over 4 shares: the four partial results of one
    expert layer, the shared expert counted once, equal the uncut
    reference's layer (and the uncut system's)."""
    cfgs = [_built(ref, n_routed_experts=4, expert_group_size=4,
                   expert_group_rank=r) for r in range(4)]
    whole = _built(ref, n_routed_experts=16, expert_group_size=1,
                   expert_group_rank=0)
    s, base = whole["s"], "layer_01"
    u = jax.random.normal(jax.random.PRNGKey(2), (21, 32))
    member = ref.Member(s, whole["theta"], None, 0.0)
    want, _ = ref.moe_ffn(s, member.layer(base, "moe"),
                          member.experts_of(base), u)
    p = whole["params"][base]["moe"]
    shared = lm_blocks.gated_mlp(MoELM._dense, p["shared"], None, 0.0, u)
    experts, weights = lm_blocks.route(p, None, 0.0, u, top_k=3, scaling=2.5)

    def held(first, count):
        stack = {n: p["experts"][n][first:first + count]
                 for n in ("gate", "up", "down")}
        return lm_blocks.routed_experts(stack, None, 0.0, u, experts,
                                        weights, first_held=first, total=16)

    parts = [held(4 * r, 4) for r in range(4)]
    total = shared + sum(y for y, _ in parts)
    np.testing.assert_allclose(total, want, atol=TOL, rtol=0)
    uncut, load = held(0, 16)
    np.testing.assert_allclose(shared + uncut, want, atol=TOL, rtol=0)
    np.testing.assert_array_equal(
        np.concatenate([l for _, l in parts]), load)
    assert int(load.sum()) == 21 * 3                # every pair lands once
    # a share alone is NOT the layer
    assert float(jnp.abs(shared + parts[0][0] - want).max()) > 0.01
    # and the models built as shares hold what the slices hold
    assert [c["lm"].first_expert_held for c in cfgs] == [0, 4, 8, 12]
    assert all(c["lm"].experts_total == 16 for c in cfgs)


def test_no_pair_is_dropped_however_uneven_the_routes(ref, tiny, monkeypatch):
    """A router whose bias sends EVERY token to the held experts routes
    four times what the layer takes at a time: the rows go through in
    several passes and the result is the reference's."""
    s, base = tiny["s"], "layer_01"
    theta = np.array(tiny["theta"])
    off, shape = ref.param_offsets(s)[f"{base}/moe/router_bias"]
    theta[off + 4:off + 8] = 5.0                   # experts 4..7 held here
    member = ref.Member(s, theta, None, 0.0)
    u = jax.random.normal(jax.random.PRNGKey(4), (21, 32))
    want, chosen = ref.moe_ffn(s, member.layer(base, "moe"),
                               member.experts_of(base), u)
    assert int(((chosen >= 4) & (chosen < 8)).sum()) == 21 * 3
    p = tiny["unravel"](jnp.asarray(theta))[base]["moe"]
    experts, weights = lm_blocks.route(p, None, 0.0, u, top_k=3, scaling=2.5)
    assert lm_blocks.expert_capacity(21 * 3, 4, 16) == 24 < 21 * 3
    y, load = lm_blocks.routed_experts(p["experts"], None, 0.0, u, experts,
                                       weights, first_held=4, total=16)
    shared = lm_blocks.gated_mlp(MoELM._dense, p["shared"], None, 0.0, u)
    np.testing.assert_allclose(shared + y, want, atol=TOL, rtol=0)
    assert int(load.sum()) == 63


def test_capacity_follows_the_expected_load():
    # the cell's chunk: 2 members x 4096 tokens x 8, 16 of 256 held
    assert lm_blocks.expert_capacity(65536, 16, 256) == 5120
    assert lm_blocks.expert_capacity(65536, 8, 256) == 2560
    # the uncut layer takes every pair at once
    assert lm_blocks.expert_capacity(63, 16, 16) == 64
    assert lm_blocks.expert_capacity(65536, 256, 256) == 65536


# ---------------------------------------- (d) the routing equations

def _router(weights, bias):
    return {"router": jnp.asarray(weights, jnp.float32),
            "router_bias": jnp.asarray(bias, jnp.float32)}


def _logit(p):
    return math.log(p / (1 - p))


ROUTES = {
    # scores 0.9 0.8 0.7 0.6: the two largest, weights renormalised to 2.5
    "renormalised and scaled": (
        [0.9, 0.8, 0.7, 0.6], [0, 0, 0, 0], [0, 1],
        [2.5 * 0.9 / 1.7, 2.5 * 0.8 / 1.7]),
    # the bias lifts expert 3 into the choice; its WEIGHT is still its score
    "the bias enters the choice only": (
        [0.9, 0.8, 0.7, 0.6], [0, 0, 0, 0.25], [0, 3],
        [2.5 * 0.9 / 1.5, 2.5 * 0.6 / 1.5]),
    # a bias that reorders the chosen: order by s + b, weights by s
    "the order is by score plus bias": (
        [0.9, 0.8, 0.7, 0.6], [0, 0.3, 0, 0], [1, 0],
        [2.5 * 0.8 / 1.7, 2.5 * 0.9 / 1.7]),
    # equal scores: the lower index first
    "ties go to the lower index": (
        [0.7, 0.9, 0.7, 0.7], [0, 0, 0, 0], [1, 0],
        [2.5 * 0.9 / 1.6, 2.5 * 0.7 / 1.6]),
}


@pytest.mark.parametrize("case", list(ROUTES))
def test_routing_case_by_case(ref, case):
    scores, bias, want_idx, want_w = ROUTES[case]
    # one token [1, 0]: the router's first row sets the logits
    p = _router([[_logit(s) for s in scores], [0.0] * 4], bias)
    u = jnp.asarray([[1.0, 0.0]])
    idx, w = lm_blocks.route(p, None, 0.0, u, top_k=2, scaling=2.5)
    np.testing.assert_array_equal(idx[0], want_idx)
    np.testing.assert_allclose(w[0], want_w, rtol=1e-5)
    np.testing.assert_allclose(float(w.sum()), 2.5, rtol=1e-6)
    s = {"num_experts_per_tok": 2, "routed_scaling_factor": 2.5}
    r_idx, r_w = ref.routes(s, {"moe/router": p["router"],
                                "moe/router_bias": p["router_bias"]}, u)
    np.testing.assert_array_equal(r_idx[0], want_idx)
    np.testing.assert_allclose(r_w[0], want_w, rtol=1e-5)


def test_the_router_reads_float32_whatever_the_compute_dtype(tiny):
    """bfloat16 operands everywhere else; the router's leaves stay float32
    (``float32_leaves``) and its scores come from the float32 state."""
    lm = tiny["lm"]
    assert set(lm.float32_leaves) == {
        f"{b}/moe/{n}" for b in ("layer_01", "layer_02", "mtp/layer")
        for n in ("router", "router_bias")}
    p = tiny["params"]["layer_01"]["moe"]
    u = jax.random.normal(jax.random.PRNGKey(0), (21, 32))
    idx, w = lm_blocks.route(p, None, 0.0, u, top_k=3, scaling=2.5)
    half = {**p, "router": p["router"].astype(jnp.bfloat16)}
    _, w16 = lm_blocks.route(half, None, 0.0, u, top_k=3, scaling=2.5)
    assert w.dtype == w16.dtype == jnp.float32
    # a rounded router is another router: its scores differ in the third
    # digit, and the float32 one's are the formula's to the seventh
    assert float(jnp.abs(w - w16).max()) > 1e-4
    s = jax.nn.sigmoid(u @ p["router"])
    picked = jnp.take_along_axis(s, idx, axis=-1)
    np.testing.assert_allclose(
        w, 2.5 * picked / picked.sum(axis=-1, keepdims=True), rtol=1e-5)


# ------------------------------------------------ (e) rotation, attention

def test_interleaved_rotation_is_the_formula_and_the_halves_permuted():
    """Position p turns the pair (x_2i, x_2i+1) by p·theta^(-2i/d); it is
    the halves rotation of the de-interleaved vector, interleaved back."""
    hd, t, theta = 8, 7, 10000.0
    x = jax.random.normal(jax.random.PRNGKey(0), (t, 3, hd))
    cos, sin = lm_blocks.rotary_tables(t, hd, theta)
    got = np.asarray(lm_blocks.rotate(x, cos, sin, interleaved=True))
    for p in range(t):
        for i in range(hd // 2):
            angle = p * theta ** (-2.0 * i / hd)
            a, b = np.asarray(x[p, :, 2 * i]), np.asarray(x[p, :, 2 * i + 1])
            np.testing.assert_allclose(
                got[p, :, 2 * i], a * np.cos(angle) - b * np.sin(angle),
                atol=1e-5)
            np.testing.assert_allclose(
                got[p, :, 2 * i + 1], b * np.cos(angle) + a * np.sin(angle),
                atol=1e-5)
    perm = np.concatenate([np.arange(0, hd, 2), np.arange(1, hd, 2)])
    halves = np.asarray(lm_blocks.rotate(x[..., perm], cos, sin))
    np.testing.assert_allclose(got[..., perm], halves, atol=1e-6)
    # relative: <R_p q, R_s k> depends on p - s alone
    q, k = x[0, 0], x[1, 0]
    rot = lambda v, p: lm_blocks.rotate(  # noqa: E731
        jnp.broadcast_to(v, (t, 1, hd)), cos, sin, interleaved=True)[p, 0]
    np.testing.assert_allclose(rot(q, 5) @ rot(k, 3), rot(q, 2) @ rot(k, 0),
                               atol=1e-5)


def test_the_core_takes_a_value_width_of_its_own(ref, tiny):
    """Heads 12 wide where they are scored and 6 where they are summed:
    the core against a plain masked softmax."""
    t, nh, dq, dv = 21, 4, 12, 6
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (t, nh, d))
               for i, d in enumerate((dq, dq, dv)))
    got = lm_blocks.attention_core(q, k, v, num_heads=nh, num_kv_heads=nh,
                                   scale=0.3, block=8)
    s = jnp.einsum("qhd,shd->hqs", q, k) * 0.3
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    want = jnp.einsum("hqs,shd->qhd", jax.nn.softmax(s, axis=-1), v)
    assert got.shape == (t, nh * dv)
    np.testing.assert_allclose(got, want.reshape(t, nh * dv), atol=1e-5)


def test_one_core_for_the_three_models(tiny):
    """``causal_attention`` (the q/k/v/o form of the other two models) is
    its projections around the same core."""
    import loop_tiny
    from estorch_tpu.models import hybrid_lm, looped_lm, moe_lm

    assert hybrid_lm._rmsnorm is looped_lm.rmsnorm is moe_lm.rmsnorm
    lm = LoopedLM(**loop_tiny.TINY)
    p = lm.init(jax.random.PRNGKey(0))["params"]["layer_00"]["attn"]
    u = jax.random.normal(jax.random.PRNGKey(1), (21, 32))
    rotary = lm_blocks.rotary_tables(21, 8, 10000.0)
    got = lm_blocks.causal_attention(
        LoopedLM._dense, p, None, 0.0, u, num_heads=4, num_kv_heads=2,
        head_dim=8, scale=0.35, block=8, rotary=rotary)
    q = lm_blocks.rotate((u @ p["q"]).reshape(21, 4, 8), *rotary)
    k = lm_blocks.rotate((u @ p["k"]).reshape(21, 2, 8), *rotary)
    ctx = lm_blocks.attention_core(q, k, u @ p["v"], num_heads=4,
                                   num_kv_heads=2, scale=0.35, block=8)
    np.testing.assert_allclose(got, ctx @ p["o"], atol=1e-5)


@pytest.mark.parametrize("widths, length, want", [
    # latent attention as the model states it: 128 a head scored with its
    # own key, 64 with the ONE rotated key (two heads a lane block), values
    # of 128
    ((128, 64, 128), 4096, "kernel"),
    ((128, 64, 128), 4000, "xla"),     # no block divides the sequence
    ((128, 128, 128), 4096, "kernel"),  # a shared part of whole lane blocks
    ((128, 32, 128), 4096, "xla"),     # four heads a block: not written
    ((192, 0, 128), 4096, "xla"),      # the 192 unsplit: not whole lanes
    ((128, 64, 64), 4096, "xla"),      # values of half a lane block
    (128, 4096, "kernel"),     # the looped model's, as before
    (64, 4096, "xla"),
    (256, 4096, "kernel"),
])
def test_attention_form_reads_the_query_key_width(widths, length, want):
    assert attention_form("tpu", 1, widths, length) == want
    assert attention_form("cpu", 1, widths, length) == "xla"
    assert attention_form("tpu", 4, widths, length) == "xla"


def test_the_models_say_the_width_their_heads_are_scored_at(tiny):
    import lm_tiny
    import loop_tiny
    from estorch_tpu.models import HybridLM

    assert tiny["lm"].qk_head_dim == 8 + 4
    def widths(lm):
        return dict(lm.declaration().kernels)[attention_facts][0]

    assert widths(tiny["lm"]) == (8, 4, 6)
    assert widths(LoopedLM(**loop_tiny.TINY)) == 8
    hybrid = HybridLM(**lm_tiny.TINY)
    assert widths(hybrid) == hybrid.head_dim
    published = MoELM(**moe_tiny.published()["build"]["kwargs"][
        "policy_kwargs"])
    assert published.qk_head_dim == 192
    assert widths(published) == (128, 64, 128)


@pytest.mark.parametrize("noise_form", ["factored", "dense", "none"])
@pytest.mark.parametrize("cols", [slice(0, 8), slice(8, None)],
                         ids=["own", "rotated"])
def test_a_projection_cut_by_columns_is_the_cut_of_the_projection(
        noise_form, cols):
    """``q_b`` is computed a part at a time (``leaf_columns``): the leaf
    ``[m, heads · 12]`` and its noise cut to a head's first 8 columns or
    its last 4 give those columns of the whole perturbed projection."""
    m, nh, width, r = 10, 3, 12, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (7, m))
    w = jax.random.normal(ks[1], (m, nh * width))
    noise = {"factored": (jax.random.normal(ks[2], (m, r)),
                          jax.random.normal(ks[3], (nh * width, r))),
             "dense": jax.random.normal(ks[2], (m, nh * width)),
             "none": None}[noise_form]
    whole = perturbed_dense(x, w, noise, 0.3).reshape(7, nh, width)
    w_cut, noise_cut = leaf_columns(w, noise, nh, cols)
    part = perturbed_dense(x, w_cut, noise_cut, 0.3)
    np.testing.assert_allclose(part, whole[..., cols].reshape(7, -1),
                               atol=1e-5)


def test_the_kernel_takes_heads_of_two_widths():
    """Heads 12 wide where they are scored and 6 where they are summed,
    through the core inside a scope: the kernel, equal to the XLA form."""
    from estorch_tpu.ops.pallas_attention import kernel_scope

    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (16, 2, d))
               for i, d in enumerate((12, 12, 6)))
    kw = dict(num_heads=2, num_kv_heads=2, scale=0.3, block=8)
    want = lm_blocks.attention_core(q, k, v, **kw)
    with kernel_scope(interpret=True):
        assert "pallas_call" in str(jax.make_jaxpr(
            lambda *a: lm_blocks.attention_core(*a, **kw))(q, k, v))
        got = lm_blocks.attention_core(q, k, v, **kw)
    assert got.shape == (16, 2 * 6)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_the_heads_kernel_is_reached_through_the_same_scope():
    """A hidden width of one 128-lane block over 512 positions fits the
    head's rule (ops/pallas_head.py): inside a ``kernel_scope`` the main
    head and the MTP head both score in the head's kernel (interpreted
    here), handed the leaf, its factors and ``c`` as the XLA form is; the
    averaged last logits stay the XLA matmul."""
    from estorch_tpu.ops.pallas_attention import kernel_scope

    lm = MoELM(**{**moe_tiny.TINY, "hidden_size": 128,
                  "layer_types": ("moe",), "attention_block": 128,
                  "head_block": 96, "behaviour_positions": 8})
    tokens = _tokens(512, 4)
    params = jax.tree_util.tree_map(
        lambda x: 3.0 * x, lm.init(jax.random.PRNGKey(2))["params"])
    spec = make_lowrank_tree_spec(lm.param_shapes(), 2,
                                  stacked=lm.stacked_leaves)
    factors = spec.unpack(
        jax.random.normal(jax.random.PRNGKey(5), (spec.noise_dim,)))
    want = lm.heads(params, factors, 0.05, tokens)
    with kernel_scope(interpret=True):
        program = str(jax.make_jaxpr(
            lambda p, f: lm.heads(p, f, 0.05, tokens))(params, factors))
        got = lm.heads(params, factors, 0.05, tokens)
    assert program.count("next_token_scores") >= 2      # main and MTP
    for g, w in zip(got[:3], want[:3]):
        assert g.shape == w.shape and bool(jnp.isfinite(g).all())
        np.testing.assert_allclose(g, w, atol=TOL, rtol=0)
    np.testing.assert_array_equal(got[3], want[3])
    assert float(jnp.abs(got[0] - want[0]).max()) > 0.0  # another program
    centre = lm.heads(params, None, 0.0, tokens)
    assert float(jnp.abs(got[1] - centre[1]).max()) > 1e-3  # the correction


# ------------------------------------------------------ (f) the MTP term

def test_the_mtp_term_scores_the_token_after_next(ref, tiny):
    """``mtp_t`` is a log-probability of ``tokens[t+2]``: changing token
    ``j`` moves ``mtp_{j-2}`` through its target (and every ``mtp_t`` with
    ``t >= j-1`` through the inputs), never ``mtp_t`` for ``t < j-2``; the
    last entry (t+2 past the end) is 0 and the score adds lambda times it."""
    lm, tokens = tiny["lm"], _tokens(21, 6)
    main, mtp, last, _ = lm.heads(tiny["params"], None, 0.0, tokens)
    assert float(mtp[-1]) == 0.0 and float(jnp.abs(mtp[:-1]).min()) > 0.0
    j = 12
    other = tokens.at[j].set((tokens[j] + 1) % 64)
    main2, mtp2, _, _ = lm.heads(tiny["params"], None, 0.0, other)
    np.testing.assert_array_equal(mtp2[:j - 2], mtp[:j - 2])
    assert float(jnp.abs(mtp2[j - 2] - mtp[j - 2])) > 1e-4
    # the main head's target at t = j - 1 is token j: one place later
    np.testing.assert_array_equal(main2[:j - 1], main[:j - 1])
    assert float(jnp.abs(main2[j - 1] - main[j - 1])) > 1e-4
    score, _, _ = lm.perturbed_apply(tiny["params"], None, 0.0, tokens)
    np.testing.assert_allclose(score, main + 0.1 * mtp, atol=1e-6)
    # the reference, entry by entry: log softmax of the MTP logits at t+2
    member = ref.Member(tiny["s"], tiny["theta"], None, 0.0)
    _, want_mtp, _ = ref.heads(tiny["s"], member, tokens, head_block=8)
    np.testing.assert_allclose(mtp, want_mtp, atol=TOL, rtol=0)
    assert float(want_mtp[-1]) == 0.0


def test_the_behaviour_is_a_mean_over_the_last_positions(ref, tiny):
    """The second output: the main head's logits averaged over the last
    ``behaviour_positions`` positions (all of them where the sequence is
    shorter); 1 is the last position's logits, what the other two models
    give.  One token's discrete choice of experts cannot make a mean of
    hundreds jump (PERF.md §6, PR 33).

    The file's tiny build and its weights throughout (``behaviour_positions``
    shapes no leaf), every forward jitted: un-jitted, a forward compiles
    each of its primitives by itself at every new length, 7 s a length."""
    params = tiny["params"]
    lms = {k: dataclasses.replace(tiny["lm"], behaviour_positions=k)
           for k in (1, 4, 512)}
    behaviour = {k: jax.jit(lambda p, t, lm=lm: lm.heads(p, None, 0.0, t)[2])
                 for k, lm in lms.items()}
    tokens = _tokens(21, 5)
    for k in lms:
        s = ref.sizes(moe_tiny.config(
            rank=2, policy={"behaviour_positions": k}))
        member = ref.Member(s, tiny["theta"], None, 0.0)
        _, _, want = ref.heads(s, member, tokens, head_block=8)
        np.testing.assert_allclose(behaviour[k](params, tokens), want,
                                   atol=TOL, rtol=0)
    # the same weights in all three: the means are means of the same rows,
    # each the last position's logits of a prefix
    short = tokens[:5]
    rows = [behaviour[1](params, short[:t]) for t in range(1, 6)]
    np.testing.assert_allclose(behaviour[1](params, short), rows[-1],
                               atol=1e-6)
    np.testing.assert_allclose(behaviour[4](params, short),
                               jnp.mean(jnp.stack(rows[-4:]), 0), atol=1e-5)
    np.testing.assert_allclose(behaviour[512](params, short),
                               jnp.mean(jnp.stack(rows), 0), atol=1e-5)
    with pytest.raises(ValueError, match="behaviour_positions"):
        MoELM(**{**moe_tiny.TINY, "behaviour_positions": 0})


def test_lambda_zero_is_the_main_head_alone(ref):
    built = _built(ref, mtp_lambda=0.0)
    tokens = _tokens(21, 8)
    score, _, _ = built["lm"].apply({"params": built["params"]}, tokens)
    main, _, _, _ = built["lm"].heads(built["params"], None, 0.0, tokens)
    np.testing.assert_array_equal(score, main)


# --------------------------------------- (g) noise of a stacked leaf

@pytest.fixture(scope="module")
def stacked_spec():
    shapes = {"experts": {"down": jax.ShapeDtypeStruct((3, 6, 10), jnp.float32),
                          "gate": jax.ShapeDtypeStruct((3, 10, 6), jnp.float32)},
              "kernel": jax.ShapeDtypeStruct((10, 6), jnp.float32),
              "taps": jax.ShapeDtypeStruct((3, 4, 2), jnp.float32)}
    return shapes, make_lowrank_tree_spec(
        shapes, 2, stacked=("experts/down", "experts/gate"))


def test_a_stacked_leaf_has_a_factor_pair_per_expert(stacked_spec):
    shapes, spec = stacked_spec
    # two stacked leaves [3, m, n]: 3 x (m + n) x 2 floats each
    assert [(i, e, m, n) for i, e, m, n, _, _ in spec.stacked_leaves] == [
        (0, 3, 6, 10), (1, 3, 10, 6)]
    assert [l[:3] for l in spec.lr_leaves] == [(2, 10, 6)]
    # a 3-D leaf nobody named stays dense, as before
    assert [l[:2] for l in spec.dense_leaves] == [(3, (3, 4, 2))]
    assert spec.noise_dim == 2 * 3 * 16 * 2 + 16 * 2 + 24
    unnamed = make_lowrank_tree_spec(shapes, 2)
    assert unnamed.stacked_leaves == () and len(unnamed.dense_leaves) == 3
    vec = jnp.arange(spec.noise_dim, dtype=jnp.float32)
    tree = spec.unpack(vec)
    a, b = tree["experts"]["down"]
    assert a.shape == (3, 6, 2) and b.shape == (3, 10, 2)
    batched = spec.unpack(jnp.stack([vec, vec + 1]))
    assert batched["experts"]["gate"][0].shape == (2, 3, 10, 2)


def test_a_stacked_leafs_noise_and_weighted_sum(stacked_spec):
    """``lowrank_tree_noise`` is ``A_e B_e^T / sqrt(r)`` an expert, and the
    weighted sum over members is the materialised sum."""
    shapes, spec = stacked_spec
    rows = jax.random.normal(jax.random.PRNGKey(0), (5, spec.noise_dim))
    weights = jnp.asarray([0.5, -1.0, 2.0, 0.0, 1.5])
    dense = [lowrank_tree_noise(spec, r) for r in rows]
    a, b = spec.unpack(rows[0])["experts"]["gate"]
    for e in range(3):
        np.testing.assert_allclose(dense[0]["experts"]["gate"][e],
                                   a[e] @ b[e].T / math.sqrt(2), atol=1e-6)
    got = lowrank_tree_weighted_sum(spec, rows, weights)
    want = jax.tree_util.tree_map(
        lambda *xs: sum(w * x for w, x in zip(weights, xs)), *dense)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, atol=1e-5)


def test_the_grouped_form_is_the_materialised_expert_of_each_row():
    """``perturbed_grouped_dense``: rows sorted by expert, members mixed."""
    e, m, n, r, members, rows = 3, 6, 5, 2, 2, 8
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    w = jax.random.normal(keys[0], (e, m, n))
    a = jax.random.normal(keys[1], (members, e, m, r))
    b = jax.random.normal(keys[2], (members, e, n, r))
    x = jax.random.normal(keys[3], (rows, m))
    row_expert = jnp.asarray([0, 0, 0, 1, 2, 2, 2, 2])
    row_member = jnp.asarray([0, 1, 1, 0, 1, 0, 0, 1])
    c = jnp.asarray([0.3, -0.2])
    got = perturbed_grouped_dense(x, w, jnp.asarray([3, 1, 4]), (a, b), c,
                                  row_expert, row_member)
    for i in range(rows):
        k, j = int(row_expert[i]), int(row_member[i])
        full = w[k] + c[j] * a[j, k] @ b[j, k].T / math.sqrt(r)
        np.testing.assert_allclose(got[i], x[i] @ full, atol=1e-5)
    centre = perturbed_grouped_dense(x, w, jnp.asarray([3, 1, 4]), None, c,
                                     row_expert, row_member)
    np.testing.assert_allclose(centre[3], x[3] @ w[1], atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("members, experts, rank", [
    (1, 16, 1), (4, 8, 1), (2, 4, 2), (3, 5, 8)])
def test_the_grouped_form_row_by_row(members, experts, rank, dtype):
    """Each row against ``x_i @ (W[e_i] + c[m_i]·A[m_i, e_i]·B[m_i, e_i]ᵀ/√r)``
    materialised in float32 (``A`` as the product reads it: in ``x``'s
    dtype), with an empty group, members mixed inside a group, and rows
    past ``sum(group_sizes)``, which get their correction alone."""
    m, n, past = 24, 20, 5
    keys = jax.random.split(jax.random.PRNGKey(experts * rank), 6)
    sizes = np.array(jax.random.randint(keys[0], (experts,), 1, 6))
    sizes[experts // 2] = 0
    held = int(sizes.sum())
    rows = held + past
    w = jax.random.normal(keys[1], (experts, m, n)).astype(dtype)
    a = jax.random.normal(keys[2], (members, experts, m, rank))
    b = jax.random.normal(keys[3], (members, experts, n, rank))
    x = jax.random.normal(keys[4], (rows, m)).astype(dtype)
    c = jnp.linspace(-0.3, 0.4, members)
    row_expert = np.concatenate([np.repeat(np.arange(experts), sizes),
                                 np.full(past, experts - 1)])
    row_member = np.asarray(jax.random.randint(keys[5], (rows,), 0, members))
    got = jax.jit(perturbed_grouped_dense)(
        x, w, jnp.asarray(sizes, jnp.int32), (a, b), c,
        jnp.asarray(row_expert, jnp.int32), jnp.asarray(row_member, jnp.int32))
    assert got.shape == (rows, n) and got.dtype == jnp.float32
    x32, w32 = np.asarray(x, np.float32), np.asarray(w, np.float32)
    a32 = np.asarray(a.astype(dtype), np.float32)
    for i in range(rows):
        k, j = row_expert[i], row_member[i]
        full = float(c[j]) * a32[j, k] @ np.asarray(b[j, k]).T / math.sqrt(rank)
        if i < held:
            full = full + w32[k]
        np.testing.assert_allclose(got[i], x32[i] @ full, atol=1e-5, rtol=0)


# -------------------------------------------- (h) sizes, layouts, rules

@pytest.mark.parametrize("bad, match", [
    ({"layer_types": ("attention",)}, "kinds"),
    ({"layer_types": ()}, "kinds"),
    ({"n_group": 2}, "n_group"),
    ({"topk_group": 2}, "topk_group"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"num_nextn_predict_layers": 2}, "num_nextn_predict_layers"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"qk_rope_head_dim": 5}, "even"),
    ({"expert_group_rank": 4}, "shares"),
    ({"num_experts_per_tok": 17}, "more experts"),
])
def test_sizes_are_validated(bad, match):
    with pytest.raises(ValueError, match=match):
        MoELM(**{**moe_tiny.TINY, **bad})


def test_init_draws_the_declared_tree(tiny):
    lm = tiny["lm"]
    params = lm.init(jax.random.PRNGKey(0), None)["params"]
    shapes = lm.param_shapes()
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(shapes))
    for got, want in zip(jax.tree_util.tree_leaves(params),
                         jax.tree_util.tree_leaves(shapes)):
        assert got.shape == want.shape and got.dtype == jnp.float32
    moe = params["layer_01"]["moe"]
    assert np.all(np.asarray(moe["router_bias"]) == 0.0)
    assert np.all(np.asarray(params["mtp"]["embed_norm"]["scale"]) == 1.0)
    assert 0.01 < float(moe["experts"]["gate"].std()) < 0.03
    assert moe["router"].shape == (32, 16) and "mlp" in params["layer_00"]


def test_published_sizes_and_layouts(ref):
    """The configuration file: the counts ISSUE 33 derives, the reference's
    layouts equal to the system's tree and noise spec, no leaf left to the
    catch-all partition rule, what the chunk rule reads."""
    cfg = moe_tiny.published()
    about = ref.describe(cfg)
    held = cfg["n_routed_experts"]
    assert held in (16, 8) and cfg["expert_group_size"] * held == 256
    mla = (2048 * 1536 + 1536 * 6144 + 2048 * 576 + 512 * 8192
           + 4096 * 2048 + 1536 + 512)
    assert mla == 26_347_520
    expert_layer = (mla + 2 * 2048 + 2048 * 256 + 256
                    + (1 + held) * 3 * 2048 * 768)
    dense_layer = mla + 3 * 2048 * 7168 + 2 * 2048
    assert dense_layer == 70_391_808
    mtp = 2 * 2048 + 4096 * 2048 + expert_layer + 2048
    want = (dense_layer + 4 * expert_layer + mtp + 2 * 16160 * 2048 + 2048)
    assert about["param_dim"] == want
    assert want == {16: 680_441_088, 8: 491_697_408}[held]
    assert cfg["deployment"]["state_bytes_per_chip"] == 14 * want
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert cfg["published"]["n_routed_experts"] == 256
    assert len(cfg["layer_types"]) == 40
    assert cfg["layer_types"] == ["dense"] + ["moe"] * 39
    kwargs = cfg["build"]["kwargs"]["policy_kwargs"]
    assert cfg["layer_types"][:5] == kwargs["layer_types"]
    lm = MoELM(**kwargs)
    assert (lm.experts_total, lm.num_experts_per_tok, lm.first_expert_held,
            lm.qk_head_dim, lm.v_head_dim, lm.q_lora_rank, lm.kv_lora_rank,
            lm.moe_intermediate_size, lm.routed_scaling_factor) == (
        256, 8, 0, 192, 128, 1536, 512, 768, 2.5)
    # every published key the module has a field for holds what it builds
    import dataclasses
    fields = dataclasses.asdict(lm)
    for key in ("hidden_size", "intermediate_size", "num_attention_heads",
                "qk_nope_head_dim", "qk_rope_head_dim", "n_shared_experts",
                "n_group", "topk_group", "norm_topk_prob", "scoring_func",
                "topk_method", "rope_interleave", "rope_theta",
                "rms_norm_eps", "num_nextn_predict_layers", "vocab_size",
                "n_routed_experts", "expert_group_size", "mtp_lambda",
                "behaviour_positions"):
        assert fields[key] == cfg[key], key
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
        assert e == held
    for i, _, _, off in spec.dense_leaves:
        assert layout[paths[i]] == ("dense", off)
    assert len(spec.stacked_leaves) == 15           # 5 expert layers x 3
    dense = {paths[i].rsplit("/", 1)[1] for i, *_ in spec.dense_leaves}
    assert dense == {"scale", "router_bias"}
    assert unmatched_leaves(lm.declaration().partition_rules, shapes) == {}
    # the work a token is: 0.6 GFLOP of matmul, the experts' part expected
    assert about["expert_flops_per_member_step"] == int(
        5 * 8 * held / 256 * 2 * 3 * 2048 * 768)
    assert 0.60e9 < about["flops_per_member_step"] < 0.64e9


def test_no_leaf_falls_to_the_catch_all(tiny):
    from estorch_tpu.models import lm_blocks

    shapes = tiny["lm"].param_shapes()
    # the model's own rules name every leaf: the blocks' (models/
    # lm_blocks.py) and latent attention's, which the blocks' do not
    assert unmatched_leaves(tiny["lm"].declaration().partition_rules,
                            shapes) == {}
    blocks = (lm_blocks.DECODER_PARTITION_RULES
              + lm_blocks.EXPERT_PARTITION_RULES)
    assert {name.rsplit("/", 1)[1] for name in unmatched_leaves(
        blocks, shapes)} == {"q_a", "q_b", "kv_a", "kv_b", "scale", "eh"}
    assert unmatched_leaves(lm_blocks.DECODER_PARTITION_RULES, shapes) != {}


def test_partition_rules_shard_the_expert_axis(devices8):
    mesh = hyperscale_mesh(2, 4, devices8)
    lm = MoELM(**moe_tiny.TINY)
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
        assert spec("mtp", "layer", "moe", "experts", n) == (
            "model", None, None)
    assert spec("layer_01", "moe", "shared", "gate") == (None, "model")
    assert spec("layer_01", "moe", "shared", "down") == ("model", None)
    assert spec("layer_01", "moe", "router") in ((), (None, None))
    assert spec("layer_01", "moe", "router_bias") in ((), (None,))
    assert spec("layer_02", "attn", "q_b") == (None, "model")
    assert spec("layer_02", "attn", "kv_b") == (None, "model")
    assert spec("layer_02", "attn", "o") == ("model", None)
    assert spec("layer_02", "attn", "q_a") in ((), (None, None))
    assert spec("layer_02", "attn", "kv_norm", "scale") in ((), (None,))
    assert spec("layer_00", "mlp", "down") == ("model", None)
    assert spec("mtp", "eh") == (None, "model")
    assert spec("mtp", "hidden_norm", "scale") in ((), (None,))
    assert spec("head", "kernel") == (None, "model")


# ------------------------------------------- (i) through ES, over meshes

def _moe_es(devices, model_shards, **over):
    from estorch_tpu import ES, JaxAgent
    from estorch_tpu.envs import TokenScoreEnv

    kw = dict(
        policy=MoELM, agent=JaxAgent, optimizer=optax.adam,
        population_size=8, sigma=0.02, policy_kwargs=moe_tiny.TINY,
        agent_kwargs={"env": TokenScoreEnv(**moe_tiny.ENV)},
        optimizer_kwargs={"learning_rate": 1e-2}, shard_params=True,
        model_shards=model_shards, low_rank=1, noise_mode="table",
        table_size=1 << 18, device=list(devices))
    kw.update(over)
    return ES(**kw)


class TestThroughTheShardedEngine:
    @pytest.fixture(scope="class")
    def one_device(self, devices8):
        es = _moe_es(devices8[:1], 1)
        offsets = np.asarray(es.engine.all_pair_offsets(es.state))
        records = []
        es.train(2, verbose=False, log_fn=records.append)
        return dict(es=es, fitness=[r["reward_mean"] for r in es.history],
                    params=np.asarray(es.state.params_flat), offsets=offsets,
                    records=records)

    @pytest.mark.parametrize("pop, model", [(2, 4), (1, 4), (2, 2)])
    def test_mesh_shapes_match_one_device(self, one_device, devices8, pop,
                                          model, centre_form):
        """The stacked leaves' expert axis over ``model`` (4 experts over 4
        or 2 devices), the same fitness and parameters as on one device."""
        es = _moe_es(devices8[:pop * model], model)
        assert es.engine.forward_form == "perturbed"
        assert (es.engine.pop_shards, es.engine.model_shards) == (pop, model)
        # both layouts of the centre; nothing to gather on a model axis of 1
        assert es.engine.centre_form == (
            centre_form if model > 1 else "split")
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
        assert es.engine.kernel_facts["attention_form"] == "xla"
        assert [r["env_steps"] for r in es.history] == [8 * 21] * 2
        # about -1.1 log(64): the main head and a tenth of the MTP's
        assert -5.0 < es.history[0]["reward_mean"] < -4.2
        gauges = es.obs.counters
        assert gauges.get("tokens_per_generation") == 8 * 21
        assert gauges.get("experts_held") == 4
        assert gauges.get("experts_total") == 16
        assert gauges.get("experts_per_token") == 3
        assert gauges.get("mtp_depth") == 1
        assert gauges.get("attention_form") == "xla"
        assert gauges.get("attention_form_by_kind") == "causal:xla"
        assert gauges.get("loop_steps", None) is None
        cfg = es.run_manifest()["config"]
        assert (cfg["experts_held"], cfg["experts_total"],
                cfg["experts_per_token"], cfg["mtp_depth"]) == (4, 16, 3, 1)
        for r in one_device["records"]:
            # 8 members x 21 tokens x 3 choices x 3 expert layers, a
            # quarter of the experts held: about 378 pairs land here
            assert 250 < r["routed_pairs"] < 520
            assert 1.0 <= r["expert_load_max_over_mean"] < 2.0

    def test_a_model_without_experts_records_no_load(self, devices8):
        import loop_tiny
        from estorch_tpu.envs import TokenScoreEnv

        es = _moe_es(devices8[:1], 1, policy=LoopedLM,
                     policy_kwargs=loop_tiny.TINY,
                     agent_kwargs={"env": TokenScoreEnv(**loop_tiny.ENV)})
        records = []
        es.train(1, verbose=False, log_fn=records.append)
        assert "routed_pairs" not in records[0]
        assert es.obs.counters.get("experts_held", None) is None
        assert "experts_held" not in es.run_manifest()["config"]

    def test_the_reference_scores_the_engines_members(self, ref, devices8):
        """Generation 0 of the engine against the reference through the
        keying contract the benchmark's runner relies on: same table, same
        offsets, same keys, both signs of every pair."""
        es = _moe_es(devices8[:1], 1, sigma=0.05)
        s = ref.sizes(moe_tiny.config(rank=1))
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
        es = _moe_es(devices8[:1], 1, compute_dtype="bfloat16")
        eng = es.engine
        dtypes = dict(zip(eng.leaf_paths, eng._leaf_dtypes))
        assert dtypes["layer_01/moe/router"] == jnp.float32
        assert dtypes["mtp/layer/moe/router_bias"] == jnp.float32
        assert dtypes["layer_01/moe/experts/gate"] == jnp.bfloat16
        assert dtypes["head/kernel"] == jnp.bfloat16
        es.train(1, verbose=False)
        assert np.isfinite(es.history[0]["reward_mean"])


class TestChunkRule:
    def test_a_stacked_leaf_counts_the_rows_routed_to_it(self, devices8,
                                                         monkeypatch):
        """The widest activation: a stacked expert leaf sees ``top_k x
        held / total`` of the positions (with the layer's margin), not
        every position, and its width is whole on every device."""
        es = _moe_es(devices8[:1], 1)
        eng = es.engine
        per_token = 3 * lm_blocks.EXPERT_CAPACITY_MARGIN / 4
        assert eng.policy.leaf_rows_per_token == dict.fromkeys(
            MoELM(**moe_tiny.TINY).stacked_leaves, per_token)
        assert eng.policy.leaf_rows == {"head/kernel": 8}
        # horizon 21: kv_b 21 x 56 = 1176; experts/down ceil(21 x .9375) x 32
        assert eng._widest_activation() == 21 * 56
        uncut = _moe_es(devices8[:1], 1, policy_kwargs={
            **moe_tiny.TINY, "n_routed_experts": 16, "expert_group_size": 1,
            "expert_group_rank": 0})
        # every position, top_k times over: ceil(21 x 3.75) x 32 = 2528
        assert uncut.engine._widest_activation() == 79 * 32
        # the centre gathered: whole members, as on one device; the centre
        # split (a chip with no room for it): ``model`` divides kv_b's
        # width and the stacked leaves' rows are then the most
        from estorch_tpu.parallel import sharded

        whole = _moe_es(devices8[:4], 4)
        assert whole.engine.centre_form == "gathered"
        assert whole.engine._widest_activation() == 21 * 56
        monkeypatch.setattr(sharded, "CHIP_MEMORY_BYTES", 0)
        split = _moe_es(devices8[:4], 4)
        assert split.engine.centre_form == "split"
        assert split.engine._widest_activation() == 20 * 32

    def test_a_name_that_is_no_leaf_is_refused(self, devices8):
        class Wrong(MoELM):
            float32_leaves = ("layer_09/moe/router",)

        with pytest.raises(ValueError, match="names no leaf"):
            _moe_es(devices8[:1], 1, policy=Wrong)
