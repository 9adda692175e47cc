"""CCAMoELM (models/cca_moe_lm.py) against the plain reference the benchmark
judges its cell by (benchmark/reference/cca_moe_lm.py): float32, ``highest``,
Python loops over layers, heads, taps and the held experts, both convolutions
as explicit shifted sums, one full masked softmax per head, an ``argmax`` of
its own, every perturbed leaf (and matrix of a stacked leaf) materialised;
and each piece of the compressed latent's mixing against its formula."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.flatten_util import ravel_pytree

import cca_moe_tiny as tiny_model
from estorch_tpu.models import CCAMoELM, lm_blocks
from estorch_tpu.models.perturbed import (lowrank_spec_for,
                                          perturbed_headwise_dense)
from estorch_tpu.ops.pallas_attention import (attention_facts,
                                              attention_form_why,
                                              kernel_scope)
from estorch_tpu.ops.pallas_combine import combine_facts
from estorch_tpu.ops.pallas_head import head_facts
from estorch_tpu.ops.pallas_scan import scan_facts
from estorch_tpu.parallel.mesh import (DEFAULT_PARTITION_RULES,
                                       hyperscale_mesh, match_partition_rules,
                                       unmatched_leaves)

# the models here are tiny (heads of 8, sequences of 16): inside a
# ``kernel_scope`` their attention calls take the kernel all the same
# (conftest.py::tiny_widths fakes the call's own rule,
# ``pallas_attention.fits``, which the interpreter does not need)
pytestmark = pytest.mark.usefixtures("tiny_widths")

# float32 on both sides; what differs is the ORDER of float32 sums (blocked
# softmax against whole, grouped and head-wise matmuls against loops) on
# values of magnitude 1: measured 2e-6.  1e-4 would still catch bfloat16
TOL = 1e-4
TINY = tiny_model.TINY


@pytest.fixture(scope="module")
def ref():
    return tiny_model.reference()


def _spread(ref, cfg, key):
    """Seeded weights with every matrix ten times its initial spread (the
    router's last one fifty times: the token's part of a logit then exceeds
    the part every token shares, and each expert gets some), every bias (the
    selection bias among them) 0.1 wide, γ and the temperatures spread
    about one: logits, routes, the carried state and every bias matter."""
    s = ref.sizes(cfg)
    theta = np.array(ref.init_theta(key, cfg))
    rng = np.random.default_rng(0)
    for path, (off, shape) in ref.param_offsets(s).items():
        name, n = path.rsplit("/", 1)[-1], math.prod(shape)
        if name == "__dim__":
            continue
        if ref.is_bias(name):
            theta[off:off + n] = 0.1 * rng.normal(size=n)
        elif name in ("router_state", "temperature"):
            theta[off:off + n] = 1.0 + 0.3 * rng.normal(size=n)
        elif name != "scale":
            theta[off:off + n] *= 50.0 if name == "w3" else 10.0
    return jnp.asarray(theta)


def _built(ref, rank=2, **policy):
    cfg = tiny_model.config(rank=rank, policy=policy)
    lm = CCAMoELM(**{**TINY, **policy})
    theta = _spread(ref, cfg, jax.random.PRNGKey(3))
    shapes = lm.param_shapes()
    _, unravel = ravel_pytree(jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, x.dtype), shapes))
    spec = lowrank_spec_for(lm, shapes, rank)
    noise = jax.random.normal(jax.random.PRNGKey(5), (spec.noise_dim,))
    return dict(cfg=cfg, s=ref.sizes(cfg), lm=lm, theta=theta,
                unravel=unravel, params=unravel(theta), spec=spec,
                noise=noise)


@pytest.fixture(scope="module")
def tiny(ref):
    return _built(ref)


def _tokens(length, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (length,), 0, 64)


def _held_load(chosen, first=2, held=2):
    flat = np.concatenate([np.asarray(c).reshape(-1) for c in chosen])
    return [int((flat == first + k).sum()) for k in range(held)]


# -------------------------------------------- (a) against the reference

@pytest.mark.parametrize("length", [21, 16, 5])
@pytest.mark.parametrize("sign", [0.0, 1.0, -1.0])
@pytest.mark.parametrize("rank", [1, 2])
def test_the_forward_matches_the_reference(ref, sign, length, rank):
    """Scores, the behaviour vector and the tokens that landed on the held
    experts: the centre (sign 0) and both members of a pair from ONE factor
    read, at rank 1 and 2; three layers, so the router's state crosses two
    layer boundaries."""
    built = _built(ref, rank=rank)
    tokens, c = _tokens(length, length), 0.05 * sign
    noise = None if sign == 0.0 else built["spec"].unpack(built["noise"])
    member = ref.Member(built["s"], built["theta"],
                        None if sign == 0.0 else built["noise"], c)
    want = ref.forward(built["s"], member, tokens, head_block=8,
                       with_choices=True)
    got = built["lm"].perturbed_apply(built["params"], noise, c, tokens)
    for g, w, shape in zip(got[:2], want[:2], [(length - 1,), (64,)]):
        assert g.shape == w.shape == shape and g.dtype == jnp.float32
        np.testing.assert_allclose(g, w, atol=TOL, rtol=0)
    np.testing.assert_array_equal(got[2], _held_load(want[2]))
    assert got[2].dtype == jnp.int32 and got[2].shape == (2,)
    assert 0 < int(got[2].sum()) <= 3 * length
    assert float(jnp.abs(want[1]).max()) > 0.5      # the logits spread
    if sign:
        centre = ref.forward(built["s"], ref.Member(
            built["s"], built["theta"], None, 0.0), tokens, head_block=8)
        assert float(jnp.abs(want[0] - centre[0]).max()) > 0.05


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, TOL),
                                        (jnp.bfloat16, 0.25)])
@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_both_forms_and_both_dtypes_match_the_reference(ref, tiny, form,
                                                        dtype, tol):
    """A perturbed member in the XLA form and in the attention kernel under
    the interpreter, in float32 and in bfloat16 (the copy the engine's
    forward reads: the whole router float32): the reference's scores and
    behaviour to the dtype's rounding (bfloat16: a token that picks another
    expert than the reference replaces its whole routed output, so the
    MEAN difference is held, of scores that spread over 1).  32 positions in
    blocks of 8: the kernel runs 4 x 4 tiles a layer."""
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
        assert program.count("jaxpr=causal_attention") == 3
    else:
        got = forward(params, factors)
    for g, w in zip(got[:2], want):
        assert g.dtype == jnp.float32 and bool(jnp.isfinite(g).all())
        if dtype == jnp.float32:
            np.testing.assert_allclose(g, w, atol=tol, rtol=0)
        else:
            assert float(jnp.mean(jnp.abs(g - w))) < tol
            assert float(jnp.std(w)) > 0.3


def test_apply_is_the_centre_alone(tiny):
    tokens = _tokens(21)
    got = tiny["lm"].apply({"params": tiny["params"]}, tokens)
    want = tiny["lm"].perturbed_apply(tiny["params"], None, 0.0, tokens)
    assert len(got) == 3
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
    assert got[2].shape == (3, 2, 2)
    for i in range(3):
        for j in range(2):
            want = member(rows[i], signs[j])
            for g, w in zip(got[:2], want[:2]):
                np.testing.assert_allclose(g[i, j], w, atol=1e-5, rtol=0)
            np.testing.assert_array_equal(got[2][i, j], want[2])


# ------------------------------------- (b) each piece against its formula

def _shifted(x, back):
    """numpy: ``x`` read ``back`` positions before, zeros first."""
    out = np.zeros_like(x)
    if back == 0:
        return x.copy()
    out[back:] = x[:-back]
    return out


@pytest.mark.parametrize("taps", [1, 2, 3])
def test_the_depthwise_convolution_is_the_shifted_sum(taps):
    """``z¹_t = Σ_j a_j ⊙ z_{t-(K-1-j)} + b``: the LAST tap is the current
    position's, zeros before the sequence."""
    rng = np.random.default_rng(taps)
    x = rng.normal(size=(9, 12)).astype(np.float32)
    a = rng.normal(size=(taps, 1, 12)).astype(np.float32)
    b = rng.normal(size=(12,)).astype(np.float32)
    want = b + sum(a[j, 0] * _shifted(x, taps - 1 - j) for j in range(taps))
    got = lm_blocks.causal_conv(jnp.asarray(x), jnp.asarray(a),
                                jnp.asarray(b))
    np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.parametrize("noise", ["none", "factored", "dense"])
@pytest.mark.parametrize("taps", [1, 2, 3])
def test_the_head_mixing_convolution_is_the_shifted_sum(taps, noise):
    """``z²_t[h] = Σ_j z¹_{t-(K-1-j)}[h] · (C_j[h] + c·E_j[h]) + b[h]``, the
    stack tap-major; a factor pair a (tap, head) matrix, or dense noise."""
    rng = np.random.default_rng(10 + taps)
    t, heads, d, c = 7, 3, 4, 0.3
    x = rng.normal(size=(t, heads, d)).astype(np.float32)
    w = rng.normal(size=(taps * heads, d, d)).astype(np.float32)
    b = rng.normal(size=(heads * d,)).astype(np.float32)
    e = np.zeros_like(w)
    handed = None
    if noise == "factored":
        fa = rng.normal(size=(taps * heads, d, 2)).astype(np.float32)
        fb = rng.normal(size=(taps * heads, d, 2)).astype(np.float32)
        e = np.einsum("kmr,knr->kmn", fa, fb) / math.sqrt(2)
        handed = (jnp.asarray(fa), jnp.asarray(fb))
    elif noise == "dense":
        e = rng.normal(size=w.shape).astype(np.float32)
        handed = jnp.asarray(e)
    want = np.tile(b.reshape(1, heads, d), (t, 1, 1))
    for j in range(taps):
        for h in range(heads):
            want[:, h] += _shifted(x[:, h], taps - 1 - j) @ (
                w[j * heads + h] + c * e[j * heads + h])
    got = lm_blocks.head_conv(jnp.asarray(x), jnp.asarray(w), handed, c,
                              jnp.asarray(b), taps)
    assert got.shape == (t, heads, d) and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, atol=2e-5)
    if noise != "none":
        plain = lm_blocks.head_conv(jnp.asarray(x), jnp.asarray(w), None,
                                    0.0, jnp.asarray(b), taps)
        assert float(jnp.abs(got - plain).max()) > 0.1


def test_the_headwise_product_shares_the_centre_under_vmap():
    """Members under a ``vmap`` read ONE stack: the centre's product is not
    batched (a batched stack would be one copy a member)."""
    x = jnp.ones((2, 5, 3, 4))
    w = jnp.ones((3, 4, 4))
    a, b = jnp.ones((2, 3, 4, 1)), jnp.ones((2, 3, 4, 1))
    text = str(jax.make_jaxpr(jax.vmap(
        lambda xs, fa, fb: perturbed_headwise_dense(xs, w, (fa, fb), 0.1)))(
            x, a, b))
    assert "f32[2,3,4,4]" not in text


@pytest.mark.parametrize("nkv", [2, 4])
def test_the_value_shift_reads_the_last_half_of_the_heads_one_back(nkv):
    v = jax.random.normal(jax.random.PRNGKey(nkv), (6, nkv, 3))
    got = np.asarray(lm_blocks.value_shift(v))
    half = nkv // 2
    np.testing.assert_array_equal(got[:, :half], v[:, :half])
    np.testing.assert_array_equal(got[1:, half:], v[:-1, half:])
    assert not got[0, half:].any()          # u_{-1} = 0


@pytest.mark.parametrize("nq, nkv", [(8, 2), (4, 4), (6, 2)])
def test_the_mean_pairs_a_query_head_with_its_groups_key_head(nq, nkv):
    k = jax.random.split(jax.random.PRNGKey(nq), 4)
    q, q_b = (jax.random.normal(k[i], (5, nq, 3)) for i in (0, 1))
    kk, k_b = (jax.random.normal(k[i], (5, nkv, 3)) for i in (2, 3))
    got_q, got_k = lm_blocks.qk_mean(q, kk, q_b, k_b)
    group = nq // nkv
    for i in range(nq):
        np.testing.assert_allclose(
            got_q[:, i], q[:, i] + 0.5 * (q_b[:, i] + k_b[:, i // group]),
            atol=1e-6)
    for g in range(nkv):
        mean = sum(q_b[:, g * group + i] for i in range(group)) / group
        np.testing.assert_allclose(
            got_k[:, g], kk[:, g] + 0.5 * (mean + k_b[:, g]), atol=1e-6)


L2_CASES = {
    "no temperature: the norm is sqrt(width)": (1.0, None),
    "a temperature a head": (1.0, [0.5, 2.0]),
    "any input norm gives the same output": (1e3, [0.5, 2.0]),
}


@pytest.mark.parametrize("case", list(L2_CASES))
def test_the_l2_scale_is_sqrt_width_over_the_norm(case):
    size, tau = L2_CASES[case]
    x = size * jax.random.normal(jax.random.PRNGKey(0), (6, 2, 16))
    scale = 1.0 if tau is None else jnp.asarray(tau)[:, None]
    got = lm_blocks.l2_scale(x, scale, 0.0)
    want = math.sqrt(16) * x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    if tau is not None:
        want = want * jnp.asarray(tau)[None, :, None]
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # scores of two scaled vectors are bounded: width x tau x cos
    other = lm_blocks.l2_scale(jnp.flip(x, axis=0), 1.0, 0.0)
    cos = jnp.sum(got * other, axis=-1) / 16.0
    bound = 1.0 if tau is None else jnp.asarray(tau)[None, :]
    assert bool((jnp.abs(cos) <= bound * (1 + 1e-5)).all())


def test_a_zero_vector_stays_zero_under_the_eps():
    got = lm_blocks.l2_scale(jnp.zeros((2, 1, 8)), 1.0, 1e-5)
    assert bool(jnp.isfinite(got).all()) and not bool(got.any())


@pytest.mark.parametrize("rotary_dim", [4, 8, 12])
def test_a_leading_slice_of_each_head_is_rotated(rotary_dim):
    """The first ``rotary_dim`` of 16 channels turn in the halves convention
    INSIDE the slice by ``theta^(-2i/rotary_dim)``; the rest stay."""
    t, theta = 9, 100.0
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(0), (t, 3, 16)))
    cos, sin = lm_blocks.rotary_tables(t, rotary_dim, theta)
    got = np.asarray(lm_blocks.rotate(jnp.asarray(x), cos, sin,
                                      rotary_dim=rotary_dim))
    half = rotary_dim // 2
    want = x.copy()
    for p in range(t):
        for i in range(half):
            angle = p * theta ** (-2.0 * i / rotary_dim)
            lo, hi = x[p, :, i], x[p, :, i + half]
            want[p, :, i] = lo * math.cos(angle) - hi * math.sin(angle)
            want[p, :, i + half] = hi * math.cos(angle) + lo * math.sin(angle)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_array_equal(got[..., rotary_dim:], x[..., rotary_dim:])
    # an orthogonal map: every head keeps its norm
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)


@pytest.mark.parametrize("interleaved", [False, True])
def test_a_whole_head_rotation_is_todays_program(interleaved):
    """``rotary_dim`` ``None`` or the head's width: the jaxpr and the values
    of a call without the argument."""
    x = jax.random.normal(jax.random.PRNGKey(0), (9, 3, 16))
    cos, sin = lm_blocks.rotary_tables(9, 16, 100.0)

    def today(x):
        return lm_blocks.rotate(x, cos, sin, interleaved)

    def whole(x):
        return lm_blocks.rotate(x, cos, sin, interleaved, rotary_dim=16)

    def none(x):
        return lm_blocks.rotate(x, cos, sin, interleaved, rotary_dim=None)

    assert (str(jax.make_jaxpr(today)(x)) == str(jax.make_jaxpr(whole)(x))
            == str(jax.make_jaxpr(none)(x)))
    np.testing.assert_array_equal(today(x), whole(x))


def _router_leaves(key, hidden=8, width=6, experts=4, bias=0.0):
    k = jax.random.split(key, 10)

    def normal(i, shape, std=1.0):
        return std * jax.random.normal(k[i], shape)

    return {"router_down": normal(0, (hidden, width)),
            "router_down_bias": normal(1, (width,), 0.1),
            "router_state": 1.0 + normal(2, (width,), 0.3),
            "router_norm": {"scale": 1.0 + normal(3, (width,), 0.1)},
            "router_mlp": {"w1": normal(4, (width, width)),
                           "b1": normal(5, (width,), 0.1),
                           "w2": normal(6, (width, width)),
                           "b2": normal(7, (width,), 0.1),
                           "w3": normal(8, (width, experts)),
                           "b3": normal(9, (experts,), 0.1)},
            "router_bias": jnp.full((experts,), bias)}


def _router_formula(p, u, below):
    from jax.scipy.special import erf

    def gelu(x):
        return 0.5 * x * (1.0 + erf(x / math.sqrt(2.0)))

    with jax.default_matmul_precision("highest"):
        r = u @ p["router_down"] + p["router_down_bias"]
        r = r + p["router_state"] * below
        x = r * jax.lax.rsqrt(jnp.mean(r * r, -1, keepdims=True) + 1e-5)
        x = x * p["router_norm"]["scale"]
        m = p["router_mlp"]
        x = gelu(x @ m["w1"] + m["b1"])
        x = gelu(x @ m["w2"] + m["b2"])
        return x @ m["w3"] + m["b3"], r


@pytest.mark.parametrize("state", ["none below", "a state below",
                                   "gamma zero"])
def test_the_router_with_and_without_the_state_from_below(state):
    """``r = u W_dn + b + γ ⊙ below`` through the norm and the three-matrix
    erf-GELU MLP; ``r`` handed on AFTER γ's term; γ = 0 makes the layer's
    route a function of its own input alone."""
    p = _router_leaves(jax.random.PRNGKey(0))
    u = jax.random.normal(jax.random.PRNGKey(1), (5, 8))
    below = jax.random.normal(jax.random.PRNGKey(2), (5, 6))
    if state == "none below":
        below = jnp.zeros_like(below)
    if state == "gamma zero":
        p = {**p, "router_state": jnp.zeros((6,))}
    got, handed = lm_blocks.state_router(p, None, 0.0, u, below, 1e-5)
    want, want_r = _router_formula(p, u, below)
    np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(handed, want_r, atol=1e-5)
    alone, _ = lm_blocks.state_router(p, None, 0.0, u, jnp.zeros_like(below),
                                      1e-5)
    moved = float(jnp.abs(got - alone).max())
    assert (moved > 1e-3) == (state == "a state below")


# logits over 4 experts (their softmax: 0.6439 0.2369 0.0871 0.0321), a
# selection bias, what to renormalise -> the chosen expert and its weight
TOP1_ROUTES = {
    "the largest probability, kept as it is":
        ([2.0, 1.0, 0.0, -1.0], [0.0] * 4, False, 0, 0.6439),
    "the bias moves the choice and not the weight":
        ([2.0, 1.0, 0.0, -1.0], [0.0, 0.5, 0.0, 0.0], False, 1, 0.2369),
    "a renormalised weight is one whatever the router says":
        ([2.0, 1.0, 0.0, -1.0], [0.0] * 4, True, 0, 1.0),
    "ties go to the lower index":
        ([1.0, 1.0, 1.0, 1.0], [0.0] * 4, False, 0, 0.25),
    "a bias that ties goes to the lower index too":
        ([0.0, 0.0, 0.0, 0.0], [0.0, 0.1, 0.1, 0.0], False, 1, 0.25),
}


@pytest.mark.parametrize("case", list(TOP1_ROUTES))
def test_top1_routing_case_by_case(ref, case):
    logits, bias, renormalise, want_e, want_w = TOP1_ROUTES[case]
    p = {"router_bias": jnp.asarray(bias)}
    e, w = lm_blocks.route(p, None, 0.0, jnp.zeros((1, 2)), top_k=1,
                           scaling=1.0, scoring="softmax",
                           logits=jnp.asarray([logits]),
                           renormalise=renormalise)
    assert e.shape == w.shape == (1, 1) and int(e[0, 0]) == want_e
    np.testing.assert_allclose(float(w[0, 0]), want_w, atol=1e-4)
    # the reference chooses alike (it never renormalises)
    prob = jax.nn.softmax(jnp.asarray([logits]))
    chosen = jnp.argmax(prob + jnp.asarray(bias), axis=-1)
    assert int(chosen[0]) == want_e


def test_a_softmax_router_without_a_bias_leaf_is_asked_none():
    """``route`` as the softmax-routed model calls it (no ``router_bias`` in
    the tree) reads no bias; with the leaf, under the same scoring, it does:
    ONE place."""
    u = jax.random.normal(jax.random.PRNGKey(0), (5, 3))
    p = {"router": jax.random.normal(jax.random.PRNGKey(1), (3, 4))}
    kw = dict(top_k=2, scaling=1.0, scoring="softmax")
    e0, w0 = lm_blocks.route(p, None, 0.0, u, **kw)
    e1, w1 = lm_blocks.route({**p, "router_bias": jnp.zeros((4,))}, None,
                             0.0, u, **kw)
    np.testing.assert_array_equal(e0, e1)
    np.testing.assert_array_equal(w0, w1)
    pushed = jnp.asarray([0.0, 0.0, 0.0, 9.0])
    e2, _ = lm_blocks.route({**p, "router_bias": pushed}, None, 0.0, u, **kw)
    assert bool((e2[:, 0] == 3).all())
    assert "router_bias" not in str(jax.make_jaxpr(
        lambda q: lm_blocks.route(q, None, 0.0, u, **kw))(p))


# --------------------------------------------------------- (c) causality

@pytest.mark.parametrize("t", [4, 11, 17])
def test_positions_after_t_leave_everything_up_to_t_bit_identical(tiny, t):
    """The taps, ``u_{t-1}`` in v, the mask and the router's state reach
    BACK only: new tokens after position ``t`` leave the scores of the
    positions before it as they were, bit for bit (score ``i`` reads the
    tokens up to ``i + 1``)."""
    lm = tiny["lm"]
    tokens = _tokens(21, 3)
    other = tokens.at[t + 1:].set((tokens[t + 1:] + 7) % 64)
    assert int((tokens != other).sum()) == 20 - t
    factors = tiny["spec"].unpack(tiny["noise"])
    forward = jax.jit(lambda tok: lm.perturbed_apply(
        tiny["params"], factors, 0.05, tok)[0])
    got, want = forward(other), forward(tokens)
    np.testing.assert_array_equal(got[:t], want[:t])
    assert float(jnp.abs(got[t:] - want[t:]).max()) > 1e-3


def test_each_mixing_piece_reaches_one_position_back(tiny):
    """A change at position ``t`` of the latent's input moves the mixed q̂
    and k̂ at ``t``, ``t + 1`` and ``t + 2`` (two taps twice over) and
    nowhere before."""
    lm = tiny["lm"]
    p = tiny["params"]["layer_01"]["attn"]
    k = jax.random.split(jax.random.PRNGKey(0), 2)
    q_b = jax.random.normal(k[0], (12, 8, 8))
    k_b = jax.random.normal(k[1], (12, 2, 8))
    q0, k0 = lm._mixed(p, None, 0.0, q_b, k_b)
    q1, k1 = lm._mixed(p, None, 0.0, q_b.at[5].add(1.0), k_b.at[5].add(1.0))
    moved = np.asarray(jnp.abs(q1 - q0).max(axis=(1, 2)) > 0)
    assert moved.tolist() == [False] * 5 + [True] * 3 + [False] * 4
    moved = np.asarray(jnp.abs(k1 - k0).max(axis=(1, 2)) > 0)
    assert moved.tolist() == [False] * 5 + [True] * 3 + [False] * 4


# ------------------------- (d) the reduction to grouped-query attention

@pytest.mark.parametrize("tau", [(1.0, 1.0), (0.5, 2.0)])
def test_identity_convolutions_and_no_mean_are_grouped_query_attention(
        ref, tiny, tau, monkeypatch):
    """With ``C_j`` the identity at the last tap and zero elsewhere, ``a``
    likewise, zero biases and the mean term removed, ``cca`` is
    grouped-query attention on L2-scaled q and k: a plain masked softmax a
    head (the half rotation and the value shift are part of both)."""
    lm, d, t = tiny["lm"], 8, 13
    p = dict(tiny["params"]["layer_00"]["attn"])
    heads = lm.latent_heads
    p["conv_time"] = jnp.zeros_like(p["conv_time"]).at[-1].set(1.0)
    p["conv_head"] = jnp.concatenate([
        jnp.zeros(((lm.cca_time1 - 1) * heads, d, d)),
        jnp.tile(jnp.eye(d)[None], (heads, 1, 1))])
    p["conv_time_bias"] = jnp.zeros_like(p["conv_time_bias"])
    p["conv_head_bias"] = jnp.zeros_like(p["conv_head_bias"])
    p["temperature"] = jnp.asarray(tau)
    monkeypatch.setattr(lm_blocks, "qk_mean",
                        lambda q, k, q_before, k_before: (q, k))
    u = jax.random.normal(jax.random.PRNGKey(4), (t, 32))
    rotary = lm_blocks.rotary_tables(t, lm.rotary_dim, lm.rope_theta)
    got = lm._attention(p, None, 0.0, u, rotary)

    def unit(x):
        return math.sqrt(d) * x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    with jax.default_matmul_precision("highest"):
        q = unit((u @ p["q"]).reshape(t, 8, d))
        k = unit((u @ p["k"]).reshape(t, 2, d)) * jnp.asarray(
            tau)[None, :, None]
        v = (u @ p["v"]).reshape(t, 2, d)
        v = v.at[:, 1].set(jnp.concatenate([jnp.zeros((1, d)), v[:-1, 1]]))
        cos, sin = ref.rotary(lm.rope_theta, lm.rotary_dim, t)
        mask = jnp.tril(jnp.ones((t, t), bool))
        ctx = []
        for h in range(8):
            q_h = ref.rotate_leading(q[:, h], cos, sin)
            k_h = ref.rotate_leading(k[:, h // 4], cos, sin)
            s = jnp.where(mask, q_h @ k_h.T / math.sqrt(d), -jnp.inf)
            ctx.append(jax.nn.softmax(s, axis=-1) @ v[:, h // 4])
        want = jnp.concatenate(ctx, axis=-1) @ p["o"]
    # (the eps under the root: 1e-5 of a mean square of order 100)
    np.testing.assert_allclose(got, want, atol=1e-4)


# ------------------------------------------- (e) the shares add up

def test_the_two_shares_add_up_to_the_uncut_layer(ref):
    """model-configs section 4: 4 tiny experts over 2 shares.  What both
    shares compute alike (the router, its state) counted once, the two
    partial results of one expert layer equal the uncut reference's layer
    (and the uncut system's), and every token lands exactly once."""
    shares = [_built(ref, num_experts=2, expert_group_size=2,
                     expert_group_rank=r) for r in range(2)]
    whole = _built(ref, num_experts=4, expert_group_size=1,
                   expert_group_rank=0)
    s, base = whole["s"], "layer_00"
    u = jax.random.normal(jax.random.PRNGKey(2), (21, 32))
    below = jax.random.normal(jax.random.PRNGKey(3), (21, 16))
    member = ref.Member(s, whole["theta"], None, 0.0)
    want, chosen, want_state = ref.moe_ffn(
        s, member.layer(base), member.experts_of(base), u, below)
    p = whole["params"][base]["moe"]

    def held(lm, first, count):
        moe = {**p, "experts": {n: p["experts"][n][first:first + count]
                                for n in ("gate", "up", "down")}}
        return lm._routed(moe, None, 0.0, u, below, jnp.float32)

    parts = [held(c["lm"], 2 * r, 2) for r, c in enumerate(shares)]
    np.testing.assert_allclose(sum(y for y, _, _ in parts), want, atol=TOL,
                               rtol=0)
    uncut, state, load = held(whole["lm"], 0, 4)
    np.testing.assert_allclose(uncut, want, atol=TOL, rtol=0)
    np.testing.assert_allclose(state, want_state, atol=TOL, rtol=0)
    for _, share_state, _ in parts:                 # computed alike
        np.testing.assert_array_equal(share_state, state)
    np.testing.assert_array_equal(
        np.concatenate([l for _, _, l in parts]), load)
    np.testing.assert_array_equal(
        load, [int((chosen == k).sum()) for k in range(4)])
    assert int(load.sum()) == 21                    # ONE expert a token
    # a share alone is NOT the layer: its other tokens get nothing
    assert float(jnp.abs(parts[0][0] - want).max()) > 0.01
    assert [c["lm"].first_expert_held for c in shares] == [0, 2]
    assert all(c["lm"].experts_total == 4 for c in shares)


# --------------------------- (f) every leaf's and every matrix's correction

LEAVES = [path for path, _ in tiny_model.reference().system_layout(
    tiny_model.reference().sizes(tiny_model.config(rank=2)))
    if not path.startswith(("layer_00", "layer_02"))]
CASES = ([(p, None) for p in LEAVES
          if "/experts/" not in p and "/conv_head" not in p
          or p.endswith("bias")]
         + [(p, k) for p in LEAVES if "/experts/" in p for k in range(2)]
         + [(p, k) for p in LEAVES if p.endswith("/conv_head")
            for k in (0, 9, 10, 19)])


@pytest.fixture(scope="module")
def one_leaf_programs(tiny):
    lm, spec = tiny["lm"], tiny["spec"]
    perturbed = jax.jit(
        lambda p, n, c, t: lm.perturbed_apply(p, spec.unpack(n), c, t))
    plain = jax.jit(lambda p, t: lm.perturbed_apply(p, None, 0.0, t))
    return perturbed, plain


@pytest.mark.parametrize("path, matrix", CASES)
def test_a_leafs_correction_is_the_materialised_sum(ref, tiny,
                                                    one_leaf_programs, path,
                                                    matrix):
    """Noise on ONE leaf (one EXPERT, one (tap, head) MATRIX of the
    head-mixing convolution; the router's matrices, γ, the temperatures, the
    tied table read at the lookup and at the head among them): the perturbed
    forward equals the plain forward of the materialised ``theta + c·E``,
    the routes it makes included."""
    perturbed, plain = one_leaf_programs
    s, spec, c = tiny["s"], tiny["spec"], 0.3
    entry = ref.noise_layout(s)[path]
    shape = ref.param_offsets(s)[path][1]
    noise = np.zeros((spec.noise_dim,), np.float32)
    full = np.asarray(tiny["noise"])
    if entry[0] == "stacked":
        _, m, n = shape
        for off, width in ((entry[1], m * 2), (entry[2], n * 2)):
            at = off + matrix * width
            noise[at:at + width] = full[at:at + width]
    else:
        n = sum(shape) * 2 if entry[0] == "lr" else math.prod(shape)
        noise[entry[1]:entry[1] + n] = full[entry[1]:entry[1] + n]
    noise, tokens = jnp.asarray(noise), _tokens(21, 2)
    member = ref.Member(s, tiny["theta"], noise, c)
    flat = jnp.concatenate([
        (jnp.stack([member.matrix(p, k) for k in range(shp[0])])
         if ref.noise_layout(s)[p][0] == "stacked"
         else member.leaf(p)).reshape(-1)
        for p, shp in ref.system_layout(s)])
    got = perturbed(tiny["params"], noise, jnp.float32(c), tokens)
    want = plain(tiny["unravel"](flat), tokens)
    centre = plain(tiny["params"], tokens)
    for g, w in zip(got[:2], want[:2]):
        np.testing.assert_allclose(g, w, atol=TOL, rtol=0)
    np.testing.assert_array_equal(got[2], want[2])
    moved = max(float(jnp.abs(w - x).max())
                for w, x in zip(want[:2], centre[:2]))
    if matrix is not None and "/experts/" in path:
        # an expert no token of this sequence chose moves nothing
        chosen = ref.forward(s, ref.Member(s, tiny["theta"], None, 0.0),
                             tokens, head_block=8, with_choices=True)[2][1]
        if not bool((chosen == 2 + matrix).any()):
            assert moved == 0.0
            return
    if path.endswith("router_bias"):
        # (0.3 of noise on β reaches no choice of these peaked routers; a
        # bias that moves one is test_top1_routing_case_by_case's)
        return
    assert moved > 1e-5, (path, matrix, moved)


def test_the_first_layers_state_scale_multiplies_zeros(ref, tiny):
    """``r_{-1} = 0``: layer 0's γ is a parameter that moves nothing."""
    s = tiny["s"]
    theta = np.array(tiny["theta"])
    off, shape = ref.param_offsets(s)["layer_00/moe/router_state"]
    theta[off:off + shape[0]] += 5.0
    tokens = _tokens(21, 2)
    got = tiny["lm"].perturbed_apply(tiny["unravel"](jnp.asarray(theta)),
                                     None, 0.0, tokens)
    want = tiny["lm"].perturbed_apply(tiny["params"], None, 0.0, tokens)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


# -------------------------------------------- (g) sizes, init, validation

@pytest.mark.parametrize("bad, match", [
    ({"num_experts_per_tok": 2}, "num_experts_per_tok = 2 is not written"),
    ({"sliding_window": 4096}, "sliding_window = 4096 is not written"),
    ({"tie_word_embeddings": False}, "tie_word_embeddings = False is not"),
    ({"attention_bias": True}, "not written"),
    ({"lm_head_bias": True}, "not written"),
    ({"layer_types": ("hybrid", "hybrid_sliding")}, "every layer"),
    ({"layer_types": ()}, "every layer"),
    ({"num_key_value_heads": 3}, "multiple of key heads"),
    ({"num_attention_heads": 3, "num_key_value_heads": 3}, "must be even"),
    ({"partial_rotary_factor": 0.1}, "turns pairs"),
    ({"partial_rotary_factor": 0.4}, "turns pairs"),
    ({"partial_rotary_factor": 1.5}, "turns pairs"),
    ({"cca_time0": 0}, "count taps"),
    ({"cca_time1": 0}, "count taps"),
    ({"expert_group_rank": 2}, "shares"),
    ({"behaviour_positions": 0}, "behaviour_positions"),
])
def test_sizes_are_validated(bad, match):
    with pytest.raises(ValueError, match=match):
        CCAMoELM(**{**TINY, **bad})


def test_a_whole_head_may_be_rotated():
    assert CCAMoELM(**{**TINY, "partial_rotary_factor": 1.0}).rotary_dim == 8


def test_init_draws_the_declared_tree(tiny):
    lm = tiny["lm"]
    params = lm.init(jax.random.PRNGKey(0), None)["params"]
    shapes = lm.param_shapes()
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(shapes))
    for got, want in zip(jax.tree_util.tree_leaves(params),
                         jax.tree_util.tree_leaves(shapes)):
        assert got.shape == want.shape and got.dtype == jnp.float32
    assert "head" not in params                     # tied
    layer = params["layer_01"]
    attn, moe = layer["attn"], layer["moe"]
    for ones in (attn["temperature"], moe["router_state"],
                 moe["router_norm"]["scale"], layer["norm1"]["scale"]):
        assert np.all(np.asarray(ones) == 1.0)
    for zeros in (attn["conv_time_bias"], attn["conv_head_bias"],
                  moe["router_down_bias"], moe["router_bias"],
                  moe["router_mlp"]["b1"], moe["router_mlp"]["b3"]):
        assert np.all(np.asarray(zeros) == 0.0)
    assert 0.01 < float(moe["experts"]["gate"].std()) < 0.03
    assert 0.01 < float(attn["conv_head"].std()) < 0.03
    assert attn["conv_time"].shape == (2, 1, 80)
    assert attn["conv_head"].shape == (20, 8, 8)
    assert attn["q"].shape == (32, 64) and attn["v"].shape == (32, 16)
    assert moe["router_mlp"]["w3"].shape == (16, 4)
    assert moe["experts"]["down"].shape == (2, 16, 32)


def test_the_declaration_names_leaves_the_tree_has(tiny):
    """Every path the declaration states is a leaf of ``param_shapes()``,
    of the shape its field means."""
    lm = tiny["lm"]
    shapes = {"/".join(str(k.key) for k in p): tuple(x.shape) for p, x in
              jax.tree_util.tree_flatten_with_path(lm.param_shapes())[0]}
    stated = lm.declaration()
    assert set(stated.stacked_leaves) <= set(shapes)
    assert all(len(shapes[p]) == 3 for p in stated.stacked_leaves)
    assert len(stated.stacked_leaves) == 3 * (3 + 1)
    assert set(stated.leaf_rows_per_token) == set(lm.expert_leaves)
    # ONE expert a token, two shares, the layer's margin
    assert set(stated.leaf_rows_per_token.values()) == {1.25 / 2}
    assert set(stated.float32_leaves) <= set(shapes)
    assert set(stated.float32_leaves) == {
        p for p in shapes if "/moe/router" in p}
    assert len(stated.float32_leaves) == 3 * 11
    assert stated.dense_noise_leaves == () and stated.leaf_rows == {}
    # heads of 8 over 2 key heads, one kind of layer and no band; the
    # head and the combine at the hidden width; no scan
    kernels = dict(stated.kernels)
    assert (kernels[attention_facts], kernels[head_facts],
            kernels[combine_facts]) == ((8, 2, None, 8), (32,), (32,))
    assert scan_facts not in kernels and stated.selection_bytes is None
    assert stated.outputs == ("expert_load",)
    assert stated.facts == {
        "experts_held": 2, "experts_total": 4, "experts_per_token": 1,
        "mtp_depth": 0, "latent_q_width": 64, "latent_kv_width": 16,
        "conv_taps": 4, "router_hidden": 16}
    assert dataclasses.is_dataclass(stated)


def test_published_sizes_and_layouts(ref):
    """The configuration file: the parameter count recomputed from the built
    tree, the published count from the published keys, the reference's
    layouts equal to the system's tree and noise spec, no leaf left to the
    catch-all partition rule."""
    cfg = tiny_model.published()
    about = ref.describe(cfg)
    layers = cfg["num_hidden_layers"]
    attention = 2 * 2048 * 1024 + 2 * 2048 * 256
    convolutions = 2 * 1280 + 2 * 10 * 128 * 128 + 2 * 1280
    router = (2048 * 256 + 256 + 256 + 256 + 2 * (256 * 256 + 256)
              + 256 * 16 + 16 + 16)
    outside = attention + convolutions + 2 + 2 * 2048 + router
    assert (attention, convolutions, router, outside) == (
        5_242_880, 332_800, 660_768, 6_240_546)
    per_layer = cfg["published"]["per_layer_parameters"]
    assert (per_layer["attention"], per_layer["convolutions"],
            per_layer["router"], per_layer["one_expert"]) == (
        attention, convolutions, router, 3 * 2048 * 2048)
    expert = 3 * 2048 * 2048
    want = layers * (outside + 8 * expert) + 32784 * 2048 + 2048
    assert about["param_dim"] == want
    assert want == {5: 601_662_890, 4: 494_759_048}[layers]
    assert cfg["deployment"]["state_bytes_per_chip"] == 14 * want
    # the card's "8.3B" outside the embedding and "A0.76B" active
    assert 40 * (outside + 16 * expert) == 8_302_685_520
    assert 40 * (outside + expert) == 752_938_320
    assert "8,302,685,520" in cfg["published"]["parameters"]
    assert "752,938,320" in cfg["published"]["parameters"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    published = cfg["published"]
    assert (published["num_hidden_layers"], published["num_experts"],
            published["vocab_size"]) == (40, 16, 262272)
    assert cfg["layer_types"] == ["hybrid"] * 40
    assert 262272 // 8 == cfg["vocab_size"] == 32784
    kwargs = cfg["build"]["kwargs"]["policy_kwargs"]
    assert cfg["layer_types"][:layers] == kwargs["layer_types"]
    lm = CCAMoELM(**kwargs)
    assert (lm.experts_total, lm.num_experts_per_tok, lm.first_expert_held,
            lm.num_attention_heads, lm.num_key_value_heads, lm.head_dim,
            lm.rotary_dim, lm.latent_heads, lm.router_hidden_size,
            lm.moe_intermediate_size) == (16, 1, 0, 8, 2, 128, 64, 10, 256,
                                          2048)
    # every published key the module has a field for holds what it builds
    fields = dataclasses.asdict(lm)
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "moe_intermediate_size", "num_experts_per_tok",
                "cca_time0", "cca_time1", "partial_rotary_factor",
                "router_hidden_size", "rms_norm_eps", "attention_bias",
                "lm_head_bias", "tie_word_embeddings", "sliding_window",
                "vocab_size", "num_experts", "expert_group_size",
                "behaviour_positions", "rope_theta"):
        assert fields[key] == cfg[key], key
    hybrid = cfg["rope_parameters"]["hybrid"]
    assert (hybrid["rope_theta"], hybrid["partial_rotary_factor"]) == (
        lm.rope_theta, lm.partial_rotary_factor)
    stated = lm.declaration()
    kernels = dict(stated.kernels)
    widths, kv_heads, _, query_heads = kernels[attention_facts]
    assert (widths, kernels[head_facts], query_heads) == (128, (2048,), 8)
    assert attention_form_why("tpu", 1, widths, cfg["horizon"], None,
                              kv_heads)[0] == "kernel"
    shapes = lm.param_shapes()
    paths = ["/".join(str(k.key) for k in p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    s = ref.sizes(cfg)
    assert ([(p, tuple(x.shape)) for p, x in
             zip(paths, jax.tree_util.tree_leaves(shapes))]
            == ref.system_layout(s))
    spec = lowrank_spec_for(lm, shapes, 1)
    layout = ref.noise_layout(s)
    assert spec.noise_dim == layout["__dim__"] == about["noise_dim"]
    for i, m, n, a_off, b_off in spec.lr_leaves:
        assert layout[paths[i]] == ("lr", a_off, b_off)
    for i, e, m, n, a_off, b_off in spec.stacked_leaves:
        assert layout[paths[i]] == ("stacked", a_off, b_off)
        assert e in (8, 20)
    for i, _, _, off in spec.dense_leaves:
        assert layout[paths[i]] == ("dense", off)
    assert len(spec.stacked_leaves) == 4 * layers
    factored = {paths[i].rsplit("/", 1)[1] for i, *_ in spec.lr_leaves}
    assert {"router_down", "w1", "w2", "w3", "embedding", "q", "o"} <= factored
    dense = {paths[i].rsplit("/", 1)[1] for i, *_ in spec.dense_leaves}
    assert dense == {"scale", "conv_time", "conv_time_bias", "conv_head_bias",
                     "temperature", "router_down_bias", "router_state", "b1",
                     "b2", "b3", "router_bias"}
    assert unmatched_leaves(stated.partition_rules, shapes) == {}
    assert about["expert_flops_per_member_step"] == int(
        layers * 1 * 8 / 16 * 2 * 3 * 2048 * 2048)
    assert about["dense_flops_per_member_step"] == layers * 2 * attention
    assert about["mix_flops_per_member_step"] == layers * 2 * 20 * 128 * 128
    assert about["head_flops_per_member_step"] == 2 * 2048 * 32784
    # the seeded weights the cell's limits were measured under
    assert set(cfg["seeded_std"]) >= {"embedding", "w1", "w2", "w3", "other"}
    assert set(cfg["seeded_scale"]) == {"final_norm/scale",
                                        "router_norm/scale"}
    assert set(cfg["seeded_orthogonal"]) == {"router_down", "w1", "w2", "w3"}


def test_no_leaf_falls_to_the_catch_all(tiny):
    shapes = tiny["lm"].param_shapes()
    # the model's own rules name every leaf: the blocks' (models/
    # lm_blocks.py) and the latent's and its router's, which they do not
    own = tiny["lm"].declaration().partition_rules
    assert unmatched_leaves(own, shapes) == {}
    assert own[:len(lm_blocks.DECODER_PARTITION_RULES)] == (
        lm_blocks.DECODER_PARTITION_RULES)
    assert unmatched_leaves(
        lm_blocks.DECODER_PARTITION_RULES + lm_blocks.EXPERT_PARTITION_RULES,
        shapes) != {}


@pytest.mark.parametrize("pop, model", [(2, 4), (1, 2)])
def test_partition_rules_name_the_new_leaves(devices8, pop, model):
    mesh = hyperscale_mesh(pop, model, devices8[:pop * model])
    lm = CCAMoELM(**{**TINY, "num_experts": 4})
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
    # the stacked convolution's (tap, head) axis, 20 matrices
    assert spec("layer_01", "attn", "conv_head") == ("model", None, None)
    assert spec("layer_00", "attn", "q") == (None, "model")
    assert spec("layer_00", "attn", "k") == (None, "model")
    assert spec("layer_00", "attn", "o") == ("model", None)
    assert spec("embed", "embedding") == ("model", None)
    for name in ("conv_time", "conv_time_bias", "conv_head_bias",
                 "temperature"):
        assert not any(spec("layer_00", "attn", name)), name
    for name in ("router_down", "router_down_bias", "router_state",
                 "router_bias"):
        assert not any(spec("layer_02", "moe", name)), name
    assert not any(spec("layer_02", "moe", "router_norm", "scale"))
    for name in ("w1", "b1", "w2", "b2", "w3", "b3"):
        assert not any(spec("layer_02", "moe", "router_mlp", name)), name


# ------------------------------------------- (h) through ES, over meshes

def _es(devices, model_shards, **over):
    from estorch_tpu import ES, JaxAgent
    from estorch_tpu.envs import TokenScoreEnv

    kw = dict(
        policy=CCAMoELM, agent=JaxAgent, optimizer=optax.adam,
        population_size=8, sigma=0.02, policy_kwargs=TINY,
        agent_kwargs={"env": TokenScoreEnv(**tiny_model.ENV)},
        optimizer_kwargs={"learning_rate": 1e-2}, shard_params=True,
        model_shards=model_shards, low_rank=1, noise_mode="table",
        table_size=1 << 18, device=list(devices))
    kw.update(over)
    return ES(**kw)


# four experts held of eight, so that a ``model`` axis of 4 divides them
MESHED = {**TINY, "num_experts": 4}


class TestThroughTheShardedEngine:
    @pytest.fixture(scope="class")
    def one_device(self, devices8):
        es = _es(devices8[:1], 1, policy_kwargs=MESHED)
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
        virtual meshes as on one device, in the XLA form: the stacked
        experts' axis and the stacked convolution's over ``model``."""
        es = _es(devices8[:pop * model], model, policy_kwargs=MESHED)
        assert es.engine.forward_form == "perturbed"
        assert (es.engine.pop_shards, es.engine.model_shards) == (pop, model)
        assert es.engine.centre_form == (
            centre_form if model > 1 else "split")
        assert es.engine.kernel_facts["attention_form"] == "xla"
        report = es.engine.sharding_report()
        assert report["layer_01/moe/experts/gate"].startswith(
            "PartitionSpec('model'")
        assert report["layer_01/attn/conv_head"].startswith(
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
                gauges.get("mtp_depth")) == (4, 8, 1, 0)
        assert (gauges.get("latent_q_width"), gauges.get("latent_kv_width"),
                gauges.get("conv_taps"),
                gauges.get("router_hidden")) == (64, 16, 4, 16)
        assert gauges.get("attention_form_by_kind") == "causal:xla"
        cfg = es.run_manifest()["config"]
        assert (cfg["latent_q_width"], cfg["latent_kv_width"],
                cfg["conv_taps"], cfg["router_hidden"],
                cfg["experts_per_token"]) == (64, 16, 4, 16, 1)
        for r in one_device["records"]:
            # 8 members x 21 tokens x 3 layers, ONE expert each, about
            # half of them held
            assert 0 < r["routed_pairs"] < 8 * 21 * 3
            assert "selected_pairs" not in r
            assert 1.0 <= r["expert_load_max_over_mean"] <= 2.0

    def test_an_uncut_run_routes_every_token_of_every_layer_once(self,
                                                                 devices8):
        """All 4 experts held: the records' routed pairs are members x
        layers x T exactly, generation after generation."""
        uncut = {**TINY, "num_experts": 4, "expert_group_size": 1,
                 "expert_group_rank": 0}
        es = _es(devices8[:1], 1, policy_kwargs=uncut)
        records = []
        es.train(2, verbose=False, log_fn=records.append)
        assert [r["routed_pairs"] for r in records] == [8 * 3 * 21] * 2

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

    def test_the_centre_copy_keeps_the_whole_router_float32(self, devices8):
        es = _es(devices8[:1], 1, compute_dtype="bfloat16")
        eng = es.engine
        dtypes = dict(zip(eng.leaf_paths, eng._leaf_dtypes))
        for name in ("router_down", "router_down_bias", "router_state",
                     "router_norm/scale", "router_mlp/w1", "router_mlp/b3",
                     "router_bias"):
            assert dtypes[f"layer_01/moe/{name}"] == jnp.float32, name
        for name in ("attn/q", "attn/conv_head", "attn/conv_time",
                     "attn/temperature", "moe/experts/gate", "norm1/scale"):
            assert dtypes[f"layer_01/{name}"] == jnp.bfloat16, name
        assert dtypes["embed/embedding"] == jnp.bfloat16
        es.train(1, verbose=False)
        assert np.isfinite(es.history[0]["reward_mean"])

    @pytest.mark.parametrize("dtype, tol", [("float32", 1e-4),
                                            ("bfloat16", 5e-2)])
    def test_forced_kernel_runs_the_generation_the_xla_form_runs(
            self, devices8, kernel_attention, dtype, tol):
        """The generation program on one device, the engine's scope open
        around its trace: the latent's attention takes the kernel (one
        ``pallas_call`` a layer) under the scale ``1/√d`` with the
        temperature folded into k, and the members' fitness is the XLA
        form's to the order of float32 sums."""
        from estorch_tpu.envs import TokenScoreEnv

        wide = {**TINY, "attention_block": 16}
        env = {"env": TokenScoreEnv(**{**tiny_model.ENV, "seq_len": 32})}
        ref_es = _es(devices8[:1], 1, compute_dtype=dtype,
                     policy_kwargs=wide, agent_kwargs=env)
        with kernel_attention():
            kern = _es(devices8[:1], 1, compute_dtype=dtype,
                       policy_kwargs=wide, agent_kwargs=env)
        assert (ref_es.engine.kernel_facts["attention_form"],
                kern.engine.kernel_facts["attention_form"]) == (
                    "xla", "kernel")
        assert kern.engine.kernel_facts["attention_form_by_kind"] == (
            "causal:kernel")
        programs = [str(jax.make_jaxpr(es.engine._generation_step)(
            es.state, es.table.data)) for es in (ref_es, kern)]
        assert [text.count("jaxpr=causal_attention")
                for text in programs] == [0, 3]
        ref_es.state, want = ref_es.engine.generation_step(ref_es.state)
        kern.state, got = kern.engine.generation_step(kern.state)
        np.testing.assert_allclose(got["fitness"], want["fitness"], atol=tol)
        assert np.isfinite(np.asarray(got["fitness"])).all()
