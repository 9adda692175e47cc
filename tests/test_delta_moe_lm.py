"""DeltaMoELM (models/delta_moe_lm.py) against the plain reference the
benchmark judges its cell by (benchmark/reference/delta_moe_lm.py): float32,
``highest``, the delta rule ONE position after the other, Python loops over
layers and over the held experts, one full masked softmax per head, the
rotation written from the formula, every perturbed leaf (and expert)
materialised, routes of its own."""

import dataclasses
import hashlib
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.flatten_util import ravel_pytree

import delta_moe_tiny as tiny_model
from pallas_costs import pallas_calls
from estorch_tpu.models import DeltaMoELM, lm_blocks
from estorch_tpu.models.delta_moe_lm import (gated_delta_rule,
                                             unit_lower_inverse)
from estorch_tpu.ops import pallas_attention
from estorch_tpu.ops.lowrank import make_lowrank_tree_spec
from estorch_tpu.ops.pallas_attention import (attention_facts,
                                              attention_form_why,
                                              kernel_scope)
from estorch_tpu.ops.pallas_combine import combine_facts
from estorch_tpu.ops.pallas_delta import delta_facts
from estorch_tpu.ops.pallas_head import head_facts
from estorch_tpu.ops.pallas_scan import scan_facts
from estorch_tpu.parallel.mesh import (DEFAULT_PARTITION_RULES,
                                       hyperscale_mesh, match_partition_rules,
                                       unmatched_leaves)

# the degraded forms the cell's reference check has to refuse
sys.path.insert(0, os.path.join(tiny_model.ROOT, "benchmark", "rehearse"))
import coarse_gdn  # noqa: E402

# float32 on both sides; what differs is the ORDER of float32 sums (chunks
# against single steps, blocked softmax against whole, grouped matmul
# against a masked loop) on values of magnitude 1: measured 2e-5.  1e-4
# would still catch bfloat16 anywhere
TOL = 1e-4
TINY = tiny_model.TINY
PERIOD = ("linear", "linear", "linear", "full")
# leaves whose seeded value is a constant: tests move them off it
CONSTANT = ("scale", "norm_scale", "A_log", "dt_bias")


@pytest.fixture(scope="module")
def ref():
    return tiny_model.reference()


def _spread(ref, cfg, key):
    """Seeded weights with every matrix ten times its initial spread, so
    that logits, scores and routes all matter, and the norm weights,
    ``A_log`` and ``dt_bias`` 0.1 wide about their seeded values: at those a
    ``(1 + w)`` read as ``w`` or a bias left out could hide."""
    s = ref.sizes(cfg)
    theta = np.array(ref.init_theta(key, cfg))
    rng = np.random.default_rng(0)
    for path, (off, shape) in ref.param_offsets(s).items():
        name, n = path.rsplit("/", 1)[-1], math.prod(shape)
        if name == "__dim__":
            continue
        if name in CONSTANT:
            theta[off:off + n] += 0.1 * rng.standard_normal(n)
        else:
            theta[off:off + n] *= 10.0
    return jnp.asarray(theta)


def _built(ref, rank=2, **policy):
    cfg = tiny_model.config(rank=rank, policy=policy)
    lm = DeltaMoELM(**{**TINY, **policy})
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


@pytest.fixture(scope="module")
def pair_of_layers(ref):
    """A linear and a full layer, the reference's outputs for one perturbed
    member and the honest forward's."""
    built = _built(ref, layer_types=("linear", "full"))
    tokens, c = _tokens(21, 3), jnp.float32(0.05)
    want = ref.forward(built["s"], ref.Member(
        built["s"], built["theta"], built["noise"], c), tokens, head_block=8)
    return {**built, "tokens": tokens, "c": c, "want": want}


def _forward_of(lm, case):
    return jax.jit(lm.perturbed_apply)(
        case["params"], case["spec"].unpack(case["noise"]), case["c"],
        case["tokens"])


@dataclasses.dataclass(frozen=True)
class Tapped(DeltaMoELM):
    """The honest model, which also hands out every layer's output and the
    routes it took (read after an un-jitted call)."""

    def _layer(self, *a):
        x, load = DeltaMoELM._layer(self, *a)
        TAPS["layers"].append(x)
        return x, load

    def _routed(self, moe, noise, c, b, dtype):
        experts, _ = lm_blocks.route(
            moe, noise, c, b, top_k=self.num_experts_per_tok, scaling=1.0,
            scoring="softmax")
        TAPS["routes"].append(experts)
        return DeltaMoELM._routed(self, moe, noise, c, b, dtype)


TAPS = {"layers": [], "routes": []}


def _tapped(lm, params, noise, c, tokens):
    """``(outputs, every layer's output, every layer's routes)`` of ONE
    jitted program (the taps are traced values, handed out as outputs)."""
    tapped = Tapped(**dataclasses.asdict(lm))

    @jax.jit
    def program(params, noise, c, tokens):
        TAPS["layers"], TAPS["routes"] = [], []
        out = tapped.perturbed_apply(params, noise, c, tokens)
        return out, list(TAPS["layers"]), list(TAPS["routes"])

    return program(params, noise, jnp.float32(c), tokens)


# -------------------------------------------- (a) against the reference

@pytest.mark.parametrize("sign, length", [
    (0.0, 21), (1.0, 21), (-1.0, 21), (1.0, 16), (-1.0, 5)])
def test_the_forward_matches_the_reference(ref, tiny, sign, length):
    """Scores, the behaviour vector, EVERY layer's output, the routes and
    the pairs that landed on the held experts: the centre (sign 0) and both
    members of a pair from ONE factor read; 21 positions are two whole
    chunks of 8 and a short one, 16 two whole ones, 5 less than one."""
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
    assert len(layers) == len(want[3]) == 4
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


@pytest.mark.parametrize("kinds, chunk", [
    (("linear",), 8), (("linear",), 64), (("full",), 8), (PERIOD, 64),
    (("full", "linear"), 8)], ids=lambda k: (
        "-".join(k) if isinstance(k, tuple) else str(k)))
def test_both_kinds_of_layer_alone_and_in_the_published_period(ref, kinds,
                                                               chunk):
    """A stack of one kind, the published period of four and the other
    order, in chunks shorter and longer than the sequence: the reference's
    scores, behaviour and every layer's output."""
    built = _built(ref, layer_types=kinds, delta_chunk=chunk)
    tokens = _tokens(21, 4)
    member = ref.Member(built["s"], built["theta"], built["noise"], 0.05)
    want = ref.forward(built["s"], member, tokens, head_block=8,
                       with_layers=True)
    got, layers, _ = _tapped(built["lm"], built["params"],
                             built["spec"].unpack(built["noise"]), 0.05,
                             tokens)
    for g, w in zip(list(got[:2]) + layers, list(want[:2]) + want[2]):
        np.testing.assert_allclose(g, w, atol=TOL, rtol=0)


@pytest.fixture(scope="module")
def wide(ref):
    """A linear and a full layer at a hidden width of 128 over 128
    positions with heads of 128, where the kernels' shapes fit, and the
    reference's outputs for one perturbed member."""
    built = _built(ref, hidden_size=128, head_dim=128, num_attention_heads=2,
                   num_key_value_heads=1, layer_types=("linear", "full"),
                   behaviour_positions=16, attention_block=64, head_block=64,
                   delta_chunk=64)
    tokens = _tokens(128, 7)
    want = ref.forward(built["s"], ref.Member(
        built["s"], built["theta"], built["noise"], 0.05), tokens,
        head_block=64)
    return {**built, "tokens": tokens, "want": want}


@pytest.mark.parametrize("form, dtype, tol", [
    ("kernel", jnp.float32, 10 * TOL), ("xla", jnp.bfloat16, 0.1)])
def test_both_forms_and_both_dtypes_match_the_reference(wide, form, dtype,
                                                        tol):
    """A perturbed member inside a kernel scope under the interpreter in
    float32 (the full layer takes the attention kernel) and in the XLA form in
    bfloat16 (the copy the engine's forward reads: routers, ``A_log`` and
    ``dt_bias`` float32): the reference's scores and behaviour to the
    dtype's rounding."""
    lm, tokens, c = wide["lm"], wide["tokens"], 0.05
    keep = set(lm.float32_leaves)
    paths = ["/".join(str(k.key) for k in p) for p, _ in
             jax.tree_util.tree_flatten_with_path(wide["params"])[0]]
    leaves, treedef = jax.tree_util.tree_flatten(wide["params"])
    params = jax.tree_util.tree_unflatten(treedef, [
        x if path in keep else x.astype(dtype)
        for x, path in zip(leaves, paths)])

    def forward(p, f):
        return lm.perturbed_apply(p, f, c, tokens)

    factors = wide["spec"].unpack(wide["noise"])
    if form == "kernel":
        with kernel_scope(interpret=True):
            kernels = pallas_calls(forward, params, factors)
            got = jax.jit(forward)(params, factors)
        # the full layer's attention (128 positions are less than a tile of
        # the head's and of the combine's kernels, which their own tests
        # hold)
        assert len(kernels) == 1
        assert kernels[0].params["name"] == "causal_attention"
    else:
        got = jax.jit(forward)(params, factors)
    for g, w in zip(got[:2], wide["want"]):
        assert g.dtype == jnp.float32 and bool(jnp.isfinite(g).all())
        if dtype == jnp.float32:
            np.testing.assert_allclose(g, w, atol=tol, rtol=0)
        else:
            assert float(jnp.mean(jnp.abs(g - w))) < tol
            assert float(jnp.std(w)) > 0.3


def test_apply_is_the_centre_alone(tiny):
    """flax's ``apply`` is the perturbed forward without noise: the same
    program, operation for operation."""
    lm, tokens = tiny["lm"], _tokens(21)
    got = jax.make_jaxpr(lambda p: lm.apply({"params": p}, tokens))(
        tiny["params"])
    want = jax.make_jaxpr(
        lambda p: lm.perturbed_apply(p, None, 0.0, tokens))(tiny["params"])
    assert str(got) == str(want)


def test_members_under_vmap_are_their_own_evaluations(pair_of_layers):
    """Two signs of one factor read under a ``vmap``, as the engine
    evaluates a pair: each is its own evaluation."""
    case = pair_of_layers
    lm, spec, tokens = case["lm"], case["spec"], case["tokens"]
    factors = spec.unpack(case["noise"])
    signs = jnp.asarray([0.05, -0.05])
    got = jax.jit(jax.vmap(
        lambda c: lm.perturbed_apply(case["params"], factors, c, tokens)))(
            signs)
    assert got[2].shape == (2, 4)
    want = _forward_of(lm, case)            # the member at +0.05
    for g, w in zip(got[:2], want[:2]):
        np.testing.assert_allclose(g[0], w, atol=2e-5, rtol=0)
        assert float(jnp.abs(g[0] - g[1]).max()) > 1e-3
    np.testing.assert_array_equal(got[2][0], want[2])


# --------------------------- (b) the chunked rule against single steps

def _rule_inputs(length, decay, seed=0, nk=2, nv=4, dk=8, dv=8):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = DeltaMoELM._unit
    q = unit(jax.random.normal(k[0], (length, nk, dk))) / math.sqrt(dk)
    key = unit(jax.random.normal(k[1], (length, nk, dk)))
    v = jax.random.normal(k[2], (length, nv, dv))
    # a step's decay about ``decay``, a head's and a position's own
    g = math.log(decay) * jnp.exp(0.3 * jax.random.normal(k[3], (length, nv)))
    beta = jax.nn.sigmoid(jax.random.normal(k[4], (length, nv)))
    return q, key, v, g, beta


@pytest.fixture(scope="module")
def rules(ref):
    return (jax.jit(gated_delta_rule, static_argnums=5),
            jax.jit(ref.delta_recurrence))


@pytest.mark.parametrize("length, chunk", [
    (128, 64), (100, 64), (7, 64), (1, 64), (21, 8), (30, 5)])
@pytest.mark.parametrize("decay", [0.5, 0.9, 0.999, 0.9999])
def test_the_chunked_rule_is_the_step_recurrence(rules, length, chunk, decay):
    """Lengths that are whole chunks, that end in a short one and that are
    shorter than one; chunks the blocked inverse takes (8 · 2^k) and one it
    hands the product whole (5); a step's decay from 0.5 (the state forgets
    within a few positions) to 0.9999 (nothing forgotten over the
    sequence)."""
    chunked, stepwise = rules
    q, k, v, g, beta = _rule_inputs(length, decay, seed=length)
    want = stepwise(q, k, v, g, beta)
    got = chunked(q, k, v, g, beta, chunk)
    assert got.shape == want.shape == (length, 4, 8)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)
    assert length < 3 or float(jnp.abs(want).max()) > 0.05


def test_the_correction_and_the_decay_both_matter(rules):
    """The delta rule is neither plain decayed linear attention nor the
    ungated delta rule: each differs from it by far more than rounding, and
    with the same key twice the second write REPLACES what the first left
    (``beta = 1``, no decay: the state holds the second value alone)."""
    chunked, _ = rules
    uncorrected = jax.jit(lambda *xs: coarse_gdn.recurrence(
        *xs, corrected=False))
    q, k, v, g, beta = _rule_inputs(64, 0.9)
    honest = chunked(q, k, v, g, beta, 8)
    plain = uncorrected(q, k, v, g, beta)
    ungated = chunked(q, k, v, jnp.zeros_like(g), beta, 8)
    assert float(jnp.abs(honest - plain).max()) > 0.01
    assert float(jnp.abs(honest - ungated).max()) > 0.01
    key = jnp.broadcast_to(DeltaMoELM._unit(jnp.ones((1, 1, 8))), (2, 1, 8))
    values = jnp.stack([jnp.full((1, 8), 3.0), jnp.full((1, 8), -1.0)])
    out = chunked(key, key, values, jnp.zeros((2, 1)), jnp.ones((2, 1)), 8)
    np.testing.assert_allclose(out[1], values[1], atol=1e-5)
    summed = uncorrected(key, key, values, jnp.zeros((2, 1)),
                         jnp.ones((2, 1)))
    np.testing.assert_allclose(summed[1], values[0] + values[1], atol=1e-5)


@pytest.mark.parametrize("n", [1, 2, 5, 8, 16, 24, 32, 64])
def test_the_inverse_is_the_triangular_solve(n):
    """``unit_lower_inverse`` against ``solve_triangular`` of the identity:
    sizes the blocked form takes (16, 32, 64), its base (8) and ones it
    hands the finite product whole."""
    from jax.scipy.linalg import solve_triangular

    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(n), (3, n, n)), -1)
    eye = jnp.eye(n)
    want = jnp.stack([solve_triangular(eye + m, eye, lower=True,
                                       unit_diagonal=True) for m in a])
    got = unit_lower_inverse(a)
    np.testing.assert_allclose(got, want, atol=1e-4 * max(
        1.0, float(jnp.abs(want).max())), rtol=0)
    np.testing.assert_allclose(
        jnp.matmul(eye + a, got, precision="highest"),
        jnp.broadcast_to(eye, a.shape), atol=2e-4 * max(
            1.0, float(jnp.abs(want).max())))


def test_the_inverse_survives_keys_that_are_all_alike():
    """Every key the same and ``beta = 1``: ``A`` is all ones below the
    diagonal, whose powers reach 1e17 in a chunk of 64 before they cancel;
    in blocks of 8 merged in pairs the inverse (``1`` on the diagonal,
    ``-1`` below it, 0 elsewhere) comes out to float32 rounding, and the
    finite product over the whole chunk does not."""
    from estorch_tpu.models.delta_moe_lm import _product_inverse

    a = jnp.tril(jnp.ones((64, 64)), -1)
    want = jnp.eye(64) - jnp.eye(64, k=-1)
    np.testing.assert_allclose(unit_lower_inverse(a), want, atol=1e-4)
    assert not float(jnp.abs(_product_inverse(a) - want).max()) < 1.0


# ------------------------------------- (c) each wrong forward is refused

WRONG = {"fp8_inputs": coarse_gdn.Fp8Gdn, "all_bf16": coarse_gdn.AllBf16Gdn,
         "no_correction": coarse_gdn.NoCorrectionGdn,
         "no_decay": coarse_gdn.NoDecayGdn,
         "chunk_reset": coarse_gdn.ChunkResetGdn,
         "no_conv": coarse_gdn.NoConvGdn,
         "no_conv_silu": coarse_gdn.NoConvSiluGdn,
         "no_qk_norm": coarse_gdn.NoQkNormGdn,
         "no_norm_gate": coarse_gdn.NoNormGateGdn,
         "no_output_gate": coarse_gdn.NoOutputGateGdn,
         "whole_head_rotation": coarse_gdn.WholeHeadRotationGdn,
         "no_shared_sigmoid": coarse_gdn.NoSharedSigmoidGdn}


def test_the_honest_forward_passes_the_comparison(pair_of_layers):
    honest = _forward_of(pair_of_layers["lm"], pair_of_layers)
    for h, w in zip(honest[:2], pair_of_layers["want"]):
        np.testing.assert_allclose(h, w, atol=TOL, rtol=0)


@pytest.mark.parametrize("name", list(WRONG))
def test_each_wrong_forward_fails_the_comparison(pair_of_layers, name):
    """In float32, where the honest forward is the reference's to 2e-5: a
    forward in fp8 or with the float32 parts in bfloat16, the correction or
    the decay dropped, the state reset at chunk boundaries, the conv or its
    SiLU left out, q and k not normalised, the gated norm's gate, the
    attention's output gate or the shared expert's sigmoid left out and the
    rotation over the whole head each move the scores AND the behaviour
    vector by thirty times the tolerance and more."""
    honest = pair_of_layers["lm"]
    wrong = WRONG[name](**dataclasses.asdict(honest))
    assert (dataclasses.asdict(wrong) == dataclasses.asdict(honest)
            and wrong.declaration() == honest.declaration())
    got = _forward_of(wrong, pair_of_layers)
    for g, w in zip(got[:2], pair_of_layers["want"]):
        assert float(jnp.abs(g - w).max()) > 30 * TOL, name


# ------------------- (d) the norms and the decay against their formulas

def test_the_zero_centred_norms_read_one_plus_w(tiny):
    """Weights 0.1 wide: ``x rsqrt(mean x² + eps) (1 + w)``, for the block
    norms and for the q/k norms a head; read as ``w`` it is another number
    altogether, and the gated norm's weight is NOT zero-centred."""
    lm = tiny["lm"]
    x = jax.random.normal(jax.random.PRNGKey(0), (21, 32))
    p = tiny["params"]["layer_00"]
    w = p["norm1"]["scale"]
    assert 0.03 < float(jnp.std(w)) < 0.3       # moved off the seeded 0
    bare = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(lm._norm(p, None, 0.0, "norm1", x),
                               bare * (1.0 + w), rtol=1e-6)
    np.testing.assert_allclose(lm_blocks.zero_centred_rmsnorm(x, w, 1e-6),
                               lm_blocks.rmsnorm(x, 1.0 + w, 1e-6))
    assert float(jnp.abs(lm._norm(p, None, 0.0, "norm1", x)
                         - bare * w).max()) > 0.5
    heads = jax.random.normal(jax.random.PRNGKey(1), (21, 4, 16))
    attn = tiny["params"]["layer_03"]["attn"]
    np.testing.assert_allclose(
        lm._norm(attn, None, 0.0, "q_norm", heads),
        heads * jax.lax.rsqrt(jnp.mean(heads * heads, -1, keepdims=True)
                              + 1e-6) * (1.0 + attn["q_norm"]["scale"]),
        rtol=1e-6)
    o = jax.random.normal(jax.random.PRNGKey(2), (21, 4, 8))
    z = jax.random.normal(jax.random.PRNGKey(3), (21, 4, 8))
    scale = p["delta"]["norm_scale"]
    np.testing.assert_allclose(
        lm._gated_norm(o, scale, z),
        o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-6) * scale
        * jax.nn.silu(z), rtol=1e-6)


def test_the_decay_and_the_unit_norm_are_the_formulas(tiny):
    p = tiny["params"]["layer_01"]["delta"]
    a = jax.random.normal(jax.random.PRNGKey(0), (21, 4))
    assert float(jnp.std(p["A_log"])) > 0.03    # moved off the seeded 0
    got = DeltaMoELM._decay(a, p["A_log"], p["dt_bias"])
    want = -jnp.exp(p["A_log"]) * jnp.log1p(jnp.exp(a + p["dt_bias"]))
    np.testing.assert_allclose(got, want, rtol=1e-5)
    assert bool((got < 0).all())
    # without the bias, or with A_log read as A, another decay
    assert float(jnp.abs(got + jnp.exp(p["A_log"]) * jax.nn.softplus(a)
                         ).max()) > 0.1
    assert float(jnp.abs(got + p["A_log"] * jax.nn.softplus(
        a + p["dt_bias"])).max()) > 0.05
    x = jax.random.normal(jax.random.PRNGKey(1), (21, 2, 8))
    np.testing.assert_allclose(
        DeltaMoELM._unit(x),
        x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6), rtol=1e-6)


def test_the_leaves_that_decide_stay_float32(tiny):
    lm = tiny["lm"]
    assert set(lm.float32_leaves) == (
        {f"layer_{i:02d}/moe/router" for i in range(4)}
        | {f"layer_{i:02d}/delta/{n}" for i in range(3)
           for n in ("A_log", "dt_bias")})
    assert lm.declaration().dense_noise_leaves == ()
    spec, shapes = tiny["spec"], lm.param_shapes()
    paths = ["/".join(str(k.key) for k in p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    dense = {paths[i].split("/", 1)[1] for i, *_ in spec.dense_leaves
             if paths[i].startswith("layer_00")}
    # what no matmul reads takes dense noise by the spec's own rule: the
    # conv's taps are 3-D, the one-column gate saves nothing factored
    assert dense == {"delta/A_log", "delta/conv", "delta/dt_bias",
                     "delta/norm_scale", "moe/shared_gate", "norm1/scale",
                     "norm2/scale"}


def test_the_rotation_turns_the_leading_quarter(ref):
    """``partial_rotary_factor`` 0.25 of heads of 16: 4 turn, by the two
    frequencies of a 4-wide rotation, 12 stay; a full layer alone tells the
    order of the earlier tokens apart through them."""
    built = _built(ref, layer_types=("full",))
    lm = built["lm"]
    assert lm.rotary_dim == 4
    cos, sin = lm_blocks.rotary_tables(21, 4, lm.rope_theta)
    want_cos, want_sin = ref.rotary(lm.rope_theta, 4, 21)
    np.testing.assert_allclose(cos, want_cos, atol=1e-6)
    x = jax.random.normal(jax.random.PRNGKey(0), (21, 4, 16))
    got = lm_blocks.rotate(x, cos, sin, rotary_dim=4)
    np.testing.assert_allclose(got, ref.rotate_leading(x, want_cos, want_sin),
                               atol=1e-6)
    np.testing.assert_array_equal(got[..., 4:], x[..., 4:])
    assert float(jnp.abs(got[1:, :, :4] - x[1:, :, :4]).max()) > 0.1


def test_float32_heads_of_256_take_narrower_key_blocks():
    """In float32 a call with heads of two lane blocks takes key blocks of
    512 where the sequence's own block is 1,024 (the (1024, 256) blocks'
    copies pass the v5e's scoped VMEM by 76 KiB: tests/test_trace_stages.py
    compiles both); the context is the XLA form's, and a bfloat16 call and
    a head of 128 keep the sequence's block."""
    from estorch_tpu.ops.pallas_attention import causal_attention

    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k0, (1024, 2 * 256))
    k, v = (jax.random.normal(key, (1024, 256)) for key in (k1, k2))

    def grid(q, k, v, head_dim):
        call, = pallas_calls(lambda q, k, v: causal_attention(
            q, k, v, num_heads=q.shape[1] // head_dim, num_kv_heads=1,
            head_dim=head_dim, scale=head_dim ** -0.5, interpret=True),
            q, k, v)
        return call.params["grid_mapping"].grid

    assert grid(q, k, v, 256)[-1] == 2 * grid(q, k, v, 256)[-2] == 2
    half = jnp.bfloat16
    assert grid(q.astype(half), k.astype(half), v.astype(half), 256)[-1] == 1
    assert grid(q, k[:, :128], v[:, :128], 128)[-1] == 1
    got = causal_attention(q, k, v, num_heads=2, num_kv_heads=1,
                           head_dim=256, scale=1 / 16, interpret=True)
    want = lm_blocks.attention_core(q, k, v, num_heads=2, num_kv_heads=1,
                                    scale=1 / 16, block=256)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


# ------------------------------------------- (e) the shares add up

def test_the_sixteen_shares_add_up_to_the_uncut_layer(ref):
    """model-configs section 4: 32 tiny experts over 16 shares (the cell's
    0-31, 32-63, ... 480-511 of 512 in small), each through
    ``routed_experts`` under routes taken from ANOTHER state than the one
    the experts read; the sixteen partial results and the shared expert
    COUNTED ONCE equal the uncut reference's layer (and the uncut
    system's)."""
    whole = _built(ref, num_experts=32, expert_group_size=1,
                   expert_group_rank=0)
    s, base = whole["s"], "layer_01"
    a = jax.random.normal(jax.random.PRNGKey(2), (21, 32))
    b = jax.random.normal(jax.random.PRNGKey(3), (21, 32))
    member = ref.Member(s, whole["theta"], None, 0.0)
    layer = member.layer(base, "linear")
    chosen, w = ref.routes(s, layer, a)
    want = (ref.held_experts(s, member.experts_of(base), b, chosen, w)
            + ref.shared_expert(layer, b))
    p = whole["params"][base]["moe"]
    experts, weights = lm_blocks.route(p, None, 0.0, a, top_k=3, scaling=1.0,
                                       scoring="softmax")
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(chosen, -1))

    def held(first, count):
        stack = {n: p["experts"][n][first:first + count]
                 for n in ("gate", "up", "down")}
        return lm_blocks.routed_experts(
            stack, None, 0.0, b, experts, weights, first_held=first,
            total=32)

    shared = whole["lm"]._shared(p, None, 0.0, b)
    parts = [held(2 * r, 2) for r in range(16)]
    np.testing.assert_allclose(sum(y for y, _ in parts) + shared, want,
                               atol=TOL, rtol=0)
    uncut, load = held(0, 32)
    np.testing.assert_allclose(uncut + shared, want, atol=TOL, rtol=0)
    np.testing.assert_array_equal(
        np.concatenate([l for _, l in parts]), load)
    assert int(load.sum()) == 21 * 3                # every pair lands once
    # a share alone is NOT the layer, and the shared expert counted sixteen
    # times is not either
    assert float(jnp.abs(parts[0][0] + shared - want).max()) > 0.01
    assert float(jnp.abs(sum(y for y, _ in parts) + 16 * shared
                         - want).max()) > 0.1
    # the models built as shares hold what the slices hold
    shares = [DeltaMoELM(**{**TINY, "num_experts": 2,
                            "expert_group_size": 16, "expert_group_rank": r})
              for r in range(16)]
    assert [lm.first_expert_held for lm in shares] == list(range(0, 32, 2))
    assert all(lm.experts_total == 32 for lm in shares)
    # the shared expert is scaled by sigmoid(b w_s), one number a token
    gate = jax.nn.sigmoid(b @ p["shared_gate"])
    assert gate.shape == (21, 1)
    plain = (jax.nn.silu(b @ p["shared"]["gate"]) * (b @ p["shared"]["up"])
             ) @ p["shared"]["down"]
    np.testing.assert_allclose(shared, gate * plain, atol=TOL, rtol=0)


# --------------------------- (f) every leaf's and every expert's correction

LEAVES = [path for path, _ in tiny_model.reference().system_layout(
    tiny_model.reference().sizes(tiny_model.config(rank=2)))
    if path.startswith(("layer_00", "layer_03", "embed", "head", "final"))]
CASES = [(p, None) for p in LEAVES if "/experts/" not in p] + [
    (p, 1) for p in LEAVES if "/experts/" in p]


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
    """Noise on ONE leaf of a linear layer, of the full layer or outside
    the layers (one EXPERT of a stacked leaf): the perturbed forward equals
    the plain forward of the materialised ``theta + c·E``, the routes it
    takes included."""
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


# -------------------------------------------- (g) sizes, init, validation

@pytest.mark.parametrize("bad, match", [
    ({"norm_topk_prob": False}, "norm_topk_prob = False is not written"),
    ({"rope_scaling": {"type": "yarn"}}, "not written"),
    ({"tie_word_embeddings": True}, "not written"),
    ({"layer_types": ("linear", "window")}, "a layer is"),
    ({"layer_types": ()}, "a layer is"),
    ({"num_key_value_heads": 3}, "multiple of key heads"),
    ({"linear_num_value_heads": 3}, "multiple of their key heads"),
    ({"partial_rotary_factor": 0.1}, "turns pairs"),
    ({"delta_chunk": 0}, "delta_chunk"),
    ({"expert_group_rank": 4}, "shares"),
    ({"num_experts_per_tok": 17}, "more experts"),
    ({"behaviour_positions": 0}, "behaviour_positions"),
])
def test_sizes_are_validated(bad, match):
    with pytest.raises(ValueError, match=match):
        DeltaMoELM(**{**TINY, **bad})


def test_init_draws_the_declared_tree(tiny):
    lm = tiny["lm"]
    params = lm.init(jax.random.PRNGKey(0), None)["params"]
    shapes = lm.param_shapes()
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(shapes))
    for got, want in zip(jax.tree_util.tree_leaves(params),
                         jax.tree_util.tree_leaves(shapes)):
        assert got.shape == want.shape and got.dtype == jnp.float32
    linear, full = params["layer_01"], params["layer_03"]
    assert np.all(np.asarray(linear["norm1"]["scale"]) == 0.0)
    assert np.all(np.asarray(linear["delta"]["norm_scale"]) == 1.0)
    assert np.all(np.asarray(full["attn"]["q_norm"]["scale"]) == 0.0)
    assert set(linear) == {"norm1", "norm2", "delta", "moe"}
    assert set(full) == {"norm1", "norm2", "attn", "moe"}
    assert set(linear["delta"]) == {"in_proj_qkvz", "in_proj_ba", "conv",
                                    "A_log", "dt_bias", "norm_scale",
                                    "out_proj"}
    assert set(full["attn"]) == {"q", "k", "v", "o", "q_norm", "k_norm"}
    assert set(linear["moe"]) == {"router", "shared", "shared_gate",
                                  "experts"}
    assert linear["delta"]["in_proj_qkvz"].shape == (32, 16 + 16 + 32 + 32)
    assert linear["delta"]["conv"].shape == (4, 1, 64)
    assert full["attn"]["q"].shape == (32, 4 * 2 * 16)      # query and gate
    assert linear["moe"]["router"].shape == (32, 16)
    # a step's decay at a' = 0 lies between 0.9 and 0.999
    decay = np.exp(-np.exp(np.asarray(linear["delta"]["A_log"]))
                   * np.log1p(np.exp(np.asarray(linear["delta"]["dt_bias"]))))
    assert np.all((decay > 0.9) & (decay < 0.9991))


def test_the_declaration(tiny):
    stated = tiny["lm"].declaration()
    # heads of 16 over 2 key heads, one kind of attention layer and no
    # band; the head and the combine at the hidden width; the delta rule's
    # heads of 8 and 8 in chunks of 8; no scan
    kernels = dict(stated.kernels)
    assert (kernels[attention_facts], kernels[head_facts],
            kernels[combine_facts], kernels[delta_facts]) == (
        (16, 2, None, 4), (32,), (32,), (8, 8, 8))
    assert scan_facts not in kernels
    assert stated.leaf_rows == {"head/kernel": 8}
    assert stated.leaf_rows_per_token == dict.fromkeys(
        tiny["lm"].stacked_leaves, 3 * 1.25 / 4)
    assert len(stated.stacked_leaves) == 12 and stated.outputs == (
        "expert_load",)
    facts = dict(stated.facts)
    assert "finite product" in facts.pop("delta_inverse")
    assert facts == {
        "experts_held": 4, "experts_total": 16, "experts_per_token": 3,
        "mtp_depth": 0, "linear_layers": 3, "full_layers": 1,
        "delta_chunk": 8}
    # a stack without a full layer states no attention at all
    alone = dataclasses.replace(tiny["lm"], layer_types=("linear",))
    assert attention_facts not in dict(alone.declaration().kernels)
    assert delta_facts in dict(alone.declaration().kernels)


def test_published_sizes_and_layouts(ref):
    """The configuration file: the parameter count recomputed from the built
    tree, the published count from the published keys, the layer kinds from
    ``full_attention_interval``, the reference's layouts equal to the
    system's tree and noise spec, no leaf left to the catch-all partition
    rule, what the engine's rules read."""
    cfg = tiny_model.published()
    about = ref.describe(cfg)
    layers = cfg["num_hidden_layers"]
    delta = (2048 * 12288 + 2048 * 64 + 4 * 8192 + 32 + 32 + 128
             + 4096 * 2048)
    full = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 256
    expert = 3 * 2048 * 512
    outside = 2048 * 512 + expert + 2048
    assert (delta, full, outside + 32 * expert) == (
        33_718_464, 27_263_488, 104_859_648)
    held = outside + 32 * expert + 4096
    want = 3 * (delta + held) + (full + held) + 2 * 18992 * 2048 + 2048
    assert about["param_dim"] == want == 625_667_136
    assert cfg["deployment"]["state_bytes_per_chip"] == 14 * want == (
        8_759_339_904)
    whole = outside + 512 * expert + 4096
    assert (36 * (delta + whole) + 12 * (full + whole) + 2 * 151936 * 2048
            + 2048) == 79_674_391_296
    published = cfg["published"]
    assert "79,674,391,296" in published["parameters"]
    assert cfg["reduced"] == ["num_hidden_layers", "num_experts",
                              "vocab_size"]
    assert (published["num_hidden_layers"], published["num_experts"],
            published["vocab_size"]) == (48, 512, 151936)
    assert 8 * cfg["vocab_size"] == 151936 and 16 * cfg["num_experts"] == 512
    assert cfg["layer_types"] == [
        "full" if (i + 1) % cfg["full_attention_interval"] == 0 else "linear"
        for i in range(48)]
    kwargs = cfg["build"]["kwargs"]["policy_kwargs"]
    assert cfg["layer_types"][:layers] == kwargs["layer_types"] == list(
        PERIOD)
    lm = DeltaMoELM(**kwargs)
    assert (lm.experts_total, lm.num_experts_per_tok, lm.first_expert_held,
            lm.num_attention_heads, lm.num_key_value_heads, lm.head_dim,
            lm.rotary_dim, lm.key_dim, lm.value_dim, lm.delta_chunk) == (
        512, 10, 0, 16, 2, 256, 64, 2048, 4096, 64)
    # every published key the module has a field for holds what it builds
    fields = dataclasses.asdict(lm)
    for key in ("hidden_size", "num_attention_heads", "num_key_value_heads",
                "head_dim", "moe_intermediate_size",
                "shared_expert_intermediate_size", "linear_num_key_heads",
                "linear_num_value_heads", "linear_key_head_dim",
                "linear_value_head_dim", "linear_conv_kernel_dim",
                "partial_rotary_factor", "num_experts_per_tok", "num_experts",
                "norm_topk_prob", "rope_theta", "rope_scaling",
                "rms_norm_eps", "tie_word_embeddings", "vocab_size",
                "expert_group_size", "behaviour_positions", "delta_chunk"):
        assert fields[key] == cfg[key], key
    assert cfg["horizon"] == 16384
    stated = lm.declaration()
    kernels = dict(stated.kernels)
    widths, kv_heads, _, query_heads = kernels[attention_facts]
    assert (widths, kv_heads, query_heads, kernels[head_facts],
            kernels[combine_facts], kernels[delta_facts]) == (
        256, 2, 16, (2048,), (2048,), (128, 128, 64))
    assert stated.leaf_rows_per_token == dict.fromkeys(
        lm.stacked_leaves, 10 * 1.25 / 16)
    # 16 query heads over 2 key heads of 256 at 16,384: two column blocks a
    # head, so the full layer takes the kernel on one chip
    form, _ = attention_form_why("tpu", 1, widths, cfg["horizon"], None,
                                 kv_heads)
    assert form == "kernel"
    assert pallas_attention.fits(256, 0, 256, None, 16384)
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
        assert e == 32
    for i, _, _, off in spec.dense_leaves:
        assert layout[paths[i]] == ("dense", off)
    assert len(spec.stacked_leaves) == 3 * layers
    dense = {paths[i].rsplit("/", 1)[1] for i, *_ in spec.dense_leaves}
    assert dense == {"scale", "norm_scale", "A_log", "dt_bias", "conv",
                     "shared_gate"}
    assert unmatched_leaves(stated.partition_rules, shapes) == {}
    assert about["expert_flops_per_member_step"] == int(
        layers * 10 * 32 / 512 * 2 * 3 * 2048 * 512)
    shared = 3 * 2048 * 512 + 2048
    assert about["dense_flops_per_member_step"] == 2 * (
        3 * (2048 * 12288 + 2048 * 64 + 4096 * 2048)
        + (2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048) + layers * shared)
    assert about["head_flops_per_member_step"] == 2 * 2048 * 18992
    # the seeded spreads name leaves the model has
    names = {p.rsplit("/", 1)[1] for p in paths} | {
        "/".join(p.rsplit("/", 2)[1:]) for p in paths}
    assert set(cfg["seeded_std"]) - {"other"} <= names
    assert set(cfg["seeded_norm"]) <= names


def test_the_seeded_weights_keep_the_mechanism_alive(ref):
    """The configuration's ``seeded_decay``: at ``a' = 0`` a step's decay
    spans 0.905 to 0.999 over the value heads (not the released
    initialisation's, under which most heads forget within one step); the q
    norm's weight is seeded off zero and every other zero-centred weight at
    it; the gated norm's at one."""
    cfg = {**tiny_model.config(rank=1), **{
        k: tiny_model.published()[k]
        for k in ("seeded_std", "seeded_norm", "seeded_decay")}}
    s = ref.sizes(cfg)
    theta = np.asarray(ref.init_theta(jax.random.PRNGKey(0), cfg))
    at = ref.param_offsets(s)

    def leaf(path):
        off, shape = at[path]
        return theta[off:off + math.prod(shape)].reshape(shape)

    decays = np.concatenate([
        np.exp(-np.exp(leaf(f"layer_0{i}/delta/A_log")) * np.log1p(np.exp(
            leaf(f"layer_0{i}/delta/dt_bias")))) for i in range(3)])
    assert decays.min() > 0.9 and decays.max() < 0.9991
    assert decays.max() - decays.min() > 0.02
    assert np.all(leaf("layer_03/attn/q_norm/scale") == 2.0)
    assert np.all(leaf("layer_03/attn/k_norm/scale") == 0.0)
    assert np.all(leaf("layer_00/norm1/scale") == 0.0)
    assert np.all(leaf("layer_00/delta/norm_scale") == 1.0)
    assert abs(float(leaf("layer_00/moe/shared/down").std()) - 0.03) < 0.01
    assert abs(float(leaf("layer_00/moe/experts/down").std()) - 0.1) < 0.01


def test_no_leaf_falls_to_the_catch_all(tiny):
    """The linear mixer's leaves and the shared expert's gate are named by
    this model's own rules, the rest by the blocks' (models/lm_blocks.py)
    that it lists ahead of them."""
    shapes = tiny["lm"].param_shapes()
    own = tiny["lm"].declaration().partition_rules
    assert unmatched_leaves(own, shapes) == {}
    blocks = (lm_blocks.DECODER_PARTITION_RULES
              + lm_blocks.EXPERT_PARTITION_RULES)
    assert own[:len(blocks)] == blocks
    # what this model adds to the blocks' rules, and the general ones: the
    # model's list with its own part taken out
    without = blocks + (own[len(blocks)],) + DEFAULT_PARTITION_RULES
    assert own[len(blocks)][0] == r"(q_norm|k_norm)/scale$"
    missed = {p.split("/", 1)[1] for p in unmatched_leaves(without, shapes)}
    # (``dt_bias`` and ``norm_scale`` would fall to the suffix rules for
    # biases and scales, which cut them over ``model``)
    assert missed == {"delta/A_log", "delta/conv", "delta/in_proj_ba",
                      "delta/in_proj_qkvz", "delta/out_proj",
                      "moe/shared_gate"}


@pytest.mark.parametrize("pop, model", [(2, 4), (1, 2)])
def test_partition_rules_name_the_leaves(devices8, pop, model):
    mesh = hyperscale_mesh(pop, model, devices8[:pop * model])
    lm = DeltaMoELM(**TINY)
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
    assert spec("layer_01", "moe", "shared_gate") in ((), (None, None))
    assert spec("layer_01", "moe", "shared", "gate") == (None, "model")
    assert spec("layer_00", "delta", "in_proj_qkvz") == (None, "model")
    assert spec("layer_00", "delta", "conv") == (None, None, "model")
    assert spec("layer_00", "delta", "A_log") == ("model",)
    assert spec("layer_00", "delta", "out_proj") == ("model", None)
    assert spec("layer_00", "delta", "in_proj_ba") in ((), (None, None))
    assert spec("layer_03", "attn", "q") == (None, "model")
    assert spec("layer_03", "attn", "o") == ("model", None)
    assert spec("layer_03", "attn", "q_norm", "scale") in ((), (None,))
    assert spec("head", "kernel") == (None, "model")
    assert spec("embed", "embedding") == ("model", None)


# ------------------------- (h) the other models are what they were

# sha256 of each other sequence model's tiny perturbed forward as a jaxpr
# (the program, text for text), read at the parent commit (PR 51's tree)
PARENT_PROGRAMS = {
    "hybrid": "b15d4a61452bd8d2", "looped": "ebb6c8c34b8c0c2a",
    "moe": "0bb70979cba6e01f", "sambay": "219afcdc0c657a48",
    "indexed_moe": "61d7e07c1a308ad3", "cca_moe": "23451f123505dcdc",
    "window_moe": "ca4a28166c1f166a"}


def _other_models():
    import cca_moe_tiny
    import indexed_moe_tiny
    import lm_tiny
    import loop_tiny
    import moe_tiny
    import sambay_tiny
    import window_moe_tiny

    from estorch_tpu import models

    return {"hybrid": (models.HybridLM, lm_tiny),
            "looped": (models.LoopedLM, loop_tiny),
            "moe": (models.MoELM, moe_tiny),
            "sambay": (models.SambaYLM, sambay_tiny),
            "indexed_moe": (models.IndexedMoELM, indexed_moe_tiny),
            "cca_moe": (models.CCAMoELM, cca_moe_tiny),
            "window_moe": (models.WindowMoELM, window_moe_tiny)}


def forward_hash(cls, module) -> str:
    """sha256 of the jaxpr of ``cls(**TINY)``'s perturbed forward over 16
    tokens with rank-1 factors."""
    lm = cls(**module.TINY)
    shapes = lm.param_shapes()
    stated = lm.declaration()
    spec = make_lowrank_tree_spec(shapes, 1, stacked=stated.stacked_leaves,
                                  dense=stated.dense_noise_leaves)
    text = str(jax.make_jaxpr(
        lambda p, n, t: lm.perturbed_apply(p, spec.unpack(n), 0.05, t))(
            shapes, jax.ShapeDtypeStruct((spec.noise_dim,), jnp.float32),
            jax.ShapeDtypeStruct((16,), jnp.int32)))
    # (a custom batching rule prints as a function with its address)
    return hashlib.sha256(
        re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()[:16]


@pytest.mark.parametrize("name", ["hybrid", "looped", "moe", "sambay",
                                  "indexed_moe", "cca_moe", "window_moe"])
def test_the_other_models_outputs_are_what_they_were(name):
    """What this model added to ``lm_blocks`` leaves every other model's
    forward the PROGRAM it was, operation for operation (so its outputs, bit
    for bit), and no rule of this model can reach a leaf of theirs: their
    own rules, which the engine tries first, name every one."""
    cls, module = _other_models()[name]
    assert forward_hash(cls, module) == PARENT_PROGRAMS[name]
    other = cls(**module.TINY)
    shapes = other.param_shapes()
    own = other.declaration().partition_rules
    assert unmatched_leaves(own, shapes) == {}
    mesh = hyperscale_mesh(1, 2, jax.devices()[:2])
    ours = match_partition_rules(
        own + DeltaMoELM(**TINY).declaration().partition_rules
        + DEFAULT_PARTITION_RULES, shapes, mesh)
    theirs = match_partition_rules(own + DEFAULT_PARTITION_RULES, shapes,
                                   mesh)
    assert all(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda a, b: a.spec == b.spec, ours, theirs)))


# ------------------------------------------- (i) through ES, over meshes

def _es(devices, model_shards, **over):
    from estorch_tpu import ES, JaxAgent
    from estorch_tpu.envs import TokenScoreEnv

    kw = dict(
        policy=DeltaMoELM, agent=JaxAgent, optimizer=optax.adam,
        population_size=8, sigma=0.02,
        policy_kwargs={**TINY, "layer_types": ("linear", "full")},
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
        records = []
        es.train(2, verbose=False, log_fn=records.append)
        return dict(es=es, fitness=[r["reward_mean"] for r in es.history],
                    params=np.asarray(es.state.params_flat), records=records)

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
        assert report["layer_01/attn/q"].startswith(
            "PartitionSpec(None, 'model'")
        assert report["layer_00/delta/in_proj_qkvz"].startswith(
            "PartitionSpec(None, 'model'")
        records = []
        es.train(2, verbose=False, log_fn=records.append)
        np.testing.assert_allclose(
            [r["reward_mean"] for r in es.history], one_device["fitness"],
            rtol=2e-5)
        np.testing.assert_allclose(np.asarray(es.state.params_flat),
                                   one_device["params"], atol=2e-5)
        assert ([r["routed_pairs"] for r in records]
                == [r["routed_pairs"] for r in one_device["records"]])

    def test_records_gauges_and_manifest(self, one_device):
        es, records = one_device["es"], one_device["records"]
        assert es.engine.forward_form == "perturbed"
        assert (es.engine.kernel_facts["attention_form"],
                es.engine.kernel_facts["combine_form"]) == (
            "xla", "xla")
        pairs = 8 * 21 * 3 * 2          # members x tokens x k x layers
        for r in records:
            assert 0 < r["routed_pairs"] < pairs
            assert r["expert_load_max_over_mean"] >= 1.0
        gauges = es.obs.counters
        assert gauges.get("experts_held") == 4
        assert gauges.get("linear_layers") == 1
        assert gauges.get("delta_chunk") == 8
        config = es.run_manifest()["config"]
        assert config["full_layers"] == 1
        assert "finite product" in config["delta_inverse"]
        assert config["attention_form"] == "xla"
        assert config["combine_form"] == "xla"


# ------------------- (j) the cell's own rehearsals that run no child process
# (benchmark/rehearse/test_gdn_cell.py: pytest tests/ never collects that
# directory; the ones that run the cell in a child stay the benchmark's own)

import test_gdn_cell as _cell  # noqa: E402

test_cell__is_added_by_files_alone = _cell.test_the_cell_is_added_by_files_alone
test_cell__metrics_name_this_cell_and_only_it = (
    _cell.test_the_gdn_metrics_name_this_cell_and_only_it)
test_cell__configuration_keeps_every_published_key = (
    _cell.test_the_configuration_file_keeps_every_published_key)
test_cell__reader_finds_nothing_in_another_models_program = (
    _cell.test_the_reader_finds_nothing_in_a_program_without_the_scopes)
test_cell__costs_are_from_shapes = _cell.test_the_costs_are_from_shapes
