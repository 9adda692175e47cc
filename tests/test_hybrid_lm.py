"""HybridLM (models/hybrid_lm.py) and the perturbed-dense primitive
(models/perturbed.py) against the plain reference the benchmark judges the
cell by (benchmark/reference/hybrid_lm.py): float32, ``highest``, fused
published layout, SEQUENTIAL recurrence, full masked softmax."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.flatten_util import ravel_pytree

import lm_tiny
from estorch_tpu.models import HybridLM
from estorch_tpu.models.perturbed import (perturbed_dense, perturbed_embed,
                                          perturbed_leaf)
from estorch_tpu.ops.lowrank import make_lowrank_tree_spec
from estorch_tpu.parallel.mesh import unmatched_leaves

# the models here are tiny (heads of 8, sequences of 16): inside a
# ``kernel_scope`` their attention calls take the kernel all the same
# (conftest.py::tiny_widths fakes the call's own rule,
# ``pallas_attention.fits``, which the interpreter does not need)
pytestmark = pytest.mark.usefixtures("tiny_widths")

# float32 on both sides; what differs is the ORDER of float32 sums (chunked
# scan against sequential recurrence, blocked softmax against whole, split
# against fused projections) on logits of magnitude 0.1: a few ulps of the
# partial sums, measured 1e-8 to 5e-7.  1e-4 leaves room for other BLAS
# orders and would still catch bfloat16 anywhere (errors of 1e-3)
TOL = 1e-4


@pytest.fixture(scope="module")
def ref():
    return lm_tiny.reference()


@pytest.fixture(scope="module")
def tiny(ref):
    cfg = lm_tiny.config(rank=2)
    lm = HybridLM(**lm_tiny.TINY)
    theta = ref.init_theta(jax.random.PRNGKey(3), cfg)
    shapes = lm.param_shapes()
    _, unravel = ravel_pytree(jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, x.dtype), shapes))
    spec = make_lowrank_tree_spec(shapes, 2)
    noise = jax.random.normal(jax.random.PRNGKey(5), (spec.noise_dim,))
    return dict(cfg=cfg, s=ref.sizes(cfg), lm=lm, theta=theta,
                params=unravel(theta), spec=spec, noise=noise)


@pytest.mark.parametrize("length", [21, 16, 5])
def test_logits_match_the_reference(ref, tiny, length):
    """21 and 5 are not multiples of the scan's chunk (8), of the
    attention block or of the head block; 16 is."""
    tokens = jax.random.randint(jax.random.PRNGKey(length), (length,), 0, 64)
    got = tiny["lm"].apply({"params": tiny["params"]}, tokens,
                           method="logits")
    want = ref.logits(tiny["s"], ref.Member(tiny["s"], tiny["theta"], None,
                                            0.0), tokens)
    assert float(jnp.abs(want).max()) > 0.01
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)


def test_chunked_scan_matches_the_sequential_recurrence(tiny):
    lm = tiny["lm"]
    t, nh, hd, n = 19, lm.mamba_n_heads, lm.mamba_d_head, lm.mamba_d_state
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (t, nh, hd))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (t, nh)))
    a = -jnp.exp(jax.random.normal(ks[2], (nh,)))
    b, c = jax.random.normal(ks[3], (t, n)), jax.random.normal(ks[4], (t, n))
    got = lm._ssd(x, dt, a, b, c, jnp.float32)
    h, want = np.zeros((nh, hd, n)), []
    for i in range(t):
        decay = np.exp(np.asarray(dt[i] * a))[:, None, None]
        h = decay * h + np.einsum("h,hd,n->hdn", dt[i], x[i], b[i])
        want.append(np.einsum("hdn,n->hd", h, c[i]))
    np.testing.assert_allclose(got, np.stack(want), atol=2e-4, rtol=1e-4)


@pytest.mark.parametrize("sign", [1.0, -1.0])
def test_both_signs_of_a_pair_from_one_factor_read(ref, tiny, sign):
    """One noise vector, unpacked once; +c and -c are the pair's two
    members, and the reference materialises W + c A B^T / sqrt(r)."""
    tokens = jax.random.randint(jax.random.PRNGKey(1), (21,), 0, 64)
    c = 0.05 * sign
    factors = tiny["spec"].unpack(tiny["noise"])
    got = tiny["lm"].logits(tiny["params"], tokens, factors, c)
    member = ref.Member(tiny["s"], tiny["theta"], tiny["noise"], c)
    want = ref.logits(tiny["s"], member, tokens)
    centre = ref.logits(tiny["s"], ref.Member(tiny["s"], tiny["theta"], None,
                                              0.0), tokens)
    assert float(jnp.abs(want - centre).max()) > 0.1   # the noise matters
    np.testing.assert_allclose(got, want, atol=TOL, rtol=0)
    logp, last = tiny["lm"].perturbed_apply(tiny["params"], factors, c, tokens)
    want_logp, want_last = ref.forward(tiny["s"], member, tokens,
                                       head_block=8)
    np.testing.assert_allclose(logp, want_logp, atol=TOL, rtol=0)
    np.testing.assert_allclose(last, want_last, atol=TOL, rtol=0)
    assert logp.shape == (20,) and last.shape == (64,)


@pytest.mark.parametrize("case", ["factored", "dense", "tied_head", "embed",
                                  "leaf", "centre"])
def test_perturbed_primitive_matches_materialised_weights(case):
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    m, n, r, c = 12, 20, 3, 0.3
    w = jax.random.normal(ks[0], (m, n))
    a, b = jax.random.normal(ks[1], (m, r)), jax.random.normal(ks[2], (n, r))
    e = a @ b.T / np.sqrt(r)
    x = jax.random.normal(ks[3], (5, m))
    if case == "factored":
        got, want = perturbed_dense(x, w, (a, b), c), x @ (w + c * e)
    elif case == "dense":
        got, want = perturbed_dense(x, w, e, c), x @ (w + c * e)
    elif case == "centre":
        got, want = perturbed_dense(x, w, None, c), x @ w
    elif case == "tied_head":
        h = jax.random.normal(ks[4], (5, n))
        got = perturbed_dense(h, w, (a, b), c, transposed=True)
        want = h @ (w + c * e).T
    elif case == "embed":
        tokens = jnp.asarray([0, 3, 11, 3])
        got = perturbed_embed(tokens, w, (a, b), c)
        want = (w + c * e)[tokens]
    else:
        got, want = perturbed_leaf(w, e, c), w + c * e
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_bfloat16_operands_accumulate_in_float32(tiny):
    tokens = jax.random.randint(jax.random.PRNGKey(2), (21,), 0, 64)
    half = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16),
                                  tiny["params"])
    factors = tiny["spec"].unpack(tiny["noise"])
    logp, last = tiny["lm"].perturbed_apply(half, factors, 0.05, tokens)
    full, _ = tiny["lm"].perturbed_apply(tiny["params"], factors, 0.05,
                                         tokens)
    assert logp.dtype == last.dtype == jnp.float32
    assert 1e-6 < float(jnp.abs(logp - full).max()) < 0.05


# ---------------------------------------------------------- attention

ATTN_LENGTHS, ATTN_BLOCKS = (5, 16, 21, 40), (8, 16, 64)


def _attention_case(length, block, dtype=jnp.float32):
    """A tiny model with that query block, its attention leaves drawn at a
    scale that spreads the softmax, and a ``[length, hidden]`` input."""
    lm = HybridLM(**{**lm_tiny.TINY, "attention_block": block})
    shapes = lm.param_shapes()["layer_01"]["attn"]
    keys = jax.random.split(jax.random.PRNGKey(100 * length + block), 5)
    p = {name: (0.2 * jax.random.normal(k, shapes[name].shape)).astype(dtype)
         for name, k in zip(sorted(shapes), keys)}
    u = jax.random.normal(keys[4], (length, lm.hidden_size)).astype(dtype)
    return lm, p, u


def _whole_sequence_attention(lm, p, u):
    """The oracle: one masked softmax over the whole ``[T, T]`` score,
    float32, the key/value heads expanded to the query heads by ``repeat``."""
    t, nq, hd = u.shape[0], lm.num_attention_heads, lm.head_dim
    groups = nq // lm.num_key_value_heads
    q = (u @ p["q"]).reshape(t, nq, hd)
    k = jnp.repeat((u @ p["k"]).reshape(t, -1, hd), groups, axis=1)
    v = jnp.repeat((u @ p["v"]).reshape(t, -1, hd), groups, axis=1)
    s = jnp.einsum("qhd,shd->hqs", q, k) * lm.attention_multiplier
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    ctx = jnp.einsum("hqs,shd->qhd", jax.nn.softmax(s, axis=-1), v)
    return ctx.reshape(t, nq * hd) @ p["o"]


@pytest.mark.parametrize("block", ATTN_BLOCKS)
@pytest.mark.parametrize("length", ATTN_LENGTHS)
def test_block_causal_attention_matches_a_whole_sequence_softmax(length,
                                                                 block):
    """Ragged tails (5, 21 and 40 at block 16), exact multiples and one
    block (everything at block 64): a score left out is one whose
    probability the mask makes exactly 0."""
    lm, p, u = _attention_case(length, block)
    got = lm._attention(p, None, 0.0, u)
    want = _whole_sequence_attention(lm, p, u)
    assert got.shape == (length, lm.hidden_size) and got.dtype == jnp.float32
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


@pytest.mark.parametrize("block", ATTN_BLOCKS)
@pytest.mark.parametrize("length", ATTN_LENGTHS)
def test_block_causal_attention_under_pairs_and_signs(length, block):
    """As the engine calls it: ``vmap`` over pairs (each with its own
    rank-2 factors) of a ``vmap`` over the two signs ``c = ±σ``, the
    factors read once a pair; the oracle materialises
    ``W + c·A·Bᵀ/√r`` for each of the members."""
    lm, p, u = _attention_case(length, block)
    pairs, rank, sigma = 2, 2, 0.1
    spec = make_lowrank_tree_spec(p, rank)
    noise = jax.random.normal(jax.random.PRNGKey(length + block),
                              (pairs, spec.noise_dim))
    signs = sigma * jnp.asarray([1.0, -1.0])

    def pair(row):
        factors = spec.unpack(row)
        return jax.vmap(lambda c: lm._attention(p, factors, c, u))(signs)

    got = jax.vmap(pair)(noise)
    assert got.shape == (pairs, 2, length, lm.hidden_size)
    centre = _whole_sequence_attention(lm, p, u)
    for i in range(pairs):
        factors = spec.unpack(noise[i])
        for j, c in enumerate(signs):
            member = {name: p[name] + c * (factors[name][0]
                                           @ factors[name][1].T)
                      / np.sqrt(rank) for name in p}
            want = _whole_sequence_attention(lm, member, u)
            assert float(jnp.abs(want - centre).max()) > 0.05
            np.testing.assert_allclose(got[i, j], want, atol=1e-5, rtol=0)


def _equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(sub)


def _attention_equations(lm, p, u):
    return list(_equations(jax.make_jaxpr(
        lambda p, u: lm._attention(p, None, 0.0, u))(p, u).jaxpr))


def test_attention_of_bfloat16_operands_stays_float32_inside():
    """Projections, scores and P·V all accumulate in float32, and the
    softmax between them is float32: the only bfloat16 values are the
    matmuls' operands."""
    lm, p, u = _attention_case(40, 16, jnp.bfloat16)
    eqns = _attention_equations(lm, p, u)
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 4 + 2 * 3
    for e in dots:
        assert {v.aval.dtype for v in e.invars} == {jnp.dtype(jnp.bfloat16)}
        assert e.outvars[0].aval.dtype == jnp.float32
    soft = [e for e in eqns if e.primitive.name in ("exp", "reduce_max",
                                                    "reduce_sum", "div")]
    assert soft and all(e.outvars[0].aval.dtype == jnp.float32 for e in soft)
    assert lm._attention(p, None, 0.0, u).dtype == jnp.float32


def test_each_query_block_is_scored_against_its_prefix_only():
    """The mechanism's counter, read from the program: at 8 blocks the
    score matmuls' key extents are block, 2·block, … T, which is 36 of the
    64 ``[block, block]`` tiles of ``T²``, and no loop is left to hide a
    wider one."""
    block, n_blocks = 16, 8
    length = block * n_blocks
    lm, p, u = _attention_case(length, block)
    assert lm.head_dim not in range(block, length + 1, block)
    eqns = _attention_equations(lm, p, u)
    assert not {e.primitive.name for e in eqns} & {"scan", "while"}
    scored = []
    for e in eqns:
        if e.primitive.name != "dot_general":
            continue
        contract, batch = e.params["dimension_numbers"]
        if not batch[0] or (e.invars[0].aval.shape[contract[0][0]]
                            != lm.head_dim):
            continue        # a projection, or P·V (which contracts keys)
        # the keys are the operand without the axis of grouped query heads
        side = 0 if e.invars[0].aval.ndim == 3 else 1
        keys, queries = e.invars[side].aval.shape, e.invars[1 - side].aval.shape
        free = [n for axis, n in enumerate(keys)
                if axis not in contract[side] + batch[side]]
        assert len(keys) == 3 and len(queries) == 4 and len(free) == 1
        assert block in queries
        scored.append(free[0])
    assert scored == [block * (i + 1) for i in range(n_blocks)]
    assert sum(block * s for s in scored) * 64 == 36 * length * length


def test_outputs_are_what_they_were_before_the_kernel_existed():
    """``apply`` outside an engine is the XLA form: these numbers were
    printed by the tree before ops/pallas_attention.py (PR 31's) and by
    this one, equal to every digit."""
    lm = HybridLM(**lm_tiny.TINY)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (21,), 0, 64)
    variables = jax.tree_util.tree_map(
        lambda x: x * 10.0 if x.ndim == 2 else x,
        lm.init(jax.random.PRNGKey(0), tokens))
    score, last = lm.apply(variables, tokens)
    np.testing.assert_allclose(
        score[:4], [-3.970382, -4.0645003, -4.1236715, -4.1806355],
        rtol=2e-6)
    np.testing.assert_allclose(
        last[:4], [-0.08525772, -0.009714369, -0.13818261, -0.20358337],
        rtol=2e-5)
    np.testing.assert_allclose(float(score.sum()), -83.3136215209961,
                               rtol=2e-6)


@pytest.mark.parametrize("length", [21, 16])
def test_forced_through_the_interpreted_kernel_it_agrees(tiny, length):
    """Inside a ``kernel_scope`` the one attention layer runs the Pallas
    kernel (interpreted here; no rotary, grouped heads, the model's own
    ``attention_multiplier`` as the scale) and the outputs agree with the
    XLA form's to the order of float32 sums."""
    from estorch_tpu.ops.pallas_attention import kernel_scope

    tokens = jax.random.randint(jax.random.PRNGKey(4), (length,), 0, 64)
    want = tiny["lm"].apply({"params": tiny["params"]}, tokens)
    with kernel_scope(interpret=True):
        got = tiny["lm"].apply({"params": tiny["params"]}, tokens)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-4, rtol=0)


@pytest.mark.parametrize("sign", [0.0, 1.0, -1.0])
def test_the_heads_kernel_is_reached_through_the_same_scope(sign):
    """A hidden width of one 128-lane block over 512 positions fits the
    head's rule (ops/pallas_head.py): inside a ``kernel_scope`` the TIED
    head (the embedding read transposed, its factors swapped, the logits
    divided by ``logits_scaling``) scores in the head's kernel, interpreted
    here: the centre and both members of a pair."""
    from estorch_tpu.ops.pallas_attention import kernel_scope

    lm = HybridLM(**{**lm_tiny.TINY, "hidden_size": 128,
                     "layer_types": ("attention", "mamba"),
                     "mamba_chunk_size": 64, "attention_block": 128,
                     "head_block": 96})
    tokens = jax.random.randint(jax.random.PRNGKey(4), (512,), 0, 64)
    params = jax.tree_util.tree_map(
        lambda x: 3.0 * x, lm.init(jax.random.PRNGKey(2))["params"])
    spec = make_lowrank_tree_spec(lm.param_shapes(), 2)
    factors = None if sign == 0.0 else spec.unpack(
        jax.random.normal(jax.random.PRNGKey(5), (spec.noise_dim,)))
    c = 0.05 * sign
    want = lm.perturbed_apply(params, factors, c, tokens)
    with kernel_scope(interpret=True):
        program = str(jax.make_jaxpr(
            lambda p, f: lm.perturbed_apply(p, f, c, tokens))(
                params, factors))
        got = lm.perturbed_apply(params, factors, c, tokens)
    assert "next_token_scores" in program
    for g, w in zip(got, want):
        assert g.shape == w.shape and bool(jnp.isfinite(g).all())
        np.testing.assert_allclose(g, w, atol=TOL, rtol=0)
    assert float(jnp.abs(got[0] - want[0]).max()) > 0.0  # another program


def test_init_draws_the_declared_tree(tiny):
    lm = tiny["lm"]
    params = lm.init(jax.random.PRNGKey(0), None)["params"]
    shapes = lm.param_shapes()
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(shapes))
    for got, want in zip(jax.tree_util.tree_leaves(params),
                         jax.tree_util.tree_leaves(shapes)):
        assert got.shape == want.shape and got.dtype == jnp.float32
    mamba = params["layer_00"]["mamba"]
    assert float(mamba["A_log"].min()) >= 0.0
    assert np.all(np.asarray(mamba["D"]) == 1.0)
    assert 0.01 < float(params["embed"]["embedding"].std()) < 0.03


def test_published_sizes_and_layouts(ref):
    """The configuration file: the parameter and operation counts ISSUE 26
    derives, the reference's layouts equal to the system's tree and noise
    spec, and no leaf left to the catch-all partition rule."""
    cfg = lm_tiny.published()
    about = ref.describe(cfg)
    assert about["param_dim"] == 951_991_232
    assert about["dense_flops_per_member_step"] == 2 * 746_192_896
    assert about["head_flops_per_member_step"] == 2 * 205_520_896
    assert cfg["num_hidden_layers"] == 10 == len(
        cfg["build"]["kwargs"]["policy_kwargs"]["layer_types"])
    assert (cfg["layer_types"][:10]
            == cfg["build"]["kwargs"]["policy_kwargs"]["layer_types"])
    lm = HybridLM(**cfg["build"]["kwargs"]["policy_kwargs"])
    shapes = lm.param_shapes()
    paths = ["/".join(str(k.key) for k in p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    s = ref.sizes(cfg)
    assert ([(p, tuple(x.shape)) for p, x in
             zip(paths, jax.tree_util.tree_leaves(shapes))]
            == ref.system_layout(s))
    spec = make_lowrank_tree_spec(shapes, 1)
    layout = ref.noise_layout(s)
    assert spec.noise_dim == layout["__dim__"] == about["noise_dim"] == 905_984
    for i, m, n, a_off, b_off in spec.lr_leaves:
        assert layout[paths[i]] == ("lr", a_off, b_off)
    for i, _, _, off in spec.dense_leaves:
        assert layout[paths[i]] == ("dense", off)
    # conv taps, per-head scalars and norm weights draw dense noise
    dense = {paths[i].rsplit("/", 1)[1] for i, *_ in spec.dense_leaves}
    assert dense == {"A_log", "D", "dt_bias", "scale", "norm_scale",
                     "conv_x_kernel", "conv_x_bias", "conv_bc_kernel",
                     "conv_bc_bias"}
    assert unmatched_leaves(lm.declaration().partition_rules, shapes) == {}
