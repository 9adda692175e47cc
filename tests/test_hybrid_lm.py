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
from estorch_tpu.parallel.mesh import (DEFAULT_PARTITION_RULES,
                                       unmatched_leaves)

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
    assert unmatched_leaves(DEFAULT_PARTITION_RULES, shapes) == {}
