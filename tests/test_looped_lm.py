"""LoopedLM (models/looped_lm.py) against the plain reference the benchmark
judges its cell by (benchmark/reference/looped_lm.py): float32, ``highest``,
Python loops over passes and layers, one full masked softmax per head, the
rotation written from the formula, every perturbed leaf materialised."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.flatten_util import ravel_pytree

import loop_tiny
from estorch_tpu.models import HybridLM, LoopedLM
from estorch_tpu.models import lm_blocks
from estorch_tpu.ops.lowrank import make_lowrank_tree_spec
from estorch_tpu.parallel.mesh import (DEFAULT_PARTITION_RULES,
                                       hyperscale_mesh, match_partition_rules,
                                       unmatched_leaves)

# the models here are tiny (heads of 8, sequences of 16): inside a
# ``kernel_scope`` their attention calls take the kernel all the same
# (conftest.py::tiny_widths fakes the call's own rule,
# ``pallas_attention.fits``, which the interpreter does not need)
pytestmark = pytest.mark.usefixtures("tiny_widths")

# float32 on both sides; what differs is the ORDER of float32 sums (blocked
# softmax against whole, scan against Python loop) on values of magnitude 1:
# measured 1e-7 to 2e-6.  1e-4 would still catch bfloat16 anywhere (1e-2)
TOL = 1e-4


@pytest.fixture(scope="module")
def ref():
    return loop_tiny.reference()


def _spread(ref, cfg, key):
    """Seeded weights with every matrix ten times its initial spread and a
    gate bias, so that logits, gate and exit distribution all vary."""
    s = ref.sizes(cfg)
    theta = np.array(ref.init_theta(key, cfg))
    for path, (off, shape) in ref.param_offsets(s).items():
        name = path.rsplit("/", 1)[-1]
        if name == "bias":
            theta[off:off + 1] = 0.3
        elif name not in ("scale", "__dim__"):
            theta[off:off + math.prod(shape)] *= 10.0
    return jnp.asarray(theta)


@pytest.fixture(scope="module")
def tiny(ref):
    cfg = loop_tiny.config(rank=2)
    lm = LoopedLM(**loop_tiny.TINY)
    theta = _spread(ref, cfg, jax.random.PRNGKey(3))
    shapes = lm.param_shapes()
    _, unravel = ravel_pytree(jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, x.dtype), shapes))
    spec = make_lowrank_tree_spec(shapes, 2)
    noise = jax.random.normal(jax.random.PRNGKey(5), (spec.noise_dim,))
    return dict(cfg=cfg, s=ref.sizes(cfg), lm=lm, theta=theta,
                unravel=unravel, params=unravel(theta), spec=spec,
                noise=noise)


def _tokens(length, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (length,), 0, 64)


# -------------------------------------------- (a) against the reference

@pytest.mark.parametrize("length", [21, 16, 5])
@pytest.mark.parametrize("sign", [0.0, 1.0, -1.0])
def test_every_pass_matches_the_reference(ref, tiny, sign, length):
    """Per-pass log-probabilities, exit distribution, last logits, and the
    policy output (score, last pass's logits): the centre (sign 0) and both
    members of a pair from ONE factor read.  21 and 5 are not multiples of
    the attention block or the head block (8); 16 is."""
    tokens, c = _tokens(length, length), 0.05 * sign
    noise = None if sign == 0.0 else tiny["spec"].unpack(tiny["noise"])
    member = ref.Member(tiny["s"], tiny["theta"],
                        None if sign == 0.0 else tiny["noise"], c)
    want = ref.passes(tiny["s"], member, tokens, head_block=8)
    got = tiny["lm"].passes(tiny["params"], noise, c, tokens)
    for g, w, shape in zip(got, want, [(4, length - 1), (4, length),
                                       (4, 64)]):
        assert g.shape == w.shape == shape and g.dtype == jnp.float32
        np.testing.assert_allclose(g, w, atol=TOL, rtol=0)
    # the four passes differ, the gate matters, the logits have a spread
    assert float(jnp.abs(want[0][0] - want[0][3]).max()) > 0.1
    assert float(want[1][0].max() - want[1][0].min()) > 0.05
    assert float(jnp.abs(want[2]).max()) > 0.5
    score, last = tiny["lm"].perturbed_apply(tiny["params"], noise, c, tokens)
    want_score, want_last = ref.forward(tiny["s"], member, tokens,
                                        head_block=8)
    np.testing.assert_allclose(score, want_score, atol=TOL, rtol=0)
    np.testing.assert_allclose(last, want_last, atol=TOL, rtol=0)
    assert score.shape == (length - 1,) and last.shape == (64,)
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


def _spread_apply(lm):
    """``apply`` of seeded weights at ten times their initial spread (so
    that attention's softmax is not flat) on 21 seeded tokens."""
    tokens = _tokens(21)
    variables = jax.tree_util.tree_map(
        lambda x: x * 10.0 if x.ndim == 2 else x,
        lm.init(jax.random.PRNGKey(0), tokens))
    return variables, tokens, lm.apply(variables, tokens)


def test_outputs_are_what_they_were_before_the_kernel_existed():
    """``apply`` outside an engine is the XLA form: these numbers were
    printed by the tree before ops/pallas_attention.py (PR 31's) and by
    this one, equal to every digit."""
    _, _, (score, last) = _spread_apply(LoopedLM(**loop_tiny.TINY))
    np.testing.assert_allclose(
        score[:4], [-3.7878175, -5.580817, -4.9464254, -5.6830497],
        rtol=2e-6)
    np.testing.assert_allclose(
        last[:4], [-0.1570187, -1.9164678, -0.34257308, 0.24097651],
        rtol=2e-5)
    np.testing.assert_allclose(float(score.sum()), -96.71588134765625,
                               rtol=2e-6)


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, TOL),
                                        (jnp.bfloat16, 0.1)])
@pytest.mark.parametrize("length", [21, 16])
def test_forced_through_the_interpreted_kernel_it_agrees(tiny, dtype, tol,
                                                         length):
    """The whole model, both forms of its attention: inside a
    ``kernel_scope`` every layer-application of every pass runs the Pallas
    kernel (interpreted here), and scores and logits agree with the XLA
    form's to the order of float32 sums, or to bfloat16's rounding of the
    probabilities where the operands are bfloat16 (a score is a log p of
    about -4, a logit about 1)."""
    from estorch_tpu.ops.pallas_attention import kernel_scope

    tokens = _tokens(length, 4)
    params = jax.tree_util.tree_map(lambda x: x.astype(dtype),
                                    tiny["params"])
    factors = tiny["spec"].unpack(tiny["noise"])
    want = tiny["lm"].perturbed_apply(params, factors, 0.05, tokens)
    with kernel_scope(interpret=True):
        got = tiny["lm"].perturbed_apply(params, factors, 0.05, tokens)
    for g, w in zip(got, want):
        assert g.dtype == jnp.float32 and bool(jnp.isfinite(g).all())
        np.testing.assert_allclose(g, w, atol=tol, rtol=0)
    assert float(jnp.abs(got[0] - want[0]).max()) > 0.0  # another program


@pytest.mark.parametrize("rank", [1, 2])
def test_the_heads_kernel_is_reached_through_the_same_scope(rank):
    """A hidden width of one 128-lane block over 512 positions fits the
    head's rule (ops/pallas_head.py): inside a ``kernel_scope`` every pass
    scores its next tokens in the head's kernel (interpreted here) beside
    the attention's, handed the leaf, its factors and ``c`` as the XLA form
    is, and scores and last logits agree to the order of float32 sums."""
    from estorch_tpu.ops.pallas_attention import kernel_scope

    lm = LoopedLM(**{**loop_tiny.TINY, "hidden_size": 128,
                     "layer_types": ("full_attention",),
                     "total_ut_steps": 2, "attention_block": 128,
                     "head_block": 96})
    tokens = _tokens(512, 4)
    params = jax.tree_util.tree_map(
        lambda x: 3.0 * x, lm.init(jax.random.PRNGKey(2))["params"])
    spec = make_lowrank_tree_spec(lm.param_shapes(), rank)
    factors = spec.unpack(
        jax.random.normal(jax.random.PRNGKey(5), (spec.noise_dim,)))
    want = lm.perturbed_apply(params, factors, 0.05, tokens)
    with kernel_scope(interpret=True):
        program = str(jax.make_jaxpr(
            lambda p, f: lm.perturbed_apply(p, f, 0.05, tokens))(
                params, factors))
        got = lm.perturbed_apply(params, factors, 0.05, tokens)
    assert "next_token_scores" in program and "causal_attention" in program
    for g, w in zip(got, want):
        assert g.shape == w.shape and bool(jnp.isfinite(g).all())
        np.testing.assert_allclose(g, w, atol=TOL, rtol=0)
    assert float(jnp.abs(got[0] - want[0]).max()) > 0.0  # another program
    centre = lm.perturbed_apply(params, None, 0.0, tokens)
    assert float(jnp.abs(got[0] - centre[0]).max()) > 1e-3  # the correction


# ------------------------------------- (b) every leaf's correction, 4 uses

LEAVES = [path for path, _ in loop_tiny.reference().system_layout(
    loop_tiny.reference().sizes(loop_tiny.config(rank=2)))]


@pytest.fixture(scope="module")
def one_leaf_programs(tiny):
    lm, spec = tiny["lm"], tiny["spec"]
    perturbed = jax.jit(lambda p, n, c, t: lm.passes(p, spec.unpack(n), c, t))
    plain = jax.jit(lambda p, t: lm.passes(p, None, 0.0, t))
    return perturbed, plain


@pytest.mark.parametrize("path", LEAVES)
def test_a_leafs_correction_reaches_every_pass(ref, tiny, one_leaf_programs,
                                               path):
    """Noise on ONE leaf: the perturbed forward equals the plain forward of
    the materialised ``theta + c·E`` in every pass; a use of the leaf that
    dropped its correction would leave that pass at the centre's values."""
    perturbed, plain = one_leaf_programs
    s, spec, c = tiny["s"], tiny["spec"], 0.3
    layout = ref.noise_layout(s)
    entry = layout[path]
    shape = ref.param_offsets(s)[path][1]
    n = (sum(shape) * 2 if entry[0] == "lr" else math.prod(shape))
    noise = np.zeros((spec.noise_dim,), np.float32)
    noise[entry[1]:entry[1] + n] = np.asarray(
        tiny["noise"][entry[1]:entry[1] + n])
    noise, tokens = jnp.asarray(noise), _tokens(21, 2)
    member = ref.Member(s, tiny["theta"], noise, c)
    flat = jnp.concatenate([member.leaf(p).reshape(-1)
                            for p, _ in ref.system_layout(s)])
    got = perturbed(tiny["params"], noise, jnp.float32(c), tokens)
    want = plain(tiny["unravel"](flat), tokens)
    centre = plain(tiny["params"], tokens)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=TOL, rtol=0)
    # the leaf moves the log-probabilities of EVERY pass (the embedding, a
    # layer leaf) or of every pass but the first (the gate acts on the
    # exit distribution from the second entry on, and on pass 1's own)
    moved = np.abs(np.asarray(want[0] - centre[0])).max(axis=1)
    moved_exit = np.abs(np.asarray(want[1] - centre[1])).max(axis=1)
    if path.startswith("exit_gate"):
        assert (moved_exit > 1e-3).all(), moved_exit
    else:
        assert (moved > 1e-3).all(), (path, moved)


# --------------------------- (c) the scanned loop against a Python loop

@pytest.mark.parametrize("sign", [0.0, 1.0])
def test_the_scan_over_passes_equals_a_python_loop(tiny, sign):
    lm, tokens, c = tiny["lm"], _tokens(21, 4), 0.05 * sign
    noise = tiny["spec"].unpack(tiny["noise"]) if sign else None
    rotary = lm_blocks.rotary_tables(21, lm.head_dim, lm.rope_theta)
    carry, outs = lm._embed(tiny["params"], noise, c, tokens), []
    for step in range(lm.total_ut_steps):
        carry, out = lm._pass(tiny["params"], noise, c, tokens, rotary,
                              carry, step)
        outs.append(out)
    want = [jnp.stack(x) for x in zip(*outs)]
    got = lm.passes(tiny["params"], noise, c, tokens)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, atol=1e-5, rtol=0)


def test_the_program_holds_the_stack_once(tiny):
    """One ``scan`` over the passes whose body holds every layer: the
    attention blocks of the lowered forward are those of ONE pass (2 layers
    x 3 blocks x 2 matmuls), not of four."""
    lm = tiny["lm"]
    jaxpr = jax.make_jaxpr(lambda p, t: lm.perturbed_apply(p, None, 0.0, t))(
        tiny["params"], _tokens(21))
    scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
    assert len(scans) == 1 and scans[0].params["length"] == 4

    def dots(jp):
        n = 0
        for e in jp.eqns:
            n += e.primitive.name == "dot_general"
            for v in e.params.values():
                for sub in v if isinstance(v, (tuple, list)) else (v,):
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        n += dots(sub)
        return n

    # per layer: q k v o, gate up down, 3 blocks x (scores, P.V); then the
    # head (blocks + last position) and the gate
    assert dots(scans[0].params["jaxpr"].jaxpr) == 2 * (7 + 6) + 2 + 1


# ------------------------------------ (d) exit distribution, one pass

def test_exit_distribution_sums_to_one(tiny):
    _, exit_p, _ = tiny["lm"].passes(
        tiny["params"], tiny["spec"].unpack(tiny["noise"]), 0.05,
        _tokens(21, 6))
    assert float(exit_p.min()) > 0.0
    np.testing.assert_allclose(exit_p.sum(axis=0), np.ones(21), atol=1e-6)


def test_one_pass_is_the_plain_decoder(ref):
    """``total_ut_steps`` 1: the exit distribution is 1 on the one pass
    whatever the gate says, and the score is that pass's log p."""
    one = {"total_ut_steps": 1}
    cfg = loop_tiny.config(rank=2, policy=one)
    lm = LoopedLM(**{**loop_tiny.TINY, **one})
    theta = _spread(ref, cfg, jax.random.PRNGKey(8))
    _, unravel = ravel_pytree(jax.tree_util.tree_map(
        lambda x: jnp.zeros(x.shape, x.dtype), lm.param_shapes()))
    tokens = _tokens(21, 7)
    logp, exit_p, last = lm.passes(unravel(theta), None, 0.0, tokens)
    np.testing.assert_array_equal(exit_p, np.ones((1, 21), np.float32))
    score, last_logits = lm.apply({"params": unravel(theta)}, tokens)
    np.testing.assert_array_equal(score, logp[0])
    np.testing.assert_array_equal(last_logits, last[0])
    s = ref.sizes(cfg)
    want, want_last = ref.forward(s, ref.Member(s, theta, None, 0.0), tokens,
                                  head_block=8)
    np.testing.assert_allclose(score, want, atol=TOL, rtol=0)
    np.testing.assert_allclose(last_logits, want_last, atol=TOL, rtol=0)
    # and the plain decoder it is: one stack, final norm, head, written out
    x = jnp.take(unravel(theta)["embed"]["embedding"], tokens, axis=0)
    rotary = lm_blocks.rotary_tables(21, lm.head_dim, lm.rope_theta)
    for i in range(2):
        x = lm._layer(unravel(theta)[f"layer_{i:02d}"], None, 0.0, x, rotary,
                      jnp.float32)
    h = lm_blocks.rmsnorm(x, unravel(theta)["final_norm"]["scale"], 1e-6)
    full = jax.nn.log_softmax(h @ unravel(theta)["head"]["kernel"])
    np.testing.assert_allclose(
        score, full[jnp.arange(20), tokens[1:]], atol=1e-5, rtol=0)


# ------------------------------------------------ the pieces and the sizes

def test_rotation_is_the_formula():
    """Position p turns the pair (x_i, x_{i+d/2}) by p·theta^(-2i/d)."""
    hd, t, theta = 8, 7, 10000.0
    x = jax.random.normal(jax.random.PRNGKey(0), (t, 3, hd))
    cos, sin = lm_blocks.rotary_tables(t, hd, theta)
    got = np.asarray(lm_blocks.rotate(x, cos, sin))
    for p in range(t):
        for i in range(hd // 2):
            angle = p * theta ** (-2.0 * i / hd)
            a, b = np.asarray(x[p, :, i]), np.asarray(x[p, :, i + hd // 2])
            np.testing.assert_allclose(
                got[p, :, i], a * np.cos(angle) - b * np.sin(angle),
                atol=1e-5)
            np.testing.assert_allclose(
                got[p, :, i + hd // 2], b * np.cos(angle) + a * np.sin(angle),
                atol=1e-5)
    # relative: <R_p q, R_s k> depends on p - s alone
    q, k = x[0, 0], x[1, 0]
    rot = lambda v, p: lm_blocks.rotate(  # noqa: E731
        jnp.broadcast_to(v, (t, 1, hd)), cos, sin)[p, 0]
    np.testing.assert_allclose(rot(q, 5) @ rot(k, 3), rot(q, 2) @ rot(k, 0),
                               atol=1e-5)


def test_both_sequence_models_call_the_same_pieces(tiny):
    """ONE attention, ONE gated FFN, ONE head scorer, ONE RMSNorm: without
    rotation and at the same weights the looped model's attention IS the
    hybrid's."""
    import lm_tiny
    from estorch_tpu.models import hybrid_lm, looped_lm

    assert hybrid_lm._rmsnorm is looped_lm.rmsnorm is lm_blocks.rmsnorm
    assert hybrid_lm.layer_name is looped_lm.layer_name
    hybrid = HybridLM(**{**lm_tiny.TINY, "attention_multiplier": None,
                         "attention_head_dim": 8})
    p = tiny["params"]["layer_00"]["attn"]
    u = jax.random.normal(jax.random.PRNGKey(1), (21, 32))
    got = lm_blocks.causal_attention(
        LoopedLM._dense, p, None, 0.0, u, num_heads=4, num_kv_heads=2,
        head_dim=8, scale=1 / math.sqrt(8), block=8, rotary=None)
    np.testing.assert_array_equal(got, hybrid._attention(p, None, 0.0, u))
    mlp = tiny["params"]["layer_00"]["mlp"]
    np.testing.assert_array_equal(
        lm_blocks.gated_mlp(LoopedLM._dense, mlp, None, 0.0, u),
        hybrid._mlp(mlp, None, 0.0, u))


@pytest.mark.parametrize("bad, match", [
    ({"head_dim": 7}, "even"),
    ({"total_ut_steps": 0}, "total_ut_steps"),
    ({"num_key_value_heads": 3}, "multiple"),
    ({"layer_types": ("mamba",)}, "full_attention"),
    ({"layer_types": ()}, "full_attention"),
    ({"tie_word_embeddings": True}, "tied"),
])
def test_sizes_are_validated(bad, match):
    with pytest.raises(ValueError, match=match):
        LoopedLM(**{**loop_tiny.TINY, **bad})
    assert LoopedLM(**{**loop_tiny.TINY, "head_dim": None}).head_dim == 8


def test_init_draws_the_declared_tree(tiny):
    lm = tiny["lm"]
    params = lm.init(jax.random.PRNGKey(0), None)["params"]
    shapes = lm.param_shapes()
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(shapes))
    for got, want in zip(jax.tree_util.tree_leaves(params),
                         jax.tree_util.tree_leaves(shapes)):
        assert got.shape == want.shape and got.dtype == jnp.float32
    assert np.all(np.asarray(params["layer_01"]["norm4"]["scale"]) == 1.0)
    assert np.all(np.asarray(params["exit_gate"]["bias"]) == 0.0)
    assert 0.01 < float(params["head"]["kernel"].std()) < 0.03
    assert float(jnp.abs(params["exit_gate"]["kernel"]).max()) > 0.0


def test_bfloat16_operands_accumulate_in_float32(tiny):
    tokens = _tokens(21, 2)
    half = jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16),
                                  tiny["params"])
    factors = tiny["spec"].unpack(tiny["noise"])
    score, last = tiny["lm"].perturbed_apply(half, factors, 0.05, tokens)
    full, _ = tiny["lm"].perturbed_apply(tiny["params"], factors, 0.05,
                                         tokens)
    assert score.dtype == last.dtype == jnp.float32
    assert 1e-6 < float(jnp.abs(score - full).max()) < 0.3


def test_published_sizes_and_layouts(ref):
    """The configuration file: the parameter and operation counts ISSUE 31
    derives, the reference's layouts equal to the system's tree and noise
    spec, no leaf left to the catch-all partition rule, and the widest
    activation the chunk rule reads."""
    cfg = loop_tiny.published()
    about = ref.describe(cfg)
    layers = cfg["num_hidden_layers"]
    assert layers in (8, 6)
    per_layer = 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert about["param_dim"] == (layers * (per_layer + 4 * 2048)
                                  + 2 * 49152 * 2048 + 2048 + 2049)
    assert about["dense_flops_per_member_step"] == 2 * 4 * layers * per_layer
    assert about["head_flops_per_member_step"] == 2 * 4 * 49152 * 2048
    kwargs = cfg["build"]["kwargs"]["policy_kwargs"]
    assert len(cfg["layer_types"]) == 48
    assert cfg["layer_types"][:layers] == kwargs["layer_types"]
    lm = LoopedLM(**kwargs)
    assert (lm.hidden_size, lm.intermediate_size, lm.num_attention_heads,
            lm.num_key_value_heads, lm.head_dim, lm.vocab_size,
            lm.rope_theta, lm.rms_norm_eps, lm.total_ut_steps,
            lm.tie_word_embeddings) == (
        2048, 5632, 16, 16, 128, 49152, 1000000, 1e-6, 4, False)
    shapes = lm.param_shapes()
    paths = ["/".join(str(k.key) for k in p) for p, _ in
             jax.tree_util.tree_flatten_with_path(shapes)[0]]
    s = ref.sizes(cfg)
    assert ([(p, tuple(x.shape)) for p, x in
             zip(paths, jax.tree_util.tree_leaves(shapes))]
            == ref.system_layout(s))
    spec = make_lowrank_tree_spec(shapes, 1)
    layout = ref.noise_layout(s)
    assert spec.noise_dim == layout["__dim__"] == about["noise_dim"]
    assert spec.noise_dim == (layers * 47_616 + 2 * 51_200 + 2048 + 2049)
    for i, m, n, a_off, b_off in spec.lr_leaves:
        assert layout[paths[i]] == ("lr", a_off, b_off)
    for i, _, _, off in spec.dense_leaves:
        assert layout[paths[i]] == ("dense", off)
    # norm weights and the gate (one column: factoring would not save)
    dense = {paths[i] for i, *_ in spec.dense_leaves}
    assert {"exit_gate/kernel", "exit_gate/bias"} <= dense
    assert {p.rsplit("/", 1)[1] for p in dense} == {"scale", "kernel",
                                                    "bias"}
    # the model's own rules name every leaf: the decoder's frame that
    # models/lm_blocks.py has, and the exit gate beside it
    own = lm.declaration().partition_rules
    assert unmatched_leaves(own, shapes) == {}
    assert set(unmatched_leaves(lm_blocks.DECODER_PARTITION_RULES, shapes)
               ) == {"exit_gate/kernel"}        # (its bias is one value)


def test_partition_rules_shard_the_new_leaves(devices8):
    mesh = hyperscale_mesh(2, 2, devices8[:4])
    lm = LoopedLM(**loop_tiny.TINY)
    shapes = lm.param_shapes()
    sh = match_partition_rules(
        lm.declaration().partition_rules + DEFAULT_PARTITION_RULES, shapes,
        mesh)
    spec = lambda *path: tuple(  # noqa: E731
        jax.tree_util.tree_reduce(lambda a, b: b, sh[path[0]][path[1]]
                                  if len(path) == 2 else
                                  sh[path[0]][path[1]][path[2]]).spec)
    assert spec("head", "kernel") == (None, "model")
    assert spec("embed", "embedding") == ("model", None)
    assert spec("exit_gate", "kernel") in ((), (None, None))
    assert spec("exit_gate", "bias") in ((), (None,))
    assert spec("layer_00", "attn", "q") == (None, "model")
    assert spec("layer_00", "attn", "o") == ("model", None)
    assert spec("layer_00", "mlp", "down") == ("model", None)
    for n in ("norm1", "norm2", "norm3", "norm4"):
        assert spec("layer_01", n, "scale") in ((), (None,))


# ------------------------------------------- (e) through ES, over meshes

def _loop_es(devices, model_shards, **over):
    from estorch_tpu import ES, JaxAgent
    from estorch_tpu.envs import TokenScoreEnv

    kw = dict(
        policy=LoopedLM, agent=JaxAgent, optimizer=optax.adam,
        population_size=8, sigma=0.02, policy_kwargs=loop_tiny.TINY,
        agent_kwargs={"env": TokenScoreEnv(**loop_tiny.ENV)},
        optimizer_kwargs={"learning_rate": 1e-2}, shard_params=True,
        model_shards=model_shards, low_rank=1, noise_mode="table",
        table_size=1 << 18, device=list(devices))
    kw.update(over)
    return ES(**kw)


class TestThroughTheShardedEngine:
    @pytest.fixture(scope="class")
    def one_device(self, devices8):
        es = _loop_es(devices8[:1], 1)
        offsets = np.asarray(es.engine.all_pair_offsets(es.state))
        es.train(2, verbose=False)
        return dict(es=es, fitness=[r["reward_mean"] for r in es.history],
                    params=np.asarray(es.state.params_flat), offsets=offsets)

    @pytest.mark.parametrize("pop, model", [(2, 2), (1, 4), (4, 1)])
    def test_mesh_shapes_match_one_device(self, one_device, devices8, pop,
                                          model, centre_form):
        es = _loop_es(devices8[:4], model)
        assert es.engine.forward_form == "perturbed"
        assert (es.engine.pop_shards, es.engine.model_shards) == (pop, model)
        # both layouts of the centre; nothing to gather on a model axis of 1
        assert es.engine.centre_form == (
            centre_form if model > 1 else "split")
        np.testing.assert_array_equal(
            es.engine.all_pair_offsets(es.state), one_device["offsets"])
        es.train(2, verbose=False)
        np.testing.assert_allclose(
            [r["reward_mean"] for r in es.history], one_device["fitness"],
            rtol=2e-6)
        np.testing.assert_allclose(np.asarray(es.state.params_flat),
                                   one_device["params"], atol=1e-5, rtol=0)

    def test_one_device_run_and_its_gauges(self, one_device):
        es = one_device["es"]
        assert (es.engine.pop_shards, es.engine.model_shards) == (1, 1)
        assert es.engine.forward_form == "perturbed"
        assert [r["env_steps"] for r in es.history] == [8 * 21] * 2
        assert -4.4 < es.history[0]["reward_mean"] < -3.9  # about -log(64)
        gauges = es.obs.counters
        assert gauges.get("tokens_per_generation") == 8 * 21
        assert gauges.get("loop_steps") == 4
        assert gauges.get("layer_applications_per_token") == 8
        cfg = es.run_manifest()["config"]
        assert cfg["loop_steps"] == 4
        assert cfg["layer_applications_per_token"] == 8
        assert cfg["tokens_per_generation"] == 8 * 21

    def test_a_later_best_member_takes_the_buffers_of_the_one_it_replaces(
            self, devices8):
        """``keep_best``: the first best member held is the program's own
        output; one that replaces it is copied into its buffers (donated),
        value for value what the program emitted, and the replaced tree is
        gone.  No program is built when that happens."""
        from estorch_tpu.utils import (compile_event_counts,
                                       install_compile_event_counters)

        def run(patched):
            es = _loop_es(devices8[:4], 2, sigma=0.05)
            if patched:
                es.engine.keep_best = lambda best, held=None: best
            held, flags = [], []
            install_compile_event_counters()

            def log(r):
                flags.append(r["improved_best"])
                held.append((es._best, compile_event_counts()["programs"]))

            es.train(6, log_fn=log, verbose=False)
            return es, held, flags

        es, held, flags = run(patched=False)
        plain, _, plain_flags = run(patched=True)
        assert flags == plain_flags and flags[0] and sum(flags) >= 2
        np.testing.assert_array_equal(es._best_flat, plain._best_flat)
        second = flags.index(True, 1)
        first_tree, later_tree = held[0][0], held[second][0]
        assert later_tree is not first_tree
        leaf = jax.tree_util.tree_leaves(first_tree)[0]
        assert leaf.is_deleted()            # donated to the copy
        kept = jax.tree_util.tree_leaves(later_tree)[0]
        assert len(kept.sharding.device_set) == 4
        # the copy program was built with the generation program, before
        # the first generation: nothing compiles at the replacement
        assert held[second][1] == held[second - 1][1]

    def test_a_model_without_a_loop_sets_no_loop_gauge(self, devices8):
        import lm_tiny
        from estorch_tpu.envs import TokenScoreEnv

        es = _loop_es(devices8[:1], 1, policy=HybridLM,
                      policy_kwargs=lm_tiny.TINY,
                      agent_kwargs={"env": TokenScoreEnv(**lm_tiny.ENV)})
        assert es.obs.counters.get("tokens_per_generation") == 8 * 21
        assert es.obs.counters.get("loop_steps", None) is None
        assert "loop_steps" not in es.run_manifest()["config"]

    def test_the_reference_scores_the_engines_members(self, ref, devices8):
        """Generation 0 of the engine against the reference through the
        keying contract the benchmark's runner relies on: same table, same
        offsets, same keys, both signs of every pair."""
        es = _loop_es(devices8[:1], 1, sigma=0.05)
        cfg = loop_tiny.config(rank=1)
        s = ref.sizes(cfg)
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


# ----------------------------- the chunk rule: what is really widest

class TestChunkRule:
    def test_the_head_counts_by_its_block_of_positions(self, devices8):
        """An untied head ``[hidden, vocab]`` is the widest factored leaf,
        but runs ``head_block`` positions at a time: the rule reads
        ``head_block x vocab`` for it, ``horizon x n`` for the others."""
        es = _loop_es(devices8[:1], 1)
        eng = es.engine
        assert eng.policy.leaf_rows == {"head/kernel": 8}
        # horizon 21: gate/up 21 x 48 = 1008 floats; the head 8 x 64 = 512
        assert eng._widest_activation() == 21 * 48
        wide = _loop_es(devices8[:1], 1, policy_kwargs={
            **loop_tiny.TINY, "head_block": 32})
        # a block longer than the sequence is the sequence: 21 x 64
        assert wide.engine._widest_activation() == 21 * 64

    def test_the_budget_sets_the_pairs_of_a_chunk(self, devices8,
                                                  monkeypatch):
        from estorch_tpu.parallel import sharded

        assert _loop_es(devices8[:1], 1).engine.pair_chunk == 4
        # room for one pair's widest activation and not for two
        monkeypatch.setattr(sharded, "ACTIVATION_BUDGET_BYTES",
                            3 * 4 * 21 * 48)
        es = _loop_es(devices8[:1], 1)
        assert (es.engine.pair_chunk, es.engine.n_pair_chunks) == (1, 4)
        es.train(1, verbose=False)
        assert np.isfinite(es.history[0]["reward_mean"])
        # a member is whole on its chip where the centre is gathered, and
        # ``model`` divides its widths where the centre stays split (a
        # chip with no room for it)
        whole = _loop_es(devices8[:4], 2)
        assert whole.engine.centre_form == "gathered"
        assert whole.engine._widest_activation() == 21 * 48
        monkeypatch.setattr(sharded, "CHIP_MEMORY_BYTES", 0)
        split = _loop_es(devices8[:4], 2)
        assert split.engine.centre_form == "split"
        assert split.engine._widest_activation() == 21 * 24

    @pytest.mark.parametrize("centre_form, across", [("gathered", 1),
                                                     ("split", 2)])
    def test_a_tied_head_reads_the_embedding_as_before(
            self, devices8, monkeypatch, centre_form, across):
        import lm_tiny
        from estorch_tpu.envs import TokenScoreEnv
        from estorch_tpu.parallel import sharded

        if centre_form == "split":
            monkeypatch.setattr(sharded, "CHIP_MEMORY_BYTES", 0)
        es = _loop_es(devices8[:4], 2, policy=HybridLM,
                      policy_kwargs=lm_tiny.TINY,
                      agent_kwargs={"env": TokenScoreEnv(**lm_tiny.ENV)})
        assert es.engine.centre_form == centre_form
        assert es.engine.policy.leaf_rows == {}
        assert es.engine._widest_activation() == 21 * 64 // across
