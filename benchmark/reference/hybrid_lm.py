"""Plain reference for one ES member of the Mamba-2 / grouped-query-attention
hybrid language model (IBM ``granitemoehybrid`` without experts): float32
``jax.numpy`` at ``highest`` matmul precision, written from the published
description and independent of the system's model code.  No batching over
members, no sharding, no engine.

What it follows, per layer (``config.json`` keys in quotes):

    x  = embedding_multiplier * E[tokens]
    x += residual_multiplier * mixer(rmsnorm(x))
    x += residual_multiplier * output_linear(silu(g) * u),
                               [g | u] = rmsnorm(x) @ input_linear
    logits = rmsnorm(x) @ E^T / logits_scaling            (tied embeddings)

Mamba-2 mixer, in the published FUSED layout: ``[z | xBC | dt] = u @ in_proj``
(``d_inner + (d_inner + 2 n_groups d_state) + n_heads`` columns), ``xBC =
silu(conv1d_causal(xBC) + bias)`` (depthwise, ``mamba_d_conv`` taps, the last
tap on the current step), ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``,
and the recurrence as a SEQUENTIAL ``lax.scan`` over time (not the chunked
form the system runs):

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t ,   y_t = C_t . h_t + D x_t

then ``y = rmsnorm(y * silu(z)) * w`` over the whole ``d_inner`` (one group)
and ``out = y @ out_proj``.  Attention: causal, ``num_key_value_heads`` shared
by groups of query heads, no positional encoding, scores scaled by
``attention_multiplier``, as one full masked softmax per head (computed a
key/value head at a time so that the ``[heads, T, T]`` scores of a 4k
sequence need not exist at once).  The head is computed in blocks of
positions for the same reason; neither changes a value.

Departures from the published model, all in the configuration file: depth
(one period of ten layers), one document per sequence (no packing), random
weights.  Left out of ``flops_per_member_step``: the scan's and the
attention's own multiply-adds (about 5% of a token's), so
``policy.flops_util`` understates by that much.

The member.  ES evaluates ``theta + sigma * sign * E``.  The SYSTEM keeps the
fused projections as separate leaves (``in_z, in_x, in_bc, in_dt``; ``gate,
up``; conv taps split the same way) so that each shards by head; its flat
vector is those leaves in sorted-key order (``system_layout``), and its
low-rank noise is laid out over the same leaves in the same order
(``noise_layout``: a 2-D leaf ``[m, n]`` with ``(m + n) r < m n`` reads ``A
[m, r]`` then ``B [n, r]`` and ``E = A B^T / sqrt(r)``; every other leaf
reads dense noise).  This file maps that vector onto the fused layout
(``Member.layer``): ``W + sigma * sign * E`` is MATERIALISED, one layer at a
time, from the same table and the same offsets.

Keying contract mirrored from the engine (``parallel/sharded.py``): with
``base = fold_in(state.key, generation)``, the offsets come from
``fold_in(base, 0)`` and the rollout keys from ``split(fold_in(base, 1),
pairs)``; members ``2k`` and ``2k+1`` share pair ``k``'s offset and key with
signs ``+1, -1``.  A pair's key picks its sequence:
``randint(key, (), 0, corpus_sequences)`` into the corpus
``randint(PRNGKey(corpus_seed), (corpus_sequences, seq_len), 0, vocab)``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import costs

MAMBA, ATTENTION = "mamba", "attention"
HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------------ sizes

def sizes(config: dict) -> dict:
    """The model's and the corpus's sizes AS BUILT: the keyword arguments
    the configuration file hands the policy and the environment."""
    kwargs = config["build"]["kwargs"]
    out = dict(kwargs["policy_kwargs"])
    out.update(kwargs["agent_kwargs"]["env"]["kwargs"])
    out["low_rank"] = kwargs["low_rank"]
    out.setdefault("attention_head_dim",
                   out["hidden_size"] // out["num_attention_heads"])
    return out


def _mamba_dims(s):
    d_inner = s["mamba_n_heads"] * s["mamba_d_head"]
    return d_inner, 2 * s["mamba_n_groups"] * s["mamba_d_state"]


def matmul_shapes(s: dict) -> tuple[list, list]:
    """``(layers' matmuls, the head's)`` a token passes, as ``(m, n)``."""
    h, ff = s["hidden_size"], s["shared_intermediate_size"]
    d_inner, bc = _mamba_dims(s)
    hd = s["attention_head_dim"]
    nq, nkv = s["num_attention_heads"], s["num_key_value_heads"]
    mlp = [(h, 2 * ff), (ff, h)]
    per_kind = {
        MAMBA: [(h, 2 * d_inner + bc + s["mamba_n_heads"]), (d_inner, h)],
        ATTENTION: [(h, nq * hd), (h, nkv * hd), (h, nkv * hd), (nq * hd, h)],
    }
    layers = [shape for kind in s["layer_types"]
              for shape in per_kind[kind] + mlp]
    return layers, [(h, s["vocab_size"])]


def system_layout(s: dict) -> list[tuple[str, tuple]]:
    """``[(path, shape), ...]`` of the system's flat parameter vector: its
    leaves in sorted-key order (upper case sorts first)."""
    h, ff, k = s["hidden_size"], s["shared_intermediate_size"], s["mamba_d_conv"]
    d_inner, bc = _mamba_dims(s)
    nh, hd = s["mamba_n_heads"], s["attention_head_dim"]
    nq, nkv = s["num_attention_heads"], s["num_key_value_heads"]
    mamba = [("A_log", (nh,)), ("D", (nh,)), ("conv_bc_bias", (bc,)),
             ("conv_bc_kernel", (k, 1, bc)), ("conv_x_bias", (d_inner,)),
             ("conv_x_kernel", (k, 1, d_inner)), ("dt_bias", (nh,)),
             ("in_bc", (h, bc)), ("in_dt", (h, nh)), ("in_x", (h, d_inner)),
             ("in_z", (h, d_inner)), ("norm_scale", (d_inner,)),
             ("out_proj", (d_inner, h))]
    attn = [("k", (h, nkv * hd)), ("o", (nq * hd, h)), ("q", (h, nq * hd)),
            ("v", (h, nkv * hd))]
    mlp = [("down", (ff, h)), ("gate", (h, ff)), ("up", (h, ff))]
    out = [("embed/embedding", (s["vocab_size"], h)), ("final_norm/scale", (h,))]
    for i, kind in enumerate(s["layer_types"]):
        base = f"layer_{i:02d}"
        mixer = ("mamba", mamba) if kind == MAMBA else ("attn", attn)
        out += [(f"{base}/{mixer[0]}/{n}", shape) for n, shape in mixer[1]]
        out += [(f"{base}/mlp/{n}", shape) for n, shape in mlp]
        out += [(f"{base}/norm1/scale", (h,)), (f"{base}/norm2/scale", (h,))]
    return out


def param_offsets(s: dict) -> dict[str, tuple[int, tuple]]:
    out, at = {}, 0
    for path, shape in system_layout(s):
        out[path] = (at, shape)
        at += math.prod(shape)
    out["__dim__"] = (at, ())
    return out


def noise_layout(s: dict) -> dict[str, tuple]:
    """``{path: ("lr", a_off, b_off) | ("dense", off)}`` and the length of
    one pair's noise vector under ``"__dim__"``."""
    r, out, at = s["low_rank"], {}, 0
    for path, shape in system_layout(s):
        if len(shape) == 2 and r * (shape[0] + shape[1]) < shape[0] * shape[1]:
            out[path] = ("lr", at, at + shape[0] * r)
            at += (shape[0] + shape[1]) * r
        else:
            out[path] = ("dense", at)
            at += math.prod(shape)
    out["__dim__"] = at
    return out


def describe(config: dict) -> dict:
    """What the harness needs to know: the length of the flat parameter
    vector, and 2 x the matmul weights one token passes
    (``costs.matmul_flops``; scan and attention FLOPs left out, see the
    module text), split into the layers' and the head's."""
    s = sizes(config)
    layers, head = matmul_shapes(s)
    return {"param_dim": param_offsets(s)["__dim__"][0],
            "noise_dim": noise_layout(s)["__dim__"],
            "flops_per_member_step": costs.matmul_flops(layers + head),
            "dense_flops_per_member_step": costs.matmul_flops(layers),
            "head_flops_per_member_step": costs.matmul_flops(head)}


# ------------------------------------------------------------------- init

def init_theta(key, config):
    """Seeded initial weights in the system's flat layout, made on the device
    in one jitted call (the Mamba-2 defaults, ``assumed`` in the
    configuration file): matrices and embedding normal with standard
    deviation 0.02, norm weights and ``D`` one, ``A_log = log U[1, 16]``,
    ``dt_bias`` the inverse softplus of a log-uniform step in [1e-3, 1e-1],
    conv taps and bias uniform in +-1/sqrt(d_conv)."""
    s = sizes(config)
    return _init_theta(key, tuple(system_layout(s)), s["mamba_d_conv"])


@jax.jit(static_argnums=(1, 2))
def _init_theta(key, layout, d_conv):
    parts = []
    bound = 1.0 / math.sqrt(d_conv)
    for i, (path, shape) in enumerate(layout):
        k, name = jax.random.fold_in(key, i), path.rsplit("/", 1)[1]
        if name in ("scale", "norm_scale", "D"):
            v = jnp.ones(shape, jnp.float32)
        elif name == "A_log":
            v = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            v = dt + jnp.log(-jnp.expm1(-dt))
        elif name.startswith("conv_"):
            v = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        else:
            v = 0.02 * jax.random.normal(k, shape, jnp.float32)
        parts.append(v.reshape(-1))
    return jnp.concatenate(parts)


# ------------------------------------------------------------ the member

def member_keys(state_key, generation, rows):
    base = jax.random.fold_in(state_key, generation)
    return jax.random.split(jax.random.fold_in(base, 1), rows)


def corpus(s: dict):
    return jax.random.randint(
        jax.random.PRNGKey(s["seed"]), (s["corpus_sequences"], s["seq_len"]),
        0, s["vocab_size"], dtype=jnp.int32)


def probe_ids(s: dict, bc_dim: int):
    return np.arange(bc_dim) * (s["vocab_size"] // bc_dim)


class Member:
    """One member's weights ``theta + sigma * sign * E``, a leaf at a time:
    ``theta`` is the centre's flat vector (host or device), ``noise`` the
    member's pair's slice of the table."""

    def __init__(self, s, theta, noise, scale):
        self.s, self.theta, self.noise, self.scale = s, theta, noise, scale
        self.at, self.noise_at = param_offsets(s), noise_layout(s)

    def leaf(self, path):
        off, shape = self.at[path]
        w = jnp.asarray(self.theta[off:off + math.prod(shape)],
                        jnp.float32).reshape(shape)
        if self.noise is None:
            return w
        entry, r = self.noise_at[path], self.s["low_rank"]
        if entry[0] == "lr":
            m, n = shape
            a = self.noise[entry[1]:entry[1] + m * r].reshape(m, r)
            b = self.noise[entry[2]:entry[2] + n * r].reshape(n, r)
            e = jnp.matmul(a, b.T, precision=HIGHEST) / math.sqrt(r)
        else:
            e = self.noise[entry[1]:entry[1] + math.prod(shape)].reshape(shape)
        return w + self.scale * e

    def layer(self, i, kind):
        """Layer ``i`` in the published FUSED layout."""
        base = f"layer_{i:02d}"
        get = lambda name: self.leaf(f"{base}/{name}")     # noqa: E731
        out = {"norm1": get("norm1/scale"), "norm2": get("norm2/scale"),
               "input_linear": jnp.concatenate(
                   [get("mlp/gate"), get("mlp/up")], axis=1),
               "output_linear": get("mlp/down")}
        if kind == MAMBA:
            out.update(
                in_proj=jnp.concatenate(
                    [get("mamba/in_z"), get("mamba/in_x"), get("mamba/in_bc"),
                     get("mamba/in_dt")], axis=1),
                conv_w=jnp.concatenate(
                    [get("mamba/conv_x_kernel")[:, 0, :],
                     get("mamba/conv_bc_kernel")[:, 0, :]], axis=1),
                conv_b=jnp.concatenate(
                    [get("mamba/conv_x_bias"), get("mamba/conv_bc_bias")]),
                A_log=get("mamba/A_log"), D=get("mamba/D"),
                dt_bias=get("mamba/dt_bias"), norm=get("mamba/norm_scale"),
                out_proj=get("mamba/out_proj"))
        else:
            out.update({n: get(f"attn/{n}") for n in "qkvo"})
        return out


# ---------------------------------------------------------------- forward

def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def mamba_mixer(s, p, u):
    t = u.shape[0]
    nh, hd, n = s["mamba_n_heads"], s["mamba_d_head"], s["mamba_d_state"]
    d_inner, k_taps = nh * hd, s["mamba_d_conv"]
    zxbcdt = mm(u, p["in_proj"])
    z = zxbcdt[:, :d_inner]
    xbc = zxbcdt[:, d_inner:2 * d_inner + 2 * n]
    dt = zxbcdt[:, 2 * d_inner + 2 * n:]
    padded = jnp.concatenate(
        [jnp.zeros((k_taps - 1, xbc.shape[1]), jnp.float32), xbc])
    conv = sum(p["conv_w"][k] * padded[k:k + t] for k in range(k_taps))
    xbc = jax.nn.silu(conv + p["conv_b"])
    x = xbc[:, :d_inner].reshape(t, nh, hd)
    b_mat, c_mat = xbc[:, d_inner:d_inner + n], xbc[:, d_inner + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])

    def step(h, xs):
        x_t, b_t, c_t, dt_t = xs
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return h, jnp.sum(h * c_t[None, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((nh, hd, n), jnp.float32),
                        (x, b_mat, c_mat, dt))
    y = (y + p["D"][:, None] * x).reshape(t, d_inner)
    y = rmsnorm(y * jax.nn.silu(z), p["norm"], s["rms_norm_eps"])
    return mm(y, p["out_proj"])


def attention_mixer(s, p, u):
    t = u.shape[0]
    nq, nkv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                   s["attention_head_dim"])
    group = nq // nkv
    q = mm(u, p["q"]).reshape(t, nkv, group, hd)
    k = mm(u, p["k"]).reshape(t, nkv, hd)
    v = mm(u, p["v"]).reshape(t, nkv, hd)
    mask = jnp.tril(jnp.ones((t, t), bool))

    def one_kv_head(xs):
        q_h, k_h, v_h = xs                     # [t, group, hd], [t, hd] x 2
        scores = jnp.einsum("qgd,sd->gqs", q_h, k_h, precision=HIGHEST)
        scores = jnp.where(mask, scores * s["attention_multiplier"], -jnp.inf)
        return jnp.einsum("gqs,sd->qgd", jax.nn.softmax(scores, axis=-1),
                          v_h, precision=HIGHEST)

    ctx = jax.lax.map(one_kv_head, (q.transpose(1, 0, 2, 3),
                                    k.transpose(1, 0, 2),
                                    v.transpose(1, 0, 2)))
    return mm(ctx.transpose(1, 0, 2, 3).reshape(t, nq * hd), p["o"])


def _layer(s, kind, p, x):
    mixer = mamba_mixer if kind == MAMBA else attention_mixer
    x = x + s["residual_multiplier"] * mixer(
        s, p, rmsnorm(x, p["norm1"], s["rms_norm_eps"]))
    gu = mm(rmsnorm(x, p["norm2"], s["rms_norm_eps"]), p["input_linear"])
    ff = gu.shape[1] // 2
    return x + s["residual_multiplier"] * mm(
        jax.nn.silu(gu[:, :ff]) * gu[:, ff:], p["output_linear"])


def _head(s, embedding, final_norm, x, tokens, block):
    """``(log p of each next token [T-1], the last position's logits)``."""
    t = x.shape[0]
    hN = rmsnorm(x, final_norm, s["rms_norm_eps"])
    n_blocks = -(-t // block)
    pad = n_blocks * block - t
    targets = jnp.pad(tokens[1:], (0, pad + 1))

    def score(xs):
        h_b, tgt = xs
        logits = mm(h_b, embedding.T) / s["logits_scaling"]
        return (jnp.take_along_axis(logits, tgt[:, None], axis=1)[:, 0]
                - jax.nn.logsumexp(logits, axis=-1))

    logp = jax.lax.map(score, (
        jnp.pad(hN, ((0, pad), (0, 0))).reshape(n_blocks, block, -1),
        targets.reshape(n_blocks, block)))
    last = mm(hN[-1:], embedding.T)[0] / s["logits_scaling"]
    return logp.reshape(-1)[:t - 1], last


def forward(s: dict, member: Member, tokens, head_block: int = 512):
    """One member over one sequence ``tokens [T]``: ``(next-token log p
    [T-1], last logits [vocab])``.  One layer's weights exist at a time."""
    kinds = tuple(s["layer_types"])
    frozen = _freeze(s)
    embedding = member.leaf("embed/embedding")
    x = s["embedding_multiplier"] * jnp.take(embedding, tokens, axis=0)
    for i, kind in enumerate(kinds):
        x = _jit_layer(frozen, kind, member.layer(i, kind), x)
    return _jit_head(frozen, embedding, member.leaf("final_norm/scale"), x,
                     tokens, min(head_block, tokens.shape[0]))


def logits(s: dict, member: Member, tokens):
    """The whole ``[T, vocab]`` logits (small sizes: the tier-1 tests)."""
    embedding = member.leaf("embed/embedding")
    x = s["embedding_multiplier"] * jnp.take(embedding, tokens, axis=0)
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(s["layer_types"]):
            x = _layer(s, kind, member.layer(i, kind), x)
        hN = rmsnorm(x, member.leaf("final_norm/scale"), s["rms_norm_eps"])
        return mm(hN, embedding.T) / s["logits_scaling"]


def _freeze(s):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in s.items()))


@jax.jit(static_argnums=(0, 1))
def _jit_layer(frozen, kind, p, x):
    with jax.default_matmul_precision("highest"):
        return _layer(dict(frozen), kind, p, x)


@jax.jit(static_argnums=(0, 5))
def _jit_head(frozen, embedding, final_norm, x, tokens, block):
    with jax.default_matmul_precision("highest"):
        return _head(dict(frozen), embedding, final_norm, x, tokens, block)


def score_members(s, theta, table, offsets, signs, keys, sigma, bc_dim):
    """``(fitness (k,), behaviour (k, bc_dim))`` of ``k`` members, one after
    the other: fitness is the mean log p of the next token over the
    member's sequence, behaviour the last position's logits at the probe
    ids.  ``offsets``, ``signs`` and ``keys`` are per member."""
    noise_dim = noise_layout(s)["__dim__"]
    all_tokens = corpus(s)
    ids = jnp.asarray(probe_ids(s, bc_dim))
    fits, bcs = [], []
    for off, sign, key in zip(np.asarray(offsets), np.asarray(signs), keys):
        noise = jax.lax.dynamic_slice(table, (int(off),), (noise_dim,))
        row = jax.random.randint(key, (), 0, s["corpus_sequences"])
        member = Member(s, theta, noise, jnp.float32(sigma) * float(sign))
        logp, last = forward(s, member, all_tokens[row])
        fits.append(float(jnp.mean(logp)))
        bcs.append(np.asarray(jnp.take(last, ids)))
    return np.asarray(fits, np.float32), np.stack(bcs)
