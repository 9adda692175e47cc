"""Plain reference for one ES member of the SambaY decoder with differential
attention (Microsoft ``phi4flash``: Phi-4-mini-flash-reasoning; arXiv
2507.06607 and 2410.05258): float32 ``jax.numpy`` at ``highest`` matmul
precision, written from the published description and independent of the
system's model code.  No batching over members, no sharding, no engine.

What it follows, per layer ``i`` of the ``L`` published (``config.json`` keys
in quotes; the Mamba sizes are ``configuration_phi4flash.py``'s defaults,
``assumed`` in the configuration file):

    x  = E[tokens]
    x += mixer_i(LN1(x));   x += down(silu(g) * u), [g | u] = LN2(x) @ gate_up
    logits = LN_f(x) @ E^T                          (tied, LayerNorm with bias)

The mixer of layer ``i`` ("mb_per_layer" 2):

- ``i`` even, below ``L/2``, and ``i = L/2``: Mamba-1 in the published FUSED
  layout: ``[x | z] = u @ in_proj``; ``x = silu(conv1d_causal(x) + b)``
  (depthwise, "mamba_d_conv" taps, the last on the current step); ``[dt | B |
  C] = x @ x_proj`` (dt_rank + 2 d_state columns); ``dt = softplus(dt @
  dt_proj + dt_bias)``; ``A = -exp(A_log)  [d_inner, d_state]``; the
  recurrence as a SEQUENTIAL ``lax.scan`` over time of the ``[d_inner,
  d_state]`` state,

      h_t = exp(dt_t (x) A) * h_{t-1} + (dt_t * x_t) (x) B_t,   y_t = h_t C_t + D * x_t

  and ``out = (y * silu(z)) @ out_proj``.  Layer ``L/2`` also keeps ``m = y``
  (before the gate, with the ``D`` term) for the layers above.
- ``i`` odd, below ``L/2``: differential attention over the keys ``(t -
  "sliding_window", t]``; ``i = L/2 + 1``: the same, full causal, and its
  keys and values are kept for the layers above.
- ``i`` even, above ``L/2``: a Gated Memory Unit, ``(silu(u @ W1) * m) @ W2``.
- ``i`` odd, above ``L/2 + 1``: differential attention whose only projections
  are ``Wq`` and ``Wo``; its keys and values are layer ``L/2 + 1``'s.

Differential attention (``H`` query and ``G`` key/value heads of ``d``):
``[q | k | v] = u @ Wqkv + b``; ``q -> [T, H/2, 2, d]``, ``k -> [T, G/2, 2,
d]``, ``v -> [T, G/2, 2d]``: adjacent heads pair, ``q1, q2 = q[:, :, 0], q[:,
:, 1]`` and likewise ``k1, k2``; diff-head ``j`` reads key/value pair ``j //
(H/G)``;

    o_j = (softmax(q1_j k1^T / sqrt d) - lambda softmax(q2_j k2^T / sqrt d)) v
    lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init,
    lambda_init = 0.8 - 0.6 exp(-0.3 i)          (i the PUBLISHED layer index)
    o_j <- rmsnorm(o_j; gamma [2d], eps) * (1 - lambda_init);   out = concat(o) @ Wo + bo

as two full masked softmaxes a diff-head, over a block of query rows at a
time so that no ``[T, T]`` array of an 8k sequence exists for more than one
head.  The head is computed in blocks of positions for the same reason;
neither changes a value.

Departures from the published model, all in the configuration file: depth
(six of 32 layers: one period of each decoder and the two boundary layers,
each with the ``lambda_init`` of its published index), an eighth of the
vocabulary, one document per sequence (no packing), random weights.  Left out
of ``flops_per_member_step``: the scan's and the attention's own
multiply-adds, so ``policy.flops_util`` understates by their share
(``benchmark/costs_sambay.py`` counts the attention's exactly).

The member.  ES evaluates ``theta + sigma * sign * E``.  The system's flat
vector is its leaves in sorted-key order (``system_layout``): its ``in_proj``
and ``qkv`` are fused as published, its FFN keeps ``gate`` and ``up`` apart
(fused here into ``gate_up``).  Its low-rank noise is laid out over the same
leaves in the same order (``noise_layout``: a 2-D leaf ``[m, n]`` with ``(m +
n) r < m n`` reads ``A [m, r]`` then ``B [n, r]`` and ``E = A B^T / sqrt(r)``;
every other leaf, and ``A_log`` although it is 2-D, reads dense noise).
``W + sigma * sign * E`` is MATERIALISED, one layer at a time, from the same
table and the same offsets.

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

MAMBA, WINDOW, MAMBA_MEM, FULL_KV, GMU, CROSS = (
    "mamba", "window", "mamba_mem", "full_kv", "gmu", "cross")
HIGHEST = jax.lax.Precision.HIGHEST
# query rows a diff-head's two score maps are made for at a time
ATTENTION_ROWS = 1024


# ------------------------------------------------------------------ sizes

def sizes(config: dict) -> dict:
    """The model's and the corpus's sizes AS BUILT: the keyword arguments
    the configuration file hands the policy and the environment."""
    kwargs = config["build"]["kwargs"]
    out = dict(kwargs["policy_kwargs"])
    out.update(kwargs["agent_kwargs"]["env"]["kwargs"])
    out["low_rank"] = kwargs["low_rank"]
    out.setdefault("mamba_d_state", 16)
    out.setdefault("mamba_d_conv", 4)
    out.setdefault("mamba_expand", 2)
    if out.get("mamba_dt_rank") is None:
        out["mamba_dt_rank"] = -(-out["hidden_size"] // 16)
    out.setdefault("layer_norm_eps", 1e-5)
    out.setdefault("sliding_window", 512)
    if out.get("layer_indices") is None:
        out["layer_indices"] = list(range(out["published_layers"]))
    out["layer_types"] = [published_kinds(out["published_layers"])[i]
                          for i in out["layer_indices"]]
    return out


def published_kinds(n_layers: int) -> list[str]:
    """The kind of every layer of the published ``n_layers`` deep model."""
    half = n_layers // 2
    kinds = []
    for i in range(n_layers):
        if i < half:
            kinds.append(MAMBA if i % 2 == 0 else WINDOW)
        elif i == half:
            kinds.append(MAMBA_MEM)
        elif i == half + 1:
            kinds.append(FULL_KV)
        else:
            kinds.append(GMU if i % 2 == 0 else CROSS)
    return kinds


def _dims(s):
    hd = s["hidden_size"] // s["num_attention_heads"]
    return (s["hidden_size"], s["mamba_expand"] * s["hidden_size"],
            s["mamba_d_state"], s["mamba_dt_rank"],
            s["num_attention_heads"] * hd, s["num_key_value_heads"] * hd, hd)


def matmul_shapes(s: dict) -> tuple[list, list]:
    """``(layers' matmuls, the head's)`` a token passes, as ``(m, n)``."""
    h, d, n, r, q, kv, _ = _dims(s)
    ff = s["intermediate_size"]
    mamba = [(h, 2 * d), (d, r + 2 * n), (r, d), (d, h)]
    per_kind = {MAMBA: mamba, MAMBA_MEM: mamba,
                WINDOW: [(h, q + 2 * kv), (q, h)],
                FULL_KV: [(h, q + 2 * kv), (q, h)],
                GMU: [(h, d), (d, h)], CROSS: [(h, q), (q, h)]}
    layers = [shape for kind in s["layer_types"]
              for shape in per_kind[kind] + [(h, 2 * ff), (ff, h)]]
    return layers, [(h, s["vocab_size"])]


def system_layout(s: dict) -> list[tuple[str, tuple]]:
    """``[(path, shape), ...]`` of the system's flat parameter vector: its
    leaves in sorted-key order (upper case sorts first)."""
    h, d, n, r, q, kv, hd = _dims(s)
    ff, k = s["intermediate_size"], s["mamba_d_conv"]
    lambdas = [(f"lambda_{x}", (hd,)) for x in ("k1", "k2", "q1", "q2")]
    mamba = [("A_log", (d, n)), ("D", (d,)), ("conv_bias", (d,)),
             ("conv_kernel", (k, 1, d)), ("dt_bias", (d,)),
             ("dt_proj", (r, d)), ("in_proj", (h, 2 * d)),
             ("out_proj", (d, h)), ("x_proj", (d, r + 2 * n))]
    attn = lambdas + [("o", (q, h)), ("o_bias", (h,)),
                      ("qkv", (h, q + 2 * kv)), ("qkv_bias", (q + 2 * kv,)),
                      ("subln", (2 * hd,))]
    cross = lambdas + [("o", (q, h)), ("o_bias", (h,)), ("q", (h, q)),
                       ("q_bias", (q,)), ("subln", (2 * hd,))]
    mixers = {MAMBA: ("mamba", mamba), MAMBA_MEM: ("mamba", mamba),
              WINDOW: ("attn", attn), FULL_KV: ("attn", attn),
              GMU: ("gmu", [("gmu_in", (h, d)), ("gmu_out", (d, h))]),
              CROSS: ("attn", cross)}
    mlp = [("down", (ff, h)), ("gate", (h, ff)), ("up", (h, ff))]
    out = [("embed/embedding", (s["vocab_size"], h)),
           ("final_norm/bias", (h,)), ("final_norm/scale", (h,))]
    for i, kind in enumerate(s["layer_types"]):
        base = f"layer_{i:02d}"
        key, leaves = mixers[kind]
        out += [(f"{base}/{key}/{name}", shape) for name, shape in leaves]
        out += [(f"{base}/mlp/{name}", shape) for name, shape in mlp]
        out += [(f"{base}/{norm}/{name}", (h,))
                for norm in ("norm1", "norm2") for name in ("bias", "scale")]
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
        if (len(shape) == 2 and not path.endswith("/A_log")
                and r * (shape[0] + shape[1]) < shape[0] * shape[1]):
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
    in one jitted call (``assumed`` in the configuration file): matrices and
    embedding normal with standard deviation 0.02; norm weights, ``subln``
    and ``D`` one, every bias zero; the Mamba-1 defaults ``A_log = log(1 ...
    d_state)`` for every channel, ``dt_bias`` the inverse softplus of a
    log-uniform step in [1e-3, 1e-1], conv taps and bias uniform in
    +-1/sqrt(d_conv); the lambda vectors normal 0.1.  (The two spreads are
    the policy's ``init_std`` and ``lambda_std`` where the build gives them:
    a rehearsal at tiny widths widens them so that its logits spread as the
    published widths' do.)"""
    s = sizes(config)
    return _init_theta(key, tuple(system_layout(s)), s["mamba_d_conv"],
                       s.get("init_std", 0.02), s.get("lambda_std", 0.1))


@jax.jit(static_argnums=(1, 2, 3, 4))
def _init_theta(key, layout, d_conv, std, lambda_std):
    parts = []
    bound = 1.0 / math.sqrt(d_conv)
    for i, (path, shape) in enumerate(layout):
        k, name = jax.random.fold_in(key, i), path.rsplit("/", 1)[1]
        if name in ("scale", "subln", "D"):
            v = jnp.ones(shape, jnp.float32)
        elif name in ("bias", "qkv_bias", "q_bias", "o_bias"):
            v = jnp.zeros(shape, jnp.float32)
        elif name == "A_log":
            v = jnp.tile(jnp.log(jnp.arange(1, shape[1] + 1,
                                            dtype=jnp.float32)), (shape[0], 1))
        elif name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            v = dt + jnp.log(-jnp.expm1(-dt))
        elif name.startswith("conv_"):
            v = jax.random.uniform(k, shape, jnp.float32, -bound, bound)
        elif name.startswith("lambda_"):
            v = lambda_std * jax.random.normal(k, shape, jnp.float32)
        else:
            v = std * jax.random.normal(k, shape, jnp.float32)
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
        """Held layer ``i`` with the published names, FFN fused."""
        base = f"layer_{i:02d}"
        get = lambda name: self.leaf(f"{base}/{name}")     # noqa: E731
        out = {f"{norm}_{x}": get(f"{norm}/{'scale' if x == 'w' else 'bias'}")
               for norm in ("norm1", "norm2") for x in ("w", "b")}
        out.update(gate_up=jnp.concatenate(
            [get("mlp/gate"), get("mlp/up")], axis=1), down=get("mlp/down"))
        if kind in (MAMBA, MAMBA_MEM):
            out.update({n: get(f"mamba/{n}") for n in (
                "in_proj", "x_proj", "dt_proj", "dt_bias", "A_log", "D",
                "out_proj")})
            out.update(conv_w=get("mamba/conv_kernel")[:, 0, :],
                       conv_b=get("mamba/conv_bias"))
        elif kind == GMU:
            out.update(W1=get("gmu/gmu_in"), W2=get("gmu/gmu_out"))
        else:
            out.update({n: get(f"attn/{n}") for n in (
                "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "subln")})
            out.update(Wo=get("attn/o"), bo=get("attn/o_bias"))
            if kind == CROSS:
                out.update(Wq=get("attn/q"), bq=get("attn/q_bias"))
            else:
                out.update(Wqkv=get("attn/qkv"), bqkv=get("attn/qkv_bias"))
        return out


# ---------------------------------------------------------------- forward

def layernorm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def mamba_mixer(s, p, u):
    """``(the mixer's output [T, hidden], y [T, d_inner])``."""
    t = u.shape[0]
    _, d, n, r, _, _, _ = _dims(s)
    k_taps = s["mamba_d_conv"]
    xz = mm(u, p["in_proj"])
    x, z = xz[:, :d], xz[:, d:]
    padded = jnp.concatenate([jnp.zeros((k_taps - 1, d), jnp.float32), x])
    x = jax.nn.silu(sum(p["conv_w"][k] * padded[k:k + t]
                        for k in range(k_taps)) + p["conv_b"])
    dbc = mm(x, p["x_proj"])
    dt = jax.nn.softplus(mm(dbc[:, :r], p["dt_proj"]) + p["dt_bias"])
    b_mat, c_mat = dbc[:, r:r + n], dbc[:, r + n:]
    a = -jnp.exp(p["A_log"])                                # [d, n]

    def step(h, xs):
        x_t, dt_t, b_t, c_t = xs
        h = (jnp.exp(dt_t[:, None] * a) * h
             + (dt_t * x_t)[:, None] * b_t[None, :])
        return h, jnp.sum(h * c_t[None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((d, n), jnp.float32),
                        (x, dt, b_mat, c_mat))
    y = y + p["D"] * x
    return mm(y * jax.nn.silu(z), p["out_proj"]), y


def differential_attention(s, p, q, k, v, index, window):
    """The differential attention of ``q [T, H d]`` over ``k [T, G d]``, ``v
    [T, G d]`` for published layer ``index``, keys ``(t - window, t]`` where
    ``window`` is given, up to and including the output projection."""
    t = q.shape[0]
    _, _, _, _, qw, kvw, hd = _dims(s)
    nq, nkv = qw // hd, kvw // hd
    rows = min(ATTENTION_ROWS, t)
    n_blocks = -(-t // rows)
    pad = n_blocks * rows - t
    q = jnp.pad(q, ((0, pad), (0, 0))).reshape(n_blocks, rows, nq // 2, 2, hd)
    k = k.reshape(t, nkv // 2, 2, hd)
    v = v.reshape(t, nkv // 2, 2 * hd)
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam_init)
    key_at = jnp.arange(t)[None, :]

    def one(xs):
        j, block = xs
        pair = j // (nq // nkv)
        query_at = (block * rows + jnp.arange(rows))[:, None]
        mask = key_at <= query_at
        if window is not None:
            mask = mask & (key_at > query_at - window)
        q_b = jax.lax.dynamic_index_in_dim(
            jax.lax.dynamic_index_in_dim(q, block, 0, False), j, 1, False)
        k_p = jax.lax.dynamic_index_in_dim(k, pair, 1, False)
        v_p = jax.lax.dynamic_index_in_dim(v, pair, 1, False)

        def softmax_map(m):
            scores = mm(q_b[:, m], k_p[:, m].T) / math.sqrt(hd)
            return jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)

        o = mm(softmax_map(0) - lam * softmax_map(1), v_p)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + s["layer_norm_eps"]) * p["subln"]
        return o * (1.0 - lam_init)

    heads, blocks = jnp.meshgrid(jnp.arange(nq // 2), jnp.arange(n_blocks),
                                 indexing="ij")
    o = jax.lax.map(one, (heads.reshape(-1), blocks.reshape(-1)))
    o = o.reshape(nq // 2, n_blocks * rows, 2 * hd)[:, :t]
    return mm(o.transpose(1, 0, 2).reshape(t, qw), p["Wo"]) + p["bo"]


def _layer(s, kind, index, p, x, memory, kv):
    """``(x after the layer, what it hands upward or None)``."""
    eps = s["layer_norm_eps"]
    _, _, _, _, qw, kvw, _ = _dims(s)
    u = layernorm(x, p["norm1_w"], p["norm1_b"], eps)
    handed = None
    if kind in (MAMBA, MAMBA_MEM):
        out, handed = mamba_mixer(s, p, u)
    elif kind == GMU:
        out = mm(jax.nn.silu(mm(u, p["W1"])) * memory, p["W2"])
    elif kind == CROSS:
        out = differential_attention(s, p, mm(u, p["Wq"]) + p["bq"], *kv,
                                     index, None)
    else:
        qkv = mm(u, p["Wqkv"]) + p["bqkv"]
        handed = (qkv[:, qw:qw + kvw], qkv[:, qw + kvw:])
        out = differential_attention(
            s, p, qkv[:, :qw], *handed, index,
            s["sliding_window"] if kind == WINDOW else None)
    x = x + out
    gu = mm(layernorm(x, p["norm2_w"], p["norm2_b"], eps), p["gate_up"])
    ff = gu.shape[1] // 2
    return x + mm(jax.nn.silu(gu[:, :ff]) * gu[:, ff:], p["down"]), handed


def _hidden(s, member, tokens, layer_fn, embedding):
    """The residual stream after the last held layer; ``m`` and ``(K, V)``
    go from the boundary layers to the layers above them."""
    x = jnp.take(embedding, tokens, axis=0)
    memory = kv = None
    for i, (kind, index) in enumerate(zip(s["layer_types"],
                                          s["layer_indices"])):
        x, handed = layer_fn(_freeze(s), kind, index, member.layer(i, kind),
                             x, memory, kv)
        if kind == MAMBA_MEM:
            memory = handed
        if kind == FULL_KV:
            kv = handed
    return x


def _head(s, embedding, norm_w, norm_b, x, tokens, block):
    """``(log p of each next token [T-1], the last position's logits)``."""
    t = x.shape[0]
    h_n = layernorm(x, norm_w, norm_b, s["layer_norm_eps"])
    n_blocks = -(-t // block)
    pad = n_blocks * block - t
    targets = jnp.pad(tokens[1:], (0, pad + 1))

    def score(xs):
        h_b, tgt = xs
        logits = mm(h_b, embedding.T)
        return (jnp.take_along_axis(logits, tgt[:, None], axis=1)[:, 0]
                - jax.nn.logsumexp(logits, axis=-1))

    logp = jax.lax.map(score, (
        jnp.pad(h_n, ((0, pad), (0, 0))).reshape(n_blocks, block, -1),
        targets.reshape(n_blocks, block)))
    return logp.reshape(-1)[:t - 1], mm(h_n[-1:], embedding.T)[0]


def forward(s: dict, member: Member, tokens, head_block: int = 512):
    """One member over one sequence ``tokens [T]``: ``(next-token log p
    [T-1], last logits [vocab])``.  One layer's weights exist at a time."""
    embedding = member.leaf("embed/embedding")
    x = _hidden(s, member, tokens, _jit_layer, embedding)
    return _jit_head(_freeze(s), embedding, member.leaf("final_norm/scale"),
                     member.leaf("final_norm/bias"), x, tokens,
                     min(head_block, tokens.shape[0]))


def logits(s: dict, member: Member, tokens):
    """The whole ``[T, vocab]`` logits (small sizes: the tier-1 tests)."""
    embedding = member.leaf("embed/embedding")
    with jax.default_matmul_precision("highest"):
        x = _hidden(s, member, tokens,
                    lambda frozen, *rest: _layer(dict(frozen), *rest),
                    embedding)
        h_n = layernorm(x, member.leaf("final_norm/scale"),
                        member.leaf("final_norm/bias"), s["layer_norm_eps"])
        return mm(h_n, embedding.T)


def carried(s: dict, member: Member, tokens):
    """``(m [T, d_inner], (K [T, G d], V [T, G d]))``: what the two boundary
    layers hand upward (small sizes: the tier-1 tests)."""
    out = {}
    embedding = member.leaf("embed/embedding")

    def layer_fn(frozen, kind, *rest):
        x, handed = _layer(dict(frozen), kind, *rest)
        if kind in (MAMBA_MEM, FULL_KV):
            out[kind] = handed
        return x, handed

    with jax.default_matmul_precision("highest"):
        _hidden(s, member, tokens, layer_fn, embedding)
    return out[MAMBA_MEM], out[FULL_KV]


def _freeze(s):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in s.items()))


@jax.jit(static_argnums=(0, 1, 2))
def _jit_layer(frozen, kind, index, p, x, memory, kv):
    with jax.default_matmul_precision("highest"):
        return _layer(dict(frozen), kind, index, p, x, memory, kv)


@jax.jit(static_argnums=(0, 6))
def _jit_head(frozen, embedding, norm_w, norm_b, x, tokens, block):
    with jax.default_matmul_precision("highest"):
        return _head(dict(frozen), embedding, norm_w, norm_b, x, tokens,
                     block)


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
