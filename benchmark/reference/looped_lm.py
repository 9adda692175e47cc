"""Plain reference for one ES member of a looped decoder language model
(ByteDance ``ouro``: "Scaling Latent Reasoning via Looped Language Models"):
float32 ``jax.numpy`` at ``highest`` matmul precision, written from the
published description and independent of the system's model code.  No
batching over members, no sharding, no engine, no scan over the passes:
Python loops over passes and layers.

What it follows (``config.json`` keys in quotes).  ``config.json`` gives the
sizes; the parts marked (*) are the published model's (paper section 3 and
the repository's ``modeling_ouro.py``) and are listed under ``assumed`` in
the configuration file, because ``config.json`` does not spell them:

    x = E[tokens]
    for s = 1 .. "total_ut_steps":           the SAME weights in every pass
        for each of "num_hidden_layers" layers:
            x += rmsnorm_2( attention( rmsnorm_1(x) ) )       (*) sandwich
            x += rmsnorm_4( W_down( silu(W_gate u) * W_up u ) ),
                                          u = rmsnorm_3(x)    (*) norms
        h_s = rmsnorm_final(x);  x = h_s     (*) the normed state is what
                                                 the next pass reads
        logits_s = h_s W_head                "tie_word_embeddings" false
        lambda_s = sigmoid(h_s w_gate + b_gate)               (*) exit gate
    p_s = lambda_s prod_{j<s}(1 - lambda_j)  for s < S,
    p_S = prod_{j<S}(1 - lambda_j)           (*) exit distribution, per
                                                 position; sums to 1

Attention: ``num_attention_heads`` query and ``num_key_value_heads``
key/value heads of ``head_dim``; queries and keys rotated by position
(rotary embedding, ``rope_theta``, the halves convention of the published
code: ``x cos + rotate_half(x) sin``, ``inv_freq_i = rope_theta^(-2i /
head_dim)``); causal, scores scaled by ``1 / sqrt(head_dim)``, as ONE full
masked softmax per head (a key/value head at a time, so that the ``[heads, T,
T]`` scores of a 4k sequence need not exist at once).  RMSNorm with
``rms_norm_eps``.  The head is computed in blocks of positions for the same
reason; neither changes a value.

The member's score (the policy output the environment turns into fitness) is
the paper's expected task loss under the exit distribution, sign turned:
``sum_s p_s log softmax(logits_s)[next token]`` per position; the behaviour
logits are the LAST pass's at the last position.  Departures, all in the
configuration file: depth (8 of 48 layers), the loss's entropy term (its
coefficient is a training setting the config does not give) is left out, no
pass is skipped (``early_exit_threshold`` 1.0), random weights.  Left out of
``flops_per_member_step``: attention's own multiply-adds, the gate's 2,048
and the last position's extra head row.

The member.  ES evaluates ``theta + sigma * sign * E``.  The system's flat
vector is its leaves in sorted-key order (``system_layout``), and its
low-rank noise is laid out over the same leaves in the same order
(``noise_layout``: a 2-D leaf ``[m, n]`` with ``(m + n) r < m n`` reads ``A
[m, r]`` then ``B [n, r]`` and ``E = A B^T / sqrt(r)``; every other leaf,
the gate's ``[hidden, 1]`` kernel among them, reads dense noise).  ``W +
sigma * sign * E`` is MATERIALISED per leaf from the same table and the same
offsets, and the same materialised leaf serves all passes (a layer's leaves
are built again in each pass only so that one layer's weights exist at a
time).

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

HIGHEST = jax.lax.Precision.HIGHEST
NORMS = ("norm1", "norm2", "norm3", "norm4")


# ------------------------------------------------------------------ sizes

def sizes(config: dict) -> dict:
    """The model's and the corpus's sizes AS BUILT: the keyword arguments
    the configuration file hands the policy and the environment."""
    kwargs = config["build"]["kwargs"]
    out = dict(kwargs["policy_kwargs"])
    out.update(kwargs["agent_kwargs"]["env"]["kwargs"])
    out["low_rank"] = kwargs["low_rank"]
    out.setdefault("head_dim",
                   out["hidden_size"] // out["num_attention_heads"])
    out.setdefault("total_ut_steps", 4)
    out.setdefault("rope_theta", 10000.0)
    out.setdefault("rms_norm_eps", 1e-6)
    return out


def matmul_shapes(s: dict) -> tuple[list, list]:
    """``(layers' matmuls, the heads')`` a token passes in ALL passes, as
    ``(m, n)``."""
    h, ff, hd = s["hidden_size"], s["intermediate_size"], s["head_dim"]
    nq, nkv = s["num_attention_heads"], s["num_key_value_heads"]
    layer = [(h, nq * hd), (h, nkv * hd), (h, nkv * hd), (nq * hd, h),
             (h, 2 * ff), (ff, h)]
    passes = s["total_ut_steps"]
    return (layer * (len(s["layer_types"]) * passes),
            [(h, s["vocab_size"])] * passes)


def system_layout(s: dict) -> list[tuple[str, tuple]]:
    """``[(path, shape), ...]`` of the system's flat parameter vector: its
    leaves in sorted-key order."""
    h, ff, hd = s["hidden_size"], s["intermediate_size"], s["head_dim"]
    nq, nkv, v = (s["num_attention_heads"], s["num_key_value_heads"],
                  s["vocab_size"])
    out = [("embed/embedding", (v, h)), ("exit_gate/bias", (1,)),
           ("exit_gate/kernel", (h, 1)), ("final_norm/scale", (h,)),
           ("head/kernel", (h, v))]
    for i in range(len(s["layer_types"])):
        base = f"layer_{i:02d}"
        out += [(f"{base}/attn/k", (h, nkv * hd)),
                (f"{base}/attn/o", (nq * hd, h)),
                (f"{base}/attn/q", (h, nq * hd)),
                (f"{base}/attn/v", (h, nkv * hd)),
                (f"{base}/mlp/down", (ff, h)), (f"{base}/mlp/gate", (h, ff)),
                (f"{base}/mlp/up", (h, ff))]
        out += [(f"{base}/{n}/scale", (h,)) for n in NORMS]
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
    vector, and 2 x the matmul weights one token passes in all
    ``total_ut_steps`` passes (``costs.matmul_flops``; attention's own and
    the gate's left out, see the module text), split into the layers' and
    the heads'."""
    s = sizes(config)
    layers, heads = matmul_shapes(s)
    return {"param_dim": param_offsets(s)["__dim__"][0],
            "noise_dim": noise_layout(s)["__dim__"],
            "flops_per_member_step": costs.matmul_flops(layers + heads),
            "dense_flops_per_member_step": costs.matmul_flops(layers),
            "head_flops_per_member_step": costs.matmul_flops(heads)}


# ------------------------------------------------------------------- init

def init_theta(key, config):
    """Seeded initial weights in the system's flat layout, made on the device
    in one jitted call (``assumed`` in the configuration file): matrices,
    embedding, head and the gate's kernel normal with standard deviation
    0.02, norm weights one, the gate's bias zero."""
    return _init_theta(key, tuple(system_layout(sizes(config))))


@jax.jit(static_argnums=(1,))
def _init_theta(key, layout):
    parts = []
    for i, (path, shape) in enumerate(layout):
        name = path.rsplit("/", 1)[1]
        if name == "scale":
            v = jnp.ones(shape, jnp.float32)
        elif name == "bias":
            v = jnp.zeros(shape, jnp.float32)
        else:
            v = 0.02 * jax.random.normal(jax.random.fold_in(key, i), shape,
                                         jnp.float32)
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
    member's pair's slice of the table (``None``: the centre alone)."""

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

    def layer(self, i):
        base = f"layer_{i:02d}"
        out = {n: self.leaf(f"{base}/attn/{n}") for n in "qkvo"}
        out.update({n: self.leaf(f"{base}/mlp/{n}")
                    for n in ("gate", "up", "down")})
        out.update({n: self.leaf(f"{base}/{n}/scale") for n in NORMS})
        return out


# ---------------------------------------------------------------- forward

def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def rotary(s: dict, length: int):
    """``(cos, sin) [T, head_dim]`` as the published code builds them: the
    angles of the ``head_dim / 2`` frequencies, repeated over both halves."""
    hd = s["head_dim"]
    inv_freq = s["rope_theta"] ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    angle = np.arange(length, dtype=np.float64)[:, None] * inv_freq[None, :]
    angle = np.concatenate([angle, angle], axis=1)
    return (jnp.asarray(np.cos(angle), jnp.float32),
            jnp.asarray(np.sin(angle), jnp.float32))


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def attention(s, p, u, cos, sin):
    t = u.shape[0]
    nq, nkv, hd = (s["num_attention_heads"], s["num_key_value_heads"],
                   s["head_dim"])
    group = nq // nkv
    q = mm(u, p["q"]).reshape(t, nq, hd)
    k = mm(u, p["k"]).reshape(t, nkv, hd)
    v = mm(u, p["v"]).reshape(t, nkv, hd)
    q = q * cos[:, None, :] + rotate_half(q) * sin[:, None, :]
    k = k * cos[:, None, :] + rotate_half(k) * sin[:, None, :]
    mask = jnp.tril(jnp.ones((t, t), bool))

    def one_kv_head(xs):
        q_h, k_h, v_h = xs                     # [t, group, hd], [t, hd] x 2
        scores = jnp.einsum("qgd,sd->gqs", q_h, k_h, precision=HIGHEST)
        scores = jnp.where(mask, scores / math.sqrt(hd), -jnp.inf)
        return jnp.einsum("gqs,sd->qgd", jax.nn.softmax(scores, axis=-1),
                          v_h, precision=HIGHEST)

    ctx = jax.lax.map(one_kv_head, (
        q.reshape(t, nkv, group, hd).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return mm(ctx.transpose(1, 0, 2, 3).reshape(t, nq * hd), p["o"])


def _layer(s, p, x, cos, sin):
    eps = s["rms_norm_eps"]
    a = attention(s, p, rmsnorm(x, p["norm1"], eps), cos, sin)
    x = x + rmsnorm(a, p["norm2"], eps)
    u = rmsnorm(x, p["norm3"], eps)
    m = mm(jax.nn.silu(mm(u, p["gate"])) * mm(u, p["up"]), p["down"])
    return x + rmsnorm(m, p["norm4"], eps)


def _close_pass(s, final_norm, head, gate_w, gate_b, x, tokens, block):
    """What closes a pass: ``(h, log p of each next token [T-1], lambda [T],
    the last position's logits)``."""
    t = x.shape[0]
    h = rmsnorm(x, final_norm, s["rms_norm_eps"])
    n_blocks = -(-t // block)
    pad = n_blocks * block - t
    targets = jnp.pad(tokens[1:], (0, pad + 1))

    def score(xs):
        h_b, tgt = xs
        logits = mm(h_b, head)
        return (jnp.take_along_axis(logits, tgt[:, None], axis=1)[:, 0]
                - jax.nn.logsumexp(logits, axis=-1))

    logp = jax.lax.map(score, (
        jnp.pad(h, ((0, pad), (0, 0))).reshape(n_blocks, block, -1),
        targets.reshape(n_blocks, block)))
    lam = jax.nn.sigmoid(mm(h, gate_w)[:, 0] + gate_b[0])
    return h, logp.reshape(-1)[:t - 1], lam, mm(h[-1:], head)[0]


def _freeze(s):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in s.items()))


@jax.jit(static_argnums=(0,))
def _jit_layer(frozen, p, x, cos, sin):
    with jax.default_matmul_precision("highest"):
        return _layer(dict(frozen), p, x, cos, sin)


@jax.jit(static_argnums=(0, 7))
def _jit_close(frozen, final_norm, head, gate_w, gate_b, x, tokens, block):
    with jax.default_matmul_precision("highest"):
        return _close_pass(dict(frozen), final_norm, head, gate_w, gate_b,
                           x, tokens, block)


def passes(s: dict, member: Member, tokens, head_block: int = 512):
    """One member over one sequence ``tokens [T]``, every pass: ``(log p of
    each next token [S, T-1], exit probability of each position [S, T],
    last position's logits [S, vocab])``.  One layer's weights exist at a
    time; head, gate and final norm are held across the passes."""
    frozen, t = _freeze(s), tokens.shape[0]
    n_layers, n_passes = len(s["layer_types"]), s["total_ut_steps"]
    cos, sin = rotary(s, t)
    head, final_norm = member.leaf("head/kernel"), member.leaf(
        "final_norm/scale")
    gate_w, gate_b = member.leaf("exit_gate/kernel"), member.leaf(
        "exit_gate/bias")
    x = jnp.take(member.leaf("embed/embedding"), tokens, axis=0)
    remain = jnp.ones((t,), jnp.float32)
    logps, exits, lasts = [], [], []
    for step in range(n_passes):
        for i in range(n_layers):
            x = _jit_layer(frozen, member.layer(i), x, cos, sin)
        x, logp, lam, last = _jit_close(
            frozen, final_norm, head, gate_w, gate_b, x, tokens,
            min(head_block, t))
        exits.append(remain if step == n_passes - 1 else lam * remain)
        remain = remain * (1.0 - lam)
        logps.append(logp)
        lasts.append(last)
    return jnp.stack(logps), jnp.stack(exits), jnp.stack(lasts)


def forward(s: dict, member: Member, tokens, head_block: int = 512):
    """The policy output: ``(sum_s p_s log p_s(next token) [T-1], the last
    pass's logits at the last position [vocab])``."""
    logp, exit_p, last = passes(s, member, tokens, head_block)
    return jnp.sum(exit_p[:, :-1] * logp, axis=0), last[-1]


def score_members(s, theta, table, offsets, signs, keys, sigma, bc_dim):
    """``(fitness (k,), behaviour (k, bc_dim))`` of ``k`` members, one after
    the other: fitness is the mean exit-weighted log p of the next token
    over the member's sequence, behaviour the last pass's logits at the last
    position at the probe ids.  ``offsets``, ``signs`` and ``keys`` are per
    member."""
    noise_dim = noise_layout(s)["__dim__"]
    all_tokens = corpus(s)
    ids = jnp.asarray(probe_ids(s, bc_dim))
    fits, bcs = [], []
    for off, sign, key in zip(np.asarray(offsets), np.asarray(signs), keys):
        noise = jax.lax.dynamic_slice(table, (int(off),), (noise_dim,))
        row = jax.random.randint(key, (), 0, s["corpus_sequences"])
        member = Member(s, theta, noise, jnp.float32(sigma) * float(sign))
        score, last = forward(s, member, all_tokens[row])
        fits.append(float(jnp.mean(score)))
        bcs.append(np.asarray(jnp.take(last, ids)))
    return np.asarray(fits, np.float32), np.stack(bcs)
