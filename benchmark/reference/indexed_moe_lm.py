"""Plain reference for one ES member of a sparse-expert decoder whose
attention reads a LEARNED SELECTION of keys: grouped-query attention under
DeepSeek-V3.2's sparse-attention indexer (the ``sa_config`` keys of
Keye-VL-2.0-30B-A3B's ``config.json``), Qwen3-MoE's expert layer (softmax
router, no shared expert) and three position streams (M-RoPE).  float32
``jax.numpy`` at ``highest`` matmul precision, written from the published
description and independent of the system's model code.  No batching over
members, no sharding, no engine, no sort of pairs, no grouped matmul, no
bisection and no tile: Python loops over layers and over the held experts, a
boolean mask per expert, the index scores and ONE full ``[rows, T]`` masked
softmax per head over ``QUERY_ROWS`` query rows at a time, the selection from
``jax.lax.top_k``'s INDICES.  It is given the same share of the model as the
system (which experts are held, which vocabulary rows) and NOT the system's
selection or routes: it selects and routes by itself.

What it follows (``config.json`` keys in quotes; (*) marks what the config
does not spell, listed under ``assumed`` in the configuration file):

    x = E[tokens]
    each layer:   x += attn(rmsnorm_1 x);   x += moe(rmsnorm_2 x)
    attn(u):  q = u W_q -> "num_attention_heads" x "head_dim"
              k = u W_k, v = u W_v -> "num_key_value_heads" x "head_dim"
              q <- rmsnorm(q; g_q), k <- rmsnorm(k; g_k) per head (*)
              q, k rotated, pairs (x_i, x_{i + d/2}), pair i by the angle
              p_c(i) theta^(-2i/d), "rope_theta"; c(i) the position stream
              of pair i: the first "mrope_section"[0] pairs the temporal
              stream, the next [1] the height, the last [2] the width
      indexer ("sa_config"): qI = u W_qI -> "indexer_num_heads" x
              "indexer_head_dim";  kI = layernorm(u W_kI) (ONE key head,
              "indexer_num_kv_heads" 1);  w = u W_w -> [T, index heads] (*)
              qI, kI rotated over their whole width by the temporal stream (*)
              I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])      for s <= t
      select: S_t = the min(t + 1, "topk") keys s <= t of largest I[t, s],
              ties to the lower s; the same S_t for every head
      score_h[t, s] = q_h[t] . k_{h // group}[s] / sqrt(d) for s in S_t,
              -inf elsewhere;  P = softmax_s;  ctx = P v;  out = ctx W_o
    moe(u):   p = softmax(u W_r) over ALL "num_experts" experts
              idx = the "num_experts_per_tok" largest (ties to the lower
              index);  g = p[idx] / (sum p[idx] + 1e-20)  ("norm_topk_prob")
              y = sum_{k: idx_k held here} g_k expert_{idx_k}(u)
              (gated SiLU, "moe_intermediate_size"); no shared expert
    h = rmsnorm_final(x);  score_t = log p(tokens[t+1]) from h W_head
    behaviour: the head's logits averaged over the last
              "behaviour_positions" positions (*)

A member's weights are ``theta + sigma * sign * E`` with ``E = A B^T /
sqrt(r)`` MATERIALISED a leaf at a time, and for a stacked expert leaf
``[experts, m, n]`` an expert at a time from that expert's own factor pair;
leaves where factoring would not save (norm weights and biases) carry dense
noise.  Table, offsets and keys are the system's (``parallel/sharded.py``),
as ``reference/moe_lm.py`` spells them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import costs, costs_moe

HIGHEST = jax.lax.Precision.HIGHEST
# query rows whose index scores and attention scores exist at once
QUERY_ROWS = 1024
DEFAULTS = dict(
    num_attention_heads=4, num_key_value_heads=2, head_dim=8, num_experts=8,
    expert_group_size=1, expert_group_rank=0, num_experts_per_tok=2,
    indexer_num_heads=2, indexer_head_dim=8, topk=8, mrope_section=(2, 1, 1),
    behaviour_positions=512, rope_theta=10000.0, rms_norm_eps=1e-6)


# ------------------------------------------------------------------ sizes

def sizes(config: dict) -> dict:
    """The model's and the corpus's sizes AS BUILT: the keyword arguments
    the configuration file hands the policy and the environment."""
    kwargs = config["build"]["kwargs"]
    out = {**DEFAULTS, **kwargs["policy_kwargs"]}
    out.update(kwargs["agent_kwargs"]["env"]["kwargs"])
    out["low_rank"] = kwargs["low_rank"]
    out["experts_total"] = out["num_experts"] * out["expert_group_size"]
    out["first_held"] = out["num_experts"] * out["expert_group_rank"]
    return out


LAYER_LEAVES = ("attn/k", "attn/k_norm/scale", "attn/o", "attn/q",
                "attn/q_norm/scale", "attn/v", "indexer/index_k",
                "indexer/index_norm/bias", "indexer/index_norm/scale",
                "indexer/index_q", "indexer/index_w", "moe/router",
                "norm1/scale", "norm2/scale")


def _layer_layout(s: dict, base: str) -> list:
    h, d = s["hidden_size"], s["head_dim"]
    nq, nkv = s["num_attention_heads"], s["num_key_value_heads"]
    hi, di = s["indexer_num_heads"], s["indexer_head_dim"]
    e, w = s["num_experts"], s["moe_intermediate_size"]
    return [(f"{base}/attn/k", (h, nkv * d)),
            (f"{base}/attn/k_norm/scale", (d,)),
            (f"{base}/attn/o", (nq * d, h)),
            (f"{base}/attn/q", (h, nq * d)),
            (f"{base}/attn/q_norm/scale", (d,)),
            (f"{base}/attn/v", (h, nkv * d)),
            (f"{base}/indexer/index_k", (h, di)),
            (f"{base}/indexer/index_norm/bias", (di,)),
            (f"{base}/indexer/index_norm/scale", (di,)),
            (f"{base}/indexer/index_q", (h, hi * di)),
            (f"{base}/indexer/index_w", (h, hi)),
            (f"{base}/moe/experts/down", (e, w, h)),
            (f"{base}/moe/experts/gate", (e, h, w)),
            (f"{base}/moe/experts/up", (e, h, w)),
            (f"{base}/moe/router", (h, s["experts_total"])),
            (f"{base}/norm1/scale", (h,)),
            (f"{base}/norm2/scale", (h,))]


def system_layout(s: dict) -> list[tuple[str, tuple]]:
    """``[(path, shape), ...]`` of the system's flat parameter vector: its
    leaves in sorted-key order."""
    h, v = s["hidden_size"], s["vocab_size"]
    out = [("embed/embedding", (v, h)), ("final_norm/scale", (h,)),
           ("head/kernel", (h, v))]
    for i in range(len(s["layer_types"])):
        out += _layer_layout(s, f"layer_{i:02d}")
    return out


def param_offsets(s: dict) -> dict[str, tuple[int, tuple]]:
    out, at = {}, 0
    for path, shape in system_layout(s):
        out[path] = (at, shape)
        at += math.prod(shape)
    out["__dim__"] = (at, ())
    return out


def noise_layout(s: dict) -> dict[str, tuple]:
    """``{path: ("lr", a_off, b_off) | ("stacked", a_off, b_off) |
    ("dense", off)}`` and the length of one pair's noise vector under
    ``"__dim__"``.  A stacked expert leaf ``[e, m, n]`` holds ``A [e, m,
    r]`` then ``B [e, n, r]``: one factor pair an expert."""
    r, out, at = s["low_rank"], {}, 0
    for path, shape in system_layout(s):
        if len(shape) == 2 and r * (shape[0] + shape[1]) < shape[0] * shape[1]:
            out[path] = ("lr", at, at + shape[0] * r)
            at += (shape[0] + shape[1]) * r
        elif (len(shape) == 3 and "/experts/" in path
              and r * (shape[1] + shape[2]) < shape[1] * shape[2]):
            out[path] = ("stacked", at, at + shape[0] * shape[1] * r)
            at += shape[0] * (shape[1] + shape[2]) * r
        else:
            out[path] = ("dense", at)
            at += math.prod(shape)
    out["__dim__"] = at
    return out


def matmul_shapes(s: dict) -> tuple[list, list, list, list]:
    """``(the attention's projections, the indexer's, the routers', the
    head's)`` a token passes, as ``(m, n)``."""
    h, d = s["hidden_size"], s["head_dim"]
    nq, nkv = s["num_attention_heads"], s["num_key_value_heads"]
    hi, di = s["indexer_num_heads"], s["indexer_head_dim"]
    layers = len(s["layer_types"])
    attn = [(h, nq * d), (h, nkv * d), (h, nkv * d), (nq * d, h)] * layers
    index = [(h, hi * di), (h, di), (h, hi)] * layers
    routers = [(h, s["experts_total"])] * layers
    return attn, index, routers, [(h, s["vocab_size"])]


def describe(config: dict) -> dict:
    """What the harness needs to know: the length of the flat parameter
    vector, and 2 x the matmul weights one token passes
    (``costs.matmul_flops``; the attention's and the indexer's own scores
    left out), split into what runs under ``es.dense`` (the attention's four
    projections), the head's, and, in the total alone, the indexer's three
    projections, the routers' and the held experts' at the pairs a uniform
    router sends them (``costs_moe.py``)."""
    s = sizes(config)
    attn, index, routers, heads = matmul_shapes(s)
    pairs = costs_moe.expected_pairs_per_token(
        s["num_experts_per_tok"], s["num_experts"], s["experts_total"])
    expert_flops = int(len(routers) * pairs * costs_moe.expert_flops_per_pair(
        s["hidden_size"], s["moe_intermediate_size"]))
    return {"param_dim": param_offsets(s)["__dim__"][0],
            "noise_dim": noise_layout(s)["__dim__"],
            "flops_per_member_step": (
                costs.matmul_flops(attn + index + routers + heads)
                + expert_flops),
            "dense_flops_per_member_step": costs.matmul_flops(attn),
            "head_flops_per_member_step": costs.matmul_flops(heads),
            "index_flops_per_member_step": costs.matmul_flops(index),
            "expert_flops_per_member_step": expert_flops,
            "expert_layers": len(routers),
            "expected_pairs_per_token_and_layer": pairs}


# ------------------------------------------------------------------- init

def init_theta(key, config):
    """Seeded initial weights in the system's flat layout, made on the device
    in one jitted call: matrices normal, norm weights one, the LayerNorm's
    bias zero.  A matrix's standard deviation is the configuration file's
    ``seeded_std`` for its leaf's name, ``other`` there for those not named
    (``assumed: initialisation`` says why the embedding, the attention's
    ``o``, the experts' ``down`` and the routers have their own), 0.02
    where the file has none."""
    stds = dict(config.get("seeded_std", {}))
    other = stds.pop("other", 0.02)
    return _init_theta(key, tuple(
        (path, shape, stds.get(path.rsplit("/", 1)[1], other))
        for path, shape in system_layout(sizes(config))))


@jax.jit(static_argnums=(1,))
def _init_theta(key, layout):
    parts = []
    for i, (path, shape, std) in enumerate(layout):
        name = path.rsplit("/", 1)[1]
        if name == "scale":
            v = jnp.ones(shape, jnp.float32)
        elif name == "bias":
            v = jnp.zeros(shape, jnp.float32)
        else:
            v = std * jax.random.normal(jax.random.fold_in(key, i), shape,
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
    """One member's weights ``theta + sigma * sign * E``, a leaf (and an
    expert) at a time: ``theta`` is the centre's flat vector (host or
    device), ``noise`` the member's pair's slice of the table (``None``: the
    centre alone)."""

    def __init__(self, s, theta, noise, scale):
        self.s, self.theta, self.noise, self.scale = s, theta, noise, scale
        self.at, self.noise_at = param_offsets(s), noise_layout(s)

    def _centre(self, off, shape):
        return jnp.asarray(self.theta[off:off + math.prod(shape)],
                           jnp.float32).reshape(shape)

    def _outer(self, a_off, b_off, m, n):
        r = self.s["low_rank"]
        a = self.noise[a_off:a_off + m * r].reshape(m, r)
        b = self.noise[b_off:b_off + n * r].reshape(n, r)
        return jnp.matmul(a, b.T, precision=HIGHEST) / math.sqrt(r)

    def leaf(self, path):
        off, shape = self.at[path]
        w = self._centre(off, shape)
        if self.noise is None:
            return w
        entry = self.noise_at[path]
        if entry[0] == "lr":
            e = self._outer(entry[1], entry[2], *shape)
        else:
            e = self.noise[entry[1]:entry[1] + math.prod(shape)].reshape(shape)
        return w + self.scale * e

    def expert(self, path, k):
        """Expert ``k``'s ``[m, n]`` of the stacked leaf at ``path``."""
        off, (_, m, n) = self.at[path]
        w = self._centre(off + k * m * n, (m, n))
        if self.noise is None:
            return w
        kind, a_off, b_off = self.noise_at[path]
        assert kind == "stacked"
        r = self.s["low_rank"]
        return w + self.scale * self._outer(
            a_off + k * m * r, b_off + k * n * r, m, n)

    def layer(self, base):
        return {n: self.leaf(f"{base}/{n}") for n in LAYER_LEAVES}

    def experts_of(self, base):
        """``[{gate, up, down}, ...]`` of the held experts of a layer."""
        return [{n: self.expert(f"{base}/moe/experts/{n}", k)
                 for n in ("gate", "up", "down")}
                for k in range(self.s["num_experts"])]


# ---------------------------------------------------------------- forward

def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def layernorm(x, w, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps
                             ) * w + b


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def gated(u, gate, up, down):
    return mm(jax.nn.silu(mm(u, gate)) * mm(u, up), down)


def text_positions(s: dict, length: int):
    """Text: the three streams hold the token's index."""
    return np.broadcast_to(np.arange(length), (len(s["mrope_section"]),
                                               length))


def rotary(theta: float, width: int, positions, sections):
    """``(cos, sin) [T, width / 2]``: frequency pair ``i`` of ``width / 2``
    turns by ``p_c(i) theta^(-2i/width)``, ``c(i)`` the stream whose section
    holds ``i``; ``positions [streams, T]``."""
    inv_freq = theta ** (-np.arange(0, width, 2, dtype=np.float64) / width)
    positions = np.asarray(positions, np.float64)
    angle = np.empty((positions.shape[1], width // 2), np.float64)
    first = 0
    for stream, pairs in enumerate(sections):
        for i in range(first, first + pairs):
            angle[:, i] = positions[stream] * inv_freq[i]
        first += pairs
    assert first == width // 2, (sections, width)
    return (jnp.asarray(np.cos(angle), jnp.float32),
            jnp.asarray(np.sin(angle), jnp.float32))


def rotate_halves(x, cos, sin):
    """The pairs ``(x_i, x_{i + d/2})`` of ``x [T, ..., d]`` turned by the
    position's angles; ``cos``, ``sin`` ``[T, d/2]``."""
    half = x.shape[-1] // 2
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           axis=-1)


def indexer(s, p, u, index_cos, index_sin):
    """``(qI [T, index heads, dI], kI [T, dI], w [T, index heads])``."""
    t = u.shape[0]
    hi, di = s["indexer_num_heads"], s["indexer_head_dim"]
    q_i = rotate_halves(mm(u, p["indexer/index_q"]).reshape(t, hi, di),
                        index_cos, index_sin)
    k_i = rotate_halves(
        layernorm(mm(u, p["indexer/index_k"]), p["indexer/index_norm/scale"],
                  p["indexer/index_norm/bias"], s["rms_norm_eps"]),
        index_cos, index_sin)
    return q_i, k_i, mm(u, p["indexer/index_w"])


def index_scores(q_i, k_i, w):
    """``I [rows, T]`` of some query rows against every key: ``sum_j w[t,
    j] relu(qI[t, j] . kI[s])``; the future not masked yet."""
    dots = jnp.einsum("qhd,sd->qhs", q_i, k_i, precision=HIGHEST)
    return jnp.sum(w[:, :, None] * jax.nn.relu(dots), axis=1)


def selection(s, scores, first_row: int):
    """``[rows, T]`` bool: per query ``t = first_row + row`` the
    ``min(t + 1, topk)`` visible keys of largest score, from
    ``jax.lax.top_k``'s indices (among equal values the lower index
    first)."""
    rows, t = scores.shape
    queries = first_row + jnp.arange(rows)[:, None]
    visible = jnp.arange(t)[None, :] <= queries
    _, idx = jax.lax.top_k(jnp.where(visible, scores, -jnp.inf),
                           min(s["topk"], t))
    picked = jnp.zeros((rows, t), bool).at[
        jnp.arange(rows)[:, None], idx].set(True)
    # a row with fewer visible keys than topk picked future ones too
    return picked & visible


def attention(s, p, u, cos, sin, index_cos, index_sin,
              ignore_selection: bool = False):
    """``(attention's output [T, hidden], the selection [T, T] bool)``,
    ``QUERY_ROWS`` query rows at a time."""
    t = u.shape[0]
    nq, nkv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                  s["head_dim"])
    eps = s["rms_norm_eps"]
    q = rotate_halves(rmsnorm(mm(u, p["attn/q"]).reshape(t, nq, d),
                              p["attn/q_norm/scale"], eps), cos, sin)
    k = rotate_halves(rmsnorm(mm(u, p["attn/k"]).reshape(t, nkv, d),
                              p["attn/k_norm/scale"], eps), cos, sin)
    v = mm(u, p["attn/v"]).reshape(t, nkv, d)
    group = nq // nkv
    q_i, k_i, w = indexer(s, p, u, index_cos, index_sin)
    ctx, chosen = [], []
    for first in range(0, t, QUERY_ROWS):
        rows = slice(first, min(first + QUERY_ROWS, t))
        sel = selection(s, index_scores(q_i[rows], k_i, w[rows]), first)
        chosen.append(sel)
        mask = sel
        if ignore_selection:
            mask = (jnp.arange(t)[None, :]
                    <= first + jnp.arange(sel.shape[0])[:, None])

        def one_head(xs, mask=mask):
            q_h, k_h, v_h = xs
            scores = jnp.matmul(q_h, k_h.T, precision=HIGHEST) / math.sqrt(d)
            prob = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
            return jnp.matmul(prob, v_h, precision=HIGHEST)

        out = jax.lax.map(one_head, (
            q[rows].transpose(1, 0, 2),
            jnp.repeat(k, group, axis=1).transpose(1, 0, 2),
            jnp.repeat(v, group, axis=1).transpose(1, 0, 2)))
        ctx.append(out.transpose(1, 0, 2).reshape(-1, nq * d))
    return mm(jnp.concatenate(ctx), p["attn/o"]), jnp.concatenate(chosen)


def routes(s, p, u):
    """``(experts [T, k], weights [T, k])``: the router over ALL experts."""
    k = s["num_experts_per_tok"]
    prob = jax.nn.softmax(mm(u, p["moe/router"]), axis=-1)
    chosen = jnp.argsort(-prob, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(prob, chosen, axis=-1)
    return chosen, w / (w.sum(axis=-1, keepdims=True) + 1e-20)


def moe_ffn(s, p, experts, u):
    """``(the held experts' part, the routes)``: a Python loop over the held
    experts, each applied to every token and kept by a boolean mask where
    the token chose it."""
    chosen, w = routes(s, p, u)
    y = jnp.zeros_like(u)
    for k, e in enumerate(experts):
        took = chosen == s["first_held"] + k                    # [T, k]
        weight = jnp.sum(jnp.where(took, w, 0.0), axis=-1)      # [T]
        y = y + weight[:, None] * gated(u, e["gate"], e["up"], e["down"])
    return y, chosen


def _layer(s, p, experts, x, cos, sin, index_cos, index_sin,
           ignore_selection=False, with_choices=False):
    eps = s["rms_norm_eps"]
    a, sel = attention(s, p, rmsnorm(x, p["norm1/scale"], eps), cos, sin,
                       index_cos, index_sin, ignore_selection)
    x = x + a
    y, chosen = moe_ffn(s, p, experts, rmsnorm(x, p["norm2/scale"], eps))
    return (x + y, sel, chosen) if with_choices else (x + y, None, None)


def _score(h, head, targets, block, tail):
    """``(log p(targets[t+1]) from h_t [T-1], the logits averaged over the
    last ``tail`` positions)`` in blocks of ``block`` positions, so that
    ``[T, vocab]`` never exists."""
    t = h.shape[0]
    n_blocks = -(-t // block)
    pad = n_blocks * block - t
    tgt = jnp.pad(targets[1:], (0, pad + 1))

    def score(xs):
        h_b, tgt_b = xs
        logits = mm(h_b, head)
        return (jnp.take_along_axis(logits, tgt_b[:, None], axis=1)[:, 0]
                - jax.nn.logsumexp(logits, axis=-1))

    logp = jax.lax.map(score, (
        jnp.pad(h, ((0, pad), (0, 0))).reshape(n_blocks, block, -1),
        tgt.reshape(n_blocks, block)))
    return logp.reshape(-1)[:t - 1], jnp.mean(mm(h[-tail:], head), axis=0)


def _freeze(s):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in s.items()))


@jax.jit(static_argnums=(0, 8, 9))
def _jit_layer(frozen, p, experts, x, cos, sin, index_cos, index_sin,
               ignore_selection, with_choices):
    with jax.default_matmul_precision("highest"):
        return _layer(dict(frozen), p, experts, x, cos, sin, index_cos,
                      index_sin, ignore_selection, with_choices)


@jax.jit(static_argnums=(0, 5))
def _jit_score(frozen, norm_w, head, x, targets, block):
    with jax.default_matmul_precision("highest"):
        s = dict(frozen)
        return _score(rmsnorm(x, norm_w, s["rms_norm_eps"]), head, targets,
                      block, s["behaviour_positions"])


def forward(s: dict, member: Member, tokens, head_block: int = 512,
            positions=None, with_choices: bool = False,
            ignore_selection: bool = False):
    """One member over one sequence ``tokens [T]``: ``(log p(tokens[t+1])
    [T-1], the head's logits averaged over the last ``behaviour_positions``
    positions [vocab])``, and with ``with_choices`` the selection ``[T, T]``
    bool and the chosen experts ``[T, k]`` of every layer.  ``positions [3,
    T]``: the three position streams (``None``: text).  One layer's weights
    exist at a time; embedding and head are held throughout.
    ``ignore_selection``: full causal attention (the rehearsals' degraded
    form)."""
    frozen, t = _freeze(s), tokens.shape[0]
    if positions is None:
        positions = text_positions(s, t)
    cos, sin = rotary(s["rope_theta"], s["head_dim"], positions,
                      s["mrope_section"])
    # the indexer turns its whole width by the temporal stream
    index_cos, index_sin = rotary(
        s["rope_theta"], s["indexer_head_dim"], np.asarray(positions)[:1],
        (s["indexer_head_dim"] // 2,))
    table, head = member.leaf("embed/embedding"), member.leaf("head/kernel")
    x = jnp.take(table, tokens, axis=0)
    selections, chosen = [], []
    for i in range(len(s["layer_types"])):
        base = f"layer_{i:02d}"
        x, sel, c = _jit_layer(frozen, member.layer(base),
                               member.experts_of(base), x, cos, sin,
                               index_cos, index_sin, ignore_selection,
                               with_choices)
        if with_choices:
            selections.append(sel)
            chosen.append(c)
    score, last = _jit_score(frozen, member.leaf("final_norm/scale"), head, x,
                             tokens, min(head_block, t))
    if with_choices:
        return score, last, selections, chosen
    return score, last


def score_members(s, theta, table, offsets, signs, keys, sigma, bc_dim):
    """``(fitness (k,), behaviour (k, bc_dim))`` of ``k`` members, one after
    the other: fitness is the mean score over the member's sequence,
    behaviour the head's averaged logits at the probe ids.  ``offsets``,
    ``signs`` and ``keys`` are per member."""
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
