"""Plain reference for one ES member of a sparse-expert decoder whose router
reads the layer's INPUT ahead of attention, with ReLU-gated experts and two
kinds of attention layer (SmallThinker-21BA3B-Instruct's ``config.json``):
float32 ``jax.numpy`` at ``highest`` matmul precision, written from the
published description and independent of the system's model code.  No
batching over members, no sharding, no engine, no sort of pairs, no grouped
matmul, no kernel and no tile: Python loops over layers and over the held
experts, a boolean mask per expert, ONE full ``[rows, T]`` masked softmax per
head over ``QUERY_ROWS`` query rows at a time (a block only so that it fits).
It is given the same share of the model as the system (which experts are
held, which vocabulary rows) and NOT the system's routes: it routes by itself.

What it follows (``config.json`` keys in quotes; (*) marks what the config
does not spell, listed under ``assumed`` in the configuration file):

    x = E[tokens]
    each layer l:
      a = rmsnorm_1 x
      p = softmax(a W_r) over ALL "moe_num_primary_experts" experts
          ("moe_primary_router_apply_softmax"): the router reads the layer's
          input, AHEAD of attention
      S = the "moe_num_active_primary_experts" largest (ties to the lower
          index);  w_e = p_e / (sum_{e' in S} p_e' + 1e-20)  ("norm_topk_prob")
      q = a W_q -> "num_attention_heads" x "head_dim"
      k = a W_k, v = a W_v -> "num_key_value_heads" x "head_dim"; no bias,
          no q/k norm (*)
      "sliding_window_layout"[l] = 1 = "rope_layout"[l] (a ``window`` layer):
          q, k rotated over the whole head, pairs (x_i, x_{i + d/2}) (*) by
          the angle t theta^(-2i/d), "rope_theta"; key s visible to query t
          iff t - "sliding_window_size" < s <= t (*: the window counts the
          query's own position)
      both layouts 0 (a ``global`` layer): no rotation, no position term;
          every s <= t visible
      h = x + softmax_s(q . k / sqrt(d)) v W_o
      b = rmsnorm_2 h
      y = sum_{e in S, e held here} w_e down_e(relu(gate_e b) * up_e b)
          ("moe_ffn_hidden_size"; ReGLU; no shared, no secondary expert (*))
      x = h + y
    h = rmsnorm_final(x);  score_t = log p(tokens[t+1]) from h W_head
    behaviour: the head's logits averaged over the last
          "behaviour_positions" positions (*)

A member's weights are ``theta + sigma * sign * E`` with ``E = A B^T /
sqrt(r)`` MATERIALISED a leaf at a time, and for a stacked expert leaf
``[experts, m, n]`` an expert at a time from that expert's own factor pair;
leaves where factoring would not save (norm weights) carry dense noise.
Table, offsets and keys are the system's (``parallel/sharded.py``), as
``reference/moe_lm.py`` spells them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import costs, costs_moe

HIGHEST = jax.lax.Precision.HIGHEST
# query rows whose attention scores exist at once
QUERY_ROWS = 1024
WINDOW = "window"       # the other kind, "global", has neither band nor turn
DEFAULTS = dict(
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    moe_num_primary_experts=8, expert_group_size=1, expert_group_rank=0,
    moe_num_active_primary_experts=2, behaviour_positions=512,
    rope_theta=10000.0, rms_norm_eps=1e-6)


# ------------------------------------------------------------------ sizes

def sizes(config: dict) -> dict:
    """The model's and the corpus's sizes AS BUILT: the keyword arguments
    the configuration file hands the policy and the environment."""
    kwargs = config["build"]["kwargs"]
    out = {**DEFAULTS, **kwargs["policy_kwargs"]}
    out.update(kwargs["agent_kwargs"]["env"]["kwargs"])
    out["low_rank"] = kwargs["low_rank"]
    out["num_experts"] = out["moe_num_primary_experts"]
    out["experts_total"] = out["num_experts"] * out["expert_group_size"]
    out["first_held"] = out["num_experts"] * out["expert_group_rank"]
    return out


LAYER_LEAVES = ("attn/k", "attn/o", "attn/q", "attn/v", "moe/router",
                "norm1/scale", "norm2/scale")


def _layer_layout(s: dict, base: str) -> list:
    h, d = s["hidden_size"], s["head_dim"]
    nq, nkv = s["num_attention_heads"], s["num_key_value_heads"]
    e, w = s["num_experts"], s["moe_ffn_hidden_size"]
    return [(f"{base}/attn/k", (h, nkv * d)),
            (f"{base}/attn/o", (nq * d, h)),
            (f"{base}/attn/q", (h, nq * d)),
            (f"{base}/attn/v", (h, nkv * d)),
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


def matmul_shapes(s: dict) -> tuple[list, list, list]:
    """``(the attention's projections, the routers', the head's)`` a token
    passes, as ``(m, n)``."""
    h, d = s["hidden_size"], s["head_dim"]
    nq, nkv = s["num_attention_heads"], s["num_key_value_heads"]
    layers = len(s["layer_types"])
    attn = [(h, nq * d), (h, nkv * d), (h, nkv * d), (nq * d, h)] * layers
    routers = [(h, s["experts_total"])] * layers
    return attn, routers, [(h, s["vocab_size"])]


def describe(config: dict) -> dict:
    """What the harness needs to know: the length of the flat parameter
    vector, and 2 x the matmul weights one token passes
    (``costs.matmul_flops``; the attention's own scores left out), split
    into what runs under ``es.dense`` (the attention's four projections),
    the head's, and, in the total alone, the routers' and the held experts'
    at the pairs a uniform router sends them (``costs_moe.py``)."""
    s = sizes(config)
    attn, routers, heads = matmul_shapes(s)
    pairs = costs_moe.expected_pairs_per_token(
        s["moe_num_active_primary_experts"], s["num_experts"],
        s["experts_total"])
    expert_flops = int(len(routers) * pairs * costs_moe.expert_flops_per_pair(
        s["hidden_size"], s["moe_ffn_hidden_size"]))
    return {"param_dim": param_offsets(s)["__dim__"][0],
            "noise_dim": noise_layout(s)["__dim__"],
            "flops_per_member_step": (
                costs.matmul_flops(attn + routers + heads) + expert_flops),
            "dense_flops_per_member_step": costs.matmul_flops(attn),
            "head_flops_per_member_step": costs.matmul_flops(heads),
            "expert_flops_per_member_step": expert_flops,
            "expert_layers": len(routers),
            "expected_pairs_per_token_and_layer": pairs}


# ------------------------------------------------------------------- init

def init_theta(key, config):
    """Seeded initial weights in the system's flat layout, made on the device
    in one jitted call: matrices normal, norm weights one.  A matrix's
    standard deviation is the configuration file's ``seeded_std`` for its
    leaf's name, ``other`` there for those not named (``assumed:
    initialisation`` says why the embedding, the attention's ``o``, the
    experts' ``down`` and the routers have their own), 0.02 where the file
    has none.  A rehearsal (the runner marks the configuration
    ``rehearsing``) reads ``rehearsal_seeded_std`` where the file has one:
    the same gains at the rehearsal's widths."""
    stds = dict((config.get("rehearsing")
                 and config.get("rehearsal_seeded_std"))
                or config.get("seeded_std", {}))
    other = stds.pop("other", 0.02)
    return _init_theta(key, tuple(
        (path, shape, stds.get(path.rsplit("/", 1)[1], other))
        for path, shape in system_layout(sizes(config))))


@jax.jit(static_argnums=(1,))
def _init_theta(key, layout):
    parts = []
    for i, (path, shape, std) in enumerate(layout):
        if path.rsplit("/", 1)[1] == "scale":
            v = jnp.ones(shape, jnp.float32)
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


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def reglu(u, gate, up, down):
    return mm(jax.nn.relu(mm(u, gate)) * mm(u, up), down)


def rotary(theta: float, width: int, length: int):
    """``(cos, sin) [T, width / 2]``: frequency pair ``i`` turns by ``t
    theta^(-2i/width)`` at position ``t``."""
    inv_freq = theta ** (-np.arange(0, width, 2, dtype=np.float64) / width)
    angle = np.arange(length, dtype=np.float64)[:, None] * inv_freq[None, :]
    return (jnp.asarray(np.cos(angle), jnp.float32),
            jnp.asarray(np.sin(angle), jnp.float32))


def rotate_halves(x, cos, sin):
    """The pairs ``(x_i, x_{i + d/2})`` of ``x [T, heads, d]`` turned by the
    position's angles; ``cos``, ``sin`` ``[T, d/2]``."""
    half = x.shape[-1] // 2
    cos, sin = cos[:, None, :], sin[:, None, :]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin],
                           axis=-1)


def visible(first_row: int, rows: int, length: int, window):
    """``[rows, T]`` bool: key ``s`` seen by query ``t = first_row + row``:
    ``s <= t``, and under a ``window`` ``s > t - window``."""
    queries = first_row + np.arange(rows)[:, None]
    keys = np.arange(length)[None, :]
    mask = keys <= queries
    if window is not None:
        mask &= keys > queries - window
    return jnp.asarray(mask)


def attention(s, p, a, kind, cos, sin):
    """The attention's output ``[T, hidden]`` of the normed layer input
    ``a``, ``QUERY_ROWS`` query rows at a time."""
    t = a.shape[0]
    nq, nkv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                  s["head_dim"])
    q = mm(a, p["attn/q"]).reshape(t, nq, d)
    k = mm(a, p["attn/k"]).reshape(t, nkv, d)
    v = mm(a, p["attn/v"]).reshape(t, nkv, d)
    window = None
    if kind == WINDOW:
        q, k = rotate_halves(q, cos, sin), rotate_halves(k, cos, sin)
        window = s["sliding_window_size"]
    # query head j reads key/value head j // (heads / kv heads), where it
    # lies: no copy of k and v a query head
    group = nq // nkv
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
    ctx = []
    for first in range(0, t, QUERY_ROWS):
        rows = slice(first, min(first + QUERY_ROWS, t))
        mask = visible(first, rows.stop - first, t, window)

        def one_head(xs, mask=mask):
            q_h, j = xs
            k_h, v_h = k[j // group], v[j // group]
            scores = jnp.matmul(q_h, k_h.T, precision=HIGHEST) / math.sqrt(d)
            prob = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
            return jnp.matmul(prob, v_h, precision=HIGHEST)

        out = jax.lax.map(one_head, (q[rows].transpose(1, 0, 2),
                                     jnp.arange(nq)))
        ctx.append(out.transpose(1, 0, 2).reshape(-1, nq * d))
    return mm(jnp.concatenate(ctx), p["attn/o"])


def routes(s, p, a):
    """``(experts [T, k], weights [T, k])``: the router over ALL experts,
    from the normed layer input."""
    k = s["moe_num_active_primary_experts"]
    prob = jax.nn.softmax(mm(a, p["moe/router"]), axis=-1)
    chosen = jnp.argsort(-prob, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(prob, chosen, axis=-1)
    return chosen, w / (w.sum(axis=-1, keepdims=True) + 1e-20)


def held_experts(s, experts, b, chosen, w):
    """The held experts' part of ``b`` under the routes ``(chosen, w)``: a
    Python loop over the held experts, each applied to every token and kept
    by a boolean mask where the token chose it."""
    y = jnp.zeros_like(b)
    for k, e in enumerate(experts):
        took = chosen == s["first_held"] + k                    # [T, k]
        weight = jnp.sum(jnp.where(took, w, 0.0), axis=-1)      # [T]
        y = y + weight[:, None] * reglu(b, e["gate"], e["up"], e["down"])
    return y


def _layer(s, p, experts, x, kind, cos, sin):
    """``(the layer's output, the chosen experts [T, k])``."""
    eps = s["rms_norm_eps"]
    a = rmsnorm(x, p["norm1/scale"], eps)
    chosen, w = routes(s, p, a)             # AHEAD of attention
    h = x + attention(s, p, a, kind, cos, sin)
    b = rmsnorm(h, p["norm2/scale"], eps)
    return h + held_experts(s, experts, b, chosen, w), chosen


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


@jax.jit(static_argnums=(0, 4))
def _jit_layer(frozen, p, experts, x, kind, cos, sin):
    with jax.default_matmul_precision("highest"):
        return _layer(dict(frozen), p, experts, x, kind, cos, sin)


@jax.jit(static_argnums=(0, 5))
def _jit_score(frozen, norm_w, head, x, targets, block):
    with jax.default_matmul_precision("highest"):
        s = dict(frozen)
        return _score(rmsnorm(x, norm_w, s["rms_norm_eps"]), head, targets,
                      block, s["behaviour_positions"])


def forward(s: dict, member: Member, tokens, head_block: int = 512,
            with_choices: bool = False, with_layers: bool = False):
    """One member over one sequence ``tokens [T]``: ``(log p(tokens[t+1])
    [T-1], the head's logits averaged over the last ``behaviour_positions``
    positions [vocab])``; with ``with_choices`` the chosen experts ``[T,
    k]`` of every layer too, with ``with_layers`` every layer's output
    ``[T, hidden]``.  One layer's weights exist at a time."""
    frozen, t = _freeze(s), tokens.shape[0]
    cos, sin = rotary(s["rope_theta"], s["head_dim"], t)
    # the embedding goes once the tokens are looked up, and the head comes
    # when the layers are done: beside the system's state on one chip the
    # float32 copies of both do not lie there while a layer runs
    x = jnp.take(member.leaf("embed/embedding"), tokens, axis=0)
    chosen, outputs = [], []
    for i, kind in enumerate(s["layer_types"]):
        base = f"layer_{i:02d}"
        x, c = _jit_layer(frozen, member.layer(base),
                          member.experts_of(base), x, kind, cos, sin)
        if with_choices:
            chosen.append(c)
        if with_layers:
            outputs.append(x)
    score, last = _jit_score(frozen, member.leaf("final_norm/scale"),
                             member.leaf("head/kernel"), x, tokens,
                             min(head_block, t))
    out = (score, last)
    if with_choices:
        out += (chosen,)
    if with_layers:
        out += (outputs,)
    return out


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
