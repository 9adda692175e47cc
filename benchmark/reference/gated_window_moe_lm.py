"""Plain reference for one ES member of a sparse-expert decoder whose two
kinds of attention layer differ in head count, band and rotation, with a gate
a head on the attention's context, behind one dense layer (Laguna-XS.2's
``config.json``): float32 ``jax.numpy`` at ``highest`` matmul precision,
written from the published description and independent of the system's model
code.  No batching over members, no sharding, no engine, no sort of pairs, no
grouped matmul, no kernel and no tile: Python loops over layers and over the
held experts, a boolean mask per expert, ONE full ``[rows, T]`` masked softmax
per head over ``QUERY_ROWS`` query rows at a time (a block only so that it
fits), YaRN's frequencies from the formula below.  It is given the same share
of the model as the system (which experts are held, which vocabulary rows)
and NOT the system's routes: it routes by itself.

What it follows (``config.json`` keys in quotes; (*) marks what the config
does not spell, listed under ``assumed`` in the configuration file):

    x = E[tokens]
    layer l, "layer_types"[l] in {full_attention, sliding_attention},
    n = "num_attention_heads_per_layer"[l], "num_key_value_heads" kv heads,
    d = "head_dim":
      a = rmsnorm_1 x                                   (pre-norm (*))
      q = a W_q -> n x d;  k = a W_k, v = a W_v -> kv heads x d; no bias
          ("attention_bias" false), no q/k norm (*); query head j reads kv
          head j // (n / kv heads)
      "rope_parameters"[kind]: D = "partial_rotary_factor" d, the FIRST D of
          every head of q and k turned, pairs (x_i, x_{i + D/2}) (*), the
          rest of the head passes
        "rope_type" default: angle t theta^(-2i/D), "rope_theta"
        "rope_type" yarn: f_i = theta^(-2i/D), i < D/2
          low  = floor(D ln(L / (beta_fast 2 pi)) / (2 ln theta)), >= 0
          high = ceil (D ln(L / (beta_slow 2 pi)) / (2 ln theta)), <= D - 1
                 (L = "original_max_position_embeddings")
          r_i  = clip((i - low) / max(high - low, 0.001), 0, 1)
          inv_freq_i = (f_i / "factor") r_i + f_i (1 - r_i)
          cos, sin of t inv_freq_i, BOTH times "attention_factor"
      sliding: key s visible to query t iff t - "sliding_window" < s <= t (*:
          the window counts the query's own position); full: every s <= t
      ctx = softmax_s(q . k / sqrt(d)) v
      g   = sigmoid(a W_g) -> n                         ("gating"; ONE number
                                                        a head (*))
      h   = x + (ctx * g) W_o
      b   = rmsnorm_2 h
      "mlp_layer_types"[l] dense:  x = h + W_down(silu(W_gate b) * W_up b)
                                   ("intermediate_size")
      sparse: s = sigmoid(b W_r) over ALL "num_experts" experts (*)
              S = the "num_experts_per_tok" largest (ties to the lower index)
              w_e = "moe_routed_scaling_factor" s_e / (sum_{e' in S} s_e' + 1e-20)
              x = h + shared(b) + sum_{e in S, e held here} w_e expert_e(b)
                  (SwiGLU (*); "moe_intermediate_size",
                  "shared_expert_intermediate_size"; the shared expert added
                  unscaled (*))
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

import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import costs, costs_moe

HIGHEST = jax.lax.Precision.HIGHEST
# query rows whose attention scores exist at once
QUERY_ROWS = 1024
FULL, SLIDING = "full_attention", "sliding_attention"
DENSE, SPARSE = "dense", "sparse"
DEFAULTS = dict(
    num_key_value_heads=2, head_dim=8, num_experts=8, expert_group_size=1,
    expert_group_rank=0, num_experts_per_tok=2,
    moe_routed_scaling_factor=1.0, behaviour_positions=512,
    rms_norm_eps=1e-6)


# ------------------------------------------------------------------ sizes

def sizes(config: dict) -> dict:
    """The model's and the corpus's sizes AS BUILT: the keyword arguments
    the configuration file hands the policy and the environment.  The three
    per-layer lists are cut to the layers that are built."""
    kwargs = config["build"]["kwargs"]
    out = {**DEFAULTS, **kwargs["policy_kwargs"]}
    out.update(kwargs["agent_kwargs"]["env"]["kwargs"])
    out["low_rank"] = kwargs["low_rank"]
    layers = len(out["layer_types"])
    out["layer_types"] = list(out["layer_types"])
    out["mlp_layer_types"] = list(out["mlp_layer_types"][:layers])
    out["heads"] = [int(n) for n in
                    out["num_attention_heads_per_layer"][:layers]]
    out["experts_total"] = out["num_experts"] * out["expert_group_size"]
    out["first_held"] = out["num_experts"] * out["expert_group_rank"]
    return out


def layer_leaves(mlp: str) -> tuple:
    """The 1-D and 2-D leaves of a layer whose FFN is ``mlp``."""
    common = ("attn/head_gate", "attn/k", "attn/o", "attn/q", "attn/v",
              "norm1/scale", "norm2/scale")
    if mlp == DENSE:
        return common + ("mlp/down", "mlp/gate", "mlp/up")
    return common + ("moe/router", "moe/shared/down", "moe/shared/gate",
                     "moe/shared/up")


def _layer_layout(s: dict, base: str, heads: int, mlp: str) -> list:
    h, d, nkv = s["hidden_size"], s["head_dim"], s["num_key_value_heads"]
    out = [(f"{base}/attn/head_gate", (h, heads)),
           (f"{base}/attn/k", (h, nkv * d)),
           (f"{base}/attn/o", (heads * d, h)),
           (f"{base}/attn/q", (h, heads * d)),
           (f"{base}/attn/v", (h, nkv * d))]
    if mlp == DENSE:
        ff = s["intermediate_size"]
        out += [(f"{base}/mlp/down", (ff, h)), (f"{base}/mlp/gate", (h, ff)),
                (f"{base}/mlp/up", (h, ff))]
    else:
        e, w = s["num_experts"], s["moe_intermediate_size"]
        sw = s["shared_expert_intermediate_size"]
        out += [(f"{base}/moe/experts/down", (e, w, h)),
                (f"{base}/moe/experts/gate", (e, h, w)),
                (f"{base}/moe/experts/up", (e, h, w)),
                (f"{base}/moe/router", (h, s["experts_total"])),
                (f"{base}/moe/shared/down", (sw, h)),
                (f"{base}/moe/shared/gate", (h, sw)),
                (f"{base}/moe/shared/up", (h, sw))]
    return out + [(f"{base}/norm1/scale", (h,)), (f"{base}/norm2/scale", (h,))]


def system_layout(s: dict) -> list[tuple[str, tuple]]:
    """``[(path, shape), ...]`` of the system's flat parameter vector: its
    leaves in sorted-key order."""
    h, v = s["hidden_size"], s["vocab_size"]
    out = [("embed/embedding", (v, h)), ("final_norm/scale", (h,)),
           ("head/kernel", (h, v))]
    for i, (heads, mlp) in enumerate(zip(s["heads"], s["mlp_layer_types"])):
        out += _layer_layout(s, f"layer_{i:02d}", heads, mlp)
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
    """``(what a token passes under es.dense: the attention's projections
    with the gate's, the dense FFN, the shared experts; the routers; the
    head)``, as ``(m, n)``."""
    h, d, nkv = s["hidden_size"], s["head_dim"], s["num_key_value_heads"]
    dense, routers = [], []
    for heads, mlp in zip(s["heads"], s["mlp_layer_types"]):
        dense += [(h, heads * d), (h, nkv * d), (h, nkv * d),
                  (heads * d, h), (h, heads)]
        if mlp == DENSE:
            ff = s["intermediate_size"]
            dense += [(h, ff), (h, ff), (ff, h)]
        else:
            sw = s["shared_expert_intermediate_size"]
            dense += [(h, sw), (h, sw), (sw, h)]
            routers.append((h, s["experts_total"]))
    return dense, routers, [(h, s["vocab_size"])]


def describe(config: dict) -> dict:
    """What the harness needs to know: the length of the flat parameter
    vector, and 2 x the matmul weights one token passes
    (``costs.matmul_flops``; the attention's own scores left out), split
    into what runs under ``es.dense`` (the attention's projections and
    gates, the dense FFN, the shared experts), the head's, and, in the
    total alone, the routers' and the held experts' at the pairs a uniform
    router sends them (``costs_moe.py``)."""
    s = sizes(config)
    dense, routers, heads = matmul_shapes(s)
    pairs = costs_moe.expected_pairs_per_token(
        s["num_experts_per_tok"], s["num_experts"], s["experts_total"])
    expert_flops = int(len(routers) * pairs * costs_moe.expert_flops_per_pair(
        s["hidden_size"], s["moe_intermediate_size"]))
    return {"param_dim": param_offsets(s)["__dim__"][0],
            "noise_dim": noise_layout(s)["__dim__"],
            "flops_per_member_step": (
                costs.matmul_flops(dense + routers + heads) + expert_flops),
            "dense_flops_per_member_step": costs.matmul_flops(dense),
            "head_flops_per_member_step": costs.matmul_flops(heads),
            "expert_flops_per_member_step": expert_flops,
            "expert_layers": len(routers),
            "expected_pairs_per_token_and_layer": pairs}


# ------------------------------------------------------------------- init

def init_theta(key, config):
    """Seeded initial weights in the system's flat layout, made on the device
    in one jitted call: matrices normal, norm weights one.  A matrix's
    standard deviation is the configuration file's ``seeded_std`` for its
    leaf's last two keys (``experts/down``) or, failing that, its name
    (``q``), ``other`` there for those not named (``assumed:
    initialisation`` says why each has its own), 0.02 where the file has
    none.  A rehearsal (the runner marks the configuration ``rehearsing``)
    reads ``rehearsal_seeded_std`` where the file has one: the same gains at
    the rehearsal's widths."""
    stds = dict((config.get("rehearsing")
                 and config.get("rehearsal_seeded_std"))
                or config.get("seeded_std", {}))
    other = stds.pop("other", 0.02)

    def std_of(path):
        keys = path.split("/")
        return stds.get("/".join(keys[-2:]), stds.get(keys[-1], other))

    return _init_theta(key, tuple(
        (path, shape, std_of(path))
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

    def layer(self, base, mlp):
        return {n: self.leaf(f"{base}/{n}") for n in layer_leaves(mlp)}

    def experts_of(self, base, mlp):
        """``[{gate, up, down}, ...]`` of the held experts of a layer
        (none in a dense one)."""
        if mlp == DENSE:
            return []
        return [{n: self.expert(f"{base}/moe/experts/{n}", k)
                 for n in ("gate", "up", "down")}
                for k in range(self.s["num_experts"])]


# ---------------------------------------------------------------- forward

def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def swiglu(u, gate, up, down):
    return mm(jax.nn.silu(mm(u, gate)) * mm(u, up), down)


def inv_freq(group: dict, width: int):
    """``(inv_freq [width / 2] float64, the factor of cos and sin)`` of one
    kind's ``rope_parameters`` group over the ``width`` that turns."""
    theta = float(group["rope_theta"])
    i = np.arange(width // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / width)
    if group.get("rope_type", "default") == "default":
        return f, 1.0
    assert group["rope_type"] == "yarn", group["rope_type"]
    span, factor = group["original_max_position_embeddings"], group["factor"]

    def turns_at(rotations):
        return width * math.log(span / (rotations * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns_at(group.get("beta_fast") or 32)), 0)
    high = min(math.ceil(turns_at(group.get("beta_slow") or 1)), width - 1)
    r = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    attention_factor = group.get("attention_factor")
    if attention_factor is None:
        attention_factor = 0.1 * math.log(factor) + 1.0
    return f / factor * r + f * (1.0 - r), float(attention_factor)


def rotary(group: dict, width: int, length: int):
    """``(cos, sin) [T, width / 2]`` float32 of positions ``0 … T-1``."""
    freq, factor = inv_freq(group, width)
    # the angle as the system's float32 product of position and frequency
    angle = (np.arange(length, dtype=np.float32)[:, None]
             * freq.astype(np.float32)[None, :])
    return (jnp.asarray(np.cos(angle) * np.float32(factor), jnp.float32),
            jnp.asarray(np.sin(angle) * np.float32(factor), jnp.float32))


def rotate_leading(x, cos, sin):
    """The first ``2 · cos.shape[-1]`` of every head of ``x [T, heads, d]``
    turned by the position's angles, pairs ``(x_i, x_{i + D/2})``; the rest
    of the head as it is."""
    half = cos.shape[-1]
    cos, sin = cos[:, None, :], sin[:, None, :]
    lo, hi, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin, rest],
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


def attention(s, p, a, kind, heads, cos, sin):
    """The attention's output ``[T, hidden]`` of the normed layer input
    ``a``, ``QUERY_ROWS`` query rows at a time."""
    t = a.shape[0]
    nkv, d = s["num_key_value_heads"], s["head_dim"]
    q = rotate_leading(mm(a, p["attn/q"]).reshape(t, heads, d), cos, sin)
    k = rotate_leading(mm(a, p["attn/k"]).reshape(t, nkv, d), cos, sin)
    v = mm(a, p["attn/v"]).reshape(t, nkv, d)
    window = s["sliding_window"] if kind == SLIDING else None
    # query head j reads key/value head j // (heads / kv heads), where it
    # lies: no copy of k and v a query head
    group = heads // nkv
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
                                     jnp.arange(heads)))
        ctx.append(out.transpose(1, 0, 2))              # [rows, heads, d]
    gate = jax.nn.sigmoid(mm(a, p["attn/head_gate"]))   # [T, heads]
    ctx = jnp.concatenate(ctx) * gate[:, :, None]
    return mm(ctx.reshape(t, heads * d), p["attn/o"])


def routes(s, p, b):
    """``(experts [T, k], weights [T, k])``: sigmoid scores over ALL
    experts, the ``k`` largest, renormalised to sum the scaling factor."""
    k = s["num_experts_per_tok"]
    score = jax.nn.sigmoid(mm(b, p["moe/router"]))
    chosen = jnp.argsort(-score, axis=-1, stable=True)[:, :k]
    w = jnp.take_along_axis(score, chosen, axis=-1)
    return chosen, s["moe_routed_scaling_factor"] * w / (
        w.sum(axis=-1, keepdims=True) + 1e-20)


def held_experts(s, experts, b, chosen, w):
    """The held experts' part of ``b`` under the routes ``(chosen, w)``: a
    Python loop over the held experts, each applied to every token and kept
    by a boolean mask where the token chose it."""
    y = jnp.zeros_like(b)
    for k, e in enumerate(experts):
        took = chosen == s["first_held"] + k                    # [T, k]
        weight = jnp.sum(jnp.where(took, w, 0.0), axis=-1)      # [T]
        y = y + weight[:, None] * swiglu(b, e["gate"], e["up"], e["down"])
    return y


def _layer(s, p, experts, x, kind, heads, mlp, cos, sin):
    """``(the layer's output, the chosen experts [T, k]; none in a dense
    layer)``."""
    eps = s["rms_norm_eps"]
    a = rmsnorm(x, p["norm1/scale"], eps)
    h = x + attention(s, p, a, kind, heads, cos, sin)
    b = rmsnorm(h, p["norm2/scale"], eps)
    if mlp == DENSE:
        return h + swiglu(b, p["mlp/gate"], p["mlp/up"], p["mlp/down"]), None
    chosen, w = routes(s, p, b)
    shared = swiglu(b, p["moe/shared/gate"], p["moe/shared/up"],
                    p["moe/shared/down"])
    return h + shared + held_experts(s, experts, b, chosen, w), chosen


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


def _freeze(s) -> str:
    """The sizes as one hashable value (they hold lists and the rope
    groups)."""
    return json.dumps(s, sort_keys=True)


@jax.jit(static_argnums=(0, 4, 5, 6))
def _jit_layer(frozen, p, experts, x, kind, heads, mlp, cos, sin):
    with jax.default_matmul_precision("highest"):
        return _layer(json.loads(frozen), p, experts, x, kind, heads, mlp,
                      cos, sin)


@jax.jit(static_argnums=(0, 5))
def _jit_score(frozen, norm_w, head, x, targets, block):
    with jax.default_matmul_precision("highest"):
        s = json.loads(frozen)
        return _score(rmsnorm(x, norm_w, s["rms_norm_eps"]), head, targets,
                      block, s["behaviour_positions"])


def forward(s: dict, member: Member, tokens, head_block: int = 512,
            with_choices: bool = False, with_layers: bool = False):
    """One member over one sequence ``tokens [T]``: ``(log p(tokens[t+1])
    [T-1], the head's logits averaged over the last ``behaviour_positions``
    positions [vocab])``; with ``with_choices`` the chosen experts ``[T,
    k]`` of every sparse layer too, with ``with_layers`` every layer's
    output ``[T, hidden]``.  One layer's weights exist at a time."""
    frozen, t = _freeze(s), tokens.shape[0]
    tables = {
        kind: rotary(s["rope_parameters"][kind], int(
            s["head_dim"] * s["rope_parameters"][kind].get(
                "partial_rotary_factor", 1.0)), t)
        for kind in set(s["layer_types"])}
    # the embedding goes once the tokens are looked up, and the head comes
    # when the layers are done: beside the system's state on one chip the
    # float32 copies of both do not lie there while a layer runs
    x = jnp.take(member.leaf("embed/embedding"), tokens, axis=0)
    chosen, outputs = [], []
    for i, (kind, heads, mlp) in enumerate(zip(
            s["layer_types"], s["heads"], s["mlp_layer_types"])):
        base = f"layer_{i:02d}"
        x, c = _jit_layer(frozen, member.layer(base, mlp),
                          member.experts_of(base, mlp), x, kind, heads, mlp,
                          *tables[kind])
        if with_choices and c is not None:
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
