"""Plain reference for one ES member of a sparse-expert decoder whose token
mixers are Gated DeltaNet layers and gated full-attention layers
(Qwen3-Next-80B-A3B-Instruct's ``config.json``): float32 ``jax.numpy`` at
``highest`` matmul precision, written from the published description and
independent of the system's model code.  No batching over members, no
sharding, no engine, no chunk, no triangular system, no sort of pairs, no
grouped matmul, no kernel and no tile: the delta rule is the STEP recurrence,
one position after the other (``lax.scan`` over positions); Python loops over
layers and over the held experts, a boolean mask per expert, ONE full
``[rows, T]`` masked softmax per head over ``QUERY_ROWS`` query rows at a
time (a block only so that it fits).  It is given the same share of the model
as the system (which experts are held, which vocabulary rows) and NOT the
system's routes: it routes by itself.

What it follows (``config.json`` keys in quotes; (*) marks what the config
does not spell, listed under ``assumed`` in the configuration file), with
``ZNorm(x; w) = x rsqrt(mean x^2 + "rms_norm_eps") (1 + w)`` (*):

    x = E[tokens]
    each layer l ("full" where (l + 1) mod "full_attention_interval" = 0):
      a = ZNorm(x; g1)
      a linear layer ("linear_num_key_heads" key heads of
      "linear_key_head_dim", "linear_num_value_heads" value heads of
      "linear_value_head_dim"; value head j reads key head j // (nv / nk)):
        [q | k | v | z] = a W_qkvz (flat (*));  [b | a'] = a W_ba
        [q | k | v] <- silu(causal depthwise conv over time,
            "linear_conv_kernel_dim" taps, no bias, the last tap the
            current position's)
        beta = sigmoid(b);  g = -exp(A_log) softplus(a' + dt_bias)
        q <- q / sqrt(sum q^2 + 1e-6) / sqrt(dk);  k <- k / sqrt(sum k^2 + 1e-6)
        S_0 = 0;  S~ = exp(g_t) S_{t-1};  S_t = S~ + k_t (x) (beta_t (v_t -
            S~^T k_t));  o_t = S_t^T q_t                      a value head
        y = o rsqrt(mean o^2 + eps) w_n * silu(z)             a value head
        h = x + y W_o
      a full layer ("num_attention_heads" over "num_key_value_heads" heads
      of "head_dim"):
        [q | gate] a head = a W_q;  k = a W_k;  v = a W_v
        q <- ZNorm(q; w_q), k <- ZNorm(k; w_k) a head; the first
            "partial_rotary_factor" x "head_dim" of each head rotated, pairs
            (x_i, x_{i + rot/2}) by the angle t "rope_theta"^(-2i/rot)
        h = x + (softmax_s(q . k / sqrt(head_dim), s <= t) v * sigmoid(gate)) W_o
      b = ZNorm(h; g2)
      p = softmax(b W_r) over ALL "num_experts" experts;  S = the
          "num_experts_per_tok" largest (ties to the lower index);
          w_e = p_e / (sum_{e' in S} p_e' + 1e-20)         ("norm_topk_prob")
      x = h + sum_{e in S, e held here} w_e FFN_e(b)
            + sigmoid(b w_s) FFN_shared(b)   (SwiGLU, "moe_intermediate_size",
                                              "shared_expert_intermediate_size")
    h = ZNorm(x; g_final);  score_t = log p(tokens[t+1]) from h W_head
    behaviour: the head's logits averaged over the last
          "behaviour_positions" positions (*)

A member's weights are ``theta + sigma * sign * E`` with ``E = A B^T /
sqrt(r)`` MATERIALISED a leaf at a time, and for a stacked expert leaf
``[experts, m, n]`` an expert at a time from that expert's own factor pair;
leaves where factoring would not save (norm weights, conv taps, ``A_log``,
``dt_bias``, the shared expert's one-column gate) carry dense noise.  Table,
offsets and keys are the system's (``parallel/sharded.py``), as
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
LINEAR, FULL = "linear", "full"
DEFAULTS = dict(
    linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=8,
    linear_value_head_dim=8, linear_conv_kernel_dim=4,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    partial_rotary_factor=0.25, num_experts=8, expert_group_size=1,
    expert_group_rank=0, num_experts_per_tok=2, behaviour_positions=512,
    rope_theta=10000.0, rms_norm_eps=1e-6)


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
    out["key_dim"] = out["linear_num_key_heads"] * out["linear_key_head_dim"]
    out["value_dim"] = (out["linear_num_value_heads"]
                        * out["linear_value_head_dim"])
    out["rotary_dim"] = int(out["head_dim"] * out["partial_rotary_factor"])
    return out


def mixer_layout(s: dict, kind: str) -> list:
    """``[(relative path, shape)]`` of a layer's token mixer, in the
    system's sorted-key order (upper case first)."""
    h, d = s["hidden_size"], s["head_dim"]
    nq, nkv = s["num_attention_heads"], s["num_key_value_heads"]
    if kind == FULL:
        return [("attn/k", (h, nkv * d)), ("attn/k_norm/scale", (d,)),
                ("attn/o", (nq * d, h)), ("attn/q", (h, nq * 2 * d)),
                ("attn/q_norm/scale", (d,)), ("attn/v", (h, nkv * d))]
    conv, nv = 2 * s["key_dim"] + s["value_dim"], s["linear_num_value_heads"]
    return [("delta/A_log", (nv,)),
            ("delta/conv", (s["linear_conv_kernel_dim"], 1, conv)),
            ("delta/dt_bias", (nv,)), ("delta/in_proj_ba", (h, 2 * nv)),
            ("delta/in_proj_qkvz", (h, conv + s["value_dim"])),
            ("delta/norm_scale", (s["linear_value_head_dim"],)),
            ("delta/out_proj", (s["value_dim"], h))]


def _layer_layout(s: dict, base: str, kind: str) -> list:
    h = s["hidden_size"]
    e, w = s["num_experts"], s["moe_intermediate_size"]
    ws = s["shared_expert_intermediate_size"]
    return [(f"{base}/{path}", shape)
            for path, shape in mixer_layout(s, kind)] + [
        (f"{base}/moe/experts/down", (e, w, h)),
        (f"{base}/moe/experts/gate", (e, h, w)),
        (f"{base}/moe/experts/up", (e, h, w)),
        (f"{base}/moe/router", (h, s["experts_total"])),
        (f"{base}/moe/shared/down", (ws, h)),
        (f"{base}/moe/shared/gate", (h, ws)),
        (f"{base}/moe/shared/up", (h, ws)),
        (f"{base}/moe/shared_gate", (h, 1)),
        (f"{base}/norm1/scale", (h,)),
        (f"{base}/norm2/scale", (h,))]


def system_layout(s: dict) -> list[tuple[str, tuple]]:
    """``[(path, shape), ...]`` of the system's flat parameter vector: its
    leaves in sorted-key order."""
    h, v = s["hidden_size"], s["vocab_size"]
    out = [("embed/embedding", (v, h)), ("final_norm/scale", (h,)),
           ("head/kernel", (h, v))]
    for i, kind in enumerate(s["layer_types"]):
        out += _layer_layout(s, f"layer_{i:02d}", kind)
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
    """``(the mixers' projections and the shared expert with its gate, the
    routers', the head's)`` a token passes, as ``(m, n)``."""
    h, ws = s["hidden_size"], s["shared_expert_intermediate_size"]
    dense, routers = [], []
    for kind in s["layer_types"]:
        dense += [shape for _, shape in mixer_layout(s, kind)
                  if len(shape) == 2]
        dense += [(h, ws), (h, ws), (ws, h), (h, 1)]
        routers.append((h, s["experts_total"]))
    return dense, routers, [(h, s["vocab_size"])]


def describe(config: dict) -> dict:
    """What the harness needs to know: the length of the flat parameter
    vector, and 2 x the matmul weights one token passes
    (``costs.matmul_flops``; the attention's own scores and the delta
    rule's products left out: ``costs_gdn.py`` counts those), split into
    what runs under ``es.dense`` (both mixers' projections, the shared
    expert and its gate), the head's, and, in the total alone, the routers'
    and the held experts' at the pairs a uniform router sends them
    (``costs_moe.py``)."""
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

def _seeded(path: str, table: dict, default):
    """``table``'s entry for the leaf at ``path``: by its last two path
    components (``shared/down``), else by its name, else ``default``."""
    parts = path.split("/")
    return table.get("/".join(parts[-2:]), table.get(parts[-1], default))


def init_theta(key, config):
    """Seeded initial weights in the system's flat layout, made on the device
    in one jitted call.  Matrices, conv taps and the embedding normal, the
    standard deviation the configuration file's ``seeded_std`` for the
    leaf's name (``shared/down``: its last two path components; ``other``
    for those not named; ``assumed: initialisation`` says why each has its
    own).  Norm weights constant: the zero-centred ``scale`` leaves at the
    file's ``seeded_norm`` for their name (0 where it has none: ``1 + w``
    is 1), the gated norm's ``norm_scale`` 1.  ``A_log`` the file's
    ``seeded_decay.A_log``; ``dt_bias`` the inverse softplus of a step
    log-uniform over ``seeded_decay.step`` (a value head's own), so that at
    ``a' = 0`` a step's decay is ``exp(-exp(A_log) step)``.  A rehearsal
    (the runner marks the configuration ``rehearsing``) reads
    ``rehearsal_seeded_std`` where the file has one."""
    stds = dict((config.get("rehearsing")
                 and config.get("rehearsal_seeded_std"))
                or config.get("seeded_std", {}))
    other = stds.pop("other", 0.02)
    norms = config.get("seeded_norm", {})
    decay = config.get("seeded_decay", {"A_log": 0.0, "step": [1e-3, 1e-1]})
    layout = []
    for path, shape in system_layout(sizes(config)):
        name = path.rsplit("/", 1)[1]
        if name == "scale":
            how = ("constant", float(_seeded(path, norms, 0.0)))
        elif name == "norm_scale":
            how = ("constant", 1.0)
        elif name == "A_log":
            how = ("constant", float(decay["A_log"]))
        elif name == "dt_bias":
            how = ("step", tuple(float(x) for x in decay["step"]))
        else:
            how = ("normal", float(_seeded(path, stds, other)))
        layout.append((shape, how))
    return _init_theta(key, tuple(layout))


@jax.jit(static_argnums=(1,))
def _init_theta(key, layout):
    parts = []
    for i, (shape, (kind, value)) in enumerate(layout):
        k = jax.random.fold_in(key, i)
        if kind == "constant":
            v = jnp.full(shape, value, jnp.float32)
        elif kind == "step":
            low, high = value
            step = jnp.exp(jax.random.uniform(
                k, shape, jnp.float32, math.log(low), math.log(high)))
            v = step + jnp.log(-jnp.expm1(-step))
        else:
            v = value * jax.random.normal(k, shape, jnp.float32)
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

    def layer(self, base, kind):
        """``{relative path: weights}`` of a layer's leaves outside the
        routed experts."""
        names = [path for path, _ in _layer_layout(self.s, base, kind)
                 if "/experts/" not in path]
        return {n[len(base) + 1:]: self.leaf(n) for n in names}

    def experts_of(self, base):
        """``[{gate, up, down}, ...]`` of the held experts of a layer."""
        return [{n: self.expert(f"{base}/moe/experts/{n}", k)
                 for n in ("gate", "up", "down")}
                for k in range(self.s["num_experts"])]


# ---------------------------------------------------------------- forward

def znorm(x, w, eps):
    """The zero-centred RMSNorm: the stored weight is ``w``, the factor
    ``1 + w``."""
    return x * jax.lax.rsqrt(
        jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w)


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def swiglu(u, gate, up, down):
    return mm(jax.nn.silu(mm(u, gate)) * mm(u, up), down)


def rotary(theta: float, width: int, length: int):
    """``(cos, sin) [T, width / 2]``: frequency pair ``i`` turns by ``t
    theta^(-2i/width)`` at position ``t``."""
    inv_freq = theta ** (-np.arange(0, width, 2, dtype=np.float64) / width)
    angle = np.arange(length, dtype=np.float64)[:, None] * inv_freq[None, :]
    return (jnp.asarray(np.cos(angle), jnp.float32),
            jnp.asarray(np.sin(angle), jnp.float32))


def rotate_leading(x, cos, sin):
    """The first ``2 x cos.shape[1]`` entries of each head of ``x [T, heads,
    d]`` turned, pairs ``(x_i, x_{i + rot/2})``; the rest as it is."""
    half = cos.shape[1]
    cos, sin = cos[:, None, :], sin[:, None, :]
    lo, hi, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin, rest],
                           axis=-1)


def causal_conv_silu(x, taps):
    """``silu(sum_j taps[j] x_{t-(K-1-j)})`` of ``x [T, C]``, zeros before
    the sequence; ``taps [K, 1, C]``, the last the current position's."""
    k_taps, t = taps.shape[0], x.shape[0]
    padded = jnp.pad(x, ((k_taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(taps[j, 0] * padded[j:j + t]
                           for j in range(k_taps)))


def unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_recurrence(q, k, v, g, beta):
    """``o [T, nv, dv]`` of the gated delta rule, ONE position after the
    other: ``q, k [T, nk, dk]``, ``v [T, nv, dv]``, ``g, beta [T, nv]``;
    value head ``j`` reads key head ``j // (nv / nk)``."""
    t, nk, dk = q.shape
    nv, dv = v.shape[1:]
    rep = nv // nk

    def step(state, xs):            # state [nk, rep, dk, dv]
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t)[..., None, None] * state
        held = jnp.einsum("hrkv,hk->hrv", state, k_t, precision=HIGHEST)
        write = b_t[..., None] * (v_t - held)
        state = state + k_t[:, None, :, None] * write[:, :, None, :]
        return state, jnp.einsum("hrkv,hk->hrv", state, q_t,
                                 precision=HIGHEST)

    _, o = jax.lax.scan(
        step, jnp.zeros((nk, rep, dk, dv), jnp.float32),
        (q, k, v.reshape(t, nk, rep, dv), g.reshape(t, nk, rep),
         beta.reshape(t, nk, rep)))
    return o.reshape(t, nv, dv)


def delta_mixer(s, p, a):
    """The Gated DeltaNet's output ``[T, hidden]`` of the normed layer input
    ``a``."""
    t, eps = a.shape[0], s["rms_norm_eps"]
    nk, nv = s["linear_num_key_heads"], s["linear_num_value_heads"]
    dk, dv = s["linear_key_head_dim"], s["linear_value_head_dim"]
    key_dim, conv = s["key_dim"], 2 * s["key_dim"] + s["value_dim"]
    qkvz = mm(a, p["delta/in_proj_qkvz"])
    z = qkvz[:, conv:].reshape(t, nv, dv)
    qkv = causal_conv_silu(qkvz[:, :conv], p["delta/conv"])
    ba = mm(a, p["delta/in_proj_ba"])
    beta = jax.nn.sigmoid(ba[:, :nv])
    g = -jnp.exp(p["delta/A_log"]) * jax.nn.softplus(
        ba[:, nv:] + p["delta/dt_bias"])
    q = unit(qkv[:, :key_dim].reshape(t, nk, dk)) / math.sqrt(dk)
    k = unit(qkv[:, key_dim:2 * key_dim].reshape(t, nk, dk))
    v = qkv[:, 2 * key_dim:].reshape(t, nv, dv)
    o = delta_recurrence(q, k, v, g, beta)
    y = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    y = y * p["delta/norm_scale"] * jax.nn.silu(z)
    return mm(y.reshape(t, nv * dv), p["delta/out_proj"])


def visible(first_row: int, rows: int, length: int):
    """``[rows, T]`` bool: key ``s`` seen by query ``t = first_row + row``."""
    queries = first_row + np.arange(rows)[:, None]
    return jnp.asarray(np.arange(length)[None, :] <= queries)


def attention(s, p, a, cos, sin):
    """The gated attention's output ``[T, hidden]`` of the normed layer
    input ``a``, ``QUERY_ROWS`` query rows at a time."""
    t, eps = a.shape[0], s["rms_norm_eps"]
    nq, nkv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                  s["head_dim"])
    both = mm(a, p["attn/q"]).reshape(t, nq, 2 * d)
    q, gate = both[..., :d], both[..., d:].reshape(t, nq * d)
    k = mm(a, p["attn/k"]).reshape(t, nkv, d)
    v = mm(a, p["attn/v"]).reshape(t, nkv, d)
    q = rotate_leading(znorm(q, p["attn/q_norm/scale"], eps), cos, sin)
    k = rotate_leading(znorm(k, p["attn/k_norm/scale"], eps), cos, sin)
    group = nq // nkv
    k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
    ctx = []
    for first in range(0, t, QUERY_ROWS):
        rows = slice(first, min(first + QUERY_ROWS, t))
        mask = visible(first, rows.stop - first, t)

        def one_head(xs, mask=mask):
            q_h, j = xs
            k_h, v_h = k[j // group], v[j // group]
            scores = jnp.matmul(q_h, k_h.T, precision=HIGHEST) / math.sqrt(d)
            prob = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
            return jnp.matmul(prob, v_h, precision=HIGHEST)

        out = jax.lax.map(one_head, (q[rows].transpose(1, 0, 2),
                                     jnp.arange(nq)))
        ctx.append(out.transpose(1, 0, 2).reshape(-1, nq * d))
    return mm(jnp.concatenate(ctx) * jax.nn.sigmoid(gate), p["attn/o"])


def routes(s, p, b):
    """``(experts [T, k], weights [T, k])``: the router over ALL experts."""
    k = s["num_experts_per_tok"]
    prob = jax.nn.softmax(mm(b, p["moe/router"]), axis=-1)
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
        y = y + weight[:, None] * swiglu(b, e["gate"], e["up"], e["down"])
    return y


def shared_expert(p, b):
    """``sigmoid(b w_s) FFN_shared(b)``: whole on every share."""
    return jax.nn.sigmoid(mm(b, p["moe/shared_gate"])) * swiglu(
        b, p["moe/shared/gate"], p["moe/shared/up"], p["moe/shared/down"])


def expert_layer(s, p, experts, h):
    """``(h + the shared and the held experts' part, the chosen experts)``."""
    b = znorm(h, p["norm2/scale"], s["rms_norm_eps"])
    chosen, w = routes(s, p, b)
    return (h + held_experts(s, experts, b, chosen, w)
            + shared_expert(p, b)), chosen


def _layer(s, p, experts, x, kind, cos, sin):
    """``(the layer's output, the chosen experts [T, k])``."""
    a = znorm(x, p["norm1/scale"], s["rms_norm_eps"])
    h = x + (delta_mixer(s, p, a) if kind == LINEAR
             else attention(s, p, a, cos, sin))
    return expert_layer(s, p, experts, h)


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
        return _score(znorm(x, norm_w, s["rms_norm_eps"]), head, targets,
                      block, s["behaviour_positions"])


def forward(s: dict, member: Member, tokens, head_block: int = 512,
            with_choices: bool = False, with_layers: bool = False):
    """One member over one sequence ``tokens [T]``: ``(log p(tokens[t+1])
    [T-1], the head's logits averaged over the last ``behaviour_positions``
    positions [vocab])``; with ``with_choices`` the chosen experts ``[T,
    k]`` of every layer too, with ``with_layers`` every layer's output
    ``[T, hidden]``.  One layer's weights exist at a time."""
    frozen, t = _freeze(s), tokens.shape[0]
    cos, sin = rotary(s["rope_theta"], s["rotary_dim"], t)
    # the embedding goes once the tokens are looked up, and the head comes
    # when the layers are done: beside the system's state on one chip the
    # float32 copies of both do not lie there while a layer runs
    x = jnp.take(member.leaf("embed/embedding"), tokens, axis=0)
    chosen, outputs = [], []
    for i, kind in enumerate(s["layer_types"]):
        base = f"layer_{i:02d}"
        x, c = _jit_layer(frozen, member.layer(base, kind),
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
