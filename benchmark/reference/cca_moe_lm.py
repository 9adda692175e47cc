"""Plain reference for one ES member of a sparse-expert decoder whose
attention is computed inside a COMPRESSED LATENT (ZAYA1-8B's ``config.json``,
``model_type`` ``zaya``; CCA, arXiv 2510.04476; the router of arXiv
2511.17127).  float32 ``jax.numpy`` at ``highest`` matmul precision, written
from the published description and independent of the system's model code.
No batching over members, no sharding, no engine, no sort of pairs, no
grouped matmul, no stacked convolution and no tile: Python loops over layers,
heads, taps and the held experts, both convolutions as explicit shifted sums
a head at a time, a boolean mask per expert, ONE full ``[rows, T]`` masked
softmax per head over ``QUERY_ROWS`` query rows at a time, an ``argmax`` of
its own.  It is given the same share of the model as the system (which
experts are held, which vocabulary rows) and NOT the system's routes.

What it follows (``config.json`` keys in quotes; (*) marks what the config
does not spell, listed under ``assumed`` in the configuration file):

    x = E[tokens];  r_{-1} = 0
    each layer:  x += cca(rmsnorm_1 x);  (y, r) = moe(rmsnorm_2 x, r);  x += y
    cca(u):  q~ = u W_q -> "num_attention_heads" x "head_dim"
             k~ = u W_k -> "num_key_value_heads" x "head_dim"
             v  = u W_v, the LAST half of the value heads read from the
                  position before (zeros before the sequence) (*)
      conv:  z = q~ and k~ side by side, heads + kv heads of them
             z1_t = sum_j a_j * z_{t-(K0-1-j)} + b1      "cca_time0" taps, per channel
             z2_t[h] = sum_j z1_{t-(K1-1-j)}[h] C_j[h] + b2[h]   "cca_time1" taps,
                  a matrix a tap and head over the head's channels; zero padding (*)
      mean:  q = q' + (q~ + k~ of the query's group) / 2
             k = k' + (mean of the group's q~ + k~) / 2 (*)
      norm:  q^ = sqrt(d) q / |q|,  k^ = sqrt(d) tau_g k / |k| per head (*)
      rope:  the first "partial_rotary_factor" x d channels of a head turned,
             pairs (x_i, x_{i + w/2}) inside that slice of width w by the
             angle p theta^(-2i/w), "rope_theta"; the rest left alone
      score_h[t, s] = q^_h[t] . k^_{h // group}[s] / sqrt(d) for s <= t
      P = softmax_s;  ctx = P v;  out = ctx W_o
    moe(u, r_below):
             r = u W_dn + b_dn + gamma * r_below          "router_hidden_size"
             z = W3 gelu(W2 gelu(W1 rmsnorm(r) + b1) + b2) + b3   (erf GELU) (*)
             p = softmax(z) over ALL "num_experts";  e = argmax(p + beta),
                 the first of equal values;  weight p[e], NOT renormalised
             y = p[e] expert_e(u) if e is held here, else 0  (gated SiLU,
                 "moe_intermediate_size")
    h = rmsnorm_final(x);  score_t = log p(tokens[t+1]) from h E^T
        ("tie_word_embeddings")
    behaviour: the head's logits averaged over the last
               "behaviour_positions" positions (*)

A member's weights are ``theta + sigma * sign * E`` with ``E = A B^T /
sqrt(r)`` MATERIALISED a leaf at a time, for a stacked leaf (the experts'
``[experts, m, n]``, the head-mixing convolution's ``[taps x heads, d, d]``)
a matrix at a time from that matrix's own factor pair; leaves where
factoring would not save (norm weights, biases, the depthwise taps, gamma,
the temperatures) carry dense noise.  Table, offsets and keys are the
system's (``parallel/sharded.py``), as ``reference/moe_lm.py`` spells them.
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
DEFAULTS = dict(
    num_attention_heads=8, num_key_value_heads=2, head_dim=16, cca_time0=2,
    cca_time1=2, partial_rotary_factor=0.5, router_hidden_size=16,
    num_experts=2, expert_group_size=1, expert_group_rank=0,
    num_experts_per_tok=1, behaviour_positions=512, rope_theta=10000.0,
    rms_norm_eps=1e-5)
STACKED = ("/experts/", "/conv_head")


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
    out["latent_heads"] = (out["num_attention_heads"]
                           + out["num_key_value_heads"])
    out["rotary_dim"] = int(out["head_dim"] * out["partial_rotary_factor"])
    return out


LAYER_LEAVES = (
    "attn/conv_head_bias", "attn/conv_time", "attn/conv_time_bias", "attn/k",
    "attn/o", "attn/q", "attn/temperature", "attn/v", "moe/router_bias",
    "moe/router_down", "moe/router_down_bias", "moe/router_mlp/b1",
    "moe/router_mlp/b2", "moe/router_mlp/b3", "moe/router_mlp/w1",
    "moe/router_mlp/w2", "moe/router_mlp/w3", "moe/router_norm/scale",
    "moe/router_state", "norm1/scale", "norm2/scale")


def _layer_layout(s: dict, base: str) -> list:
    h, d, r = s["hidden_size"], s["head_dim"], s["router_hidden_size"]
    nq, nkv, lat = (s["num_attention_heads"], s["num_key_value_heads"],
                    s["latent_heads"])
    e, w, total = s["num_experts"], s["moe_intermediate_size"], s["experts_total"]
    return [(f"{base}/attn/conv_head", (s["cca_time1"] * lat, d, d)),
            (f"{base}/attn/conv_head_bias", (lat * d,)),
            (f"{base}/attn/conv_time", (s["cca_time0"], 1, lat * d)),
            (f"{base}/attn/conv_time_bias", (lat * d,)),
            (f"{base}/attn/k", (h, nkv * d)),
            (f"{base}/attn/o", (nq * d, h)),
            (f"{base}/attn/q", (h, nq * d)),
            (f"{base}/attn/temperature", (nkv,)),
            (f"{base}/attn/v", (h, nkv * d)),
            (f"{base}/moe/experts/down", (e, w, h)),
            (f"{base}/moe/experts/gate", (e, h, w)),
            (f"{base}/moe/experts/up", (e, h, w)),
            (f"{base}/moe/router_bias", (total,)),
            (f"{base}/moe/router_down", (h, r)),
            (f"{base}/moe/router_down_bias", (r,)),
            (f"{base}/moe/router_mlp/b1", (r,)),
            (f"{base}/moe/router_mlp/b2", (r,)),
            (f"{base}/moe/router_mlp/b3", (total,)),
            (f"{base}/moe/router_mlp/w1", (r, r)),
            (f"{base}/moe/router_mlp/w2", (r, r)),
            (f"{base}/moe/router_mlp/w3", (r, total)),
            (f"{base}/moe/router_norm/scale", (r,)),
            (f"{base}/moe/router_state", (r,)),
            (f"{base}/norm1/scale", (h,)),
            (f"{base}/norm2/scale", (h,))]


def system_layout(s: dict) -> list[tuple[str, tuple]]:
    """``[(path, shape), ...]`` of the system's flat parameter vector: its
    leaves in sorted-key order.  No head: it is the embedding's."""
    out = [("embed/embedding", (s["vocab_size"], s["hidden_size"])),
           ("final_norm/scale", (s["hidden_size"],))]
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
    ``"__dim__"``.  A stacked leaf ``[e, m, n]`` holds ``A [e, m, r]`` then
    ``B [e, n, r]``: one factor pair a matrix."""
    r, out, at = s["low_rank"], {}, 0
    for path, shape in system_layout(s):
        if len(shape) == 2 and r * (shape[0] + shape[1]) < shape[0] * shape[1]:
            out[path] = ("lr", at, at + shape[0] * r)
            at += (shape[0] + shape[1]) * r
        elif (len(shape) == 3 and any(mark in path for mark in STACKED)
              and r * (shape[1] + shape[2]) < shape[1] * shape[2]):
            out[path] = ("stacked", at, at + shape[0] * shape[1] * r)
            at += shape[0] * (shape[1] + shape[2]) * r
        else:
            out[path] = ("dense", at)
            at += math.prod(shape)
    out["__dim__"] = at
    return out


def matmul_shapes(s: dict) -> tuple[list, list, list, list]:
    """``(the attention's projections, the head-mixing convolution's
    matrices, the routers', the head's)`` a token passes, as ``(m, n)``."""
    h, d, r = s["hidden_size"], s["head_dim"], s["router_hidden_size"]
    nq, nkv = s["num_attention_heads"], s["num_key_value_heads"]
    layers = len(s["layer_types"])
    attn = [(h, nq * d), (h, nkv * d), (h, nkv * d), (nq * d, h)] * layers
    conv = [(d, d)] * (s["cca_time1"] * s["latent_heads"] * layers)
    routers = [(h, r), (r, r), (r, r), (r, s["experts_total"])] * layers
    return attn, conv, routers, [(h, s["vocab_size"])]


def describe(config: dict) -> dict:
    """What the harness needs to know: the length of the flat parameter
    vector, and 2 x the matmul weights one token passes
    (``costs.matmul_flops``; the attention's own scores left out), split
    into what runs under ``es.dense`` (the attention's four projections),
    the head's, and, in the total alone, the head-mixing convolution's, the
    routers' and the held experts' at the pairs a uniform router sends them
    (``costs_moe.py``)."""
    s = sizes(config)
    attn, conv, routers, heads = matmul_shapes(s)
    layers = len(s["layer_types"])
    pairs = costs_moe.expected_pairs_per_token(
        s["num_experts_per_tok"], s["num_experts"], s["experts_total"])
    expert_flops = int(layers * pairs * costs_moe.expert_flops_per_pair(
        s["hidden_size"], s["moe_intermediate_size"]))
    return {"param_dim": param_offsets(s)["__dim__"][0],
            "noise_dim": noise_layout(s)["__dim__"],
            "flops_per_member_step": (
                costs.matmul_flops(attn + conv + routers + heads)
                + expert_flops),
            "dense_flops_per_member_step": costs.matmul_flops(attn),
            "head_flops_per_member_step": costs.matmul_flops(heads),
            "mix_flops_per_member_step": costs.matmul_flops(conv),
            "route_flops_per_member_step": costs.matmul_flops(routers),
            "expert_flops_per_member_step": expert_flops,
            "expert_layers": layers,
            "expected_pairs_per_token_and_layer": pairs}


# ------------------------------------------------------------------- init

# learned scales start at one; every bias at zero
ONES = ("scale", "router_state", "temperature")


def is_bias(name: str) -> bool:
    return name.endswith("bias") or name in ("b1", "b2", "b3")


def init_theta(key, config):
    """Seeded initial weights in the system's flat layout, made on the device
    in one jitted call: matrices and the depthwise taps normal, norm
    weights, gamma and the temperatures one, every bias (beta among them)
    zero.  A matrix's standard deviation is the configuration file's
    ``seeded_std`` for its leaf's name, ``other`` there for those not named
    (``assumed: initialisation`` says why some have their own), 0.02 where
    the file has none.  A leaf named in ``seeded_orthogonal`` is drawn with
    ORTHONORMAL columns (the Q of a Gaussian's QR) and rescaled to that
    spread: every direction of its input is passed alike, which is what
    keeps a router of several matrices balanced.  ``seeded_scale`` gives a
    learned scale, by the end of its path, another start than one (the
    final norm's under a TIED head: the same table is read at the lookup
    and at the logits)."""
    stds = dict(config.get("seeded_std", {}))
    other = stds.pop("other", 0.02)
    scales = config.get("seeded_scale", {})
    orthogonal = set(config.get("seeded_orthogonal", ()))

    def start(path):
        name = path.rsplit("/", 1)[1]
        if name in ONES:
            return "one", next((v for end, v in scales.items()
                                if path.endswith(end)), 1.0)
        if is_bias(name):
            return "zero", 0.0
        return ("orthogonal" if name in orthogonal else "normal",
                stds.get(name, other))

    return _init_theta(key, tuple(
        (shape,) + start(path) for path, shape in system_layout(sizes(config))))


@jax.jit(static_argnums=(1,))
def _init_theta(key, layout):
    parts = []
    for i, (shape, kind, value) in enumerate(layout):
        if kind in ("one", "zero"):
            v = jnp.full(shape, value, jnp.float32)
        else:
            v = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if kind == "orthogonal":    # unit columns, then the spread
                v = jnp.linalg.qr(v)[0] * math.sqrt(shape[0])
            v = value * v
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
    """One member's weights ``theta + sigma * sign * E``, a leaf (and a
    matrix of a stacked leaf) at a time: ``theta`` is the centre's flat
    vector (host or device), ``noise`` the member's pair's slice of the table
    (``None``: the centre alone)."""

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
        elif entry[0] == "dense":
            e = self.noise[entry[1]:entry[1] + math.prod(shape)].reshape(shape)
        else:
            raise ValueError(f"{path} is stacked: read it a matrix at a time")
        return w + self.scale * e

    def matrix(self, path, k):
        """Matrix ``k`` ``[m, n]`` of the stacked leaf at ``path``."""
        off, (_, m, n) = self.at[path]
        w = self._centre(off + k * m * n, (m, n))
        if self.noise is None:
            return w
        entry = self.noise_at[path]
        if entry[0] == "dense":     # where factoring a matrix would not save
            at = entry[1] + k * m * n
            return w + self.scale * self.noise[at:at + m * n].reshape(m, n)
        r = self.s["low_rank"]
        return w + self.scale * self._outer(
            entry[1] + k * m * r, entry[2] + k * n * r, m, n)

    def layer(self, base):
        return {n: self.leaf(f"{base}/{n}") for n in LAYER_LEAVES}

    def experts_of(self, base):
        """``[{gate, up, down}, ...]`` of the held experts of a layer."""
        return [{n: self.matrix(f"{base}/moe/experts/{n}", k)
                 for n in ("gate", "up", "down")}
                for k in range(self.s["num_experts"])]

    def conv_of(self, base):
        """``[tap][head] -> [d, d]`` of the head-mixing convolution: the
        stack is tap-major."""
        heads = self.s["latent_heads"]
        return [[self.matrix(f"{base}/attn/conv_head", j * heads + h)
                 for h in range(heads)] for j in range(self.s["cca_time1"])]


# ---------------------------------------------------------------- forward

def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def gated(u, gate, up, down):
    return mm(jax.nn.silu(mm(u, gate)) * mm(u, up), down)


def gelu(x):
    return 0.5 * x * (1.0 + jax.scipy.special.erf(x / math.sqrt(2.0)))


def earlier(x, back: int):
    """``x`` read ``back`` positions before, zeros before the sequence."""
    if back == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:back]), x[:-back]], axis=0)


def rotary(theta: float, width: int, length: int):
    """``(cos, sin) [T, width / 2]``: pair ``i`` of ``width / 2`` turns by
    ``p theta^(-2i/width)`` at position ``p``."""
    inv_freq = theta ** (-np.arange(0, width, 2, dtype=np.float64) / width)
    angle = np.arange(length, dtype=np.float64)[:, None] * inv_freq[None, :]
    return (jnp.asarray(np.cos(angle), jnp.float32),
            jnp.asarray(np.sin(angle), jnp.float32))


def rotate_leading(x, cos, sin):
    """The leading ``w = 2 x cos.shape[1]`` channels of ``x [T, d]`` turned
    in pairs ``(x_i, x_{i + w/2})``, the rest left alone."""
    w = 2 * cos.shape[1]
    lo, hi, rest = x[:, :w // 2], x[:, w // 2:w], x[:, w:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin, rest],
                           axis=-1)


def cca(s, p, conv, u, cos, sin, faults=()):
    """The attention's output ``[T, hidden]``; ``faults``: the degraded
    forms a rehearsal asks for (``no_value_shift``, ``no_mean``,
    ``whole_rotation``)."""
    t = u.shape[0]
    nq, nkv, d = (s["num_attention_heads"], s["num_key_value_heads"],
                  s["head_dim"])
    group, eps = nq // nkv, s["rms_norm_eps"]
    q_pre = [mm(u, p["attn/q"])[:, h * d:(h + 1) * d] for h in range(nq)]
    k_pre = [mm(u, p["attn/k"])[:, g * d:(g + 1) * d] for g in range(nkv)]
    v_all = mm(u, p["attn/v"])
    v = []
    for g in range(nkv):
        v_g = v_all[:, g * d:(g + 1) * d]
        shifted = g >= nkv // 2 and "no_value_shift" not in faults
        v.append(earlier(v_g, 1) if shifted else v_g)
    # the depthwise convolution, a head's channels at a time
    pre = q_pre + k_pre
    taps, bias1 = p["attn/conv_time"], p["attn/conv_time_bias"]
    k0, k1 = s["cca_time0"], s["cca_time1"]
    z1 = []
    for h, z in enumerate(pre):
        cols = slice(h * d, (h + 1) * d)
        acc = bias1[cols]
        for j in range(k0):
            acc = acc + taps[j, 0, cols] * earlier(z, k0 - 1 - j)
        z1.append(acc)
    # the head-mixing one
    bias2 = p["attn/conv_head_bias"]
    z2 = []
    for h, z in enumerate(z1):
        acc = bias2[h * d:(h + 1) * d]
        for j in range(k1):
            acc = acc + mm(earlier(z, k1 - 1 - j), conv[j][h])
        z2.append(acc)
    mean = 0.0 if "no_mean" in faults else 0.5
    q = [z2[h] + mean * (q_pre[h] + k_pre[h // group]) for h in range(nq)]
    k = [z2[nq + g] + mean * (
        sum(q_pre[g * group:(g + 1) * group]) / group + k_pre[g])
        for g in range(nkv)]
    # sqrt(d) x / |x|, with the norm's eps under the root as the system has it
    def unit(x):
        return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)

    tau = p["attn/temperature"]
    q = [unit(x) for x in q]
    k = [unit(x) * tau[g] for g, x in enumerate(k)]
    if "whole_rotation" in faults:
        cos, sin = rotary(s["rope_theta"], d, t)
    q = [rotate_leading(x, cos, sin) for x in q]
    k = [rotate_leading(x, cos, sin) for x in k]
    ctx = []
    for first in range(0, t, QUERY_ROWS):
        rows = slice(first, min(first + QUERY_ROWS, t))
        n_rows = rows.stop - rows.start
        mask = (jnp.arange(t)[None, :]
                <= first + jnp.arange(n_rows)[:, None])
        heads = []
        for h in range(nq):
            scores = mm(q[h][rows], k[h // group].T) / math.sqrt(d)
            prob = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
            heads.append(mm(prob, v[h // group]))
        ctx.append(jnp.concatenate(heads, axis=-1))
    return mm(jnp.concatenate(ctx), p["attn/o"])


def router(s, p, u, below, faults=()):
    """``(probabilities [T, experts], the chosen expert [T], the state
    handed upward [T, router width])``."""
    r = mm(u, p["moe/router_down"]) + p["moe/router_down_bias"]
    if "no_state" not in faults:
        r = r + p["moe/router_state"] * below
    x = rmsnorm(r, p["moe/router_norm/scale"], s["rms_norm_eps"])
    x = gelu(mm(x, p["moe/router_mlp/w1"]) + p["moe/router_mlp/b1"])
    x = gelu(mm(x, p["moe/router_mlp/w2"]) + p["moe/router_mlp/b2"])
    z = mm(x, p["moe/router_mlp/w3"]) + p["moe/router_mlp/b3"]
    prob = jax.nn.softmax(z, axis=-1)
    return prob, jnp.argmax(prob + p["moe/router_bias"], axis=-1), r


def moe_ffn(s, p, experts, u, below, faults=()):
    """``(the held experts' part, the chosen expert [T], the state)``: a
    Python loop over the held experts, each applied to every token and kept
    by a boolean mask where the token chose it, at the weight ``p[e]``."""
    prob, chosen, state = router(s, p, u, below, faults)
    weight = jnp.take_along_axis(prob, chosen[:, None], axis=-1)[:, 0]
    if "renormalised" in faults:
        weight = jnp.ones_like(weight)
    y = jnp.zeros_like(u)
    for k, e in enumerate(experts):
        took = chosen == s["first_held"] + k
        y = y + jnp.where(took, weight, 0.0)[:, None] * gated(
            u, e["gate"], e["up"], e["down"])
    return y, chosen, state


def _layer(s, p, experts, conv, x, below, cos, sin, faults=()):
    eps = s["rms_norm_eps"]
    x = x + cca(s, p, conv, rmsnorm(x, p["norm1/scale"], eps), cos, sin,
                faults)
    y, chosen, state = moe_ffn(s, p, experts,
                               rmsnorm(x, p["norm2/scale"], eps), below,
                               faults)
    return x + y, state, chosen


def _score(h, table, targets, block, tail):
    """``(log p(targets[t+1]) from h_t [T-1], the logits averaged over the
    last ``tail`` positions)`` against the TIED table ``[vocab, hidden]``,
    in blocks of ``block`` positions, so that ``[T, vocab]`` never exists."""
    t = h.shape[0]
    n_blocks = -(-t // block)
    pad = n_blocks * block - t
    tgt = jnp.pad(targets[1:], (0, pad + 1))

    def score(xs):
        h_b, tgt_b = xs
        logits = mm(h_b, table.T)
        return (jnp.take_along_axis(logits, tgt_b[:, None], axis=1)[:, 0]
                - jax.nn.logsumexp(logits, axis=-1))

    logp = jax.lax.map(score, (
        jnp.pad(h, ((0, pad), (0, 0))).reshape(n_blocks, block, -1),
        tgt.reshape(n_blocks, block)))
    return logp.reshape(-1)[:t - 1], jnp.mean(mm(h[-tail:], table.T), axis=0)


def _freeze(s):
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in s.items()))


@jax.jit(static_argnums=(0, 8))
def _jit_layer(frozen, p, experts, conv, x, below, cos, sin, faults):
    with jax.default_matmul_precision("highest"):
        return _layer(dict(frozen), p, experts, conv, x, below, cos, sin,
                      faults)


@jax.jit(static_argnums=(0, 5))
def _jit_score(frozen, norm_w, table, x, targets, block):
    with jax.default_matmul_precision("highest"):
        s = dict(frozen)
        return _score(rmsnorm(x, norm_w, s["rms_norm_eps"]), table, targets,
                      block, s["behaviour_positions"])


def forward(s: dict, member: Member, tokens, head_block: int = 512,
            with_choices: bool = False, faults=()):
    """One member over one sequence ``tokens [T]``: ``(log p(tokens[t+1])
    [T-1], the head's logits averaged over the last ``behaviour_positions``
    positions [vocab])``, and with ``with_choices`` the chosen expert ``[T]``
    of every layer.  One layer's weights exist at a time; the embedding is
    held throughout.  ``faults``: degraded forms, for the rehearsals."""
    frozen, t = _freeze(s), tokens.shape[0]
    cos, sin = rotary(s["rope_theta"], s["rotary_dim"], t)
    table = member.leaf("embed/embedding")
    x = jnp.take(table, tokens, axis=0)
    state = jnp.zeros((t, s["router_hidden_size"]), jnp.float32)
    chosen = []
    for i in range(len(s["layer_types"])):
        base = f"layer_{i:02d}"
        x, state, c = _jit_layer(frozen, member.layer(base),
                                 member.experts_of(base),
                                 member.conv_of(base), x, state, cos, sin,
                                 tuple(faults))
        chosen.append(c)
    score, last = _jit_score(frozen, member.leaf("final_norm/scale"), table,
                             x, tokens, min(head_block, t))
    if with_choices:
        return score, last, chosen
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
