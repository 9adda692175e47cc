"""Plain reference for one ES member of a sparse-expert decoder with latent
attention and one next-token-plus-one (MTP) module: the DeepSeek-V3
architecture (arXiv 2412.19437), whose keys JoyAI-LLM-Flash's ``config.json``
uses.  float32 ``jax.numpy`` at ``highest`` matmul precision, written from
the published description and independent of the system's model code.  No
batching over members, no sharding, no engine, no sort and no grouped
matmul: Python loops over layers and over the held experts, a boolean mask
per expert, ONE full masked softmax per head.  It is given the same share of
the model as the system (which experts are held, which vocabulary rows) and
NOT the system's routes: it routes by itself.

What it follows (``config.json`` keys in quotes; (*) marks what the config
does not spell, listed under ``assumed`` in the configuration file):

    x = E[tokens]
    each layer:   x += attn(rmsnorm_1 x);   x += ffn(rmsnorm_2 x)
    attn(u):  c_q = rmsnorm(u W_qa) ["q_lora_rank"];  q = c_q W_qb
                  -> "num_attention_heads" x [q_nope "qk_nope_head_dim" ;
                                              q_rope "qk_rope_head_dim"]
              [c_kv "kv_lora_rank" ; k_rope] = u W_kva
              [k_nope ; v "v_head_dim"] per head = rmsnorm(c_kv) W_kvb
              q_rope, k_rope rotated by position: "rope_theta", pairs
              (x_2i, x_2i+1) ("rope_interleave"), angle p theta^(-2i/d);
              k_rope is ONE vector read by every head
              score = [q_nope;q_rope].[k_nope;k_rope] / sqrt(nope + rope),
              causal softmax, context = P v  -> W_o
    ffn of the first "first_k_dense_replace" layers: gated SiLU,
              "intermediate_size"
    ffn of the others:  s = sigmoid(u W_r)   over ALL routed experts
              idx = the "num_experts_per_tok" largest of s + b ("noaux_tc":
                    the bias b enters the choice only; ties to the lower
                    index)
              w = "routed_scaling_factor" . s[idx] / (sum s[idx] + 1e-20)
                                                       ("norm_topk_prob")
              y = shared(u) + sum_{k: idx_k held here} w_k expert_{idx_k}(u)
              (experts and the shared one: gated SiLU,
               "moe_intermediate_size")
    h = rmsnorm_final(x);  main_t = log p(tokens[t+1]) from h W_head
    MTP (*), "num_nextn_predict_layers" 1:
              z_t = [rmsnorm_e(E[tokens[t+1]]) ; rmsnorm_h(h_t)] W_eh
              z <- one more expert layer of its own (attn + routed ffn)
              mtp_t = log p(tokens[t+2]) from rmsnorm_mtp(z_t) W_head
    score_t = main_t + lambda . mtp_t (*), mtp_t = 0 where t+2 is past the
              end; behaviour: the MAIN head's logits averaged over the last
              "behaviour_positions" positions (*)

A member's weights are ``theta + sigma * sign * E`` with ``E = A B^T /
sqrt(r)`` MATERIALISED a leaf at a time, and for a stacked expert leaf
``[experts, m, n]`` an expert at a time from that expert's own factor pair;
leaves where factoring would not save (norm weights, the selection bias)
carry dense noise.  Table, offsets and keys are the system's
(``parallel/sharded.py``): generation ``g`` of a state with key ``K`` uses
``base = fold_in(K, g)``, the pair offsets from ``fold_in(base, 0)`` and the
rollout keys from ``split(fold_in(base, 1), pairs)``; members ``2k`` and
``2k+1`` share pair ``k``'s offset and key with signs ``+1, -1``.  A pair's
key picks its sequence: ``randint(key, (), 0, corpus_sequences)`` into the
corpus ``randint(PRNGKey(corpus_seed), (corpus_sequences, seq_len), 0,
vocab)``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import costs, costs_moe

HIGHEST = jax.lax.Precision.HIGHEST
DEFAULTS = dict(
    num_attention_heads=4, q_lora_rank=16, kv_lora_rank=8,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    n_routed_experts=8, expert_group_size=1, expert_group_rank=0,
    num_experts_per_tok=2, routed_scaling_factor=1.0, mtp_lambda=0.1,
    behaviour_positions=512, rope_theta=10000.0, rms_norm_eps=1e-6)


# ------------------------------------------------------------------ sizes

def sizes(config: dict) -> dict:
    """The model's and the corpus's sizes AS BUILT: the keyword arguments
    the configuration file hands the policy and the environment."""
    kwargs = config["build"]["kwargs"]
    out = {**DEFAULTS, **kwargs["policy_kwargs"]}
    out.update(kwargs["agent_kwargs"]["env"]["kwargs"])
    out["low_rank"] = kwargs["low_rank"]
    out["experts_total"] = out["n_routed_experts"] * out["expert_group_size"]
    out["first_held"] = out["n_routed_experts"] * out["expert_group_rank"]
    return out


def _layer_layout(s: dict, base: str, kind: str) -> list:
    h, nh = s["hidden_size"], s["num_attention_heads"]
    dn, dr, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    ql, kl = s["q_lora_rank"], s["kv_lora_rank"]
    out = [(f"{base}/attn/kv_a", (h, kl + dr)),
           (f"{base}/attn/kv_b", (kl, nh * (dn + dv))),
           (f"{base}/attn/kv_norm/scale", (kl,)),
           (f"{base}/attn/o", (nh * dv, h)),
           (f"{base}/attn/q_a", (h, ql)),
           (f"{base}/attn/q_b", (ql, nh * (dn + dr))),
           (f"{base}/attn/q_norm/scale", (ql,))]
    if kind == "dense":
        ff = s["intermediate_size"]
        out += [(f"{base}/mlp/down", (ff, h)), (f"{base}/mlp/gate", (h, ff)),
                (f"{base}/mlp/up", (h, ff))]
    else:
        e, w = s["n_routed_experts"], s["moe_intermediate_size"]
        out += [(f"{base}/moe/experts/down", (e, w, h)),
                (f"{base}/moe/experts/gate", (e, h, w)),
                (f"{base}/moe/experts/up", (e, h, w)),
                (f"{base}/moe/router", (h, s["experts_total"])),
                (f"{base}/moe/router_bias", (s["experts_total"],)),
                (f"{base}/moe/shared/down", (w, h)),
                (f"{base}/moe/shared/gate", (h, w)),
                (f"{base}/moe/shared/up", (h, w))]
    return out + [(f"{base}/norm1/scale", (h,)), (f"{base}/norm2/scale", (h,))]


def system_layout(s: dict) -> list[tuple[str, tuple]]:
    """``[(path, shape), ...]`` of the system's flat parameter vector: its
    leaves in sorted-key order."""
    h, v = s["hidden_size"], s["vocab_size"]
    out = [("embed/embedding", (v, h)), ("final_norm/scale", (h,)),
           ("head/kernel", (h, v))]
    for i, kind in enumerate(s["layer_types"]):
        out += _layer_layout(s, f"layer_{i:02d}", kind)
    out += [("mtp/eh", (2 * h, h)), ("mtp/embed_norm/scale", (h,)),
            ("mtp/final_norm/scale", (h,)), ("mtp/hidden_norm/scale", (h,))]
    return out + _layer_layout(s, "mtp/layer", "moe")


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
    """``(matmuls outside the experts and the routers, the routers', the
    two heads')`` a token passes, as ``(m, n)``: latent attention's five
    projections in every layer and the MTP layer, the dense FFN, the shared
    experts, the MTP's ``eh``."""
    h, nh = s["hidden_size"], s["num_attention_heads"]
    dn, dr, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    ql, kl, w = s["q_lora_rank"], s["kv_lora_rank"], s["moe_intermediate_size"]
    mla = [(h, ql), (ql, nh * (dn + dr)), (h, kl + dr),
           (kl, nh * (dn + dv)), (nh * dv, h)]
    kinds = list(s["layer_types"]) + ["moe"]        # the MTP layer
    dense, routers = [(2 * h, h)], []
    for kind in kinds:
        dense += mla
        if kind == "dense":
            dense += [(h, 2 * s["intermediate_size"]),
                      (s["intermediate_size"], h)]
        else:
            dense += [(h, 2 * w), (w, h)]
            routers.append((h, s["experts_total"]))
    return dense, routers, [(h, s["vocab_size"])] * 2


def describe(config: dict) -> dict:
    """What the harness needs to know: the length of the flat parameter
    vector, and 2 x the matmul weights one token passes
    (``costs.matmul_flops``; attention's own scores left out), split into
    what runs outside the experts (``dense``: under ``es.dense``), the two
    heads', and, in the total alone, the routers' and the held experts' at
    the pairs a uniform router sends them (``costs_moe.py``)."""
    s = sizes(config)
    dense, routers, heads = matmul_shapes(s)
    expert_layers = len(routers)
    pairs = costs_moe.expected_pairs_per_token(
        s["num_experts_per_tok"], s["n_routed_experts"], s["experts_total"])
    expert_flops = int(expert_layers * pairs * costs_moe.expert_flops_per_pair(
        s["hidden_size"], s["moe_intermediate_size"]))
    return {"param_dim": param_offsets(s)["__dim__"][0],
            "noise_dim": noise_layout(s)["__dim__"],
            "flops_per_member_step": (
                costs.matmul_flops(dense + routers + heads) + expert_flops),
            "dense_flops_per_member_step": costs.matmul_flops(dense),
            "head_flops_per_member_step": costs.matmul_flops(heads),
            "expert_flops_per_member_step": expert_flops,
            "expert_layers": expert_layers,
            "expected_pairs_per_token_and_layer": pairs}


# ------------------------------------------------------------------- init

def init_theta(key, config):
    """Seeded initial weights in the system's flat layout, made on the device
    in one jitted call (``assumed`` in the configuration file): matrices,
    embedding, head, routers and experts normal with standard deviation
    0.02, norm weights one, selection biases zero."""
    return _init_theta(key, tuple(system_layout(sizes(config))))


@jax.jit(static_argnums=(1,))
def _init_theta(key, layout):
    parts = []
    for i, (path, shape) in enumerate(layout):
        name = path.rsplit("/", 1)[1]
        if name == "scale":
            v = jnp.ones(shape, jnp.float32)
        elif name == "router_bias":
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
        names = ["attn/kv_a", "attn/kv_b", "attn/kv_norm/scale", "attn/o",
                 "attn/q_a", "attn/q_b", "attn/q_norm/scale", "norm1/scale",
                 "norm2/scale"]
        names += (["mlp/down", "mlp/gate", "mlp/up"] if kind == "dense" else
                  ["moe/router", "moe/router_bias", "moe/shared/down",
                   "moe/shared/gate", "moe/shared/up"])
        return {n: self.leaf(f"{base}/{n}") for n in names}

    def experts_of(self, base):
        """``[{gate, up, down}, ...]`` of the held experts of a layer."""
        return [{n: self.expert(f"{base}/moe/experts/{n}", k)
                 for n in ("gate", "up", "down")}
                for k in range(self.s["n_routed_experts"])]


# ---------------------------------------------------------------- forward

def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def gated(u, gate, up, down):
    return mm(jax.nn.silu(mm(u, gate)) * mm(u, up), down)


def rotary(s: dict, length: int):
    """``(cos, sin) [T, qk_rope_head_dim / 2]``: position ``p`` turns pair
    ``i`` by ``p theta^(-2i/d)``."""
    d = s["qk_rope_head_dim"]
    inv_freq = s["rope_theta"] ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angle = np.arange(length, dtype=np.float64)[:, None] * inv_freq[None, :]
    return (jnp.asarray(np.cos(angle), jnp.float32),
            jnp.asarray(np.sin(angle), jnp.float32))


def rotate_pairs(x, cos, sin):
    """The pairs ``(x_2i, x_2i+1)`` of ``x [T, ..., d]`` turned by the
    position's angles; ``cos``, ``sin`` ``[T, d/2]``."""
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[-1],)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def attention(s, p, u, cos, sin):
    t = u.shape[0]
    nh, dn, dr, dv = (s["num_attention_heads"], s["qk_nope_head_dim"],
                      s["qk_rope_head_dim"], s["v_head_dim"])
    kl, eps = s["kv_lora_rank"], s["rms_norm_eps"]
    q = mm(rmsnorm(mm(u, p["attn/q_a"]), p["attn/q_norm/scale"], eps),
           p["attn/q_b"]).reshape(t, nh, dn + dr)
    kv_a = mm(u, p["attn/kv_a"])
    kv = mm(rmsnorm(kv_a[:, :kl], p["attn/kv_norm/scale"], eps),
            p["attn/kv_b"]).reshape(t, nh, dn + dv)
    q_rope = rotate_pairs(q[..., dn:], cos, sin)
    k_rope = rotate_pairs(kv_a[:, kl:], cos, sin)            # [t, dr], shared
    mask = jnp.tril(jnp.ones((t, t), bool))

    def one_head(xs):
        q_n, q_r, k_n, v_h = xs
        scores = (jnp.matmul(q_n, k_n.T, precision=HIGHEST)
                  + jnp.matmul(q_r, k_rope.T, precision=HIGHEST))
        scores = jnp.where(mask, scores / math.sqrt(dn + dr), -jnp.inf)
        return jnp.matmul(jax.nn.softmax(scores, axis=-1), v_h,
                          precision=HIGHEST)

    ctx = jax.lax.map(one_head, (
        q[..., :dn].transpose(1, 0, 2), q_rope.transpose(1, 0, 2),
        kv[..., :dn].transpose(1, 0, 2), kv[..., dn:].transpose(1, 0, 2)))
    return mm(ctx.transpose(1, 0, 2).reshape(t, nh * dv), p["attn/o"])


def routes(s, p, u):
    """``(experts [T, k], weights [T, k])``: the router over ALL experts."""
    k = s["num_experts_per_tok"]
    score = jax.nn.sigmoid(mm(u, p["moe/router"]))
    chosen = jnp.argsort(-(score + p["moe/router_bias"]), axis=-1,
                         stable=True)[:, :k]
    w = jnp.take_along_axis(score, chosen, axis=-1)
    return chosen, s["routed_scaling_factor"] * w / (
        w.sum(axis=-1, keepdims=True) + 1e-20)


def moe_ffn(s, p, experts, u):
    """``(shared(u) + the held experts' part, the routes)``: a Python loop
    over the held experts, each applied to every token and kept by a boolean
    mask where the token chose it."""
    chosen, w = routes(s, p, u)
    y = gated(u, p["moe/shared/gate"], p["moe/shared/up"],
              p["moe/shared/down"])
    for k, e in enumerate(experts):
        took = chosen == s["first_held"] + k                    # [T, k]
        weight = jnp.sum(jnp.where(took, w, 0.0), axis=-1)      # [T]
        y = y + weight[:, None] * gated(u, e["gate"], e["up"], e["down"])
    return y, chosen


def _layer(s, kind, p, experts, x, cos, sin):
    eps = s["rms_norm_eps"]
    x = x + attention(s, p, rmsnorm(x, p["norm1/scale"], eps), cos, sin)
    u = rmsnorm(x, p["norm2/scale"], eps)
    if kind == "dense":
        return x + gated(u, p["mlp/gate"], p["mlp/up"], p["mlp/down"]), None
    y, chosen = moe_ffn(s, p, experts, u)
    return x + y, chosen


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


@jax.jit(static_argnums=(0, 1))
def _jit_layer(frozen, kind, p, experts, x, cos, sin):
    with jax.default_matmul_precision("highest"):
        return _layer(dict(frozen), kind, p, experts, x, cos, sin)


@jax.jit(static_argnums=(0, 5))
def _jit_score(frozen, norm_w, head, x, targets, block):
    with jax.default_matmul_precision("highest"):
        s = dict(frozen)
        return _score(rmsnorm(x, norm_w, s["rms_norm_eps"]), head, targets,
                      block, s["behaviour_positions"])


@jax.jit(static_argnums=(0,))
def _jit_mtp_input(frozen, embed_norm, hidden_norm, eh, final_norm, rows, x):
    with jax.default_matmul_precision("highest"):
        eps = dict(frozen)["rms_norm_eps"]
        h = rmsnorm(x, final_norm, eps)
        return mm(jnp.concatenate([rmsnorm(rows, embed_norm, eps),
                                   rmsnorm(h, hidden_norm, eps)], axis=-1),
                  eh)


def heads(s: dict, member: Member, tokens, head_block: int = 512,
          with_routes: bool = False):
    """One member over one sequence ``tokens [T]``: ``(main_t [T-1], mtp_t
    [T-1] with 0 where t+2 is past the end, the main head's logits averaged
    over the last ``behaviour_positions`` positions)``,
    and with ``with_routes`` the chosen experts ``[T, k]`` of every expert
    layer, the MTP's last.  One layer's weights exist at a time; embedding
    and head are held throughout."""
    frozen, t = _freeze(s), tokens.shape[0]
    block = min(head_block, t)
    cos, sin = rotary(s, t)
    table, head = member.leaf("embed/embedding"), member.leaf("head/kernel")
    x = jnp.take(table, tokens, axis=0)
    chosen = []
    for i, kind in enumerate(s["layer_types"]):
        base = f"layer_{i:02d}"
        x, c = _jit_layer(
            frozen, kind, member.layer(base, kind),
            member.experts_of(base) if kind == "moe" else None, x, cos, sin)
        chosen.append(c)
    final_norm = member.leaf("final_norm/scale")
    main, last = _jit_score(frozen, final_norm, head, x, tokens, block)
    # position t beside token t+1; the last position has none: token 0,
    # which no earlier position sees
    shifted = jnp.concatenate([tokens[1:], jnp.zeros((1,), tokens.dtype)])
    z = _jit_mtp_input(
        frozen, member.leaf("mtp/embed_norm/scale"),
        member.leaf("mtp/hidden_norm/scale"), member.leaf("mtp/eh"),
        final_norm, jnp.take(table, shifted, axis=0), x)
    z, c = _jit_layer(frozen, "moe", member.layer("mtp/layer", "moe"),
                      member.experts_of("mtp/layer"), z, cos, sin)
    chosen.append(c)
    mtp, _ = _jit_score(frozen, member.leaf("mtp/final_norm/scale"), head, z,
                        shifted, block)
    mtp = jnp.where(jnp.arange(t - 1) < t - 2, mtp, 0.0)
    if with_routes:
        return main, mtp, last, [c for c in chosen if c is not None]
    return main, mtp, last


def forward(s: dict, member: Member, tokens, head_block: int = 512):
    """The policy output: ``(main_t + lambda mtp_t [T-1], the main head's
    logits averaged over the last ``behaviour_positions`` positions
    [vocab])``."""
    main, mtp, last = heads(s, member, tokens, head_block)
    return main + s["mtp_lambda"] * mtp, last


def score_members(s, theta, table, offsets, signs, keys, sigma, bc_dim):
    """``(fitness (k,), behaviour (k, bc_dim))`` of ``k`` members, one after
    the other: fitness is the mean score over the member's sequence,
    behaviour the main head's averaged logits at the probe ids.
    ``offsets``, ``signs`` and ``keys`` are per member."""
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
