"""The multiply-adds ONE token needs per PART of a sequence model, computed
from the configuration file's widths.  A part is a parameter leaf's key, the
name the program gives the operations that multiply that leaf
(``estorch_tpu/obs/trace.py``: ``part``; ``layers/part.py`` reads them from a
trace).  The benchmark's own arithmetic, kept with it (as ``costs.py`` and
``costs_moe.py``), so that a later PR cannot change a utilisation by changing
a cost model; written from the models' equations, not from the reference
modules, whose ``dense_flops_per_member_step + head_flops_per_member_step``
the parts outside routed experts, routers and the exit gate must add up to
(``counted_by_reference``; tier-1 holds all three configurations to it).

Attention's own work is the EXACT causal count: ``2 · (score width + value
width)`` for every visible (query, key) pair of every head, ``T·(T+1)/2``
pairs a sequence, so that the number reads the same work whether a kernel or
the XLA form runs, whatever tiles either multiplies.
"""

from __future__ import annotations

from benchmark import costs_moe

# the groups ``layers/part.py`` reports a utilisation for: the last word
# of a part's path
FFN = ("gate", "up", "down")
MIXER = ("q", "k", "v", "o", "in_z", "in_x", "in_bc", "in_dt", "out_proj",
         "q_a", "q_b", "kv_a", "kv_b")
HEAD = ("head", "embed")
# the routed experts' leaves sit under this path element
EXPERTS = "experts"
# matmuls the reference modules' counts leave out
NOT_IN_REFERENCE = ("router", "exit_gate")


def sizes(config: dict) -> dict:
    """The model's widths AS BUILT and the sequence length: the keyword
    arguments the configuration file hands the policy and the environment."""
    kwargs = config["build"]["kwargs"]
    return {**kwargs["policy_kwargs"],
            "seq_len": kwargs["agent_kwargs"]["env"]["kwargs"]["seq_len"]}


def _add(out: dict, times: int, shapes: dict) -> None:
    for name, (m, n) in shapes.items():
        flops, bytes_ = out.get(name, (0, 0))
        # the input row once in bfloat16, the output row once in float32
        out[name] = (flops + times * 2 * m * n,
                     bytes_ + times * (2 * m + 4 * n))


def _hybrid(s: dict) -> tuple[dict, float]:
    h, ff = s["hidden_size"], s["shared_intermediate_size"]
    d_inner = s["mamba_n_heads"] * s["mamba_d_head"]
    nq, nkv = s["num_attention_heads"], s["num_key_value_heads"]
    hd = s.get("head_dim") or h // nq
    mamba = sum(kind == "mamba" for kind in s["layer_types"])
    attention = len(s["layer_types"]) - mamba
    out: dict = {}
    _add(out, mamba, {
        "in_z": (h, d_inner), "in_x": (h, d_inner),
        "in_bc": (h, 2 * s["mamba_n_groups"] * s["mamba_d_state"]),
        "in_dt": (h, s["mamba_n_heads"]), "out_proj": (d_inner, h)})
    _add(out, attention, {"q": (h, nq * hd), "k": (h, nkv * hd),
                          "v": (h, nkv * hd), "o": (nq * hd, h)})
    _add(out, mamba + attention,
         {"gate": (h, ff), "up": (h, ff), "down": (ff, h)})
    _add(out, 1, {"embed": (h, s["vocab_size"])})       # the tied head
    return out, attention * nq * 2 * (hd + hd)


def _looped(s: dict) -> tuple[dict, float]:
    h, ff = s["hidden_size"], s["intermediate_size"]
    nq, nkv = s["num_attention_heads"], s["num_key_value_heads"]
    hd = s.get("head_dim") or h // nq
    passes = s.get("total_ut_steps", 4)
    applications = len(s["layer_types"]) * passes
    out: dict = {}
    _add(out, applications, {
        "q": (h, nq * hd), "k": (h, nkv * hd), "v": (h, nkv * hd),
        "o": (nq * hd, h), "gate": (h, ff), "up": (h, ff), "down": (ff, h)})
    _add(out, passes, {"head": (h, s["vocab_size"]), "exit_gate": (h, 1)})
    return out, applications * nq * 2 * (hd + hd)


def _moe(s: dict) -> tuple[dict, float]:
    h, nh = s["hidden_size"], s["num_attention_heads"]
    dn, dr, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    ql, kl, w = s["q_lora_rank"], s["kv_lora_rank"], s["moe_intermediate_size"]
    kinds = list(s["layer_types"]) + ["moe"]            # the MTP's layer
    expert_layers = sum(kind == "moe" for kind in kinds)
    total = s["n_routed_experts"] * s.get("expert_group_size", 1)
    out: dict = {}
    _add(out, len(kinds), {
        "q_a": (h, ql), "q_b": (ql, nh * (dn + dr)), "kv_a": (h, kl + dr),
        "kv_b": (kl, nh * (dn + dv)), "o": (nh * dv, h)})
    ff = s["intermediate_size"]
    _add(out, len(kinds) - expert_layers,
         {"gate": (h, ff), "up": (h, ff), "down": (ff, h)})
    _add(out, expert_layers, {
        "shared.gate": (h, w), "shared.up": (h, w), "shared.down": (w, h),
        "router": (h, total)})
    _add(out, 1, {"eh": (2 * h, h)})
    _add(out, 2, {"head": (h, s["vocab_size"])})        # main and MTP
    # the held experts at the pairs a uniform router sends them: an
    # expectation (costs_moe.py), in a third each of the expert's three
    pairs = expert_layers * costs_moe.expected_pairs_per_token(
        s["num_experts_per_tok"], s["n_routed_experts"], total)
    for name, (m, n) in {"gate": (h, w), "up": (h, w), "down": (w, h)}.items():
        out[f"{EXPERTS}.{name}"] = (pairs * 2 * m * n,
                                    pairs * (2 * m + 4 * n))
    return out, len(kinds) * nh * 2 * (dn + dr + dv)


MODELS = {"hybrid_lm": _hybrid, "looped_lm": _looped, "moe_lm": _moe}


def parts(config: dict) -> dict:
    """``{part: (FLOPs, least bytes)}`` of ONE token through every matmul
    that multiplies the leaf the part names, summed over the layers (and
    passes) that have one: FLOPs ``2 · m · n``; bytes the input row once in
    bfloat16 and the output row once in float32, ``2·m + 4·n``: what the
    projection moves through HBM at the least (its weights, read once a
    call, left out).  A path (``shared.gate``, ``experts.gate``) where one
    key serves two places."""
    return MODELS[config["reference"]](sizes(config))[0]


def attention_flops_per_token(config: dict) -> float:
    """The exact causal count, averaged over the positions of a sequence of
    the configuration's length: position ``t`` sees ``t + 1`` keys, so a
    token sees ``(T + 1) / 2`` on average, each ``2 · (score width + value
    width)`` a head and attention layer (or layer-application)."""
    s = sizes(config)
    return MODELS[config["reference"]](s)[1] * (s["seq_len"] + 1) / 2


def group_of(part: str) -> str | None:
    """``"ffn"``, ``"mixer"`` or ``"head"`` for a part's path; ``None`` for
    a routed expert's leaf and for a part of no group (``eh``, ``router``,
    ``exit_gate``, the embedding's lookup is told apart by its stage)."""
    words = part.split(".")
    if EXPERTS in words:
        return None
    last = words[-1]
    return ("ffn" if last in FFN else "mixer" if last in MIXER
            else "head" if last in HEAD else None)


def counted_by_reference(config: dict) -> int:
    """The parts' FLOPs outside routed experts, routers and the exit gate:
    what the reference module's ``dense_flops_per_member_step +
    head_flops_per_member_step`` count, by another road."""
    return sum(flops for name, (flops, _) in parts(config).items()
               if EXPERTS not in name.split(".")
               and name not in NOT_IN_REFERENCE)
