"""A tiny GatedWindowMoELM and its plain reference, shared by the tests of the
path whose two kinds of attention layer differ in their head count.  The
reference is the benchmark's own file
(benchmark/reference/gated_window_moe_lm.py), loaded by path."""

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "laguna-xs.2-33b-a3b-ep16.json")
FULL, SLIDING = "full_attention", "sliding_attention"

# the two kinds' rope groups in the published form: the full layers turn HALF
# of a head (4 of 8: two frequency pairs) under YaRN, whose ramp runs from
# pair 0 to pair 1 at these numbers (low 0, high 1), the sliding layers the
# whole head under plain rope
ROPE = {
    FULL: {"rope_theta": 500000.0, "rope_type": "yarn", "factor": 8.0,
           "original_max_position_embeddings": 8, "beta_slow": 1,
           "beta_fast": 4, "attention_factor": 1.2079441541679836,
           "partial_rotary_factor": 0.5},
    SLIDING: {"rope_type": "default", "rope_theta": 10000.0,
              "partial_rotary_factor": 1},
    "original_max_position_embeddings": 8}

# share 1 of 4: experts 4..7 of 16 are held, 3 a token.  Four query heads in
# the full layers and six in the sliding ones over two key heads of 8 (groups
# of 2 and of 3); the dense layer first, then one period of the published
# pattern; a band of 6 bites from the seventh position on over 21 positions.
# The per-layer lists are LONGER than the stack, as the published ones are
TINY = dict(
    layer_types=(FULL, SLIDING, SLIDING, FULL),
    mlp_layer_types=("dense", "sparse", "sparse", "sparse", "sparse"),
    num_attention_heads_per_layer=(4, 6, 6, 4, 6),
    rope_parameters=ROPE, vocab_size=64, hidden_size=32,
    intermediate_size=48, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, sliding_window=6,
    num_key_value_heads=2, head_dim=8, num_experts=4, expert_group_size=4,
    expert_group_rank=1, num_experts_per_tok=3,
    moe_routed_scaling_factor=2.5, behaviour_positions=8,
    rms_norm_eps=1e-6, attention_block=8, head_block=8)
ENV = dict(vocab_size=64, seq_len=21, corpus_sequences=4, seed=0)


def reference():
    if ROOT not in sys.path:        # the reference imports benchmark.costs
        sys.path.insert(0, ROOT)
    spec = importlib.util.spec_from_file_location(
        "reference_gated_window_moe_lm",
        os.path.join(ROOT, "benchmark", "reference",
                     "gated_window_moe_lm.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config(rank=1, policy=None, **env):
    """What the reference reads its sizes from, for the tiny model."""
    kwargs = {**TINY, **(policy or {})}
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        kwargs[key] = list(kwargs[key])
    return {"build": {"kwargs": {
        "policy_kwargs": kwargs,
        "agent_kwargs": {"env": {"kwargs": {**ENV, **env}}},
        "low_rank": rank}}}


def published():
    with open(CONFIG_FILE) as f:
        return json.load(f)
