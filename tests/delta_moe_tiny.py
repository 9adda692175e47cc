"""A tiny DeltaMoELM and its plain reference, shared by the tests of the
gated-delta-rule path.  The reference is the benchmark's own file
(benchmark/reference/delta_moe_lm.py), loaded by path."""

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "qwen3-next-80b-a3b-ep16.json")

# share 1 of 4: experts 4..7 of 16 are held, 3 a token.  Linear layers of 2
# key and 4 value heads of 8 (value heads 2j, 2j+1 read key head j) in
# chunks of 8 positions, so that 21 positions are two whole chunks and a
# short one; a full layer of 4 query heads over 2 key heads of 16, of which
# 4 turn; one period of the published pattern (three linear, one full)
TINY = dict(
    layer_types=("linear", "linear", "linear", "full"), vocab_size=64,
    hidden_size=32, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=8, linear_value_head_dim=8,
    linear_conv_kernel_dim=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, partial_rotary_factor=0.25, num_experts=4,
    expert_group_size=4, expert_group_rank=1, num_experts_per_tok=3,
    behaviour_positions=8, rope_theta=10000.0, rms_norm_eps=1e-6,
    attention_block=8, head_block=8, delta_chunk=8)
ENV = dict(vocab_size=64, seq_len=21, corpus_sequences=4, seed=0)


def reference():
    if ROOT not in sys.path:        # the reference imports benchmark.costs
        sys.path.insert(0, ROOT)
    spec = importlib.util.spec_from_file_location(
        "reference_delta_moe_lm",
        os.path.join(ROOT, "benchmark", "reference", "delta_moe_lm.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config(rank=1, policy=None, **env):
    """What the reference reads its sizes from, for the tiny model."""
    kwargs = {**TINY, **(policy or {})}
    kwargs["layer_types"] = list(kwargs["layer_types"])
    return {"build": {"kwargs": {
        "policy_kwargs": kwargs,
        "agent_kwargs": {"env": {"kwargs": {**ENV, **env}}},
        "low_rank": rank}}}


def published():
    with open(CONFIG_FILE) as f:
        return json.load(f)
