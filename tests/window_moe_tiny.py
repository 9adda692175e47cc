"""A tiny WindowMoELM and its plain reference, shared by the tests of the
router-ahead-of-attention path.  The reference is the benchmark's own file
(benchmark/reference/window_moe_lm.py), loaded by path."""

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker-21b-a3b-ep4.json")

# share 1 of 4: experts 4..7 of 16 are held, 3 a token.  Six query heads
# over two key heads of 8 (groups of 3); one period of the published
# pattern, a global layer and then window layers, whose band of 6 bites from
# the seventh position on over 21 positions
TINY = dict(
    layer_types=("global", "window", "window"), vocab_size=64, hidden_size=32,
    moe_ffn_hidden_size=16, sliding_window_size=6, num_attention_heads=6,
    num_key_value_heads=2, head_dim=8, moe_num_primary_experts=4,
    expert_group_size=4, expert_group_rank=1,
    moe_num_active_primary_experts=3, behaviour_positions=8,
    rope_theta=10000.0, rms_norm_eps=1e-6, attention_block=8, head_block=8)
ENV = dict(vocab_size=64, seq_len=21, corpus_sequences=4, seed=0)


def reference():
    if ROOT not in sys.path:        # the reference imports benchmark.costs
        sys.path.insert(0, ROOT)
    spec = importlib.util.spec_from_file_location(
        "reference_window_moe_lm",
        os.path.join(ROOT, "benchmark", "reference", "window_moe_lm.py"))
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
