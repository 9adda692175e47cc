"""A tiny MoELM and its plain reference, shared by the tests of the
sparse-expert path.  The reference is the benchmark's own file
(benchmark/reference/moe_lm.py), loaded by path."""

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "joyai-llm-flash-5layers.json")

# share 1 of 4: experts 4..7 of 16 are held, 3 a token, so a token has
# none, one or several of its experts here.  Heads are 12 wide where they
# are scored (8 + 4 rotated) and 6 where they are summed
TINY = dict(
    layer_types=("dense", "moe", "moe"), vocab_size=64, hidden_size=32,
    intermediate_size=48, moe_intermediate_size=16, num_attention_heads=4,
    q_lora_rank=16, kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=6, n_routed_experts=4, expert_group_size=4,
    expert_group_rank=1, num_experts_per_tok=3, routed_scaling_factor=2.5,
    mtp_lambda=0.1, rope_theta=10000.0, rms_norm_eps=1e-6,
    attention_block=8, head_block=8)
ENV = dict(vocab_size=64, seq_len=21, corpus_sequences=4, seed=0)


def reference():
    if ROOT not in sys.path:        # the reference imports benchmark.costs
        sys.path.insert(0, ROOT)
    spec = importlib.util.spec_from_file_location(
        "reference_moe_lm",
        os.path.join(ROOT, "benchmark", "reference", "moe_lm.py"))
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
