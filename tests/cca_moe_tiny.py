"""A tiny CCAMoELM and its plain reference, shared by the tests of the
compressed-latent path.  The reference is the benchmark's own file
(benchmark/reference/cca_moe_lm.py), loaded by path."""

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs", "zaya1-8b-ep2.json")

# share 1 of 2: experts 2..3 of 4 are held, ONE a token.  Eight query heads
# over two key heads of 8, so a group is four query heads and the mean
# crosses it; half of a head (4 channels, 2 frequency pairs) is rotated; two
# taps in each convolution, so position t reads t - 1 twice over (t - 2 in
# all) and the value shift once more; a router 16 wide whose state the
# second and third layer read from the one below
TINY = dict(
    layer_types=("hybrid", "hybrid", "hybrid"), vocab_size=64, hidden_size=32,
    moe_intermediate_size=16, num_attention_heads=8, num_key_value_heads=2,
    head_dim=8, cca_time0=2, cca_time1=2, partial_rotary_factor=0.5,
    router_hidden_size=16, num_experts=2, expert_group_size=2,
    expert_group_rank=1, num_experts_per_tok=1, behaviour_positions=8,
    rope_theta=10000.0, rms_norm_eps=1e-5, attention_block=8, head_block=8)
ENV = dict(vocab_size=64, seq_len=21, corpus_sequences=4, seed=0)


def reference():
    if ROOT not in sys.path:        # the reference imports benchmark.costs
        sys.path.insert(0, ROOT)
    spec = importlib.util.spec_from_file_location(
        "reference_cca_moe_lm",
        os.path.join(ROOT, "benchmark", "reference", "cca_moe_lm.py"))
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
