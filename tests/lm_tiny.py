"""A tiny HybridLM and its plain reference, shared by the tests of the
sequence-model path.  The reference is the benchmark's own file
(benchmark/reference/hybrid_lm.py), loaded by path."""

import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "granite-4.0-h-micro-1period.json")

TINY = dict(
    layer_types=("mamba", "attention", "mamba"), vocab_size=64,
    hidden_size=32, mamba_n_heads=4, mamba_d_head=8, mamba_d_state=16,
    mamba_d_conv=4, mamba_n_groups=1, mamba_chunk_size=8,
    num_attention_heads=4, num_key_value_heads=2, shared_intermediate_size=64,
    attention_multiplier=0.125, embedding_multiplier=12.0,
    residual_multiplier=0.22, logits_scaling=8.0, rms_norm_eps=1e-5,
    attention_block=8, head_block=8)
ENV = dict(vocab_size=64, seq_len=21, corpus_sequences=4, seed=0)


def reference():
    import sys

    if ROOT not in sys.path:        # the reference imports benchmark.costs
        sys.path.insert(0, ROOT)
    spec = importlib.util.spec_from_file_location(
        "reference_hybrid_lm",
        os.path.join(ROOT, "benchmark", "reference", "hybrid_lm.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config(rank=1, **env):
    """What the reference reads its sizes from, for the tiny model."""
    return {"build": {"kwargs": {
        "policy_kwargs": {**TINY, "layer_types": list(TINY["layer_types"])},
        "agent_kwargs": {"env": {"kwargs": {**ENV, **env}}},
        "low_rank": rank}}}


def published():
    with open(CONFIG_FILE) as f:
        return json.load(f)
