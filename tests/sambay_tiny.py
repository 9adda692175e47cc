"""A tiny SambaYLM and its plain reference, shared by the tests of the
SambaY path.  The reference is the benchmark's own file
(benchmark/reference/sambay_lm.py), loaded by path."""

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "phi-4-mini-flash-6layers.json")

# the cut the benchmark makes, of a published depth of 8: one period of each
# decoder and the two boundary layers.  8 query over 4 key/value heads of 4:
# four diff-heads over two key/value pairs.  A window of 5 against attention
# blocks of 8 and sequences of 21: under, inside and across a block
TINY = dict(
    vocab_size=64, hidden_size=32, intermediate_size=48,
    num_attention_heads=8, num_key_value_heads=4, published_layers=8,
    layer_indices=(0, 1, 4, 5, 6, 7), sliding_window=5, mamba_d_state=4,
    mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=3, layer_norm_eps=1e-5,
    scan_chunk=4, attention_block=8, head_block=8)
ENV = dict(vocab_size=64, seq_len=21, corpus_sequences=4, seed=0)


def reference():
    if ROOT not in sys.path:        # the reference imports benchmark.costs
        sys.path.insert(0, ROOT)
    spec = importlib.util.spec_from_file_location(
        "reference_sambay_lm",
        os.path.join(ROOT, "benchmark", "reference", "sambay_lm.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config(rank=1, policy=None, **env):
    """What the reference reads its sizes from, for the tiny model."""
    kwargs = {**TINY, **(policy or {})}
    kwargs["layer_indices"] = list(kwargs["layer_indices"])
    return {"build": {"kwargs": {
        "policy_kwargs": kwargs,
        "agent_kwargs": {"env": {"kwargs": {**ENV, **env}}},
        "low_rank": rank}}}


def published():
    with open(CONFIG_FILE) as f:
        return json.load(f)
