"""A tiny LoopedLM and its plain reference, shared by the tests of the
looped-model path.  The reference is the benchmark's own file
(benchmark/reference/looped_lm.py), loaded by path."""

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "ouro-2.6b-8layers.json")

# grouped heads (4 query, 2 key/value) although the published model has as
# many key/value heads as query heads: the shared attention serves both
TINY = dict(
    layer_types=("full_attention", "full_attention"), vocab_size=64,
    hidden_size=32, intermediate_size=48, num_attention_heads=4,
    num_key_value_heads=2, head_dim=8, rope_theta=10000.0,
    rms_norm_eps=1e-6, total_ut_steps=4, attention_block=8, head_block=8)
ENV = dict(vocab_size=64, seq_len=21, corpus_sequences=4, seed=0)


def reference():
    if ROOT not in sys.path:        # the reference imports benchmark.costs
        sys.path.insert(0, ROOT)
    spec = importlib.util.spec_from_file_location(
        "reference_looped_lm",
        os.path.join(ROOT, "benchmark", "reference", "looped_lm.py"))
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
