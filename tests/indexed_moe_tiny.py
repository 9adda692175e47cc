"""A tiny IndexedMoELM and its plain reference, shared by the tests of the
learned-selection path.  The reference is the benchmark's own file
(benchmark/reference/indexed_moe_lm.py), loaded by path."""

import importlib.util
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG_FILE = os.path.join(ROOT, "benchmark", "configs",
                           "keye-vl-2.0-30b-a3b-ep8.json")

# share 1 of 4: experts 4..7 of 16 are held, 3 a token.  Four query heads
# over two key heads of 8; an indexer of 2 heads of 8 that keeps 6 keys a
# query, so that over 21 positions the selection bites from the seventh on;
# the 4 frequency pairs of a head turn by three streams (2 + 1 + 1)
TINY = dict(
    layer_types=("moe", "moe"), vocab_size=64, hidden_size=32,
    moe_intermediate_size=16, num_attention_heads=4, num_key_value_heads=2,
    head_dim=8, num_experts=4, expert_group_size=4, expert_group_rank=1,
    num_experts_per_tok=3, indexer_num_heads=2, indexer_head_dim=8, topk=6,
    mrope_section=(2, 1, 1), behaviour_positions=8, rope_theta=10000.0,
    rms_norm_eps=1e-6, attention_block=8, index_block=8, head_block=8)
ENV = dict(vocab_size=64, seq_len=21, corpus_sequences=4, seed=0)


def reference():
    if ROOT not in sys.path:        # the reference imports benchmark.costs
        sys.path.insert(0, ROOT)
    spec = importlib.util.spec_from_file_location(
        "reference_indexed_moe_lm",
        os.path.join(ROOT, "benchmark", "reference", "indexed_moe_lm.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def config(rank=1, policy=None, **env):
    """What the reference reads its sizes from, for the tiny model."""
    kwargs = {**TINY, **(policy or {})}
    kwargs["layer_types"] = list(kwargs["layer_types"])
    kwargs["mrope_section"] = list(kwargs["mrope_section"])
    return {"build": {"kwargs": {
        "policy_kwargs": kwargs,
        "agent_kwargs": {"env": {"kwargs": {**ENV, **env}}},
        "low_rank": rank}}}


def published():
    with open(CONFIG_FILE) as f:
        return json.load(f)
