"""``trace_reduce.py`` on synthetic intervals and on a small trace recorded
on a TPU v5e (``data/tiny_tpu.xplane.pb``, made by
``record_tiny_trace.py``)."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import trace_reduce  # noqa: E402

TINY = os.path.join(HERE, "data", "tiny_tpu.xplane.pb")


def test_union_and_gap_names():
    assert trace_reduce.union([(0, 1), (0.5, 2), (3, 4), (3.2, 3.5)]) == [
        (0, 2), (3, 4)]
    spans = [("inside_generation", 0.0, 2.0), ("record", 2.0, 3.1)]
    named = trace_reduce.name_gaps([(2.0, 3.0), (1.0, 1.1)], spans)
    assert named[0][0] == "record" and named[0][1] == pytest.approx(1.0)
    assert named[1][0] == "inside_generation"
    assert trace_reduce.name_gaps([(9.0, 9.5)], spans)[0][0] == "outside_spans"


def test_operation_names():
    text = "%fusion.458 = f32[10240,1,256]{2,1,0} fusion(bf16[10240,256,256] %p)"
    assert trace_reduce.op_id(text) == "fusion.458"
    assert trace_reduce.op_label(text).startswith("fusion.458_f32_10240_1_256")
    assert trace_reduce.CONTAINER.match("while.12")
    assert trace_reduce.CONTAINER.match("while")
    assert not trace_reduce.CONTAINER.match("while_body_fusion.3".replace(
        "while_", "fused_"))
    assert trace_reduce.COLLECTIVE.match("all-gather.3")
    assert trace_reduce.COLLECTIVE.match("all-reduce-start.1")


@pytest.fixture(scope="module")
def tiny():
    if not os.path.exists(TINY):
        pytest.skip("no recorded trace")
    return trace_reduce.load(TINY)


def test_recorded_trace_reduces(tiny):
    marks = trace_reduce.fence_times(tiny)
    assert len(marks) == 4
    reduced = trace_reduce.reduce(tiny, window=(marks[0], marks[-1]))
    assert reduced is not None
    d = reduced["devices"][reduced["busiest"]]
    assert d["window_s"] == pytest.approx(marks[-1] - marks[0])
    assert 0.0 < d["busy_s"] < d["window_s"]
    # no container in the table, and the table adds up to the busy time
    # (leaf operations of one stream do not overlap)
    assert not any(trace_reduce.CONTAINER.match(k) for k in d["per_op"])
    assert sum(d["per_op"].values()) == pytest.approx(d["busy_s"], rel=1e-6)
    # three calls between four fences: the gaps between calls are idle
    assert sum(b - a for a, b in d["gaps"]) == pytest.approx(
        d["window_s"] - d["busy_s"], rel=1e-9)
    assert d["collective_s"] == 0.0
