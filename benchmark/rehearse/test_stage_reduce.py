"""``xplane_meta.py`` and ``stage_reduce.py`` on the two small traces
recorded on a TPU v5e: ``data/tiny_tpu.xplane.pb`` (PR 23, no stage scope:
everything is ``unscoped``) and ``data/tiny_tpu_scoped.xplane.pb`` (PR 24,
``record_tiny_scoped_trace.py``: two stages, and two ``Telemetry`` phases a
call as annotations), and the two readers on made-up runs."""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import stage_reduce, trace_reduce, xplane_meta  # noqa: E402
from benchmark.files import load_file_module  # noqa: E402

TINY = os.path.join(HERE, "data", "tiny_tpu.xplane.pb")
SCOPED = os.path.join(HERE, "data", "tiny_tpu_scoped.xplane.pb")
TPU = "/device:TPU:0"


def reader(group):
    return load_file_module(os.path.join(os.path.dirname(HERE), "layers",
                                         group + ".py"))


def both(path):
    pd = trace_reduce.load(path)
    marks = trace_reduce.fence_times(pd)
    window = (marks[0], marks[-1])
    return (pd, marks, trace_reduce.reduce(pd, window=window),
            stage_reduce.reduce(pd, xplane_meta.event_metadata(path), window))


def test_innermost_stage_of_a_name_stack():
    stage_of = stage_reduce.stage_of
    assert stage_of("jit(gen)/vmap(es.rollout)/while/body/closed_call/"
                    "es.policy/dot_general:") == "policy"
    assert stage_of("jit(gen)/es.update/vmap()/while/body/es.env/sin") == "env"
    assert stage_of("jit(work)/while/body/closed_call/dot_general:") == (
        stage_reduce.UNSCOPED)
    assert stage_of("jit(f)/shapes.policy/add") == stage_reduce.UNSCOPED
    # jax wraps a scope entered under a transform in the transform's name
    assert stage_of("jit(gen)/vmap(es.env)/jit(_uniform)/vmap()/while/"
                    "body/closed_call/add") == "env"
    assert stage_of("jit(gen)/transpose(jvp(es.policy))/mul") == "policy"
    # a word that is not one of STAGES names no stage, wherever it stands
    assert stage_of("jit(gen)/vmap(es.rollout)/while/body/add") == (
        stage_reduce.UNSCOPED)
    assert stage_of("jit(gen)/es.grad/es.rollouts/my_es.policy/add") == "grad"
    assert stage_of(None) == stage_of("") == stage_reduce.UNSCOPED


def test_metadata_of_the_unscoped_trace():
    meta = xplane_meta.event_metadata(TINY)[TPU]
    fusion9, = [v for k, v in meta.items() if k.startswith("%fusion.9 = ")]
    assert fusion9 == {"tf_op": "jit(work)/while/body/closed_call/"
                                "dot_general:",
                       "flops": 268959744, "bytes_accessed": 3145728}
    assert "/host:CPU" in xplane_meta.event_metadata(TINY)


def test_unscoped_trace_is_booked_unscoped_and_sums_to_busy():
    _, _, reduced, staged = both(TINY)
    d = stage_reduce.busiest_device(staged)
    assert set(d["stage_s"]) == {stage_reduce.UNSCOPED}
    assert d["scoped_ops"] == 0
    assert sum(d["stage_s"].values()) == pytest.approx(
        trace_reduce.busiest_device(reduced)["busy_s"], abs=1e-6)
    # a program from before the scopes: nothing to report
    assert reader("stage").read(
        {"stage_reduce": {"staged": staged, "spans": []}}) == {}
    # phases in the trace but no stage: a stale compile-cache entry, read
    # as the trace reads
    stale = reader("stage").read({"stage_reduce": {
        "staged": staged, "spans": [("dispatch", 0.0, 1.0, 0)]}})
    assert stale["stage.unscoped_share"] == 1.0
    assert sum(stale.values()) == 1.0 and len(stale) == 6


def test_scoped_trace_has_two_stages_that_sum_to_busy():
    _, _, reduced, staged = both(SCOPED)
    d = stage_reduce.busiest_device(staged)
    assert {"policy", "env"} <= set(d["stage_s"]) <= {
        "policy", "env", stage_reduce.UNSCOPED}
    assert d["stage_s"]["policy"] > 0 and d["stage_s"]["env"] > 0
    assert sum(d["stage_s"].values()) == pytest.approx(
        trace_reduce.busiest_device(reduced)["busy_s"], abs=1e-6)
    stacks = {o[3] for o in d["ops"]["policy"].values()}
    assert all("es.policy" in s for s in stacks)
    shares = reader("stage").read({"stage_reduce": {"staged": staged}})
    assert shares["stage.policy_share"] == pytest.approx(
        d["stage_s"]["policy"] / d["busy_s"])
    assert shares["stage.noise_share"] == 0.0
    assert sum(shares.values()) == pytest.approx(1.0, abs=1e-9)


def test_host_spans_come_from_the_traces_annotations():
    pd, marks, reduced, staged = both(SCOPED)
    spans = stage_reduce.host_spans(pd)
    assert [(n, g) for n, _, _, g in spans] == [
        ("dispatch", 0), ("device", 0), ("dispatch", 1), ("device", 1),
        ("dispatch", 2), ("device", 2)]
    for (_, _, end, _), (_, start, _, _) in zip(spans, spans[1:]):
        assert end <= start
    assert stage_reduce.host_spans(trace_reduce.load(TINY)) == []
    named = trace_reduce.name_gaps(
        trace_reduce.busiest_device(reduced)["gaps"],
        stage_reduce.gap_spans(spans, (marks[0], marks[-1])))
    assert {n for n, _ in named} <= {"dispatch", "inside_generation",
                                     stage_reduce.BETWEEN}
    # this recorder's fences OPEN a call, and the device plane's clock runs
    # a millisecond ahead of the host plane's: a call's operations land
    # before its own fence, so each tail is the previous call's
    tails = stage_reduce.tail_gaps(
        stage_reduce.busiest_device(staged)["ends"], marks)
    assert tails and all(0.0 <= t < 0.005 for t in tails)


def test_gap_spans_and_tail_gaps_on_made_up_spans():
    spans = [("dispatch", 1.0, 1.2, 7), ("device", 1.2, 3.0, 7),
             ("update", 3.5, 4.5, 7), ("update/merge", 3.6, 3.7, 7)]
    named = stage_reduce.gap_spans(spans, (0.0, 5.0))
    assert named[0] == ("update/merge", 3.6, 3.7)       # innermost first
    assert ("inside_generation", 1.2, 3.0) in named
    assert [s for s in named if s[0] == stage_reduce.BETWEEN] == [
        (stage_reduce.BETWEEN, 0.0, 1.0), (stage_reduce.BETWEEN, 3.0, 3.5),
        (stage_reduce.BETWEEN, 4.5, 5.0)]
    assert trace_reduce.name_gaps([(3.62, 3.68), (3.1, 3.3)], named) == [
        [stage_reduce.BETWEEN, pytest.approx(0.2)],
        ["update/merge", pytest.approx(0.06)]]
    # last operation of each generation to the fence that closes it; a
    # generation without an operation gives nothing
    assert stage_reduce.tail_gaps([0.5, 1.0, 1.9, 2.6],
                                  [0.0, 1.0, 2.0, 3.0]) == [
        0.0, pytest.approx(0.1), pytest.approx(0.4)]
    assert stage_reduce.tail_gaps([0.5], [0.0, 1.0, 2.0]) == [0.5]
    assert stage_reduce.tail_gaps([0.5], [0.0]) == []


def test_span_reader_on_a_made_up_run():
    run = {"fences": [10.0, 11.0, 12.0],
           "records": [{"phases": {"record": 0.002}}, {"phases": None}],
           "stage_reduce": {"tail_gaps": [0.004, 0.003, 0.006]}}
    assert reader("span").read(run) == {
        "span.record_share": pytest.approx(0.001),
        "span.tail_gap_s": 0.004}
    # a trace that is not the one the runner reduced
    assert reader("span").read({**run, "stage_reduce": None}) == {
        "span.record_share": pytest.approx(0.001)}
    # a run that took no trace reads none (``--trace 0``, a CPU rehearsal)
    assert stage_reduce.of_run({"trace": None}) is None
