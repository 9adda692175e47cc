"""The program's start-up as spans (obs/spans.py "Set-up spans"): the
vocabulary a tiny ``ES`` yields on both engines, where the spans are kept
and where they are not, the listener's acquisition log
(utils/backend.py), the ``"setup"`` key of a run's first record, and that
none of it fences.
"""

import json
import logging
import time

import jax
import jax.numpy as jnp
import optax
import pytest

from estorch_tpu import ES, JaxAgent, MLPPolicy
from estorch_tpu.envs import Pendulum
from estorch_tpu.obs import Telemetry, export_trace, validate_record
from estorch_tpu.obs import spans as spans_mod
from estorch_tpu.obs.export import validate_trace
from estorch_tpu.obs.recorder import read_heartbeat
from estorch_tpu.obs.spans import (NULL_TELEMETRY, SETUP_SPANS, TIMELINE,
                                   Timeline, format_setup, setup_summary)
from estorch_tpu.utils import backend

ENGINES = {
    "replicated": {},
    "sharded": dict(shard_params=True, noise_mode="table", low_rank=1),
}
# what each engine's construction and compile open, beneath the vocabulary
EXPECTED = {
    "replicated": set(SETUP_SPANS) - {"setup/init_state",
                                      "setup/compile/copy_into"},
    "sharded": set(SETUP_SPANS) - {"setup/init_state"},
}


def tiny_es(engine="replicated", **kw):
    return ES(MLPPolicy, JaxAgent, optax.adam, population_size=16,
              sigma=0.05, policy_kwargs=dict(action_dim=1, hidden=(8, 8)),
              agent_kwargs=dict(env=Pendulum(), horizon=10),
              optimizer_kwargs=dict(learning_rate=1e-2),
              table_size=1 << 14, **ENGINES[engine], **kw)


@pytest.fixture
def timeline(monkeypatch):
    """A timeline of this test's own: the process's has every other
    test's spans on it."""
    fresh = Timeline()
    fresh.imported = TIMELINE.imported
    monkeypatch.setattr(spans_mod, "TIMELINE", fresh)
    # and a log of its own: a worker that has run thousands of tests has
    # filled the process's, which then only counts
    monkeypatch.setattr(backend, "_ACQUISITION_LOG", [])
    return fresh


@pytest.fixture(params=sorted(ENGINES))
def trained(request, timeline):
    backend.install_compile_event_counters()
    es = tiny_es(request.param)
    records = []
    es.train(2, log_fn=records.append, verbose=False)
    return request.param, es, records, timeline


class TestTheVocabulary:
    def test_a_tiny_es_yields_the_set_up_spans(self, trained):
        engine, _, _, timeline = trained
        assert {s[0] for s in timeline.spans} == EXPECTED[engine]

    def test_each_child_lies_inside_its_parent(self, trained):
        _, _, _, timeline = trained
        whole = {}
        for name, begin, end, _, parent in timeline.spans:
            whole.setdefault(name, []).append((begin, end))
        children = [s for s in timeline.spans if s[4]]
        assert children
        for name, begin, end, _, parent in children:
            assert name.startswith(parent + "/")
            assert any(a <= begin <= end <= b for a, b in whole[parent])

    def test_none_is_in_a_generations_phases(self, trained):
        _, _, records, _ = trained
        for record in records:
            assert record["phases"]
            assert not any(name.startswith("setup/")
                           for name in record["phases"])

    def test_the_first_generations_phases_are_kept_whole(self, trained):
        _, _, records, timeline = trained
        names = [p[0] for p in timeline.phases]
        assert names[:4] == ["dispatch", "device", "host_sync", "record"]
        assert [p[4] for p in timeline.phases[:8]] == [0] * 4 + [1] * 4
        for (_, begin, end, _, _), dur in zip(
                timeline.phases, [records[0]["phases"][n] for n in names[:4]]):
            assert end - begin == pytest.approx(dur, abs=1e-5)

    def test_the_engines_spans_in_init_reach_the_es_telemetry(self, trained):
        """On the parent the engine held NULL_TELEMETRY until
        ``_post_engine_init``: whatever it spanned before was lost."""
        _, es, _, timeline = trained
        assert es.engine.telemetry is es.obs is not NULL_TELEMETRY
        assert "setup/init/init_state" in {s[0] for s in timeline.spans}
        assert es.obs.hists.snapshot()["phase/setup/init/init_state"][
            "count"] == 1

    def test_an_init_state_outside_init_stands_at_the_top(self, trained):
        _, es, _, timeline = trained
        before = len(timeline.spans)
        es.engine.init_state(jnp.zeros((es._spec.dim,)),
                             jax.random.PRNGKey(1))
        (name, _, _, _, parent), = timeline.spans[before:]
        assert name == "setup/init_state" and parent is None

    def test_the_first_record_carries_the_set_up(self, trained):
        _, _, records, _ = trained
        first, second = records
        assert "setup" in first and "setup" not in second
        assert validate_record(first) == []
        setup = json.loads(json.dumps(first["setup"]))
        assert {s["name"] for s in setup["spans"]} >= {
            "setup/init", "setup/compile/lower", "setup/compile/acquire"}
        assert 0 < setup["imported_s"] < setup["first_generation_done_s"]
        assert all(s["end_s"] <= setup["first_generation_done_s"]
                   for s in setup["spans"])
        acquired = setup["acquisitions"]
        assert acquired["programs"] >= 1
        assert acquired["backend_s"] > 0 and acquired["costliest"]

    def test_the_ledgers_entry_is_the_listeners(self, trained):
        """One fact, one record: the generation program's ledger entry
        carries what the listener kept of the same acquisition."""
        _, _, records, _ = trained
        entry, = [e for e in records[0]["compile_events"]
                  if e["program"].startswith("generation_step")]
        assert "generation" in entry["fun_name"]
        assert 0 < entry["acquire_s"] <= entry["compile_s"]
        assert isinstance(entry["cache_hit"], bool)

    def test_obs_trace_renders_the_set_up(self, trained):
        _, _, records, _ = trained
        trace = export_trace(records)
        assert validate_trace(trace) == []
        events = trace["traceEvents"]
        drawn = {e["name"]: e for e in events if e.get("cat") == "setup"}
        assert {"setup/init", "setup/compile"} <= set(drawn)
        first = next(e for e in events if e.get("cat") == "generation")
        assert drawn["setup/compile"]["ts"] + drawn["setup/compile"][
            "dur"] <= first["ts"] + 1.0
        assert any(e["name"] == "acquisitions in set-up" for e in events)


class TestNamesAndKeeping:
    def test_a_set_up_name_nests_by_its_leaf(self, timeline):
        t = Telemetry()
        with t.phase("setup/init"):
            with t.phase("mesh"):
                pass
            with t.phase("setup/init_state"):
                pass
        with t.phase("dispatch"):
            with t.phase("setup/compile"):
                with t.phase("lower"):
                    pass
        assert [(s[0], s[4]) for s in timeline.spans] == [
            ("setup/init/mesh", "setup/init"),
            ("setup/init/init_state", "setup/init"),
            ("setup/init", None),
            ("setup/compile/lower", "setup/compile"),
            ("setup/compile", None)]
        assert set(t.take_phases()) == {"dispatch"}

    def test_discard_and_take_neither_drop_nor_double(self, timeline):
        t = Telemetry()
        with t.phase("setup/init"):
            pass
        t.discard_phases()
        assert t.take_phases() == {}
        t.discard_phases()
        assert [s[0] for s in timeline.spans] == ["setup/init"]
        assert setup_summary()["spans"][0]["name"] == "setup/init"

    def test_the_timeline_is_bounded(self, timeline, monkeypatch):
        monkeypatch.setattr(Timeline, "SPAN_CAP", 3)
        monkeypatch.setattr(Timeline, "PHASE_CAP", 2)
        t = Telemetry()
        for _ in range(5):
            with t.phase("setup/init"):
                pass
            with t.phase("eval"):
                pass
        assert len(timeline.spans) == 3 and timeline.dropped == 2
        assert len(timeline.phases) == 2
        assert setup_summary()["spans_dropped"] == 2

    def test_a_span_beats_on_entry(self, timeline, tmp_path):
        path = str(tmp_path / "hb.json")
        t = Telemetry(heartbeat_path=path)
        with t.phase("setup/init"):
            assert read_heartbeat(path)["phase"] == "setup/init"
            with t.phase("engine_build"):
                assert read_heartbeat(path)["phase"] == (
                    "setup/init/engine_build")

    @pytest.mark.parametrize("telemetry", [False, Telemetry(enabled=False),
                                           NULL_TELEMETRY])
    def test_a_disabled_telemetry_records_nothing(self, timeline, telemetry):
        hub = spans_mod.resolve_telemetry(telemetry)
        assert hub.phase("setup/init") is spans_mod._NULL_CM
        es = tiny_es(telemetry=telemetry)
        records = []
        es.train(1, log_fn=records.append, verbose=False)
        assert timeline.spans == [] and timeline.phases == []
        assert "setup" not in records[0]

    def test_the_env_var_turns_the_spans_off(self, timeline, monkeypatch):
        monkeypatch.setenv("ESTORCH_OBS", "0")
        tiny_es()
        assert timeline.spans == []

    def test_the_process_start_and_the_import_are_stamped(self):
        assert TIMELINE.process_start < TIMELINE.imported < (
            time.perf_counter())
        assert spans_mod._process_age_s() > 0.0

    def test_one_line_says_where_the_time_went(self, timeline, caplog):
        es = tiny_es()
        with caplog.at_level(logging.INFO, logger="estorch_tpu.algo.es"):
            es.train(2, verbose=False)
        lines = [r.getMessage() for r in caplog.records
                 if "first generation complete" in r.getMessage()]
        assert len(lines) == 1
        assert "setup/init" in lines[0] and "setup/compile" in lines[0]
        assert lines[0] == format_setup(es.history[0]["setup"])

    @pytest.mark.parametrize("note", ['note("init")', 'note("compile")'])
    def test_no_note_is_left_in_algo(self, note):
        import pathlib

        import estorch_tpu.algo as algo

        for path in pathlib.Path(algo.__file__).parent.glob("*.py"):
            assert note not in path.read_text(), path


class TestNoFence:
    @pytest.mark.parametrize("engine", sorted(ENGINES))
    def test_a_set_up_span_adds_no_fence(self, engine, timeline,
                                         monkeypatch):
        """``ES(...)`` and ``engine.compile`` wait for nothing on the
        parent (0 calls, read there); under the spans they still do not."""
        calls = []
        real = jax.block_until_ready
        monkeypatch.setattr(jax, "block_until_ready",
                            lambda x: (calls.append(1), real(x))[1])
        es = tiny_es(engine)
        es.engine.compile(es.state)
        assert len(timeline.spans) >= len(EXPECTED[engine])
        assert len(calls) == 0


class TestTheAcquisitionLog:
    @pytest.fixture
    def log(self, monkeypatch):
        backend.install_compile_event_counters()
        kept = []
        monkeypatch.setattr(backend, "_ACQUISITION_LOG", kept)
        return kept

    def acquire_twice(self, tmp_path):
        """A fresh build into a cache directory of its own, then the same
        program again by a process that no longer holds it: a hit."""
        def boot_probe(x):
            return jnp.tanh(x * 3.0 + 1.0).sum()

        before = backend.compile_event_counts()
        x = jnp.arange(7.0)
        with backend.scoped_compilation_cache(str(tmp_path)):
            jax.jit(boot_probe)(x).block_until_ready()
            mid = backend.compile_event_counts()
            jax.clear_caches()
            backend._reset_live_cache()
            jax.jit(boot_probe)(x).block_until_ready()
            after = backend.compile_event_counts()
        return before, mid, after

    def test_a_fresh_build_and_a_hit_with_name_kind_and_interval(
            self, log, tmp_path):
        t0 = time.perf_counter()
        self.acquire_twice(tmp_path)
        t1 = time.perf_counter()
        mine = [e for e in log if e[1] and "boot_probe" in e[1]]
        kinds = [e[0] for e in mine]
        assert kinds.count("trace") == kinds.count("lower") == 2
        fresh, hit = [e for e in mine if e[0] == "backend"]
        assert fresh[1] == hit[1] == "jit(boot_probe)"
        assert fresh[4] is False and hit[4] is True
        for _, _, end, duration, _ in mine:
            assert duration > 0 and t0 <= end - duration <= end <= t1
        retrievals = [e for e in log if e[0] == "retrieval"]
        assert len(retrievals) == 1 and retrievals[0][1] is None
        # the retrieval lies inside the acquisition it served
        assert hit[2] - hit[3] <= retrievals[0][2] <= hit[2]
        assert backend.acquisition_log() == log

    def test_the_three_counts_are_what_they_were(self, log, tmp_path):
        before, mid, after = self.acquire_twice(tmp_path)
        built = [e for e in log if e[0] == "backend"]
        assert mid["programs"] - before["programs"] >= 1
        assert mid["cache_hits"] == before["cache_hits"]
        assert after["programs"] - before["programs"] == len(built)
        assert after["cache_hits"] - before["cache_hits"] == sum(
            1 for e in built if e[4]) >= 1
        assert after["build_s"] - before["build_s"] == pytest.approx(
            sum(e[3] for e in built))
        assert set(after) == {"programs", "cache_hits", "build_s"}

    def test_the_summary_and_the_newest_acquisition(self, log, tmp_path):
        self.acquire_twice(tmp_path)
        summary = backend.acquisition_summary()
        assert summary["programs"] == len(
            [e for e in log if e[0] == "backend"])
        assert summary["cache_hits"] >= 1 and summary["retrieval_s"] > 0
        assert len(summary["costliest"]) <= 5
        newest = backend.last_acquisition()
        assert newest["cache_hit"] is True
        assert newest["fun_name"] == "jit(boot_probe)"
        assert backend.last_acquisition(since=time.perf_counter()) == {}
        log.clear()
        assert backend.acquisition_summary() == {}

    def test_the_log_is_bounded(self, log, tmp_path, monkeypatch):
        monkeypatch.setattr(backend, "ACQUISITION_LOG_CAP", 2)
        monkeypatch.setattr(backend, "_ACQUISITION_DROPPED", [0])
        before, _, after = self.acquire_twice(tmp_path)
        assert len(log) == 2 and backend._ACQUISITION_DROPPED[0] > 0
        # past the cap an event is still counted
        assert after["programs"] - before["programs"] >= 2
        assert backend.acquisition_summary()["events_dropped"] > 0
