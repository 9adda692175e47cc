"""The ``boot.*`` reader (``benchmark/layers/boot.py``): the booking of every
instant of set-up to one part on synthetic timelines, the sum that has to
come to ``setup_s``, and two cells end to end on the CPU.  Not chip numbers:
a rehearsal prints every metric as ``rehearsal.<name>``."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.files import load_file_module  # noqa: E402

boot = load_file_module(os.path.join(ROOT, "benchmark", "layers", "boot.py"))

NAMES = {"boot." + name for name in boot.PARTS} | {"boot.programs",
                                                   "boot.retrieval_s"}


def span(name, begin, end, parent=None):
    return (name, begin, end, 1, parent)


def phases_of(begin, *durations, generation=0):
    out, at = [], begin
    for name, d in zip(("dispatch", "device", "host_sync", "record"),
                       durations):
        out.append((name, at, at + d, 1, generation))
        at += d
    return out


def event(kind, name, begin, end, hit=None):
    return (kind, name, end, end - begin, hit)


def booked(spans=(), phases=(), events=(), start=0.0, imported=2.0,
           fence=20.0):
    out = boot.book(start, imported, fence, list(spans), list(phases),
                    list(events))
    assert sum(out["parts"].values()) == pytest.approx(fence - start,
                                                       abs=1e-9)
    return out


def test_nested_spans_book_to_the_innermost():
    out = booked(spans=[
        span("setup/init/module_init", 4.0, 6.0, "setup/init"),
        span("setup/init/init_state", 7.0, 8.5, "setup/init"),
        span("setup/init", 3.0, 9.0),
        span("setup/init_state", 9.5, 10.0),
        span("setup/compile/lower", 10.0, 11.0, "setup/compile"),
        span("setup/compile/acquire", 11.0, 13.0, "setup/compile"),
        span("setup/compile/facts", 13.0, 13.25, "setup/compile"),
        span("setup/compile/copy_into", 13.25, 13.5, "setup/compile"),
        span("setup/compile", 10.0, 13.5)],
        phases=phases_of(14.0, 0.5, 1.0, 0.25, 0.25))
    p = out["parts"]
    assert p["before_program_s"] == 2.0
    assert p["before_init_s"] == 1.0
    assert p["build_s"] == pytest.approx(6.0 - 1.5)
    assert p["state_s"] == pytest.approx(1.5 + 0.5)
    assert p["lower_s"] == 1.0
    assert p["acquire_s"] == pytest.approx(2.5)
    assert p["warmup_s"] == pytest.approx(2.0)
    assert p["after_warmup_s"] == pytest.approx(4.0)
    # 9.0-9.5 and 13.5-14.0: between the landmarks, under nothing
    assert p["unspanned_s"] == pytest.approx(1.0)
    assert p["programs_s"] == 0.0 and out["programs"] == 0


def test_an_acquisition_is_innermost_outside_the_compile():
    """An event books before the span it lies in, one that straddles a
    span's end keeps all of its interval, nested trace events count once,
    and the compile's own events book with its two spans."""
    out = booked(spans=[
        span("setup/init/module_init", 4.0, 6.0, "setup/init"),
        span("setup/init", 3.0, 9.0),
        span("setup/compile/lower", 10.0, 11.0, "setup/compile"),
        span("setup/compile/acquire", 11.0, 13.0, "setup/compile"),
        span("setup/compile", 10.0, 13.0)],
        events=[
            event("trace", "inner", 4.5, 4.75),
            event("trace", "outer", 4.25, 5.0),
            event("backend", "jit(outer)", 5.0, 5.5, hit=True),
            event("retrieval", None, 5.25, 5.5),
            event("backend", "jit(straddles)", 8.5, 9.5, hit=False),
            event("trace", "generation", 10.0, 10.5),
            event("lower", "jit(generation)", 10.5, 11.0),
            event("retrieval", None, 11.0, 12.0),
            event("backend", "jit(generation)", 11.0, 13.0, hit=True),
            event("backend", "jit(after the fence)", 21.0, 22.0)])
    p = out["parts"]
    assert p["programs_s"] == pytest.approx(0.75 + 0.5 + 1.0)
    assert p["build_s"] == pytest.approx(6.0 - 0.75 - 0.5 - 0.5)
    assert p["lower_s"] == 1.0 and p["acquire_s"] == 2.0
    assert out["programs"] == 2 and out["programs_in_compile"] == 1
    assert out["retrieval_s"] == pytest.approx(1.25)
    # 9.0-9.5 is the event's; 9.5-10.0 and 13.0-20.0 lie under nothing,
    # and with no generation before the fence nothing is "after" one
    assert p["unspanned_s"] == pytest.approx(0.5 + 7.0)
    assert p["after_warmup_s"] == 0.0


def test_overlapping_threads_book_an_instant_once():
    """A second thread's span over the main thread's: the later to begin
    owns the overlap, and no second is counted twice."""
    out = booked(spans=[
        ("setup/init", 3.0, 9.0, 1, None),
        ("setup/init_state", 8.0, 11.0, 2, None),
        ("setup/compile", 10.0, 12.0, 1, None)])
    p = out["parts"]
    assert p["build_s"] == 5.0
    assert p["state_s"] == 2.0
    assert p["acquire_s"] == 2.0


def test_a_probe_after_the_warm_up_books_like_any_other_build():
    out = booked(spans=[span("setup/init", 3.0, 4.0),
                        span("setup/init/init_state", 16.5, 17.0,
                             "setup/init"),
                        span("setup/init", 16.0, 17.0)],
                 phases=phases_of(10.0, 1.0, 1.0, 1.0, 1.0))
    p = out["parts"]
    assert p["build_s"] == 1.5 and p["state_s"] == 0.5
    assert p["after_warmup_s"] == pytest.approx(2.0 + 3.0)
    assert p["unspanned_s"] == 6.0


def fake_program(monkeypatch, spans, phases=(), events=(), start=0.0,
                 imported=2.0, backend_up=True):
    from estorch_tpu.obs import spans as program
    from estorch_tpu.utils import backend

    timeline = program.Timeline()
    timeline.process_start, timeline.imported = start, imported
    timeline.backend_up_at_import = backend_up
    timeline.spans, timeline.phases = list(spans), list(phases)
    monkeypatch.setattr(program, "TIMELINE", timeline)
    monkeypatch.setattr(backend, "_ACQUISITION_LOG", list(events))


def test_the_reader_takes_the_bring_up_out_and_sums_to_setup_s(
        monkeypatch, capsys):
    fake_program(monkeypatch, [
        span("setup/init/engine_build", 5.0, 6.0, "setup/init"),
        span("setup/init", 3.0, 9.0),
        span("setup/compile/lower", 10.0, 11.0, "setup/compile"),
        span("setup/compile/acquire", 11.0, 13.0, "setup/compile"),
        span("setup/compile", 10.0, 13.0)],
        phases=phases_of(14.0, 0.5, 1.0, 0.25, 0.25),
        events=[event("backend", "jit(generation)", 11.0, 13.0, hit=True),
                event("backend", "jit(small)", 3.5, 3.75, hit=False)])
    run = {"fences": [20.0, 21.0], "bring_up_s": 1.5,
           "compile": {"aot_s": 3.01, "setup": {"programs": 2}}}
    out = boot.read(run)
    assert set(out) == NAMES
    assert out["boot.before_program_s"] == 0.5
    assert sum(out["boot." + name] for name in boot.PARTS) == pytest.approx(
        20.0 - 1.5, abs=1e-9)
    assert out["boot.programs"] == 1 and out["boot.programs_s"] == 0.25
    said = capsys.readouterr().out
    assert "[boot] setup/init/engine_build: 1.000 s" in said
    assert "acquisition backend jit(generation): 2.000 s" in said
    assert "= 3.000 s; compile.aot_s 3.01" in said
    assert "1 executables acquired outside setup/compile + 1 inside = 2; " \
           "compile's set-up snapshot counts 2" in said


def test_the_reader_refuses_parts_that_do_not_sum(monkeypatch):
    fake_program(monkeypatch, [span("setup/init", 3.0, 9.0)])
    real = boot.book

    def short(*args):
        out = real(*args)
        out["parts"]["build_s"] -= 0.01
        return out

    monkeypatch.setattr(boot, "book", short)
    with pytest.raises(ValueError, match="sum to"):
        boot.read({"fences": [20.0], "bring_up_s": 1.0})


@pytest.mark.parametrize("spans, imported, backend_up, why", [
    ([], 2.0, True, "no set-up span"),                  # ESTORCH_OBS=0
    ([("setup/init", 3.0, 9.0, 1, None)], 0.5, True, "cannot be placed"),
    ([("setup/init", 2.5, 9.0, 1, None)], 2.0, False, "cannot be placed"),
    ([("setup/init", 3.0, 9.0, 1, None)], 2.0, None, "cannot be placed"),
])
def test_an_empty_timeline_reads_nothing_and_says_why(
        monkeypatch, capsys, spans, imported, backend_up, why):
    fake_program(monkeypatch, spans, imported=imported,
                 backend_up=backend_up)
    assert boot.read({"fences": [20.0, 21.0], "bring_up_s": 1.0}) == {}
    assert why in capsys.readouterr().out


def test_a_bring_up_after_the_import_comes_out_of_before_init(monkeypatch):
    """``train_lm_runner`` imports the package to check the configuration's
    names and asks for the devices afterwards: no backend was live at the
    import, and the bring-up lies before the first ``setup/init``."""
    fake_program(monkeypatch, [span("setup/init", 12.0, 15.0)],
                 imported=2.0, backend_up=False)
    out = boot.read({"fences": [20.0], "bring_up_s": 8.0})
    assert out["boot.before_program_s"] == 2.0
    assert out["boot.before_init_s"] == 2.0
    assert sum(out["boot." + name] for name in boot.PARTS) == 12.0


def test_a_program_without_a_timeline_reads_nothing(monkeypatch, capsys):
    """The parent of the PR that brought the timeline: the reader is laid
    over it too, and has to return nothing without raising."""
    from estorch_tpu.obs import spans as program

    monkeypatch.delattr(program, "TIMELINE")
    assert boot.read({"fences": [20.0], "bring_up_s": 1.0}) == {}
    assert "no set-up timeline" in capsys.readouterr().out


def rehearse(cell, cache, devices, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env.update(extra_env or {})
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "3000000019", "--seconds", "2",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    return json.loads(p.stdout.splitlines()[-1])["metrics"], p.stdout


@pytest.mark.slow
@pytest.mark.parametrize("cell, devices", [
    ("synth376-train-1chip", 1),            # the replicated engine
    ("ouro-2.6b-es-4k-1chip", 1),           # the param-sharded engine
])
def test_a_rehearsed_cell_prints_all_twelve(tmp_path, cell, devices):
    metrics, said = rehearse(cell, tmp_path, devices)
    assert {"rehearsal." + name for name in NAMES} <= set(metrics)
    parts = sum(metrics["rehearsal.boot." + name]["value"]
                for name in boot.PARTS)
    assert parts == pytest.approx(metrics["rehearsal.setup_s"]["value"]
                                  if "rehearsal.setup_s" in metrics
                                  else parts, abs=1e-3)
    line = next(ln for ln in said.splitlines()
                if ln.startswith("[boot] setup_s "))
    setup_line = next(ln for ln in said.splitlines() if "set-up: " in ln)
    assert float(line.split()[2]) == pytest.approx(
        float(setup_line.split("set-up: ")[1].split()[0]), abs=0.006)
    assert metrics["rehearsal.boot.programs"]["value"] > 0
    assert metrics["rehearsal.boot.lower_s"]["value"] > 0


@pytest.mark.slow
def test_a_rehearsal_with_the_telemetry_off_reads_nothing(tmp_path):
    metrics, said = rehearse("synth376-train-1chip", tmp_path, 1,
                             {"ESTORCH_OBS": "0"})
    assert not any(name.startswith("rehearsal.boot.") for name in metrics)
    assert "[boot] no set-up span" in said
