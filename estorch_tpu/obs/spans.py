"""Span telemetry: low-overhead phase timers for the training loop.

A *span* is one timed phase of a generation — ``sample`` / ``eval`` /
``update`` on the host and pooled backends, ``dispatch`` / ``device`` /
``host_sync`` on the fused device path (docs/observability.md has the
full list of span names).  The host cannot see inside the fused
program's ``device`` span; the program names its own stages
(``es.noise``, ``es.policy``, ... — obs/trace.py ``STAGES``) and a
profiler trace splits the span by them.  Spans nest: a phase entered
inside another is recorded under ``parent/child`` (e.g.
``update/obsnorm_merge``), and the parent's time includes its children —
per-phase *share* therefore sums top-level names only.

Every phase is also a span in the profiler's trace: it enters
``obs.trace.annotate(name, generation=...)`` for its life, so under
``jax.profiler`` the phases sit on a ``/host:CPU`` line of the same
``.xplane.pb`` as the device operations, with true starts and ends and
the generation they belong to.  With no profiler running that is about a
microsecond a phase.

Device honesty: wall-clocking an async-dispatched jitted call measures
dispatch, not compute (esguard R07).  Every device span either contains
its own materialization (``np.asarray`` of an output) or passes
``fence=`` — a callable run before the clock stops, typically
``jax.block_until_ready`` on the program's outputs.

Set-up spans: a span whose name starts ``setup/`` (``SETUP_SPANS``) is
the process's and not one generation's.  It is kept WHOLE (name, start and
end on ``time.perf_counter``, thread, parent) in the one bounded
:data:`TIMELINE` of the process, beside the process's own start and the
moment ``estorch_tpu`` finished importing, and stays out of the
per-generation accumulator: ``take_phases`` / ``discard_phases`` neither
drop nor double it.  The first generation's record carries the timeline
under ``"setup"`` (:func:`setup_summary`); the benchmark's ``boot.*``
metrics read it (docs/observability.md "Set-up spans").  A set-up span
never fences: where the host did not wait, it does not wait under a span.

Overhead budget: a disabled Telemetry's ``phase()`` yields a cached
no-op context manager (two attribute loads); an enabled one costs two
``perf_counter`` calls, a trace annotation + dict update per span.  Heartbeat/file work only
happens when a heartbeat path is configured (supervisors opt in via the
``ESTORCH_OBS_HEARTBEAT`` env var).  The budget for default-on spans is
<2% of generation wall time (``bench.py --obs-ab`` is the gate).  On the
chip (v5e, ``synth376-train-1chip``, a generation of 0.198 s, two pairs a
seed a pair, the default ``Telemetry`` against ``ESTORCH_OBS=0``; chip
runs, PR 50): ``steps_per_s_per_chip`` 4,072,953 against 4,072,722
(+0.006%) and 4,070,604 against 4,073,563 (-0.073%), ``setup_s`` 16.69
against 16.69 s and 16.58 against 16.95 s: inside the runs' own spread,
a fortieth of the budget.  One set-up span costs 6.8 us on that host, a
generation phase 6.1 us, a listener callback that keeps its event 1.0 to
1.4 us; a process leaves 13 to 22 set-up spans and 2,400 to 6,300 kept
events before its first measured generation: under 10 ms.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time

from .counters import Counters, NullCounters
from .hist import Histograms, NullHistograms
from .profile.ledger import CompileLedger, ledger_counters
from .recorder import HEARTBEAT_ENV, FlightRecorder, Heartbeat
from .trace import annotate

OBS_DISABLE_ENV = "ESTORCH_OBS"  # "0" disables default-on telemetry

# shared stateless no-op context manager: the disabled path costs one
# attribute check + one return, no generator construction per span
_NULL_CM = contextlib.nullcontext()

# ------------------------------------------------------------ set-up spans
#
# the vocabulary of the program's start-up (docs/observability.md "Set-up
# spans").  ``Telemetry.phase`` is how each is opened: a name that starts
# ``setup/`` nests under an open set-up span by its leaf (an engine's
# ``init_state`` asks for ``setup/init_state`` and is
# ``setup/init/init_state`` inside ``ES.__init__``) and stands at the top
# anywhere else.
SETUP_PREFIX = "setup/"
SETUP_SPANS = (
    "setup/init",                # all of ES.__init__
    "setup/init/module_init",    # flax init from the real observation
    "setup/init/param_spec",     # ravel and spec
    "setup/init/noise_table",
    "setup/init/mesh",
    "setup/init/engine_build",   # the engine's constructor
    "setup/init/init_state",
    "setup/init/cost_model",     # _post_engine_init
    "setup/init_state",          # an engine's init_state outside ES.__init__
    "setup/compile",             # an engine's compile
    "setup/compile/lower",       # .lower(...): trace and MLIR
    "setup/compile/acquire",     # .compile(): retrieval or build, the load
    "setup/compile/facts",       # compiled_cost_facts / memory_analysis
    "setup/compile/copy_into",   # the sharded engine's second program
)


def _process_age_s() -> float:
    """Seconds since the kernel started this process (0 where ``/proc``
    is absent), as ``benchmark/run.py::process_age_s`` reads it."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


class Timeline:
    """The process's start-up on ONE clock (``time.perf_counter``, the
    clock of the benchmark's fences): when the process started, when
    ``estorch_tpu`` finished importing, every set-up span whole, and the
    first generations' phases whole (a reader places the warm-up by them).
    Set-up is the process's, not one ES's: a run that builds two ES
    objects, or a reader that is handed no ES, sees one timeline.
    Bounded: past ``SPAN_CAP`` a set-up span is counted in ``dropped`` and
    not kept; past ``PHASE_CAP`` a phase is not kept (every record's
    ``phases`` has its seconds anyway)."""

    SPAN_CAP = 4096
    PHASE_CAP = 512

    def __init__(self):
        now = time.perf_counter()
        self.process_start = now - _process_age_s()
        self.imported: float | None = None
        self.backend_up_at_import: bool | None = None
        # (name, start, end, thread id, parent's name or None)
        self.spans: list[tuple] = []
        # (name, start, end, thread id, generation)
        self.phases: list[tuple] = []
        self.dropped = 0

    def mark_imported(self) -> None:
        """Called once, by the last line of ``estorch_tpu/__init__.py``.
        Also notes whether a jax backend was live by then: a caller that
        asked for the devices BEFORE it imported the package paid the
        runtime's bring-up before this stamp, one that asks later pays it
        after (a reader that takes the bring-up out has to know which)."""
        if self.imported is None:
            self.imported = time.perf_counter()
            try:
                from jax._src import xla_bridge

                self.backend_up_at_import = bool(xla_bridge._backends)
            except (ImportError, AttributeError):
                self.backend_up_at_import = None

    def add_span(self, name, start, end, parent) -> None:
        if len(self.spans) < self.SPAN_CAP:
            self.spans.append(
                (name, start, end, threading.get_ident(), parent))
        else:
            self.dropped += 1

    def add_phase(self, name, start, end, generation) -> None:
        if len(self.phases) < self.PHASE_CAP:
            self.phases.append(
                (name, start, end, threading.get_ident(), generation))


TIMELINE = Timeline()


def setup_summary() -> dict:
    """The timeline as a generation record carries it (``"setup"``), made
    the moment the first generation is complete: seconds since the
    process's start throughout.  The acquisition summary is
    ``utils.backend.acquisition_summary`` 's and is empty where no listener
    was installed."""
    from ..utils.backend import acquisition_summary

    t0 = TIMELINE.process_start
    out = {
        "schema": 1,
        "first_generation_done_s": round(time.perf_counter() - t0, 6),
        "spans": [
            {"name": name, "start_s": round(a - t0, 6),
             "end_s": round(b - t0, 6), "thread": thread,
             **({"parent": parent} if parent else {})}
            for name, a, b, thread, parent in list(TIMELINE.spans)],
        "acquisitions": acquisition_summary(),
    }
    if TIMELINE.imported is not None:
        out["imported_s"] = round(TIMELINE.imported - t0, 6)
    if TIMELINE.dropped:
        out["spans_dropped"] = TIMELINE.dropped
    return out


def format_setup(setup: dict) -> str:
    """One line: time to the first generation and its parts (the import,
    the top-level set-up spans summed by name, the acquisitions)."""
    total = setup["first_generation_done_s"]
    tops: dict[str, float] = {}
    for s in setup["spans"]:
        if "parent" not in s:
            tops[s["name"]] = tops.get(s["name"], 0.0) + (
                s["end_s"] - s["start_s"])
    parts = [f"before the package {setup['imported_s']:.2f} s"] \
        if "imported_s" in setup else []
    parts += [f"{name} {dur:.2f} s" for name, dur in tops.items()]
    acq = setup["acquisitions"]
    if acq.get("programs"):
        parts.append(
            f"{acq['programs']} executables acquired "
            f"({acq['cache_hits']} from the cache) in {acq['backend_s']:.2f} "
            f"s, traced and lowered in "
            f"{acq['trace_s'] + acq['lower_s']:.2f} s")
    return (f"first generation complete {total:.2f} s after the process "
            f"started: " + ", ".join(parts))


class Telemetry:
    """Per-run telemetry hub: spans + counters + flight recorder + heartbeat.

    One instance rides each ``ES`` (``es.obs``); engines receive it as
    their ``telemetry`` attribute so sub-generation phases land in the
    same accumulator the train loop flushes into the generation record.
    """

    def __init__(self, enabled: bool = True,
                 heartbeat_path: str | None = None,
                 recorder_capacity: int = 512):
        self.enabled = bool(enabled)
        # disabled hubs swallow counter writes too — engines inc
        # unconditionally, and the shared NULL_TELEMETRY default must
        # never aggregate state across unrelated engines (see NullCounters)
        self.counters = Counters() if self.enabled else NullCounters()
        # streaming histograms (obs/hist.py): the distribution-shaped
        # facts — queue waits, per-phase durations, staleness — that
        # counters/gauges erase; same inert-when-disabled contract
        self.hists = Histograms() if self.enabled else NullHistograms()
        self.recorder = FlightRecorder(recorder_capacity)
        self.heartbeat = Heartbeat(heartbeat_path) if heartbeat_path else None
        self.generation = 0
        # span nesting is PER THREAD (the overlap scheduler runs the
        # engine's sample/eval/update spans from a background thread
        # while the main thread records host_sync/record — one shared
        # stack would interleave their pushes/pops into bogus names
        # like "async/dispatch/eval"); the accumulator is shared and
        # lock-guarded so both threads' spans land in the same record
        self._acc: dict[str, float] = {}
        self._acc_lock = threading.Lock()
        self._tls = threading.local()
        self._annotate = annotate  # swapped for a no-op where jax is absent
        # performance-attribution facts (obs/profile/): the per-program
        # compile ledger and the run's analytic cost model — engines feed
        # the first, ES sets the second, `obs profile` joins them
        self.compile_ledger = CompileLedger()
        self.cost_model: dict | None = None

    # ------------------------------------------------------------- factory

    @classmethod
    def from_env(cls) -> "Telemetry":
        """Default-on construction honoring the env-var protocol:
        ``ESTORCH_OBS=0`` disables, ``ESTORCH_OBS_HEARTBEAT=<path>``
        (set by supervisors like bench.py stages) enables the heartbeat
        file."""
        enabled = os.environ.get(OBS_DISABLE_ENV, "1") != "0"
        hb = os.environ.get(HEARTBEAT_ENV) or None
        return cls(enabled=enabled, heartbeat_path=hb if enabled else None)

    # --------------------------------------------------------------- spans

    def phase(self, name: str, fence=None):
        """Time one phase; ``fence()`` (if given) runs before the clock
        stops — pass a ``block_until_ready`` closure for device work."""
        if not self.enabled:
            return _NULL_CM
        return self._phase_cm(name, fence)

    @property
    def _stack(self) -> list[str]:
        """This thread's span-nesting stack (see __init__)."""
        stack = getattr(self._tls, "stack", None)
        if stack is None:
            stack = self._tls.stack = []
        return stack

    @contextlib.contextmanager
    def _phase_cm(self, name: str, fence):
        stack = self._stack
        parent = stack[-1] if stack else None
        if name.startswith(SETUP_PREFIX):
            # set-up is the process's: under an open set-up span the leaf
            # nests, anywhere else the span stands at the top
            if parent is not None and parent.startswith(SETUP_PREFIX):
                full = f"{parent}/{name[len(SETUP_PREFIX):]}"
            else:
                parent, full = None, name
        else:
            full = f"{parent}/{name}" if parent else name
        setup = full.startswith(SETUP_PREFIX)
        stack.append(full)
        if self.heartbeat is not None:
            # beat on ENTRY: a wedge inside this phase leaves its name —
            # not the previous phase's — as the last-known state
            self.heartbeat.beat(full, self.generation,
                                self.counters.snapshot(),
                                hists=self.hists.snapshot(compact=True))
        try:
            # the phase as a span in the profiler's trace (obs/trace.py)
            annotation = self._annotate(full, generation=self.generation)
        except ImportError:
            # no jax profiler in this process: phases still record
            self._annotate = lambda name, **ids: _NULL_CM
            annotation = _NULL_CM
        t0 = time.perf_counter()
        try:
            with annotation:
                yield
                if fence is not None:
                    fence()
        finally:
            t1 = time.perf_counter()
            dt = t1 - t0
            stack.pop()
            if setup:
                # kept whole in the process's timeline, and out of the
                # generation's accumulator
                TIMELINE.add_span(full, t0, t1, parent)
            else:
                with self._acc_lock:
                    self._acc[full] = self._acc.get(full, 0.0) + dt
                TIMELINE.add_phase(full, t0, t1, self.generation)
            # per-phase duration DISTRIBUTION, not just the sum: the
            # accumulator's per-generation total is what records carry,
            # the histogram is what `obs regress --tail` gates on
            self.hists.observe("phase/" + full, dt)
            trace = getattr(self._tls, "trace", None)
            if trace is not None:
                self.recorder.add("span", full, dur_s=dt,
                                  generation=self.generation, trace=trace)
            else:
                self.recorder.add("span", full, dur_s=dt,
                                  generation=self.generation)

    # ------------------------------------------------------------- traces

    @contextlib.contextmanager
    def trace_ctx(self, trace_id: str):
        """Causal identity for spans/events: everything recorded inside
        this context carries ``trace=trace_id`` into the flight recorder
        (serve request ids, async dispatch ids — docs/observability.md
        "Tails & traces").  Thread-local, like span nesting."""
        prev = getattr(self._tls, "trace", None)
        self._tls.trace = trace_id
        try:
            yield
        finally:
            self._tls.trace = prev

    def observe(self, name: str, value: float, n: int = 1,
                exemplar: str | None = None, **ladder) -> None:
        """Record ``n`` observations into the named streaming histogram
        (obs/hist.py; ladder kwargs apply on first observe only;
        ``exemplar`` attaches a trace id to the value's bucket)."""
        self.hists.observe(name, value, n, exemplar=exemplar, **ladder)

    def take_phases(self) -> dict[str, float]:
        """Flush this generation's span accumulator (merged into the
        generation record) and advance the generation counter."""
        if not self.enabled:
            return {}
        with self._acc_lock:
            out = {k: round(v, 6) for k, v in self._acc.items()}
            self._acc.clear()
        self.generation += 1
        self.counters.inc("generations")
        self.counters.sample_peak_rss()
        if self.heartbeat is not None:
            self.heartbeat.beat("between_generations", self.generation,
                                self.counters.snapshot(),
                                hists=self.hists.snapshot(compact=True))
        return out

    def discard_phases(self) -> None:
        """Drop accumulated spans without emitting them.  Train loops
        call this on entry: a generation that aborted mid-phase (dead
        env raising through the loop — the documented catch-and-resume
        contract) leaves partial spans behind, which must not be merged
        into the next successful generation's record.  The flight
        recorder keeps the aborted spans for post-mortems."""
        with self._acc_lock:
            self._acc.clear()

    def note(self, phase: str) -> None:
        """Heartbeat-only marker for long un-spanned stretches (backend
        init, XLA compile): a wedge there should still leave a last-known
        phase behind, without polluting the span accumulator."""
        if self.enabled and self.heartbeat is not None:
            self.heartbeat.beat(phase, self.generation,
                                self.counters.snapshot(),
                                hists=self.hists.snapshot(compact=True))

    # ------------------------------------------------- compile ledger

    def set_cost_model(self, model: dict | None) -> None:
        """Attach the run's analytic FLOPs/bytes model (obs/profile/
        costmodel.py); ES writes it into the generation-0 record so
        ``obs profile`` can turn phase seconds into achieved rates."""
        if self.enabled:
            self.cost_model = dict(model) if model else None

    def compile_event(self, program: str, dur_s: float, compiled=None,
                      count_recompiles: int = 1, **extra):
        """Record one program compile: ledger entry (+ XLA cost facts
        duck-typed off ``compiled`` when given), ``recompiles`` counter
        (``count_recompiles`` programs — 0 when the caller counts its
        own), per-program registry gauges for /metrics, and a flight-
        recorder event.  Thread-safe primitives only (the serving
        batcher records from its worker thread)."""
        if not self.enabled:
            return None
        from ..utils.backend import last_acquisition
        from .profile.costmodel import compiled_cost_facts

        facts = compiled_cost_facts(compiled) if compiled is not None else {}
        # the listener's record of the same acquisition, where it kept one
        # inside the caller's interval (utils/backend.py): the program's
        # name in jax, the seconds of the acquisition alone, and whether
        # the persistent cache served it
        facts.update(last_acquisition(
            since=time.perf_counter() - float(dur_s) - 0.5))
        entry = self.compile_ledger.record(
            program, dur_s, generation=self.generation, **facts, **extra)
        if count_recompiles:
            self.counters.inc("recompiles", count_recompiles)
        # cumulative compile seconds across the run's programs (gauge:
        # re-derivable from the ledger, last-write-wins by design)
        self.counters.gauge("compile_time_s", round(sum(
            e.get("compile_s", 0.0) for e in self.compile_ledger.entries()),
            6))
        for name, value in ledger_counters([entry]).items():
            self.counters.gauge(name, value)
        self.recorder.add("event", "compile", generation=self.generation,
                          program=program, dur_s=dur_s)
        return entry

    def take_compile_events(self) -> list[dict]:
        """Ledger entries recorded since the last flush — merged into the
        generation record as ``compile_events`` (obs profile / obs trace
        read them back)."""
        if not self.enabled:
            return []
        return self.compile_ledger.take_new()

    # -------------------------------------------------------------- events

    def event(self, name: str, **extra) -> None:
        """Record a non-span event (compile, retry, error) in the ring.
        The current :meth:`trace_ctx` id rides along unless the caller
        passed its own ``trace=``."""
        if self.enabled:
            trace = getattr(self._tls, "trace", None)
            if trace is not None and "trace" not in extra:
                extra["trace"] = trace
            self.recorder.add("event", name, generation=self.generation,
                              **extra)


class _NullTelemetry(Telemetry):
    """Shared disabled instance — the default ``telemetry`` attribute of
    every engine, so instrumented code never branches on None."""

    def __init__(self):
        super().__init__(enabled=False)


NULL_TELEMETRY = _NullTelemetry()


def resolve_telemetry(telemetry) -> Telemetry:
    """ES's ``telemetry=`` kwarg → a Telemetry: None → env-driven
    default-on, bool → forced on/off, instance → as-is."""
    if telemetry is None:
        return Telemetry.from_env()
    if isinstance(telemetry, Telemetry):
        return telemetry
    if telemetry is True:
        return Telemetry(enabled=True,
                         heartbeat_path=os.environ.get(HEARTBEAT_ENV) or None)
    if telemetry is False:
        return Telemetry(enabled=False)
    raise TypeError(
        f"telemetry must be None, a bool, or a Telemetry, got {telemetry!r}")
