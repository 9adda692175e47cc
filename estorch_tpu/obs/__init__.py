"""estorch_tpu.obs — first-class observability for ES runs.

Production ES is operationally opaque by default: a generation is one
fused device program, a wedge surfaces as a supervisor timeout, and a
regression shows up as a single slower steps/s number with no phase
attribution.  This package makes every run, wedge, and regression
explain itself (docs/observability.md):

- **spans** (`spans.py`): per-phase timers (sample/eval/update/...) with
  ``block_until_ready`` fencing, merged into each generation record;
- **counters/gauges** (`counters.py`): recompiles, env-steps, rollout
  failures, peak RSS — one snapshot per run;
- **flight recorder + heartbeat** (`recorder.py`): ring buffer of recent
  spans/events + an atomically-rewritten last-known-state file that
  bench.py, the supervisor, and doctor read when a run stops answering;
- **sinks** (`sinks.py`): JSONL / TensorBoard / fan-out record writers
  (absorbed from ``utils.metrics``; old names still importable there);
- **manifest** (`manifest.py`): config + jax version + device topology +
  git sha, written once per run;
- **summarize** (`summarize.py`, ``python -m estorch_tpu.obs``): phase
  time share, throughput trend, stall diagnosis from a run JSONL;
- **export** (`export/`): the operator-facing surfaces — Prometheus
  text exposition (+ the jax-free ``serve-metrics`` sidecar), Perfetto
  trace-event export (``obs trace``), and the ``obs regress`` perf gate
  over committed ``BENCH_*.json`` baselines.

``utils.metrics`` remains as a re-export shim for backward
compatibility; the jax-profiler hooks live in ``obs.trace`` alone.
"""

from . import export  # noqa: F401  (prometheus/sidecar/trace/regress)
from .counters import Counters, NullCounters
from .export import (MetricsSidecar, export_trace, parse_exposition,
                     render_exposition, validate_trace)
from .hist import Histogram, Histograms, NullHistograms
from .manifest import collect_manifest, load_manifest, write_manifest
from .recorder import (HEARTBEAT_ENV, STALE_AFTER_S, FlightRecorder,
                       Heartbeat, describe_heartbeat, read_heartbeat)
from .sinks import (JsonlSink, JsonlWriter, MultiSink, MultiWriter,
                    TensorBoardSink, TensorBoardWriter)
from .spans import NULL_TELEMETRY, Telemetry, resolve_telemetry
from .summarize import (format_summary, load_records,
                        load_records_tolerant, selfcheck, summarize,
                        validate_record)
from .trace import STAGES, annotate, stage, trace

__all__ = [
    "Counters",
    "NullCounters",
    "Histogram",
    "Histograms",
    "NullHistograms",
    "FlightRecorder",
    "Heartbeat",
    "HEARTBEAT_ENV",
    "STALE_AFTER_S",
    "describe_heartbeat",
    "read_heartbeat",
    "JsonlSink",
    "JsonlWriter",
    "MultiSink",
    "MultiWriter",
    "TensorBoardSink",
    "TensorBoardWriter",
    "Telemetry",
    "NULL_TELEMETRY",
    "resolve_telemetry",
    "collect_manifest",
    "write_manifest",
    "load_manifest",
    "format_summary",
    "load_records",
    "load_records_tolerant",
    "export",
    "MetricsSidecar",
    "export_trace",
    "validate_trace",
    "parse_exposition",
    "render_exposition",
    "selfcheck",
    "summarize",
    "validate_record",
    "STAGES",
    "annotate",
    "stage",
    "trace",
]
