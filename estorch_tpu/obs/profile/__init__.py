"""estorch_tpu.obs.profile — per-phase performance attribution.

The accounting layer over the span/counters hub (docs/observability.md
"Profiling"): turn wall-clock phase spans into achieved FLOP/s and
bytes/s against a platform roofline, keep per-program compile facts in
a structured ledger, and report MFU against the published peaks of the
chip's ``device_kind``, or honestly ``cpu_calibrated`` off-chip.

- :mod:`costmodel` — analytic FLOPs/bytes per phase from the run config;
- :mod:`roofline`  — published peaks by device_kind / measured CPU
  calibration;
- :mod:`ledger`    — compile events riding JSONL, Prometheus, Perfetto;
- :mod:`report`    — the ``obs profile`` CLI body + selfcheck.
"""

from .costmodel import (FUSED_PHASES, MODELED_PHASES, compiled_cost_facts,
                        generation_cost, phase_cost_for)
from .ledger import CompileLedger, collect_compile_events, ledger_counters
from .report import (find_cost_model, format_profile, profile_records,
                     selfcheck)
from .roofline import (DEVICE_ROOFLINES, device_roofline,
                       measure_cpu_roofline, platform_roofline)

__all__ = [
    "FUSED_PHASES",
    "MODELED_PHASES",
    "CompileLedger",
    "DEVICE_ROOFLINES",
    "collect_compile_events",
    "compiled_cost_facts",
    "device_roofline",
    "find_cost_model",
    "format_profile",
    "generation_cost",
    "ledger_counters",
    "measure_cpu_roofline",
    "phase_cost_for",
    "platform_roofline",
    "profile_records",
    "selfcheck",
]
