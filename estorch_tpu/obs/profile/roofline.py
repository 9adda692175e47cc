"""Rooflines: the denominators that make achieved rates honest.

``obs profile`` divides per-phase achieved FLOP/s and bytes/s by a peak.
For an accelerator the peak is a published fact about one kind of chip,
so the table below is keyed by ``device_kind`` exactly as JAX reports it
on the machine (``jax.devices()[0].device_kind``), each entry with the
source of its numbers; a kind that is not in the table is an error, never
a default.  On CPU there is no such number worth quoting: the "peak" of a
loaded shared-core host is whatever it can actually do today — so the
CPU roofline is MEASURED, not quoted: a short in-process GEMM (numpy →
BLAS, the best compute this host offers python) and a large memcpy
(stream bandwidth).  Every CPU-derived utilization is tagged
``cpu_calibrated`` so nobody mistakes "fraction of this host's measured
GEMM rate" for an MFU against accelerator silicon.

Deliberately jax-free (numpy + stdlib): the ``obs profile`` CLI reads a
finished run's records and needs no device runtime.
"""

from __future__ import annotations

import time

import numpy as np

# device_kind (as `jax.devices()[0].device_kind` printed it on the chip
# machine — chip_smoke.py's first lines) -> per-chip peaks and their source
DEVICE_ROOFLINES = {
    "TPU v5 lite": {
        "device_kind": "TPU v5 lite",
        "platform": "tpu",
        "basis": "tpu_v5e_bf16_peak",
        "peak_flops_per_s": 197e12,
        "peak_bytes_per_s": 819e9,
        "source": "Google Cloud documentation, \"TPU v5e\": 197 TFLOP/s "
                  "bf16 and 819 GB/s of HBM bandwidth per chip",
    },
}


def device_roofline(device_kind: str) -> dict:
    """The published per-chip roofline of ``device_kind``.  Raises for a
    kind the table does not hold: add the entry with its source before
    reporting a utilization on new hardware."""
    try:
        return dict(DEVICE_ROOFLINES[device_kind])
    except KeyError:
        raise ValueError(
            f"no roofline for device_kind {device_kind!r}; known: "
            f"{sorted(DEVICE_ROOFLINES)} (obs/profile/roofline.py — add "
            "the kind with the source of its peaks)") from None


_CPU_CACHE: dict | None = None


def measure_cpu_roofline(budget_s: float = 0.25, gemm_n: int = 384,
                         copy_mb: int = 32) -> dict:
    """Measured CPU roofline: best-of-repeats GEMM FLOP/s + memcpy bytes/s.

    Best-of (not median): the roofline is the *ceiling* this host can
    reach, and on a loaded shared core every slow repeat is interference,
    not capability.  ``budget_s`` bounds each of the two measurements.
    """
    n = int(gemm_n)
    a = np.random.default_rng(0).standard_normal((n, n)).astype(np.float32)
    b = np.random.default_rng(1).standard_normal((n, n)).astype(np.float32)
    a @ b  # warm-up: BLAS thread pool + page faults outside the clock
    flops_per_mm = 2.0 * n * n * n
    best_flops = 0.0
    deadline = time.perf_counter() + float(budget_s)
    while time.perf_counter() < deadline:
        t0 = time.perf_counter()
        a @ b
        dt = time.perf_counter() - t0
        if dt > 0:
            best_flops = max(best_flops, flops_per_mm / dt)

    src = np.zeros(int(copy_mb) * 2**20 // 4, np.float32)
    dst = np.empty_like(src)
    np.copyto(dst, src)  # warm-up
    moved = 2.0 * src.nbytes  # one read + one write per copy
    best_bw = 0.0
    deadline = time.perf_counter() + float(budget_s)
    while time.perf_counter() < deadline:
        t0 = time.perf_counter()
        np.copyto(dst, src)
        dt = time.perf_counter() - t0
        if dt > 0:
            best_bw = max(best_bw, moved / dt)
    return {
        "platform": "cpu",
        "basis": "cpu_calibrated",
        "peak_flops_per_s": best_flops,
        "peak_bytes_per_s": best_bw,
        "gemm_n": n,
        "copy_mb": int(copy_mb),
    }


def platform_roofline(platform: str, measure: bool = True) -> dict:
    """The roofline of an OFF-CHIP run: measured on CPU (cached per
    process — the calibration GEMM should run once, not per phase).
    ``measure=False`` on CPU returns None-peaks with the
    ``cpu_calibrated`` basis, for callers that only want the tag.

    A TPU is not a platform-wide fact: its peaks come from
    :func:`device_roofline`, by ``device_kind``.  Any OTHER platform
    (gpu, …) gets None-peaks and no basis: the host GEMM calibration
    measures this host's CPU, and dividing an accelerator's rate by it
    would produce exactly the dishonest cross-silicon number the basis
    tag exists to prevent — rates-only reporting is the honest answer
    until that platform gets its own denominator."""
    global _CPU_CACHE
    if platform == "tpu":
        raise ValueError(
            "a TPU roofline is keyed by device_kind: use "
            "device_roofline(jax.devices()[0].device_kind)")
    if platform != "cpu":
        return {"platform": str(platform), "basis": None,
                "peak_flops_per_s": None, "peak_bytes_per_s": None}
    if not measure:
        return {"platform": "cpu", "basis": "cpu_calibrated",
                "peak_flops_per_s": None, "peak_bytes_per_s": None}
    if _CPU_CACHE is None:
        _CPU_CACHE = measure_cpu_roofline()
    return dict(_CPU_CACHE)
