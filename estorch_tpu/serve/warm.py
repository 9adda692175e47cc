"""Warm-start bundles: ship the bucket ladder's compiled XLA programs
WITH the artifact, so a fresh replica's first request never waits on JIT.

The compile ledger (PR 6) shows exactly where a fresh serving process
spends its startup: the batcher's construction-time bucket verification
compiles one batched program per ladder shape — a multi-second JIT storm
for a large policy, paid again by every replica the fleet spins up.
This module moves that cost to EXPORT time, once:

* :func:`warm_bundle` replays the exact serve-time load path (``load_
  bundle`` → predict-program builders → :func:`build_serving_batcher`
  with its verification pass) under a scoped redirect of jax's
  persistent XLA compilation cache into ``<bundle>/warm/`` — so the warm
  directory ends up holding precisely the executables a serving process
  will ask for, auxiliary one-op programs included (a "zero fresh builds
  at load" proof fails on any program left out);
* :func:`install_warmth` copies those entries into the serving process's
  compile cache directory (``JAX_COMPILATION_CACHE_DIR`` where set, else
  ``utils.backend.default_compilation_cache_dir()``) BEFORE any jax
  work, so every subsequent compile request is a persistent-cache
  retrieval.  The bundle itself is never written
  to — jax's cache touches per-entry atime files on read, and a bundle
  must stay immutable under its manifest checksums (possibly on a
  read-only mount).

Warmth is advisory, never load-bearing: entries key on the exact HLO +
jax version + platform, so a mismatch (new jax on the serving host, cpu
bundle on a tpu) simply misses and compiles fresh — ``install_warmth``
detects the foreseeable mismatches up front and reports a structured
reason instead of silently shipping dead weight into the cache dir.
The proof of warmth is counted, not assumed: the server snapshots the
jax build counters (``utils.backend.compile_event_counts``) around the
bundle load and publishes ``compiles_at_load`` / ``warm_cache_hits``.
"""

from __future__ import annotations

import os
import shutil
import time
from typing import Sequence

import numpy as np

from .batcher import DynamicBatcher
from .bundle import WARM_DIR, BundleError, _sha256_file, load_bundle

# The documented per-bucket accuracy bound for quantized serving: the
# worst row of the quantized program may deviate from the f32 anchor by
# at most this fraction of the anchor output's scale
# (serve/batcher.py::measure_quant_divergence defines the metric).
# bf16 keeps ~8 mantissa bits (~0.4% per rounding); two GEMM layers plus
# activations accumulate to low single-digit percents for well-scaled
# policies, so 5% separates "quantization noise" from "this policy
# amplifies rounding error" with margin on both sides.
BF16_DIVERGENCE_BOUND = 0.05


def build_serving_batcher(
    bundle,
    *,
    max_batch: int = 32,
    max_wait_ms: float = 4.0,
    max_queue: int = 256,
    dtype: str = "f32",
    quant_bound: float | None = None,
    telemetry=None,
) -> DynamicBatcher:
    """THE serve-time batcher construction — one definition shared by the
    server's engine build and the export-time warm replay, so the warm
    cache can never drift from what a serving process actually compiles.

    ``dtype="bf16"`` builds the quantized fast path next to the f32
    reference: the batcher measures per-bucket divergence and excludes
    drifting buckets (f32 fallback at the same shape); a bundle that did
    not opt in, or a policy past the bound at the anchor, raises
    :class:`BundleError` — the server's 409, the CLI's exit 2.
    """
    batch_fn = bundle.batched_predict_fn()  # refuses recurrent bundles
    quant_fn = None
    bound = None
    if dtype != "f32":
        quant_fn = bundle.batched_predict_fn(dtype=dtype)  # opt-in check
        bound = float(quant_bound if quant_bound is not None
                      else BF16_DIVERGENCE_BOUND)
    try:
        return DynamicBatcher(
            batch_fn, bundle.obs_shape, max_batch=max_batch,
            max_wait_ms=max_wait_ms, max_queue=max_queue,
            telemetry=telemetry, quant_fn=quant_fn, quant_bound=bound,
            quant_label=dtype,
        )
    except ValueError as e:
        # slot-dependent anchor or out-of-bound quantization: bundle-grade
        # rejections — /reload answers 409, the CLI exits 2
        raise BundleError(
            f"bundle at {bundle.path!r} cannot serve ({dtype}): {e}"
        ) from e


def _platform_facts() -> dict:
    import jax

    return {
        "jax_version": jax.__version__,
        "platform": jax.default_backend(),
        "device_count": len(jax.devices()),
    }


def warm_bundle(
    path: str,
    *,
    max_batch: int = 32,
    dtypes: Sequence[str] = ("f32",),
    quant_bound: float | None = None,
) -> tuple[dict, dict]:
    """Pre-trace + compile the bundle's serving programs into
    ``<bundle>/warm/`` and return ``(warm_block, sha_entries)`` for the
    manifest.  Called by ``export_bundle(warm=True)`` on an
    already-committed (cold) bundle; the caller re-commits the manifest
    with the returned block.

    Replays the REAL load path for every requested dtype: bundle load
    (auxiliary programs included), the batcher's bucket-verification
    storm (the ladder compiles), the quantized divergence measurement
    when a non-f32 dtype is warmed, the batch-1 GEMV leg, and the
    single-observation predict program — each compiled under a scoped
    cache redirect so exactly these executables land in the bundle.
    """
    from ..utils.backend import scoped_compilation_cache

    path = os.path.abspath(path)
    warm_dir = os.path.join(path, WARM_DIR)
    shutil.rmtree(warm_dir, ignore_errors=True)  # re-export: start clean
    t0 = time.perf_counter()
    buckets: list[int] = []
    excluded: list[int] = []
    with scoped_compilation_cache(warm_dir):
        import jax

        # the exporting process (it just trained) holds in-memory
        # executables for many auxiliary programs; those would NOT
        # recompile during the replay and so would never land in the
        # warm dir — then a fresh serving process would miss exactly
        # them.  Clearing forces every program the load path touches
        # through the (redirected) persistent cache.
        jax.clear_caches()
        bundle = load_bundle(path)
        obs_shape = bundle.obs_shape
        if bundle.recurrent:
            # recurrent bundles serve in-process only (no batcher): warm
            # the single-predict program and be done
            bundle.predict(np.zeros(obs_shape, np.float32))
        else:
            for dtype in dtypes:
                b = build_serving_batcher(bundle, max_batch=max_batch,
                                          dtype=dtype,
                                          quant_bound=quant_bound)
                if dtype == "f32":
                    buckets = list(b.buckets)
                    excluded = list(b.buckets_excluded)
                b.close(drain=True, timeout=10.0)
            # the --max-batch 1 leg (GEMV family) and the in-process
            # Bundle.predict program
            bundle.batched_predict_fn()(
                np.zeros((1,) + obs_shape, np.float32))
            bundle.predict(np.zeros(obs_shape, np.float32))
    # prune: atime files are jax's read-bookkeeping, recreated harmlessly
    # in the INSTALLED copy — shipping them would put mutable state under
    # an immutability checksum
    for fname in os.listdir(warm_dir):
        if fname.endswith("-atime"):
            os.remove(os.path.join(warm_dir, fname))
    entries: dict[str, int] = {}
    shas: dict[str, str] = {}
    for fname in sorted(os.listdir(warm_dir)):
        fpath = os.path.join(warm_dir, fname)
        entries[fname] = os.path.getsize(fpath)
        shas[f"{WARM_DIR}/{fname}"] = _sha256_file(fpath)
    if not entries:
        raise BundleError(
            "warm export produced no cache entries — the persistent XLA "
            "compilation cache is not functional on this jax build"
        )
    block = {
        "format": "xla_cache",
        "max_batch": int(max_batch),
        "buckets": buckets,
        "buckets_excluded": excluded,
        "dtypes": list(dtypes),
        "warm_s": round(time.perf_counter() - t0, 3),
        "entries": entries,
        **_platform_facts(),
    }
    if bundle.recurrent:
        # only the single-predict program exists — the ladder-complete
        # structural check does not apply
        block["recurrent_only"] = True
    return block, shas


def install_warmth(path: str, manifest: dict) -> dict:
    """Install a bundle's packed warmth into this process's persistent
    compilation cache; returns a structured status dict (never raises on
    incompatibility — a stale-warmth bundle is still a valid bundle):

    ``{"installed": bool, "reason": str|None, "entries": int,
       "cache_dir": str|None, "jax_version": str, "platform": str}``

    Must run BEFORE the process's first jax compilation of the serving
    programs — the server calls it at the top of its engine build.
    Mismatched jax version or platform means the cache keys cannot hit;
    that is a finding (the doctor's warm probe reports it too), not an
    error, and the process simply compiles fresh.
    """
    warm = manifest.get("warm")
    if not isinstance(warm, dict):
        return {"installed": False, "reason": "no warmth packed",
                "entries": 0, "cache_dir": None}
    facts = _platform_facts()
    out = {"installed": False, "entries": 0, "cache_dir": None,
           "jax_version": warm.get("jax_version"),
           "platform": warm.get("platform")}
    if warm.get("format") != "xla_cache":
        out["reason"] = (f"unknown warmth format {warm.get('format')!r} — "
                         "this version installs only 'xla_cache'")
        return out
    if warm.get("jax_version") != facts["jax_version"]:
        out["reason"] = (
            f"warmth was built under jax {warm.get('jax_version')}, this "
            f"process runs {facts['jax_version']} — cache keys cannot "
            "match; ignoring warmth (re-export the bundle with warm=True "
            "under the serving jax version)")
        return out
    if warm.get("platform") != facts["platform"]:
        out["reason"] = (
            f"warmth was compiled for platform {warm.get('platform')!r}, "
            f"this process runs {facts['platform']!r} — executables are "
            "not portable across backends; ignoring warmth")
        return out
    from ..utils.backend import (_disable_path_dependent_cache_keys,
                                 current_compilation_cache_dir,
                                 enable_compilation_cache)

    cache_dir = current_compilation_cache_dir()
    if cache_dir is None:
        cache_dir = enable_compilation_cache()
    else:
        # a directory placed from outside (JAX_COMPILATION_CACHE_DIR) is
        # left where it is; the warm entries' keys were still computed
        # without the directory's path in them
        _disable_path_dependent_cache_keys()
        os.makedirs(cache_dir, exist_ok=True)
    warm_dir = os.path.join(os.path.abspath(path), WARM_DIR)
    n = 0
    for fname in warm.get("entries", {}):
        src = os.path.join(warm_dir, fname)
        dst = os.path.join(cache_dir, fname)
        if not os.path.exists(dst):
            shutil.copy2(src, dst)
        n += 1
    out["installed"] = True
    out["entries"] = n
    out["cache_dir"] = cache_dir
    if warm.get("device_count") != facts["device_count"]:
        out["note"] = (
            f"warmth was exported with {warm.get('device_count')} "
            f"device(s), this process has {facts['device_count']} — "
            "single-device serving programs usually still hit, but "
            "cross-process bit parity wants matching --cpu-devices anyway")
    return out
