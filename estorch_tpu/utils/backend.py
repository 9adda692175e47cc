"""Backend helpers: the CPU test mesh, the persistent compilation cache,
XLA build counters.

JAX picks the platform the usual way (``JAX_PLATFORMS``, else the best
backend installed: the TPU on the chip machine).  The virtual 8-device
CPU mesh the tests and dry runs use is an explicit request
(:func:`force_cpu_backend`), made before first device use.
"""

from __future__ import annotations

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def default_compilation_cache_dir() -> str:
    """Where the compile cache lives when ``JAX_COMPILATION_CACHE_DIR``
    does not place it: one fixed, git-ignored directory in the checkout.
    Fixed because the path is part of what a process must agree on to
    share entries; in the checkout because nothing under ``$HOME``
    outlives a run on a throwaway machine."""
    return os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".xla_cache")


def enable_compilation_cache(
    cache_dir: str | None = None, min_compile_time_s: float = 1.0
) -> str:
    """Turn on XLA's persistent compilation cache and return the directory.

    A fresh process pays the XLA compile of the fused generation
    program before the first update.  With the persistent cache, every
    process after the first loads the compiled executable from disk —
    across bench stages, example scripts, pool workers, and restarts
    after a crash (the checkpoint/resume story's missing half).

    Placement: where ``JAX_COMPILATION_CACHE_DIR`` is set, jax has
    already read it and that directory is THE cache — ``cache_dir`` is
    ignored and no directory is set in code.  Otherwise ``cache_dir``,
    else :func:`default_compilation_cache_dir`.

    ``min_compile_time_s`` gates which programs are worth persisting
    (default 1s — the tiny host-side jits stay out of the cache).  Safe to
    call before OR after backend init, and re-callable with a new
    directory: JAX pins its cache object on first use and never re-reads
    the dir config, so a dir change must also reset the live cache (done
    here) or it would silently keep using the old path.

    CPU caveat: XLA:CPU AOT entries record exact machine features; the
    loader logs noisy E-level feature-mismatch warnings (observed even
    same-machine for XLA-internal pseudo-features like
    ``+prefer-no-scatter``) and a cache shared ACROSS heterogeneous CPUs
    could in principle hit SIGILL — keep the cache directory per-machine.
    TPU executables key on the chip generation and have no such edge.
    """
    import jax

    placed = os.environ.get(CACHE_DIR_ENV)
    if placed:
        cache_dir = placed
    else:
        if cache_dir is None:
            cache_dir = default_compilation_cache_dir()
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    os.makedirs(cache_dir, exist_ok=True)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", float(min_compile_time_s)
    )
    # -1: no size floor AND no filesystem-specific override (the default 0
    # permits an override that can skip small entries on some filesystems)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    _disable_path_dependent_cache_keys()
    _reset_live_cache()
    return cache_dir


def _disable_path_dependent_cache_keys() -> None:
    """Keep cache keys independent of the cache DIRECTORY's path.

    With the persistent cache enabled, jax default-enables
    auxiliary XLA caches whose path — derived from the cache dir — lands
    in ``debug_options`` and is hashed into every cache key
    (``xla_gpu_per_fusion_autotune_cache_dir`` is not on the cache-key
    sanitizer's clear list).  That makes entries non-portable: a warm
    bundle's programs (compiled under ``<bundle>/warm``) could never hit
    from the serving process's cache dir.  The auxiliary caches are
    GPU-only machinery (fusion autotuning), nothing lost on cpu/tpu."""
    import jax

    jax.config.update("jax_persistent_cache_enable_xla_caches", "")


def _reset_live_cache() -> None:
    """Drop JAX's already-initialized persistent-cache object (if any) so
    the dir config takes effect; harmless when nothing was initialized."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    cc.reset_cache()


def current_compilation_cache_dir() -> str | None:
    """The persistent-cache directory this process is configured with, or
    None when the cache is disabled (the default outside conftest)."""
    import jax

    return jax.config.jax_compilation_cache_dir or None


def scoped_compilation_cache(cache_dir: str, min_compile_time_s: float = 0.0):
    """Context manager: redirect the persistent XLA compilation cache to
    ``cache_dir`` for the duration of the block, then restore the prior
    configuration (including "disabled").

    ``min_compile_time_s=0`` persists EVERY program compiled inside the
    block — the warm-bundle export wants the tiny auxiliary programs
    (``convert_element_type``, ``broadcast_in_dim``, …) too, because a
    "zero fresh builds at load" proof fails on any program left out.
    Process-global (jax config is), so don't run concurrent exports.
    """
    import contextlib

    import jax

    @contextlib.contextmanager
    def _scope():
        prior_dir = current_compilation_cache_dir()
        prior_min = jax.config.jax_persistent_cache_min_compile_time_secs
        prior_size = jax.config.jax_persistent_cache_min_entry_size_bytes
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          float(min_compile_time_s))
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        # portability is the POINT of the warm export: keys must not
        # depend on where the cache dir happens to live
        _disable_path_dependent_cache_keys()
        _reset_live_cache()
        try:
            yield cache_dir
        finally:
            jax.config.update("jax_compilation_cache_dir", prior_dir or "")
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", prior_min)
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", prior_size)
            _reset_live_cache()

    return _scope()


# --------------------------------------------------------------- XLA builds
#
# Cold start is made of XLA executable builds, and proving a warm bundle
# works means COUNTING them: jax's monitoring stream emits
# ``/jax/core/compile/backend_compile_duration`` once per executable
# ACQUISITION (fresh build or persistent-cache retrieval — pxla wraps
# ``compile_or_get_cached`` in it) and ``/jax/compilation_cache/cache_hits``
# once per retrieval, so ``fresh = programs - cache_hits`` holds whether or
# not a persistent cache is configured.  The serve server snapshots these
# around bundle load to publish ``compiles_at_load`` / ``warm_cache_hits``.

_COMPILE_EVENT_COUNTS = {"programs": 0, "cache_hits": 0, "build_s": 0.0}
_COMPILE_COUNTERS_INSTALLED = False

# Besides the counts, every event of an executable's acquisition with its
# name and its interval: (kind, fun_name, end on ``time.perf_counter``
# stamped in the callback, duration, cache hit or None).  ``trace`` and
# ``lower`` are jax's tracing of a function to a jaxpr and the jaxpr's
# lowering to an MLIR module; ``backend`` the acquisition itself (a
# retrieval from the persistent cache or a build, and the load), with
# whether a cache hit came with it; ``retrieval`` the cache's own read
# inside a hit, which carries no name.  Bounded: past
# ``ACQUISITION_LOG_CAP`` entries an event is counted and not kept.
ACQUISITION_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "backend",
    "/jax/compilation_cache/cache_retrieval_time_sec": "retrieval",
}
ACQUISITION_LOG_CAP = 32768
_ACQUISITION_LOG: list[tuple] = []
_ACQUISITION_DROPPED = [0]


def install_compile_event_counters() -> None:
    """Idempotently register jax monitoring listeners feeding
    :func:`compile_event_counts` and :func:`acquisition_log`."""
    global _COMPILE_COUNTERS_INSTALLED
    if _COMPILE_COUNTERS_INSTALLED:
        return
    import threading
    import time

    from jax._src import monitoring

    # a hit is announced before the acquisition that it belongs to ends,
    # on the thread that acquires
    pending = threading.local()

    def _on_event(event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            _COMPILE_EVENT_COUNTS["cache_hits"] += 1
            pending.hit = True

    def _on_duration(event, duration, **kw):
        kind = ACQUISITION_KINDS.get(event)
        if kind is None:
            return
        end = time.perf_counter()
        hit = None
        if kind == "backend":
            _COMPILE_EVENT_COUNTS["programs"] += 1
            _COMPILE_EVENT_COUNTS["build_s"] += float(duration)
            hit = getattr(pending, "hit", False)
            pending.hit = False
        if len(_ACQUISITION_LOG) < ACQUISITION_LOG_CAP:
            _ACQUISITION_LOG.append(
                (kind, kw.get("fun_name"), end, float(duration), hit))
        else:
            _ACQUISITION_DROPPED[0] += 1

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    _COMPILE_COUNTERS_INSTALLED = True


def compile_event_counts() -> dict:
    """Point-in-time copy of the build counters: ``programs`` (executable
    acquisitions), ``cache_hits`` (persistent-cache retrievals among
    them), ``build_s`` (wall seconds in acquisition — retrievals included,
    they are milliseconds).  Delta two snapshots around a load to get the
    load's fresh-build count: ``(programs - cache_hits)`` after minus
    before."""
    return dict(_COMPILE_EVENT_COUNTS)


def acquisition_log() -> list[tuple]:
    """Point-in-time copy of the acquisition events the listener kept:
    ``(kind, fun_name, end, duration, cache_hit)``, ``end`` on
    ``time.perf_counter``, in the order they ended."""
    return list(_ACQUISITION_LOG)


def last_acquisition(since: float = 0.0) -> dict:
    """The newest ``backend`` event that ended after ``since`` (perf
    counter), as the facts a compile-ledger entry carries; ``{}`` where
    the listener kept none."""
    for kind, fun_name, end, duration, hit in reversed(_ACQUISITION_LOG):
        if end < since:
            break
        if kind == "backend":
            return {"fun_name": fun_name, "acquire_s": round(duration, 6),
                    "cache_hit": bool(hit)}
    return {}


def acquisition_summary() -> dict:
    """What the acquisitions so far came to: how many executables, how
    many of them cache hits, the seconds by kind, and the costliest five
    by name (a generation record's ``"setup"["acquisitions"]``)."""
    log = list(_ACQUISITION_LOG)
    if not log:
        return {}
    by_kind = {kind: 0.0 for kind in ACQUISITION_KINDS.values()}
    for kind, _, _, duration, _ in log:
        by_kind[kind] += duration
    backend = [e for e in log if e[0] == "backend"]
    named = sorted((e for e in log if e[0] != "retrieval"),
                   key=lambda e: -e[3])[:5]
    out = {
        "programs": len(backend),
        "cache_hits": sum(1 for e in backend if e[4]),
        **{f"{kind}_s": round(s, 6) for kind, s in by_kind.items()},
        "costliest": [{"kind": kind, "fun_name": fun_name,
                       "dur_s": round(duration, 6)}
                      for kind, fun_name, _, duration, _ in named],
    }
    if _ACQUISITION_DROPPED[0]:
        out["events_dropped"] = _ACQUISITION_DROPPED[0]
    return out


def enable_cpu_gloo_collectives() -> None:
    """Route CPU cross-process collectives through Gloo — required for
    ``jax.distributed`` multi-process runs on the CPU backend (the
    default CPU client answers any cross-process psum with
    "Multiprocess computations aren't implemented").  Only effective
    before the backend initializes."""
    import jax

    jax.config.update("jax_cpu_collectives_implementation", "gloo")


def force_cpu_backend(n_devices: int = 8) -> bool:
    """Switch to the CPU backend with ``n_devices`` virtual devices.
    Returns True if the config took; False if the backend was already
    initialized (caller proceeds with whatever is live)."""
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", max(int(n_devices), 1))
    except RuntimeError:
        return False
    return True
