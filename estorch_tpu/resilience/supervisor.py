"""Supervised auto-resume: the layer that keeps a run alive end to end.

Two granularities, composable:

* :func:`run_resilient` — **in-process** per-generation fault
  containment.  Wraps ``es.train(1)`` in a snapshot/restore loop: a
  generation that raises (dead env, checkpoint-write crash, injected
  chaos) is rolled back completely — state, generation counter, history,
  best-snapshot, meta-population/archive — counted
  (``generations_skipped``), and re-run.  Because the noise stream is
  derived from ``(key, generation)``, the re-run of a transient fault is
  bit-identical to a run that never faulted.  Bounded: persistent faults
  re-raise after ``max_consecutive_skips``.

* :class:`Supervisor` — **cross-process** restart-from-checkpoint.  The
  training loop runs in a child process (``spawn``: a fresh interpreter,
  so a parent's initialized JAX/torch runtime is never forked into the
  child); the parent watches child liveness two ways — exit status, and
  the heartbeat file (``ESTORCH_OBS_HEARTBEAT`` protocol,
  obs/recorder.py) for the silent-wedge case where the process is alive
  but stopped making progress.  On death or staleness it restarts the
  child with exponential backoff; the child resumes from
  ``PeriodicCheckpointer.latest()`` (the newest *finalized* payload — a
  crash mid-write cannot shadow the last good checkpoint).  Restart
  provenance (reason, exit code, last heartbeat, per-child counters)
  lands in the run manifest's ``resilience`` section, which
  ``python -m estorch_tpu.obs summarize`` surfaces.

The reference hangs forever when one worker dies mid-gather (SURVEY.md
§5); this module is the opposite contract: SIGKILL the whole run at any
point and the supervisor drives it to the same final parameters.
"""

from __future__ import annotations

import importlib
import json
import multiprocessing as mp
import os
import time

from ..obs.recorder import HEARTBEAT_ENV, STALE_AFTER_S, read_heartbeat
from . import chaos as _chaos


# ---------------------------------------------------------------------
# in-process: per-generation containment
# ---------------------------------------------------------------------

def _snapshot(es) -> dict:
    """Everything ``es.train(1)`` may mutate, cheap to capture (states are
    immutable NamedTuples; lists are shallow-copied).

    Param-sharded exception: the sharded engine DONATES its state, so a
    by-reference snapshot would hold buffers the very next generation
    deletes — the restore would hand back corpses ("buffer has been
    deleted or donated") instead of resuming.  Those states are deep-
    copied device-side (`.copy()` preserves each leaf's sharding); one
    extra state copy per generation is the price of rollback on the
    donated path, paid only under run_resilient.
    """
    state = es.state
    if getattr(es, "_shard_params", False):
        import jax

        state = jax.tree_util.tree_map(
            lambda x: x.copy() if hasattr(x, "copy") else x, state)
    snap = {
        "state": state,
        "generation": es.generation,
        "history_len": len(es.history),
        "best_reward": es.best_reward,
        "best_flat": es._best_flat,
    }
    if hasattr(es, "meta_states"):
        snap["meta_states"] = list(es.meta_states)
        snap["center_bc"] = list(es._center_bc)
    if hasattr(es, "archive"):
        snap["archive"] = es.archive.state_dict()
    if hasattr(es, "weight"):  # NSRA schedule
        snap["nsra"] = (es.weight, es._stagnation)
    return snap


def _restore(es, snap: dict) -> None:
    es.state = snap["state"]
    es.generation = snap["generation"]
    del es.history[snap["history_len"]:]
    es.best_reward = snap["best_reward"]
    es._best_flat = snap["best_flat"]
    if "meta_states" in snap:
        es.meta_states = list(snap["meta_states"])
        es._center_bc = list(snap["center_bc"])
    if "archive" in snap:
        from ..algo.archive import NoveltyArchive

        es.archive = NoveltyArchive.from_state_dict(snap["archive"])
    if "nsra" in snap:
        es.weight, es._stagnation = snap["nsra"]
    es.obs.discard_phases()  # partial spans of the aborted generation


def run_resilient(
    es,
    n_steps: int,
    n_proc: int = 1,
    log_fn=None,
    verbose: bool = False,
    checkpointer=None,
    max_skips: int = 16,
    max_consecutive_skips: int = 4,
):
    """Train ``n_steps`` generations, skipping (and re-running) any
    generation that raises instead of dying.

    ``checkpointer`` (a ``PeriodicCheckpointer``) is composed into the
    per-record callback, so a crash *inside a checkpoint save* rolls the
    just-finished generation back too — it re-runs deterministically and
    re-saves.  Returns ``es``.  Up to ``max_consecutive_skips``
    consecutive (and ``max_skips`` total) failed attempts are tolerated;
    one more re-raises — resilience must not become an infinite loop on
    a dead env.
    """
    target = es.generation + int(n_steps)
    consec = skips = 0

    def _log(record):
        if checkpointer is not None:
            checkpointer.on_record(record)
        if log_fn is not None:
            log_fn(record)

    while es.generation < target:
        # chaos process-level events key on the NEXT generation to run
        _chaos.process_wedge(es.generation)
        _chaos.process_kill(es.generation)
        snap = _snapshot(es)
        try:
            es.train(1, n_proc=n_proc, log_fn=_log, verbose=verbose)
        except Exception as e:  # noqa: BLE001 — containment IS the feature;
            # every skip is counted, recorded, and bounded below
            _restore(es, snap)
            skips += 1
            consec += 1
            es.obs.counters.inc("generations_skipped")
            es.obs.event("generation_skipped", gen=snap["generation"],
                         error=repr(e)[:200])
            if consec > max_consecutive_skips or skips > max_skips:
                raise
            continue
        consec = 0
    return es


# ---------------------------------------------------------------------
# cross-process: supervised restart from checkpoint
# ---------------------------------------------------------------------

def _resolve_factory(es_factory):
    """Accept a picklable callable or a ``"module:attr"`` spec string."""
    if isinstance(es_factory, str):
        mod, _, attr = es_factory.partition(":")
        if not attr:
            raise ValueError(
                f"factory spec {es_factory!r} must be 'module:attr'"
            )
        return getattr(importlib.import_module(mod), attr)
    return es_factory


def _generic_child_main(child_spec, child_args: tuple, root: str) -> None:
    """Child body for a generic supervised process (``child_target``):
    point the heartbeat into the supervision root, resolve the target in
    the CHILD (spec strings avoid pickling), run it.  The target owns its
    own platform policy — this runs in a spawned, fresh interpreter."""
    os.environ[HEARTBEAT_ENV] = os.path.join(root, "heartbeat.json")
    _resolve_factory(child_spec)(root, *child_args)


def _child_main(es_factory, root: str, target_generation: int, every: int,
                n_proc: int, verbose: bool) -> None:
    """Runs in the spawned child: build → resume from latest checkpoint →
    train resiliently to the target → final checkpoint."""
    # before the factory runs: ES reads the heartbeat path from the env at
    # construction, and the supervisor watches exactly this file
    os.environ[HEARTBEAT_ENV] = os.path.join(root, "heartbeat.json")
    es = _resolve_factory(es_factory)()

    from ..obs.sinks import JsonlSink
    from ..utils.checkpoint import PeriodicCheckpointer, restore_checkpoint

    # beat through the setup stretch: restore/manifest IO can take seconds
    # (orbax import, git-sha subprocess) and the staleness watchdog must
    # see progress, not a silent gap after the construction beat
    es.obs.note("supervisor_setup")
    ck = PeriodicCheckpointer(es, root, every=every)
    latest = ck.latest()
    if latest is not None:
        es.obs.note("supervisor_restore")
        restore_checkpoint(es, latest)
        es.obs.counters.inc("supervisor_resumes")
        es.obs.event("resumed_from_checkpoint", path=latest,
                     gen=es.generation)
    manifest_path = os.path.join(root, "manifest.json")
    if not os.path.exists(manifest_path):
        es.obs.note("supervisor_manifest")
        es.write_manifest(manifest_path)
    sink = JsonlSink(os.path.join(root, "run.jsonl"))
    try:
        if es.generation < target_generation:
            run_resilient(es, target_generation - es.generation,
                          n_proc=n_proc, log_fn=sink, verbose=verbose,
                          checkpointer=ck)
        if es.generation > 0:
            # final checkpoint regardless of `every` alignment (idempotent:
            # an existing directory for this generation is overwritten with
            # identical state)
            ck.save(es.generation - 1)
        ck.close()
    finally:
        sink.close()
        if hasattr(es.engine, "close"):
            es.engine.close()


class Supervisor:
    """Run training to ``target_generation`` with automatic restart.

    ``es_factory`` must be a picklable zero-arg callable (module-level
    function) or a ``"module:attr"`` spec — the child is *spawned* (fresh
    interpreter), never forked, so an initialized parent JAX runtime is
    not inherited mid-state.  The factory is also where platform policy
    belongs (e.g. ``force_cpu_backend`` before building the ES).

    The checkpoint directory ``ckpt_root`` is the unit of resumability:
    heartbeat, run JSONL, manifest, published counter totals
    (``counters.json``, scraped by the obs metrics sidecar), and
    ``gen_*`` checkpoints all live there, so a run's post-mortem is one
    directory.
    """

    def __init__(
        self,
        es_factory=None,
        ckpt_root: str = "",
        target_generation: int = 0,
        *,
        every: int = 5,
        n_proc: int = 1,
        max_restarts: int = 5,
        backoff_s: float = 0.5,
        backoff_max_s: float = 30.0,
        stale_after_s: float = STALE_AFTER_S,
        startup_grace_s: float = 120.0,
        poll_s: float = 0.5,
        verbose: bool = False,
        child_target=None,
        child_args: tuple = (),
    ):
        if (es_factory is None) == (child_target is None):
            raise ValueError(
                "pass exactly one of es_factory (training child) or "
                "child_target (generic supervised child)"
            )
        if not ckpt_root:
            raise ValueError("ckpt_root is required")
        self.es_factory = es_factory
        self.child_target = child_target
        self.child_args = tuple(child_args)
        self.ckpt_root = os.path.abspath(ckpt_root)
        self.target_generation = int(target_generation)
        self.every = int(every)
        self.n_proc = int(n_proc)
        self.max_restarts = int(max_restarts)
        self.backoff_s = float(backoff_s)
        self.backoff_max_s = float(backoff_max_s)
        self.stale_after_s = float(stale_after_s)
        self.startup_grace_s = float(startup_grace_s)
        self.poll_s = float(poll_s)
        self.verbose = bool(verbose)
        self.restarts: list[dict] = []
        self._counters_total: dict[str, float] = {}
        self._hists_total: dict[str, dict] = {}
        self._counters_through_ts = 0.0
        self._publish_error: str | None = None
        self._child = None
        self._stop_requested = False
        self._stop_signaled = False
        os.makedirs(self.ckpt_root, exist_ok=True)

    # ------------------------------------------------------------- paths

    @property
    def heartbeat_path(self) -> str:
        return os.path.join(self.ckpt_root, "heartbeat.json")

    @property
    def manifest_path(self) -> str:
        return os.path.join(self.ckpt_root, "manifest.json")

    def latest_checkpoint(self) -> str | None:
        from ..utils.checkpoint import latest_checkpoint

        return latest_checkpoint(self.ckpt_root)

    # --------------------------------------------------------------- run

    def run(self) -> dict:
        """Drive the run to completion; returns
        ``{"ok", "restarts", "checkpoint", "reason"}``."""
        import signal as _signal

        ctx = mp.get_context("spawn")
        attempt = 0
        ok = False
        reason = None
        while True:
            if self._stop_requested:
                # stop arrived during backoff: don't spawn a child only
                # to terminate it immediately
                ok = True
                break
            started = time.time()
            if self.child_target is not None:
                child = ctx.Process(
                    target=_generic_child_main,
                    args=(self.child_target, self.child_args,
                          self.ckpt_root),
                )
            else:
                child = ctx.Process(
                    target=_child_main,
                    args=(self.es_factory, self.ckpt_root,
                          self.target_generation, self.every, self.n_proc,
                          self.verbose),
                )
            child.start()
            self._child = child
            failure = self._watch(child, started)
            if failure is not None and not self._stop_requested:
                # record the restart BEFORE folding+publishing counters,
                # so the sidecar's restart_count gauge counts this death
                # for the whole next child's lifetime, not one publish late
                self.restarts.append({
                    "ts": time.time(),
                    "attempt": attempt,
                    "reason": failure,
                    "exitcode": child.exitcode,
                    "heartbeat": read_heartbeat(self.heartbeat_path),
                })
            self._accumulate_counters(started)
            if failure is None:
                ok = True
                break
            if self._stop_requested:
                # an operator stop is completion, not a crash to restart.
                # Clean when the child honored the forwarded SIGTERM
                # (exit 0 after drain) OR died BY that SIGTERM's default
                # disposition — a stop during startup lands before the
                # child installs its handler (several seconds of
                # jax import / bundle load), and that is still a normal
                # operator stop, not a crash to report
                ok = child.exitcode == 0 or (
                    self._stop_signaled
                    and child.exitcode == -int(_signal.SIGTERM))
                reason = None if ok else failure
                break
            attempt += 1
            if attempt > self.max_restarts:
                reason = failure
                break
            # exponential backoff: give a flapping environment (OOM killer,
            # a chip still held by the dying child) room to recover
            # instead of hammering it
            time.sleep(min(self.backoff_s * (2 ** (attempt - 1)),
                           self.backoff_max_s))
        self._write_provenance(ok)
        return {
            "ok": ok,
            "restarts": list(self.restarts),
            "checkpoint": self.latest_checkpoint(),
            "reason": reason,
        }

    def request_stop(self, signum: int | None = None) -> None:
        """Operator stop (signal-handler safe): forward SIGTERM to the
        running child so it can drain, and stop restarting.  The serving
        stack routes its own SIGTERM here (serve/server.py).

        The forward is sent EXACTLY ONCE (handlers and ``_watch`` both
        run on the main thread, so the flag needs no lock): a second
        SIGTERM can land after the child's drain, during interpreter
        finalization when its handler is already torn down — killing a
        cleanly-drained child with the default disposition (-15)."""
        del signum
        self._stop_requested = True
        child = self._child
        if child is not None and child.is_alive() and not self._stop_signaled:
            self._stop_signaled = True
            child.terminate()  # SIGTERM — graceful drain, not kill

    def _watch(self, child, started: float) -> str | None:
        """Block until the child exits or is killed for staleness.
        Returns None on clean (exit 0) completion, else a reason string."""
        while True:
            child.join(timeout=self.poll_s)
            if child.exitcode is not None:
                if child.exitcode == 0:
                    return None
                return (f"child died with exit code {child.exitcode}"
                        + (" (signal)" if child.exitcode < 0 else ""))
            if self._stop_requested and not self._stop_signaled:
                # stop raced past request_stop's terminate (child was
                # between start() and _child assignment): forward it here
                self._stop_signaled = True
                child.terminate()
            hb = read_heartbeat(self.heartbeat_path)
            if hb is not None and float(hb.get("ts", 0.0)) >= started:
                # this child has beaten at least once: staleness watchdog
                if hb["age_s"] > self.stale_after_s:
                    child.kill()
                    child.join(timeout=10)
                    return (f"heartbeat stale ({hb['age_s']:.0f}s > "
                            f"{self.stale_after_s:.0f}s) — killed wedged "
                            f"child (last phase={hb.get('phase')!r} "
                            f"gen={hb.get('generation')})")
            elif time.time() - started > self.startup_grace_s:
                # never beat: wedged in import/init (the known device
                # bring-up failure mode doctor.py documents)
                child.kill()
                child.join(timeout=10)
                return (f"no heartbeat within {self.startup_grace_s:.0f}s "
                        "of start — child wedged before init finished")

    def _accumulate_counters(self, started: float) -> None:
        """Fold the (just-exited) child's last-heartbeat counters into the
        cross-restart totals.  Per-child counters start at zero, so the
        sum over children is the run's true total — this is how a
        SIGKILLed child's ``generations_rejected`` survives its death.
        A beat older than this child's start is a PREVIOUS child's file
        (the child died before beating) — counting it again would
        double-count that child's totals.

        The totals are also PUBLISHED atomically (``counters.json`` in
        the run dir, obs/export/sidecar.py) so the metrics sidecar can
        keep answering scrapes with monotone totals across the restart:
        the published ``through_ts`` tells the sidecar which heartbeat
        is already folded in, so a dead child's final beat is never
        counted twice."""
        hb = read_heartbeat(self.heartbeat_path)
        if hb is not None and float(hb.get("ts", 0.0)) >= started:
            for name, val in (hb.get("counters") or {}).items():
                if isinstance(val, (int, float)):
                    self._counters_total[name] = (
                        self._counters_total.get(name, 0) + val
                    )
            if isinstance(hb.get("hists"), dict):
                # latency DISTRIBUTIONS survive the child the same way
                # its sums do: bucket-wise fold (obs/hist.py)
                from ..obs.hist import merge_snapshots

                self._hists_total = merge_snapshots(
                    self._hists_total, hb["hists"])
            self._counters_through_ts = float(hb.get("ts", 0.0))
        # publish even when this child never beat (wedged import killed
        # by the startup grace): the restart_count the sidecar scrapes
        # must count that death too, not wait for a later child's beat
        self._publish_counters(through_ts=self._counters_through_ts)

    def _publish_counters(self, through_ts: float,
                          completed: bool | None = None) -> None:
        from ..obs.export.sidecar import publish_counters

        extra: dict = {"restart_count": len(self.restarts)}
        if completed is not None:
            extra["completed"] = completed
        try:
            publish_counters(self.ckpt_root, self._counters_total,
                             through_ts, extra=extra,
                             hists=self._hists_total or None)
            self._publish_error = None
        except OSError as e:
            # best-effort observability: a full disk must not become a
            # supervision failure — but the evidence rides the manifest
            self._publish_error = repr(e)

    def _write_provenance(self, ok: bool) -> None:
        """Merge restart provenance into the run manifest (atomic write —
        readers racing a restart never see a partial file)."""
        # final published snapshot FIRST: scrapes after the run ends get
        # the full cross-restart totals + completion verdict, and a
        # publish failure here still lands in the manifest written below
        # ("the evidence rides the manifest" — it can't if the manifest
        # is already closed)
        self._publish_counters(through_ts=self._counters_through_ts,
                               completed=ok)
        data: dict = {}
        try:
            with open(self.manifest_path) as f:
                data = json.load(f)
        except (OSError, ValueError):
            data = {}  # child died before writing one: provenance-only file
        data["resilience"] = {
            "target_generation": self.target_generation,
            "completed": ok,
            "restart_count": len(self.restarts),
            "restarts": self.restarts,
            "counters": dict(self._counters_total),
        }
        if self._publish_error:
            data["resilience"]["counters_publish_error"] = \
                self._publish_error
        tmp = self.manifest_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(data, f, indent=2, default=float)
        os.replace(tmp, self.manifest_path)
