"""Checkpoint / resume — exact-state persistence via Orbax.

The reference has NOTHING here: users ``torch.save`` the policy state_dict
by hand and lose optimizer moments, RNG position, the novelty archive, and
the NSRA weight (SURVEY.md §5 'Checkpoint / resume').  estorch_tpu
checkpoints the FULL algorithm state, so resume is bit-exact: the noise
stream is derived from ``(key, generation)``, hence restoring those two plus
params/optimizer reproduces the run as if never interrupted.

Layout of a checkpoint directory:
- ``state/``    — Orbax tree of all numeric state (params, optax state, rng
                  key, generation counters, best snapshot, archive BCs,
                  meta-population centers)
- ``meta.json`` — strings/flags (backend, algo, config echo, NSRA scalars)
- ``host_opt.pt`` — host backend only: torch optimizer state_dicts
                  (torch-native serialization, one per center)
"""

from __future__ import annotations

import json
import os
from typing import Any

import numpy as np


def _np(x):
    return np.asarray(x)


def _pack_state(es, st) -> dict:
    """Numeric-only view of one engine state (device ESState or HostState)."""
    d = {
        "params_flat": _np(st.params_flat),
        "generation": int(st.generation),
    }
    # host states may carry the None sentinel (pre-sigma-field, engine falls
    # back to its init σ) — persist that fallback value, not a crash
    d["sigma"] = float(es.engine.sigma if st.sigma is None else st.sigma)
    if es.backend == "host":
        d["key"] = int(st.key)
    else:
        d["key"] = _np(st.key)
        d["opt_state"] = _to_numpy_tree(st.opt_state)
        if getattr(st, "obs_stats", None) is not None:
            d["obs_stats"] = _to_numpy_tree(st.obs_stats)
    return d


def _to_numpy_tree(tree: Any) -> Any:
    import jax

    return jax.tree_util.tree_map(_np, tree)


def _all_states(es) -> list:
    return list(es.meta_states) if hasattr(es, "meta_states") else [es.state]


def _state_tree(es) -> dict:
    """The numeric state tree (Orbax-safe: arrays/ints/floats only)."""
    tree = {
        "generation": int(es.generation),
        "best_reward": float(es.best_reward) if np.isfinite(es.best_reward) else -1e30,
        "has_best": int(es._best_flat is not None),
        "best_flat": (
            _np(es._best_flat)
            if es._best_flat is not None
            else np.zeros(0, np.float32)
        ),
        "states": [_pack_state(es, s) for s in _all_states(es)],
    }
    if hasattr(es, "archive"):
        tree["archive_bcs"] = es.archive.bcs
        tree["center_bc"] = [_np(b) for b in es._center_bc]
    return tree


CHECKPOINT_FORMAT_VERSION = 3  # v3: HOST states carry annealable sigma too
# (v2 added it to device states only)


def _meta_dict(es) -> dict:
    meta = {
        "format_version": CHECKPOINT_FORMAT_VERSION,
        "backend": es.backend,
        "algo": type(es).__name__,
        "population_size": es.population_size,
        "sigma": es.sigma,
        "seed": es.seed,
        "generation": int(es.generation),
        "history_len": len(es.history),
        # state-SCHEMA flag: obs_norm adds obs_stats to every device state;
        # restoring across a mismatch would otherwise fail deep inside
        # Orbax (template mismatch) or silently drop the stats
        "obs_norm": bool(getattr(es, "_obs_norm", False)),
    }
    if hasattr(es, "archive"):
        meta["archive_k"] = es.archive.k
        meta["archive_bc_dim"] = es.archive.bc_dim
        meta["archive_max_size"] = es.archive.max_size
    if hasattr(es, "weight"):  # NSRA
        meta["nsra_weight"] = float(es.weight)
        meta["nsra_stagnation"] = int(es._stagnation)
    if hasattr(es, "_rng"):
        # meta-selection RNG position — without it a resumed novelty run
        # picks different meta-individuals than the uninterrupted run
        meta["meta_rng_state"] = es._rng.bit_generator.state
    return meta


class AsyncSaveHandle:
    """Returned by ``save_checkpoint(..., asynchronous=True)``: the array
    write continues in Orbax's background thread while training proceeds.
    Call :meth:`wait` (idempotent) before restoring from the path or
    exiting the process."""

    def __init__(self, ckptr, owned: bool = True):
        self._ckptr = ckptr
        self._owned = owned  # shared checkpointers (PeriodicCheckpointer)
        # are closed by their owner, not per-save
        self._done = False

    def wait(self) -> None:
        if not self._done:
            self._ckptr.wait_until_finished()
            if self._owned:
                self._ckptr.close()
            self._done = True


def save_checkpoint(es, path: str, asynchronous: bool = False,
                    _async_ckptr=None):
    """Write a complete checkpoint of ``es`` to directory ``path``.

    ``asynchronous=True``: the device→disk array write happens in Orbax's
    background thread, so on a real accelerator the training loop is not
    blocked for the save's disk time (JAX snapshots the on-device values
    at save-call time — later training steps cannot corrupt the write).
    Returns an :class:`AsyncSaveHandle`; call ``.wait()`` before restoring
    or process exit.  Synchronous saves return ``None``.
    """
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    os.makedirs(path, exist_ok=True)
    # sidecar files FIRST, Orbax payload LAST: the finalized state/ dir is
    # the commit point (Orbax writes to a tmp dir and renames), so a crash
    # at ANY earlier moment leaves a directory that latest_checkpoint()
    # skips — never a restorable-looking checkpoint missing its sidecars
    with open(os.path.join(path, "meta.json"), "w") as f:
        json.dump(_meta_dict(es), f, indent=2)
    # per-generation records survive resume (meta's history_len cross-checks)
    with open(os.path.join(path, "history.json"), "w") as f:
        json.dump(es.history, f)
    if es.backend == "host":
        import torch

        torch.save(
            [s.opt_state for s in _all_states(es)],
            os.path.join(path, "host_opt.pt"),
        )
    # deterministic chaos: a scheduled mid-checkpoint-write crash lands
    # exactly here — sidecars written, payload not finalized
    from ..resilience.chaos import crash_checkpoint

    crash_checkpoint(es.generation)
    if asynchronous:
        # _async_ckptr: a long-lived checkpointer supplied by the caller
        # (PeriodicCheckpointer) — Orbax's intended reuse pattern; a bare
        # call gets its own, closed by the handle's wait()
        ckptr = _async_ckptr or ocp.AsyncCheckpointer(
            ocp.StandardCheckpointHandler()
        )
        ckptr.save(
            os.path.join(path, "state"),
            args=ocp.args.StandardSave(_state_tree(es)),
            force=True,
        )
        return AsyncSaveHandle(ckptr, owned=_async_ckptr is None)
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(os.path.join(path, "state"), _state_tree(es), force=True)
    ckptr.wait_until_finished()
    return None


def restore_checkpoint(es, path: str) -> None:
    """Restore ``es`` in place from a checkpoint written by save_checkpoint.

    ``es`` must be constructed with the same configuration (policy, agent,
    optimizer, population, sigma, seed) — the standard JAX restore pattern:
    rebuild the program, then load the state.
    """
    import orbax.checkpoint as ocp

    path = os.path.abspath(path)
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    version = meta.get("format_version", 0)
    # v3 only added sigma to HOST states; a v2 DEVICE/POOLED checkpoint's
    # payload is byte-identical to v3 and remains loadable
    v2_compatible = version == 2 and meta.get("backend") != "host"
    if version != CHECKPOINT_FORMAT_VERSION and not v2_compatible:
        raise ValueError(
            f"checkpoint format v{version} != supported "
            f"v{CHECKPOINT_FORMAT_VERSION} (older states lack the annealable "
            "sigma field — v2 device-only, v3 all backends); re-save from "
            "the run that wrote it"
        )
    if meta["backend"] != es.backend:
        raise ValueError(
            f"checkpoint backend {meta['backend']!r} != this object's {es.backend!r}"
        )
    if meta["algo"] != type(es).__name__:
        raise ValueError(
            f"checkpoint algo {meta['algo']!r} != this object's {type(es).__name__!r}"
        )
    # schema gate: obs_norm changes every device state's shape (obs_stats).
    # Checkpoints from before the flag existed lack the key → treated as
    # written with obs_norm off.
    ck_obs_norm = bool(meta.get("obs_norm", False))
    es_obs_norm = bool(getattr(es, "_obs_norm", False))
    if ck_obs_norm != es_obs_norm:
        raise ValueError(
            f"checkpoint was written with obs_norm={ck_obs_norm} but this "
            f"object was constructed with obs_norm={es_obs_norm} — rebuild "
            "with the matching setting (the running obs stats are part of "
            f"training state), e.g. pass obs_norm={ck_obs_norm} to the "
            "constructor or config recipe (humanoid2d_device/_pop10k "
            "default obs_norm=True since round 4; older checkpoints need "
            "the explicit obs_norm=False override)"
        )

    # An async save writes meta.json immediately while the Orbax array
    # drain runs in the background (Orbax writes to a tmp dir and renames
    # on finalize) — so a path can pass every meta/schema check above and
    # still have no restorable payload.  Catch it here with a clear error
    # instead of a deep Orbax FileNotFoundError.
    state_dir = os.path.join(path, "state")
    if not os.path.isdir(state_dir):
        raise ValueError(
            f"checkpoint at {path!r} has no finalized state/ payload — "
            "an async save is still draining (call handle.wait() / "
            "PeriodicCheckpointer.wait() first) or the write crashed "
            "mid-save; use PeriodicCheckpointer.latest() to find the "
            "newest restorable checkpoint"
        )

    ckptr = ocp.StandardCheckpointer()
    tree = ckptr.restore(state_dir, _state_tree(es))

    es.generation = int(tree["generation"])
    br = float(tree["best_reward"])
    es.best_reward = -np.inf if br <= -1e29 else br
    es._best_flat = _np(tree["best_flat"]) if int(tree["has_best"]) else None

    hist_path = os.path.join(path, "history.json")
    if os.path.exists(hist_path):  # absent in pre-round-2 checkpoints
        with open(hist_path) as f:
            es.history = json.load(f)
        if len(es.history) != meta.get("history_len", len(es.history)):
            import warnings

            warnings.warn(
                f"checkpoint history.json holds {len(es.history)} records "
                f"but meta.json recorded {meta['history_len']} — the "
                "checkpoint write was likely interrupted; records may be "
                "stale/partial (numeric state is unaffected)",
                stacklevel=2,
            )

    host_opts = None
    if es.backend == "host":
        import torch

        host_opts = torch.load(
            os.path.join(path, "host_opt.pt"), weights_only=False
        )

    states = [
        _unpack_state(es, packed, None if host_opts is None else host_opts[i])
        for i, packed in enumerate(tree["states"])
    ]
    if hasattr(es, "meta_states"):
        es.meta_states = states
    es.state = states[0]

    if hasattr(es, "archive"):
        from ..algo.archive import NoveltyArchive

        es.archive = NoveltyArchive.from_state_dict(
            {
                "k": meta["archive_k"],
                "bc_dim": meta["archive_bc_dim"],
                "max_size": meta.get("archive_max_size", 0),
                "bcs": _np(tree["archive_bcs"]),
            }
        )
        es._center_bc = [_np(b) for b in tree["center_bc"]]
    if "nsra_weight" in meta and hasattr(es, "weight"):
        es.weight = float(meta["nsra_weight"])
        es._stagnation = int(meta["nsra_stagnation"])
    if "meta_rng_state" in meta and hasattr(es, "_rng"):
        es._rng = np.random.default_rng()
        es._rng.bit_generator.state = meta["meta_rng_state"]


def _unpack_state(es, packed: dict, host_opt=None):
    if es.backend == "host":
        from ..host.engine import HostState

        return HostState(
            params_flat=_np(packed["params_flat"]).astype(np.float32),
            opt_state=host_opt,
            key=int(packed["key"]),
            generation=int(packed["generation"]),
            sigma=float(packed["sigma"]),
        )
    import jax.numpy as jnp

    from ..parallel.engine import ESState, replicate_on_mesh

    obs_stats = packed.get("obs_stats")
    if obs_stats is not None:
        obs_stats = tuple(
            jnp.asarray(x, jnp.float32) for x in obs_stats
        )
    return replicate_on_mesh(ESState(
        params_flat=jnp.asarray(packed["params_flat"]),
        opt_state=packed["opt_state"],
        key=jnp.asarray(packed["key"]),
        generation=jnp.int32(packed["generation"]),
        sigma=jnp.float32(packed["sigma"]),
        obs_stats=obs_stats,
    ), es.mesh)


def latest_checkpoint(root: str) -> str | None:
    """Newest checkpoint under ``root`` whose Orbax payload is FINALIZED.

    An async save mid-drain, or a crash mid-write, leaves meta.json
    without a ``state/`` dir (Orbax writes to a tmp dir and renames on
    finalize) — such a directory must not shadow the older restorable
    one.  Module-level so supervisors (resilience/supervisor.py) can find
    the resume point without constructing an ES first."""
    try:
        cks = sorted(d for d in os.listdir(root) if d.startswith("gen_"))
    except OSError:
        return None
    for d in reversed(cks):
        if os.path.isdir(os.path.join(root, d, "state")):
            return os.path.join(root, d)
    return None


class PeriodicCheckpointer:
    """Save every K generations; keeps the newest ``max_to_keep`` checkpoints.

    Usage (composes with train's log_fn):
        ck = PeriodicCheckpointer(es, "ckpts", every=10)
        es.train(100, log_fn=ck.on_record)
    """

    def __init__(self, es, root: str, every: int = 10, max_to_keep: int = 3,
                 asynchronous: bool = False):
        self.es = es
        self.root = os.path.abspath(root)
        self.every = int(every)
        self.max_to_keep = int(max_to_keep)
        # asynchronous: each save's array write drains in Orbax's
        # background thread while training continues; the previous save is
        # awaited before the next one starts (at most one write in flight),
        # and ONE long-lived AsyncCheckpointer serves every save
        self.asynchronous = bool(asynchronous)
        self._pending = None
        self._ckptr = None
        if self.asynchronous:
            import orbax.checkpoint as ocp

            self._ckptr = ocp.AsyncCheckpointer(
                ocp.StandardCheckpointHandler()
            )
        os.makedirs(self.root, exist_ok=True)

    def on_record(self, record: dict) -> None:
        gen = record["generation"]
        if (gen + 1) % self.every == 0:
            self.save(gen)

    def save(self, gen: int) -> str:
        self.wait()
        path = os.path.join(self.root, f"gen_{gen:08d}")
        self._pending = save_checkpoint(
            self.es, path, asynchronous=self.asynchronous,
            _async_ckptr=self._ckptr,
        )
        if self._pending is None:
            self._gc()  # sync save: already durable
        # async: GC is DEFERRED to wait() — collecting now could delete the
        # last durable checkpoint while this one is still draining, leaving
        # nothing restorable if the process dies mid-write
        return path

    def wait(self) -> None:
        """Block until the in-flight async save (if any) is durable, then
        collect stale checkpoints.  Called automatically before each new
        save; call it yourself before reading ``latest()`` or exiting."""
        if self._pending is not None:
            self._pending.wait()
            self._pending = None
            self._gc()

    def close(self) -> None:
        """Drain the in-flight save and release the async checkpointer."""
        self.wait()
        if self._ckptr is not None:
            self._ckptr.close()
            self._ckptr = None

    def latest(self) -> str | None:
        """Newest restorable checkpoint (see :func:`latest_checkpoint`)."""
        return latest_checkpoint(self.root)

    def _gc(self) -> None:
        import shutil

        cks = sorted(d for d in os.listdir(self.root) if d.startswith("gen_"))
        for stale in cks[: -self.max_to_keep]:
            shutil.rmtree(os.path.join(self.root, stale), ignore_errors=True)
