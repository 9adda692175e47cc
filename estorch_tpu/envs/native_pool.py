"""ctypes bindings for the C++ envpool (+ NumPy fallback).

The native host env-stepper (estorch_tpu/native/envpool.cpp) replaces the
reference's per-process Python rollout workers for host-env configs: N envs
step in parallel C++ threads while the TPU runs the batched policy forward.
If the shared library is missing, it is built on demand with ``make``; if no
compiler is available, a NumPy vectorized fallback with identical semantics
(auto-reset on done, same dynamics) keeps everything functional.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import Optional

import numpy as np

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "native")
_LIB_PATH = os.path.abspath(os.path.join(_NATIVE_DIR, "libenvpool.so"))

ENV_IDS = {"cartpole": 0, "pendulum": 1, "pong84": 2}
# policy-facing observation shape; differs from the flat buffer for pixels
_OBS_SHAPES = {0: (4,), 1: (3,), 2: (84, 84, 1)}
_OBS_DIMS = {k: int(np.prod(v)) for k, v in _OBS_SHAPES.items()}
_ACT_DIMS = {0: 1, 1: 1, 2: 1}
_DISCRETE = {0: True, 1: False, 2: True}
_N_ACTIONS = {0: 2, 1: 0, 2: 3}  # discrete action count (0 = continuous)
_NUMPY_FALLBACK_IDS = (0, 1)  # envs _NumpyPool actually implements


def env_spec(env_name: str) -> dict:
    """Static facts about a pool env — no pool construction needed."""
    if env_name not in ENV_IDS:
        raise ValueError(f"unknown env {env_name!r}; available: {sorted(ENV_IDS)}")
    eid = ENV_IDS[env_name]
    return {
        "env_id": eid,
        "obs_dim": _OBS_DIMS[eid],
        "obs_shape": _OBS_SHAPES[eid],
        "act_dim": _ACT_DIMS[eid],
        "discrete": _DISCRETE[eid],
        "n_actions": _N_ACTIONS[eid],
    }


def _stale(lib_path: str) -> bool:
    src = os.path.join(_NATIVE_DIR, "envpool.cpp")
    try:
        return os.path.getmtime(lib_path) < os.path.getmtime(src)
    except OSError:
        return True


def _load_library() -> Optional[ctypes.CDLL]:
    if not os.path.exists(_LIB_PATH) or _stale(_LIB_PATH):
        # serialize concurrent builders (fork workers, parallel test runs):
        # without the lock two `make -B` runs race and one process can load
        # a partially-written .so; under the lock the loser re-checks and
        # finds the winner's fresh library
        lock_path = os.path.join(os.path.abspath(_NATIVE_DIR), ".build.lock")
        try:
            import fcntl  # POSIX-only; ImportError lands in the fallback path

            with open(lock_path, "w") as lk:
                fcntl.flock(lk, fcntl.LOCK_EX)
                if not os.path.exists(_LIB_PATH) or _stale(_LIB_PATH):
                    subprocess.run(
                        ["make", "-C", os.path.abspath(_NATIVE_DIR), "-B"],
                        check=True,
                        capture_output=True,
                        timeout=120,
                    )
        except (subprocess.SubprocessError, ImportError, OSError):
            # no build, no library: a .so older than the tracked
            # envpool.cpp implements some OTHER version of the envs, and
            # loading it would pass every shape check while stepping
            # different dynamics
            return None
    try:
        lib = ctypes.CDLL(_LIB_PATH)
    except OSError:
        return None
    lib.envpool_create.restype = ctypes.c_void_p
    lib.envpool_create.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
    ]
    lib.envpool_destroy.argtypes = [ctypes.c_void_p]
    lib.envpool_obs_dim.argtypes = [ctypes.c_void_p]
    lib.envpool_obs_dim.restype = ctypes.c_int
    lib.envpool_act_dim.argtypes = [ctypes.c_void_p]
    lib.envpool_act_dim.restype = ctypes.c_int
    f32p = ctypes.POINTER(ctypes.c_float)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.envpool_reset.argtypes = [ctypes.c_void_p, f32p]
    lib.envpool_step.argtypes = [ctypes.c_void_p, f32p, f32p, f32p, u8p]
    return lib


_LIB: Optional[ctypes.CDLL] = None
_LIB_TRIED = False


def _get_lib() -> Optional[ctypes.CDLL]:
    global _LIB, _LIB_TRIED
    if not _LIB_TRIED:
        _LIB = _load_library()
        _LIB_TRIED = True
    return _LIB


class NativeEnvPool:
    """N batched envs stepped by the C++ thread pool (NumPy fallback inside).

    API (all arrays are (n_envs, ...) float32):
        obs = pool.reset()
        obs, rew, done = pool.step(actions)   # auto-resets finished envs
    """

    def __init__(self, env: str, n_envs: int, n_threads: int = 0, seed: int = 0):
        if env not in ENV_IDS:
            raise ValueError(f"unknown env {env!r}; available: {sorted(ENV_IDS)}")
        self.env_name = env
        self.env_id = ENV_IDS[env]
        self.n_envs = int(n_envs)
        self.obs_dim = _OBS_DIMS[self.env_id]
        self.obs_shape = _OBS_SHAPES[self.env_id]
        self.act_dim = _ACT_DIMS[self.env_id]
        self.discrete = _DISCRETE[self.env_id]
        self.n_actions = _N_ACTIONS[self.env_id]
        n_threads = n_threads or min(os.cpu_count() or 1, 16)

        self._lib = _get_lib()
        self._handle = None
        if self._lib is not None:
            self._handle = self._lib.envpool_create(
                self.env_id, self.n_envs, int(n_threads), int(seed)
            )
        if self._handle is None:
            if self.env_id not in _NUMPY_FALLBACK_IDS:
                raise RuntimeError(
                    f"{env!r} requires the C++ envpool (the NumPy fallback "
                    f"implements only "
                    f"{[k for k, v in ENV_IDS.items() if v in _NUMPY_FALLBACK_IDS]}); "
                    "ensure g++/make are available so estorch_tpu/native builds"
                )
            self._fallback = _NumpyPool(self.env_id, self.n_envs, seed)
        else:
            self._fallback = None

        self._obs = np.empty((self.n_envs, self.obs_dim), np.float32)
        self._rew = np.empty((self.n_envs,), np.float32)
        self._done = np.empty((self.n_envs,), np.uint8)

    @property
    def is_native(self) -> bool:
        return self._handle is not None

    def reset(self) -> np.ndarray:
        if self._fallback is not None:
            return self._fallback.reset()
        self._lib.envpool_reset(
            self._handle, self._obs.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
        )
        return self._obs.copy()

    def step(self, actions: np.ndarray):
        if self._fallback is not None:
            return self._fallback.step(actions)
        acts = np.ascontiguousarray(
            np.asarray(actions, np.float32).reshape(self.n_envs, self.act_dim)
        )
        f32p = ctypes.POINTER(ctypes.c_float)
        self._lib.envpool_step(
            self._handle,
            acts.ctypes.data_as(f32p),
            self._obs.ctypes.data_as(f32p),
            self._rew.ctypes.data_as(f32p),
            self._done.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        )
        return self._obs.copy(), self._rew.copy(), self._done.astype(bool)

    def close(self) -> None:
        if self._handle is not None:
            self._lib.envpool_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class _NumpyPool:
    """Vectorized NumPy twin of the C++ pool (same dynamics, same auto-reset)."""

    def __init__(self, env_id: int, n_envs: int, seed: int):
        self.env_id = env_id
        self.n = n_envs
        self.rng = np.random.default_rng(seed)
        self.state = None

    def reset(self) -> np.ndarray:
        if self.env_id == 0:
            self.state = self.rng.uniform(-0.05, 0.05, (self.n, 4)).astype(np.float32)
            return self.state.copy()
        th = self.rng.uniform(-np.pi, np.pi, self.n).astype(np.float32)
        thdot = self.rng.uniform(-1.0, 1.0, self.n).astype(np.float32)
        self.state = np.stack([th, thdot], 1)
        return self._pendulum_obs()

    def _reset_rows(self, rows: np.ndarray) -> None:
        k = int(rows.sum())
        if k == 0:
            return
        if self.env_id == 0:
            self.state[rows] = self.rng.uniform(-0.05, 0.05, (k, 4)).astype(np.float32)
        else:
            th = self.rng.uniform(-np.pi, np.pi, k)
            thdot = self.rng.uniform(-1.0, 1.0, k)
            self.state[rows] = np.stack([th, thdot], 1).astype(np.float32)

    def _pendulum_obs(self) -> np.ndarray:
        th, thdot = self.state[:, 0], self.state[:, 1]
        return np.stack([np.cos(th), np.sin(th), thdot], 1).astype(np.float32)

    def step(self, actions: np.ndarray):
        a = np.asarray(actions, np.float32).reshape(self.n, -1)
        if self.env_id == 0:
            g, mc, mp, l, fm, tau = 9.8, 1.0, 0.1, 0.5, 10.0, 0.02
            x, x_dot, th, th_dot = (self.state[:, i] for i in range(4))
            force = np.where(a[:, 0] > 0.5, fm, -fm)
            costh, sinth = np.cos(th), np.sin(th)
            tm = mc + mp
            pml = mp * l
            temp = (force + pml * th_dot**2 * sinth) / tm
            thacc = (g * sinth - costh * temp) / (l * (4.0 / 3.0 - mp * costh**2 / tm))
            xacc = temp - pml * thacc * costh / tm
            self.state = np.stack(
                [x + tau * x_dot, x_dot + tau * xacc, th + tau * th_dot,
                 th_dot + tau * thacc], 1,
            ).astype(np.float32)
            done = (np.abs(self.state[:, 0]) > 2.4) | (
                np.abs(self.state[:, 2]) > 12 * 2 * np.pi / 360
            )
            rew = np.ones(self.n, np.float32)
            self._reset_rows(done)
            return self.state.copy(), rew, done
        # pendulum
        ms, mt, dt, g, m, l = 8.0, 2.0, 0.05, 10.0, 1.0, 1.0
        th, thdot = self.state[:, 0], self.state[:, 1]
        u = np.clip(a[:, 0], -mt, mt)
        an = ((th + np.pi) % (2 * np.pi)) - np.pi
        cost = an**2 + 0.1 * thdot**2 + 0.001 * u**2
        newthdot = np.clip(
            thdot + (3 * g / (2 * l) * np.sin(th) + 3.0 / (m * l**2) * u) * dt, -ms, ms
        )
        self.state = np.stack([th + newthdot * dt, newthdot], 1).astype(np.float32)
        done = np.zeros(self.n, bool)
        return self._pendulum_obs(), (-cost).astype(np.float32), done
