"""Flight recorder (ring buffer of recent spans/events) + heartbeat file.

The failure mode these exist for: a run wedges — hung device runtime,
hung env pool, deadlocked worker — and the only post-mortem evidence is a
parent's ``timeout after 480s`` line.  The flight recorder keeps the
last N span/event records in memory (dumpable on demand or at crash
handlers); the heartbeat is the *externally visible* half: a tiny JSON
file rewritten atomically at every phase transition, so any supervisor
(bench.py stage parent, resilience/supervisor.py, doctor.py) can read the
last-known phase + generation + age of a child it cannot otherwise
inspect.

Heartbeat protocol (docs/observability.md):

* writer: serialize ``{"ts", "pid", "phase", "generation", "counters"}``
  to ``path + ".tmp"`` and ``os.replace`` it over ``path`` — readers
  never see a partial file;
* reader: :func:`read_heartbeat` returns the dict plus ``age_s`` (now −
  ts); a missing/corrupt file returns ``None`` — "wedged before the
  first beat" is itself a diagnosis;
* the path travels in the ``ESTORCH_OBS_HEARTBEAT`` environment
  variable, so supervisors enable it for children without touching
  their argv.
"""

from __future__ import annotations

import collections
import json
import os
import threading
import time

HEARTBEAT_ENV = "ESTORCH_OBS_HEARTBEAT"
# a beat older than this is "stale" for doctor/bench diagnosis purposes;
# generous vs real generation times (seconds) but far below stage timeouts
STALE_AFTER_S = 120.0


class FlightRecorder:
    """Bounded in-memory ring of recent telemetry events (oldest evicted)."""

    def __init__(self, capacity: int = 512):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._ring: collections.deque[dict] = collections.deque(
            maxlen=capacity)

    def add(self, kind: str, name: str, **extra) -> None:
        self._ring.append({"ts": time.time(), "kind": kind, "name": name,
                           **extra})

    def events(self) -> list[dict]:
        """Oldest → newest copy of the ring."""
        return list(self._ring)

    def last(self) -> dict | None:
        return self._ring[-1] if self._ring else None

    def __len__(self) -> int:
        return len(self._ring)

    def dump_jsonl(self, path: str) -> None:
        """Append the ring to a JSONL file (crash-dump / post-mortem).

        Atomic (same tmp + ``os.replace`` contract as :class:`Heartbeat`):
        the append is staged by copying the existing file into ``.tmp``,
        writing the ring after it, then renaming over ``path`` — a crash
        mid-dump leaves either the previous complete file or the new
        complete file, never a truncated JSONL for the metrics sidecar or
        ``obs trace --events`` to choke on."""
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            if os.path.exists(path):  # single-writer file: no TOCTOU race
                with open(path) as old:
                    prev = old.read()
                if prev and not prev.endswith("\n"):
                    # a pre-atomic-era torn tail is one lost partial
                    # event: DROP it — newline-terminating it would move
                    # the malformed line mid-file, where tolerant readers
                    # rightly treat it as corruption, not a crash artifact
                    cut = prev.rfind("\n")
                    prev = prev[:cut + 1] if cut >= 0 else ""
                f.write(prev)
            for ev in self._ring:
                f.write(json.dumps(ev, default=float) + "\n")
        os.replace(tmp, path)


class Heartbeat:
    """Atomic last-known-state file for external liveness monitoring.

    Thread-safe: the serving stack beats from two threads (the batcher's
    phase entries and the idle-period beater), and both write through the
    same ``.tmp`` staging file — unserialized, a reader could replace-in
    a half-written payload and a watchdog would misread a healthy process
    as corrupt/stale."""

    def __init__(self, path: str):
        self.path = os.path.abspath(path)
        self._lock = threading.Lock()
        os.makedirs(os.path.dirname(self.path) or ".", exist_ok=True)

    def beat(self, phase: str, generation: int,
             counters: dict | None = None,
             hists: dict | None = None) -> None:
        payload = {
            "ts": time.time(),
            "pid": os.getpid(),
            "phase": phase,
            "generation": int(generation),
        }
        if counters:
            payload["counters"] = counters
        if hists:
            # histogram snapshots (obs/hist.py to_dict shape) ride the
            # beat so the supervisor can fold a dead child's latency
            # DISTRIBUTIONS into counters.json, not just its sums
            payload["hists"] = hists
        tmp = self.path + ".tmp"
        with self._lock:
            with open(tmp, "w") as f:
                json.dump(payload, f, default=float)
            os.replace(tmp, self.path)


def read_heartbeat(path: str) -> dict | None:
    """Heartbeat dict + ``age_s``, or None when absent/unreadable.

    None is a finding, not an error: the child either never constructed
    telemetry (wedged in import/init) or was not heartbeat-enabled.
    """
    try:
        with open(path) as f:
            hb = json.load(f)
        hb["age_s"] = max(0.0, time.time() - float(hb["ts"]))
        return hb
    except (OSError, ValueError, KeyError, TypeError):
        return None


def describe_heartbeat(path: str) -> str:
    """One diagnostic clause for failure lines: last phase + gen + age."""
    hb = read_heartbeat(path)
    if hb is None:
        return "no heartbeat written — wedged before the first phase?"
    return (f"last phase={hb.get('phase', '?')} "
            f"gen={hb.get('generation', '?')} "
            f"heartbeat {hb['age_s']:.0f}s ago")
