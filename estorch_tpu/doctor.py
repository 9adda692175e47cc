"""Environment doctor: diagnose the accelerator and runtime before training.

The device backend behind JAX can WEDGE: every device-touching call —
sometimes including bare ``jax.devices()`` — hangs indefinitely, with no
exception to catch (a chip still held by another process does exactly
this).  A user whose training script "does nothing" has no way to tell a
slow first compile from a dead accelerator.  This module probes the
backend from a SUBPROCESS with a hard timeout (the only reliable wedge
detector: an in-process call cannot be timed out once it enters the
runtime), then reports everything else that commonly decides whether a
config can run: the C++ env pool, optional sim/rollout dependencies, and
whether the virtual CPU mesh the tests use comes up.

Reference has no counterpart (estorch is pure CPU python); this is the
aux-subsystem "failure detection" obligation (SURVEY.md §5) applied to the
accelerator itself.

Use:  python -m estorch_tpu.doctor [--timeout S] [--run-dir DIR]
      [--resilience-probe]
"""

from __future__ import annotations

import importlib.util
import json
import subprocess
import sys

_PROBE = """
import jax
ds = jax.devices()
print("PROBE_OK", ds[0].platform, len(ds))
"""


def probe_device(timeout_s: float = 45.0) -> dict:
    """Probe the default JAX backend in a child process with a hard timeout.

    Returns {"status": "healthy"|"wedged"|"error", ...detail}.  "wedged"
    means the child neither finished nor failed within ``timeout_s`` —
    the signature of a hung device runtime (vs a clean init error, which
    returns fast with stderr).
    """
    import tempfile

    # capture into FILES, not pipes: whatever the child wrote before
    # hanging must survive the kill (PIPE partials are lost on timeout),
    # and a file needs no reader thread that could itself block
    with tempfile.TemporaryFile("w+") as fo, \
            tempfile.TemporaryFile("w+") as fe:
        proc = subprocess.Popen([sys.executable, "-c", _PROBE],
                                stdout=fo, stderr=fe, text=True)
        try:
            proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            unreapable = False
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                # child stuck in uninterruptible sleep (D state — a wedged
                # device driver can do this): SIGKILL cannot reap it, and
                # the doctor must not hang on the very wedge it detects —
                # report the un-reapable child, it is itself a finding
                unreapable = True
            fe.seek(0)
            out = {"status": "wedged", "timeout_s": timeout_s,
                   "stderr_tail": fe.read()[-500:]}
            if unreapable:
                out["unreapable_child"] = True
            return out
        fo.seek(0), fe.seek(0)
        out, err = fo.read(), fe.read()
    for line in out.splitlines():
        if line.startswith("PROBE_OK"):
            _, platform, n = line.split()
            return {"status": "healthy", "platform": platform,
                    "n_devices": int(n)}
    return {"status": "error", "returncode": proc.returncode,
            "stderr_tail": err[-500:]}


# staged probe: each marker proves one layer of the device path alive,
# so a timeout's LAST marker names the layer that wedged.  flush=True on
# every print — the parent reads the file after killing the child, and
# an unflushed marker would misclassify the hang one stage early.
_STAGED_PROBE = """
import sys
print("PROBE_START", flush=True)
import jax
print("PROBE_JAX_OK", flush=True)
ds = jax.devices()
print("PROBE_DEVICES_OK", ds[0].platform, len(ds), flush=True)
import jax.numpy as jnp
fn = jax.jit(lambda x: (x @ x).sum())
x = jnp.ones((128, 128), jnp.float32)
compiled = fn.lower(x).compile()
print("PROBE_COMPILE_OK", flush=True)
compiled(x).block_until_ready()
print("PROBE_EXEC_OK", flush=True)
"""

# ordered (marker, hang-reason-when-absent) pairs: the first missing
# marker after a timeout names the stage that wedged
_PROBE_STAGES = (
    ("PROBE_JAX_OK", "init-hang"),
    ("PROBE_DEVICES_OK", "init-hang"),
    ("PROBE_COMPILE_OK", "compile-hang"),
    ("PROBE_EXEC_OK", "exec-hang"),
)


def classify_device_probe(out: str, timed_out: bool, returncode
                          ) -> tuple[str, str | None]:
    """(status, reason) from a staged probe's output — pure so the
    reason-code classification is unit-testable without wedging anything.

    Reasons (docs/observability.md "Profiling"): ``no-device`` (the
    runtime answered fast: no such backend), ``init-hang`` /
    ``compile-hang`` / ``exec-hang`` (the layer that went silent),
    ``error`` (failed fast after device init — not a wedge, read the
    stderr).  ``ok`` says the DEFAULT backend works, whatever it is:
    :func:`check_device` adds ``wrong-platform`` for callers that asked
    for a particular one."""
    markers = {ln.split()[0] for ln in out.splitlines() if ln.strip()}
    if "PROBE_EXEC_OK" in markers and not timed_out and returncode == 0:
        return "ok", None
    if timed_out:
        for marker, reason in _PROBE_STAGES:
            if marker not in markers:
                return "failed", reason
        return "failed", "exec-hang"  # all markers but the child lived on
    if "PROBE_DEVICES_OK" not in markers:
        # failed fast before any device existed: the backend said no
        # (missing runtime, no chip, refused platform) — not a wedge
        return "failed", "no-device"
    return "failed", "error"


def _run_staged_probe(script: str, timeout_s: float, env: dict) -> dict:
    """Run a marker-printing probe script in a killed-on-timeout child.

    The ONE subprocess harness every staged probe shares (device + mesh):
    file-captured stdout/stderr (a pipe's partials die with the kill; a
    file needs no reader thread that could itself block), hard timeout,
    SIGKILL + bounded reap with the un-reapable (D-state) child reported
    as a finding of its own.  Returns {out, err, timed_out, returncode,
    unreapable, elapsed_s} for the caller's classifier to shape.
    """
    import tempfile
    import time

    t0 = time.perf_counter()
    with tempfile.TemporaryFile("w+") as fo, \
            tempfile.TemporaryFile("w+") as fe:
        proc = subprocess.Popen([sys.executable, "-c", script],
                                stdout=fo, stderr=fe, text=True, env=env)
        timed_out = False
        unreapable = False
        try:
            proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            timed_out = True
            proc.kill()
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                unreapable = True  # D-state child: itself a finding
        fo.seek(0), fe.seek(0)
        out_text, err_text = fo.read(), fe.read()
    return {
        "out": out_text, "err": err_text, "timed_out": timed_out,
        "returncode": proc.returncode, "unreapable": unreapable,
        "elapsed_s": round(time.perf_counter() - t0, 2),
    }


def check_device(timeout_s: float = 20.0,
                 platform: str | None = None) -> dict:
    """Prove the device path alive-or-wedged in SECONDS with a typed
    reason, replacing the old discover-by-480s-stage-timeout: a staged
    subprocess runs import → device init → XLA compile → execute, each
    stage leaving a marker, and a hang is classified by the first marker
    missing when the timeout kills it.

    The result always names the ``platform`` the probe found.
    ``platform`` pins ``JAX_PLATFORMS`` in the child AND is required of
    what it finds: ``"tpu"`` asks "is the CHIP there and alive", and a
    probe that came up on anything else fails with ``wrong-platform``
    (a healthy CPU backend is not a chip).  Deliberately stdlib-only at
    module scope so bench.py can file-load this module jax-free (the
    stage-protocol discipline).
    """
    import os

    env = dict(os.environ)
    if platform is not None:
        env["JAX_PLATFORMS"] = platform
    run = _run_staged_probe(_STAGED_PROBE, timeout_s, env)
    status, reason = classify_device_probe(run["out"], run["timed_out"],
                                           run["returncode"])
    result: dict = {
        "status": status,
        "elapsed_s": run["elapsed_s"],
        "timeout_s": timeout_s,
    }
    if platform is not None:
        result["requested_platform"] = platform
    for ln in run["out"].splitlines():
        if ln.startswith("PROBE_DEVICES_OK"):
            _, plat, n = ln.split()
            result["platform"] = plat
            result["n_devices"] = int(n)
    if (status == "ok" and platform is not None
            and result.get("platform") != platform):
        result["status"], reason = "failed", "wrong-platform"
    if reason is not None:
        result["reason"] = reason
        result["stderr_tail"] = run["err"][-500:]
    if run["unreapable"]:
        result["unreapable_child"] = True
    return result


# mesh probe: proves the param-sharded path (parallel/sharded.py,
# docs/sharding.md) can run on THIS host's virtual CPU mesh — 2-D mesh
# build, partition-rule resolution over a dummy tree, and one sharded
# dummy program (donated params operand, explicit out_shardings)
# compiled AND executed.  Forced onto the CPU backend in the child so
# the probe cannot touch (or wedge on) a real device runtime.
_MESH_PROBE = """
import sys
print("MESH_START", flush=True)
from estorch_tpu.utils import force_cpu_backend
force_cpu_backend(8)
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from estorch_tpu.parallel.mesh import (DEFAULT_PARTITION_RULES,
                                       hyperscale_mesh,
                                       match_partition_rules)
mesh = hyperscale_mesh(2, 4)
print("MESH_BUILD_OK", mesh.devices.size, flush=True)
tree = {"dense": {"kernel": jnp.zeros((8, 16)), "bias": jnp.zeros((16,))}}
sh = match_partition_rules(DEFAULT_PARTITION_RULES, tree, mesh)
params = jax.device_put(tree, sh)
print("MESH_RULES_OK", flush=True)
fn = jax.jit(
    lambda p: jax.tree_util.tree_map(lambda x: x * 2.0, p),
    donate_argnums=(0,), in_shardings=(sh,), out_shardings=sh)
compiled = fn.lower(params).compile()
print("MESH_COMPILE_OK", flush=True)
out = compiled(params)
jax.block_until_ready(out)
print("MESH_EXEC_OK", flush=True)
"""

_MESH_STAGES = (
    ("MESH_BUILD_OK", "mesh-build"),
    ("MESH_RULES_OK", "partition-rules"),
    ("MESH_COMPILE_OK", "sharded-compile"),
    ("MESH_EXEC_OK", "sharded-exec"),
)


def classify_mesh_probe(out: str, timed_out: bool, returncode
                        ) -> tuple[str, str | None]:
    """(status, failed-stage) from the mesh probe's markers — pure, so
    the classification is unit-testable without a mesh."""
    markers = {ln.split()[0] for ln in out.splitlines() if ln.strip()}
    if "MESH_EXEC_OK" in markers and not timed_out and returncode == 0:
        return "ok", None
    for marker, stage in _MESH_STAGES:
        if marker not in markers:
            return "failed", stage
    return "failed", "sharded-exec"


def check_mesh(timeout_s: float = 90.0) -> dict:
    """Can the param-sharded engine run here?  A staged subprocess builds
    the 2-D virtual-CPU mesh, resolves the default partition rules, and
    compiles+executes one donated sharded program — the first missing
    marker names the failing layer (jax too old for NamedSharding jit,
    broken virtual-device config, GSPMD lowering failure, ...)."""
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    run = _run_staged_probe(_MESH_PROBE, timeout_s, env)
    status, stage = classify_mesh_probe(run["out"], run["timed_out"],
                                        run["returncode"])
    result: dict = {
        "status": status,
        "elapsed_s": run["elapsed_s"],
        "timeout_s": timeout_s,
    }
    if status != "ok":
        result["failed_stage"] = stage
        result["timed_out"] = run["timed_out"]
        result["stderr_tail"] = run["err"][-500:]
    if run["unreapable"]:
        result["unreapable_child"] = True
    return result


# scenario probe: proves the scenario suite (estorch_tpu/scenarios,
# docs/scenarios.md) works here — (1) the distribution draw is
# deterministic in (seed, variant) and stacks host-side, (2) one tiny
# jitted rollout evaluates episodes across 3 variants with the drawn
# constants as TRACED OPERANDS (finite fitness, variant ids in range).
# Forced onto the CPU backend in the child so the probe cannot touch
# (or wedge on) a real device runtime.
_SCENARIO_PROBE = """
import sys
print("SCEN_START", flush=True)
from estorch_tpu.utils import force_cpu_backend
force_cpu_backend(2)
import jax
import jax.numpy as jnp
import numpy as np
from estorch_tpu.envs.pendulum import Pendulum
from estorch_tpu.envs.rollout import make_rollout
from estorch_tpu.scenarios import ScenarioEnv, default_distribution
dist = default_distribution(Pendulum(), n_variants=3, spread=0.2, seed=0)
a = dist.draw_concrete(1)
b = dist.draw_concrete(1)
assert a == b, ("non-deterministic draw", a, b)
stacked = dist.draw_all()
for name in dist.names:
    assert np.asarray(stacked[name]).shape == (3,), name
print("SCEN_DRAW_OK", flush=True)
env = ScenarioEnv(Pendulum(), dist)
rollout = jax.jit(jax.vmap(
    make_rollout(env, lambda p, obs: jnp.tanh(obs @ p), 5),
    in_axes=(None, 0)))
res = rollout(jnp.zeros((3, 1)),
              jax.random.split(jax.random.PRNGKey(0), 6))
f = np.asarray(res.total_reward)
v = np.rint(np.asarray(res.bc)[:, -1]).astype(int)
assert np.isfinite(f).all(), f
assert set(v) <= {0, 1, 2}, v
print("SCEN_ROLLOUT_OK", flush=True)
"""

_SCENARIO_STAGES = (
    ("SCEN_DRAW_OK", "draw-determinism"),
    ("SCEN_ROLLOUT_OK", "traced-rollout"),
)


def classify_scenario_probe(out: str, timed_out: bool, returncode
                            ) -> tuple[str, str | None]:
    """(status, failed-stage) from the scenario probe's markers — pure,
    so the classification is unit-testable without running the probe."""
    markers = {ln.split()[0] for ln in out.splitlines() if ln.strip()}
    if "SCEN_ROLLOUT_OK" in markers and not timed_out and returncode == 0:
        return "ok", None
    for marker, stage in _SCENARIO_STAGES:
        if marker not in markers:
            return "failed", stage
    return "failed", "traced-rollout"


def check_scenarios(timeout_s: float = 90.0) -> dict:
    """Can the scenario suite run here?  Findings, never tracebacks: a
    failure names the stage (draw-determinism vs traced-rollout) with a
    stderr tail, and a hung child is killed at the timeout."""
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    run = _run_staged_probe(_SCENARIO_PROBE, timeout_s, env)
    status, stage = classify_scenario_probe(run["out"], run["timed_out"],
                                            run["returncode"])
    result: dict = {
        "status": status,
        "elapsed_s": run["elapsed_s"],
        "timeout_s": timeout_s,
    }
    if status != "ok":
        result["failed_stage"] = stage
        result["timed_out"] = run["timed_out"]
        result["stderr_tail"] = run["err"][-500:]
    if run["unreapable"]:
        result["unreapable_child"] = True
    return result


# elastic probe: proves the multi-host layers (parallel/multihost.py +
# parallel/elastic.py, docs/multihost.md) can run here — staged:
# (1) jax.distributed bring-up of TWO real OS processes over loopback
#     (Gloo CPU collectives, timed barrier),
# (2) the global population mesh spanning both processes' devices,
# (3) one cross-process psum through that mesh,
# (4) the elastic coordinator's TCP round-trip (join → sync → center →
#     dispatch → result), which is deliberately jax-free.
# The parent orchestrates, prints one marker per stage, and bounds every
# wait; the first missing marker names the failing layer.
_ELASTIC_WORKER = """
import sys
pid, port, out_path = int(sys.argv[1]), sys.argv[2], sys.argv[3]
from estorch_tpu.utils.backend import force_cpu_backend
force_cpu_backend(2)
import estorch_tpu.parallel.multihost as mh
f = open(out_path, "w", buffering=1)
mh.initialize("127.0.0.1:" + port, 2, pid, timeout_s=45,
              cpu_collectives=True)
print("WINIT", file=f)
import jax
mesh = mh.global_population_mesh()
print("WMESH", mesh.devices.size, file=f)
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
fn = jax.jit(jax.shard_map(lambda x: jax.lax.psum(x, "pop"), mesh=mesh,
                           in_specs=(P(),), out_specs=P(), check_vma=False))
out = fn(jnp.ones(4))
print("WPSUM", float(out[0]), file=f)
"""

_ELASTIC_PROBE = """
import json
import os
import socket
import subprocess
import sys
import tempfile
import time

print("ELASTIC_START", flush=True)
workdir = tempfile.mkdtemp(prefix="estorch_elastic_probe_")
worker_py = os.path.join(workdir, "worker.py")
with open(worker_py, "w") as f:
    f.write(%r)
with socket.socket() as s:
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
marks = [os.path.join(workdir, "w%%d.txt" %% i) for i in range(2)]
env = dict(os.environ, JAX_PLATFORMS="cpu")
procs = [subprocess.Popen([sys.executable, worker_py, str(i), str(port),
                           marks[i]], env=env,
                          stdout=subprocess.DEVNULL,
                          stderr=subprocess.PIPE, text=True)
         for i in range(2)]

def both_have(marker, deadline):
    while time.monotonic() < deadline:
        got = 0
        for m in marks:
            try:
                with open(m) as f:
                    if any(ln.startswith(marker) for ln in f):
                        got += 1
            except OSError:
                pass
        if got == 2:
            return True
        if any(p.poll() not in (None, 0) for p in procs):
            return False
        time.sleep(0.1)
    return False

deadline = time.monotonic() + 70
try:
    if not both_have("WINIT", deadline):
        raise SystemExit(3)
    print("ELASTIC_INIT_OK", flush=True)
    if not both_have("WMESH", deadline):
        raise SystemExit(3)
    print("ELASTIC_MESH_OK", flush=True)
    if not both_have("WPSUM", deadline):
        raise SystemExit(3)
    print("ELASTIC_PSUM_OK", flush=True)
finally:
    for p in procs:
        if p.poll() is None:
            p.kill()
        try:
            p.wait(timeout=5)
        except subprocess.TimeoutExpired:
            pass
        if p.returncode not in (None, 0):
            sys.stderr.write((p.stderr.read() or "")[-800:])

# stage 4: coordinator round-trip — jax-free by construction
import numpy as np
from estorch_tpu.parallel.elastic import (ElasticCoordinator, recv_msg,
                                          send_msg)
coord = ElasticCoordinator(join_grace_s=5.0)
cl = socket.create_connection(coord.address, timeout=5)
cl.settimeout(0.05)
send_msg(cl, {"t": "join", "host": 0})
deadline = time.monotonic() + 10

def next_msg():
    while time.monotonic() < deadline:
        got = recv_msg(cl, 0.05)
        if got is not None:
            return got
    raise SystemExit(4)

header, arrays = next_msg()
assert header["t"] == "sync", header
coord.push_center(0, np.arange(4, dtype=np.float32), 0.1)
assert coord.dispatch(0, 0) == 0
seen = set()
while {"center", "dispatch"} - seen:
    header, arrays = next_msg()
    seen.add(header["t"])
    if header["t"] == "center":
        assert arrays["center"].tolist() == [0.0, 1.0, 2.0, 3.0]
send_msg(cl, {"t": "result", "dispatch": 0, "steps": 3, "eval_s": 0.01},
         {"fitness": np.ones(4, np.float32)})
got = ([], [], [])
while not got[0] and time.monotonic() < deadline:
    got = coord.poll(0.2)
assert got[0] and got[0][0]["dispatch"] == 0, got
coord.close()
cl.close()
print("ELASTIC_COORD_OK", flush=True)
""" % (_ELASTIC_WORKER,)

_ELASTIC_STAGES = (
    ("ELASTIC_INIT_OK", "distributed-init"),
    ("ELASTIC_MESH_OK", "mesh-build"),
    ("ELASTIC_PSUM_OK", "cross-process-psum"),
    ("ELASTIC_COORD_OK", "coordinator-roundtrip"),
)


def classify_elastic_probe(out: str, timed_out: bool, returncode
                           ) -> tuple[str, str | None]:
    """(status, failed-stage) from the elastic probe's markers — pure,
    so the classification is unit-testable without spawning a fleet."""
    markers = {ln.split()[0] for ln in out.splitlines() if ln.strip()}
    if "ELASTIC_COORD_OK" in markers and not timed_out and returncode == 0:
        return "ok", None
    for marker, stage in _ELASTIC_STAGES:
        if marker not in markers:
            return "failed", stage
    return "failed", "coordinator-roundtrip"


def check_elastic(timeout_s: float = 120.0) -> dict:
    """Can the elastic multi-host path run here?  Findings, never
    tracebacks: a staged subprocess brings up a REAL 2-process
    ``jax.distributed`` job over loopback, builds the cross-process
    mesh, runs one cross-process psum, then round-trips the elastic
    coordinator protocol — the first missing marker names the failing
    layer (no Gloo, broken loopback, protocol regression, ...)."""
    import os

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    # the probe writes worker.py into a tempdir and runs it as a script,
    # so the worker's sys.path[0] is that tempdir — from a source
    # checkout (package not pip-installed) estorch_tpu is only
    # importable if we forward our own package root explicitly
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = (root + os.pathsep + env["PYTHONPATH"]
                         if env.get("PYTHONPATH") else root)
    run = _run_staged_probe(_ELASTIC_PROBE, timeout_s, env)
    status, stage = classify_elastic_probe(run["out"], run["timed_out"],
                                           run["returncode"])
    result: dict = {
        "status": status,
        "elapsed_s": run["elapsed_s"],
        "timeout_s": timeout_s,
    }
    if status != "ok":
        result["failed_stage"] = stage
        result["timed_out"] = run["timed_out"]
        result["stderr_tail"] = run["err"][-500:]
    if run["unreapable"]:
        result["unreapable_child"] = True
    return result


def check_native_pool() -> dict:
    """Is the C++ env pool built/loadable, or will pools fall back to NumPy?"""
    try:
        from .envs import native_pool

        lib = native_pool._load_library()
        return {"cpp_pool": lib is not None}
    except Exception as e:  # diagnostic tool: never crash the report
        return {"cpp_pool": False, "error": repr(e)}


def check_optional_deps() -> dict:
    """Presence of the optional simulators/ROM stacks configs gate on."""
    out = {}
    for mod, why in (
        ("mujoco", "host/pooled MuJoCo configs"),
        ("mujoco.mjx", "device-native MuJoCo physics (in-tree fallback: envs/locomotion.py)"),
        ("ale_py", "real Atari (atari_frostbite); pong84 needs nothing"),
        ("gymnasium", "host/pooled gym envs"),
    ):
        try:
            found = importlib.util.find_spec(mod) is not None
        except Exception:
            # find_spec("pkg.sub") IMPORTS pkg first: a missing parent
            # raises ModuleNotFoundError, a broken native install can
            # raise ImportError/OSError — never crash the report (this is
            # the exact machine the doctor exists to diagnose)
            found = False
        out[mod] = {"available": found, "needed_for": why}
    return out


def check_host() -> dict:
    """Host-side facts that decide what parallelism can actually help:
    worker threads/processes cannot speed up a 1-core box (they time-slice
    it), and the persistent compile cache is what makes fresh processes
    cheap."""
    import os

    import jax

    from .utils.backend import default_compilation_cache_dir

    # report the LIVE cache dir when one is configured, else the default
    # enable_compilation_cache() would use
    cache_dir = (
        jax.config.jax_compilation_cache_dir
        or default_compilation_cache_dir()
    )
    cached = (
        len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0
    )
    return {
        "cpu_count": os.cpu_count(),
        "note": (
            "1 CPU: host worker threads/processes and virtual devices "
            "time-slice one core — correctness yes, speedup no"
            if (os.cpu_count() or 1) == 1 else
            f"{os.cpu_count()} CPUs available for host workers / env pools"
        ),
        "compile_cache_dir": cache_dir,
        "compile_cache_entries": cached,
        "compile_cache_hint": (
            "utils.enable_compilation_cache() makes every later process "
            "load compiled programs from disk (<1s) instead of paying the "
            "20-40s XLA compile"
        ),
    }


def check_obs(run_dir: str | None = None) -> dict:
    """Observability plumbing health (estorch_tpu/obs/):

    - is the trace/telemetry directory writable (JSONL sinks, jax
      profiler traces, heartbeat files all land there)?
    - is TensorBoard importable (TensorBoardSink), or is JsonlSink the
      only option?
    - export probe: spin up the Prometheus metrics sidecar
      (obs/export/sidecar.py) over a synthetic temp run-dir, scrape it
      over loopback, and validate the exposition PARSES — all stdlib, no
      jax touch, so "can this host be scraped" is answerable even from a
      wedged-runtime machine;
    - given a run dir: heartbeat freshness — the liveness verdict for a
      run that stopped printing ("wedged or dead" vs "slow but beating").
    """
    import os
    import tempfile

    from .obs.recorder import STALE_AFTER_S, read_heartbeat

    trace_dir = os.environ.get("ESTORCH_OBS_DIR") or tempfile.gettempdir()
    try:
        probe = os.path.join(trace_dir, f".obs_write_probe_{os.getpid()}")
        with open(probe, "w") as f:
            f.write("ok")
        os.remove(probe)
        writable = True
        err = None
    except OSError as e:  # diagnostic tool: never crash the report
        writable, err = False, repr(e)
    out: dict = {
        "trace_dir": {"path": trace_dir, "writable": writable,
                      **({"error": err} if err else {})},
    }
    try:
        tb = importlib.util.find_spec("torch.utils.tensorboard") is not None
    except Exception:
        tb = False
    out["tensorboard"] = {
        "available": tb,
        "needed_for": "obs.TensorBoardSink (obs.JsonlSink needs nothing)",
    }
    out["export"] = _export_probe()
    if run_dir is not None:
        hb_path = os.path.join(run_dir, "heartbeat.json")
        hb = read_heartbeat(hb_path)
        if hb is None:
            out["heartbeat"] = {
                "path": hb_path, "found": False,
                "hint": "no heartbeat — run never started telemetry, "
                        "finished long ago, or this is the wrong dir",
            }
        else:
            out["heartbeat"] = {
                "path": hb_path, "found": True,
                "age_s": round(hb["age_s"], 1),
                "stale": hb["age_s"] > STALE_AFTER_S,
                "phase": hb.get("phase"),
                "generation": hb.get("generation"),
            }
    return out


def _export_probe() -> dict:
    """Loopback-scrape the metrics sidecar against a synthetic temp
    run-dir and validate the exposition parses (obs/export/): the
    end-to-end proof that a supervised run on THIS host would be
    scrapeable.  Stdlib only — never touches jax or a device runtime."""
    import json as _json
    import os
    import tempfile
    import time as _time
    import urllib.request

    try:
        from .obs.export.prometheus import (parse_exposition,
                                            samples_by_name,
                                            validate_histogram_series)
        from .obs.export.sidecar import MetricsSidecar, publish_counters
        from .obs.hist import Histogram

        probe_hist = Histogram()
        probe_hist.observe(0.002)
        with tempfile.TemporaryDirectory() as d:
            hb_ts = _time.time()
            with open(os.path.join(d, "heartbeat.json"), "w") as f:
                _json.dump({"ts": hb_ts, "pid": os.getpid(),
                            "phase": "doctor_probe", "generation": 1,
                            "counters": {"env_steps": 1},
                            "hists": {"probe_s": probe_hist.to_dict()}}, f)
            # published totals + a NEWER live beat: the scrape must
            # compose both (the cross-restart monotonicity contract) —
            # for the flat counters AND the histogram buckets
            publish_counters(d, {"env_steps": 2}, through_ts=hb_ts - 1.0,
                             extra={"restart_count": 1},
                             hists={"probe_s": probe_hist.to_dict()})
            sidecar = MetricsSidecar(d, port=0)
            sidecar.start_background()
            try:
                with urllib.request.urlopen(
                        f"http://{sidecar.host}:{sidecar.port}/metrics",
                        timeout=10) as resp:
                    body = resp.read().decode()
            finally:
                sidecar.close()
        samples = parse_exposition(body)  # ValueError on malformed lines
        vals = samples_by_name(samples)
        problems = []
        if vals.get("estorch_env_steps") != 3:
            problems.append(
                f"published+live composition broke: env_steps="
                f"{vals.get('estorch_env_steps')} (want 3)")
        if vals.get("estorch_up") != 1:
            problems.append("fresh heartbeat did not read as up")
        problems.extend(validate_histogram_series(samples))
        if vals.get("estorch_probe_s_count") != 2:
            problems.append(
                f"published+live HISTOGRAM composition broke: probe_s "
                f"count={vals.get('estorch_probe_s_count')} (want 2)")
        return {
            "ok": not problems,
            "samples": len(samples),
            **({"problems": problems} if problems else {}),
        }
    except Exception as e:  # diagnostic tool: never crash the report
        return {"ok": False, "error": repr(e)}


# tiny host-backend ES save/restore round trip, run in a SUBPROCESS with a
# hard timeout (the orbax/jax import chain inits a backend — on a wedged
# machine that hang must not take the doctor down with it).  __ROOT__ is
# substituted (plain replace — str.format would trip on the dict braces)
# with the repr of the checkpoint root under test.
_RESILIENCE_PROBE = """
import os, shutil
import numpy as np
import torch
from estorch_tpu.utils import force_cpu_backend
force_cpu_backend(1)
from estorch_tpu import ES
from estorch_tpu.utils.checkpoint import restore_checkpoint, save_checkpoint

class P(torch.nn.Module):
    def __init__(self):
        super().__init__()
        self.l = torch.nn.Linear(2, 1)
    def forward(self, x):
        return self.l(x)

class A:
    def rollout(self, policy):
        with torch.no_grad():
            v = torch.nn.utils.parameters_to_vector(policy.parameters())
        return -float((v ** 2).sum())

def make():
    return ES(P, A, torch.optim.Adam, population_size=4, sigma=0.1, seed=0,
              optimizer_kwargs={"lr": 1e-2}, table_size=1 << 10,
              telemetry=False)

root = os.path.join(__ROOT__, "doctor_resilience_probe_%d" % os.getpid())
try:
    es = make()
    es.train(1, verbose=False)
    save_checkpoint(es, root)
    es2 = make()
    restore_checkpoint(es2, root)
    assert es2.generation == 1, es2.generation
    np.testing.assert_array_equal(np.asarray(es.state.params_flat),
                                  np.asarray(es2.state.params_flat))
finally:
    shutil.rmtree(root, ignore_errors=True)
print("RESILIENCE_PROBE_OK")
"""


def _roundtrip_probe(root: str, timeout_s: float = 180.0) -> dict:
    """Save/restore a tiny ES under ``root`` in a timed-out subprocess."""
    import tempfile

    with tempfile.TemporaryFile("w+") as fo, \
            tempfile.TemporaryFile("w+") as fe:
        proc = subprocess.Popen(
            [sys.executable, "-c",
             _RESILIENCE_PROBE.replace("__ROOT__", repr(root))],
            stdout=fo, stderr=fe, text=True)
        try:
            proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            proc.kill()
            try:
                proc.wait(timeout=5)
            except subprocess.TimeoutExpired:
                fe.seek(0)
                return {"status": "wedged", "timeout_s": timeout_s,
                        "unreapable_child": True,
                        "stderr_tail": fe.read()[-500:]}
            fe.seek(0)
            return {"status": "wedged", "timeout_s": timeout_s,
                    "stderr_tail": fe.read()[-500:]}
        fo.seek(0), fe.seek(0)
        out, err = fo.read(), fe.read()
    if "RESILIENCE_PROBE_OK" in out:
        return {"status": "ok"}
    return {"status": "error", "returncode": proc.returncode,
            "stderr_tail": err[-500:]}


def check_resilience(ckpt_root: str | None = None,
                     probe: bool = False,
                     probe_timeout_s: float = 180.0) -> dict:
    """Can a run here actually survive faults?  (docs/resilience.md)

    - is the checkpoint root (``ESTORCH_CKPT_ROOT`` or tempdir) writable
      — without it the Supervisor has nothing to resume from;
    - ``probe=True``: a full save/restore round trip on a tiny host ES
      in a timed-out subprocess — the end-to-end proof that resume works
      on THIS machine's orbax/torch/jax install;
    - is fork available — worker respawn (host/procpool.py) needs it;
    - heartbeat-watchdog config sanity: a heartbeat path with telemetry
      disabled means a supervisor would see no beats and kill healthy
      runs.
    """
    import os
    import tempfile

    from .obs.recorder import HEARTBEAT_ENV, STALE_AFTER_S
    from .obs.spans import OBS_DISABLE_ENV

    root = (ckpt_root or os.environ.get("ESTORCH_CKPT_ROOT")
            or tempfile.gettempdir())
    try:
        probe_file = os.path.join(root, f".ckpt_write_probe_{os.getpid()}")
        with open(probe_file, "w") as f:
            f.write("ok")
        os.remove(probe_file)
        writable, err = True, None
    except OSError as e:  # diagnostic tool: never crash the report
        writable, err = False, repr(e)
    out: dict = {
        "ckpt_root": {"path": root, "writable": writable,
                      **({"error": err} if err else {})},
    }
    if probe and writable:
        out["roundtrip"] = _roundtrip_probe(root, probe_timeout_s)
    import multiprocessing as mp

    out["fork"] = {
        "available": os.name == "posix" and "fork" in mp.get_all_start_methods(),
        "needed_for": "host process workers + respawn (host/procpool.py)",
    }
    hb_path = os.environ.get(HEARTBEAT_ENV)
    obs_enabled = os.environ.get(OBS_DISABLE_ENV, "1") != "0"
    watchdog: dict = {
        "heartbeat_env_set": bool(hb_path),
        "telemetry_enabled": obs_enabled,
        "stale_after_s": STALE_AFTER_S,
    }
    if hb_path and not obs_enabled:
        watchdog["warning"] = (
            f"{HEARTBEAT_ENV} is set but {OBS_DISABLE_ENV}=0 disables "
            "telemetry — a staleness watchdog would see no beats and kill "
            "healthy runs"
        )
    if hb_path:
        hb_dir = os.path.dirname(os.path.abspath(hb_path)) or "."
        watchdog["heartbeat_dir_writable"] = os.access(hb_dir, os.W_OK)
    out["heartbeat_watchdog"] = watchdog
    return out


def check_serve(bundle: str | None = None) -> dict:
    """Serving readiness (estorch_tpu/serve, docs/serving.md):

    - can this host bind a loopback listening socket (the server's one
      OS-level requirement beyond python)?
    - does the dynamic batcher round-trip requests (coalescing, bucket
      padding, recompile accounting) — exercised with a plain-numpy
      batch fn, so this check never touches jax or a device runtime;
    - given ``bundle``: structural validation of the artifact (manifest
      schema, payload checksum, param count) via
      ``serve.bundle.validate_bundle`` — again without importing jax, so
      a corrupt bundle is diagnosable from a wedged-runtime machine.
    """
    import socket

    out: dict = {}
    try:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        out["loopback"] = {"bindable": True, "probe_port": port}
    except OSError as e:  # diagnostic tool: never crash the report
        out["loopback"] = {"bindable": False, "error": repr(e)}

    try:
        import numpy as np

        from .obs.spans import Telemetry
        from .serve.batcher import DynamicBatcher

        tel = Telemetry(enabled=True)
        b = DynamicBatcher(lambda arr: arr * 2.0, (3,), max_batch=4,
                           max_wait_ms=1.0, telemetry=tel)
        got = b.predict([1.0, 2.0, 3.0], timeout=10.0)
        b.close()
        ok = np.allclose(got, [2.0, 4.0, 6.0])
        out["batcher"] = {
            "ok": bool(ok),
            "recompiles": int(tel.counters.get("recompiles")),
            "buckets": list(b.buckets),
        }
    except Exception as e:
        out["batcher"] = {"ok": False, "error": repr(e)}

    if bundle is not None:
        from .serve.bundle import BundleError, validate_bundle

        try:
            man = validate_bundle(bundle)
            out["bundle"] = {
                "path": bundle, "valid": True,
                "version": man["version"],
                "param_dim": man["param_dim"],
                "module": man["module"]["import"],
                "obs_norm": bool(man.get("obs_norm")),
                "recurrent": bool(man.get("recurrent")),
                "warm": _probe_bundle_warmth(man),
            }
        except (BundleError, OSError) as e:
            out["bundle"] = {"path": bundle, "valid": False,
                             "error": str(e)}
    return out


def _probe_bundle_warmth(manifest: dict) -> dict:
    """The warm-bundle probe (serve/warm.py, docs/serving.md "Cold start
    & quantized serving"), jax-free like the rest of check_serve:
    validate_bundle already proved the packed warmth structurally sound
    (entries present, checksummed, ladder complete), so what is left is
    the COMPATIBILITY finding — warmth built under a different jax
    version than this host's install can never hit and will be ignored
    at load; an operator should re-export rather than wonder why the
    replica still pays the JIT storm.  The installed jax version comes
    from package metadata, so a wedged runtime can still be probed."""
    warm = manifest.get("warm")
    if not isinstance(warm, dict):
        return {"present": False}
    out = {
        "present": True,
        "format": warm.get("format"),
        "entries": len(warm.get("entries") or {}),
        "buckets": warm.get("buckets"),
        "dtypes": warm.get("dtypes"),
        "jax_version": warm.get("jax_version"),
        "platform": warm.get("platform"),
    }
    try:
        from importlib.metadata import version

        installed = version("jax")
    except Exception:
        installed = None
    out["installed_jax"] = installed
    if installed is None:
        out["compatible"] = None
        out["finding"] = ("jax is not importable as package metadata on "
                          "this host — warmth compatibility unknown")
    elif installed != warm.get("jax_version"):
        out["compatible"] = False
        out["finding"] = (
            f"warmth was built under jax {warm.get('jax_version')} but "
            f"this host has jax {installed} — cache keys cannot match, "
            "the warmth will be ignored at load; re-export the bundle "
            "with warm=True under the serving jax version")
    else:
        out["compatible"] = True
    return out


def check_router() -> dict:
    """Can this host run the fleet front router?  (serve/router.py,
    docs/serving.md "Fleet")

    Loopback end-to-end probe, jax-free: spin a 2-replica TOY fleet
    (stdlib HTTP servers answering the /predict //healthz //stats
    shapes), route through a real :class:`Router`, then kill one
    replica and assert the next requests still answer (failover within
    the retry budget) and that the router's ``/metrics`` parses through
    the validating parser.  Never crashes the report: any failure comes
    back as ``{"ok": False, ...}``."""
    import json as _json
    import threading
    import urllib.request
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    try:
        from .obs.export.prometheus import parse_exposition
        from .serve.router import Router

        def make_replica():
            class Toy(BaseHTTPRequestHandler):
                protocol_version = "HTTP/1.1"

                def log_message(self, *a):
                    pass

                def _j(self, obj):
                    body = _json.dumps(obj).encode()
                    self.send_response(200)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)

                def do_GET(self):
                    if self.path == "/healthz":
                        self._j({"ok": True, "draining": False,
                                 "queue_depth": 0})
                    else:
                        self._j({"queue_depth": 0,
                                 "request_ms": {"p99": 1.0}})

                def do_POST(self):
                    n = int(self.headers.get("Content-Length", 0))
                    data = _json.loads(self.rfile.read(n))
                    self._j({"action": [v * 2.0 for v in data["obs"]]})

            srv = ThreadingHTTPServer(("127.0.0.1", 0), Toy)
            threading.Thread(target=srv.serve_forever,
                             daemon=True).start()
            return srv

        problems = []
        a, b = make_replica(), make_replica()
        router = Router(
            [("ra", f"127.0.0.1:{a.server_address[1]}"),
             ("rb", f"127.0.0.1:{b.server_address[1]}")],
            port=0, poll_interval_s=30.0,  # stale health: exercise RETRY
            upstream_timeout_s=5.0)
        router.start_background()
        try:
            url = f"http://{router.host}:{router.port}"

            def predict(obs):
                req = urllib.request.Request(
                    url + "/predict",
                    _json.dumps({"obs": obs}).encode(),
                    {"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=10) as r:
                    return _json.loads(r.read())

            if predict([1.0])["action"] != [2.0]:
                problems.append("routed predict answered wrong")
            a.shutdown()
            a.server_close()
            for i in range(4):  # must fail over to rb, zero errors
                got = predict([float(i)])["action"]
                if got != [2.0 * i]:
                    problems.append(f"failover answer wrong: {got}")
            st = router.stats()
            retries = st["counters"].get("router_retries_total", 0)
            with urllib.request.urlopen(url + "/metrics",
                                        timeout=10) as r:
                body = r.read().decode()
            parse_exposition(body)
            if "estorch_router_breaker_state" not in body:
                problems.append("per-replica breaker gauge missing "
                                "from /metrics")
            return {"ok": not problems, "retries": int(retries),
                    "breakers": {x["name"]: x["breaker"]
                                 for x in st["replicas"]},
                    **({"problems": problems} if problems else {})}
        finally:
            router.shutdown(drain=False)
            b.shutdown()
            b.server_close()
    except Exception as e:  # diagnostic tool: never crash the report
        return {"ok": False, "error": repr(e)}


def check_tracing() -> dict:
    """Can this host assemble a CROSS-PROCESS distributed trace?
    (obs/tracing.py + obs/agg/traces.py, docs/observability.md
    "Distributed tracing")

    Loopback end-to-end probe, jax-free: a real :class:`Router` with a
    run dir routes one forced-sampled request (``X-Trace-Sampled: 1``)
    to a toy stdlib replica that keeps its OWN :class:`ProcessTracer`
    and records a ``request`` segment parented on the router's
    forwarded ``X-Parent-Span``.  Both processes' tracers flush, then
    assembly (``obs trace --fleet``'s engine) must join the trace
    across both, with at least one cross-process parent→child hop, and
    the Perfetto export must validate.  Never crashes the report: any
    failure comes back as ``{"ok": False, ...}``."""
    import json as _json
    import os
    import tempfile
    import threading
    import time as _time
    import urllib.request
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    try:
        from .obs.agg import traces as traces_agg
        from .obs.export.traceevent import validate_trace
        from .obs.tracing import (PARENT_SPAN_HEADER, SAMPLED_HEADER,
                                  TRACE_HEADER, TRACES_FILENAME,
                                  ProcessTracer, make_segment)
        from .serve.router import Router

        problems: list[str] = []
        trace_id = "doctor-trace-1"
        with tempfile.TemporaryDirectory() as td:
            replica_dir = os.path.join(td, "replica")
            os.makedirs(replica_dir)
            tracer = ProcessTracer(
                "replica", head_every=1,
                path=os.path.join(replica_dir, TRACES_FILENAME))

            class Toy(BaseHTTPRequestHandler):
                protocol_version = "HTTP/1.1"

                def log_message(self, *a):
                    pass

                def _j(self, obj):
                    body = _json.dumps(obj).encode()
                    self.send_response(200)
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)

                def do_GET(self):
                    if self.path == "/healthz":
                        self._j({"ok": True, "draining": False,
                                 "queue_depth": 0})
                    else:
                        self._j({"queue_depth": 0,
                                 "request_ms": {"p99": 1.0}})

                def do_POST(self):
                    t0 = _time.monotonic()
                    trace = self.headers.get(TRACE_HEADER) or ""
                    parent = self.headers.get(PARENT_SPAN_HEADER) or None
                    forced = self.headers.get(SAMPLED_HEADER) == "1"
                    n = int(self.headers.get("Content-Length", 0))
                    data = _json.loads(self.rfile.read(n))
                    self._j({"action": [v * 2.0 for v in data["obs"]]})
                    if trace:
                        dt = _time.monotonic() - t0
                        tracer.add(make_segment(
                            trace, tracer.span_id(), parent, "replica",
                            "request", t0, dt, {"status": 200}))
                        tracer.finish(trace, dt, forced=forced)

            srv = ThreadingHTTPServer(("127.0.0.1", 0), Toy)
            threading.Thread(target=srv.serve_forever, daemon=True).start()
            router_dir = os.path.join(td, "router")
            router = Router(
                [("ra", f"127.0.0.1:{srv.server_address[1]}")],
                port=0, poll_interval_s=30.0, upstream_timeout_s=5.0,
                run_dir=router_dir)
            router.start_background()
            try:
                req = urllib.request.Request(
                    f"http://{router.host}:{router.port}/predict",
                    _json.dumps({"obs": [1.0]}).encode(),
                    {"Content-Type": "application/json",
                     TRACE_HEADER: trace_id, SAMPLED_HEADER: "1"})
                with urllib.request.urlopen(req, timeout=10) as r:
                    got = _json.loads(r.read())
                    echoed = r.headers.get(TRACE_HEADER)
                if got.get("action") != [2.0]:
                    problems.append(f"routed predict answered wrong: {got}")
                if echoed != trace_id:
                    problems.append(
                        f"router did not echo {TRACE_HEADER}: {echoed!r}")
            finally:
                router.shutdown(drain=False)
                srv.shutdown()
                srv.server_close()
            tracer.flush()

            segs = traces_agg.load_segments(traces_agg.trace_files([td]))
            asm = traces_agg.assemble(segs)
            trace = asm.get(trace_id)
            if trace is None:
                problems.append(
                    f"trace {trace_id!r} did not assemble "
                    f"(got {sorted(asm)})")
                return {"ok": False, "problems": problems}
            if len(trace["procs"]) < 2:
                problems.append(
                    f"trace did not cross processes: {trace['procs']}")
            hops = traces_agg.cross_process_edges(trace)
            if not hops:
                problems.append("no cross-process parent->child hop — "
                                "X-Parent-Span not propagated")
            export = traces_agg.export_fleet_trace([trace])
            errs = validate_trace(export)
            if errs:
                problems.append(f"perfetto export invalid: {errs[:3]}")
            return {"ok": not problems, "procs": trace["procs"],
                    "segments": len(trace["segments"]),
                    "cross_hops": len(hops),
                    "sampled": trace.get("sampled"),
                    **({"problems": problems} if problems else {})}
    except Exception as e:  # diagnostic tool: never crash the report
        return {"ok": False, "error": repr(e)}


def check_collector() -> dict:
    """Can this host run the fleet-aggregation plane?  (obs/agg/,
    docs/observability.md "Fleet aggregation")

    Loopback end-to-end probe: spin a synthetic target (the metrics
    sidecar over a temp run dir with a fresh heartbeat), point a
    collector with an absence rule at it PLUS a dead port, run one
    collection tick, and assert the full chain — sample stored in the
    time-series store, rules evaluated (the dead target's absence rule
    fires, the live one's does not), and the collector's ``/alerts`` and
    ``/metrics`` parse over loopback.  Stdlib only, never touches jax,
    and never crashes the report: a refused port or any other failure
    comes back as ``{"ok": False, "error"/"problems": ...}``."""
    import json as _json
    import os
    import socket
    import tempfile
    import time as _time
    import urllib.request

    try:
        from .obs.agg.collector import Collector, Target
        from .obs.agg.rules import RulesEngine
        from .obs.agg.store import SeriesStore
        from .obs.export.prometheus import parse_exposition
        from .obs.export.sidecar import MetricsSidecar

        problems = []
        with tempfile.TemporaryDirectory() as d:
            run_dir = os.path.join(d, "run")
            os.makedirs(run_dir)
            with open(os.path.join(run_dir, "heartbeat.json"), "w") as f:
                _json.dump({"ts": _time.time(), "pid": os.getpid(),
                            "phase": "doctor_probe", "generation": 1,
                            "counters": {"env_steps": 3}}, f)
            sidecar = MetricsSidecar(run_dir, port=0)
            sidecar.start_background()
            # bound-but-not-listening: connects get RST for the whole
            # probe (closing it would race the port back to the
            # allocator, which could hand it to the collector itself)
            dead_sock = socket.socket()
            dead_sock.bind(("127.0.0.1", 0))
            dead_port = dead_sock.getsockname()[1]
            col = None
            try:
                store = SeriesStore(os.path.join(d, "store"))
                rules = RulesEngine([
                    {"name": "replica-down", "kind": "absence",
                     "metric": "estorch_up", "for_s": 0, "window_s": 30},
                ])
                col = Collector(
                    [Target("probe-run",
                            url=f"http://{sidecar.host}:{sidecar.port}"
                                "/metrics", timeout_s=5.0),
                     Target("probe-dead",
                            url=f"http://127.0.0.1:{dead_port}/metrics",
                            timeout_s=0.5)],
                    store, rules, port=0)
                col.start_background()
                now = _time.time()
                tick = col.tick(now)
                if not tick["targets"]["probe-run"]["ok"]:
                    problems.append(
                        f"live target scrape failed: {tick}")
                stored = store.latest("estorch_env_steps",
                                      {"target": "probe-run"},
                                      window_s=60, now=now)
                if not stored:
                    problems.append("scraped sample not found in store")
                fired = {(t["rule"], t["target"])
                         for t in tick["transitions"]
                         if t["event"] == "firing"}
                if ("replica-down", "probe-dead") not in fired:
                    problems.append(
                        f"absence rule did not fire for the dead "
                        f"target: {fired}")
                if ("replica-down", "probe-run") in fired:
                    problems.append("absence rule fired for the live "
                                    "target")
                base = f"http://{col.host}:{col.port}"
                with urllib.request.urlopen(base + "/alerts",
                                            timeout=10) as resp:
                    alerts = _json.loads(resp.read().decode())
                if not any(a["rule"] == "replica-down"
                           and a["target"] == "probe-dead"
                           for a in alerts["active"]):
                    problems.append(f"/alerts missing the active "
                                    f"absence alert: {alerts}")
                with urllib.request.urlopen(base + "/metrics",
                                            timeout=10) as resp:
                    parse_exposition(resp.read().decode())
            finally:
                if col is not None:
                    col.close()
                dead_sock.close()
                sidecar.close()
        return {"ok": not problems,
                **({"problems": problems} if problems else {})}
    except Exception as e:  # diagnostic tool: never crash the report
        return {"ok": False, "error": repr(e)}


def check_autoscaler() -> dict:
    """Can this host close the serving control loop?  (obs/agg/
    autoscale.py, docs/serving.md "Autoscaling")

    Loopback decision dry-run: seed a synthetic store with a demand
    ramp, write a matching capacity artifact, and run one control cycle
    with ``dry_run`` — the decision must be a scale-up, logged to the
    append-only decision log, and the log must replay bit-exactly.  A
    mismatched capacity model (wrong bundle sha) must be REFUSED.
    Stdlib only, never touches jax, never crashes the report."""
    import json as _json
    import os
    import tempfile

    try:
        from .obs.agg import autoscale as _az
        from .obs.agg.store import SeriesStore

        problems = []
        with tempfile.TemporaryDirectory() as d:
            store = SeriesStore(os.path.join(d, "store"))
            t0 = 1_000_000.0
            for ts, total in ((t0, 0.0), (t0 + 10, 100.0)):
                store.append([
                    {"name": "estorch_router_requests_total",
                     "labels": {"target": "probe"}, "value": total},
                    {"name": "estorch_router_replica_up",
                     "labels": {"target": "probe", "replica": "r0"},
                     "value": 1.0},
                ], ts=ts)
            cap_path = os.path.join(d, "capacity.json")
            capacity = {"schema": _az.CAPACITY_SCHEMA, "kind": "capacity",
                        "created_ts": t0, "slo_ms": 50.0,
                        "quantile": "p99", "max_rps_at_slo": 5.0,
                        "saturated": False,
                        "rungs": [{"offered_rps": 5.0, "ok": True}],
                        "bundle_sha": "ab" * 32, "bundle_version": 1,
                        "platform": "cpu"}
            with open(cap_path, "w") as f:
                _json.dump(capacity, f)
            bad = _az.validate_capacity(capacity)
            if bad:
                problems.append(f"capacity artifact rejected: {bad}")
            az = _az.Autoscaler(
                os.path.join(d, "store"), capacity=cap_path,
                fleet_identity={"bundle_sha": "ab" * 32,
                                "platform": "cpu"},
                policy={"min_replicas": 1, "max_replicas": 8,
                        "window_s": 10.0}, dry_run=True)
            # 10 rps against 5 rps/replica: the only sane verdict is up
            ev = az.tick(now=t0 + 10)
            if ev is None or ev["verdict"]["action"] != "up":
                problems.append(f"dry-run decision not a scale-up: "
                                f"{ev and ev['verdict']}")
            elif ev["actuation"] != {"attempted": False,
                                     "dry_run": True}:
                problems.append(f"dry-run actuated: {ev['actuation']}")
            rep = _az.replay(az.log_path)
            if not rep["ok"]:
                problems.append(f"decision log replay mismatch: "
                                f"{rep['mismatches'][:2]}")
            try:
                _az.Autoscaler(
                    os.path.join(d, "store"), capacity=cap_path,
                    fleet_identity={"bundle_sha": "cd" * 32,
                                    "platform": "cpu"},
                    dry_run=True)
                problems.append("mismatched capacity model accepted")
            except _az.AutoscaleError as e:
                # the refusal IS the pass; gate that it names both shas
                if "cd" * 6 not in str(e):
                    problems.append(
                        f"mismatch refusal names neither sha: {e}")
        return {"ok": not problems,
                **({"problems": problems} if problems else {})}
    except Exception as e:  # diagnostic tool: never crash the report
        return {"ok": False, "error": repr(e)}


def report(timeout_s: float = 45.0, run_dir: str | None = None,
           resilience_probe: bool = False,
           serve_bundle: str | None = None) -> dict:
    # ONE staged probe serves both rows: the typed verdict (the row
    # bench.py's platform decision reads — no-device / init-hang /
    # compile-hang / exec-hang, docs/observability.md "Profiling") and
    # the legacy healthy/wedged/error summary derived from it, so a
    # wedged host costs one timeout, not two serial ones.  The caller's
    # timeout_s (--timeout) rules: capping it here would classify a
    # slow-but-healthy host as wedged, the exact false alarm a larger
    # --timeout is passed to avoid.  probe_device remains available for
    # callers that want the bare wedge check.
    probe = check_device(timeout_s=timeout_s)
    if probe["status"] == "ok":
        dev = {"status": "healthy", "platform": probe["platform"],
               "n_devices": probe["n_devices"]}
    elif str(probe.get("reason", "")).endswith("-hang"):
        dev = {"status": "wedged", "timeout_s": probe["timeout_s"],
               "stderr_tail": probe.get("stderr_tail", "")}
        if probe.get("unreapable_child"):
            dev["unreapable_child"] = True
    else:
        dev = {"status": "error",
               "stderr_tail": probe.get("stderr_tail", "")}
    rep = {
        "device": dev,
        "device_probe": probe,
        "native": check_native_pool(),
        "mesh": check_mesh(),
        "elastic": check_elastic(),
        "scenarios": check_scenarios(),
        "optional": check_optional_deps(),
        "host": check_host(),
        "obs": check_obs(run_dir),
        "collector": check_collector(),
        "resilience": check_resilience(probe=resilience_probe),
        "serve": check_serve(bundle=serve_bundle),
        "router": check_router(),
        "tracing": check_tracing(),
        "autoscaler": check_autoscaler(),
    }
    if dev["status"] == "wedged":
        rep["hint"] = (
            "device runtime is hung (not merely compiling): a chip belongs "
            "to one process at a time, so look for another process that "
            "still holds it (a parent that touched jax before spawning "
            "this one, a server left running) and stop it; nothing "
            "measured on another backend stands in for the chip"
        )
    elif dev["status"] == "error":
        rep["hint"] = (
            "backend failed fast (see stderr_tail) — a clean init error, "
            "not a wedge: fix the installation or JAX_PLATFORMS.  The "
            "virtual CPU mesh (JAX_PLATFORMS=cpu, utils.force_cpu_backend"
            "(8)) is for tests and dry runs, not a substitute for the chip"
        )
    return rep


def main(argv=None):
    import argparse

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--timeout", type=float, default=45.0,
                   help="device probe timeout in seconds")
    p.add_argument("--run-dir", default=None, metavar="DIR",
                   help="training run directory: report heartbeat "
                        "freshness for a run that stopped answering")
    p.add_argument("--resilience-probe", action="store_true",
                   help="also run the checkpoint save/restore round-trip "
                        "probe (a tiny ES in a timed-out subprocess)")
    p.add_argument("--bundle", default=None, metavar="DIR",
                   help="policy bundle to validate (manifest schema + "
                        "payload checksum, no jax import)")
    args = p.parse_args(argv)
    rep = report(args.timeout, run_dir=args.run_dir,
                 resilience_probe=args.resilience_probe,
                 serve_bundle=args.bundle)
    print(json.dumps(rep, indent=2))
    return 0 if rep["device"]["status"] == "healthy" else 1


if __name__ == "__main__":
    sys.exit(main())
