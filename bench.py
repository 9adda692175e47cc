"""Benchmark: device-native ES generation throughput on the flagship config.

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "extras": {...}}

Metric: env-steps/sec/chip (BASELINE.json primary metric) for a full ES
generation — noise-table perturbation, vmapped policy rollouts, centered
ranks, psum'd rank-weighted update — on Pendulum (never terminates, so every
scanned step is a real env step; no done-mask inflation) with a 64x64 MLP,
population 4096, horizon 200: ~819k env steps per generation.

extras: a Humanoid-sized-policy point (SyntheticEnv obs 376 → 256×256 → 17,
the __graft_entry__ flagship shape), a pop-10240 point, and a
physics-on-chip locomotion point (Cheetah2D — never terminates, so its
step counts carry the same honesty property; its MFU counts policy-forward
FLOPs only, not the physics).  "mfu" is policy-forward FLOPs against the
published bf16 peak of the chip's ``device_kind`` (obs/profile/
roofline.py; an unknown kind is an error) regardless of config dtype —
one fixed denominator keeps cross-dtype A/B numbers comparable.  Per-phase
achieved rates and the compile ledger ride each row (``phases`` /
``compile``).

The measured path has NO fallback: the typed device probe
(doctor.check_device(platform="tpu"): alive-or-wedged in seconds with a
no-device / wrong-platform / init-hang / compile-hang / exec-hang
reason) must find the chip, and every stage must succeed, or the run
exits non-zero with one line saying why.  ``--cpu`` is the explicit
request the CPU tests and dry runs make; a CPU run is labelled
``env_steps_per_sec_cpu_mesh``, never ``env_steps_per_sec_per_chip``,
and carries no MFU.

vs_baseline: ratio against a reference-style estorch loop measured live on
this host — per-member Python loop, torch CPU MLP forward per step,
gymnasium Pendulum env.step — the architecture SURVEY.md §3.2/§3.3 documents
(single process; the reference scales it by n_proc workers, so divide by
core count for a per-core figure if comparing to the 720-core runs).

Stage protocol (each stage is a child process: a chip belongs to one
process at a time, so the parent stays jax-free and each child is the
only process on the chip while it lives):
    bench.py --stage-one '<json cfg>'   measure one config, print one JSON
                                        (fails without a TPU; add --cpu to
                                        ask for the CPU mesh — harness
                                        validation only)
    bench.py --stage-ab                 run the curated A/B subset (see
                                        AB_MATRIX; not a full cross),
                                        one JSON line per config as it lands
    bench.py --obs-ab                   telemetry-overhead A/B: spans on vs
                                        off on the headline config (the <2%
                                        observability acceptance gate)
    bench.py --chaos [--selfcheck]      recovery-overhead A/B: a host
                                        process-worker run with a 1-worker-
                                        kill-per-20-generations chaos plan
                                        vs the same run clean — measures
                                        what respawn+retry cost, and proves
                                        participation stays full under
                                        faults.  --selfcheck shrinks it to
                                        the run_lint.sh gate: nonzero exit
                                        when recovery did not actually
                                        recover
    bench.py --async-ab [--selfcheck]   barrier-vs-async scheduler A/B
                                        (estorch_tpu/algo/scheduler.py,
                                        docs/async.md): the same tiny
                                        host run under an identical
                                        deterministic straggler plan,
                                        once through ES.train's barrier
                                        loop and once through the event-
                                        driven fold scheduler — medians
                                        + a noise band learned from
                                        interleaved repeats (obs
                                        regress), gating the >=1.25x
                                        throughput win, step ≈
                                        max(eval, update) from the
                                        per-phase spans, and the zero-
                                        silent-drop fold accounting
    bench.py --regress [BASELINE.json]  perf gate (estorch_tpu/obs/export/
                                        regress.py): measure the headline
                                        config `--repeats` times (fresh
                                        stage children), compare the
                                        median against the committed
                                        BENCH_*.json baseline with a
                                        noise band learned from the
                                        repeats; exit 1 on regression.
                                        Defaults to the newest BENCH_r*
                                        file (fails without a TPU; --cpu
                                        asks for the CPU mesh — only
                                        gate against a baseline measured
                                        on the same platform)
    bench.py --serve [--selfcheck]      serving A/B (estorch_tpu/serve,
                                        docs/serving.md): export a trained
                                        pendulum bundle, serve it, drive
                                        closed-loop load — dynamic batching
                                        vs the same server at max_batch=1.
                                        Gates bit-exact responses, clean
                                        SIGTERM drain, recompiles ≤
                                        n_buckets; the full form also gates
                                        the ≥3x batching win on a big
                                        (memory-bound) policy.  --selfcheck
                                        shrinks the policy to the
                                        run_lint.sh functional gate
    bench.py                            headline + extras, the driver entry

Every stage child writes a heartbeat file (ESTORCH_OBS_HEARTBEAT →
estorch_tpu/obs/recorder.py): a stage timeout reports the child's last
phase + generation + heartbeat age instead of guessing at a wedge.
"""

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np


def _load_repo_module(name, *relpath):
    """Load a repo module by FILE, without the package __init__.

    The loaded modules are jax-free, but `import estorch_tpu...`
    executes the package init, which imports jax — and importing jax in
    THIS process would touch the possibly-wedged device runtime before
    the stage protocol's subprocess+timeout isolation can protect us
    (the round-1 lesson the whole stage design exists for).  A direct
    file load keeps one implementation of each protocol while keeping
    the bench driver accelerator-free."""
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        *relpath)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


obs_recorder = _load_repo_module("_estorch_obs_recorder",
                                 "estorch_tpu", "obs", "recorder.py")
HEARTBEAT_ENV = obs_recorder.HEARTBEAT_ENV
describe_heartbeat = obs_recorder.describe_heartbeat
read_heartbeat = obs_recorder.read_heartbeat


def _load_obs_regress():
    """estorch_tpu/obs/export/regress.py, same jax-free contract."""
    return _load_repo_module("_estorch_obs_regress",
                             "estorch_tpu", "obs", "export", "regress.py")


def _load_doctor():
    """estorch_tpu/doctor.py by file: check_device (the typed staged
    probe the platform decision reads) is stdlib-only — the whole module
    imports jax-free, same contract as the recorder/regress loads."""
    return _load_repo_module("_estorch_doctor", "estorch_tpu", "doctor.py")


# ---------------------------------------------------------------------
# crash-durable scratch: per-driver-pid workdir + stale-artifact sweep
# ---------------------------------------------------------------------

_BENCH_TMP_ROOT = os.path.join(tempfile.gettempdir(), "estorch_bench")


def _bench_workdir() -> str:
    """Per-process scratch dir for crash-durable buffers (stage
    heartbeats, capture histories).  Kept when this process dies a
    fatal-signal death (the diagnostics must survive the crash), removed
    on clean driver exit, and swept by :func:`_sweep_stale_bench_dirs`
    on the NEXT driver run once the owning pid is gone — so crashed runs
    cannot accumulate in the temp dir forever."""
    d = os.path.join(_BENCH_TMP_ROOT, str(os.getpid()))
    os.makedirs(d, exist_ok=True)
    return d


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True  # exists, someone else's
    return True


def _sweep_stale_bench_dirs() -> None:
    """Remove bench scratch left by CRASHED prior runs: per-pid workdirs
    whose owner is gone, plus the legacy flat-file buffers
    (``bench_stderr_<pid>.log`` / ``bench_hb_<pid>_*.json``) older
    drivers wrote straight into the temp dir."""
    import glob
    import re as _re
    import shutil

    if os.path.isdir(_BENCH_TMP_ROOT):
        for name in os.listdir(_BENCH_TMP_ROOT):
            path = os.path.join(_BENCH_TMP_ROOT, name)
            try:
                pid = int(name)
            except ValueError:
                continue  # not ours to judge
            if not _pid_alive(pid):
                shutil.rmtree(path, ignore_errors=True)
    tmp = tempfile.gettempdir()
    for pattern in ("bench_stderr_*.log", "bench_hb_*.json"):
        for path in glob.glob(os.path.join(tmp, pattern)):
            m = _re.search(r"_(\d+)", os.path.basename(path))
            if m and not _pid_alive(int(m.group(1))):
                try:
                    os.remove(path)
                except OSError:
                    pass


def _cleanup_bench_workdir() -> None:
    """Clean-exit removal of this process's scratch dir (a crash skips
    this by construction — that is the point of the buffers)."""
    import shutil

    shutil.rmtree(os.path.join(_BENCH_TMP_ROOT, str(os.getpid())),
                  ignore_errors=True)

# The XLA:CPU persistent-cache loader logs an E-level machine-feature dump
# even for same-machine pseudo-feature mismatches (+prefer-no-scatter etc.,
# utils/backend.py::enable_compilation_cache docstring).  It dominated the
# committed BENCH_r03.json tail looking like a SIGILL hazard; these markers
# identify its lines so the recorded artifact leads with signal.
_XLA_NOISE_MARKERS = (
    "XLA:CPU AOT result",
    "machine features",
    "Machine type used for XLA:CPU compilation",
)


def _clean_stderr(text: str) -> str:
    """Drop the known-noisy XLA:CPU AOT feature-mismatch dump lines."""
    return "\n".join(
        ln for ln in text.splitlines()
        if not any(m in ln for m in _XLA_NOISE_MARKERS)
    )


SMALL = {"env": "pendulum", "hidden": [64, 64], "population": 4096,
         "horizon": 200}
BIG = {"env": "synthetic", "hidden": [256, 256], "population": 4096,
       "horizon": 200}
POP10K = {"env": "synthetic", "hidden": [256, 256], "population": 10240,
          "horizon": 200, "eval_chunk": 1024}  # bound materialized member
# weights: whole-shard at 10240x166k floats would gamble with 16 GB HBM
LOCO = {"env": "cheetah2d", "hidden": [64, 64], "population": 1024,
        "horizon": 200}  # physics-on-chip point (cheetah2d_device recipe)
LOCO10K = {"env": "humanoid2d", "hidden": [256, 256], "population": 10240,
           "horizon": 100, "eval_chunk": 1024}  # config-3 scale with
# physics: the humanoid2d_pop10k recipe's shape at horizon 100 (a bench
# row, not a training run — scan length and alive-step fraction differ)


def _env_and_policy(cfg):
    from estorch_tpu.envs import (Cheetah2D, Humanoid2D, Pendulum,
                                  SyntheticEnv)

    if cfg["env"] == "pendulum":
        env = Pendulum()
        pk = {"action_dim": 1, "hidden": tuple(cfg["hidden"]),
              "discrete": False, "action_scale": 2.0}
    elif cfg["env"] in ("cheetah2d", "humanoid2d"):
        # device-native physics INSIDE the generation program; the cheetah
        # never terminates, so every scanned step is a real env step (same
        # honesty property the Pendulum headline relies on).  The humanoid
        # terminates on falls — its steps/s reflects the done-mask like a
        # real training run
        env = Cheetah2D() if cfg["env"] == "cheetah2d" else Humanoid2D()
        pk = {"action_dim": env.action_dim, "hidden": tuple(cfg["hidden"]),
              "discrete": False, "action_scale": 1.0}
    else:
        env = SyntheticEnv()
        pk = {"action_dim": env.action_dim, "hidden": tuple(cfg["hidden"]),
              "discrete": False, "action_scale": 1.0}
    return env, pk


def policy_flops_per_member_step(cfg):
    """2·Σ(m·n) over the MLP's matmuls — the MXU work per member env-step."""
    env, _ = _env_and_policy(cfg)
    dims = [env.obs_dim, *cfg["hidden"], env.action_dim]
    return 2 * sum(a * b for a, b in zip(dims[:-1], dims[1:]))


class NoTpuError(RuntimeError):
    """The measured path found no TPU and ``--cpu`` was not asked for."""


def measure_one(cfg, force_cpu=False):
    """Run one config; returns dict(rate, platform, mfu, ...).  Raises
    :class:`NoTpuError` unless it runs on a TPU or ``force_cpu`` asked
    for the CPU mesh — there is no fallback."""
    if force_cpu:
        from estorch_tpu.utils import force_cpu_backend

        force_cpu_backend(8)
    # stages are fresh subprocesses: persist XLA executables so repeated
    # configs (headline rerun, A/B repeats) skip the compile; compile time
    # never counts toward the metric either way
    from estorch_tpu.utils import enable_compilation_cache

    enable_compilation_cache()
    import jax
    import optax

    from estorch_tpu import ES, JaxAgent, MLPPolicy

    found = jax.devices()[0].platform
    if not force_cpu and found != "tpu":
        raise NoTpuError(
            f"no TPU: jax came up on platform {found!r} "
            f"({len(jax.devices())} device(s)); pass --cpu to ask for the "
            "CPU mesh explicitly")
    env, pk = _env_and_policy(cfg)
    on_tpu = found == "tpu"
    # the param-sharded engine (estorch_tpu/parallel/sharded.py,
    # docs/sharding.md) is f32-only; replicated rows keep the platform
    # default
    shard = bool(cfg.get("shard"))
    dtype = cfg.get("dtype",
                    "float32" if shard
                    else ("bfloat16" if on_tpu else "float32"))
    shard_kwargs = {}
    if shard:
        shard_kwargs = dict(
            shard_params=True,
            model_shards=cfg.get("model_shards"),
            noise_mode=cfg.get("noise_mode", "auto"),
        )
    es = ES(
        policy=MLPPolicy,
        agent=JaxAgent,
        optimizer=optax.adam,
        population_size=cfg["population"],
        sigma=0.05,
        policy_kwargs=pk,
        agent_kwargs={"env": env, "horizon": cfg["horizon"]},
        optimizer_kwargs={"learning_rate": 1e-2},
        eval_chunk=cfg.get("eval_chunk", 0),
        compute_dtype=dtype,
        low_rank=cfg.get("low_rank", 0),
        obs_norm=cfg.get("obs_norm", False),
        # default None: spans on, heartbeat picked up from the env var the
        # stage parent set.  The --obs-ab rows pass an explicit bool to
        # measure the spans' own overhead
        telemetry=cfg.get("telemetry"),
        **shard_kwargs,
    )
    gens = cfg.get("gens", 5)
    es.train(1, verbose=False)  # warm-up generation (compile + AOT sanity)
    t0 = time.perf_counter()
    es.train(gens, verbose=False)
    dt = time.perf_counter() - t0
    steps = sum(r["env_steps"] for r in es.history[-gens:])
    n_chips = es.mesh.devices.size
    rate = steps / dt / n_chips
    platform = es.mesh.devices.flat[0].platform

    # memory evidence rides along with every point: device peak HBM (TPU
    # PJRT memory_stats; absent on the CPU backend) and host peak RSS —
    # the noise-table/chunking sizing claims need numbers, not prose
    peak_hbm = None
    if platform == "tpu":
        stats = es.mesh.devices.flat[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        peak_hbm = round(peak / 2**30, 3) if peak else None
    import resource

    # ru_maxrss is KiB on Linux but bytes on macOS
    rss_div = 2**30 if sys.platform == "darwin" else 2**20
    peak_rss = round(
        resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / rss_div, 3
    )

    # MFU exists on the chip only: the published bf16 peak of its
    # device_kind (cross-dtype comparability; an unknown kind raises).
    # Off the chip it is null — a CPU rate over a CPU ceiling is not a
    # utilization of anything a user pays for
    from estorch_tpu.obs.profile import device_roofline, profile_records

    # MFU numerator comes from the run's OWN cost model when one was
    # built (shard-aware since the sharded engine landed: noise mode,
    # low-rank forward term, per-device attribution ride along); the
    # static helper is the fallback for telemetry-off rows
    cost_model = getattr(es.obs, "cost_model", None) or {}
    flops_per_step = (cost_model.get("flops_per_env_step")
                      or policy_flops_per_member_step(cfg))
    if platform == "tpu":
        roof = device_roofline(es.mesh.devices.flat[0].device_kind)
        mfu = rate * flops_per_step / roof["peak_flops_per_s"]
        mfu_basis = roof["basis"]
    else:
        roof = {"platform": platform, "basis": None,
                "peak_flops_per_s": None, "peak_bytes_per_s": None}
        mfu = mfu_basis = None

    # per-phase attribution of the measured generations (obs/profile/):
    # seconds share + achieved FLOP/s per phase, the compile ledger, and
    # the analytic-vs-XLA cross-check ride the bench row
    phases = None
    compile_block = None
    try:
        # full history, not just the timed window: the compile ledger
        # flushed into the warm-up generation's record, and the warm-up's
        # spans are as representative as the timed ones for attribution
        prof = profile_records(es.history, roof,
                               cost_model=es.obs.cost_model)
        phases = {
            name: {k: (round(v, 8) if isinstance(v, float) else v)
                   for k, v in row.items()
                   if k in ("share", "seconds", "flops_per_s", "mfu",
                            "arith_intensity", "bound")}
            for name, row in (prof.get("phases") or {}).items()
        }
        compile_block = prof.get("compile")
    except Exception as e:  # noqa: BLE001 — attribution must not kill a row
        print(f"bench: phase attribution failed: {e!r}", file=sys.stderr)
    out = {
        "rate": rate,
        "platform": platform,
        "device_kind": es.mesh.devices.flat[0].device_kind,
        "dtype": dtype,
        "mfu": round(mfu, 8) if mfu is not None else None,
        "mfu_basis": mfu_basis,
        "phases": phases,
        "compile": compile_block,
        "peak_hbm_gb": peak_hbm,
        "peak_rss_gb": peak_rss,
        "cfg": cfg,
    }
    hist_out = cfg.get("history_out")
    if hist_out:
        # per-generation records for the baseline-capture path: exactly
        # the keys the regress phase/tail gates consume, written
        # atomically like every other artifact.  history_skip drops the
        # leading warm-up/compile records — a committed TAIL baseline
        # whose p99 is a compile spike would wave real steady-state
        # regressions through (p99 of ~35 samples is the max sample)
        skip = max(0, int(cfg.get("history_skip", 1)))
        keep = ("generation", "env_steps", "env_steps_per_sec",
                "wall_time_s", "phases", "reward_mean")
        tmp = hist_out + ".tmp"
        with open(tmp, "w") as f:
            for rec in es.history[skip:]:
                f.write(json.dumps({k: rec[k] for k in keep if k in rec},
                                   default=float) + "\n")
        os.replace(tmp, hist_out)
    if shard:
        # peak-memory extras: XLA's per-device argument/output/temp bytes
        # for the compiled (sharded, donated) generation program — with
        # sharded inputs those ARE shard sizes (compile ledger contract)
        out["shard"] = {
            "noise_mode": es.engine.noise_mode,
            "mesh": {"pop": es.engine.pop_shards,
                     "model": es.engine.model_shards},
            "per_device_peak_bytes": es.engine.memory_facts().get(
                "peak_bytes"),
            "mfu_from_cost_model": bool(
                cost_model.get("flops_per_env_step")),
        }
    return out


def measure_reference_style_baseline(budget_s=6.0) -> float:
    """Single-process estorch-style loop: torch MLP + gymnasium Pendulum."""
    import gymnasium as gym
    import torch

    policy = torch.nn.Sequential(
        torch.nn.Linear(3, 64), torch.nn.Tanh(),
        torch.nn.Linear(64, 64), torch.nn.Tanh(),
        torch.nn.Linear(64, 1), torch.nn.Tanh(),
    )
    env = gym.make("Pendulum-v1")
    obs, _ = env.reset(seed=0)
    steps = 0
    t0 = time.perf_counter()
    with torch.no_grad():
        while time.perf_counter() - t0 < budget_s:
            for _ in range(200):
                a = policy(torch.from_numpy(np.asarray(obs, dtype=np.float32)))
                obs, r, term, trunc, _ = env.step(a.numpy() * 2.0)
                steps += 1
                if term or trunc:
                    obs, _ = env.reset()
    env.close()
    return steps / (time.perf_counter() - t0)


def run_stage_detailed(cfg, timeout_s=480, force_cpu=False):
    """One config in a child with a hard timeout — the device runtime can
    wedge at init OR mid-run, and the driver must still report it.  Always
    returns a row dict with a "rate" key (None on failure, plus "error" /
    "stderr_tail" saying why) — the machine-readable form the on-chip A/B
    artifact records, so a failed row's diagnosis survives in the artifact
    instead of only on a long-gone stderr.  A child that exits non-zero
    is a failed stage whatever it printed.

    Every stage child runs with a heartbeat file (obs/recorder.py
    protocol): on timeout the failure line carries the child's last
    phase + generation + heartbeat age instead of a guess — "wedged in
    phase=device at gen 0, silent for 470s" vs "slow but beating"."""
    hb_path = os.path.join(
        _bench_workdir(),
        f"hb_{abs(hash(json.dumps(cfg, sort_keys=True))) % 10**8}.json",
    )
    try:
        argv = [sys.executable, __file__, "--stage-one", json.dumps(cfg)]
        if force_cpu:
            argv.append("--cpu")
        try:
            r = subprocess.run(
                argv, timeout=timeout_s, capture_output=True, text=True,
                env={**os.environ, HEARTBEAT_ENV: hb_path},
            )
        except subprocess.TimeoutExpired:
            row = {"rate": None, "cfg": cfg,
                   "error": (f"timeout after {timeout_s}s "
                             f"({describe_heartbeat(hb_path)})")}
            hb = read_heartbeat(hb_path)
            if hb is not None:
                row["heartbeat"] = hb
            return row
    finally:
        try:
            os.remove(hb_path)
        except OSError:
            pass
    if r.returncode != 0:
        return {"rate": None, "cfg": cfg,
                "error": f"stage exited {r.returncode}",
                "stderr_tail": _clean_stderr(r.stderr)[-800:]}
    try:
        last = [ln for ln in r.stdout.strip().splitlines()
                if ln.startswith("{")][-1]
        out = json.loads(last)
        float(out["rate"]), str(out["platform"]), str(out["dtype"])
        _ = out["mfu"]  # null off-TPU, but the key must exist
        _ = out["peak_hbm_gb"], out["peak_rss_gb"]  # memory evidence keys
        return out
    except (IndexError, KeyError, TypeError, ValueError):
        return {"rate": None, "cfg": cfg, "error": "unparseable",
                "stdout_tail": r.stdout[-500:],
                "stderr_tail": _clean_stderr(r.stderr)[-800:]}


def run_stage(cfg, timeout_s=480, force_cpu=False):
    """run_stage_detailed, collapsed to the dict-or-None contract the
    headline path uses; failure diagnostics go to OUR stderr (the JSON-line
    contract owns stdout only)."""
    out = run_stage_detailed(cfg, timeout_s=timeout_s, force_cpu=force_cpu)
    if out.get("rate") is None:
        print(f"bench: stage failed cfg={cfg}: {out.get('error')}\n"
              f"{out.get('stdout_tail', '')}\n{out.get('stderr_tail', '')}",
              file=sys.stderr)
        return None
    return out


AB_MATRIX = [
    # (label, base-config, overrides)
    ("small/standard/f32", SMALL, {"dtype": "float32"}),
    ("small/standard/bf16", SMALL, {"dtype": "bfloat16"}),
    ("big/standard/bf16", BIG, {"dtype": "bfloat16"}),
    ("big/lowrank1/bf16", BIG, {"dtype": "bfloat16", "low_rank": 1}),
    ("big/lowrank4/bf16", BIG, {"dtype": "bfloat16", "low_rank": 4}),
    ("pop10k/lowrank1/bf16", POP10K,
     {"dtype": "bfloat16", "low_rank": 1, "gens": 3}),
    ("loco/standard/bf16", LOCO, {"dtype": "bfloat16", "gens": 3}),
    ("loco/standard/f32", LOCO, {"dtype": "float32", "gens": 3}),
    ("loco10k/lowrank1/bf16", LOCO10K,
     {"dtype": "bfloat16", "low_rank": 1, "gens": 3}),
    # the north-star composition (round 4): running obs normalization ON
    # TOP of the rank-1 noise representation — measures what the per-step
    # normalize + per-generation center probe cost at config-3 scale.
    # Shares LOCO10K with the row above so the pair can never diverge.
    ("loco10k/lowrank1+obsnorm/bf16", LOCO10K,
     {"dtype": "bfloat16", "low_rank": 1, "obs_norm": True, "gens": 3}),
]


def stage_ab(force_cpu=False) -> int:
    _require_tpu_unless(force_cpu)
    seen = {}
    failed = []
    for label, base, over in AB_MATRIX:
        cfg = {**base, **over}
        label_spec = None
        if force_cpu:
            # CPU can't run emulated bf16 at bench sizes in sane time, and
            # relative mode comparisons only make sense at one dtype there —
            # rows that coerce to an already-measured cfg alias its result.
            # The label must say what was MEASURED (f32), not what the
            # matrix row specs for on-chip runs; label_spec keeps the
            # original for joining against future TPU rows
            cfg = {**cfg, "dtype": "float32", "gens": 2}
            if "bf16" in label:
                label_spec, label = label, label.replace("bf16", "f32")
        key = json.dumps(cfg, sort_keys=True)
        if key in seen:
            if label_spec is None and label == seen[key]:
                # an explicit row coerced to a cfg already measured under
                # the SAME label — a second line with an identical label
                # (and self-referential alias_of) would be ambiguous for
                # consumers that join by label; skip it
                continue
            # keep the alias line keyed by the ORIGINAL spec label (e.g.
            # the bf16 row whose cfg coerced onto an f32 measurement):
            # labels stay unique and future TPU rows still join on it
            line = {"label": label_spec or label, "alias_of": seen[key],
                    "cfg": cfg}
            print(json.dumps(line), flush=True)
            continue
        seen[key] = label
        res = run_stage(cfg, timeout_s=1200 if force_cpu else 600,
                        force_cpu=force_cpu)
        if res is None:
            failed.append(label)
        line = {"label": label, **(res or {"rate": None, "cfg": cfg})}
        if label_spec:
            line["label_spec"] = label_spec
        print(json.dumps(line), flush=True)
    return _fail_if(failed, "--stage-ab")


def stage_obs_ab(force_cpu=False, gens=3, repeats=3) -> int:
    """Telemetry overhead A/B: the SAME config with default-on spans vs
    telemetry disabled — the <2% observability acceptance gate.

    The ON arm includes everything the hub records by default: spans,
    counters, AND the streaming histograms (obs/hist.py — per-phase
    duration distributions observed on every span exit), so this A/B is
    also the histogram-on vs histogram-off overhead gate; a disabled
    hub swallows observes through NullHistograms the same way it
    swallows counter writes.

    This host's single-run rates swing far more than 2% (shared-core
    load; the round-4 contamination lesson), so one pair of stages
    cannot resolve a 2% effect: ``repeats`` INTERLEAVED on/off pairs are
    measured (ABAB..., so slow drift hits both arms equally) and the
    verdict compares the per-arm MEDIANS.  Per-run rows land as JSON
    lines for the artifact; the ``obs/overhead`` line carries the
    medians + the verdict."""
    _require_tpu_unless(force_cpu)
    rates = {"spans_on": [], "spans_off": []}
    failed = []
    for rep in range(repeats):
        for label, tel in (("spans_on", True), ("spans_off", False)):
            cfg = {**SMALL, "gens": gens, "telemetry": tel}
            if force_cpu:
                cfg["dtype"] = "float32"
            r = run_stage(cfg, timeout_s=1200 if force_cpu else 600,
                          force_cpu=force_cpu)
            if r and r.get("rate"):
                rates[label].append(r["rate"])
            else:
                failed.append(f"{label}#{rep}")
            print(json.dumps({"label": f"obs/{label}", "rep": rep,
                              **(r or {"rate": None, "cfg": cfg})}),
                  flush=True)
    on, off = sorted(rates["spans_on"]), sorted(rates["spans_off"])
    if on and off:
        # statistics.median averages the middle pair on even arm sizes —
        # a timed-out repeat must not bias the gate toward either verdict
        import statistics

        med_on = statistics.median(on)
        med_off = statistics.median(off)
        # overhead = throughput lost with spans on (positive = spans cost)
        overhead = (med_off - med_on) / med_off * 100.0
        print(json.dumps({
            "label": "obs/overhead",
            "median_on": round(med_on, 1), "median_off": round(med_off, 1),
            "runs_per_arm": len(on),
            "spread_pct": round(
                (max(on + off) - min(on + off)) / med_off * 100.0, 1),
            "overhead_pct": round(overhead, 2),
            "pass_lt_2pct": overhead < 2.0,
        }), flush=True)
    return _fail_if(failed, "--obs-ab")


def _tiny_host_es(cfg, worker_mode="process"):
    """Shared tiny host-backend ES for the chaos / async-ab stages: a
    4→8→2 torch MLP and a quadratic-fitness agent whose rollout runs
    ``work_s`` of sleep (GIL-released, like a real env stepping in C) —
    enough per-member cost that generations have a cadence for
    stragglers to perturb."""
    import torch

    from estorch_tpu import ES

    class TinyPolicy(torch.nn.Module):
        def __init__(self):
            super().__init__()
            self.net = torch.nn.Sequential(
                torch.nn.Linear(4, 8), torch.nn.Tanh(), torch.nn.Linear(8, 2)
            )

        def forward(self, x):
            return self.net(x)

    work_s = float(cfg.get("work_s", 0.0))

    class QuadAgent:
        def rollout(self, policy):
            with torch.no_grad():
                v = torch.nn.utils.parameters_to_vector(policy.parameters())
                r = -float((v**2).sum())
            if work_s:
                time.sleep(work_s)
            self.last_episode_steps = 1
            return r

    return ES(TinyPolicy, QuadAgent, torch.optim.Adam,
              population_size=int(cfg.get("population", 16)), sigma=0.05,
              seed=0, optimizer_kwargs={"lr": 0.01}, table_size=1 << 12,
              worker_mode=worker_mode)


def _async_accounting(es, baseline=None):
    """The zero-silent-drop invariant, read once from the event log +
    counters (docs/async.md): every dispatched member is consumed (fold
    or fresh), discarded with evidence, or lost to a counted worker
    death.  All the async gates (--chaos mixed leg, --async-ab,
    --elastic-ab) report THIS block, so they can never check different
    invariants.  ``baseline`` is a counters snapshot taken before the
    timed run (an untimed warm-up shares ``es.obs.counters`` but gets
    its own event log — without the delta, warm-up folds could satisfy
    a timed-window gate)."""
    log = es.async_event_log
    counters = es.obs.counters.snapshot()
    base = baseline or {}

    def since(name):
        return int(counters.get(name, 0)) - int(base.get(name, 0))

    consumed = sum(len(u["consumed"]) for u in log.updates)
    dispatched = len(log.dispatches) * es.population_size
    return {
        "results_folded": since("results_folded"),
        "stale_discarded": since("stale_discarded"),
        "results_lost": since("results_lost"),
        "consumed": consumed,
        "dispatched": dispatched,
        "accounting_ok": (dispatched == consumed + len(log.discarded)
                          + len(log.lost)),
    }


def measure_chaos_one(cfg):
    """Child body for --stage-chaos-one: a tiny host-backend ES with fork
    workers, optionally under a chaos plan (worker kills, and — the
    mixed-fault async leg — straggler stalls with jitter), measured in
    generations/sec.  ``cfg["async"]`` routes through the event-driven
    scheduler (estorch_tpu/algo/scheduler.py) instead of the barrier
    loop.  Host path only: construction imports jax but never touches
    the device runtime, so it neither needs nor holds a chip
    (run_lint exports JAX_PLATFORMS=cpu on top)."""
    from estorch_tpu.resilience.chaos import CHAOS_ENV, ChaosPlan

    gens = int(cfg.get("gens", 60))
    n_proc = int(cfg.get("n_proc", 2))
    if cfg.get("chaos"):
        plan = ChaosPlan.generate(
            seed=0, n_generations=gens,
            kill_every=int(cfg.get("kill_every", 0)),
            n_workers=n_proc,
            straggler_every=int(cfg.get("straggler_every", 0)),
            straggler_sleep_s=float(cfg.get("sleep_s", 1.0)),
            straggler_jitter_s=float(cfg.get("jitter_s", 0.0)),
            population_size=int(cfg.get("population", 16)),
        )
        os.environ[CHAOS_ENV] = plan.to_json()
    es = _tiny_host_es(cfg, worker_mode="process")
    es.train(1, n_proc=n_proc, verbose=False)  # warm-up: fork the pool
    t0 = time.perf_counter()
    if cfg.get("async"):
        es.train_async(gens, n_proc=n_proc, verbose=False,
                       max_stale=int(cfg.get("max_stale", 4096)))
    else:
        es.train(gens, n_proc=n_proc, verbose=False)
    dt = time.perf_counter() - t0
    counters = es.obs.counters.snapshot()
    out = {
        "gps": round(gens / dt, 2),
        "generations": len(es.history),
        "n_failed_total": int(sum(r["n_failed"] for r in es.history)),
        "workers_respawned": int(counters.get("workers_respawned", 0)),
        "members_retried": int(counters.get("members_retried", 0)),
        "chaos_worker_kills": int(counters.get("chaos_worker_kills", 0)),
        "generations_rejected": int(counters.get("generations_rejected", 0)),
        "cfg": cfg,
    }
    if cfg.get("async"):
        out.update(_async_accounting(es))
    es.engine.close()
    return out


def stage_chaos(selfcheck=False):
    """Recovery-overhead A/B (chaos vs clean) via the stage protocol; the
    selfcheck form is the run_lint.sh gate.  Returns the process exit
    code: 0 when recovery actually recovered (full participation under
    worker kills, and the async scheduler survived the MIXED
    straggler+kill plan with its accounting intact), 1 otherwise."""
    gens = 24 if selfcheck else 60
    kill_every = 8 if selfcheck else 20
    base = {"gens": gens, "kill_every": kill_every, "population": 16,
            "n_proc": 2}
    # the mixed-fault async leg: the SAME kills plus a straggler stall
    # (with jitter) every kill_every//2 generations, driven through the
    # event-driven scheduler — both fault classes against the async path
    mixed = {**base, "chaos": True, "async": True,
             "straggler_every": max(kill_every // 2, 2),
             "sleep_s": 0.3, "jitter_s": 0.2, "work_s": 0.002}
    rows = {}
    for label, cfg in (("clean", {**base, "chaos": False}),
                       ("chaos", {**base, "chaos": True}),
                       ("mixed_async", mixed)):
        argv = [sys.executable, __file__, "--stage-chaos-one",
                json.dumps(cfg)]
        # a pre-existing ESTORCH_CHAOS in the caller's environment
        # (resilience.chaos.CHAOS_ENV; literal here — the bench driver
        # stays import-free) would contaminate the CLEAN leg and turn the
        # A/B into chaos-vs-chaos; the chaos leg sets its own plan
        child_env = {**os.environ, "JAX_PLATFORMS": "cpu"}
        child_env.pop("ESTORCH_CHAOS", None)
        try:
            r = subprocess.run(argv, timeout=600, capture_output=True,
                               text=True, env=child_env)
        except subprocess.TimeoutExpired:
            print(json.dumps({"label": f"chaos/{label}", "gps": None,
                              "error": "timeout after 600s"}), flush=True)
            continue
        try:
            last = [ln for ln in r.stdout.strip().splitlines()
                    if ln.startswith("{")][-1]
            rows[label] = json.loads(last)
        except (IndexError, ValueError):
            print(json.dumps({"label": f"chaos/{label}", "gps": None,
                              "error": f"stage exited {r.returncode}",
                              "stderr_tail": r.stderr[-800:]}), flush=True)
            continue
        print(json.dumps({"label": f"chaos/{label}", **rows[label]}),
              flush=True)
    clean, chaos = rows.get("clean"), rows.get("chaos")
    mixed_row = rows.get("mixed_async")
    if not clean or not chaos or not mixed_row:
        print(json.dumps({"label": "chaos/recovery", "error":
                          "one or more stages failed"}), flush=True)
        return 1
    overhead = (clean["gps"] - chaos["gps"]) / clean["gps"] * 100.0
    expected_kills = gens // kill_every
    # full recovery means: every generation trained, every kill respawned
    # (a kill at the FINAL generation has no next boundary to respawn at —
    # hence the -1), and NO member lost — the same-generation retry path
    # covered every killed worker's slice
    recovered = (
        chaos["generations"] == gens + 1  # incl. warm-up generation
        and chaos["chaos_worker_kills"] >= expected_kills
        and chaos["workers_respawned"] >= expected_kills - 1
        and chaos["n_failed_total"] == 0
    )
    # the async leg's contract is different by design: a killed worker's
    # in-flight slice is LOST (counted), not retried — recovery means
    # the scheduler finished every update anyway, respawned the killed
    # workers, and accounted every dispatched member (consumed /
    # discarded / lost), with zero silent drops
    mixed_ok = (
        mixed_row["generations"] == gens + 1
        and mixed_row["chaos_worker_kills"] >= expected_kills
        and mixed_row["workers_respawned"] >= expected_kills - 1
        and bool(mixed_row.get("accounting_ok"))
    )
    print(json.dumps({
        "label": "chaos/recovery",
        "clean_gps": clean["gps"],
        "chaos_gps": chaos["gps"],
        "overhead_pct": round(overhead, 1),
        "worker_kills": chaos["chaos_worker_kills"],
        "workers_respawned": chaos["workers_respawned"],
        "members_retried": chaos["members_retried"],
        "n_failed_total": chaos["n_failed_total"],
        "full_participation": chaos["n_failed_total"] == 0,
        "mixed_async": {
            "gps": mixed_row["gps"],
            "worker_kills": mixed_row["chaos_worker_kills"],
            "workers_respawned": mixed_row["workers_respawned"],
            "results_folded": mixed_row.get("results_folded"),
            "stale_discarded": mixed_row.get("stale_discarded"),
            "results_lost": mixed_row.get("results_lost"),
            "accounting_ok": mixed_row.get("accounting_ok"),
            "pass": mixed_ok,
        },
        "pass": recovered and mixed_ok,
    }), flush=True)
    return 0 if (recovered and mixed_ok) else 1


def measure_async_one(cfg):
    """Child body for --stage-async-one: ONE leg of the sync-vs-async
    A/B — a tiny host thread-worker ES under a deterministic straggler
    plan (sleep + jitter every K generations), driven either by the
    barrier loop (``ES.train``) or the event-driven scheduler
    (``ES.train_async``, estorch_tpu/algo/scheduler.py).  Both legs see
    the IDENTICAL plan (jitter is seeded by event id), so the only
    variable is the scheduling.  Prints one JSON row with the rate and
    — async leg — the fold/discard/lost accounting and the per-phase
    step-vs-max evidence the driver gates on."""
    from estorch_tpu.resilience.chaos import CHAOS_ENV, ChaosPlan

    gens = int(cfg.get("gens", 20))
    n_proc = int(cfg.get("n_proc", 2))
    plan = ChaosPlan.generate(
        seed=0, n_generations=gens,
        straggler_every=int(cfg.get("straggler_every", 2)),
        straggler_sleep_s=float(cfg.get("sleep_s", 0.3)),
        straggler_jitter_s=float(cfg.get("jitter_s", 0.2)),
        population_size=int(cfg.get("population", 16)),
    )
    os.environ[CHAOS_ENV] = plan.to_json()
    es = _tiny_host_es(cfg, worker_mode="thread")
    t0 = time.perf_counter()
    if cfg.get("async"):
        es.train_async(gens, n_proc=n_proc, verbose=False,
                       max_stale=int(cfg.get("max_stale", 4096)))
    else:
        es.train(gens, n_proc=n_proc, verbose=False)
    dt = time.perf_counter() - t0
    # per-update step-vs-max evidence from the recorded phase spans:
    # wall ≈ max(eval, update) is the async promise (the sync barrier
    # loop's wall is their SUM plus the straggler stall)
    walls, maxes = [], []
    for r in es.history:
        ph = r.get("phases") or {}
        ev, up = float(ph.get("eval", 0.0)), float(ph.get("update", 0.0))
        if ev or up:
            walls.append(float(r["wall_time_s"]))
            maxes.append(max(ev, up))
    import statistics

    step_max_ratio = (
        round(statistics.median(walls) / statistics.median(maxes), 3)
        if maxes and statistics.median(maxes) > 0 else None)
    counters = es.obs.counters.snapshot()
    out = {
        "mode": "async" if cfg.get("async") else "sync",
        "gps": round(gens / dt, 3),
        "wall_s": round(dt, 3),
        "generations": len(es.history),
        "step_max_ratio": step_max_ratio,
        "n_failed_total": int(sum(r["n_failed"] for r in es.history)),
        "cfg": cfg,
    }
    if cfg.get("async"):
        out.update(
            **_async_accounting(es),
            overlap_efficiency=counters.get("overlap_efficiency"),
            stale_reuse_ratio=counters.get("stale_reuse_ratio"),
        )
    es.engine.close()
    return out


def stage_async_ab(selfcheck=False):
    """Sync-barrier vs async-scheduler A/B under an injected straggler
    plan (ISSUE 9 acceptance; the selfcheck form is the run_lint.sh
    gate).  Interleaved repeats per arm (the --obs-ab loaded-host
    discipline), medians + a noise band learned from the repeats via
    ``obs regress``.  Exit 0 only when (1) async generation throughput
    beats sync by >= 1.25x beyond the learned band, (2) the async leg's
    step time ≈ max(eval, update) per the recorded spans, and (3) the
    zero-silent-drop accounting holds — every late result folded with a
    recorded weight or counted discarded/lost."""
    regress = _load_obs_regress()
    base = ({"gens": 14, "population": 16, "n_proc": 2,
             "straggler_every": 2, "sleep_s": 0.25, "jitter_s": 0.15,
             "work_s": 0.002, "max_stale": 4096}
            if selfcheck else
            {"gens": 30, "population": 16, "n_proc": 2,
             "straggler_every": 2, "sleep_s": 0.4, "jitter_s": 0.25,
             "work_s": 0.004, "max_stale": 4096})
    repeats = 2 if selfcheck else 3
    rates = {"sync": [], "async": []}
    async_rows = []
    for rep in range(repeats):
        for mode in ("sync", "async"):
            cfg = {**base, "async": mode == "async"}
            argv = [sys.executable, __file__, "--stage-async-one",
                    json.dumps(cfg)]
            child_env = {**os.environ, "JAX_PLATFORMS": "cpu"}
            child_env.pop("ESTORCH_CHAOS", None)  # the stage owns its plan
            try:
                r = subprocess.run(argv, timeout=600, capture_output=True,
                                   text=True, env=child_env)
                last = [ln for ln in r.stdout.strip().splitlines()
                        if ln.startswith("{")][-1]
                row = json.loads(last)
            except subprocess.TimeoutExpired:
                print(json.dumps({"label": f"async/{mode}", "rep": rep,
                                  "error": "timeout after 600s"}),
                      flush=True)
                continue
            except (IndexError, ValueError):
                print(json.dumps({"label": f"async/{mode}", "rep": rep,
                                  "error": f"stage exited {r.returncode}",
                                  "stderr_tail": r.stderr[-800:]}),
                      flush=True)
                continue
            rates[mode].append(row["gps"])
            if mode == "async":
                async_rows.append(row)
            print(json.dumps({"label": f"async/{mode}", "rep": rep,
                              **row}), flush=True)
    if not rates["sync"] or not rates["async"]:
        print(json.dumps({"label": "async/ab",
                          "error": "one or both arms have no samples"}),
              flush=True)
        return 1
    # medians + learned noise band: async as "current" vs sync as the
    # baseline — an honest win must clear the band AND the 1.25x floor
    verdict = regress.compare(rates["async"], rates["sync"],
                              metric="generations_per_sec")
    ratio = (verdict["current_median"] / verdict["baseline_median"]
             if verdict["baseline_median"] else None)
    folded = sum(r.get("results_folded", 0) for r in async_rows)
    accounting_ok = all(r.get("accounting_ok") for r in async_rows)
    step_ratios = [r["step_max_ratio"] for r in async_rows
                   if r.get("step_max_ratio") is not None]
    import statistics

    step_max = (round(statistics.median(step_ratios), 3)
                if step_ratios else None)
    ok = (
        ratio is not None and ratio >= 1.25
        and bool(verdict.get("improved"))
        and accounting_ok
        and folded > 0  # the straggler plan MUST have exercised the fold
        and step_max is not None and step_max <= 1.35
    )
    print(json.dumps({
        "label": "async/ab",
        "sync_median_gps": verdict["baseline_median"],
        "async_median_gps": verdict["current_median"],
        "ratio": round(ratio, 3) if ratio else None,
        "band_pct": verdict["band_pct"],
        "improved_beyond_band": bool(verdict.get("improved")),
        "results_folded": folded,
        "stale_discarded": sum(r.get("stale_discarded", 0)
                               for r in async_rows),
        "results_lost": sum(r.get("results_lost", 0) for r in async_rows),
        "accounting_ok": accounting_ok,
        "async_step_vs_max_phase": step_max,
        "pass": ok,
    }), flush=True)
    return 0 if ok else 1


def _elastic_spec(cfg):
    """The shared ES spec of every --elastic-ab process (coordinator,
    subprocess hosts, sync SPMD workers): same seed => same table =>
    same noise coordinates everywhere (parallel/elastic.py
    es_from_spec)."""
    return {
        "env": "CartPole",
        "population_size": int(cfg.get("population", 16)),
        "horizon": int(cfg.get("horizon", 64)),
        "seed": 7,
        "sigma": 0.1,
        "lr": 1e-2,
        "table_size": 1 << 18,
        "telemetry": True,
    }


def _elastic_plan_json(cfg):
    """The declared straggle_host plan BOTH legs run under — host 1 is
    slow at EVERY generation/dispatch (seeded jitter on top), so the
    sync leg's psum barrier pays the stall fleet-wide while the elastic
    leg only loses host 1's contribution rate.  Built identically in
    every child (same seed => same events => same jitter)."""
    from estorch_tpu.resilience.chaos import ChaosPlan

    plan = ChaosPlan.generate(
        seed=0,
        n_generations=int(cfg["gens"]) * 3 + 16,
        straggle_host_every=1,
        straggle_host=1,
        straggle_host_sleep_s=float(cfg.get("sleep_s", 0.3)),
        straggle_host_jitter_s=float(cfg.get("jitter_s", 0.1)),
    )
    return plan.to_json()


def elastic_sync_worker(cfg):
    """Child body for --stage-elastic-worker: ONE process of the
    synchronous 2-process SPMD multihost leg (jax.distributed over
    loopback + Gloo CPU collectives, tests/test_multiprocess.py
    layering).  Every process steps the same fused program under the
    declared straggle_host plan via multihost.train_sync — the psum
    barrier makes host 1's stall everyone's stall, which is exactly
    what the elastic leg is measured against.  The leader prints the
    timed row."""
    from estorch_tpu.resilience.chaos import CHAOS_ENV
    from estorch_tpu.utils.backend import force_cpu_backend

    force_cpu_backend(int(cfg.get("cpu_devices", 2)))
    os.environ[CHAOS_ENV] = _elastic_plan_json(cfg)
    import estorch_tpu.parallel.multihost as mh
    from estorch_tpu.parallel.elastic import es_from_spec

    assert mh.initialize(f"127.0.0.1:{cfg['port']}", num_processes=2,
                         process_id=int(cfg["pid"]), timeout_s=90,
                         cpu_collectives=True)
    es = es_from_spec(_elastic_spec(cfg),
                      mesh=mh.global_population_mesh())
    gens = int(cfg["gens"])
    es.train(1, verbose=False)  # warm-up: compile outside the window
    t0 = time.perf_counter()
    mh.train_sync(es, gens, verbose=False)
    dt = time.perf_counter() - t0
    return {
        "mode": "sync",
        "leader": mh.process_info()["is_leader"],
        "gps": round(gens / dt, 3),
        "wall_s": round(dt, 3),
        "generations": int(es.generation),
    }


def measure_elastic_one(cfg):
    """Child body for --stage-elastic-one (elastic leg): a live elastic
    fleet on this machine — the coordinator (device-backend ES + the
    host-granular fold scheduler, docs/multihost.md) plus two REAL
    subprocess hosts joined through the ``python -m
    estorch_tpu.parallel.elastic`` CLI, all under the same declared
    straggle_host plan the sync leg pays.  Prints the timed row with
    the dispatched == consumed + discarded + lost accounting."""
    import signal
    import subprocess as sp

    from estorch_tpu.resilience.chaos import CHAOS_ENV

    plan_json = _elastic_plan_json(cfg)
    os.environ[CHAOS_ENV] = plan_json
    spec = {**_elastic_spec(cfg), "cpu_devices": 2}
    from estorch_tpu.parallel.elastic import ElasticCoordinator, es_from_spec

    es = es_from_spec(spec)
    # grace must satisfy 4 * join_grace_s < the driver's 600s child
    # timeout: four consecutive grace-expired dispatches are what the
    # scheduler's dry-out diagnosis needs, and a SIGKILLed child loses
    # the host-log evidence this function exists to print
    coord = ElasticCoordinator(join_grace_s=120.0)
    host_env = {**os.environ, "JAX_PLATFORMS": "cpu",
                CHAOS_ENV: plan_json}
    # host output goes to FILES, never unread pipes: a chatty host
    # blocking on a full 64KB pipe mid-run would look exactly like the
    # dead-slow host this leg exists to measure
    logdir = tempfile.mkdtemp(prefix="elastic-hosts-")
    host_logs = [open(os.path.join(logdir, f"host{i}.log"), "w+")
                 for i in range(2)]
    hosts = [
        sp.Popen([sys.executable, "-m", "estorch_tpu.parallel.elastic",
                  "--join", f"{coord.address[0]}:{coord.address[1]}",
                  "--spec", json.dumps(spec), "--host", str(i)],
                 env=host_env, stdout=f, stderr=sp.STDOUT, text=True)
        for i, f in enumerate(host_logs)
    ]
    gens = int(cfg["gens"])
    try:
        # warm-up: coordinator fold/update compiles + both hosts join
        # and compile, all outside the timed window
        es.train_elastic(1, fleet=coord, verbose=False)
        warm = dict(es.obs.counters.snapshot())
        t0 = time.perf_counter()
        es.train_elastic(gens, fleet=coord, verbose=False)
        dt = time.perf_counter() - t0
    finally:
        coord.close()
        for i, h in enumerate(hosts):
            try:
                h.wait(timeout=10)
            except sp.TimeoutExpired:
                h.send_signal(signal.SIGKILL)
                h.wait(timeout=10)
            host_logs[i].close()
            if h.returncode not in (0, -signal.SIGKILL):
                with open(host_logs[i].name) as f:
                    print(f"elastic host {i} exited {h.returncode}: "
                          f"{f.read()[-800:]}", file=sys.stderr)
    counters = es.obs.counters.snapshot()
    return {
        "mode": "elastic",
        "gps": round(gens / dt, 3),
        "wall_s": round(dt, 3),
        "hosts": 2,
        "hosts_lost": int(counters.get("hosts_lost", 0))
        - int(warm.get("hosts_lost", 0)),
        "membership_events": len(es.async_event_log.membership),
        **_async_accounting(es, baseline=warm),
    }


def _run_elastic_leg(mode, base, rep=0):
    """Run ONE --elastic-ab leg in fresh child processes and return its
    timed row, or None after printing the failure evidence.  Shared by
    the A/B gate and --capture-baseline's committed elastic row."""
    import socket

    child_env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    child_env.pop("ESTORCH_CHAOS", None)  # legs own their plan
    if mode == "sync":
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]
        procs = [subprocess.Popen(
            [sys.executable, __file__, "--stage-elastic-worker",
             json.dumps({**base, "pid": pid, "port": port})],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True, env=child_env) for pid in range(2)]
        row = None
        # drain BOTH workers' pipes concurrently: these are one SPMD
        # job, so worker 1 blocking on a full unread pipe while we
        # communicate() with worker 0 would stall the barrier fleet-wide
        from concurrent.futures import ThreadPoolExecutor
        with ThreadPoolExecutor(len(procs)) as pool:
            futs = [pool.submit(p.communicate, None, 600) for p in procs]
            try:
                outs = [f.result() for f in futs]
            except subprocess.TimeoutExpired:
                for p in procs:
                    p.kill()
                print(json.dumps({"label": "elastic/sync", "rep": rep,
                                  "error": "timeout after 600s"}),
                      flush=True)
                return None
        for p, (out, err) in zip(procs, outs):
            lines = [ln for ln in out.strip().splitlines()
                     if ln.startswith("{")]
            try:
                cand = json.loads(lines[-1]) if lines else None
            except ValueError:  # died mid-print: fail the leg, not the gate
                cand = None
            if p.returncode != 0 or cand is None:
                print(json.dumps(
                    {"label": "elastic/sync", "rep": rep,
                     "error": f"worker exited {p.returncode}",
                     "stderr_tail": err[-800:]}), flush=True)
            elif cand.get("leader"):
                row = cand
        return row
    argv = [sys.executable, __file__, "--stage-elastic-one",
            json.dumps(base)]
    try:
        r = subprocess.run(argv, timeout=600, capture_output=True,
                           text=True, env=child_env)
        last = [ln for ln in r.stdout.strip().splitlines()
                if ln.startswith("{")][-1]
        return json.loads(last)
    except subprocess.TimeoutExpired:
        print(json.dumps({"label": "elastic/elastic", "rep": rep,
                          "error": "timeout after 600s"}), flush=True)
        return None
    except (IndexError, ValueError):
        print(json.dumps(
            {"label": "elastic/elastic", "rep": rep,
             "error": f"stage exited {r.returncode}",
             "stderr_tail": r.stderr[-800:]}), flush=True)
        return None


def capture_elastic_row(gens=8):
    """The committed-baseline elastic row (--capture-baseline): one
    sync-SPMD + one elastic-fleet measurement under the shared declared
    straggle_host plan, summarized for BENCH_r*.json extras."""
    base = {"gens": int(gens), "population": 16, "horizon": 64,
            "sleep_s": 0.3, "jitter_s": 0.1}
    sync_row = _run_elastic_leg("sync", base)
    el_row = _run_elastic_leg("elastic", base)
    if not sync_row or not el_row:
        return {"error": "one or both elastic legs failed", "cfg": base}
    return {
        "cfg": base,
        "sync_gps": sync_row["gps"],
        "elastic_gps": el_row["gps"],
        "ratio": round(el_row["gps"] / sync_row["gps"], 3),
        "results_folded": el_row.get("results_folded"),
        "results_lost": el_row.get("results_lost"),
        "accounting_ok": el_row.get("accounting_ok"),
    }


def stage_elastic_ab(selfcheck=False):
    """Synchronous-SPMD-multihost vs elastic host-granular fold A/B
    under an identical declared straggle_host plan (ISSUE 15
    acceptance; the selfcheck form is the run_lint.sh gate).
    Interleaved repeats, medians + a noise band learned from the
    repeats via ``obs regress``.  Exit 0 only when (1) elastic
    generation throughput beats the synchronous multihost loop by >=
    1.25x beyond the band, (2) stale host contributions actually folded
    (the plan MUST have exercised the IW path), and (3) the
    zero-silent-drop accounting holds: dispatched == consumed +
    discarded + lost."""
    regress = _load_obs_regress()
    base = ({"gens": 6, "population": 16, "horizon": 64,
             "sleep_s": 0.3, "jitter_s": 0.1}
            if selfcheck else
            {"gens": 12, "population": 16, "horizon": 64,
             "sleep_s": 0.5, "jitter_s": 0.25})
    repeats = 2 if selfcheck else 3
    rates = {"sync": [], "elastic": []}
    elastic_rows = []
    for rep in range(repeats):
        for mode in ("sync", "elastic"):
            row = _run_elastic_leg(mode, base, rep)
            if row is None:
                continue
            if mode == "elastic":
                elastic_rows.append(row)
            rates[mode].append(row["gps"])
            print(json.dumps({"label": f"elastic/{mode}", "rep": rep,
                              **row}), flush=True)
    if not rates["sync"] or not rates["elastic"]:
        print(json.dumps({"label": "elastic/ab",
                          "error": "one or both arms have no samples"}),
              flush=True)
        return 1
    verdict = regress.compare(rates["elastic"], rates["sync"],
                              metric="generations_per_sec")
    ratio = (verdict["current_median"] / verdict["baseline_median"]
             if verdict["baseline_median"] else None)
    folded = sum(r.get("results_folded", 0) for r in elastic_rows)
    accounting_ok = all(r.get("accounting_ok") for r in elastic_rows)
    ok = (
        ratio is not None and ratio >= 1.25
        and bool(verdict.get("improved"))
        and accounting_ok
        and folded > 0  # stale host contributions MUST have folded
    )
    print(json.dumps({
        "label": "elastic/ab",
        "sync_median_gps": verdict["baseline_median"],
        "elastic_median_gps": verdict["current_median"],
        "ratio": round(ratio, 3) if ratio else None,
        "band_pct": verdict["band_pct"],
        "improved_beyond_band": bool(verdict.get("improved")),
        "results_folded": folded,
        "stale_discarded": sum(r.get("stale_discarded", 0)
                               for r in elastic_rows),
        "results_lost": sum(r.get("results_lost", 0)
                            for r in elastic_rows),
        "hosts_lost": sum(r.get("hosts_lost", 0) for r in elastic_rows),
        "accounting_ok": accounting_ok,
        "pass": ok,
    }), flush=True)
    return 0 if ok else 1


def measure_shard_ab(cfg):
    """Child body for --stage-shard-ab-one: replicated vs param-sharded
    same-seed A/B on the virtual CPU mesh (estorch_tpu/parallel/sharded.py,
    docs/sharding.md).  Three legs in one process:

    1. numerical — a table-noise sharded run must match the replicated
       fused path allclose at f32 (reduction order is the only licensed
       difference);
    2. memory — per-device peak bytes (compile ledger memory_analysis;
       shard sizes for sharded inputs) of the sharded program vs the
       replicated program's on the SAME config;
    3. sharded row — the program-noise sharded config runs the headline
       row's recipe and its FLOPs come from the shard-aware cost model
       (no MFU: this gate runs on the CPU mesh).
    """
    from estorch_tpu.utils import enable_compilation_cache, force_cpu_backend

    force_cpu_backend(8)
    enable_compilation_cache()
    import numpy as np
    import optax

    from estorch_tpu import ES, JaxAgent, MLPPolicy
    from estorch_tpu.envs import SyntheticEnv

    env = SyntheticEnv()
    pk = {"action_dim": env.action_dim, "hidden": tuple(cfg["hidden"]),
          "discrete": False, "action_scale": 1.0}
    common = dict(
        policy=MLPPolicy, agent=JaxAgent, optimizer=optax.adam,
        population_size=cfg["population"], sigma=0.05,
        policy_kwargs=pk,
        agent_kwargs={"env": env, "horizon": cfg["horizon"]},
        optimizer_kwargs={"learning_rate": 1e-2}, seed=0,
        eval_chunk=cfg.get("eval_chunk", 8),
        table_size=cfg.get("table_size", 1 << 21),
        telemetry=True,
    )
    gens = int(cfg.get("gens", 3))
    out = {"cfg": cfg}

    def ledger_peak(es, program):
        for rec in es.history:
            for e in rec.get("compile_events", []):
                if e.get("program") == program and "peak_bytes" in e:
                    return e["peak_bytes"]
        return None

    es_r = ES(**common)
    es_r.train(gens, verbose=False)
    es_s = ES(shard_params=True, noise_mode="table",
              model_shards=cfg.get("model_shards"), **common)
    es_s.train(gens, verbose=False)
    a = np.asarray(es_r.state.params_flat)
    b = np.asarray(es_s.state.params_flat)
    max_rel = float(np.max(np.abs(a - b) / np.maximum(np.abs(a), 1e-6)))
    out["numerical"] = {
        "match": bool(np.allclose(a, b, rtol=2e-4, atol=1e-5)),
        "max_rel_err": max_rel,
        "steps_equal": all(
            r1["env_steps"] == r2["env_steps"]
            for r1, r2 in zip(es_r.history, es_s.history)),
        "generations": gens,
    }
    # the sharded headline-row recipe: program noise, rate + MFU from
    # the shard-aware cost model
    prog_cfg = {**cfg, "shard": True, "telemetry": True}
    prog_cfg.pop("table_size", None)
    row = measure_one(prog_cfg, force_cpu=True)  # this A/B is a CPU gate
    out["sharded_row"] = {
        "rate": round(row["rate"], 1),
        "platform": row["platform"],
        **(row.get("shard") or {}),
    }
    # memory verdict: the SCALING mode (program noise — the sharded
    # default) vs the replicated program, per-device.  The table-mode
    # peak is reported but not gated: its 4·table_size replicated
    # argument is counted by memory_analysis while the replicated
    # engine's closed-over table lowers as an embedded constant the
    # arg/temp accounting does not see — comparing those two would be
    # apples to oranges (the parity mode exists for numerics, not scale)
    rep_peak = ledger_peak(es_r, "generation_step")
    prog_peak = out["sharded_row"].get("per_device_peak_bytes")
    out["memory"] = {
        "replicated_per_device_peak_bytes": rep_peak,
        "sharded_per_device_peak_bytes": prog_peak,
        "sharded_table_mode_peak_bytes": ledger_peak(
            es_s, "generation_step_sharded"),
        "ratio": (round(prog_peak / rep_peak, 4)
                  if rep_peak and prog_peak else None),
        # the analytic replicated bound the test narrative uses: params
        # + adam moments, f32, on EVERY device when replicated
        "replicated_state_bytes": int(3 * es_r.engine.spec.dim * 4),
    }
    return out


def stage_shard_ab(selfcheck=False):
    """Replicated-vs-sharded A/B via the stage protocol; the selfcheck
    form is the run_lint.sh gate.  Exit 0 only when the sharded path (1)
    matches the replicated fused path numerically at the same seed, (2)
    fits in LESS per-device memory than the replicated program on the
    same config, and (3) produces a non-null MFU from the shard-aware
    cost model."""
    cfg = ({"env": "synthetic", "hidden": [64, 64], "population": 32,
            "horizon": 50, "gens": 3, "eval_chunk": 8}
           if selfcheck else
           {"env": "synthetic", "hidden": [768, 768], "population": 64,
            "horizon": 100, "gens": 3, "eval_chunk": 8})
    argv = [sys.executable, __file__, "--stage-shard-ab-one",
            json.dumps(cfg)]
    try:
        r = subprocess.run(
            argv, timeout=900, capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
    except subprocess.TimeoutExpired:
        print(json.dumps({"label": "shard/ab",
                          "error": "timeout after 900s"}), flush=True)
        return 1
    try:
        last = [ln for ln in r.stdout.strip().splitlines()
                if ln.startswith("{")][-1]
        row = json.loads(last)
    except (IndexError, ValueError):
        print(json.dumps({"label": "shard/ab",
                          "error": f"stage exited {r.returncode}",
                          "stderr_tail": r.stderr[-800:]}), flush=True)
        return 1
    num = row.get("numerical") or {}
    mem = row.get("memory") or {}
    srow = row.get("sharded_row") or {}
    mem_ok = (mem.get("ratio") is not None and mem["ratio"] < 1.0)
    verdict = {
        "label": "shard/ab",
        "numerical_match": bool(num.get("match")),
        "max_rel_err": num.get("max_rel_err"),
        "steps_equal": bool(num.get("steps_equal")),
        "memory": mem,
        "sharded_row": srow,
        "pass": (bool(num.get("match")) and bool(num.get("steps_equal"))
                 and mem_ok and bool(srow.get("mfu_from_cost_model"))),
    }
    print(json.dumps(verdict), flush=True)
    return 0 if verdict["pass"] else 1


def measure_scenario_one(cfg):
    """Child body for --stage-scenario-one: the scenario suite's two
    claims, measured (estorch_tpu/scenarios, docs/scenarios.md):

    1. wall-clock — ONE domain-randomized run (N variants drawn
       in-program, traced operands) vs the old way to cover N scenarios:
       N sequential single-scenario runs, each compiling its own
       closed-over constants;
    2. compile ledger — the randomized run's program count must be
       independent of variant count (the traced-operand contract): an
       N-variant run and an N//3-variant run build the SAME number of
       XLA programs.

    The persistent compilation cache is deliberately NOT enabled here:
    the sequential leg's per-variant recompiles are the phenomenon being
    measured, and a warm cache on the second lint run would fake the
    win away.
    """
    from estorch_tpu.utils import force_cpu_backend

    force_cpu_backend(1)
    import dataclasses
    import time

    import optax

    from estorch_tpu import ES, JaxAgent, MLPPolicy
    from estorch_tpu.envs.pendulum import Pendulum
    from estorch_tpu.scenarios import ScenarioDistribution

    variants = int(cfg.get("variants", 10))
    gens = int(cfg.get("gens", 3))
    horizon = int(cfg.get("horizon", 30))
    pop = int(cfg.get("population", 32))
    hidden = tuple(cfg.get("hidden", [16]))
    base_env = Pendulum()
    # absolute ranges (not the ±spread helper): the sequential leg
    # instantiates concrete Pendulum(**draw) envs from the same draws
    ranges = {"g": (7.0, 13.0), "m": (0.7, 1.3), "l": (0.7, 1.3)}

    def build(env=None, dist=None):
        return ES(
            MLPPolicy, JaxAgent(env or base_env, horizon=horizon),
            optax.adam, population_size=pop, sigma=0.05, seed=0,
            policy_kwargs={"action_dim": 1, "hidden": hidden,
                           "discrete": False, "action_scale": 2.0},
            optimizer_kwargs={"learning_rate": 0.01},
            table_size=1 << 15, scenarios=dist, telemetry=True)

    def n_compiles(es):
        return sum(len(r.get("compile_events", [])) for r in es.history)

    def run_randomized(n):
        dist = ScenarioDistribution(ranges, n_variants=n, seed=0)
        t0 = time.perf_counter()
        es = build(dist=dist)
        es.train(gens, verbose=False)
        wall = time.perf_counter() - t0
        seen: set = set()
        for r in es.history:
            seen |= {v for v, c in enumerate(r["scenarios"]["counts"])
                     if c}
        return {"wall_s": round(wall, 3), "compiles": n_compiles(es),
                "variants_seen": len(seen),
                "block": es.history[-1]["scenarios"]}

    # untimed process warm-up: the first ES build in a process pays
    # one-off eager-dispatch/op-cache costs that would otherwise land
    # entirely on whichever timed leg runs first
    warm = build(env=base_env)
    warm.train(1, verbose=False)

    out = {"cfg": cfg}
    out["randomized"] = run_randomized(variants)
    # the O(1)-programs control: far fewer variants, same program count
    out["randomized_small"] = run_randomized(max(2, variants // 3))
    dist = ScenarioDistribution(ranges, n_variants=variants, seed=0)
    t0 = time.perf_counter()
    seq_compiles = 0
    for v in range(variants):
        env_v = dataclasses.replace(base_env, **dist.draw_concrete(v))
        es_v = build(env=env_v)
        es_v.train(gens, verbose=False)
        seq_compiles += n_compiles(es_v)
    out["sequential"] = {
        "wall_s": round(time.perf_counter() - t0, 3),
        "compiles": seq_compiles,
        "runs": variants,
    }
    out["speedup"] = round(
        out["sequential"]["wall_s"] / max(out["randomized"]["wall_s"],
                                          1e-9), 2)
    return out


SCENARIO_SPEEDUP_GATE = 3.0  # one randomized run vs N sequential runs
SCENARIO_COVERAGE_GATE = 0.9  # fraction of variants a run must visit


def stage_scenario_ab(selfcheck=False):
    """Scenario-suite A/B via the stage protocol; the selfcheck form is
    the run_lint.sh gate.  Exit 0 only when (1) the N-variant randomized
    run beats N sequential single-scenario runs >= 3x wall-clock, (2)
    the compile-ledger program count is O(1) in variant count (N-variant
    == N//3-variant), and (3) per-variant fitness is surfaced with >=90%
    of variants visited."""
    cfg = ({"variants": 10, "gens": 3, "population": 48,
            "horizon": 60, "hidden": [48, 48]}
           if selfcheck else
           {"variants": 10, "gens": 3, "population": 64,
            "horizon": 100, "hidden": [32, 32]})
    argv = [sys.executable, __file__, "--stage-scenario-one",
            json.dumps(cfg)]
    try:
        r = subprocess.run(
            argv, timeout=900, capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
    except subprocess.TimeoutExpired:
        print(json.dumps({"label": "scenario/ab",
                          "error": "timeout after 900s"}), flush=True)
        return 1
    try:
        last = [ln for ln in r.stdout.strip().splitlines()
                if ln.startswith("{")][-1]
        row = json.loads(last)
    except (IndexError, ValueError):
        print(json.dumps({"label": "scenario/ab",
                          "error": f"stage exited {r.returncode}",
                          "stderr_tail": r.stderr[-800:]}), flush=True)
        return 1
    rand = row.get("randomized") or {}
    small = row.get("randomized_small") or {}
    seq = row.get("sequential") or {}
    block = rand.get("block") or {}
    variants = int(cfg["variants"])
    coverage = rand.get("variants_seen", 0) / variants
    verdict = {
        "label": "scenario/ab",
        "speedup": row.get("speedup"),
        "speedup_gate": SCENARIO_SPEEDUP_GATE,
        "randomized_compiles": rand.get("compiles"),
        "small_variant_compiles": small.get("compiles"),
        "sequential_compiles": seq.get("compiles"),
        "programs_o1": rand.get("compiles") == small.get("compiles"),
        "variants_seen": rand.get("variants_seen"),
        "coverage": round(coverage, 3),
        "fitness_block_ok": (
            block.get("n_variants") == variants
            and sum(block.get("counts", [])) == int(cfg["population"])),
        "pass": (
            (row.get("speedup") or 0) >= SCENARIO_SPEEDUP_GATE
            and rand.get("compiles") == small.get("compiles")
            and coverage >= SCENARIO_COVERAGE_GATE
            and block.get("n_variants") == variants
            and sum(block.get("counts", [])) == int(cfg["population"])),
    }
    print(json.dumps(verdict), flush=True)
    return 0 if verdict["pass"] else 1


def measure_serve_one(cfg):
    """Child body for --stage-serve-one: export a trained pendulum bundle,
    then run the dynamic-batching vs batch-size-1 serving A/B against it
    (both legs are the SAME server binary, only --max-batch differs).
    Also verifies the bit-exactness contract (served responses vs this
    process's es.predict on a batch — same --cpu-devices 1 config on both
    sides) and the SIGTERM drain.  Returns one JSON row."""
    from estorch_tpu.utils import force_cpu_backend

    force_cpu_backend(1)
    import signal

    import jax
    import optax

    from estorch_tpu import ES, JaxAgent
    from estorch_tpu.envs.pendulum import Pendulum
    from estorch_tpu.models import MLPPolicy
    from estorch_tpu.serve.loadgen import run_load

    hidden = int(cfg.get("hidden", 256))
    gens = int(cfg.get("gens", 1))
    duration = float(cfg.get("duration_s", 2.0))
    max_batch = int(cfg.get("max_batch", 32))
    # table must cover the (hidden x hidden)-dominated param dim; the next
    # power of two above 2*hidden^2 always does
    table_size = max(1 << 14, 1 << (2 * hidden * hidden).bit_length())
    es = ES(
        MLPPolicy, JaxAgent(Pendulum(), horizon=8), optax.adam,
        population_size=4, sigma=0.05, seed=0,
        policy_kwargs={"action_dim": 1, "hidden": (hidden, hidden),
                       "discrete": False, "action_scale": 2.0},
        optimizer_kwargs={"learning_rate": 0.01},
        table_size=table_size,
        device=jax.devices()[0],
    )
    es.train(gens, verbose=False)
    # anchor-sized check set: served responses chain to the ANCHOR
    # (largest) bucket via the batcher's verification, and the anchor
    # shape is where es.predict's direct program and the serving vmap
    # agree — a reference at any other batch shape could legitimately
    # differ by 1 ulp (tests/test_serve.py sizes its check set the same
    # way)
    rng = np.random.default_rng(0)
    check_obs = rng.standard_normal((max_batch, 3)).astype(np.float32)
    ref = np.asarray(es.predict(check_obs))

    def leg(mb, conns):
        port_file = os.path.join(workdir, f"port_{mb}.json")
        argv = [sys.executable, "-m", "estorch_tpu.serve", "--bundle",
                bundle, "--port", "0", "--port-file", port_file,
                "--cpu-devices", "1", "--max-batch", str(mb),
                "--beat-interval", "0.5"]
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "ESTORCH_OBS_HEARTBEAT": os.path.join(workdir,
                                                     f"hb_{mb}.json")}
        proc = subprocess.Popen(argv, stdout=subprocess.PIPE, text=True,
                                env=env)
        try:
            ready = json.loads(proc.stdout.readline())
            addr = ready["url"]
            # correctness pass first: every response must be bit-equal to
            # the exporting run's es.predict rows (GEMM family — buckets
            # are >= 2 whenever max_batch >= 2; the max_batch=1 leg is
            # the GEMV family, equal to es.predict on single obs)
            chk = run_load(addr, conns=4, total=len(check_obs),
                           duration_s=30.0,
                           obs_list=[o.tolist() for o in check_obs],
                           collect_responses=True)
            if mb == 1:
                exact_ref = np.stack([
                    np.asarray(es.predict(o)) for o in check_obs])
            else:
                exact_ref = ref
            # a lost/non-200 check response is a FINDING (bit_exact
            # False + its row listed), not a stage crash
            acts = [r.get("action") if isinstance(r, dict) else None
                    for r in chk["responses"]]
            if any(a is None for a in acts):
                bit_exact = False
                mismatch_rows = [i for i, a in enumerate(acts)
                                 if a is None]
            else:
                got = np.asarray(acts, np.float32)
                bit_exact = got.tobytes() == exact_ref.tobytes()
                mismatch_rows = [] if bit_exact else [
                    i for i in range(len(check_obs))
                    if got[i].tobytes() != exact_ref[i].tobytes()]
            load = run_load(addr, conns=conns, duration_s=duration,
                            obs=[0.1, 0.2, 0.3])
            from estorch_tpu.serve.client import ServeClient

            with ServeClient(addr) as c:
                stats = c.stats()
            proc.send_signal(signal.SIGTERM)
            out, _ = proc.communicate(timeout=60)
            final = json.loads(out.strip().splitlines()[-1])
            return {
                "rps": load["throughput_rps"],
                "p50_ms": load["latency_ms"]["p50"],
                "p99_ms": load["latency_ms"]["p99"],
                "errors": load["errors"] + chk["errors"],
                "shed": int(stats["shed_total"]),
                "recompiles": int(stats["recompiles"]),
                "n_buckets": len(stats["buckets"])
                + len(stats.get("buckets_excluded", [])),
                "buckets_excluded": stats.get("buckets_excluded", []),
                "mean_batch": stats["mean_batch"],
                "bit_exact": bit_exact,
                **({"bit_mismatch_rows": mismatch_rows}
                   if mismatch_rows else {}),
                "drain_clean": bool(final.get("clean"))
                and proc.returncode == 0,
            }
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)

    # the exported policy is large (hidden^2 params); the finally covers
    # EVERYTHING from export on, or a failed run leaks 100+ MB in /tmp
    import shutil

    workdir = tempfile.mkdtemp(prefix="serve_bench_")
    try:
        bundle = es.export_bundle(os.path.join(workdir, "bundle"))
        dyn = leg(max_batch, conns=int(cfg.get("conns", 32)))
        b1 = leg(1, conns=8)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    ratio = (dyn["rps"] / b1["rps"]) if b1["rps"] else None
    return {"hidden": hidden, "dyn": dyn, "b1": b1,
            "ratio": round(ratio, 2) if ratio else None, "cfg": cfg}


def stage_serve(selfcheck=False):
    """Serving A/B via the stage protocol; the selfcheck form is the
    run_lint.sh gate (functional: bit-exactness, clean drain, bucket
    accounting — the ≥3x throughput win is gated by the full form and by
    the tier-1 serving demo, which size the policy to be memory-bound).
    Returns the process exit code."""
    cfg = ({"hidden": 256, "gens": 1, "duration_s": 1.5, "conns": 16}
           if selfcheck else
           {"hidden": 4096, "gens": 1, "duration_s": 4.0, "conns": 32})
    argv = [sys.executable, __file__, "--stage-serve-one", json.dumps(cfg)]
    child_env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    try:
        r = subprocess.run(argv, timeout=900, capture_output=True,
                           text=True, env=child_env)
    except subprocess.TimeoutExpired:
        print(json.dumps({"label": "serve", "error": "timeout after 900s"}),
              flush=True)
        return 1
    try:
        last = [ln for ln in r.stdout.strip().splitlines()
                if ln.startswith("{")][-1]
        row = json.loads(last)
    except (IndexError, ValueError):
        print(json.dumps({"label": "serve", "error":
                          f"stage exited {r.returncode}",
                          "stderr_tail": r.stderr[-800:]}), flush=True)
        return 1
    dyn, b1 = row["dyn"], row["b1"]
    functional = (
        dyn["bit_exact"] and b1["bit_exact"]
        and dyn["drain_clean"] and b1["drain_clean"]
        and dyn["errors"] == 0 and b1["errors"] == 0
        and dyn["shed"] == 0 and b1["shed"] == 0
        and dyn["recompiles"] <= dyn["n_buckets"]
        and b1["recompiles"] <= b1["n_buckets"]
    )
    ok = functional if selfcheck else (
        functional and row["ratio"] is not None and row["ratio"] >= 3.0)
    print(json.dumps({"label": "serve/ab", **row, "pass": ok}), flush=True)
    return 0 if ok else 1


def measure_coldstart_one(cfg):
    """Child body for --stage-coldstart-one: export the demo pendulum
    policy as a WARM bundle (packed XLA-cache entries + bf16 opt-in,
    serve/warm.py), then measure, in fresh server processes:

    * warm vs cold (--no-warm) legs, ``repeats`` each: process spawn →
      ready, ready → first response (the JIT pause lands here on the
      cold leg), first-``first_n``-requests p99, and the compile-ledger
      proof (compiles_at_load / warm_cache_hits from /stats);
    * steady-state bf16 vs f32 batched throughput in-process at the
      anchor bucket, with the measured per-bucket divergence.

    Returns one JSON row; the parent (stage_coldstart) gates it."""
    from estorch_tpu.utils import force_cpu_backend

    force_cpu_backend(1)
    import signal

    import jax
    import optax

    from estorch_tpu import ES, JaxAgent
    from estorch_tpu.envs.pendulum import Pendulum
    from estorch_tpu.models import MLPPolicy
    from estorch_tpu.serve.loadgen import coldstart_probe

    hidden = int(cfg.get("hidden", 6144))
    gens = int(cfg.get("gens", 1))
    max_batch = int(cfg.get("max_batch", 16))
    repeats = int(cfg.get("repeats", 3))
    first_n = int(cfg.get("first_n", 100))
    table_size = max(1 << 14, 1 << (2 * hidden * hidden).bit_length())
    es = ES(
        MLPPolicy, JaxAgent(Pendulum(), horizon=8), optax.adam,
        population_size=4, sigma=0.05, seed=0,
        policy_kwargs={"action_dim": 1, "hidden": (hidden, hidden),
                       "discrete": False, "action_scale": 2.0},
        optimizer_kwargs={"learning_rate": 0.01},
        table_size=table_size,
        device=jax.devices()[0],
    )
    es.train(gens, verbose=False)

    def leg(no_warm):
        port_file = os.path.join(workdir,
                                 f"port_{'c' if no_warm else 'w'}.json")
        argv = [sys.executable, "-m", "estorch_tpu.serve", "--bundle",
                bundle, "--port", "0", "--port-file", port_file,
                "--cpu-devices", "1", "--max-batch", str(max_batch),
                "--beat-interval", "0.5"] + (["--no-warm"] if no_warm
                                             else [])
        # every leg starts from an EMPTY compile cache of its own: the
        # warm leg's hits must come from the bundle, and the cold leg must
        # not find what a warm leg installed
        cache = tempfile.mkdtemp(prefix="cc_", dir=workdir)
        env = {**os.environ, "JAX_PLATFORMS": "cpu",
               "JAX_COMPILATION_CACHE_DIR": cache}
        t_spawn = time.perf_counter()
        proc = subprocess.Popen(argv, stdout=subprocess.PIPE, text=True,
                                env=env)
        try:
            ready = json.loads(proc.stdout.readline())
            ready_s = time.perf_counter() - t_spawn
            addr = ready["url"].split("://", 1)[1]
            probe = coldstart_probe(addr, total=first_n, conns=4,
                                    obs=[0.1, 0.2, 0.3])
            from estorch_tpu.serve.client import ServeClient

            with ServeClient(addr) as c:
                stats = c.stats()
            proc.send_signal(signal.SIGTERM)
            out, _ = proc.communicate(timeout=120)
            final = json.loads(out.strip().splitlines()[-1])
            cold = stats.get("cold_start") or {}
            return {
                "ready_s": round(ready_s, 3),
                # spawn -> first answered request: THE cold-start metric
                "ttfr_s": round(ready_s + (probe["ttfr_s"] or 0.0), 3),
                "first_p99_ms": probe["first_p99_ms"],
                "first_p50_ms": probe["first_p50_ms"],
                "errors": probe["errors"],
                "compiles_at_load": cold.get("compiles_at_load"),
                "warm_cache_hits": cold.get("warm_cache_hits"),
                "warm_installed": bool((cold.get("warm") or {})
                                       .get("installed")),
                "drain_clean": bool(final.get("clean"))
                and proc.returncode == 0,
            }
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)

    def steady_state_bf16():
        """Anchor-bucket batched throughput, f32 vs bf16, in-process.
        Fenced (np.asarray materializes) and median-of-repeats."""
        import statistics

        import numpy as np

        from estorch_tpu.serve.batcher import measure_quant_divergence
        from estorch_tpu.serve.bundle import load_bundle

        b = load_bundle(bundle, install_warm=True)
        f32 = b.batched_predict_fn()
        bf16 = b.batched_predict_fn(dtype="bf16")
        rng = np.random.default_rng(0)
        obs = rng.standard_normal(
            (max_batch,) + b.obs_shape).astype(np.float32)
        div = measure_quant_divergence(bf16, f32, b.obs_shape,
                                       [max_batch])
        out = {}
        for name, fn in (("f32", f32), ("bf16", bf16)):
            fn(obs)  # compile/warm outside the timed window
            ts = []
            for _ in range(7):
                t0 = time.perf_counter()
                np.asarray(fn(obs))
                ts.append(time.perf_counter() - t0)
            med = statistics.median(ts)
            out[name] = {"ms_per_batch": round(med * 1e3, 3),
                         "rows_per_s": round(max_batch / med, 1)}
        ratio = (out["f32"]["ms_per_batch"] / out["bf16"]["ms_per_batch"]
                 if out["bf16"]["ms_per_batch"] else None)
        return {
            **out,
            "throughput_ratio": round(ratio, 3) if ratio else None,
            "divergence": {str(k): round(v, 6) for k, v in div.items()},
            # XLA:CPU has no bf16 GEMM kernel (measured: the upconvert
            # path is SLOWER than f32) — the >=1.5x gate applies where
            # the hardware has one (TPU MXU); off-chip the number is
            # recorded honestly and the MACHINERY is what's gated
            "bf16_native": jax.default_backend() == "tpu",
            "platform": jax.default_backend(),
        }

    import shutil

    workdir = tempfile.mkdtemp(prefix="coldstart_bench_")
    try:
        t0 = time.perf_counter()
        bundle = es.export_bundle(os.path.join(workdir, "bundle"),
                                  warm=True, warm_max_batch=max_batch,
                                  serve_bf16=True)
        export_warm_s = round(time.perf_counter() - t0, 3)
        warm_rows = [leg(no_warm=False) for _ in range(repeats)]
        cold_rows = [leg(no_warm=True) for _ in range(repeats)]
        bf16_row = steady_state_bf16()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return {"hidden": hidden, "max_batch": max_batch,
            "first_n": first_n, "export_warm_s": export_warm_s,
            "warm": warm_rows, "cold": cold_rows, "bf16": bf16_row,
            "platform": "cpu", "cfg": cfg}


def stage_coldstart(selfcheck=False):
    """Cold-start + quantized-serving gate (docs/serving.md "Cold start
    & quantized serving"); the selfcheck form is the run_lint.sh gate.

    Gates: the warm leg loads with ZERO fresh XLA builds (all
    persistent-cache hits) while the cold leg provably pays the storm;
    warm time-to-first-response beats cold beyond the learned noise band
    (obs regress compare on repeat medians); every bf16 bucket's
    divergence is MEASURED and inside the documented bound; and — on
    hardware with a native bf16 path (TPU) — bf16 steady-state batch
    throughput >= 1.5x f32.  Off-chip the ratio is recorded honestly
    (XLA:CPU's bf16 lowering is an upconvert) and the
    accuracy machinery is what gates."""
    regress = _load_obs_regress()
    cfg = ({"hidden": 1024, "gens": 1, "repeats": 3, "first_n": 40,
            "max_batch": 16}
           if selfcheck else
           {"hidden": 6144, "gens": 1, "repeats": 3, "first_n": 100,
            "max_batch": 16})
    argv = [sys.executable, __file__, "--stage-coldstart-one",
            json.dumps(cfg)]
    child_env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    try:
        r = subprocess.run(argv, timeout=1800, capture_output=True,
                           text=True, env=child_env)
    except subprocess.TimeoutExpired:
        print(json.dumps({"label": "coldstart",
                          "error": "timeout after 1800s"}), flush=True)
        return 1
    try:
        last = [ln for ln in r.stdout.strip().splitlines()
                if ln.startswith("{")][-1]
        row = json.loads(last)
    except (IndexError, ValueError):
        print(json.dumps({"label": "coldstart", "error":
                          f"stage exited {r.returncode}",
                          "stderr_tail": r.stderr[-800:]}), flush=True)
        return 1
    problems = []
    for leg, rows in (("warm", row["warm"]), ("cold", row["cold"])):
        for i, x in enumerate(rows):
            # per-repeat BENCH rows so `obs regress --label
            # coldstart/<leg>` can gate them against a committed file:
            # value is a RATE (first responses per second) because the
            # regress verdict treats higher as better
            print(json.dumps({
                "label": f"coldstart/{leg}", "rep": i,
                "metric": "first_response_per_s",
                "value": round(1.0 / x["ttfr_s"], 4),
                "platform": row["platform"], **x}), flush=True)
            if x["errors"]:
                problems.append(f"{leg} rep {i}: {x['errors']} errors")
            if not x["drain_clean"]:
                problems.append(f"{leg} rep {i}: unclean drain")
    for i, x in enumerate(row["warm"]):
        if x["compiles_at_load"] != 0:
            problems.append(
                f"warm rep {i}: {x['compiles_at_load']} fresh XLA builds "
                "at load (want 0 — every program a cache/AOT hit)")
        if not x["warm_cache_hits"]:
            problems.append(f"warm rep {i}: zero cache hits")
        if not x["warm_installed"]:
            problems.append(f"warm rep {i}: warmth not installed")
    for i, x in enumerate(row["cold"]):
        if not x["compiles_at_load"]:
            problems.append(
                f"cold rep {i}: no fresh builds — the control leg did "
                "not pay the JIT storm this A/B exists to show")
    # warm beats cold on time-to-first-response beyond the learned band
    warm_rates = [1.0 / x["ttfr_s"] for x in row["warm"]]
    cold_rates = [1.0 / x["ttfr_s"] for x in row["cold"]]
    verdict = regress.compare(warm_rates, cold_rates,
                              metric="first_response_per_s")
    if not verdict["improved"]:
        problems.append(
            f"warm TTFR does not beat cold beyond the noise band: "
            f"warm median {verdict['current_median']}/s vs cold "
            f"{verdict['baseline_median']}/s (band "
            f"{verdict['band_pct']}%)")
    bf16 = row["bf16"]
    bound_key = max(bf16["divergence"], key=lambda k: bf16["divergence"][k])
    from estorch_tpu.serve.warm import BF16_DIVERGENCE_BOUND

    if bf16["divergence"][bound_key] > BF16_DIVERGENCE_BOUND:
        problems.append(
            f"bf16 divergence {bf16['divergence']} exceeds the bound "
            f"{BF16_DIVERGENCE_BOUND}")
    if bf16["bf16_native"] and (bf16["throughput_ratio"] or 0) < 1.5:
        problems.append(
            f"bf16 steady-state ratio {bf16['throughput_ratio']} < 1.5x "
            "on a native-bf16 platform")
    ok = not problems
    print(json.dumps({"label": "coldstart", "export_warm_s":
                      row["export_warm_s"], "ttfr": verdict,
                      "bf16": bf16, "problems": problems, "pass": ok}),
          flush=True)
    return 0 if ok else 1


def measure_fleet_one(cfg):
    """Child body for --stage-fleet-one: export a warm bundle, run a
    2-replica fleet + front router (serve/fleet.py) with a declared
    ``kill_replica`` chaos event mid-load, then a capacity sweep against
    the router.  Returns one JSON row; stage_fleet gates it."""
    from estorch_tpu.utils import force_cpu_backend

    force_cpu_backend(1)
    import jax
    import optax

    from estorch_tpu import ES, JaxAgent
    from estorch_tpu.envs.pendulum import Pendulum
    from estorch_tpu.models import MLPPolicy
    from estorch_tpu.resilience.chaos import CHAOS_ENV
    from estorch_tpu.serve.client import ServeClient
    from estorch_tpu.serve.fleet import Fleet
    from estorch_tpu.serve.loadgen import capacity_sweep, run_load

    hidden = int(cfg.get("hidden", 64))
    max_batch = int(cfg.get("max_batch", 4))
    duration_s = float(cfg.get("duration_s", 6.0))
    kill_at_s = float(cfg.get("kill_at_s", 2.0))
    es = ES(
        MLPPolicy, JaxAgent(Pendulum(), horizon=8), optax.adam,
        population_size=4, sigma=0.05, seed=0,
        policy_kwargs={"action_dim": 1, "hidden": (hidden, hidden),
                       "discrete": False, "action_scale": 2.0},
        optimizer_kwargs={"learning_rate": 0.01},
        table_size=1 << 14, device=jax.devices()[0],
    )
    es.train(1, verbose=False)

    import shutil

    workdir = tempfile.mkdtemp(prefix="fleet_bench_")
    fleet = None
    try:
        bundle = es.export_bundle(os.path.join(workdir, "bundle"),
                                  warm=True, warm_max_batch=max_batch)
        fleet = Fleet(
            {"schema": 1, "bundle": bundle, "replicas": 2,
             "serve": {"max_batch": max_batch, "cpu_devices": 1},
             "router": {"retry_budget": 2, "breaker_open_s": 0.5},
             "respawn": {"backoff_s": 0.2}},
            os.path.join(workdir, "run"), port=0)
        fleet.start()
        if not fleet.wait_ready(180):
            return {"error": "fleet did not come up",
                    "status": fleet.status()}
        # INITIAL spawns carry the same warmth proof as respawns:
        # wait_ready() pinned each slot's cold_start from /stats
        initial_cold = [
            {"replica": s.name,
             "compiles_at_load": (s.cold_start or {}).get(
                 "compiles_at_load")}
            for s in fleet.slots]
        # declare the chaos only once the fleet serves: kill_at_s means
        # seconds into SERVING, not into the replicas' jax import
        os.environ[CHAOS_ENV] = json.dumps({
            "events": [{"kind": "kill_replica", "at_s": kill_at_s,
                        "replica": 1}],
            "ledger": os.path.join(workdir, "chaos_ledger")})
        fleet.arm_chaos()
        addr = f"{fleet.router.host}:{fleet.router.port}"
        load = run_load(addr, conns=8, duration_s=duration_s,
                        obs=[0.1, 0.2, 0.3])
        # wait for the respawn to land so its warm proof is readable
        t0 = time.monotonic()
        respawned = False
        while time.monotonic() - t0 < 120:
            slot = fleet.slots[1]
            breakers = {r.name: r.breaker.state
                        for r in fleet.router.replicas()}
            if (slot.restarts >= 1 and slot.state == "up"
                    and breakers.get("r1") == "closed"):
                respawned = True
                break
            time.sleep(0.2)
        cold = None
        if respawned:
            with ServeClient(fleet.slots[1].address) as c:
                cold = c.stats().get("cold_start")
        sweep = capacity_sweep(addr, slo_ms=float(
            cfg.get("slo_ms", 2000.0)),
            rps_ladder=[float(r) for r in cfg.get("rps_ladder",
                                                  [50, 100])],
            conns=8, rung_duration_s=float(cfg.get("rung_s", 1.0)),
            obs=[0.1, 0.2, 0.3])
        st = fleet.router.stats()
        return {
            "load": {k: load[k] for k in ("requests", "errors", "shed",
                                          "throughput_rps",
                                          "latency_ms")},
            "counters": st["counters"],
            "respawned": respawned,
            "respawn_cold_start": cold,
            "initial_cold_starts": initial_cold,
            "events": [e["event"] for e in fleet.events],
            "capacity": sweep,
            "platform": "cpu", "cfg": cfg,
        }
    finally:
        if fleet is not None:
            fleet.shutdown()
        os.environ.pop(CHAOS_ENV, None)
        shutil.rmtree(workdir, ignore_errors=True)


def stage_fleet(selfcheck=False):
    """Fleet robustness gate (serve/router.py + serve/fleet.py,
    docs/serving.md "Fleet"); the selfcheck form is the run_lint.sh
    gate.  Gates: a replica SIGKILLed under concurrent load loses ZERO
    client answers (failover retries within the budget), the breaker
    opened and re-closed, the fleet respawned the corpse WARM
    (compiles_at_load == 0 — PR-12 bundles make a respawn free), and
    the capacity sweep reports a sane max-RPS-at-SLO ladder."""
    cfg = ({"hidden": 48, "duration_s": 5.0, "kill_at_s": 2.0,
            "rps_ladder": [40, 80], "rung_s": 0.8}
           if selfcheck else
           {"hidden": 512, "duration_s": 10.0, "kill_at_s": 3.0,
            "rps_ladder": [50, 100, 200, 400], "rung_s": 2.0})
    argv = [sys.executable, __file__, "--stage-fleet-one",
            json.dumps(cfg)]
    child_env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    try:
        r = subprocess.run(argv, timeout=900, capture_output=True,
                           text=True, env=child_env)
    except subprocess.TimeoutExpired:
        print(json.dumps({"label": "fleet", "error": "timeout after "
                                                     "900s"}),
              flush=True)
        return 1
    try:
        last = [ln for ln in r.stdout.strip().splitlines()
                if ln.startswith("{")][-1]
        row = json.loads(last)
    except (IndexError, ValueError):
        print(json.dumps({"label": "fleet", "error":
                          f"stage exited {r.returncode}",
                          "stderr_tail": r.stderr[-800:]}), flush=True)
        return 1
    problems = []
    if row.get("error"):
        problems.append(row["error"])
    else:
        load = row["load"]
        if load["errors"] or load["shed"]:
            problems.append(
                f"lost client answers under the kill: {load['errors']} "
                f"errors, {load['shed']} shed of {load['requests']}")
        if load["requests"] < 50:
            problems.append(f"load too thin to prove anything: "
                            f"{load['requests']} requests")
        c = row["counters"]
        if not c.get("router_breaker_opens_total"):
            problems.append("breaker never opened for the killed "
                            "replica")
        # INITIAL spawns are judged by the same warmth bar as respawns
        # (today's bundles make the first load free too)
        for ic in row.get("initial_cold_starts") or []:
            if ic.get("compiles_at_load") != 0:
                problems.append(
                    f"initial spawn {ic.get('replica')} was not warm: "
                    f"compiles_at_load={ic.get('compiles_at_load')} "
                    f"(want 0)")
        if not row["respawned"]:
            problems.append("fleet did not respawn the killed replica "
                            "(or its breaker never re-closed)")
        else:
            # warmth is only measurable on a replica that DID respawn
            cold = row.get("respawn_cold_start") or {}
            if cold.get("compiles_at_load") != 0:
                problems.append(
                    f"respawn was not warm: compiles_at_load="
                    f"{cold.get('compiles_at_load')} (want 0)")
        cap = row["capacity"]
        if cap.get("max_rps_at_slo") is None:
            problems.append(f"capacity sweep found no passing rung: "
                            f"{cap}")
        if any(not rung["requests"] for rung in cap["rungs"]):
            problems.append(f"capacity rung ran zero requests: "
                            f"{cap['rungs']}")
    ok = not problems
    print(json.dumps({"label": "fleet", **row, "problems": problems,
                      "pass": ok}), flush=True)
    return 0 if ok else 1


def measure_autoscale_one(cfg):
    """Child body for --stage-autoscale-one: the full closed control
    loop on loopback — warm bundle, 2-replica fleet, in-process
    collector scraping the router into a store, capacity artifact from
    a real sweep, and the autoscaler actuating over HTTP POST /scale.
    Offered load triples mid-run, a declared ``kill_replica`` chaos
    event lands during the scale-up, then traffic drops to a trickle so
    the low-watermark path retires a replica.  Returns one JSON row;
    stage_autoscale gates it."""
    import threading

    from estorch_tpu.utils import force_cpu_backend

    force_cpu_backend(1)
    import jax
    import optax

    from estorch_tpu import ES, JaxAgent
    from estorch_tpu.envs.pendulum import Pendulum
    from estorch_tpu.models import MLPPolicy
    from estorch_tpu.obs.agg import autoscale as azmod
    from estorch_tpu.obs.agg.collector import Collector, Target
    from estorch_tpu.obs.agg.store import SeriesStore
    from estorch_tpu.resilience.chaos import CHAOS_ENV
    from estorch_tpu.serve.fleet import Fleet
    from estorch_tpu.serve.loadgen import (capacity_sweep, run_load,
                                           write_capacity_artifact)

    hidden = int(cfg.get("hidden", 48))
    max_batch = int(cfg.get("max_batch", 4))
    slo_ms = float(cfg.get("slo_ms", 2000.0))
    base_rps = float(cfg.get("base_rps", 25.0))
    es = ES(
        MLPPolicy, JaxAgent(Pendulum(), horizon=8), optax.adam,
        population_size=4, sigma=0.05, seed=0,
        policy_kwargs={"action_dim": 1, "hidden": (hidden, hidden),
                       "discrete": False, "action_scale": 2.0},
        optimizer_kwargs={"learning_rate": 0.01},
        table_size=1 << 14, device=jax.devices()[0],
    )
    es.train(1, verbose=False)

    import shutil

    workdir = tempfile.mkdtemp(prefix="autoscale_bench_")
    fleet = scaler = None
    col_stop = threading.Event()
    col_thread = None
    try:
        bundle = es.export_bundle(os.path.join(workdir, "bundle"),
                                  warm=True, warm_max_batch=max_batch)
        fleet = Fleet(
            {"schema": 1, "bundle": bundle, "replicas": 2,
             "serve": {"max_batch": max_batch, "cpu_devices": 1},
             "router": {"retry_budget": 2, "breaker_open_s": 0.5},
             "respawn": {"backoff_s": 0.2},
             "autoscale": {"min_replicas": 2, "max_replicas": 4}},
            os.path.join(workdir, "run"), port=0)
        fleet.start()
        if not fleet.wait_ready(180):
            return {"error": "fleet did not come up",
                    "status": fleet.status()}
        addr = f"{fleet.router.host}:{fleet.router.port}"
        # per-replica capacity model from a REAL sweep against one
        # replica (not the router): the artifact the policy trusts
        sweep = capacity_sweep(
            fleet.slots[0].address, slo_ms=slo_ms,
            rps_ladder=[float(cfg.get("cap_rps", 40.0))], conns=8,
            rung_duration_s=float(cfg.get("cap_rung_s", 1.0)),
            obs=[0.1, 0.2, 0.3])
        if sweep.get("max_rps_at_slo") is None:
            return {"error": f"capacity sweep saturated: {sweep}"}
        cap_path = os.path.join(workdir, "capacity.json")
        write_capacity_artifact(sweep, cap_path, bundle=bundle)
        # in-process collector: scrape the router into the store the
        # autoscaler reads — the daemon never sees the fleet directly
        store_dir = os.path.join(workdir, "store")
        col = Collector([Target("fleet", url=f"http://{addr}/metrics",
                                timeout_s=5.0)],
                        SeriesStore(store_dir), None, serve_http=False)

        def scrape_loop():
            while not col_stop.is_set():
                col.tick()
                col_stop.wait(0.4)

        col_thread = threading.Thread(target=scrape_loop,
                                      name="bench-collector",
                                      daemon=True)
        col_thread.start()
        scaler = azmod.Autoscaler(
            store_dir, capacity=cap_path, fleet_admin=addr,
            interval_s=float(cfg.get("scaler_interval_s", 0.5)),
            policy={"min_replicas": 2, "max_replicas": 4,
                    "headroom": 1.2,
                    "window_s": float(cfg.get("window_s", 5.0)),
                    "up_cooldown_s": 3.0, "down_cooldown_s": 4.0,
                    "low_watermark": 0.5,
                    "low_hold_s": float(cfg.get("low_hold_s", 3.0))})
        scaler.start_background()
        # chaos declared now: at_s counts from arm — the kill lands in
        # the high-load phase, i.e. during/just after the scale-up
        os.environ[CHAOS_ENV] = json.dumps({
            "events": [{"kind": "kill_replica",
                        "at_s": float(cfg.get("kill_at_s", 8.0)),
                        "replica": 1}],
            "ledger": os.path.join(workdir, "chaos_ledger")})
        fleet.arm_chaos()
        phases = {}
        # phase A: baseline load the min fleet absorbs (target < min)
        phases["base"] = run_load(
            addr, mode="open", target_rps=base_rps,
            duration_s=float(cfg.get("base_s", 5.0)),
            conns=8, obs=[0.1, 0.2, 0.3])
        # phase B: offered load TRIPLES — demand math wants 3 replicas
        phases["spike"] = run_load(
            addr, mode="open", target_rps=base_rps * 3,
            duration_s=float(cfg.get("spike_s", 10.0)),
            conns=16, obs=[0.1, 0.2, 0.3])
        # the scale-up may still be spawning when the spike ends: wait
        # for desired AND actual to converge above the floor
        scaled_up = False
        t0 = time.monotonic()
        while time.monotonic() - t0 < 120:
            sc = fleet.status()["scale"]
            if sc["desired"] > 2 and sc["actual"] >= sc["desired"]:
                scaled_up = True
                break
            time.sleep(0.2)
        up_status = fleet.status()
        # phase C: trickle — utilization sits under the low watermark
        # until the sustained window retires a replica, drained
        phases["trickle"] = run_load(
            addr, mode="open", target_rps=float(cfg.get("trickle_rps",
                                                        4.0)),
            duration_s=float(cfg.get("trickle_s", 14.0)),
            conns=4, obs=[0.1, 0.2, 0.3])
        scaled_down = False
        t0 = time.monotonic()
        while time.monotonic() - t0 < 60:
            sc = fleet.status()["scale"]
            if sc["desired"] < up_status["scale"]["desired"] \
                    and sc["actual"] == sc["desired"]:
                scaled_down = True
                break
            time.sleep(0.2)
        scaler.stop()
        col_stop.set()
        rep = azmod.replay(scaler.log_path)
        events = [e["event"] for e in fleet.events]
        scale_events = [e for e in fleet.events
                        if e["event"].startswith("scale_")
                        or e["event"].startswith("replica_retir")]
        return {
            "phases": {k: {kk: v[kk] for kk in
                           ("requests", "errors", "shed",
                            "throughput_rps", "latency_ms")}
                       for k, v in phases.items()},
            "capacity": {"max_rps_at_slo": sweep["max_rps_at_slo"],
                         "slo_ms": sweep["slo_ms"]},
            "scaled_up": scaled_up,
            "scaled_down": scaled_down,
            "scale_status": fleet.status()["scale"],
            "scale_events": scale_events,
            "events": events,
            "counters": fleet.router.stats()["counters"],
            "replay": {"ok": rep["ok"], "decisions": rep["decisions"],
                       "mismatches": rep["mismatches"][:3]},
            "platform": "cpu", "cfg": cfg,
        }
    finally:
        if scaler is not None:
            scaler.stop()
        col_stop.set()
        if col_thread is not None:
            col_thread.join(timeout=10)
        if fleet is not None:
            fleet.shutdown()
        os.environ.pop(CHAOS_ENV, None)
        shutil.rmtree(workdir, ignore_errors=True)


def stage_autoscale(selfcheck=False):
    """Autoscaler E2E gate (obs/agg/autoscale.py + serve/fleet.py,
    docs/serving.md "Autoscaling"); the selfcheck form is the
    run_lint.sh gate.  Gates: offered load triples mid-run and the
    replica count demonstrably tracks it (up past the floor, back down
    after the trickle), p99 stays inside the SLO through every phase,
    ZERO client errors/shed including through a declared kill_replica
    during the scale-up, every scale-up replica loads warm
    (compiles_at_load == 0), the retirement drains cleanly, and the
    decision log replays bit-exactly."""
    cfg = ({"hidden": 48, "base_rps": 25.0, "base_s": 5.0,
            "spike_s": 10.0, "trickle_s": 14.0, "kill_at_s": 8.0}
           if selfcheck else
           {"hidden": 256, "base_rps": 40.0, "base_s": 8.0,
            "spike_s": 15.0, "trickle_s": 20.0, "kill_at_s": 12.0,
            "cap_rps": 60.0, "cap_rung_s": 2.0})
    argv = [sys.executable, __file__, "--stage-autoscale-one",
            json.dumps(cfg)]
    child_env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    try:
        r = subprocess.run(argv, timeout=900, capture_output=True,
                           text=True, env=child_env)
    except subprocess.TimeoutExpired:
        print(json.dumps({"label": "autoscale",
                          "error": "timeout after 900s"}), flush=True)
        return 1
    try:
        last = [ln for ln in r.stdout.strip().splitlines()
                if ln.startswith("{")][-1]
        row = json.loads(last)
    except (IndexError, ValueError):
        print(json.dumps({"label": "autoscale", "error":
                          f"stage exited {r.returncode}",
                          "stderr_tail": r.stderr[-800:]}), flush=True)
        return 1
    problems = []
    if row.get("error"):
        problems.append(row["error"])
    else:
        slo_ms = row["capacity"]["slo_ms"]
        for name, load in row["phases"].items():
            if load["errors"] or load["shed"]:
                problems.append(
                    f"{name}: lost client answers: {load['errors']} "
                    f"errors, {load['shed']} shed of "
                    f"{load['requests']}")
            if load["latency_ms"]["p99"] > slo_ms:
                problems.append(
                    f"{name}: p99 {load['latency_ms']['p99']}ms "
                    f"breached the {slo_ms}ms SLO")
        if row["phases"]["spike"]["requests"] < 100:
            problems.append("spike phase too thin to prove tracking")
        if not row["scaled_up"]:
            problems.append(
                f"replica count never tracked the 3x load spike: "
                f"{row['scale_status']}")
        if not row["scaled_down"]:
            problems.append(
                f"no scale-down after the trickle window: "
                f"{row['scale_status']}")
        # every scale_up must be matched by a scale_up_warm proof
        # (compiles_at_load == 0 read off the new replica's /stats)
        for ev in row["scale_events"]:
            if ev["event"] == "scale_up_cold":
                problems.append(f"scale-up spawned COLD: {ev}")
        ups = [e for e in row["scale_events"]
               if e["event"] == "scale_up"]
        warm = [e for e in row["scale_events"]
                if e["event"] == "scale_up_warm"]
        if row["scaled_up"] and not ups:
            problems.append("scale-up left no added-replica evidence")
        if len(warm) < len(ups):
            problems.append(f"{len(ups)} scale-up(s) but only "
                            f"{len(warm)} warm proof(s)")
        retired = [e for e in row["scale_events"]
                   if e["event"] == "replica_retired"]
        if row["scaled_down"] and not any(e.get("drained")
                                          for e in retired):
            problems.append(f"retirement did not drain: {retired}")
        if "chaos_kill_replica" not in row["events"]:
            problems.append("declared kill_replica chaos never fired")
        if not row["replay"]["ok"] or not row["replay"]["decisions"]:
            problems.append(
                f"decision log did not replay bit-exactly: "
                f"{row['replay']}")
    ok = not problems
    print(json.dumps({"label": "autoscale", **row,
                      "problems": problems, "pass": ok}), flush=True)
    return 0 if ok else 1


def _default_regress_baseline() -> str | None:
    """Newest committed BENCH_r*.json beside this file, by name."""
    import glob

    here = os.path.dirname(os.path.abspath(__file__))
    cands = sorted(glob.glob(os.path.join(here, "BENCH_r*.json")))
    return cands[-1] if cands else None


def stage_regress(baseline: str | None, repeats: int = 3,
                  force_cpu: bool = False) -> int:
    """Perf gate against a committed baseline (obs/export/regress.py).

    The headline config is measured ``repeats`` times in fresh stage
    children (the --obs-ab repeat discipline: one run cannot resolve a
    small effect on a loaded shared core, so the verdict compares the
    repeat median and learns its noise band from the repeats); a drop
    beyond the band vs the baseline's recorded value exits 1.  Rows and
    the verdict land as JSON lines like every other stage."""
    regress = _load_obs_regress()
    baseline = baseline or _default_regress_baseline()
    if not baseline:
        print(json.dumps({"label": "regress", "error":
                          "no BENCH_r*.json baseline found"}), flush=True)
        return 2
    try:
        base_rows = regress.load_rows(baseline)
        base_samples, base_metric = regress.extract_samples(base_rows)
    except (OSError, ValueError) as e:
        print(json.dumps({"label": "regress",
                          "error": f"baseline: {e}"}), flush=True)
        return 2
    base_platform = regress.measurement_platform(base_rows)
    # probe BEFORE measuring: on a wedged host the repeats would each eat
    # a full stage timeout
    _require_tpu_unless(force_cpu)
    rates = []
    cur_platform = None
    for rep in range(int(repeats)):
        r = run_stage(dict(SMALL), timeout_s=1200 if force_cpu else 600,
                      force_cpu=force_cpu)
        if r and r.get("rate"):
            rates.append(r["rate"])
            cur_platform = r.get("platform") or cur_platform
        print(json.dumps({"label": "regress/repeat", "rep": rep,
                          **(r or {"rate": None, "cfg": SMALL})}),
              flush=True)
    if len(rates) < int(repeats):
        print(json.dumps({"label": "regress", "error":
                          f"{int(repeats) - len(rates)} of {int(repeats)} "
                          "repeats failed"}), flush=True)
        return 2
    try:
        # the ONE platform guard compare_files uses: a cross-platform
        # verdict is a platform mismatch, not a perf result
        regress.ensure_same_platform(cur_platform, base_platform,
                                     cur_what="this run",
                                     base_what=baseline)
    except ValueError as e:
        print(json.dumps({"label": "regress", "baseline": baseline,
                          "error": str(e)}), flush=True)
        return 2
    verdict = regress.compare(rates, base_samples, metric=base_metric)
    print(json.dumps({"label": "regress", "baseline": baseline,
                      **verdict}), flush=True)
    return 0 if verdict["verdict"] == "pass" else 1


def stage_capture_baseline(out_path: str | None = None, repeats: int = 3,
                           gens: int = 12, skip: int = 2,
                           force_cpu: bool = False) -> int:
    """``bench.py --capture-baseline``: produce a committed-baseline
    artifact carrying what ALL the gates need (ROADMAP item 5) — the
    aggregate headline (median of fresh-process repeats), per-generation
    ``phase_rows`` embedded so ``obs regress --phases`` and ``--tail``
    can finally compare against committed history instead of ad-hoc
    reruns, and the typed device-probe verdict.  Writes the BENCH_r*
    schema (atomic tmp+rename) and prints the artifact path + headline
    as JSON lines."""
    regress = _load_obs_regress()
    probe = _require_tpu_unless(force_cpu)
    rates: list[float] = []
    phase_rows: list[dict] = []
    dtype = platform = None
    workdir = _bench_workdir()
    for rep in range(int(repeats)):
        hist_path = os.path.join(workdir, f"capture_hist_{rep}.jsonl")
        # skip covers the warm-up generation PLUS the first timed
        # generation(s): measured captures show the first timed gen
        # still pays compile/cache-load (~7s dispatch vs ~0.5ms steady),
        # and a tail baseline must defend steady state, not the warm-up
        cfg = {**SMALL, "gens": int(gens), "history_out": hist_path,
               "history_skip": int(skip)}
        r = run_stage(cfg, timeout_s=1800 if force_cpu else 900,
                      force_cpu=force_cpu)
        row = {"label": "capture/repeat", "rep": rep}
        if r and r.get("rate"):
            rates.append(r["rate"])
            dtype = r.get("dtype") or dtype
            platform = r.get("platform") or platform
            row["rate"] = round(r["rate"], 1)
            try:
                with open(hist_path) as f:
                    for ln in f:
                        rec = json.loads(ln)
                        rec["repeat"] = rep
                        phase_rows.append(rec)
                os.remove(hist_path)
            except (OSError, ValueError) as e:
                row["history_error"] = str(e)
        else:
            row["rate"] = None
        print(json.dumps(row), flush=True)
    if len(rates) < int(repeats) or not phase_rows:
        print(json.dumps({"label": "capture", "error":
                          f"{int(repeats) - len(rates)} of {int(repeats)} "
                          "repeats failed or left no phase rows"}),
              flush=True)
        return 2
    rates.sort()
    n = len(rates)
    headline = rates[n // 2] if n % 2 else 0.5 * (rates[n // 2 - 1]
                                                  + rates[n // 2])
    # per-group p99s ride the extras so a human reading the committed
    # JSON sees the tail the --tail gate will defend
    groups = regress.extract_tail_groups(phase_rows)
    tail_headline = {
        name: {"p99_s": round(regress._quantile(samples, 0.99), 6),
               "n": len(samples)}
        for name, samples in sorted(groups.items())
    }
    phases_headline: dict = {}
    for name, samples in regress.extract_phase_samples(phase_rows).items():
        ss = sorted(samples)
        m = len(ss)
        phases_headline[name] = round(
            ss[m // 2] if m % 2 else 0.5 * (ss[m // 2 - 1] + ss[m // 2]), 6)
    # the elastic multi-host row (docs/multihost.md): one sync-SPMD +
    # one elastic-fleet leg under the shared straggle_host plan, so the
    # committed trajectory carries the barrier-vs-fold contrast the
    # --elastic-ab gate defends
    elastic_row = capture_elastic_row()
    print(json.dumps({"label": "capture/elastic", **elastic_row}),
          flush=True)
    artifact = {
        "n": len(rates),
        "cmd": "python bench.py --capture-baseline",
        "rc": 0,
        "platform": platform,
        "parsed": {
            "metric": _rate_metric(platform),
            "value": round(headline, 1),
            "unit": (f"env-steps/s/device (Pendulum MLP64x64 pop4096 h200 "
                     f"standard/{dtype}, {platform})"),
        },
        "extras": {
            "device_probe": probe,
            "repeat_rates": [round(x, 1) for x in rates],
            "phases_headline": phases_headline,
            "tail_headline": tail_headline,
            "elastic": elastic_row,
        },
        # the embedded history the --phases/--tail gates consume
        # (obs/export/regress.py expand_embedded_rows)
        "phase_rows": phase_rows,
    }
    if out_path is None:
        here = os.path.dirname(os.path.abspath(__file__))
        idx = 1
        import glob

        for p in glob.glob(os.path.join(here, "BENCH_r*.json")):
            tail = os.path.basename(p)[len("BENCH_r"):-len(".json")]
            if tail.isdigit():
                idx = max(idx, int(tail) + 1)
        out_path = os.path.join(here, f"BENCH_r{idx:02d}.json")
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(artifact, f, indent=2)
        f.write("\n")
    os.replace(tmp, out_path)
    print(json.dumps({"label": "capture", "out": out_path,
                      "value": artifact["parsed"]["value"],
                      "n_phase_rows": len(phase_rows),
                      "phases": sorted(phases_headline)}), flush=True)
    _cleanup_bench_workdir()
    return 0


class EvidenceLockBusy(Exception):
    """The evidence flock is held by another measurement/study process."""


def acquire_evidence_lock(max_wait_s=None, respect_env=True):
    """THE lock protocol for the single host core (round-4 load-
    contamination lesson): every on-chip measurement and CPU-mesh study
    stage serializes through an flock on `.evidence.lock` at the repo
    root.  One implementation, called by every bench.py mode that
    measures.

    Returns an open fd holding the lock (kernel releases it at process
    exit), or None when `respect_env` and EVIDENCE_LOCK_HELD is set (a
    parent already holds the lock and spawned us;
    re-taking it would self-deadlock).  `max_wait_s`: None blocks
    indefinitely, 0 is a non-blocking attempt, otherwise a bounded poll;
    on busy at the deadline raises EvidenceLockBusy."""
    if respect_env and os.environ.get("EVIDENCE_LOCK_HELD"):
        return None
    import fcntl
    fd = os.open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              ".evidence.lock"), os.O_CREAT | os.O_RDWR)
    if max_wait_s is None:
        fcntl.flock(fd, fcntl.LOCK_EX)
        return fd
    deadline = time.time() + max_wait_s
    while True:
        try:
            fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
            return fd
        except BlockingIOError:
            if time.time() >= deadline:
                os.close(fd)
                raise EvidenceLockBusy(
                    f"evidence lock still busy after {max_wait_s:.0f}s")
            time.sleep(10.0)


def _lock_or_warn(max_wait_s=300.0):
    """Bounded wait, then proceed with a stderr note rather than risk an
    external caller's timeout nulling the round's one recorded bench."""
    try:
        return acquire_evidence_lock(max_wait_s=max_wait_s)
    except EvidenceLockBusy:
        print(f"bench: evidence lock still busy after {max_wait_s:.0f}s — "
              "proceeding; rates may be load-shared", file=sys.stderr)
        return None


def _rate_metric(platform) -> str:
    """The name a rate is printed under: only a TPU run is per chip."""
    return ("env_steps_per_sec_per_chip" if platform == "tpu"
            else "env_steps_per_sec_cpu_mesh")


def _die(why: str, code: int = 3):
    """The measured path's only failure exit: one line, non-zero."""
    print(f"bench: FAILED: {why}", file=sys.stderr)
    sys.exit(code)


def _fail_if(failed: list, mode: str) -> int:
    """Exit code of a stage driver: 1 and one line when stages failed."""
    if failed:
        print(f"bench: FAILED: {mode}: {len(failed)} stage(s) failed: "
              f"{', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


def _require_tpu_unless(force_cpu: bool, timeout_s: float = 60.0) -> dict:
    """The stage drivers' platform decision, in seconds and without a
    fallback: an explicit --cpu skips the probe (and is recorded as what
    was asked for); otherwise the typed staged probe
    (doctor.check_device) must find a live TPU, or the run exits non-zero
    with one line saying what it found.  The probe runs in a child, so
    this parent stays jax-free and the chip is free again for the stage
    children."""
    if force_cpu:
        return {"status": "skipped", "requested_platform": "cpu"}
    probe = _load_doctor().check_device(timeout_s=timeout_s, platform="tpu")
    if probe.get("status") != "ok":
        _die(f"no TPU: device probe {probe.get('status')} "
             f"({probe.get('reason')}; found platform "
             f"{probe.get('platform')!r}) in {probe.get('elapsed_s')}s — "
             "--cpu asks for the CPU mesh explicitly")
    print(f"bench: device probe: ok platform={probe.get('platform')} "
          f"n_devices={probe.get('n_devices')} in {probe.get('elapsed_s')}s",
          file=sys.stderr)
    return probe


def _stage_or_die(cfg, timeout_s=600):
    r = run_stage(cfg, timeout_s=timeout_s)
    if r is None:
        _die(f"stage failed: {json.dumps(cfg)}", code=1)
    return r


def main():
    _lock_or_warn()
    _sweep_stale_bench_dirs()
    # the verdict rides the artifact as extras["device_probe"]
    probe = _require_tpu_unless(False)
    # dtype deliberately unset: measure_one picks bf16 on TPU.  Headline
    # runs the STANDARD forward until an on-chip A/B says otherwise
    result = _stage_or_die(dict(SMALL))
    rate, platform = result["rate"], result["platform"]
    base_rate = measure_reference_style_baseline()

    extras = {
        "mfu_headline": result["mfu"],
        # what the headline MFU's denominator IS: the published bf16 peak
        # of the chip's device_kind
        "mfu_basis": result.get("mfu_basis"),
        "device_kind": result.get("device_kind"),
        "device_probe": probe,
        "phases_headline": result.get("phases"),
    }
    # the sharded headline row (docs/sharding.md): the big-policy shape on
    # the param-sharded engine — in-program noise, donated generations,
    # MFU from the shard-aware cost model, per-device peak bytes from the
    # compile ledger (f32 by engine contract)
    r = _stage_or_die({**BIG, "shard": True, "gens": 3})
    extras["sharded"] = {
        "rate": round(r["rate"], 1),
        "mfu": round(r["mfu"], 6) if r["mfu"] is not None else None,
        "dtype": r["dtype"],
        **(r.get("shard") or {}),
    }
    for name, base in (("big_policy", BIG), ("pop10k", POP10K),
                       ("locomotion", LOCO)):
        r = _stage_or_die({**base, "gens": 3})
        extras[name] = {
            "rate": round(r["rate"], 1),
            "mfu": round(r["mfu"], 6) if r["mfu"] is not None else None,
            "dtype": r["dtype"],
            "peak_hbm_gb": r.get("peak_hbm_gb"),
        }

    unit = (f"env-steps/s/chip (Pendulum MLP64x64 pop4096 h200 "
            f"standard/{result['dtype']}, {platform} "
            f"{result.get('device_kind')})")
    print(
        json.dumps(
            {
                "metric": _rate_metric(platform),
                "value": round(rate, 1),
                "unit": unit,
                "vs_baseline": round(rate / base_rate, 2),
                "platform": platform,
                "extras": extras,
            }
        )
    )
    _cleanup_bench_workdir()


_USAGE = """\
usage: bench.py [MODE]

no arguments        full headline benchmark (needs a TPU: exits non-zero
                    with one line when the probe finds none or a stage
                    fails; prints exactly one JSON line)
  --stage-ab        the curated forward A/B (AB_MATRIX)
  --obs-ab          telemetry-overhead A/B
  --chaos [--selfcheck]   recovery-overhead A/B under injected faults
                    (clean vs kills vs a mixed straggler+kill plan on
                     the async scheduler)
  --async-ab [--selfcheck]  sync barrier loop vs event-driven async
                    scheduler under an injected straggler plan
                    (medians + learned noise band via obs regress;
                     gates the >=1.25x throughput win and the
                     zero-silent-drop accounting)
  --elastic-ab [--selfcheck]  synchronous 2-process SPMD multihost loop
                    vs the elastic host-granular fold scheduler under an
                    identical declared straggle_host plan (medians +
                    learned band via obs regress; gates the >=1.25x
                    throughput win, stale-host folds actually firing,
                    and dispatched == consumed + discarded + lost)
  --serve [--selfcheck]   dynamic-batching serving A/B
  --fleet [--selfcheck]   serving-fleet robustness gate: replica SIGKILL
                    under load (declared ESTORCH_CHAOS kill_replica)
                    loses zero client answers, breaker opens/closes,
                    warm respawn (compiles_at_load==0), capacity-sweep
                    max-RPS-at-SLO ladder
  --autoscale [--selfcheck]  autoscaler E2E gate: collector store +
                    capacity artifact + POST /scale close the loop —
                    load triples mid-run, gates p99-in-SLO, zero client
                    errors/shed (including through a declared
                    kill_replica during the scale-up), replica count
                    tracking load both directions, warm scale-ups,
                    drained retirement, bit-exact decision-log replay
  --coldstart [--selfcheck]  warm-bundle vs cold-start A/B + bf16
                    steady-state throughput (gates zero-fresh-builds
                    warm loads, warm-beats-cold TTFR beyond the learned
                    band, measured bf16 divergence; >=1.5x bf16
                    throughput on native-bf16 hardware)
  --shard-ab [--selfcheck]  replicated vs param-sharded same-seed A/B
                    (numerical match + per-device peak bytes + MFU row)
  --scenario-ab [--selfcheck]  scenario-suite A/B: one 10-variant
                    domain-randomized run vs 10 sequential
                    single-scenario runs (gates the >=3x wall-clock win,
                    compile-ledger programs O(1) in variant count, and
                    per-variant fitness coverage)
  --capture-baseline [--out PATH] [--repeats N] [--gens N] [--skip N] [--cpu]
                    produce a committed-baseline BENCH_r*.json carrying
                    the headline median PLUS embedded STEADY-STATE
                    per-generation phase_rows (--skip drops the leading
                    warm-up/compile generations per repeat, default 2),
                    so `obs regress --phases/--tail` gate against
                    committed history
  --regress [BASELINE] [--repeats N] [--cpu]   gate vs newest BENCH_r*.json
(--stage-one/--stage-chaos-one/--stage-async-one/--stage-elastic-one/
 --stage-elastic-worker/--stage-serve-one/--stage-fleet-one/
 --stage-autoscale-one/--stage-shard-ab-one/--stage-scenario-one are
 internal child modes)
"""


if __name__ == "__main__":
    if "-h" in sys.argv or "--help" in sys.argv:
        print(_USAGE, end="")
        sys.exit(0)
    if "--stage-one" in sys.argv:
        cfg = json.loads(sys.argv[sys.argv.index("--stage-one") + 1])
        try:
            out = measure_one(cfg, force_cpu="--cpu" in sys.argv)
        except NoTpuError as e:
            _die(str(e))
        print(json.dumps(out))
    elif "--stage-ab" in sys.argv:
        _lock_or_warn()
        _sweep_stale_bench_dirs()
        rc = stage_ab(force_cpu="--cpu" in sys.argv)
        _cleanup_bench_workdir()
        sys.exit(rc)
    elif "--obs-ab" in sys.argv:
        _lock_or_warn()
        _sweep_stale_bench_dirs()
        rc = stage_obs_ab(force_cpu="--cpu" in sys.argv)
        _cleanup_bench_workdir()
        sys.exit(rc)
    elif "--stage-chaos-one" in sys.argv:
        cfg = json.loads(sys.argv[sys.argv.index("--stage-chaos-one") + 1])
        print(json.dumps(measure_chaos_one(cfg)))
    elif "--stage-async-one" in sys.argv:
        cfg = json.loads(sys.argv[sys.argv.index("--stage-async-one") + 1])
        print(json.dumps(measure_async_one(cfg)))
    elif "--async-ab" in sys.argv:
        # the selfcheck form runs inside run_lint.sh (tiny host config,
        # no device): skip the evidence lock a full measurement takes
        if "--selfcheck" not in sys.argv:
            _lock_or_warn()
        sys.exit(stage_async_ab(selfcheck="--selfcheck" in sys.argv))
    elif "--stage-elastic-worker" in sys.argv:
        cfg = json.loads(
            sys.argv[sys.argv.index("--stage-elastic-worker") + 1])
        print(json.dumps(elastic_sync_worker(cfg)))
    elif "--stage-elastic-one" in sys.argv:
        cfg = json.loads(
            sys.argv[sys.argv.index("--stage-elastic-one") + 1])
        print(json.dumps(measure_elastic_one(cfg)))
    elif "--elastic-ab" in sys.argv:
        # the selfcheck form runs inside run_lint.sh (tiny config, CPU
        # processes over loopback): skip the evidence lock a full
        # measurement takes
        if "--selfcheck" not in sys.argv:
            _lock_or_warn()
        sys.exit(stage_elastic_ab(selfcheck="--selfcheck" in sys.argv))
    elif "--stage-shard-ab-one" in sys.argv:
        cfg = json.loads(sys.argv[sys.argv.index("--stage-shard-ab-one") + 1])
        print(json.dumps(measure_shard_ab(cfg)))
    elif "--shard-ab" in sys.argv:
        # the selfcheck form runs inside run_lint.sh (tiny config, forced
        # CPU mesh in the child): skip the evidence lock a full
        # measurement takes
        if "--selfcheck" not in sys.argv:
            _lock_or_warn()
        sys.exit(stage_shard_ab(selfcheck="--selfcheck" in sys.argv))
    elif "--stage-scenario-one" in sys.argv:
        cfg = json.loads(
            sys.argv[sys.argv.index("--stage-scenario-one") + 1])
        print(json.dumps(measure_scenario_one(cfg)))
    elif "--scenario-ab" in sys.argv:
        # the selfcheck form runs inside run_lint.sh (tiny config, CPU
        # child): skip the evidence lock a full measurement takes
        if "--selfcheck" not in sys.argv:
            _lock_or_warn()
        sys.exit(stage_scenario_ab(selfcheck="--selfcheck" in sys.argv))
    elif "--stage-serve-one" in sys.argv:
        cfg = json.loads(sys.argv[sys.argv.index("--stage-serve-one") + 1])
        print(json.dumps(measure_serve_one(cfg)))
    elif "--stage-fleet-one" in sys.argv:
        cfg = json.loads(sys.argv[sys.argv.index("--stage-fleet-one") + 1])
        print(json.dumps(measure_fleet_one(cfg)))
    elif "--stage-autoscale-one" in sys.argv:
        cfg = json.loads(
            sys.argv[sys.argv.index("--stage-autoscale-one") + 1])
        print(json.dumps(measure_autoscale_one(cfg)))
    elif "--autoscale" in sys.argv:
        # the selfcheck form runs inside run_lint.sh (tiny policy, CPU,
        # loopback only): skip the evidence lock a full measurement takes
        if "--selfcheck" not in sys.argv:
            _lock_or_warn()
        sys.exit(stage_autoscale(selfcheck="--selfcheck" in sys.argv))
    elif "--fleet" in sys.argv:
        # the selfcheck form runs inside run_lint.sh (tiny policy, CPU,
        # loopback only): skip the evidence lock a full measurement takes
        if "--selfcheck" not in sys.argv:
            _lock_or_warn()
        sys.exit(stage_fleet(selfcheck="--selfcheck" in sys.argv))
    elif "--stage-coldstart-one" in sys.argv:
        cfg = json.loads(
            sys.argv[sys.argv.index("--stage-coldstart-one") + 1])
        print(json.dumps(measure_coldstart_one(cfg)))
    elif "--coldstart" in sys.argv:
        # the selfcheck form runs inside run_lint.sh (smaller policy,
        # CPU, loopback only): skip the evidence lock a full measurement
        # takes
        if "--selfcheck" not in sys.argv:
            _lock_or_warn()
        sys.exit(stage_coldstart(selfcheck="--selfcheck" in sys.argv))
    elif "--capture-baseline" in sys.argv:
        _lock_or_warn()
        _sweep_stale_bench_dirs()
        kw = {}
        if "--out" in sys.argv:
            kw["out_path"] = sys.argv[sys.argv.index("--out") + 1]
        if "--repeats" in sys.argv:
            kw["repeats"] = int(sys.argv[sys.argv.index("--repeats") + 1])
        if "--gens" in sys.argv:
            kw["gens"] = int(sys.argv[sys.argv.index("--gens") + 1])
        if "--skip" in sys.argv:
            kw["skip"] = int(sys.argv[sys.argv.index("--skip") + 1])
        sys.exit(stage_capture_baseline(force_cpu="--cpu" in sys.argv,
                                        **kw))
    elif "--regress" in sys.argv:
        _lock_or_warn()
        idx = sys.argv.index("--regress")
        baseline = None
        if idx + 1 < len(sys.argv) and not sys.argv[idx + 1].startswith("-"):
            baseline = sys.argv[idx + 1]
        repeats = 3
        if "--repeats" in sys.argv:
            repeats = int(sys.argv[sys.argv.index("--repeats") + 1])
        _sweep_stale_bench_dirs()
        rc = stage_regress(baseline, repeats=repeats,
                           force_cpu="--cpu" in sys.argv)
        _cleanup_bench_workdir()
        sys.exit(rc)
    elif "--serve" in sys.argv:
        # the selfcheck form runs inside run_lint.sh (tiny policy, CPU,
        # loopback only): skip the evidence lock a full measurement takes
        if "--selfcheck" not in sys.argv:
            _lock_or_warn()
        sys.exit(stage_serve(selfcheck="--selfcheck" in sys.argv))
    elif "--chaos" in sys.argv:
        # the selfcheck form runs inside run_lint.sh (single tiny host
        # config, no device): skip the evidence lock a full measurement
        # would take
        if "--selfcheck" not in sys.argv:
            _lock_or_warn()
        sys.exit(stage_chaos(selfcheck="--selfcheck" in sys.argv))
    elif len(sys.argv) > 1:
        # the default full bench takes NO arguments — a typo'd flag
        # silently launching a multi-minute measurement is the worst
        # possible "help" (this happened: `--help` ran the benchmark)
        print(f"bench.py: unrecognized arguments: "
              f"{' '.join(sys.argv[1:])}\n{_USAGE}",
              end="", file=sys.stderr)
        sys.exit(2)
    else:
        main()
