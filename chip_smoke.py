"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py            # from the root of a checkout, on a TPU host

Drives the main path once through the entry points a user calls, at the full
width of a shipped model, with weights made from a seed:

* **train** (a child process, the only one on the chip while it lives):
  ``estorch_tpu.configs.humanoid2d_pop10k()`` exactly as shipped — Humanoid2D,
  MLP 25→256→256→10, population 10240, mirrored, rank-1 noise, running obs
  normalization — three generations through ``ES.train``.  Checks: the mesh
  spans every device jax reports, env steps per generation inside what the
  config implies, finite fitness, finite parameters that moved, ZERO XLA
  programs built after generation 0, non-zero peak HBM on every mesh device.
  Then the Pallas kernels (row gather, weighted sum) are
  lowered through Mosaic at this policy's shapes and compared with their
  ``jnp`` references; on a host with more
  than one chip, the multi-chip checks run in the same process (all-gather
  and all-reduce over N participants in the compiled program; one chip vs
  all chips from one seed — same fitness, same update, same first trained
  generation, a bounded drift after the second; one param-sharded
  generation on a (pop, model) mesh).  It
  ends by recording ``es.predict`` for 32 observations and exporting a
  serving bundle, and exits — which frees the chip.
* **serve**: a fresh ``python -m estorch_tpu.serve --bundle …`` (no
  ``--cpu-devices``).  This parent, which never touches a jax backend, is
  the client: 16 requests over one keep-alive connection, 16 from four
  concurrent connections, every action finite and equal to the trainer's
  ``es.predict``, ``/stats`` counting 32, SIGTERM, a clean drain, exit 0.

Both children share one persistent compile cache: ``JAX_COMPILATION_CACHE_DIR``
where it is set, else the fixed in-checkout default — a second run reports
cache hits where the first built.

Exit code 0 and, as the LAST line of stdout, one JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": …, "count": …}}`` only
if every phase held.  Anything else — no accelerator, a failed check, a
timeout — is a non-zero exit with one ``chip_smoke: FAILED`` line and no
result.  Everything is generated from seeds; nothing git would not commit is
read; outputs go to ``chip_smoke_out/`` (git-ignored).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(HERE, "chip_smoke_out")
GENERATIONS = 3
N_REQUESTS = 32
TRAIN_LIMIT_S = 840.0
SERVE_LIMIT_S = 240.0


class SmokeFailure(Exception):
    """A phase did not hold; the message is the one line printed."""


def check(cond, why: str) -> None:
    if not cond:
        raise SmokeFailure(why)


# =========================================================================
# train child: the only process on the chip while it lives
# =========================================================================

def _np(x):
    import numpy as np

    return np.asarray(x)


def _kernel_check(es) -> None:
    """The Pallas kernels through Mosaic (interpret=False) at this
    policy's shapes, against their jnp references: the row gather bit for
    bit, the weighted sum at the tolerances of
    tests/test_pallas_noise.py — except the reduction's absolute floor,
    which is the f32 forward-error bound of an n-term sum (n·eps·max|w|·
    max|ε|): over 75,018 outputs some sums land near zero, where the
    tests' 1e-6 (fine for their ≤ 257 outputs) is below what reordering
    an f32 sum can move.  References run at f32 matmul precision: the
    kernels accumulate in f32, and the chip's default f32 matmul is a
    single bf16 pass."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from estorch_tpu.ops import rank_weighted_noise_sum
    from estorch_tpu.ops.pallas_noise import (gather_noise_rows,
                                              weighted_noise_sum)

    spec, table = es._spec, es.table
    dim = int(spec.dim)
    key = jax.random.key(20)
    n = 64
    last = table.size - dim
    offs = jax.random.randint(key, (n,), 0, last, dtype=jnp.int32)
    # every alignment class of the DMA window, and the table's last row
    offs = offs.at[:6].set(jnp.array([0, 1, 127, 1023, last - 1, last],
                                     jnp.int32))
    w = jax.random.normal(jax.random.fold_in(key, 1), (n,))
    t0 = time.perf_counter()
    for dtype in (jnp.bfloat16, jnp.float32):
        got = gather_noise_rows(table.data, offs, dim=dim, dtype=dtype,
                                interpret=False)
        want = jax.vmap(lambda o: table.slice(o, dim))(offs).astype(dtype)
        np.testing.assert_array_equal(_np(got.astype(jnp.float32)),
                                      _np(want.astype(jnp.float32)))
    print(f"kernel gather_noise_rows: dim {dim}, {n} rows, bf16 and f32: "
          "compiled by Mosaic, equal to table.slice bit for bit", flush=True)
    with jax.default_matmul_precision("highest"):
        got = weighted_noise_sum(table.data, offs, w, dim=dim,
                                 interpret=False)
        want = rank_weighted_noise_sum(table, offs, w, dim=dim)
        atol = (n * float(np.finfo(np.float32).eps)
                * float(jnp.abs(w).max()) * float(jnp.abs(table.data).max()))
        np.testing.assert_allclose(_np(got), _np(want), rtol=1e-5, atol=atol)
        print(f"kernel weighted_noise_sum: dim {dim}, {n} rows: compiled by "
              f"Mosaic, matches rank_weighted_noise_sum (max abs err "
              f"{float(jnp.abs(got - want).max()):.2e})", flush=True)
    print(f"kernels: {time.perf_counter() - t0:.1f} s", flush=True)


def _collectives_over(text: str, n: int) -> dict:
    """How many all-gather / all-reduce ops of a compiled program's text
    run over replica groups of exactly ``n`` participants (XLA prints the
    groups as ``{{0,1,2,3}}`` or, in iota form, ``[groups,size]<=[…]``)."""
    import re

    found = {"all-gather": 0, "all-reduce": 0}
    for line in text.splitlines():
        op = re.search(r" (all-gather|all-reduce)(?:-start)?\(", line)
        if not op:
            continue
        listed = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
        iota = re.search(r"replica_groups=\[\d+,(\d+)\]<=", line)
        size = (len(listed.group(1).split(",")) if listed
                else int(iota.group(1)) if iota else 0)
        if size == n:
            found[op.group(1)] += 1
    return found


def _multi_chip_check(es, devices) -> None:
    """Tentpole 6: population parallelism over every chip of the host,
    one process driving all of them."""
    import jax
    import numpy as np
    import optax

    from estorch_tpu import ES, JaxAgent, MLPPolicy
    from estorch_tpu.envs import Pendulum, SyntheticEnv
    from estorch_tpu.ops import centered_rank_np

    n = len(devices)
    t0 = time.perf_counter()
    problems: list = []  # every part reports before the phase fails

    def expect(cond, why):
        if not cond:
            problems.append(why)

    # (a) the compiled pop-10k generation program talks to all n chips
    compiled = es.engine._generation_step.lower(es.state).compile()
    found = _collectives_over(compiled.as_text(), n)
    print(f"multi-chip: generation program collectives over {n} "
          f"participants: {found}", flush=True)
    expect(found["all-gather"] > 0 and found["all-reduce"] > 0,
           f"generation program lacks an all-gather and an all-reduce over "
           f"{n} participants: {found}")

    # (b) one seed, one chip vs all chips, at the tolerance
    # tests/test_engine.py uses for 8-vs-1 (rtol 2e-5, atol 1e-6): the
    # fitness of one population, the update from IDENTICAL rank weights
    # (noise regenerated per device, partial sums psum'd over n chips),
    # and one trained generation.  After that the runs are allowed to
    # drift: the psum orders its sum differently than one chip does, so
    # the centres differ in the last bit after generation 0; generation 1
    # rolls 4096 members through 200 pendulum steps from those centres, a
    # few near-tied members trade ranks, and Adam's per-coordinate
    # normalisation shows that at coordinates whose gradient is near zero
    # (measured on the v5e: 3.3e-5 to 6.1e-5 at worst, some 220 of 4481
    # coordinates past the strict tolerance; the cause is inferred, not
    # separately verified).  Two trained generations must agree within 5%
    # of one Adam step.
    lr, strict = 1e-2, dict(rtol=2e-5, atol=1e-6)
    strict_s = "rtol 2e-5 / atol 1e-6"

    def small(device):
        # a 16 MiB noise table: six programs are built here, and each
        # carries its table as a constant
        return ES(
            MLPPolicy, JaxAgent, optax.adam, population_size=4096,
            sigma=0.05, seed=0, device=device, table_size=1 << 22,
            policy_kwargs={"action_dim": 1, "hidden": (64, 64),
                           "discrete": False, "action_scale": 2.0},
            agent_kwargs={"env": Pendulum(), "horizon": 200},
            optimizer_kwargs={"learning_rate": lr}, telemetry=False)

    def worst(a, b):
        return float(np.abs(a - b).max())

    one, many = small(devices[:1]), small(devices)
    expect(one.mesh.devices.size == 1 and many.mesh.devices.size == n,
           "parity meshes are not 1 and all devices")
    f1 = _np(one.engine.evaluate(one.state).fitness)
    fn = _np(many.engine.evaluate(many.state).fitness)
    weights = centered_rank_np(f1)
    u1 = _np(one.engine.apply_weights(one.state, weights)[0].params_flat)
    un = _np(many.engine.apply_weights(many.state, weights)[0].params_flat)
    one.train(1, verbose=False)
    many.train(1, verbose=False)
    g1, gn = _np(one.state.params_flat), _np(many.state.params_flat)
    print(f"multi-chip: 1-vs-{n}: fitness of one population differs at "
          f"{int((f1 != fn).sum())} of {f1.size} members; max abs param "
          f"diff {worst(u1, un):.3e} after the update from identical "
          f"weights, {worst(g1, gn):.3e} after one trained generation",
          flush=True)
    expect(np.allclose(fn, f1, **strict), f"1-vs-{n} fitness differs")
    expect(np.allclose(un, u1, **strict),
           f"1-vs-{n} update from identical weights differs beyond "
           f"{strict_s} (max abs diff {worst(u1, un):.3e})")
    expect(np.allclose(gn, g1, **strict),
           f"1-vs-{n} parameters after one trained generation differ "
           f"beyond {strict_s} (max abs diff {worst(g1, gn):.3e})")
    one.train(1, verbose=False)
    many.train(1, verbose=False)
    p1, pn = _np(one.state.params_flat), _np(many.state.params_flat)
    off = ~np.isclose(pn, p1, **strict)
    print(f"multi-chip: 1-vs-{n} after two trained generations: max abs "
          f"param diff {worst(p1, pn):.3e} ({int(off.sum())} of {p1.size} "
          f"coordinates past {strict_s}); reward_mean "
          f"{[r['reward_mean'] for r in one.history]} vs "
          f"{[r['reward_mean'] for r in many.history]}", flush=True)
    expect(worst(p1, pn) <= 0.05 * lr,
           f"1-vs-{n} parameters after two trained generations differ by "
           f"more than 5% of one Adam step (max abs diff "
           f"{worst(p1, pn):.3e})")

    # (c) one generation of the param-sharded engine on a (pop, model) mesh
    env = SyntheticEnv()
    big = ES(
        MLPPolicy, JaxAgent, optax.adam, population_size=4096, sigma=0.05,
        seed=0, shard_params=True, model_shards=2,
        policy_kwargs={"action_dim": env.action_dim, "hidden": (256, 256),
                       "discrete": False, "action_scale": 1.0},
        agent_kwargs={"env": env, "horizon": 200},
        optimizer_kwargs={"learning_rate": 1e-2}, telemetry=False)
    big.train(1, verbose=False)
    leaves = jax.tree_util.tree_leaves(big.state.params)
    for leaf in leaves:
        on = {s.device for s in leaf.addressable_shards}
        expect(on == set(devices),
               f"sharded leaf {leaf.shape} lives on {len(on)} of {n} devices")
        expect(bool(np.isfinite(_np(leaf)).all()),
               "sharded params not finite")
    rec = big.history[-1]
    expect(np.isfinite(rec["reward_mean"]) and rec["n_failed"] == 0,
           f"sharded generation fitness not finite: {rec}")
    print(f"multi-chip: sharded generation on mesh "
          f"{dict(zip(big.mesh.axis_names, big.mesh.devices.shape))}: "
          f"{len(leaves)} leaves each on {n} devices, reward_mean "
          f"{rec['reward_mean']:.3f}, {rec['wall_time_s']:.2f} s", flush=True)
    print(f"multi-chip: {time.perf_counter() - t0:.1f} s", flush=True)
    check(not problems, "multi-chip: " + "; ".join(problems))


def train_child() -> None:
    import jax
    import numpy as np

    devices = jax.devices()
    dev = devices[0]
    print(f"jax {jax.__version__}", flush=True)
    print(f"platform {dev.platform}", flush=True)
    print(f"device_kind {dev.device_kind}", flush=True)
    print(f"device_count {len(devices)}", flush=True)
    check(dev.platform == "tpu",
          f"jax found platform {dev.platform!r} ({len(devices)} device(s)), "
          "not a TPU")
    label = f"[{dev.device_kind} x{len(devices)}]"

    from estorch_tpu import configs
    from estorch_tpu.obs.profile import device_roofline
    from estorch_tpu.utils import (compile_event_counts,
                                   enable_compilation_cache,
                                   install_compile_event_counters)

    device_roofline(dev.device_kind)  # an unknown chip is an error here too
    cache_dir = enable_compilation_cache(min_compile_time_s=0.0)
    print(f"compile cache {cache_dir}", flush=True)
    install_compile_event_counters()

    t_build = time.perf_counter()
    es = configs.humanoid2d_pop10k()  # exactly as shipped: nothing is cut
    cfg = es.config
    pop = cfg.population_size
    print(f"config humanoid2d_pop10k: population {pop}, horizon "
          f"{cfg.horizon}, dim {es._spec.dim}, low_rank {cfg.low_rank}, "
          f"obs_norm {cfg.obs_norm}, eval_chunk {cfg.eval_chunk}, mirrored "
          f"{cfg.mirrored}; built in {time.perf_counter() - t_build:.1f} s",
          flush=True)
    check((pop, cfg.horizon, cfg.low_rank, cfg.obs_norm, cfg.mirrored)
          == (10240, 400, 1, True, True) and es._spec.dim == 75018,
          "configs.humanoid2d_pop10k is not the shipped configuration")
    check(es.mesh.devices.size == len(devices)
          and set(es.mesh.devices.flat) == set(devices),
          f"mesh {[d.id for d in es.mesh.devices.flat]} does not span the "
          f"{len(devices)} devices jax reports")

    params0 = _np(es.state.params_flat).copy()
    c0 = compile_event_counts()
    es.train(1, verbose=True)
    c1 = compile_event_counts()
    es.train(GENERATIONS - 1, verbose=True)
    c2 = compile_event_counts()

    def delta(a, b):
        hits = b["cache_hits"] - a["cache_hits"]
        return {"programs": b["programs"] - a["programs"],
                "cache_hits": hits,
                "fresh": b["programs"] - a["programs"] - hits,
                "seconds": round(b["build_s"] - a["build_s"], 2)}

    first, later = delta(c0, c1), delta(c1, c2)
    print(f"{label} set-up: generation program compile (AOT) "
          f"{es.compile_time_s:.1f} s; XLA programs acquired through "
          f"generation 0: {first}", flush=True)
    print(f"{label} XLA programs acquired in generations 1-"
          f"{GENERATIONS - 1}: {later}", flush=True)
    check(later["programs"] == 0,
          f"{later['programs']} XLA program(s) built after generation 0 — "
          "the generation program must compile once")

    check(len(es.history) == GENERATIONS, "history length")
    for r in es.history:
        check(pop <= r["env_steps"] <= pop * cfg.horizon,
              f"generation {r['generation']}: env_steps {r['env_steps']} "
              f"outside [{pop}, {pop * cfg.horizon}]")
        check(r["n_failed"] == 0 and all(
            np.isfinite(r[k]) for k in ("reward_max", "reward_mean",
                                        "reward_min", "grad_norm")),
            f"generation {r['generation']}: fitness not finite: {r}")
    params = _np(es.state.params_flat)
    check(bool(np.isfinite(params).all()), "updated parameters not finite")
    moved = float(np.abs(params - params0).max())
    check(moved > 0.0, "parameters did not move")
    print(f"{label} seconds per generation: "
          f"{[round(r['wall_time_s'], 3) for r in es.history]}; env steps: "
          f"{[r['env_steps'] for r in es.history]}; max |Δθ| {moved:.4f}",
          flush=True)
    peaks = {}
    for d in es.mesh.devices.flat:
        peak = (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        check(peak > 0, f"device {d.id} reports no peak_bytes_in_use")
        peaks[d.id] = round(peak / 2**20, 1)
    print(f"{label} peak HBM per device (MiB): {peaks}", flush=True)

    _kernel_check(es)
    if len(devices) > 1:
        _multi_chip_check(es, devices)

    # what the server must answer: the trainer's own predict, recorded
    # beside the bundle
    obs = np.random.default_rng(7).standard_normal(
        (N_REQUESTS, int(es.env.obs_dim))).astype(np.float32)
    actions = _np(es.predict(obs))
    check(actions.shape == (N_REQUESTS, int(es.env.action_dim))
          and bool(np.isfinite(actions).all()), "es.predict not finite")
    bundle = es.export_bundle(os.path.join(OUT, "bundle"),
                              version="chip-smoke")
    with open(os.path.join(OUT, "expected.json"), "w") as f:
        json.dump({"obs": obs.tolist(), "actions": actions.tolist(),
                   "device": {"platform": dev.platform,
                              "kind": dev.device_kind,
                              "count": len(devices)}}, f)
    print(f"bundle {bundle}", flush=True)


# =========================================================================
# serve phase: a fresh server process; this (jax-free) parent is the client
# =========================================================================

def serve_phase(env: dict, expected: dict) -> None:
    import numpy as np

    from estorch_tpu.serve import ServeClient

    log_path = os.path.join(OUT, "serve.log")
    port_file = os.path.join(OUT, "port.json")
    if os.path.exists(port_file):
        os.remove(port_file)
    argv = [sys.executable, "-m", "estorch_tpu.serve",
            "--bundle", os.path.join(OUT, "bundle"), "--port", "0",
            "--port-file", port_file, "--max-batch", "32"]
    deadline = time.monotonic() + SERVE_LIMIT_S
    with open(log_path, "w") as log:
        proc = subprocess.Popen(argv, stdout=log, stderr=subprocess.STDOUT,
                                env=env, cwd=HERE)
    try:
        while not os.path.exists(port_file):
            check(proc.poll() is None,
                  f"server exited {proc.returncode} before READY:\n"
                  + open(log_path).read()[-2000:])
            check(time.monotonic() < deadline, "server never became READY")
            time.sleep(0.2)
        ready = json.loads(
            [ln for ln in open(log_path) if ln.startswith('{"ready"')][0])
        print(f"serve ready: {json.dumps(ready)}", flush=True)
        check(ready.get("platform") == "tpu",
              f"server serves on platform {ready.get('platform')!r}, "
              "not the TPU")
        obs = np.asarray(expected["obs"], np.float32)
        want = np.asarray(expected["actions"], np.float32)
        got = np.full_like(want, np.nan)
        address = ready["url"]

        def ask(rows):  # one keep-alive connection per caller
            with ServeClient(address) as c:
                return [c.predict(obs[i].tolist()) for i in rows]

        # 16 over one connection, then 16 from four concurrent connections
        parts = [range(16)] + [range(16 + k, N_REQUESTS, 4) for k in range(4)]
        try:
            got[list(parts[0])] = ask(parts[0])
            with ThreadPoolExecutor(max_workers=4) as pool:
                for rows, answers in zip(parts[1:],
                                         pool.map(ask, parts[1:], timeout=60)):
                    got[list(rows)] = answers
        except Exception as e:  # noqa: BLE001 — any client fault fails the phase
            raise SmokeFailure(f"a /predict request failed: {e!r}") from e
        check(bool(np.isfinite(got).all()), "a served action is not finite")
        err = float(np.abs(got - want).max())
        print(f"serve: {N_REQUESTS} answers, max |served - es.predict| "
              f"{err:.2e}", flush=True)
        check(err <= 1e-5, f"served actions differ from the trainer's "
                           f"es.predict by {err:.3e} (> 1e-5)")
        with ServeClient(address) as c:
            stats = c.stats()
        counted = int(stats["counters"].get("requests_total", -1))
        check(counted == N_REQUESTS,
              f"/stats counted {counted} requests, sent {N_REQUESTS}")
        proc.send_signal(signal.SIGTERM)
        try:
            code = proc.wait(timeout=60.0)
        except subprocess.TimeoutExpired:
            raise SmokeFailure("server did not exit within 60 s of SIGTERM")
        final = json.loads(open(log_path).read().strip().splitlines()[-1])
        print(f"serve final: clean={final.get('clean')} exit={code}",
              flush=True)
        check(final.get("clean") is True and code == 0,
              f"server did not drain clean (exit {code}): {final}")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30.0)


# =========================================================================
# parent
# =========================================================================

def parent() -> dict:
    try:
        import jax
        from jax._src import xla_bridge

        from estorch_tpu.utils.backend import (CACHE_DIR_ENV,
                                               default_compilation_cache_dir)
    except ImportError as e:
        raise SmokeFailure(
            f"the program is not here ({e}); run from the root of a "
            "checkout") from None
    os.makedirs(OUT, exist_ok=True)
    # ONE compile cache for both children, enabled before either compiles:
    # the directory the environment names, else the fixed in-checkout
    # default; every program persisted (the serving buckets build in
    # well under jax's 1 s default threshold)
    env = dict(os.environ)
    env.setdefault(CACHE_DIR_ENV, default_compilation_cache_dir())
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "-1"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (HERE, env.get("PYTHONPATH")) if p)
    print(f"chip_smoke: jax {jax.__version__}, compile cache "
          f"{env[CACHE_DIR_ENV]}, out {OUT}", flush=True)

    expected_path = os.path.join(OUT, "expected.json")
    if os.path.exists(expected_path):
        os.remove(expected_path)
    t0 = time.monotonic()
    proc = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", "train"],
        env=env, cwd=HERE)
    try:
        code = proc.wait(timeout=TRAIN_LIMIT_S)
    except subprocess.TimeoutExpired:
        raise SmokeFailure(
            f"train child exceeded {TRAIN_LIMIT_S:.0f} s") from None
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30.0)
    check(code == 0, f"train child exited {code}")
    print(f"chip_smoke: train phase {time.monotonic() - t0:.1f} s",
          flush=True)
    with open(expected_path) as f:
        expected = json.load(f)

    t0 = time.monotonic()
    serve_phase(env, expected)
    print(f"chip_smoke: serve phase {time.monotonic() - t0:.1f} s",
          flush=True)
    # a parent that had touched jax would have held the chip against both
    # children; prove it never did
    check(not xla_bridge._backends,
          f"the parent initialised a jax backend: {list(xla_bridge._backends)}")
    return expected["device"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--child", choices=("train",), default=None,
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    try:
        if args.child == "train":
            train_child()
            return 0
        device = parent()
    except (SmokeFailure, AssertionError) as e:
        print(f"chip_smoke: FAILED: {e}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
