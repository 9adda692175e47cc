"""Traffic kind ``train``: generations back to back through ``ES.train``.

Set-up (all of it inside ``setup_s``, but for the runtime's own bring-up of
the chips): build the ES from the configuration file and ``--seed`` on the
cell's chips, warm up, check generation 0 of the measured program against
the plain reference and, where the configuration has ``first_steps``, the
first steps of a small build of the same file tightly.  Window: ``ES.train`` called in small fixed batches
with a ``log_fn`` that stamps each generation's completion fence; it closes
at the first fence at or after ``--seconds``.  With ``--trace 1`` a few more
generations run under the profiler AFTER the window, so that the readings of
the window are the same with and without a trace.
"""

from __future__ import annotations

import dataclasses
import os
import shutil
import statistics
import time

from benchmark import trace_reduce, window
from benchmark.files import (import_name, load_file_module, load_json,
                             resolve)

HERE = os.path.dirname(os.path.abspath(__file__))


def build_es(config: dict, seed: int, devices, extra_kwargs: dict,
             declares: dict | None = None):
    """The ES of the configuration file on ``devices``, with its inputs made
    from ``seed``: initial weights and the PRNG key that draws every noise
    offset and rollout.  The noise table is the file's fixed ``table_seed``
    and not ``seed``: the program embeds the table in the generation
    program as a constant, so a table per seed would be a new executable
    per run, and no run would ever find its program in the compile cache.

    Everything that depends on the policy family (parameter count, initial
    weights, the plain rollout) comes from the reference module the file
    names; every top-level key of the file that is also a field of the
    built ``EngineConfig`` (or ``obs_dim``, ``action_dim``, ``param_dim``)
    has to hold what was built, so the file describes what is run
    (``extra_kwargs`` and ``declares`` stand in for the keys they name)."""
    import jax

    build = config["build"]
    kwargs = {**build.get("kwargs", {}), **extra_kwargs}
    es = import_name(build["$call"])(**resolve(kwargs),
                                     seed=config["table_seed"],
                                     device=list(devices))
    ref = load_file_module(os.path.join(HERE, "reference",
                                        config["reference"] + ".py"))
    declared = {**config, **extra_kwargs, **(declares or {}),
                "param_dim": ref.describe(config)["param_dim"]}
    built = {**dataclasses.asdict(es.config),
             "obs_dim": int(es.env.obs_dim),
             "action_dim": int(es.env.action_dim),
             "param_dim": int(es._spec.dim)}
    differ = {k: {"file": declared[k], "built": built[k]}
              for k in built if k in declared and declared[k] != built[k]}
    if differ:
        raise SystemExit(f"configuration file {config['name']} does not "
                         f"describe what was built: {differ}")
    key = jax.random.PRNGKey(seed)
    theta = ref.init_theta(jax.random.fold_in(key, 0), config)
    es.state = es.engine.init_state(theta, jax.random.fold_in(key, 1))
    return es, ref


def check_reference(what, es, ref, outputs, tol, config, seed, source,
                    source_state, say) -> bool:
    """Generation-0 ``outputs`` (fitness and behaviour vector per member,
    as ``es``'s program gave them) of a seeded sample of members against
    the plain reference.  A member agrees when its return is within
    ``rtol`` of the reference's and, where ``tol`` has ``behaviour_atol``,
    every component of its behaviour vector within that.  ``correct`` needs
    ``min_agree_share`` of the sample to agree and, where ``tol`` has
    ``outlier_rtol``, EVERY member's return within that.
    The configuration file gives each number with its reason.  ``source``
    is the ES whose generation-0 ``source_state`` and table the reference
    reads theta, offsets, keys and noise from: the system's own, except in
    the rehearsal that gives the reference another seed."""
    import jax.numpy as jnp
    import numpy as np

    cfg = es.config
    members = np.sort(np.random.default_rng(seed).choice(
        cfg.population_size, replace=False,
        size=min(config["reference_members"], cfg.population_size)))
    rows = members // 2 if cfg.mirrored else members
    signs = (np.where(members % 2 == 0, 1.0, -1.0) if cfg.mirrored
             else np.ones(len(members))).astype(np.float32)
    n_rows = cfg.population_size // 2 if cfg.mirrored else cfg.population_size
    offsets = np.asarray(source.engine.all_pair_offsets(source_state))[rows]
    keys = ref.member_keys(source_state.key, source_state.generation,
                           n_rows)[rows]
    fn = ref.make_reference(es.env, config, cfg.horizon,
                            obs_clip=cfg.obs_clip if cfg.obs_norm else None)
    want, want_steps, want_bc = (np.asarray(x) for x in fn(
        source_state.params_flat, source.table.data, jnp.asarray(offsets),
        jnp.asarray(signs), keys, source_state.sigma, source_state.obs_stats))
    got, got_bc = (np.asarray(x)[members] for x in outputs)
    err = np.abs(got - want)
    agree = err <= tol["rtol"] * np.abs(want)
    bc_err = np.abs(got_bc - want_bc).reshape(len(members), -1).max(axis=1)
    if "behaviour_atol" in tol:
        agree &= bc_err <= tol["behaviour_atol"]
    share = float(agree.mean())
    outliers = int((err > tol.get("outlier_rtol", np.inf)
                    * np.abs(want)).sum())
    rel = err / np.maximum(np.abs(want), 1e-6)
    ok = bool(np.isfinite(got).all() and share >= tol["min_agree_share"]
              and outliers == 0)
    limits = ", ".join(f"{k} {v}" for k, v in tol.items() if k != "why")
    say(f"reference, {what}: {len(members)} members over {cfg.horizon} "
        f"steps, {int(agree.sum())} agree within {limits} (share "
        f"{share:.4f}), {outliers} past the outlier limit; relative "
        f"difference median "
        f"{float(np.median(rel)):.6g}, max {float(rel.max()):.6g}; behaviour "
        f"difference median {float(np.median(bc_err)):.6g}, max "
        f"{float(bc_err.max()):.6g}: {'ok' if ok else 'MISMATCH'}")
    say(f"reference, {what}: member system reference alive_steps "
        f"behaviour_diff: " + "; ".join(
            f"{m} {g:.6g} {w:.6g} {s} {b:.3g}" for m, g, w, s, b in
            zip(members.tolist(), got.tolist(), want.tolist(),
                want_steps.tolist(), bc_err.tolist())))
    return ok


def delta(a: dict, b: dict) -> dict:
    programs = b["programs"] - a["programs"]
    hits = b["cache_hits"] - a["cache_hits"]
    return {"programs": programs, "cache_hits": hits,
            "fresh": programs - hits, "build_s": b["build_s"] - a["build_s"]}


def spans_from_records(fences, records):
    """Host spans on the fences' clock, rebuilt from each record's own
    phase durations, which end at the generation's fence in the order
    dispatch, device, host_sync, record."""
    spans = []
    for prev, t, r in zip(fences[:-1], fences[1:], records):
        p = r.get("phases") or {}
        rec_s = p.get("record", 0.0)
        start = t - rec_s - r["wall_time_s"]
        spans.append(("between_generations", prev, start))
        at = start
        for name, key in (("dispatch", "dispatch"),
                          ("inside_generation", "device"),
                          ("host_sync", "host_sync")):
            spans.append((name, at, at + p.get(key, 0.0)))
            at += p.get(key, 0.0)
        spans.append(("record", t - rec_s, t))
    return spans


class Fences:
    """The ``log_fn`` given to ``ES.train``: stamps each generation's
    completion fence on the host clock (inside an annotation, so that a
    trace carries the same fence) and keeps its record."""

    def __init__(self):
        self.times, self.records = [], []

    def open(self):
        self(None)
        self.records.clear()

    def __call__(self, record):
        import jax

        with jax.profiler.TraceAnnotation(trace_reduce.FENCE):
            self.times.append(time.perf_counter())
        self.records.append(record)


def trace_generations(es, n, trace_dir, window_wall_s, say):
    """``n`` more generations under the profiler: the reduced trace and the
    ``breakdown`` of the busiest chip, or ``(None, None)``."""
    import jax

    shutil.rmtree(trace_dir, ignore_errors=True)
    traced = Fences()
    t = time.perf_counter()
    jax.profiler.start_trace(trace_dir)
    try:
        traced.open()
        es.train(n, log_fn=traced, verbose=False)
    finally:
        jax.profiler.stop_trace()
    t_traced = time.perf_counter()
    pd = trace_reduce.load(trace_dir)
    marks = trace_reduce.fence_times(pd)
    say(f"trace: {n} generations, wall_time_s "
        f"{[r['wall_time_s'] for r in traced.records]}; the window's median "
        f"was {window_wall_s:.6f}")
    if len(marks) != len(traced.times):
        say(f"trace: {len(marks)} fence annotations for "
            f"{len(traced.times)} fences; not reduced")
        return None, None
    reduced = trace_reduce.reduce(pd, window=(marks[0], marks[-1]))
    say(f"trace: taken in {t_traced - t:.2f} s, read and reduced in "
        f"{time.perf_counter() - t_traced:.2f} s")
    if not reduced:
        return None, None
    shift = statistics.median(m - f for m, f in zip(marks, traced.times))
    spans = [(name, a + shift, b + shift) for name, a, b in
             spans_from_records(traced.times, traced.records)]
    busiest = trace_reduce.busiest_device(reduced)
    for name, d in reduced["devices"].items():
        say(f"trace: {name} busy_s {d['busy_s']:.6f} of {d['window_s']:.6f} "
            f"in {d['events']} events, collectives {d['collective_s']:.6f} s")
    return reduced, {
        "device_ops": trace_reduce.top_ops(busiest["per_op"]),
        "idle_gaps": trace_reduce.name_gaps(busiest["gaps"], spans)}


def run(cell, config, traffic, args, out_dir, say, setup_clock):
    import jax
    import numpy as np

    # the runtime's own bring-up of the chips is the machine's, not the
    # program's: it is timed, printed and left out of ``setup_s``
    t = time.perf_counter()
    devices = jax.devices()
    bring_up_s = time.perf_counter() - t
    dev = devices[0]
    say.prefix = f"[{dev.platform} {dev.device_kind} x{len(devices)}]"
    if dev.platform != "tpu" and not args.rehearse:
        raise SystemExit(f"no TPU: jax came up on platform {dev.platform!r} "
                         f"with {len(devices)} device(s)")
    if len(devices) < cell["chips"]:
        raise SystemExit(f"cell {cell['name']} needs {cell['chips']} chips, "
                         f"jax reports {len(devices)}")
    used = devices[:cell["chips"]]

    from estorch_tpu.utils import (compile_event_counts,
                                   enable_compilation_cache,
                                   install_compile_event_counters)

    cache_dir = enable_compilation_cache(min_compile_time_s=0.0)
    install_compile_event_counters()
    c_start = compile_event_counts()
    say(f"jax {jax.__version__}; compile cache {cache_dir}; process start "
        f"to devices asked for {setup_clock(t):.2f} s, device bring-up "
        f"{bring_up_s:.2f} s")

    extra = config.get("rehearsal_kwargs", {}) if args.rehearse else {}
    t = time.perf_counter()
    es, ref = build_es(config, args.seed, used, extra)
    about = ref.describe(config)
    cfg = es.config
    steps_per_generation = cfg.population_size * cfg.horizon
    say(f"built {config['name']} in {time.perf_counter() - t:.2f} s: "
        f"population {cfg.population_size}, horizon {cfg.horizon}, "
        f"{about['param_dim']} parameters, {about}, {cfg.compute_dtype}, "
        f"low_rank {cfg.low_rank}, eval_chunk {cfg.eval_chunk}")
    mesh_ok = (es.mesh.devices.size == len(used)
               and set(es.mesh.devices.flat) == set(used))

    stamp = Fences()
    fences, records = stamp.times, stamp.records

    state0 = es.state
    params0 = np.asarray(state0.params_flat).copy()
    t = time.perf_counter()
    es.train(traffic["warmup_generations"], log_fn=stamp, verbose=False)
    say(f"warm-up: {traffic['warmup_generations']} generations in "
        f"{time.perf_counter() - t:.2f} s (AOT {es.compile_time_s:.2f} s); "
        f"seconds each {[round(r['wall_time_s'], 4) for r in records]}")

    other_seed = (args.reference_seed is not None
                  and args.reference_seed != args.seed)

    def reference_source(system, state, *build_args):
        """Whose generation-0 state the reference reads: the system's own,
        or (rehearsal) that of the same build from another seed."""
        if not other_seed:
            return system, state
        other = build_es(config, args.reference_seed, used, *build_args)[0]
        return other, other.state

    # generation 0 again, by the program that is measured, for its outputs
    t = time.perf_counter()
    _, metrics0 = es.engine.generation_step(state0)
    reference_ok = check_reference(
        "the measured program", es, ref,
        (metrics0["fitness"], metrics0["bc"]), config["reference_tolerance"],
        config, args.seed, *reference_source(es, state0, extra), say)
    say(f"reference check took {time.perf_counter() - t:.2f} s")

    # where physics amplifies rounding, the first steps of every sampled
    # member tightly: the same build at a short horizon, before any fall
    first = config.get("first_steps")
    if first:
        t = time.perf_counter()
        build_args = ({**extra, **first["kwargs"]}, first["declares"])
        probe = build_es(config, args.seed, used, *build_args)[0]
        out = probe.engine.evaluate(probe.state)
        reference_ok &= check_reference(
            "first steps", probe, ref, (out.fitness, out.bc),
            first["tolerance"], config, args.seed,
            *reference_source(probe, probe.state, *build_args), say)
        say(f"first-steps check took {time.perf_counter() - t:.2f} s")
        del probe, out

    rejected0 = es.obs.counters.get("generations_rejected")
    c_setup = compile_event_counts()
    del fences[:], records[:]
    raised = None
    fences.append(time.perf_counter())          # the window opens
    setup_s = setup_clock(fences[0]) - bring_up_s
    say(f"set-up: {setup_s:.2f} s from process start to the window, "
        f"without the bring-up of {bring_up_s:.2f} s")
    try:
        while fences[-1] - fences[0] < args.seconds:
            es.train(traffic["generations_per_call"], log_fn=stamp,
                     verbose=False)
    except Exception as e:  # a generation that raised is a failed one
        raised = e
        say(f"generation raised: {e!r}")
    c_window = compile_event_counts()
    w_fences = (window.close_window(fences, args.seconds) if raised is None
                else list(fences))
    w_records = records[:len(w_fences) - 1]
    readings = window.intervals(w_fences)
    for i, (d, r) in enumerate(zip(readings, w_records)):
        say(f"reading {i}: interval_s {d:.6f} wall_time_s "
            f"{r['wall_time_s']:.6f} env_steps {r['env_steps']}")
    rejected = int(es.obs.counters.get("generations_rejected") - rejected0)

    run_facts = {
        "fences": w_fences, "records": w_records, "chips": cell["chips"],
        "steps_per_generation": steps_per_generation,
        "policy_flops_per_member_step": about["flops_per_member_step"],
        "bring_up_s": bring_up_s,
        "peaks": load_json(os.path.join(HERE, "peaks.json")).get(
            dev.device_kind),
        "compile": {"setup": delta(c_start, c_setup),
                    "window": delta(c_setup, c_window),
                    "aot_s": es.compile_time_s},
        "trace": None, "traced_generations": 0,
    }
    say(f"compile: set-up {run_facts['compile']['setup']}, window "
        f"{run_facts['compile']['window']}")

    breakdown = None
    device_block = {"platform": dev.platform, "kind": dev.device_kind,
                    "count": len(devices)}
    if args.trace and raised is None:
        n = traffic["trace_generations"]
        reduced, breakdown = trace_generations(
            es, n, os.path.join(out_dir, "trace"),
            statistics.median(r["wall_time_s"] for r in w_records), say)
        if reduced:
            run_facts.update(trace=reduced, traced_generations=n)
            device_block.update(busy_s=reduced["busy_s_mean"],
                                window_s=reduced["window_s"])

    # peak HBM on the fullest chip, from the runtime's own counters.  On
    # this runtime ``peak_bytes_in_use`` counts live arrays and program
    # constants; what a running program holds in temporaries is reserved
    # apart and counted in ``peak_bytes_reserved`` (chip run, PR 23: 276 MiB
    # in use and 5.7 GiB reserved while the generation program updates an
    # f32[10240,75018] buffer of 2.9 GiB in place).  The peak is their sum.
    stats = [d.memory_stats() or {} for d in used]
    say(f"memory_stats of the first chip: {stats[0]}")
    peak = max((s.get("peak_bytes_in_use", 0)
                + s.get("peak_bytes_reserved", 0) for s in stats), default=0)
    run_facts["memory_peak_bytes"] = peak
    device_block["memory_peak_bytes"] = peak

    params = np.asarray(es.state.params_flat)
    finite = bool(np.isfinite(params).all())
    moved = float(np.abs(params - params0).max())
    in_window = run_facts["compile"]["window"]["programs"]
    checks = {"reference": reference_ok, "mesh_spans_chips": mesh_ok,
              "params_finite": finite, "params_moved": moved > 0.0,
              "no_program_built_in_window": in_window == 0,
              "no_generation_failed": raised is None and rejected == 0}
    say(f"checks: {checks}; max |theta - theta0| {moved:.6g}")

    end_to_end = {}
    if len(w_fences) > 1:
        end_to_end["steps_per_s_per_chip"] = window.steps_per_s_per_chip(
            w_fences, steps_per_generation, cell["chips"])
    end_to_end["setup_s"] = setup_s
    return {
        "correct": all(checks.values()),
        "attempted": len(readings) + rejected + (1 if raised else 0),
        "failed": rejected + (1 if raised else 0),
        "end_to_end": end_to_end,
        "run_facts": run_facts,
        "device": device_block,
        "breakdown": breakdown,
    }
