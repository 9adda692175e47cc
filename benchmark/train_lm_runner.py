"""Traffic kind ``train_lm``: generations back to back through ``ES.train`` on
an engine that DONATES its state (``parallel/sharded.py``), for a sequence
model scored by a plain reference of its own (``reference/hybrid_lm.py``).

The same window, readings, fences, trace step and ``correct`` conditions as
``train_runner.py``, whose ``Fences``, ``delta`` and ``trace_generations``
it loads.  One difference, which a donated state forces: ``train_runner``
keeps the initial state across the warm-up and runs generation 0 again
afterwards, but a donated state is gone once it has been stepped.  So this
runner snapshots what the reference needs (theta on the host, key,
generation, sigma, the pair offsets) BEFORE the first call of the measured
program, takes generation 0's ``fitness`` and ``bc`` from that first call,
and compares afterwards.  It drops the ES's own initial state before it
places the seeded one: two sharded states and a flat theta do not fit one
chip beside each other at the published widths.

``correct`` needs all of: (1) a seeded sample of members (both signs of at
least one pair) over their whole sequence against the reference: the
behaviour logits within ``behaviour_atol`` and the fitness, as a difference
from log(vocabulary), within ``rtol`` (of that difference, or of
``fitness_floor`` where the difference is smaller); (2) parameters finite and moved, no
program built in the window, the mesh spans the cell's chips, no
generation rejected.  Rehearsal switch (``--reference-seed``): the reference
is given another seed's theta, key and offsets.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import os
import statistics
import time

from benchmark import window
from benchmark.files import (import_name, load_file_module, load_json,
                             resolve)

HERE = os.path.dirname(os.path.abspath(__file__))


def as_built(config: dict, extra_kwargs: dict) -> dict:
    """The configuration with ``extra_kwargs`` (the rehearsal's tiny sizes)
    laid over its build: what the reference module reads its sizes from."""
    build = config["build"]
    return {**config, "build": {
        **build, "kwargs": {**build["kwargs"], **extra_kwargs}}}


def absent_names(node) -> list[str]:
    """The dotted names under ``node`` (``$import``, ``$call``) that this
    program does not have: a program from before the sequence model cannot
    run the cell, and finds that out here, before it asks for the chips."""
    if isinstance(node, list):
        return [name for v in node for name in absent_names(v)]
    if not isinstance(node, dict):
        return []
    absent = []
    for key in ("$import", "$call"):
        if key in node:
            try:
                import_name(node[key])
            except (ImportError, AttributeError):
                absent.append(node[key])
    return absent + [name for v in node.values()
                     for name in absent_names(v)]


def build_es(config: dict, devices, ref):
    """The ES of the configuration file on ``devices``.  Every top-level key
    of the file that is also a field of the built ``EngineConfig``, a
    constructor argument of the built policy, or ``param_dim``, has to hold
    what was built, so the file describes what is run."""
    build = config["build"]
    es = import_name(build["$call"])(**resolve(build["kwargs"]),
                                     seed=config["table_seed"],
                                     device=list(devices))
    about = ref.describe(config)
    layer_types = list(es.module.layer_types)
    built = {**dataclasses.asdict(es.config),
             **dataclasses.asdict(es.module),
             "num_hidden_layers": len(layer_types),
             "layer_types": layer_types,
             "param_dim": int(es._spec.dim)}
    # the file keeps the published ``layer_types`` whole; the build takes
    # its first ``num_hidden_layers`` entries
    declared = {**config, "param_dim": about["param_dim"],
                "layer_types": config["layer_types"][:len(layer_types)]}
    differ = {k: {"file": declared[k], "built": built[k]}
              for k in built if k in declared and declared[k] != built[k]}
    if differ and not config.get("rehearsing"):
        raise SystemExit(f"configuration file {config['name']} does not "
                         f"describe what was built: {differ}")
    return es, about


def seeded_theta(ref, config, seed):
    """Initial weights from ``seed`` as a flat host vector (made on the
    device in one program, then brought over: at the published widths the
    device holds it only while nothing else is there), and the PRNG key
    of the state: it draws every noise offset and picks every pair's
    sequence."""
    import jax
    import numpy as np

    key = jax.random.PRNGKey(seed)
    theta = np.asarray(ref.init_theta(jax.random.fold_in(key, 0), config))
    return theta, jax.random.fold_in(key, 1)


def placed(es, theta, state_key):
    """The engine's state on the mesh, and the snapshot the reference
    reads: theta on the host, key, generation, sigma and the pair offsets
    of generation 0."""
    import numpy as np

    state = es.engine.init_state(theta, state_key)
    snapshot = {
        "theta": theta, "key": np.asarray(state.key),
        "generation": int(state.generation), "sigma": float(state.sigma),
        "offsets": np.asarray(es.engine.all_pair_offsets(state))}
    return state, snapshot


def check_reference(es, ref, config, outputs, snapshot, seed, say) -> bool:
    """Generation-0 ``outputs`` (fitness and behaviour vector per member, as
    the measured program gave them) of a seeded sample of members against
    the plain reference, which reads theta, key and offsets from
    ``snapshot``, noise from the same table, and runs on one chip."""
    import jax.numpy as jnp
    import numpy as np

    cfg, tol = es.config, config["reference_tolerance"]
    s = ref.sizes(config)
    n_pairs = cfg.population_size // 2
    rng = np.random.default_rng(seed)
    # both signs of one pair, then single members of other pairs
    pairs = rng.choice(n_pairs, replace=False,
                       size=min(n_pairs, config["reference_members"] - 1))
    members = np.sort(np.concatenate(
        [[2 * pairs[0], 2 * pairs[0] + 1],
         2 * pairs[1:] + rng.integers(0, 2, size=len(pairs) - 1)]))
    rows = members // 2
    signs = np.where(members % 2 == 0, 1.0, -1.0).astype(np.float32)
    keys = ref.member_keys(jnp.asarray(snapshot["key"]),
                           snapshot["generation"], n_pairs)[rows]
    t = time.perf_counter()
    want, want_bc = ref.score_members(
        s, snapshot["theta"], es.table.data, snapshot["offsets"][rows],
        signs, keys, snapshot["sigma"], es.env.bc_dim)
    got, got_bc = (np.asarray(x)[members] for x in outputs)
    log_v = math.log(s["vocab_size"])
    err = np.abs(got - want)
    # relative to the fitness's distance from log(vocabulary), which is
    # what ES ranks by; that distance can pass through zero, so below
    # ``fitness_floor`` (its usual size) the limit stops shrinking
    rel = err / np.maximum(np.abs(want + log_v), tol["fitness_floor"])
    bc_err = np.abs(got_bc - want_bc).max(axis=1)
    agree = (rel <= tol["rtol"]) & (bc_err <= tol["behaviour_atol"])
    ok = bool(np.isfinite(got).all() and np.isfinite(got_bc).all()
              and agree.all())
    say(f"reference, the measured program: {len(members)} members over "
        f"{cfg.horizon} tokens in {time.perf_counter() - t:.2f} s, "
        f"{int(agree.sum())} agree within rtol {tol['rtol']} of |fitness + "
        f"log vocabulary| (floored at {tol['fitness_floor']}) and "
        f"behaviour_atol {tol['behaviour_atol']}; "
        f"relative difference median {float(np.median(rel)):.6g}, max "
        f"{float(rel.max()):.6g}; behaviour difference median "
        f"{float(np.median(bc_err)):.6g}, max {float(bc_err.max()):.6g}: "
        f"{'ok' if ok else 'MISMATCH'}")
    say("reference, the measured program: member system reference "
        "fitness_plus_log_vocab behaviour_diff: " + "; ".join(
            f"{m} {g:.8g} {w:.8g} {w + log_v:.6g} {b:.3g}"
            for m, g, w, b in zip(members.tolist(), got.tolist(),
                                  want.tolist(), bc_err.tolist())))
    return ok


def run(cell, config, traffic, args, out_dir, say, setup_clock):
    import jax
    import numpy as np

    absent = absent_names(config["build"])
    if absent:
        raise SystemExit(f"this program cannot run {config['name']}: it has "
                         f"no {', '.join(absent)}")
    base = load_file_module(os.path.join(HERE, "train_runner.py"))

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

    if args.rehearse:
        config = {**as_built(config, config.get("rehearsal_kwargs", {})),
                  "rehearsing": True}
    ref = load_file_module(os.path.join(HERE, "reference",
                                        config["reference"] + ".py"))

    def peak_in_use():
        return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in used)

    # the seeded weights first, while the chips hold nothing else
    t = time.perf_counter()
    theta, state_key = seeded_theta(ref, config, args.seed)
    say(f"seeded weights made and brought to the host in "
        f"{time.perf_counter() - t:.2f} s; peak bytes in use "
        f"{peak_in_use()}")
    t = time.perf_counter()
    es, about = build_es(config, used, ref)
    cfg = es.config
    steps_per_generation = cfg.population_size * cfg.horizon
    say(f"built {config['name']} in {time.perf_counter() - t:.2f} s: "
        f"population {cfg.population_size}, {cfg.horizon} tokens a member, "
        f"{about}, {cfg.compute_dtype}, low_rank {cfg.low_rank}, forward "
        f"{es.engine.forward_form}, eval_chunk {es.engine.eval_chunk}, mesh "
        f"{dict(zip(es.mesh.axis_names, es.mesh.devices.shape))}, "
        f"{es.engine.param_bytes_per_chip} bytes of centre a chip")
    mesh_ok = (es.mesh.devices.size == len(used)
               and set(es.mesh.devices.flat) == set(used))

    # the ES's own initial state goes before the seeded one is placed
    t = time.perf_counter()
    es.state = None
    gc.collect()
    say(f"peak bytes in use after the build {peak_in_use()}")
    es.state, snapshot = placed(es, theta, state_key)
    params0 = theta
    if (args.reference_seed is not None
            and args.reference_seed != args.seed):
        # rehearsal: the reference reads another seed's theta, key, offsets
        _, snapshot = placed(es, *seeded_theta(ref, config,
                                               args.reference_seed))
    say(f"seeded state placed in {time.perf_counter() - t:.2f} s; peak "
        f"bytes in use {peak_in_use()}")

    # generation 0 by the program that is measured: its first call, made
    # here so that its fitness and behaviour vectors can be kept
    t = time.perf_counter()
    es.compile_time_s = es.engine.compile(es.state)
    es.state, metrics0 = es.engine.generation_step(es.state)
    outputs0 = (np.asarray(metrics0["fitness"]), np.asarray(metrics0["bc"]))
    del metrics0
    say(f"generation 0 compiled and run in {time.perf_counter() - t:.2f} s "
        f"(AOT {es.compile_time_s:.2f} s)")

    stamp = base.Fences()
    fences, records = stamp.times, stamp.records
    t = time.perf_counter()
    es.train(traffic["warmup_generations"], log_fn=stamp, verbose=False)
    say(f"warm-up: {traffic['warmup_generations']} generations in "
        f"{time.perf_counter() - t:.2f} s; seconds each "
        f"{[round(r['wall_time_s'], 4) for r in records]}")

    t = time.perf_counter()
    reference_ok = check_reference(es, ref, config, outputs0, snapshot,
                                   args.seed, say)
    say(f"reference check took {time.perf_counter() - t:.2f} s")
    del snapshot

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
        "dense_flops_per_member_step": about["dense_flops_per_member_step"],
        "head_flops_per_member_step": about["head_flops_per_member_step"],
        "bring_up_s": bring_up_s,
        "peaks": load_json(os.path.join(HERE, "peaks.json")).get(
            dev.device_kind),
        "compile": {"setup": base.delta(c_start, c_setup),
                    "window": base.delta(c_setup, c_window),
                    "aot_s": es.compile_time_s},
        "trace": None, "traced_generations": 0,
    }
    say(f"compile: set-up {run_facts['compile']['setup']}, window "
        f"{run_facts['compile']['window']}")
    say(f"gauges: { {k: es.obs.counters.get(k) for k in ('forward_form', 'tokens_per_generation', 'noise_rows_per_generation', 'mesh_shape', 'param_bytes_per_chip')} }")

    breakdown = None
    device_block = {"platform": dev.platform, "kind": dev.device_kind,
                    "count": len(devices)}
    if args.trace and raised is None and w_records:
        n = traffic["trace_generations"]
        reduced, breakdown = base.trace_generations(
            es, n, os.path.join(out_dir, "trace"),
            statistics.median(r["wall_time_s"] for r in w_records), say)
        if reduced:
            run_facts.update(trace=reduced, traced_generations=n)
            device_block.update(busy_s=reduced["busy_s_mean"],
                                window_s=reduced["window_s"])

    # peak HBM on the fullest chip: live arrays and constants
    # (``peak_bytes_in_use``) plus what a running program reserves for its
    # temporaries (``peak_bytes_reserved``), as ``train_runner`` counts it
    stats = [d.memory_stats() or {} for d in used]
    say(f"memory_stats of the first chip: {stats[0]}")
    peak = max((s.get("peak_bytes_in_use", 0)
                + s.get("peak_bytes_reserved", 0) for s in stats), default=0)
    run_facts["memory_peak_bytes"] = peak
    device_block["memory_peak_bytes"] = peak

    # finite and moved, read a leaf at a time on the mesh: gathering the
    # whole vector onto one chip is what this engine exists to avoid
    leaves = jax.tree_util.tree_leaves(es.state.params)
    finite = all(bool(jax.numpy.isfinite(x).all()) for x in leaves)
    at, moved = 0, 0.0
    for x in leaves:
        was = jax.device_put(params0[at:at + x.size].reshape(x.shape),
                             x.sharding)
        moved = max(moved, float(jax.numpy.abs(x - was).max()))
        at += x.size
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
