"""Run-JSONL summarizer: per-phase time share, throughput trend, stalls.

``python -m estorch_tpu.obs summarize run.jsonl`` answers the three
questions every perf PR and every wedged run raises:

1. *Where does the time go?* — per-phase share aggregated from the span
   breakdown each record carries (top-level phases only; nested
   ``parent/child`` spans are listed under their parent).
2. *Is it getting slower?* — first-half vs second-half env-steps/s.
3. *Did it stall?* — generations whose wall time is a large multiple of
   the median, plus (``--heartbeat``) the live last-phase/age of a run
   that never finished.

``--selfcheck`` validates the module's golden record against the record
schema — run in CI (run_lint.sh) so ``ES._base_record`` drift and schema
drift fail fast, before a consumer parses mismatched JSONL.
"""

from __future__ import annotations

import json
import math

from .recorder import STALE_AFTER_S, read_heartbeat

# record schema: key -> (types, required).  Floats accept ints (JSON
# round-trips 1.0 as 1); NaN/inf are legal values (failed generations).
RECORD_SCHEMA: dict[str, tuple[tuple[type, ...], bool]] = {
    "generation": ((int,), True),
    "reward_max": ((float, int), True),
    "reward_mean": ((float, int), True),
    "reward_min": ((float, int), False),
    "n_failed": ((int,), False),
    "best_reward": ((float, int), True),
    "improved_best": ((bool,), False),
    "env_steps": ((int,), True),
    "env_steps_per_sec": ((float, int), True),
    "grad_norm": ((float, int), False),
    "sigma": ((float, int), False),
    "wall_time_s": ((float, int), True),
    "phases": ((dict,), False),
    # performance attribution (obs/profile/): the compile ledger flushes
    # into whichever record follows a compile; the analytic cost model
    # rides the run's first record only
    "compile_events": ((list,), False),
    "cost_model": ((dict,), False),
    # the process's start-up (obs/spans.py::setup_summary): set-up spans
    # whole and the acquisition summary, on the run's first record only
    "setup": ((dict,), False),
    # async scheduler accounting (algo/scheduler.py, docs/async.md):
    # consumed/fresh/folded/stale_discarded per update + overlap facts
    "async": ((dict,), False),
    # scenario suite (estorch_tpu/scenarios, docs/scenarios.md):
    # per-variant fitness block — n_variants + per-variant counts/mean/best
    "scenarios": ((dict,), False),
}

# integer accounting keys an ``async`` block must carry (the zero-drop
# contract: consumed = fresh + folded, discards counted)
ASYNC_REQUIRED_KEYS = ("consumed", "fresh", "folded", "stale_discarded")

# a record shaped exactly like ES._base_record + span merge emits — the
# selfcheck fixture.  If _base_record changes shape, update BOTH (the
# tier-1 test_obs.py run-produced-records check catches a one-sided edit).
GOLDEN_RECORD = {
    "generation": 0,
    "reward_max": -120.5,
    "reward_mean": -400.25,
    "reward_min": -800.0,
    "n_failed": 0,
    "best_reward": -120.5,
    "improved_best": True,
    "env_steps": 819200,
    "env_steps_per_sec": 512000.0,
    "grad_norm": 0.731,
    "sigma": 0.05,
    "wall_time_s": 1.6,
    "phases": {"sample": 0.01, "eval": 1.2, "update": 0.3,
               "update/obsnorm_merge": 0.05},
    "compile_events": [
        {"program": "generation_step", "compile_s": 24.8, "generation": 0,
         "xla_flops": 7.1e12, "peak_bytes": 2.5e9, "first_call": True},
    ],
    "cost_model": {"schema": 1, "flops_per_env_step": 8704,
                   "bytes_per_env_step": 17924,
                   "per_generation": {"sample": {"flops": 7.3e7,
                                                 "bytes": 1.1e8},
                                      "update": {"flops": 3.7e7,
                                                 "bytes": 5.5e7}},
                   "population": 4096, "param_dim": 4481,
                   "noise_dim": 4481, "mirrored": True, "low_rank": 0,
                   "episodes_per_member": 1, "dtype_bytes": 4,
                   "matmul_shapes": [[3, 64], [64, 64], [64, 1]],
                   "env_steps_per_generation": 819200},
}


def validate_record(rec: dict) -> list[str]:
    """Schema problems in one record ([] when clean)."""
    problems = []
    if not isinstance(rec, dict):
        return [f"record is {type(rec).__name__}, not an object"]
    for key, (types, required) in RECORD_SCHEMA.items():
        if key not in rec:
            if required:
                problems.append(f"missing required key {key!r}")
            continue
        v = rec[key]
        # bool is an int subclass — don't let True satisfy an int field
        if isinstance(v, bool) and bool not in types:
            problems.append(f"{key!r} is bool, expected "
                            f"{'/'.join(t.__name__ for t in types)}")
        elif not isinstance(v, types):
            problems.append(f"{key!r} is {type(v).__name__}, expected "
                            f"{'/'.join(t.__name__ for t in types)}")
    phases = rec.get("phases")
    if isinstance(phases, dict):
        for name, dur in phases.items():
            if not isinstance(name, str):
                problems.append(f"phase key {name!r} is not a string")
            elif (not isinstance(dur, (int, float))
                  or isinstance(dur, bool) or dur < 0):
                problems.append(f"phase {name!r} duration {dur!r} is not a "
                                "non-negative number")
    setup = rec.get("setup")
    if isinstance(setup, dict):
        spans = setup.get("spans")
        if not isinstance(spans, list):
            problems.append("setup.spans is not a list")
        else:
            for span in spans:
                ok = (isinstance(span, dict)
                      and isinstance(span.get("name"), str)
                      and all(isinstance(span.get(k), (int, float))
                              and not isinstance(span.get(k), bool)
                              for k in ("start_s", "end_s"))
                      and span["start_s"] <= span["end_s"])
                if not ok:
                    problems.append(f"setup span {span!r} is not a name "
                                    "with start_s <= end_s")
        if not isinstance(setup.get("acquisitions"), dict):
            problems.append("setup.acquisitions is not an object")
    a = rec.get("async")
    if isinstance(a, dict):
        for key in ASYNC_REQUIRED_KEYS:
            v = a.get(key)
            if not isinstance(v, int) or isinstance(v, bool) or v < 0:
                problems.append(f"async.{key} {v!r} is not a "
                                "non-negative int")
        if (isinstance(a.get("consumed"), int)
                and isinstance(a.get("fresh"), int)
                and isinstance(a.get("folded"), int)
                and a["consumed"] != a["fresh"] + a["folded"]):
            problems.append(
                f"async accounting broken: consumed {a['consumed']} != "
                f"fresh {a['fresh']} + folded {a['folded']}")
    sc = rec.get("scenarios")
    if isinstance(sc, dict):
        nv = sc.get("n_variants")
        if not isinstance(nv, int) or isinstance(nv, bool) or nv < 1:
            problems.append(f"scenarios.n_variants {nv!r} is not a "
                            "positive int")
        else:
            for key in ("counts", "mean", "best"):
                v = sc.get(key)
                if not isinstance(v, list) or len(v) != nv:
                    problems.append(
                        f"scenarios.{key} is not a length-{nv} list")
                elif key == "counts" and any(
                        not isinstance(c, int) or isinstance(c, bool)
                        or c < 0 for c in v):
                    problems.append("scenarios.counts has a negative "
                                    "or non-int entry")
                elif key != "counts" and any(
                        not (x is None or (isinstance(x, (int, float))
                                           and not isinstance(x, bool)))
                        for x in v):
                    problems.append(f"scenarios.{key} has a non-numeric "
                                    "entry")
    for i, e in enumerate(rec.get("compile_events") or []):
        if not isinstance(e, dict) or not isinstance(e.get("program"), str):
            problems.append(f"compile_events[{i}] lacks a program name")
        elif (not isinstance(e.get("compile_s"), (int, float))
              or isinstance(e.get("compile_s"), bool)
              or e["compile_s"] < 0):
            problems.append(f"compile_events[{i}] compile_s "
                            f"{e.get('compile_s')!r} is not a "
                            "non-negative number")
    return problems


def load_records(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def load_records_tolerant(path: str) -> tuple[list[dict], int]:
    """Like :func:`load_records`, but a malformed FINAL line is dropped
    instead of raised: an append-only run JSONL whose writer crashed (or
    was SIGKILLed — the supervised case) legitimately ends in a partial
    line, and the post-mortem tools (`obs summarize`, `obs trace`) exist
    for exactly those runs.  Returns ``(records, n_dropped)`` so the CLI
    can say the tail was dropped; garbage EARLIER in the file still
    raises — that is corruption, not a crash artifact."""
    with open(path) as f:
        lines = [(i, ln) for i, ln in enumerate(f.read().splitlines(), 1)
                 if ln.strip()]
    records: list[dict] = []
    for pos, (lineno, ln) in enumerate(lines):
        try:
            records.append(json.loads(ln))
        except ValueError as e:
            if pos == len(lines) - 1 and records:
                # a crash artifact is a torn tail BEHIND valid records;
                # a file whose only line is malformed is the wrong file,
                # not a truncated run
                return records, 1
            raise ValueError(f"line {lineno}: {e}") from e
    return records, 0


def _median(xs: list[float]) -> float:
    s = sorted(xs)
    n = len(s)
    if n == 0:
        return float("nan")
    return s[n // 2] if n % 2 else 0.5 * (s[n // 2 - 1] + s[n // 2])


STALL_FACTOR = 5.0  # a generation this many × the median wall time stalls

# TAIL-HEAVY async queue-wait callout: p99/p50 beyond this ratio AND
# p99 above this floor.  The floor matters because the histogram ladder
# clamps sub-10µs waits to its underflow midpoint — a fast healthy fold
# loop can show a huge RATIO whose absolute p99 is half a millisecond,
# which is not a diagnosis worth shouting about
TAIL_RATIO_THRESHOLD = 10.0
TAIL_P99_FLOOR_S = 0.05

# WORST-VARIANT callout (scenario suite): a variant whose aggregated
# mean fitness lags the cross-variant family median by more than this
# many cross-variant MADs is called out — one systematically-losing
# scenario hiding inside a healthy-looking family mean is exactly what
# per-variant accounting exists to surface (docs/scenarios.md)
SCENARIO_MAD_FACTOR = 2.0


def _scenarios_section(records: list[dict]) -> tuple[dict | None,
                                                     str | None]:
    """(scenarios summary, diagnosis clause) aggregated over the run's
    per-generation blocks, or (None, None) for un-randomized runs.
    Count-weighted per-variant means, run-best bests, summed counts —
    the stdlib twin of scenarios/fitness.py's numpy aggregation (this
    module stays stdlib-only)."""
    blocks = [r["scenarios"] for r in records
              if isinstance(r.get("scenarios"), dict)
              and isinstance(r["scenarios"].get("n_variants"), int)]
    if not blocks:
        return None, None
    width = max(int(b["n_variants"]) for b in blocks)
    counts = [0] * width
    wsum = [0.0] * width
    wcnt = [0.0] * width
    best: list[float | None] = [None] * width

    def num(x):
        return (float(x) if isinstance(x, (int, float))
                and not isinstance(x, bool) and math.isfinite(x) else None)

    for b in blocks:
        cs = b.get("counts") or []
        ms = b.get("mean") or []
        bs = b.get("best") or []
        for v in range(min(width, len(cs))):
            c = int(cs[v]) if isinstance(cs[v], int) else 0
            counts[v] += c
            m = num(ms[v]) if v < len(ms) else None
            if m is not None and c > 0:
                wsum[v] += m * c
                wcnt[v] += c
            bb = num(bs[v]) if v < len(bs) else None
            if bb is not None:
                best[v] = bb if best[v] is None else max(best[v], bb)
    means = [wsum[v] / wcnt[v] if wcnt[v] else None for v in range(width)]
    section = {
        "n_variants": width,
        "coverage": round(sum(1 for c in counts if c) / width, 4),
        "counts": counts,
        "mean": [round(m, 4) if m is not None else None for m in means],
        "best": [round(b, 4) if b is not None else None for b in best],
    }
    clause = None
    finite = [m for m in means if m is not None]
    if len(finite) >= 3:
        med = _median(finite)
        mad = _median([abs(m - med) for m in finite])
        worst_v = min((v for v in range(width) if means[v] is not None),
                      key=lambda v: means[v])
        lag = med - means[worst_v]
        if mad > 0 and lag > SCENARIO_MAD_FACTOR * mad:
            section["worst_variant"] = {
                "variant": worst_v,
                "mean": round(means[worst_v], 4),
                "family_median": round(med, 4),
                "cross_variant_mad": round(mad, 4),
                "lag_in_mads": round(lag / mad, 2),
            }
            clause = (
                f"WORST-VARIANT: scenario variant {worst_v} mean "
                f"{means[worst_v]:.4g} lags the family median {med:.4g} "
                f"by {lag / mad:.1f}x the cross-variant MAD — one "
                "scenario is systematically losing; inspect its drawn "
                "constants (manifest config.scenarios)")
    return section, clause


# counters surfaced in the summary/diagnosis when nonzero — the
# resilience layer's evidence that a run survived faults rather than
# never seeing any (docs/resilience.md)
RESILIENCE_COUNTERS = (
    "generations_rejected",
    "generations_skipped",
    "workers_respawned",
    "members_retried",
    "rollout_failures",
    "supervisor_resumes",
    "chaos_worker_kills",
)

# serving counters (estorch_tpu/serve, docs/serving.md): present in a
# policy server's heartbeat — `requests_total` is the marker that the
# process being summarized serves traffic rather than training
SERVE_COUNTERS = (
    "requests_total",
    "batches_total",
    "batched_requests_total",
    "shed_total",
    "recompiles",
    "batch_errors_total",
    "reloads_total",
)


def _serving_block(counter_src: dict | None) -> tuple[dict | None, str | None]:
    """(serving summary, diagnosis clause) from a counter snapshot, or
    (None, None) when the counters aren't a policy server's."""
    if not counter_src or not counter_src.get("requests_total"):
        return None, None
    c = {k: counter_src.get(k, 0) for k in SERVE_COUNTERS}
    batches = c["batches_total"]
    mean_batch = (round(c["batched_requests_total"] / batches, 2)
                  if batches else None)
    serving = {
        "requests": int(c["requests_total"]),
        "batches": int(batches),
        "mean_batch": mean_batch,
        "shed": int(c["shed_total"]),
        "recompiles": int(c["recompiles"]),
    }
    if c["batch_errors_total"]:
        serving["batch_errors"] = int(c["batch_errors_total"])
    if c["reloads_total"]:
        serving["reloads"] = int(c["reloads_total"])
    clause = (f"serving: {serving['requests']} requests in "
              f"{serving['batches']} batches"
              + (f" (mean batch {mean_batch})" if mean_batch else ""))
    if serving["shed"]:
        clause += f", {serving['shed']} SHED — the server is saturated"
    if serving.get("batch_errors"):
        clause += f", {serving['batch_errors']} batch errors"
    return serving, clause


def _load_manifest_resilience(manifest_path: str | None) -> dict | None:
    """The run manifest's ``resilience`` section (supervisor-written
    restart provenance + cross-restart counter totals), or None."""
    if not manifest_path:
        return None
    try:
        with open(manifest_path) as f:
            data = json.load(f)
    except (OSError, ValueError):
        return None
    res = data.get("resilience")
    return res if isinstance(res, dict) else None


def summarize(records: list[dict], heartbeat_path: str | None = None,
              manifest_path: str | None = None) -> dict:
    """Aggregate a run's records into the summary dict the CLI prints.

    With no records but a heartbeat (a policy server has no generation
    records), the summary is liveness + the serving counters — the
    ``summarize --heartbeat <path>`` form for serving processes."""
    if not records:
        out: dict = {"generations": 0}
        diagnosis = []
        hb = read_heartbeat(heartbeat_path) if heartbeat_path else None
        if hb is not None:
            out["heartbeat"] = hb
            state = (f"last phase={hb.get('phase')} beat "
                     f"{hb['age_s']:.0f}s ago")
            if hb.get("phase") == "drained":
                diagnosis.append(f"server drained cleanly; {state}")
            elif hb["age_s"] > STALE_AFTER_S:
                diagnosis.append(f"STALE heartbeat: {state} — the process "
                                 "is wedged or dead")
            else:
                diagnosis.append(f"heartbeat fresh: {state}")
            serving, clause = _serving_block(hb.get("counters"))
            if serving is not None:
                out["serving"] = serving
                diagnosis.append(clause)
        out["diagnosis"] = "; ".join(diagnosis) or "no records"
        return out
    # supervisor-replayed generations (the gap between the last checkpoint
    # and a crash) appear twice in an append-only run JSONL — keep the
    # LAST occurrence per generation (the replay that actually counted)
    # so totals/medians/trend describe the run, not the run plus replays.
    # Records without a generation key are kept as-is.
    seen_gens = [r.get("generation") for r in records]
    n_replayed = 0
    if len(set(g for g in seen_gens if g is not None)) < sum(
            1 for g in seen_gens if g is not None):
        last_idx = {g: i for i, g in enumerate(seen_gens) if g is not None}
        kept = [r for i, r in enumerate(records)
                if seen_gens[i] is None or last_idx[seen_gens[i]] == i]
        n_replayed = len(records) - len(kept)
        records = kept
    walls = [float(r.get("wall_time_s", 0.0)) for r in records]
    steps = [int(r.get("env_steps", 0)) for r in records]
    wall_total = sum(walls)

    # ---- per-phase aggregation (top-level vs nested) -------------------
    top: dict[str, float] = {}
    children: dict[str, dict[str, float]] = {}
    for r in records:
        for name, dur in (r.get("phases") or {}).items():
            if "/" in name:
                parent, _, child = name.partition("/")
                children.setdefault(parent, {})
                children[parent][child] = (
                    children[parent].get(child, 0.0) + float(dur))
            else:
                top[name] = top.get(name, 0.0) + float(dur)
    span_total = sum(top.values())
    phase_share = {
        name: {"seconds": round(sec, 4),
               "share": round(sec / span_total, 4) if span_total else 0.0}
        for name, sec in sorted(top.items(), key=lambda kv: -kv[1])
    }
    for parent, kids in children.items():
        if parent in phase_share:
            phase_share[parent]["children"] = {
                k: round(v, 4) for k, v in kids.items()}

    # ---- throughput trend ---------------------------------------------
    half = len(records) // 2
    trend = None
    if half >= 1 and sum(walls[:half]) > 0 and sum(walls[half:]) > 0:
        first = sum(steps[:half]) / sum(walls[:half])
        second = sum(steps[half:]) / sum(walls[half:])
        trend = {
            "first_half_steps_per_s": round(first, 1),
            "second_half_steps_per_s": round(second, 1),
            "ratio": round(second / first, 4) if first > 0 else None,
        }

    # ---- stall detection ----------------------------------------------
    med = _median(walls)
    stalls = [
        {"generation": int(r.get("generation", i)),
         "wall_time_s": round(w, 3),
         "x_median": round(w / med, 1)}
        for i, (r, w) in enumerate(zip(records, walls))
        if med > 0 and w > STALL_FACTOR * med
    ]

    # ---- async scheduler section (records carrying an "async" block) --
    async_recs = [r["async"] for r in records
                  if isinstance(r.get("async"), dict)]
    async_block = None
    if async_recs:
        consumed = sum(int(a.get("consumed", 0)) for a in async_recs)
        folded = sum(int(a.get("folded", 0)) for a in async_recs)
        discarded = sum(int(a.get("stale_discarded", 0))
                        for a in async_recs)
        oes = [a["overlap_efficiency"] for a in async_recs
               if isinstance(a.get("overlap_efficiency"), (int, float))
               and not isinstance(a.get("overlap_efficiency"), bool)]
        async_block = {
            "updates": len(async_recs),
            "consumed": consumed,
            "folded": folded,
            "stale_discarded": discarded,
            "stale_reuse_ratio": (round(folded / consumed, 4)
                                  if consumed else None),
            "overlap_efficiency": (round(_median(oes), 4) if oes
                                   else None),
            "max_staleness": max((int(a.get("max_staleness", 0))
                                  for a in async_recs), default=0),
        }
        # queue-wait / staleness quantiles: the LAST record's block is
        # the run-cumulative histogram state (algo/scheduler.py), so it
        # IS the run's distribution summary
        for key in ("queue_wait_s", "staleness_q"):
            qs = async_recs[-1].get(key)
            if (isinstance(qs, dict)
                    and isinstance(qs.get("p50"), (int, float))
                    and isinstance(qs.get("p99"), (int, float))):
                async_block[key] = {"p50": float(qs["p50"]),
                                    "p99": float(qs["p99"])}
        qw = async_block.get("queue_wait_s")
        if qw and qw["p50"] > 0:
            async_block["queue_wait_tail_ratio"] = round(
                qw["p99"] / qw["p50"], 2)

    scenarios_section, scenario_clause = _scenarios_section(records)

    diagnosis = []
    if stalls:
        worst = max(stalls, key=lambda s: s["x_median"])
        diagnosis.append(
            f"gen {worst['generation']} took {worst['x_median']}x the "
            f"median generation ({worst['wall_time_s']}s vs {med:.3f}s)")
    if trend and trend["ratio"] is not None and trend["ratio"] < 0.8:
        diagnosis.append(
            f"throughput decayed to {trend['ratio']:.0%} of the first half")
    manifest_res = _load_manifest_resilience(manifest_path)
    run_completed = bool(manifest_res and manifest_res.get("completed"))
    hb = None
    if heartbeat_path:
        hb = read_heartbeat(heartbeat_path)
        if hb is None:
            diagnosis.append(
                f"heartbeat unreadable at {heartbeat_path} — run never "
                "started telemetry, or the path is wrong")
        else:
            state = (f"last phase={hb.get('phase')} "
                     f"gen={hb.get('generation')} "
                     f"beat {hb['age_s']:.0f}s ago")
            if hb["age_s"] > STALE_AFTER_S and run_completed:
                # the supervisor recorded clean completion: an old beat is
                # the FINAL child's last state, not a wedge
                diagnosis.append(f"run completed (supervised); {state}")
            elif hb["age_s"] > STALE_AFTER_S:
                diagnosis.append(f"STALE heartbeat: {state} — the run is "
                                 "wedged or dead, not slow")
            else:
                diagnosis.append(f"heartbeat fresh: {state}")

    # ---- resilience: counters + supervisor restart provenance ----------
    # manifest counters are cross-restart totals (the supervisor sums each
    # child's last heartbeat) — prefer them over the live heartbeat's,
    # which only covers the CURRENT child
    counter_src = None
    if manifest_res and isinstance(manifest_res.get("counters"), dict):
        counter_src = manifest_res["counters"]
    elif hb and isinstance(hb.get("counters"), dict):
        counter_src = hb["counters"]
    counters = None
    if counter_src is not None:
        counters = {k: counter_src[k] for k in RESILIENCE_COUNTERS
                    if counter_src.get(k)}
        hits = [f"{int(counters[k])} {k}" for k in counters]
        if hits:
            diagnosis.append("resilience: " + ", ".join(hits))
    serving, serve_clause = _serving_block(counter_src)
    if serve_clause:
        diagnosis.append(serve_clause)
    restarts = None
    if manifest_res is not None:
        n_restarts = int(manifest_res.get("restart_count", 0))
        restarts = {
            "count": n_restarts,
            "completed": manifest_res.get("completed"),
            "reasons": [r.get("reason") for r in
                        manifest_res.get("restarts", [])],
        }
        if n_restarts:
            # reasons may be absent/truncated in a hand-edited or partial
            # manifest — diagnostics must degrade, never crash
            last = (f" (last: {restarts['reasons'][-1]})"
                    if restarts["reasons"] else "")
            diagnosis.append(
                f"supervisor restarted the run {n_restarts}x{last}")
    if n_replayed:
        diagnosis.append(
            f"{n_replayed} replayed generation record"
            f"{'s' if n_replayed != 1 else ''} deduped (re-run after a "
            "restart resumed from an earlier checkpoint)")
    if async_block:
        clause = (f"async: {async_block['folded']}/"
                  f"{async_block['consumed']} results folded stale "
                  f"(ratio {async_block['stale_reuse_ratio']})")
        if async_block["stale_discarded"]:
            clause += (f", {async_block['stale_discarded']} DISCARDED "
                       "past the staleness horizon")
        diagnosis.append(clause)
        ratio = async_block.get("queue_wait_tail_ratio")
        if ratio is not None and ratio > TAIL_RATIO_THRESHOLD:
            qw = async_block["queue_wait_s"]
            if qw["p99"] >= TAIL_P99_FLOOR_S:
                diagnosis.append(
                    f"TAIL-HEAVY async queue wait: p99 {qw['p99']}s is "
                    f"{ratio}x p50 {qw['p50']}s — a few results wait far "
                    "longer than typical (stragglers or a starved fold "
                    "loop); check async/eval_s and stale discards")
    if scenarios_section is not None:
        diagnosis.append(
            f"scenarios: {scenarios_section['n_variants']} variants, "
            f"{scenarios_section['coverage']:.0%} covered")
        if scenario_clause:
            diagnosis.append(scenario_clause)
    if not diagnosis:
        diagnosis.append("steady: no stalls, no throughput decay")

    out = {
        "generations": len(records),
        "wall_time_s": round(wall_total, 3),
        "env_steps": sum(steps),
        "env_steps_per_sec": (round(sum(steps) / wall_total, 1)
                              if wall_total > 0 else None),
        "span_coverage": (round(span_total / wall_total, 4)
                          if wall_total > 0 and span_total else 0.0),
        "phase_share": phase_share,
        "throughput": trend,
        "stalls": stalls,
        "diagnosis": "; ".join(diagnosis),
    }
    if hb is not None:
        out["heartbeat"] = hb
    if counters:
        out["counters"] = counters
    if serving is not None:
        out["serving"] = serving
    if restarts is not None:
        out["restarts"] = restarts
    if async_block is not None:
        out["async"] = async_block
    if scenarios_section is not None:
        out["scenarios"] = scenarios_section
    return out


def _format_serving(s: dict) -> list[str]:
    sv = s.get("serving")
    if not sv:
        return []
    line = (f"serving          {sv['requests']:,} requests  "
            f"{sv['batches']:,} batches")
    if sv.get("mean_batch"):
        line += f"  mean batch {sv['mean_batch']}"
    line += f"  shed={sv['shed']}  recompiles={sv['recompiles']}"
    return [line]


def format_summary(s: dict) -> str:
    """Human rendering of :func:`summarize`'s dict."""
    if not s.get("generations"):
        if s.get("serving") or s.get("heartbeat"):
            return "\n".join(_format_serving(s)
                             + [f"diagnosis        {s['diagnosis']}"])
        return "no records"
    lines = [
        f"generations      {s['generations']}",
        f"wall time        {s['wall_time_s']:.3f}s",
        f"env steps        {s['env_steps']:,}",
        f"env steps/s      {s['env_steps_per_sec']:,}"
        if s["env_steps_per_sec"] is not None else "env steps/s      n/a",
    ]
    if s["phase_share"]:
        lines.append(f"phase share      (covers "
                     f"{s['span_coverage']:.0%} of wall)")
        for name, row in s["phase_share"].items():
            bar = "#" * max(1, int(40 * row["share"]))
            lines.append(f"  {name:<14} {row['share']:7.1%}  "
                         f"{row['seconds']:9.3f}s  {bar}")
            for child, sec in row.get("children", {}).items():
                lines.append(f"    └ {child:<12} {'':7}  {sec:9.3f}s")
    else:
        lines.append("phase share      none recorded (telemetry disabled?)")
    t = s.get("throughput")
    if t:
        lines.append(
            f"throughput       {t['first_half_steps_per_s']:,} → "
            f"{t['second_half_steps_per_s']:,} steps/s "
            f"(x{t['ratio']})")
    if s.get("counters"):
        lines.append("resilience       " + "  ".join(
            f"{k}={int(v)}" for k, v in s["counters"].items()))
    a = s.get("async")
    if a:
        line = (f"async            {a['updates']} updates  "
                f"{a['folded']}/{a['consumed']} folded stale")
        if a.get("stale_reuse_ratio") is not None:
            line += f" (ratio {a['stale_reuse_ratio']})"
        if a.get("overlap_efficiency") is not None:
            line += f"  overlap {a['overlap_efficiency']}"
        line += f"  discarded={a['stale_discarded']}"
        lines.append(line)
        qw, st = a.get("queue_wait_s"), a.get("staleness_q")
        if qw or st:
            tail = "async tails      "
            if qw:
                tail += (f"queue-wait p50={qw['p50']}s "
                         f"p99={qw['p99']}s")
                if a.get("queue_wait_tail_ratio") is not None:
                    tail += f" (p99/p50 {a['queue_wait_tail_ratio']}x)"
            if st:
                tail += (f"  staleness p50={st['p50']} "
                         f"p99={st['p99']}")
            lines.append(tail)
    sc = s.get("scenarios")
    if sc:
        means = [m for m in sc["mean"] if m is not None]
        line = (f"scenarios        {sc['n_variants']} variants  "
                f"coverage {sc['coverage']:.0%}")
        if means:
            line += (f"  mean {min(means):.4g}..{max(means):.4g}")
        lines.append(line)
        wv = sc.get("worst_variant")
        if wv:
            lines.append(
                f"  └ worst v{wv['variant']:<3} mean {wv['mean']:.4g}  "
                f"({wv['lag_in_mads']}x MAD below median "
                f"{wv['family_median']:.4g})")
    lines.extend(_format_serving(s))
    if s.get("restarts") and s["restarts"]["count"]:
        lines.append(f"restarts         {s['restarts']['count']} "
                     f"(completed={s['restarts']['completed']})")
    lines.append(f"diagnosis        {s['diagnosis']}")
    return "\n".join(lines)


def selfcheck() -> list[str]:
    """Schema self-validation for CI ([] = healthy).

    Checks the golden record against the schema, that a synthetic run
    through :func:`summarize` produces the promised keys, and that the
    stall detector fires on an obvious stall.
    """
    problems = list(validate_record(GOLDEN_RECORD))
    # a deliberately-broken record must FAIL validation (the validator
    # itself could silently rot into accepting everything)
    broken = dict(GOLDEN_RECORD, env_steps="many")
    broken.pop("reward_mean")
    if not validate_record(broken):
        problems.append("validator accepted a broken record")
    recs = []
    for g in range(6):
        r = dict(GOLDEN_RECORD, generation=g,
                 wall_time_s=1.0 if g != 4 else 30.0)
        recs.append(json.loads(json.dumps(r)))  # via-JSON: CLI-equivalent
    s = summarize(recs)
    for key in ("generations", "wall_time_s", "env_steps",
                "env_steps_per_sec", "phase_share", "throughput",
                "stalls", "diagnosis"):
        if key not in s:
            problems.append(f"summary missing {key!r}")
    if not s.get("stalls"):
        problems.append("stall detector missed a 30x-median generation")
    share = s.get("phase_share", {})
    for phase in ("sample", "eval", "update"):
        if phase not in share:
            problems.append(f"phase_share missing {phase!r}")
    if "update" in share and "obsnorm_merge" not in share["update"].get(
            "children", {}):
        problems.append("nested span update/obsnorm_merge not aggregated")
    total_share = sum(row["share"] for row in share.values())
    if share and not math.isclose(total_share, 1.0, abs_tol=1e-3):
        problems.append(f"top-level shares sum to {total_share}, not 1")
    if format_summary(s) == "no records":
        problems.append("format_summary rendered nothing")

    # async scheduler surfacing (algo/scheduler.py): records carrying an
    # "async" block must validate, aggregate into the async section, and
    # render — and broken accounting must FAIL validation
    async_rec = dict(GOLDEN_RECORD, generation=6,
                     **{"async": {"consumed": 16, "fresh": 10, "folded": 6,
                                  "stale_discarded": 1, "max_staleness": 2,
                                  "mean_lambda": 0.91,
                                  "overlap_efficiency": 0.8,
                                  "dispatches": [6, 7],
                                  "consumed_dispatches": [[5, 10], [6, 6]],
                                  "discarded_dispatches": [[4, 1]],
                                  "queue_wait_s": {"p50": 0.004,
                                                   "p99": 0.09},
                                  "staleness_q": {"p50": 0.0, "p99": 2.0}}})
    problems += [f"async golden: {p}"
                 for p in validate_record(json.loads(json.dumps(async_rec)))]
    broken_async = dict(GOLDEN_RECORD,
                        **{"async": {"consumed": 16, "fresh": 10,
                                     "folded": 3, "stale_discarded": 0}})
    if not validate_record(broken_async):
        problems.append("validator accepted consumed != fresh + folded")
    sa = summarize(recs + [json.loads(json.dumps(async_rec))])
    ab = sa.get("async")
    if not ab or ab.get("folded") != 6 or ab.get("consumed") != 16:
        problems.append("summary missed the async accounting block")
    if ab and ab.get("stale_reuse_ratio") != round(6 / 16, 4):
        problems.append("stale_reuse_ratio mis-derived")
    if "async" not in sa.get("diagnosis", ""):
        problems.append("diagnosis missed the async section")
    if "DISCARDED" not in sa["diagnosis"]:
        problems.append("diagnosis missed the stale-discard callout")
    if "async" not in format_summary(sa):
        problems.append("format_summary dropped the async block")
    # tail health: queue-wait/staleness quantiles surface, and a
    # p99/p50 ratio > 10 is called out as TAIL-HEAVY in the diagnosis
    if ab and ab.get("queue_wait_s", {}).get("p99") != 0.09:
        problems.append("async queue-wait quantiles not surfaced")
    if ab and ab.get("staleness_q", {}).get("p99") != 2.0:
        problems.append("async staleness quantiles not surfaced")
    if ab and ab.get("queue_wait_tail_ratio") != round(0.09 / 0.004, 2):
        problems.append("queue-wait p99/p50 ratio mis-derived")
    if "TAIL-HEAVY" not in sa.get("diagnosis", ""):
        problems.append("diagnosis missed the tail-heavy queue-wait "
                        "callout (p99/p50 > 10)")
    if "queue-wait" not in format_summary(sa):
        problems.append("format_summary dropped the async tails line")
    # a healthy tail (ratio <= 10) must NOT be called out
    calm = dict(async_rec)
    calm["async"] = dict(async_rec["async"],
                         **{"queue_wait_s": {"p50": 0.004, "p99": 0.02}})
    sc = summarize(recs + [json.loads(json.dumps(calm))])
    if "TAIL-HEAVY" in sc.get("diagnosis", ""):
        problems.append("tail-heavy callout fired on a 5x (healthy) "
                        "p99/p50 ratio")
    # ...nor must a huge RATIO whose absolute p99 is sub-millisecond
    # (the histogram ladder clamps tiny p50s — ratio alone is not a
    # diagnosis)
    fast = dict(async_rec)
    fast["async"] = dict(async_rec["async"],
                         **{"queue_wait_s": {"p50": 9.1e-06,
                                             "p99": 0.0005}})
    sf = summarize(recs + [json.loads(json.dumps(fast))])
    if "TAIL-HEAVY" in sf.get("diagnosis", ""):
        problems.append("tail-heavy callout fired on a sub-millisecond "
                        "p99 (ladder-floor ratio artifact)")
    # a synchronous run must not grow an async section
    if summarize(recs).get("async"):
        problems.append("sync run grew an async section")

    # scenario suite (estorch_tpu/scenarios, docs/scenarios.md): records
    # carrying a per-variant fitness block must validate, aggregate into
    # the scenarios section count-weighted, and surface a worst-variant
    # callout when one variant lags the family by >2x the cross-variant
    # MAD — while a balanced family stays quiet
    def scen_rec(gen, means):
        return dict(GOLDEN_RECORD, generation=gen, scenarios={
            "n_variants": len(means), "counts": [4] * len(means),
            "mean": means, "best": [m + 5.0 for m in means]})

    lag = [-100.0, -102.0, -98.0, -101.0, -99.0, -400.0]
    sr = [json.loads(json.dumps(scen_rec(g, lag))) for g in range(3)]
    problems += [f"scenario golden: {p}" for p in validate_record(sr[0])]
    broken_sc = dict(GOLDEN_RECORD, scenarios={
        "n_variants": 4, "counts": [1, 2], "mean": [0.0], "best": "big"})
    if not validate_record(broken_sc):
        problems.append("validator accepted a malformed scenarios block")
    ssc = summarize(recs + sr)
    blk = ssc.get("scenarios")
    if not blk or blk.get("n_variants") != 6:
        problems.append("summary missed the scenarios section")
    if blk and blk.get("coverage") != 1.0:
        problems.append("scenario coverage mis-derived")
    if blk and blk.get("mean", [None])[0] != -100.0:
        problems.append("per-variant mean not count-weighted across "
                        "generations")
    if blk and blk.get("best", [None])[0] != -95.0:
        problems.append("per-variant best not aggregated as run max")
    if not blk or blk.get("worst_variant", {}).get("variant") != 5:
        problems.append("worst-variant callout missed a 2x-MAD laggard")
    if "WORST-VARIANT" not in ssc.get("diagnosis", ""):
        problems.append("diagnosis missed the worst-variant callout")
    if "scenarios" not in format_summary(ssc):
        problems.append("format_summary dropped the scenarios block")
    balanced = [json.loads(json.dumps(
        scen_rec(g, [-100.0, -102.0, -98.0, -101.0, -99.0, -103.0])))
        for g in range(3)]
    sb = summarize(recs + balanced)
    if "WORST-VARIANT" in sb.get("diagnosis", ""):
        problems.append("worst-variant callout fired on a balanced family")
    if summarize(recs).get("scenarios"):
        problems.append("un-randomized run grew a scenarios section")

    # resilience surfacing: a chaos run's rejected-generation counters and
    # the supervisor's restart provenance must show up in the summary —
    # validated against synthetic heartbeat/manifest files so drift fails
    # here, not in a post-mortem
    import os
    import tempfile
    import time as _time

    with tempfile.TemporaryDirectory() as d:
        hb_path = os.path.join(d, "heartbeat.json")
        with open(hb_path, "w") as f:
            json.dump({"ts": _time.time(), "pid": 1, "phase": "eval",
                       "generation": 3,
                       "counters": {"generations_rejected": 2,
                                    "workers_respawned": 1}}, f)
        mf_path = os.path.join(d, "manifest.json")
        with open(mf_path, "w") as f:
            json.dump({"resilience": {
                "restart_count": 1, "completed": True,
                "restarts": [{"reason": "child died with exit code -9"}],
                "counters": {"generations_rejected": 2,
                             "generations_skipped": 1}}}, f)
        sr = summarize(recs, heartbeat_path=hb_path, manifest_path=mf_path)
        if sr.get("counters", {}).get("generations_rejected") != 2:
            problems.append("summary missed generations_rejected counter")
        if sr.get("restarts", {}).get("count") != 1:
            problems.append("summary missed supervisor restart count")
        if "restarted" not in sr["diagnosis"]:
            problems.append("diagnosis missed the supervisor restart")
        if "resilience" not in format_summary(sr):
            problems.append("format_summary dropped resilience counters")
        # heartbeat-only fallback (no supervisor/manifest in the run)
        sh = summarize(recs, heartbeat_path=hb_path)
        if sh.get("counters", {}).get("workers_respawned") != 1:
            problems.append("heartbeat counters not surfaced sans manifest")

        # serving process: no generation records, counters in the
        # heartbeat (estorch_tpu/serve writes exactly this shape) — the
        # summarize --heartbeat form must surface the serving section
        serve_hb = os.path.join(d, "serve_heartbeat.json")
        with open(serve_hb, "w") as f:
            json.dump({"ts": _time.time(), "pid": 2, "phase": "serving",
                       "generation": 0,
                       "counters": {"requests_total": 640,
                                    "batches_total": 40,
                                    "batched_requests_total": 640,
                                    "shed_total": 3,
                                    "recompiles": 5}}, f)
        ss = summarize([], heartbeat_path=serve_hb)
        sv = ss.get("serving")
        if not sv or sv.get("requests") != 640 or sv.get("mean_batch") != 16:
            problems.append("serving counters not aggregated from a "
                            "server heartbeat")
        if "serving" not in ss.get("diagnosis", ""):
            problems.append("diagnosis missed the serving section")
        if "SHED" not in ss["diagnosis"]:
            problems.append("diagnosis missed serving shed (saturation)")
        if "serving" not in format_summary(ss):
            problems.append("format_summary dropped the serving block")
        # a TRAINING run's summary must not grow a serving section just
        # because resilience counters exist
        if summarize(recs, heartbeat_path=hb_path).get("serving"):
            problems.append("non-serving run grew a serving section")
    return problems
