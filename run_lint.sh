#!/usr/bin/env bash
# Fast pre-test gate: esguard static analysis + bytecode compile check.
# Pure AST + compileall — runs on CPU in seconds, touches no device
# (JAX_PLATFORMS=cpu keeps the selfcheck gates below off any chip; the
# analyzer itself imports neither jax nor the analyzed modules).
set -euo pipefail
cd "$(dirname "$0")"

export JAX_PLATFORMS=cpu

echo "== esguard =="
# Two-speed gate.  When a base ref is available (CI PRs export
# ESGUARD_CHANGED_RANGE, or origin/main exists locally) the changed-file
# pass runs FIRST so a racy edit fails in well under a second; the full
# whole-program pass (lockset rules R18-R22 need every module linked,
# and the esguard_ratchet.json shrink-only counts are checked here)
# always follows, archiving the machine-readable findings report for CI.
CHANGED_RANGE="${ESGUARD_CHANGED_RANGE:-}"
if [ -z "$CHANGED_RANGE" ] && git rev-parse --verify -q origin/main >/dev/null 2>&1; then
    CHANGED_RANGE="origin/main...HEAD"
fi
if [ -n "$CHANGED_RANGE" ]; then
    echo "-- changed files ($CHANGED_RANGE) --"
    python -m estorch_tpu.analysis --changed "$CHANGED_RANGE"
fi
echo "-- full tree --"
ARTIFACT_DIR="${ESGUARD_ARTIFACT_DIR:-/tmp/esguard}"
mkdir -p "$ARTIFACT_DIR"
python -m estorch_tpu.analysis --format=json estorch_tpu/ \
    > "$ARTIFACT_DIR/findings.json" \
    || { cat "$ARTIFACT_DIR/findings.json"; exit 1; }
python -m estorch_tpu.analysis estorch_tpu/
echo "findings artifact: $ARTIFACT_DIR/findings.json"

echo "== obs selfcheck =="
# record-schema validation of the golden generation record + summarize
# pipeline (estorch_tpu/obs/summarize.py) — schema drift fails fast here,
# before a JSONL consumer parses mismatched records
python -m estorch_tpu.obs summarize --selfcheck

echo "== obs profile selfcheck =="
# performance-attribution gate (estorch_tpu/obs/profile/): a synthetic
# run with known per-step FLOPs must produce exactly the expected MFU,
# compile-ledger entries must round-trip the Prometheus exposition
# parser, degenerate inputs must degrade to a note (never a crash), and
# an injected 30% eval-phase slowdown must be flagged NAMING the eval
# phase.  Stdlib+numpy, sub-second.
python -m estorch_tpu.obs profile --selfcheck

echo "== obs regress selfcheck =="
# perf-gate gate (estorch_tpu/obs/export/regress.py): the statistical
# regression detector must flag a synthetic 30% slowdown injected into a
# copied baseline AND pass an identical-run comparison — a gate that can
# do neither would either cry wolf on every loaded-host run or wave real
# regressions through.  Pure stdlib, milliseconds.
python -m estorch_tpu.obs regress --selfcheck

echo "== obs hist selfcheck =="
# streaming-histogram gate (estorch_tpu/obs/hist.py): exact small-N
# quantiles, a known-distribution sample inside the documented bucket
# error bound, merge associativity, and the cross-restart composition +
# Prometheus exposition round trips.  Stdlib, milliseconds.
python -m estorch_tpu.obs hist --selfcheck

echo "== obs collect selfcheck =="
# fleet-collector gate (estorch_tpu/obs/agg/): synthetic healthy /
# garbage / dead-port targets under one collector — every tick must
# tolerate the dead pair, absence + burn-rate rules must fire NAMING the
# target, stored quantiles must sit inside the histogram ladder's
# documented bound, and the collector's own /metrics + /alerts must
# parse.  Stdlib, ~seconds.
python -m estorch_tpu.obs collect --selfcheck

echo "== collector file-run probe =="
# the wedged-host contract, proven the same way the sidecar/loadgen
# prove it: the collector runs AS A FILE (no package import, no jax)
# and still passes the full selfcheck
python estorch_tpu/obs/agg/collector.py --selfcheck

echo "== obs trace selfcheck =="
# distributed-trace assembly gate (estorch_tpu/obs/agg/traces.py): a
# synthetic three-process fleet run dir (router + two replicas) with a
# hedged trace, a torn tail, and a foreign trace — assembly must join
# the hedge across all three processes with the loser marked cancelled,
# isolate the foreign trace, skip the torn line, and the Perfetto
# export must validate.  Stdlib, milliseconds.
python -m estorch_tpu.obs trace --fleet --selfcheck

echo "== obs regress tail selfcheck =="
# tail-gate gate (estorch_tpu/obs/export/regress.py compare_tail): a
# median-clean pair with ~2% of requests slowed 5x (the chaos-shed
# signature) must PASS the median gate but be FLAGGED at p99, naming
# the quantile and the endpoint/phase.  Pure stdlib, milliseconds.
python -m estorch_tpu.obs regress --tail --selfcheck

echo "== chaos selfcheck =="
# recovery-path gate (estorch_tpu/resilience, docs/resilience.md): a tiny
# host-backend run under a worker-kill chaos plan must keep FULL
# population participation (respawn + same-generation retry) — measured
# against a clean twin; fails when recovery regressed.  Host path only,
# no device touch.
python bench.py --chaos --selfcheck

echo "== async-ab selfcheck =="
# barrier-free-scheduler gate (estorch_tpu/algo/scheduler.py,
# docs/async.md): the same tiny host run under an identical
# deterministic straggler plan must run >=1.25x faster through the
# event-driven fold scheduler than through the synchronous barrier
# loop (medians + learned noise band), with every late result folded
# or counted — zero silent drops.  Host path only, no device touch.
python bench.py --async-ab --selfcheck

echo "== elastic-ab selfcheck =="
# elastic multi-host gate (estorch_tpu/parallel/elastic.py +
# algo/scheduler.py ElasticScheduler, docs/multihost.md): under an
# IDENTICAL declared straggle_host plan, the elastic host-granular fold
# must beat the synchronous 2-process SPMD multihost loop >=1.25x
# beyond the learned noise band (a slow host costs throughput, the
# barrier costs the fleet), stale host contributions must actually
# FOLD with clipped importance weights, and the accounting invariant
# dispatched == consumed + discarded + lost must hold.  CPU processes
# over loopback (jax.distributed/Gloo for the sync leg, stdlib TCP for
# the elastic fleet), ~2 min.
python bench.py --elastic-ab --selfcheck

echo "== shard-ab selfcheck =="
# param-sharded gate (estorch_tpu/parallel/sharded.py, docs/sharding.md):
# a same-seed sharded run must match the replicated fused path allclose
# at f32, the program-noise sharded program must fit in LESS per-device
# memory than the replicated one (compile-ledger memory_analysis), and
# the sharded row must report a non-null MFU from the shard-aware cost
# model.  Virtual CPU mesh in a child process, tiny config.
python bench.py --shard-ab --selfcheck

echo "== scenario-ab selfcheck =="
# scenario-suite gate (estorch_tpu/scenarios, docs/scenarios.md): one
# 10-variant domain-randomized run must beat 10 sequential
# single-scenario runs >=3x wall-clock, the compile ledger must show
# the program count independent of variant count (traced-operand
# contract — the recompile-per-variant smell esguard R16 hunts), and
# per-variant fitness must surface with full variant coverage.  CPU
# child, ~40s.
python bench.py --scenario-ab --selfcheck

echo "== loadgen smoke =="
# the load generator validated against an in-process stdlib echo server
# (closed+open loop, latency percentiles, response indexing).  Run as a
# FILE, not a module: loadgen is deliberately stdlib-only, so this works
# even where the jax import chain is broken/wedged.
python estorch_tpu/serve/loadgen.py --selfcheck

echo "== serve selfcheck =="
# serving-vertical gate (estorch_tpu/serve, docs/serving.md): export a
# trained pendulum bundle, serve it through the dynamic batcher, drive
# concurrent load — gates bit-exact responses (vs the exporting run's
# es.predict), bucket/recompile accounting, zero shed, and a clean
# SIGTERM drain.  CPU only; the >=3x batching-throughput gate lives in
# the full `bench.py --serve` form and the tier-1 serving demo.
python bench.py --serve --selfcheck

echo "== fleet selfcheck =="
# serving-fleet gate (serve/router.py + serve/fleet.py, docs/serving.md
# "Fleet"): a 2-replica fleet under concurrent load with a DECLARED
# kill_replica chaos event must lose zero client answers (failover
# retries within the budget), open and re-close the breaker, respawn
# the corpse WARM (compiles_at_load == 0), and report a sane
# capacity-sweep ladder (max RPS at a p99 SLO).  CPU only, ~60s.
python bench.py --fleet --selfcheck

echo "== autoscale policy selfcheck =="
# autoscaler policy/log/refusal gate (obs/agg/autoscale.py,
# docs/serving.md "Autoscaling") against a synthetic store: demand
# scale-up, cooldown suppression, burn-rate step, sustained
# low-watermark scale-down, bit-exact decision-log replay + tamper
# detection, and the mismatched-capacity refusal naming both sides.
# Run as a FILE (the wedged-host contract): stdlib only, no jax,
# milliseconds.
python estorch_tpu/obs/agg/autoscale.py --selfcheck

echo "== autoscale selfcheck =="
# closed-control-loop E2E gate (obs/agg/autoscale.py + serve/fleet.py,
# docs/serving.md "Autoscaling"): a 2-replica fleet + in-process
# collector + real capacity sweep + autoscaler actuating over HTTP
# POST /scale — offered load TRIPLES mid-run and the replica count
# must track it (up past the floor, back down after the trickle), p99
# stays inside the SLO, zero client errors/shed including through a
# declared kill_replica during the scale-up, every scale-up replica
# loads warm (compiles_at_load == 0), the retirement drains cleanly,
# and the decision log replays bit-exactly.  CPU only, ~90s.
python bench.py --autoscale --selfcheck

echo "== coldstart selfcheck =="
# warm-bundle + quantized-serving gate (serve/warm.py, docs/serving.md
# "Cold start & quantized serving"): a warm bundle must load with ZERO
# fresh XLA builds (all persistent-cache hits) while the cold control
# leg provably pays the JIT storm, warm time-to-first-response must beat
# cold beyond the learned noise band, and every bf16 bucket's divergence
# must be measured inside the documented bound.  The >=1.5x bf16
# throughput gate applies on native-bf16 hardware (TPU); off-chip the
# ratio is recorded honestly (XLA:CPU bf16 is an upconvert path).
python bench.py --coldstart --selfcheck

echo "== compileall =="
python -m compileall -q estorch_tpu/ tests/ examples/

echo "lint gate: OK"
