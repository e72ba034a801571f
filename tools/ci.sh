#!/usr/bin/env bash
# Local CI gate (ISSUE 2 + 3 + 11 + 15 + 17 + 18 + 19 + 20):
#   ruff -> jaxlint (AST) -> jaxpr audit + jaxcost budget gate + shardcheck
#   + protocheck protocol lint
#   + hbmcheck HBM residency/liveness/capacity gate
#   -> telemetry/chaos/serve smokes
#   -> tpu-scope (timeline reconstruction + health verb + bench gate)
#   -> protocheck explorer smoke (bounded interleaving/fault search)
#   -> tpu-load traffic replay + fleet router smokes (baseline-diffed)
#   -> tier-1 pytest.
#
#   tools/ci.sh            # full gate
#   tools/ci.sh --fast     # skip the pytest leg (lint + audit + gates only)
#
# ruff is optional in minimal containers (the image does not bake it);
# the repo-specific invariants are enforced by `python -m
# tpu_pbrt.analysis` regardless. The jaxcost budget gate compares the
# entry-point static rooflines against the committed
# tpu_pbrt/analysis/budgets.json — a perf regression fails HERE even
# when no accelerator is reachable; after
# an INTENTIONAL hot-path change refresh with
# `python -m tpu_pbrt.analysis --update-budgets` and commit the file.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

echo "== ruff"
if command -v ruff >/dev/null 2>&1; then
    ruff check tpu_pbrt tests bench.py
elif python -c "import ruff" >/dev/null 2>&1; then
    python -m ruff check tpu_pbrt tests bench.py
else
    echo "   ruff not installed — skipping (pip install ruff to enable)"
fi

# fail-FAST stage: the AST lint costs ~2 s with no jax import; a lint
# error aborts here before the multi-minute trace/compile stages below
# (which re-lint — the duplication is the price of the early exit).
# --no-protocheck/--no-hbmcheck too: layers 5-6 spin up real
# RenderServices / evaluate the serve memory model, so they belong with
# the heavier stages, not the syntax gate.
echo "== jaxlint AST layer (python -m tpu_pbrt.analysis --no-audit --no-cost --no-shardcheck --no-protocheck --no-hbmcheck)"
python -m tpu_pbrt.analysis --no-audit --no-cost --no-shardcheck --no-protocheck --no-hbmcheck

# the full analysis stage runs every layer and reports EVERY failing
# stage before exiting non-zero (ISSUE 11 satellite).
# (layer 5, protocheck, also runs here: SV-* protocol lint + the
# mutation-regression corpus + a small bounded exploration. layer 6,
# hbmcheck, gates the serve stack's static HBM model — worst-case
# footprint vs the platform capacity table + the committed
# hbm_budgets.json, terminal-path buffer release, residency-estimate
# accuracy, donation-alias dedup.)
echo "== jaxpr audit + jaxcost budget gate + shardcheck + protocheck + hbmcheck (python -m tpu_pbrt.analysis)"
python -m tpu_pbrt.analysis

# telemetry smoke (ISSUE 4): render a cropped cornell through the real
# CLI with --trace + the flight recorder, then gate on the artifacts —
# the trace JSON must validate against the Chrome-trace schema and the
# flight JSONL must carry >= 1 heartbeat for every render phase.
echo "== telemetry smoke: --trace render + trace/flight validation"
SMOKE_DIR="$(mktemp -d)"
trap 'rm -rf "$SMOKE_DIR"' EXIT
XLA_FLAGS="${XLA_FLAGS:-} --xla_backend_optimization_level=0" \
TPU_PBRT_FLIGHT_PATH="$SMOKE_DIR/flight.jsonl" \
python -m tpu_pbrt.main scenes/cornell-path.pbrt --quick --quiet \
    --cropwindow 0 0.25 0 0.25 \
    -o "$SMOKE_DIR/smoke.pfm" --trace "$SMOKE_DIR/trace.json" \
    --metrics-path "$SMOKE_DIR/metrics.prom"
python -m tpu_pbrt.obs "$SMOKE_DIR/trace.json" \
    --flight "$SMOKE_DIR/flight.jsonl" \
    --require-phases render,render_done,develop --min-spans 3 \
    --metrics "$SMOKE_DIR/metrics.prom"

# stream-tracer recovery smoke: a poisoned mid-render dispatch in a
# killeroo-like scene must recover to a film bit-identical to the clean
# render. The only row of the matrix whose recovery ladder runs over a
# stream-traced scene (cornell compiles to the brute path); running it
# alone first gives a fast, named failure before the full matrix below.
echo "== stream tracer recovery smoke (python -m tpu_pbrt.chaos --only stream-tracer)"
python -m tpu_pbrt.chaos --only stream-tracer

# pipelined-dispatch smoke (ISSUE 13): a poisoning dispatch loss with
# TPU_PBRT_PIPELINE=3 chunk-slices in flight must flush the window,
# roll back to a deferred-written checkpoint and recover a film
# bit-identical to the undisturbed render. Standalone first for a fast,
# named failure; the full matrix below re-runs it under the explicit
# default depth.
echo "== pipelined dispatch smoke (python -m tpu_pbrt.chaos --only pipeline)"
TPU_PBRT_PIPELINE=2 python -m tpu_pbrt.chaos --only pipeline

# chaos recovery matrix (ISSUE 5): every fault scenario — poisoned/clean
# dispatch loss, torn/crashed/bit-flipped checkpoint writes, corrupt
# checkpoint resume, NaN wave, retry-budget exhaustion, mesh device
# loss, plus the ISSUE 20 fleet rows (replica killed mid-job resumes
# elsewhere from the spool; a restarted router adopts the replicas)
# — must recover to a film BIT-identical to the undisturbed render
# (the nan-wave-scrub row instead gates the degrade semantics: finite
# image + nonfinite_deposits>0). Runs on CPU; no accelerator needed.
# TPU_PBRT_PIPELINE=2 is the default, exported explicitly so the gate
# keeps covering the pipelined drain even if the default ever moves
echo "== chaos recovery matrix (python -m tpu_pbrt.chaos)"
TPU_PBRT_PIPELINE=2 python -m tpu_pbrt.chaos

# render-service smoke (ISSUE 6 + ISSUE 10 + ISSUE 15): submit two
# cropped cornell jobs to one service, preempt/resume one mid-render,
# and require both films finite AND bit-identical to a solo
# run-to-completion render, a warm resubmit with 0 scene compiles + 0
# jit retraces, >= 1 streamed preview, a DETERMINISTIC shed count from
# an over-SLO submit burst, a lint-clean Prometheus metrics exposition
# with per-tenant histograms, trace-id exemplars on the slice
# histogram, and a clean health-watchdog verdict. The run is
# tracing-armed (TPU_PBRT_TRACE_PATH/FLIGHT_PATH) so the next stage can
# reconstruct its job timelines.
echo "== render service smoke, tracing-armed (python -m tpu_pbrt.serve --selftest)"
XLA_FLAGS="${XLA_FLAGS:-} --xla_backend_optimization_level=0" \
TPU_PBRT_TRACE_PATH="$SMOKE_DIR/serve_trace.json" \
TPU_PBRT_FLIGHT_PATH="$SMOKE_DIR/serve_flight.jsonl" \
TPU_PBRT_PIPELINE=2 python -m tpu_pbrt.serve --selftest

# tpu-scope stage (ISSUE 15): (1) rebuild every job's causal timeline
# from the selftest's trace + per-job flight exports and require it
# complete — paired job/wait/slice async spans, bound dispatch->retire
# flow arrows, ok-retired coverage of every chunk, flight heartbeats
# joined by trace id; (2) round-trip the JSONL daemon's `health` verb
# (the watchdog must report ok on an idle service — the chaos matrix
# above already proved the wedge/backoff-storm rows DO flag it);
# (3) the bench regression gate's selftest: baseline self-pass, infra
# outage exemption, synthetic 50% regression caught by metric name.
echo "== tpu-scope: timeline reconstruction + health verb + bench gate"
python tools/scope.py "$SMOKE_DIR/serve_trace.json" \
    --flight "$SMOKE_DIR/serve_flight.jsonl" --check
printf '%s\n' '{"op": "health"}' '{"op": "shutdown"}' \
    | python -m tpu_pbrt.serve > "$SMOKE_DIR/health.jsonl"
python - "$SMOKE_DIR/health.jsonl" <<'EOF'
import json, sys
docs = [json.loads(x) for x in open(sys.argv[1]) if x.strip()]
rep = next(d for d in docs if d.get("op") == "health")
assert rep["ok"] and rep["firing"] == [], rep
names = {c["name"] for c in rep["conditions"]}
assert names == {"wedge", "backoff_storm", "slo_burn", "nonfinite_spike"}, names
print(f"health verb OK ({len(names)} conditions, none firing)")
EOF
python tools/bench_gate.py --selftest

# protocheck explorer smoke (ISSUE 17): a bounded exhaustive search
# over decision sequences — arrival orders x pipeline depths 1-3 x
# CHAOS fault placements x preempt/resume timings — running the REAL
# RenderService under a VirtualClock with stub dispatches, checking
# every PROTO-* invariant after every decision plus the PROTO-DET
# byte-identical-replay gate. Fixed seed and node/depth budget: the
# whole grid completes in seconds, well under the 60 s CI allowance.
# The exported canonical-drain trace carries virtual-time stamps
# (otherData.clock = "virtual"); scope --check must accept it.
echo "== protocheck explorer smoke (python tools/explore.py --ci)"
python tools/explore.py --ci --seed 0 --nodes 40 --depth 7 \
    --trace-out "$SMOKE_DIR/explore_trace.json"
python tools/scope.py "$SMOKE_DIR/explore_trace.json" --check

# tpu-load smoke (ISSUE 19): seeded traffic scenarios replayed against
# the REAL RenderService in accelerated virtual time — determinism
# (byte-identical decision logs across same-seed replays), burst shed
# fraction + per-class p99 queue waits within spec, zero health-
# watchdog false positives on clean scenarios (required flags on the
# storm ones), pin balance at drain, and a capacity-sweep knee. Fixed
# seed, hard wall budget. The exported trace carries dense multi-
# tenant traffic in virtual time; scope --check must accept it. The
# deterministic gate report is diffed against the committed baseline;
# after an INTENTIONAL
# scheduling/policy change refresh with:
#   python -m tpu_pbrt.load --ci --seed 7 --report LOADTEST_baseline.json
echo "== tpu-load traffic-replay smoke (python -m tpu_pbrt.load --ci)"
python -m tpu_pbrt.load --ci --seed 7 --budget-s 120 \
    --report "$SMOKE_DIR/load_report.json" \
    --trace-out "$SMOKE_DIR/load_trace.json"
python tools/scope.py "$SMOKE_DIR/load_trace.json" --check
if ! diff -u LOADTEST_baseline.json "$SMOKE_DIR/load_report.json"; then
    echo "   LOADTEST_baseline.json is stale — gate outcomes moved (see"
    echo "   diff above); refresh after an INTENTIONAL policy change:"
    echo "   python -m tpu_pbrt.load --ci --seed 7 --report LOADTEST_baseline.json"
    exit 1
fi

# tpu-fleet stage (ISSUE 20): replicated serve behind the failover
# router. (1) the fleet selftest — two REAL in-process replicas under
# one VirtualClock: scene-affinity routing with a residency warm hit,
# fleet-edge shedding at a clamped knee, and a kill-one failover whose
# resumed film is BIT-identical to the undisturbed solo render — with
# tracing armed so (2) scope --check validates the cross-replica
# timeline (router-owned root spans spanning the re-route). (3) the
# seeded router mutant: a failover that re-submits WITHOUT consuming
# the old instance must be flagged by PROTO-ROUTE-DUP by name
# (--mutate exits 1 on detection, so the gate inverts). (4) the
# multi-replica load smoke: the same seeded workloads replayed through
# the router at --replicas 2, decision logs byte-deterministic per
# (spec, seed, N), gates evaluated fleet-wide, report diffed against
# the committed baseline; after an INTENTIONAL routing/policy change:
#   python -m tpu_pbrt.load --scenario steady --scenario heavy \
#     --scenario editstorm --replicas 2 --seed 7 --report FLEET_baseline.json
echo "== fleet router smoke, tracing-armed (python -m tpu_pbrt.fleet --selftest)"
XLA_FLAGS="${XLA_FLAGS:-} --xla_backend_optimization_level=0" \
TPU_PBRT_TRACE_PATH="$SMOKE_DIR/fleet_trace.json" \
python -m tpu_pbrt.fleet --selftest
python tools/scope.py "$SMOKE_DIR/fleet_trace.json" --check
echo "== fleet failover-dedup mutant (python tools/explore.py --mutate failover-skips-spool-consume)"
if python tools/explore.py --mutate failover-skips-spool-consume > "$SMOKE_DIR/fleet_mutant.log" 2>&1; then
    echo "   seeded failover-dedup mutant NOT detected — PROTO-ROUTE-DUP gate rotted"
    cat "$SMOKE_DIR/fleet_mutant.log"
    exit 1
fi
grep -q "PROTOCHECK VIOLATION PROTO-ROUTE-DUP" "$SMOKE_DIR/fleet_mutant.log" || {
    echo "   mutant flagged, but not by PROTO-ROUTE-DUP:"
    cat "$SMOKE_DIR/fleet_mutant.log"
    exit 1
}
echo "== fleet multi-replica load smoke (python -m tpu_pbrt.load --replicas 2)"
python -m tpu_pbrt.load --scenario steady --scenario heavy \
    --scenario editstorm --replicas 2 --seed 7 \
    --report "$SMOKE_DIR/fleet_report.json"
if ! diff -u FLEET_baseline.json "$SMOKE_DIR/fleet_report.json"; then
    echo "   FLEET_baseline.json is stale — routed gate outcomes moved"
    echo "   (see diff above); refresh after an INTENTIONAL change:"
    echo "   python -m tpu_pbrt.load --scenario steady --scenario heavy --scenario editstorm --replicas 2 --seed 7 --report FLEET_baseline.json"
    exit 1
fi

# hbm leak-mutant smoke (ISSUE 18): re-introduce the seeded park-path
# film leak through the REAL entry point and require PROTO-HBM to flag
# it by name. `--mutate` exits 1 ON DETECTION, so the gate inverts:
# exit 0 here means the leak went unnoticed and the HBM liveness gate
# has rotted.
echo "== hbm leak-mutant smoke (python tools/explore.py --mutate park-skips-film-release)"
if python tools/explore.py --mutate park-skips-film-release > "$SMOKE_DIR/hbm_mutant.log" 2>&1; then
    echo "   seeded HBM leak mutant NOT detected — PROTO-HBM gate rotted"
    cat "$SMOKE_DIR/hbm_mutant.log"
    exit 1
fi
grep -q "PROTOCHECK VIOLATION PROTO-HBM" "$SMOKE_DIR/hbm_mutant.log" || {
    echo "   mutant flagged, but not by PROTO-HBM:"
    cat "$SMOKE_DIR/hbm_mutant.log"
    exit 1
}

# metrics registry selftest + bench trajectory report (ISSUE 10
# satellites): the registry's record -> exposition -> lint -> percentile
# loop must close with zero renders, and whatever BENCH_r*.json
# captures are committed (none at present) must still parse into the
# one-table perf trajectory — non-zero here means the bench JSON schema
# drifted. Where a BENCH_REPORT.md is committed it must be the
# regenerated table.
echo "== metrics selftest + bench trajectory report"
python -m tpu_pbrt.obs --metrics-selftest
python tools/bench_report.py > "$SMOKE_DIR/bench_report.md"
if [[ -f BENCH_REPORT.md ]] && ! diff -q "$SMOKE_DIR/bench_report.md" BENCH_REPORT.md >/dev/null 2>&1; then
    echo "   BENCH_REPORT.md is stale — regenerate with:"
    echo "   python tools/bench_report.py > BENCH_REPORT.md"
    exit 1
fi

if [[ "${1:-}" == "--fast" ]]; then
    echo "== pytest skipped (--fast)"
    exit 0
fi

echo "== tier-1 pytest"
python -m pytest tests/ -q -m 'not slow' -p no:cacheprovider
