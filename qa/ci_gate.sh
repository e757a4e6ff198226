#!/usr/bin/env bash
# qa/ci_gate.sh — both analyzers outside pytest, SARIF artifacts for CI.
#
#   qa/ci_gate.sh [BASE_REF] [SEED]
#
# 1. cephlint --diff BASE_REF  (default origin/main, falling back to
#    HEAD~1): whole-package static analysis, report narrowed to the
#    files changed since BASE_REF — then a timed FULL default-check run
#    as the scan-cost regression guard: the whole-package scan must
#    stay <=10s (the fast tier-1 budget), cost printed into
#    cephlint-full.txt next to the SARIF artifact.
# 2. cephrace --seed SEED (default 1): the short seeded thrash scenario
#    under the dynamic detector.
# 3. traffic smoke (ceph_tpu/bench/traffic.py): CPU backend, 2 clients,
#    ~5 s — fails when the batched/per-op encode throughput ratio drops
#    below 1.0 (the write-batcher regression gate); JSON lands next to
#    the SARIF artifacts.
# 4. trace smoke (--trace-smoke): the 2-client CLUSTER traffic run,
#    untraced vs sampling=1.0 — fails when the traced run produces no
#    connected trace tree (client submit -> replica commit), when the
#    per-stage breakdown misses one of admission/queue/encode/subop/
#    commit, or when tracing-enabled overhead exceeds 10% of the
#    untraced smoke.  Artifacts: traffic_trace.json (bench JSON) and
#    trace_perfetto.json (open in ui.perfetto.dev).
# 5. backend health smoke (ceph_tpu/qa/health_smoke.py): simulated
#    wedge must raise TPU_BACKEND_DEGRADED + KERNEL_FALLBACK_LATCHED in
#    `health detail` and on the prometheus exporter, dump_kernel_
#    telemetry must answer with its full schema, and recovery must
#    clear both checks.
# 6. bench off the chip: on a CPU host bench.py must exit non-zero,
#    name the missing chip and print no JSON line.
# 7. accounting smoke (ceph_tpu/qa/accounting_smoke.py): a 2-client
#    cluster must render per-(client,pool) labeled series on the
#    prometheus exporter with per-client bytes summing to the aggregate
#    within tolerance, `perf history` must answer from the mon, and a
#    failpoint-delayed op must surface in dump_historic_slow_ops with
#    per-stage attribution and a tail-promoted cross-entity trace
#    (trace_sampling_rate=0 — the head coin flip said no).
# 8. QoS smoke (ceph_tpu/qa/qos_smoke.py): the bully scenario (1 heavy
#    streamer vs N small Poisson writers) on a real LocalCluster,
#    controller off vs on — fails when worst-victim satisfaction
#    (achieved/offered) drops below the 0.5 starvation floor,
#    aggregate GiB/s regresses >10%, victim p99 improves <1.5x, or
#    the controller never pushed.
# 9. recovery smoke (ceph_tpu/qa/recovery_smoke.py): kill/revive an OSD
#    under 2-client traffic — fails unless PG_DEGRADED raises and
#    clears, progress events complete at 1.0, degraded objects drain to
#    0, ceph_recovery_*{pool,codec} series render on the exporter with
#    a plausible repair ratio (~k for RS), and the tail-promoted
#    recovery trace tree is connected cross-entity at sampling=0.
# 10. placement smoke (ceph_tpu/qa/placement_smoke.py): mark an OSD out
#    under a small live cluster — the placement module's remap forecast
#    (batched-CRUSH epoch diff, `placement diff`) must match the
#    observed acting-set churn within 10%, ceph_placement_*/ceph_remap_*
#    /ceph_balancer_* series must render on the exporter, a balancer
#    run against a stacked imbalance must commit moves and improve the
#    exported score, and PG_IMBALANCE must raise then clear.
# 11. topology smoke (ceph_tpu/qa/topology_smoke.py): the same
#    production encode must be bit-identical under a cpu-1, mesh-8, and
#    sentinel-degraded (two devices pinned failed) DevicePolicy, and the
#    degraded mesh must shrink to the survivors.  Step 1's cephlint run includes the
#    CL9/CL10 device-topology & sharding checks that pin the policy
#    refactor behind this smoke, and the CL11/CL12 determinism +
#    observability-drift checks (no extra step: the run uses the
#    default check set, so new checks ride it automatically).
#
# Analyzers emit SARIF 2.1.0 into qa/_sarif/ (github code-scanning uploads
# resolve URIs against the repo root, which is where this script runs
# from).  Exit is non-zero if EITHER gate reports active findings —
# the same exit contracts the pytest gates (tests/test_analyzer.py,
# tests/test_race.py) enforce.
set -u
cd "$(dirname "$0")/.."

BASE_REF="${1:-}"
SEED="${2:-1}"
OUT_DIR="qa/_sarif"
mkdir -p "$OUT_DIR"

if [ -z "$BASE_REF" ]; then
    if git rev-parse --verify -q origin/main >/dev/null; then
        BASE_REF=origin/main
    else
        BASE_REF=HEAD~1
    fi
fi

rc=0

echo "== cephlint (diff vs $BASE_REF) =="
python -m ceph_tpu.qa.analyzer --diff "$BASE_REF" --format=sarif \
    > "$OUT_DIR/cephlint.sarif"
lint_rc=$?
if [ $lint_rc -ge 2 ]; then
    # usage/parse error, not findings: the sarif on stdout is garbage —
    # drop it rather than hand CI an empty/invalid artifact
    rm -f "$OUT_DIR/cephlint.sarif"
    echo "cephlint: ERROR (exit $lint_rc):"
    python -m ceph_tpu.qa.analyzer --diff "$BASE_REF" || true
    rc=1
elif [ $lint_rc -eq 1 ]; then
    echo "cephlint: findings on changed files:"
    python -m ceph_tpu.qa.analyzer --diff "$BASE_REF" || true
    rc=1
else
    echo "cephlint: clean"
fi

echo "== cephlint scan-cost guard (full default-check run) =="
# the fast tier-1 class budgets the whole-package scan at 10s; a new
# check that blows the budget must fail HERE, not slowly eat tier-1
lint_t0=$(python -c 'import time; print(time.monotonic())')
python -m ceph_tpu.qa.analyzer ceph_tpu > "$OUT_DIR/cephlint-full.txt" \
    || true
lint_cost=$(python -c "import time; print(round(time.monotonic() - $lint_t0, 2))")
echo "cephlint full-scan cost: ${lint_cost}s (budget 10s)" \
    | tee -a "$OUT_DIR/cephlint-full.txt"
if python -c "import sys; sys.exit(0 if float('$lint_cost') <= 10.0 else 1)"; then
    echo "cephlint scan cost: OK"
else
    echo "cephlint scan cost: ${lint_cost}s EXCEEDS the 10s tier-1 budget"
    rc=1
fi

echo "== cephrace (seeded thrash, seed=$SEED) =="
JAX_PLATFORMS=cpu python -m ceph_tpu.qa.race --seed "$SEED" \
    --scenario thrash --events 4 --format=sarif \
    > "$OUT_DIR/cephrace.sarif"
race_rc=$?
if [ $race_rc -ge 2 ]; then
    rm -f "$OUT_DIR/cephrace.sarif"
    echo "cephrace: ERROR (exit $race_rc) — scenario crashed or baseline unreadable"
    rc=1
elif [ $race_rc -eq 1 ]; then
    echo "cephrace: findings:"
    JAX_PLATFORMS=cpu python -m ceph_tpu.qa.race --seed "$SEED" \
        --scenario thrash --events 4 || true
    rc=1
else
    echo "cephrace: clean"
fi

echo "== traffic smoke (batched vs per-op encode) =="
CEPH_TPU_BENCH_FORCE_CPU=1 python -m ceph_tpu.bench.traffic \
    --cpu --clients 2 --seconds 2 --json --smoke \
    > "$OUT_DIR/traffic.json"
traffic_rc=$?
if [ $traffic_rc -eq 0 ]; then
    echo "traffic smoke: ok"
elif python -c "import json,sys; json.load(open('$OUT_DIR/traffic.json'))" \
        2>/dev/null; then
    # the scenario ran and produced a result: rc!=0 means the ratio gate
    echo "traffic smoke: FAILED (batched/per-op ratio < 1.0):"
    cat "$OUT_DIR/traffic.json"
    rc=1
else
    # crashed before producing JSON: an error, not a perf regression
    rm -f "$OUT_DIR/traffic.json"
    echo "traffic smoke: ERROR (exit $traffic_rc) — scenario crashed"
    rc=1
fi

echo "== trace smoke (cluster traffic, untraced vs sampling=1.0) =="
CEPH_TPU_BENCH_FORCE_CPU=1 JAX_PLATFORMS=cpu python -m ceph_tpu.bench.traffic \
    --cpu --trace-smoke --clients 2 --seconds 2 --json \
    --trace-out "$OUT_DIR/trace_perfetto.json" \
    > "$OUT_DIR/traffic_trace.json"
trace_rc=$?
if [ $trace_rc -eq 0 ]; then
    echo "trace smoke: ok"
elif python -c "import json,sys; json.load(open('$OUT_DIR/traffic_trace.json'))" \
        2>/dev/null; then
    # ran to completion: rc!=0 means a gate fired (disconnected tree,
    # missing stage, or >10% tracing overhead) — details in the JSON
    echo "trace smoke: FAILED:"
    python -c "import json; [print(' -', p) for p in json.load(open('$OUT_DIR/traffic_trace.json'))['problems']]" || true
    rc=1
else
    rm -f "$OUT_DIR/traffic_trace.json" "$OUT_DIR/trace_perfetto.json"
    echo "trace smoke: ERROR (exit $trace_rc) — scenario crashed"
    rc=1
fi

echo "== backend health smoke (simulated wedge -> raise -> clear) =="
# forces a wedge through the sentinel's env probe override + a latched
# codec fallback, asserts TPU_BACKEND_DEGRADED / KERNEL_FALLBACK_LATCHED
# raise in `health detail` and on the prometheus exporter, smoke-checks
# the dump_kernel_telemetry JSON schema, then recovers and asserts the
# checks clear (ceph_tpu/qa/health_smoke.py; docs/observability.md)
python -m ceph_tpu.qa.health_smoke > "$OUT_DIR/health_smoke.json"
health_rc=$?
if [ $health_rc -eq 0 ]; then
    echo "health smoke: ok"
elif python -c "import json; json.load(open('$OUT_DIR/health_smoke.json'))" \
        2>/dev/null; then
    echo "health smoke: FAILED:"
    python -c "import json; [print(' -', p) for p in json.load(open('$OUT_DIR/health_smoke.json'))['problems']]" || true
    rc=1
else
    rm -f "$OUT_DIR/health_smoke.json"
    echo "health smoke: ERROR (exit $health_rc) — scenario crashed"
    rc=1
fi

echo "== bench off the chip (fails loudly, prints no number) =="
JAX_PLATFORMS=cpu python bench.py > "$OUT_DIR/bench_nochip.out" \
    2> "$OUT_DIR/bench_nochip.err"
bench_rc=$?
if [ $bench_rc -eq 0 ] || [ -s "$OUT_DIR/bench_nochip.out" ] \
        || ! grep -q "no TPU" "$OUT_DIR/bench_nochip.err"; then
    echo "bench off the chip: FAILED — rc=$bench_rc, want non-zero, no" \
         "stdout, and the missing chip named"
    rc=1
else
    echo "bench off the chip: ok (rc=$bench_rc)"
fi

echo "== accounting smoke (labeled series + slow-op forensics) =="
# per-client labeled series on the exporter, bytes conservation, `perf
# history` through the mon, and a failpoint-delayed op surfacing in
# dump_historic_slow_ops with a tail-promoted cross-entity trace
# (ceph_tpu/qa/accounting_smoke.py; docs/observability.md)
python -m ceph_tpu.qa.accounting_smoke > "$OUT_DIR/accounting_smoke.json"
acct_rc=$?
if [ $acct_rc -eq 0 ]; then
    echo "accounting smoke: ok"
elif python -c "import json; json.load(open('$OUT_DIR/accounting_smoke.json'))" \
        2>/dev/null; then
    echo "accounting smoke: FAILED:"
    python -c "import json; [print(' -', p) for p in json.load(open('$OUT_DIR/accounting_smoke.json'))['problems']]" || true
    rc=1
else
    rm -f "$OUT_DIR/accounting_smoke.json"
    echo "accounting smoke: ERROR (exit $acct_rc) — scenario crashed"
    rc=1
fi

echo "== QoS smoke (bully scenario, controller off vs on) =="
# per-client mClock classes + batcher share + live controller must
# improve victim fairness and p99 without costing >10% aggregate
# (ceph_tpu/qa/qos_smoke.py; docs/qos.md)
python -m ceph_tpu.qa.qos_smoke > "$OUT_DIR/qos_smoke.json"
qos_rc=$?
if [ $qos_rc -eq 0 ]; then
    echo "qos smoke: ok"
elif python -c "import json; json.load(open('$OUT_DIR/qos_smoke.json'))" \
        2>/dev/null; then
    echo "qos smoke: FAILED:"
    python -c "import json; [print(' -', p) for p in json.load(open('$OUT_DIR/qos_smoke.json'))['problems']]" || true
    rc=1
else
    rm -f "$OUT_DIR/qos_smoke.json"
    echo "qos smoke: ERROR (exit $qos_rc) — scenario crashed"
    rc=1
fi

echo "== recovery smoke (kill/revive observability) =="
# PG_DEGRADED/progress raise and clear around a kill/revive under
# 2-client traffic, ceph_recovery_* renders with a plausible repair
# ratio, and the recovery trace tree assembles cross-entity at
# sampling=0 (ceph_tpu/qa/recovery_smoke.py; docs/observability.md)
python -m ceph_tpu.qa.recovery_smoke > "$OUT_DIR/recovery_smoke.json"
heal_rc=$?
if [ $heal_rc -eq 0 ]; then
    echo "recovery smoke: ok"
elif python -c "import json; json.load(open('$OUT_DIR/recovery_smoke.json'))" \
        2>/dev/null; then
    echo "recovery smoke: FAILED:"
    python -c "import json; [print(' -', p) for p in json.load(open('$OUT_DIR/recovery_smoke.json'))['problems']]" || true
    rc=1
else
    rm -f "$OUT_DIR/recovery_smoke.json"
    echo "recovery smoke: ERROR (exit $heal_rc) — scenario crashed"
    rc=1
fi

echo "== placement smoke (remap forecast + balancer scoring) =="
# forecast-vs-observed churn on an osd-out, balancer score improvement
# against a stacked imbalance, PG_IMBALANCE raise/clear, and the
# ceph_placement_*/ceph_remap_*/ceph_balancer_* series on the exporter
# (ceph_tpu/qa/placement_smoke.py; docs/observability.md)
JAX_PLATFORMS=cpu python -m ceph_tpu.qa.placement_smoke \
    > "$OUT_DIR/placement_smoke.json"
place_rc=$?
if [ $place_rc -eq 0 ]; then
    echo "placement smoke: ok"
elif python -c "import json; json.load(open('$OUT_DIR/placement_smoke.json'))" \
        2>/dev/null; then
    echo "placement smoke: FAILED:"
    python -c "import json; [print(' -', p) for p in json.load(open('$OUT_DIR/placement_smoke.json'))['problems']]" || true
    rc=1
else
    rm -f "$OUT_DIR/placement_smoke.json"
    echo "placement smoke: ERROR (exit $place_rc) — scenario crashed"
    rc=1
fi

echo "== topology smoke (cpu-1 / mesh-N / degraded parity) =="
# the same sharded encode through three injected DevicePolicy variants
# must be bit-identical, and the sentinel-degraded mesh must shrink
# instead of wedging
# (ceph_tpu/qa/topology_smoke.py; docs/static_analysis.md CL9)
python -m ceph_tpu.qa.topology_smoke > "$OUT_DIR/topology_smoke.json"
topo_rc=$?
if [ $topo_rc -eq 0 ]; then
    echo "topology smoke: ok"
elif python -c "import json; json.load(open('$OUT_DIR/topology_smoke.json'))" \
        2>/dev/null; then
    echo "topology smoke: FAILED:"
    python -c "import json; [print(' -', p) for p in json.load(open('$OUT_DIR/topology_smoke.json'))['problems']]" || true
    rc=1
else
    rm -f "$OUT_DIR/topology_smoke.json"
    echo "topology smoke: ERROR (exit $topo_rc) — scenario crashed"
    rc=1
fi

echo "== read smoke (coalesced READ plane: speedup / GET / boot / degraded / ranged) =="
# batched >= 3x per-op at 32 CPU clients, GET-heavy cache promotion,
# boot-storm coalescing, degraded p99 under the CI bar, and the ranged
# degraded decode dispatching exactly k x window bytes into the kernel
# (ceph_tpu/qa/read_smoke.py; docs/read_path.md)
JAX_PLATFORMS=cpu python -m ceph_tpu.qa.read_smoke \
    > "$OUT_DIR/read_smoke.json"
read_rc=$?
if [ $read_rc -eq 0 ]; then
    echo "read smoke: ok"
elif python -c "import json; json.load(open('$OUT_DIR/read_smoke.json'))" \
        2>/dev/null; then
    echo "read smoke: FAILED:"
    python -c "import json; [print(' -', p) for p in json.load(open('$OUT_DIR/read_smoke.json'))['problems']]" || true
    rc=1
else
    rm -f "$OUT_DIR/read_smoke.json"
    echo "read smoke: ERROR (exit $read_rc) — scenario crashed"
    rc=1
fi

echo "== storm smoke (250-stub seeded failure storm + invariant gates) =="
# real mon+mgr over 250 stub OSDs: seeded kill/revive waves, rack
# netsplit, reweight churn under 2-tenant traffic; every invariant
# green (no acked-write loss, PGs clean, forecast-vs-observed <=10%,
# bounded oscillation, class conservation, health symmetry, replay
# determinism) plus a bare-map remap storm cross-check
# (ceph_tpu/qa/storm_smoke.py; docs/storm_sim.md)
JAX_PLATFORMS=cpu python -m ceph_tpu.qa.storm_smoke \
    > "$OUT_DIR/storm_smoke.json"
storm_rc=$?
if [ $storm_rc -eq 0 ]; then
    echo "storm smoke: ok"
elif python -c "import json; json.load(open('$OUT_DIR/storm_smoke.json'))" \
        2>/dev/null; then
    echo "storm smoke: FAILED:"
    python -c "import json; [print(' -', p) for p in json.load(open('$OUT_DIR/storm_smoke.json'))['problems']]" || true
    rc=1
else
    rm -f "$OUT_DIR/storm_smoke.json"
    echo "storm smoke: ERROR (exit $storm_rc) — scenario crashed"
    rc=1
fi

echo "Artifacts in $OUT_DIR/ (cephlint.sarif, cephrace.sarif, traffic.json, traffic_trace.json, trace_perfetto.json, health_smoke.json, bench_wedged.json, accounting_smoke.json, qos_smoke.json, recovery_smoke.json, placement_smoke.json, topology_smoke.json, read_smoke.json, storm_smoke.json)"
exit $rc
