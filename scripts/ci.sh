#!/usr/bin/env bash
# Tiered CI entrypoint — the same subcommands the GitHub workflow runs,
# so local runs and the CI matrix cannot drift.
#
#   ci.sh collect      fast-fail: the suite must import and collect
#   ci.sh unit         full tier-1 pytest run (regressions block merge)
#   ci.sh kernels      Pallas kernel parity in interpret mode
#   ci.sh smoke        serving-stack smokes: pipelined, sharded, and
#                      multi-process shard workers, end-to-end
#   ci.sh chaos        fault-tolerance smoke: 2-shard x 2-replica
#                      remote-worker fleet under load with seeded fault
#                      injection + SIGKILL mid-run (zero failed
#                      requests, post-heal parity)
#   ci.sh churn        live-index soak: seeded interleaved upsert/
#                      delete/query trace over a 2-shard process-worker
#                      stack, rebuild parity at every quiesce point and
#                      zero failed requests across the compaction swap
#   ci.sh bench-gate   pinned-seed mini benchmark vs committed baseline
#   ci.sh all          every stage above, in order (tier-1 default)
#
# Extra args after `unit` are forwarded to pytest (e.g.
# `ci.sh unit -k sharding`). Running with no subcommand = `all`.
# Each stage's wall time is reported in a summary at exit.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

STAGE_NAMES=()
STAGE_SECS=()

summary() {
    local status=$?
    if [ "${#STAGE_NAMES[@]}" -gt 0 ]; then
        echo
        echo "── ci stage summary ──────────────────────────"
        local i
        for i in "${!STAGE_NAMES[@]}"; do
            printf '  %-12s %6ss\n' "${STAGE_NAMES[$i]}" "${STAGE_SECS[$i]}"
        done
        echo "──────────────────────────────────────────────"
    fi
    return $status
}
trap summary EXIT

run_stage() {
    local name="$1"; shift
    echo "── ci stage: ${name} ──"
    local t0=$SECONDS
    "$@"
    STAGE_NAMES+=("$name")
    STAGE_SECS+=("$((SECONDS - t0))")
}

ensure_hypothesis() {
    # property tests are skipped without hypothesis (optional test
    # extra); install it when the image has network access
    python -c "import hypothesis" 2>/dev/null \
        || pip install -q hypothesis 2>/dev/null \
        || echo "hypothesis unavailable (offline image) — property tests skip"
}

stage_collect() {
    # cheapest possible fail: import errors and broken test modules
    # surface in seconds, before any index gets built. Output is
    # swallowed on success (thousands of test ids) but replayed on
    # failure — a silent red collect job would be undiagnosable.
    local out
    if ! out=$(python -m pytest -q --collect-only 2>&1); then
        printf '%s\n' "$out" | tail -60
        return 1
    fi
}

stage_unit() {
    ensure_hypothesis
    python -m pytest -x -q "$@"
}

stage_kernels() {
    # kernel parity in Pallas interpret mode, run explicitly: the kernel
    # bodies (maxsim, decompress+maxsim, splade single/batched, and the
    # fused rerank tail incl. its bitwise split-pipeline equivalence)
    # must match their jnp oracles even when a filtered unit run
    # skipped them
    python -m pytest -q tests/test_kernels.py tests/test_splade_stage1.py \
        -k "interpret or fused_rerank"
}

stage_smoke() {
    # pipelined smoke: full serving stack with the stage-graph executor
    # (pipeline_depth=2) over the device stage-1 scorer (the Pallas
    # backend needs a TPU: chip_smoke.py covers it there)
    python -m repro.launch.serve --pipeline-depth 2 --splade-backend jax \
        --max-batch 8 --qps 100 --n 32

    # scatter-gather smoke: 2-shard group through the sharded plans
    # (per-shard mmap segments, fanout gathers, global top-k merge)
    python -m repro.launch.serve --shards 2 --pipeline-depth 2 \
        --max-batch 8 --qps 100 --n 32

    # process-group smoke: the same 2-shard topology with one
    # shared-nothing worker process per shard behind the RPC
    # coordinator, tensors over the zero-copy shm ring arenas
    # (spawn, serve, graceful shutdown — no orphans, no arena leaks)
    python -m repro.launch.serve --shards 2 --shard-workers process \
        --shard-transport shm \
        --pipeline-depth 2 --max-batch 8 --qps 100 --n 24

    # front-door smoke: coordinator caches + SLO admission under a
    # Zipf-skewed trace — repeats resolve from the exact cache, the
    # stage-1 cache backs the misses, and the generous SLO must not
    # shed a single request on a healthy run
    python -m repro.launch.serve --pipeline-depth 2 --max-batch 8 \
        --cache-exact 512 --cache-stage1 512 \
        --admission-slo-ms 60000 --skew 1.2 --qps 200 --n 48
}

stage_chaos() {
    # chaos smoke: a 2-shard x 2-replica fleet of standalone workers
    # on remote TCP endpoints, Poisson load with a seeded FaultyChannel
    # schedule (drops/delays/truncated/corrupt frames) while a timed
    # choreography SIGKILLs one replica of every shard mid-run and
    # restarts it — the sweep asserts zero failed requests and
    # post-heal bitwise parity with the healthy baseline
    python -m benchmarks.bench_latency --chaos-sweep --quick
}

stage_churn() {
    # live-index churn soak, the CI tier: every mutation and query goes
    # through the TCP front of a 2-shard process-worker group, with
    # from-scratch rebuild parity asserted at each quiesce point and a
    # compaction swap under concurrent traffic (results/churn_ci.json)
    python scripts/churn_soak.py --quick
}

stage_bench_gate() {
    python scripts/bench_gate.py
}

cmd="${1:-all}"
[ $# -gt 0 ] && shift

case "$cmd" in
    collect)    run_stage collect stage_collect ;;
    unit)       run_stage unit stage_unit "$@" ;;
    kernels)    run_stage kernels stage_kernels ;;
    smoke)      run_stage smoke stage_smoke ;;
    chaos)      run_stage chaos stage_chaos ;;
    churn)      run_stage churn stage_churn ;;
    bench-gate) run_stage bench-gate stage_bench_gate ;;
    all)
        run_stage collect stage_collect
        run_stage unit stage_unit "$@"
        run_stage kernels stage_kernels
        run_stage smoke stage_smoke
        run_stage chaos stage_chaos
        run_stage churn stage_churn
        run_stage bench-gate stage_bench_gate
        ;;
    *)
        echo "usage: ci.sh [collect|unit|kernels|smoke|chaos|churn|bench-gate|all]" >&2
        exit 2
        ;;
esac
