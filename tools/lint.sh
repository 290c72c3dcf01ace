#!/usr/bin/env bash
# CI gate: jaxlint (new findings vs LINT_BASELINE.json), jaxgraph (IR-level
# audit + FLOP/byte budget gate vs GRAPH_BASELINE.json), and the
# bench_compare regression gate over BENCH_BASELINES.json + runs.jsonl.
#
# Exit 0 only when ALL pass:
#   - `python -m blockchain_simulator_tpu.lint --format json` reports zero
#     non-baselined findings (exit 1 on any new finding, 2 on parse errors);
#   - `python -m blockchain_simulator_tpu.lint.graph --format json` traces
#     every registered executable factory and reports zero non-baselined IR
#     findings / budget regressions (GRAPH=0 skips — it costs ~1.5 min of
#     tracing on the 2-core box);
#   - the serving smoke (`python -m blockchain_simulator_tpu.serve
#     --self-test`) drives the daemon over real HTTP (SERVE=0 skips);
#   - the chaos drill (`tools/chaos_drill.py --quick`) runs every scripted
#     fault scenario twice under one seed, invariant-clean and
#     deterministic (CHAOS=0 skips);
#   - the fleet drill (`tools/fleet_bench.py --quick`) does the same for
#     the replicated serving tier (replica death/WAL handoff, hedged
#     failover, retry storm, double-claim) plus a 2-replica micro-bench
#     (FLEET=0 skips);
#   - the sweep resume drill (`tools/sweep_resume_drill.py --quick`)
#     SIGKILLs a real journaled-sweep subprocess mid-grid and demands
#     the resume recompute at most the in-flight chunk with rows
#     bit-equal (RESUME=0 skips);
#   - the query drill (`tools/query_drill.py --quick`) answers one
#     adaptive query against its dense grid (same boundary, bit-equal
#     rows) and SIGKILLs a journaled-query subprocess mid-search,
#     demanding the resume recompute zero completed steps (QUERY=0
#     skips);
#   - `tools/bench_compare.py` sees no metric drop beyond its threshold.
#
# When $BLOCKSIM_RUNS_JSONL is set the lint runs themselves land in
# runs.jsonl (metrics "jaxlint_new_findings", "jaxgraph_new_findings", and
# per-program "graph_*_gflops"/"graph_*_bytes") via utils/obs.py, so the
# findings + budget trajectories are charted by bench_compare next to the
# perf history (*_findings metrics and the graph_* prefix are never gated
# there — the budget gate lives in lint.graph itself).
#
# After both gates, tools/warm_bench.sh checks the cold-vs-warm compile
# split of two bench.py rehearsals against jax's persistent compile cache
# (WARM_BENCH=0 skips; see the block below).
#
# Every stage is a CPU rehearsal: the chain claims no chip (the chip is
# reached by sending `python chip_smoke.py` through the chip tool).
#
# Usage: tools/lint.sh [--threshold 0.5]
set -u
REPO="$(cd "$(dirname "$0")/.." && pwd)"
cd "$REPO"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"

rc=0

echo "== jaxlint =="
python -m blockchain_simulator_tpu.lint \
    blockchain_simulator_tpu tools bench.py chip_smoke.py --format json
lint_rc=$?
if [ "$lint_rc" -ne 0 ]; then
    echo "lint.sh: jaxlint FAILED (rc=$lint_rc)" >&2
    rc=1
fi

if [ "${GRAPH:-1}" != "0" ]; then
    echo "== jaxgraph =="
    python -m blockchain_simulator_tpu.lint.graph --format json
    graph_rc=$?
    if [ "$graph_rc" -ne 0 ]; then
        echo "lint.sh: jaxgraph FAILED (rc=$graph_rc)" >&2
        rc=1
    fi
fi

# shardlint (lint/comms): every mesh-capable factory compiled under its
# representative virtual-device meshes, post-SPMD collectives extracted
# and gated against COMMS_BASELINE.json (counts + bytes-moved-per-device,
# growth from a zero pin always fails).  COMMS=0 skips (~2.5 min of SPMD
# compiles on this box); lands comms_new_findings + per-program
# comms_*_bytes in runs.jsonl (charted, never gated by bench_compare —
# the budget gate lives in lint.comms itself).
if [ "${COMMS:-1}" != "0" ]; then
    echo "== shardlint =="
    python -m blockchain_simulator_tpu.lint.comms --format json
    comms_rc=$?
    if [ "$comms_rc" -ne 0 ]; then
        echo "lint.sh: shardlint FAILED (rc=$comms_rc)" >&2
        rc=1
    fi
fi

# Serving smoke (serve/__main__.py --self-test): ephemeral daemon on the
# CPU backend, a batch/reject/health drill over real HTTP, one JSON summary
# line; lands serve_rps / serve_p99_ms in runs.jsonl when set (p99 is gated
# lower-is-better by bench_compare).  SERVE=0 skips (~30 s of compile on
# the 2-core box); tests/test_zserve.py covers the self-test end to end.
if [ "${SERVE:-1}" != "0" ]; then
    echo "== serve smoke =="
    python -m blockchain_simulator_tpu.serve --self-test
    serve_rc=$?
    if [ "$serve_rc" -ne 0 ]; then
        echo "lint.sh: serve smoke FAILED (rc=$serve_rc)" >&2
        rc=1
    fi
fi

# Chaos drill (tools/chaos_drill.py --quick): every scripted fault
# scenario run twice under one chaos seed — zero invariant violations,
# byte-equal summaries — against the real server/dispatch/cache stack;
# lands chaos_invariant_violations / chaos_replay_divergence in
# runs.jsonl (charted, never gated by bench_compare — the drill's own
# exit code is the gate).  CHAOS=0 skips (~40 s of drills on the 2-core
# box); the full kill -9 leg lives in the slow-marked test and the
# committed ARTIFACT_chaos_drill.json.
if [ "${CHAOS:-1}" != "0" ]; then
    echo "== chaos drill =="
    python tools/chaos_drill.py --quick
    chaos_rc=$?
    if [ "$chaos_rc" -ne 0 ]; then
        echo "lint.sh: chaos drill FAILED (rc=$chaos_rc)" >&2
        rc=1
    fi
fi

# Fleet drill + micro-bench (tools/fleet_bench.py --quick): every fleet
# chaos scenario (replica death/WAL handoff, hedged failover, retry
# storm, double-claim race) run twice under one seed — invariant-clean
# and byte-equal — plus a 2-replica in-process micro-bench; lands
# fleet_invariant_violations / fleet_rps in runs.jsonl (charted, never
# gated by bench_compare — the drill's own exit code is the gate).
# FLEET=0 skips (~1 min on the 1-core box); the full subprocess scaling
# bench + kill -9 leg is `python tools/fleet_bench.py` and the committed
# ARTIFACT_fleet_bench.json.
if [ "${FLEET:-1}" != "0" ]; then
    echo "== fleet drill =="
    python tools/fleet_bench.py --quick
    fleet_rc=$?
    if [ "$fleet_rc" -ne 0 ]; then
        echo "lint.sh: fleet drill FAILED (rc=$fleet_rc)" >&2
        rc=1
    fi
fi

# Sweep resume drill (tools/sweep_resume_drill.py --quick): a REAL
# kill -9 against a journaled-sweep subprocess (parallel/journal.py) —
# completed chunks must never recompute, the resumed journal must replay
# bit-equal rows with zero dispatches, zero invariant violations; lands
# resume_invariant_violations / resume_recomputed_chunks in runs.jsonl
# (charted, never gated by bench_compare — the drill's own exit code is
# the gate).  RESUME=0 skips (~20 s on the 1-core box); the full-scale
# artifact run is `python tools/sweep_resume_drill.py` and the committed
# ARTIFACT_resume_sweep.json.
if [ "${RESUME:-1}" != "0" ]; then
    echo "== sweep resume drill =="
    python tools/sweep_resume_drill.py --quick
    resume_rc=$?
    if [ "$resume_rc" -ne 0 ]; then
        echo "lint.sh: sweep resume drill FAILED (rc=$resume_rc)" >&2
        rc=1
    fi
fi

# Mesh-sweep smoke (tools/mesh_sweep_bench.py --quick): a small fault
# grid dispatched through the mesh-partitioned sweep executable
# (parallel/partition.py) on the 8-virtual-device CPU mesh — rows must be
# bit-equal to the single-device path and compile exactly ONE executable;
# lands sweep_points_per_s in runs.jsonl where bench_compare gates it
# higher-is-better.  MESH_SWEEP=0 skips (~1 min of compile on this box);
# the full-scale artifact run is `python tools/mesh_sweep_bench.py`.
if [ "${MESH_SWEEP:-1}" != "0" ]; then
    echo "== mesh sweep smoke =="
    python tools/mesh_sweep_bench.py --quick
    mesh_rc=$?
    if [ "$mesh_rc" -ne 0 ]; then
        echo "lint.sh: mesh sweep smoke FAILED (rc=$mesh_rc)" >&2
        rc=1
    fi
fi

# Tick-engine smoke (tools/tick_bench.py --quick): the multi-seed
# Monte Carlo tick executable (parallel/sweep.multi_seed_fn) vs the
# vmapped and sequential dispatch arms on a small grid — rows must be
# bit-equal (exact sampler) and compile exactly ONE executable; lands
# tick_rounds_per_s in runs.jsonl where bench_compare gates it
# higher-is-better.  TICK=0 skips (~1 min of compile on this box); the
# full-scale artifact run is `python tools/tick_bench.py` and the
# committed ARTIFACT_tick_bench.json.
if [ "${TICK:-1}" != "0" ]; then
    echo "== tick bench smoke =="
    python tools/tick_bench.py --quick
    tick_rc=$?
    if [ "$tick_rc" -ne 0 ]; then
        echo "lint.sh: tick bench smoke FAILED (rc=$tick_rc)" >&2
        rc=1
    fi
fi

# Topology smoke (tools/topo_bench.py --quick): the sparse-axis
# correctness pins — kregular(k=N-1) bit-equal to dense per protocol,
# committee C=1 contains the flat metrics — plus one genuinely sparse
# kregular rung compiled and run end to end (ops/gatherdeliv.py).  The
# full-scale ladder (10k/100k/1M + the dense-vs-sparse 10k ratio) is
# `python tools/topo_bench.py` and the committed ARTIFACT_topo_scale.json;
# the ladder/committee topo_* series gate in bench_compare against the
# committed BENCH_BASELINES.json pins.  TOPO=0 skips (~1 min of small
# compiles on this box).
if [ "${TOPO:-1}" != "0" ]; then
    echo "== topo smoke =="
    python tools/topo_bench.py --quick
    topo_rc=$?
    if [ "$topo_rc" -ne 0 ]; then
        echo "lint.sh: topo smoke FAILED (rc=$topo_rc)" >&2
        rc=1
    fi
fi

# Sharded-topology smoke (tools/shard_topo_bench.py --quick): the
# mesh-sharded overlay pins — sharded kregular/committee bit-equal to the
# single-device PR 15 programs on a 2-device mesh (uneven n and the
# mesh-size-1 identity arm included), ONE registry entry across fault
# counts — plus one sharded rung over the full 8-virtual-device mesh;
# lands shard_topo_ticks_per_s in runs.jsonl where bench_compare gates it
# higher-is-better (the full run's shard_topo_full_* series stays
# chart-only so smoke and full scales never mix).  SHARD_TOPO=0 skips
# (~1 min of small compiles on this box); the full-scale run is `python
# tools/shard_topo_bench.py` and the committed ARTIFACT_shard_topo.json.
if [ "${SHARD_TOPO:-1}" != "0" ]; then
    echo "== shard topo smoke =="
    python tools/shard_topo_bench.py --quick
    shard_topo_rc=$?
    if [ "$shard_topo_rc" -ne 0 ]; then
        echo "lint.sh: shard topo smoke FAILED (rc=$shard_topo_rc)" >&2
        rc=1
    fi
fi

# Gather-locality smoke (tools/gather_locality_bench.py --quick): the
# shard-local exchange contract read straight off the post-SPMD HLO —
# the kregular overlay program compiled under BOTH data-movement layouts
# on the 8-virtual-device mesh, demanding the exchange layout carry ZERO
# all-gathers (prologue bytes/device reduced >= (D-1)/D vs the regather
# layout, all-to-all islands only); lands gather_prologue_reduction in
# runs.jsonl (charted; the bench's own exit code is the gate).  GATHER=0
# skips (~1 min of compiles on this box); the full-scale run (4M rung +
# ticks/s ratio + 10M aval math) is `python tools/gather_locality_bench.py`
# and the committed ARTIFACT_gather_locality.json.
if [ "${GATHER:-1}" != "0" ]; then
    echo "== gather locality smoke =="
    python tools/gather_locality_bench.py --quick
    gather_rc=$?
    if [ "$gather_rc" -ne 0 ]; then
        echo "lint.sh: gather locality smoke FAILED (rc=$gather_rc)" >&2
        rc=1
    fi
fi

# Telemetry report (tools/telemetry_report.py --quick): a real in-process
# fleet drill (router -> replica -> batcher -> dispatch) with spans
# captured — every admitted id must have a closed span tree and the named
# segments must cover >= 95% of one request's wall (utils/telemetry.py);
# lands telemetry_span_miss / telemetry_coverage_pct in runs.jsonl
# (charted, never gated by bench_compare — the report's own exit code is
# the gate).  TELEM=0 skips (~30 s warm on this box); the full run adds
# the serve_bench overhead leg and writes ARTIFACT_telemetry.json.
if [ "${TELEM:-1}" != "0" ]; then
    echo "== telemetry report =="
    python tools/telemetry_report.py --quick
    telem_rc=$?
    if [ "$telem_rc" -ne 0 ]; then
        echo "lint.sh: telemetry report FAILED (rc=$telem_rc)" >&2
        rc=1
    fi
fi

# Consensus observability report (tools/consensus_obs_report.py --quick):
# every protocol x topology combo armed-vs-disarmed (primary metrics must
# stay bit-equal under the exact sampler), monitors clean, the synthetic
# byzantine forge must fire, forensics must localize, and the armed
# overhead must stay <= 5% on the tick path + serve flush; lands
# consobs_overhead_pct / consobs_invariant_violations in runs.jsonl
# (charted, never gated by bench_compare — the report's own exit code is
# the gate).  CONSOBS=0 skips; the full run writes ARTIFACT_consobs.json.
if [ "${CONSOBS:-1}" != "0" ]; then
    echo "== consensus obs report =="
    python tools/consensus_obs_report.py --quick
    consobs_rc=$?
    if [ "$consobs_rc" -ne 0 ]; then
        echo "lint.sh: consensus obs report FAILED (rc=$consobs_rc)" >&2
        rc=1
    fi
fi

# Adaptive-query drill (tools/query_drill.py --quick): the bisection
# engine vs its dense grid (identical boundary, bit-equal rows under the
# exact sampler) plus a subprocess SIGKILL mid-search whose resume must
# serve every completed generation from the journal (0 recomputed
# steps); lands query_dispatch_savings_x / query_invariant_violations in
# runs.jsonl (charted, never gated by bench_compare — the drill's own
# exit code is the gate).  QUERY=0 skips; the full run writes
# ARTIFACT_query.json.
if [ "${QUERY:-1}" != "0" ]; then
    echo "== query drill =="
    python tools/query_drill.py --quick
    query_rc=$?
    if [ "$query_rc" -ne 0 ]; then
        echo "lint.sh: query drill FAILED (rc=$query_rc)" >&2
        rc=1
    fi
fi

echo "== bench_compare =="
if [ -n "${BLOCKSIM_RUNS_JSONL:-}" ] && [ -f "${BLOCKSIM_RUNS_JSONL}" ]; then
    python tools/bench_compare.py --runs "${BLOCKSIM_RUNS_JSONL}" "$@"
else
    python tools/bench_compare.py "$@"
fi
bench_rc=$?
if [ "$bench_rc" -ne 0 ]; then
    echo "lint.sh: bench_compare FAILED (rc=$bench_rc)" >&2
    rc=1
fi

# Cold-vs-warm compile check (tools/warm_bench.sh): two bench.py rehearsals
# against jax's persistent compile cache — $JAX_COMPILATION_CACHE_DIR when
# set, else the fixed <repo>/.jax_cache; fails when the second run adds
# cache entries, or when the first run was cold and the second's compile_s
# does not improve.  Scaled down here (2000 nodes, 200 rounds) so the gate
# stays cheap; WARM_BENCH=0 skips (the test-suite smoke does).
if [ "${WARM_BENCH:-1}" != "0" ]; then
    echo "== warm_bench =="
    WARM_BENCH_N="${WARM_BENCH_N:-2000}" \
    WARM_BENCH_ROUNDS="${WARM_BENCH_ROUNDS:-200}" \
    WARM_BENCH_OUT="${WARM_BENCH_OUT:-$(mktemp /tmp/warm_bench.XXXXXX.json)}" \
        bash tools/warm_bench.sh
    warm_rc=$?
    if [ "$warm_rc" -ne 0 ]; then
        echo "lint.sh: warm_bench FAILED (rc=$warm_rc)" >&2
        rc=1
    fi
fi

exit $rc
