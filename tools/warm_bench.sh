#!/usr/bin/env bash
# Cold-vs-warm compile check: run bench.py TWICE against jax's persistent
# compilation cache and emit ARTIFACT_warm_bench.json recording both
# compile_s values and the cache's entry counts.  The cache is where
# $JAX_COMPILATION_CACHE_DIR says when it is set, else the fixed
# <repo>/.jax_cache every entry point uses (utils/aotcache.enable_xla_cache)
# — never a temp dir of this script's own: a cache that moves never hits.
# The second run must add NO cache entries, and where the first run was cold
# (it added entries) its compile_s must beat the first's.  For a guaranteed
# cold first run point $JAX_COMPILATION_CACHE_DIR at an empty directory.
#
# This is a rehearsal of the cache plumbing on whatever platform
# $JAX_PLATFORMS names (default cpu): compile_s is a set-up fact, and off the
# chip bench.py prints no value (exit code 3, accepted here).
#
# Chained after the lint + bench_compare gates by tools/lint.sh (skip with
# WARM_BENCH=0).  Env knobs:
#   WARM_BENCH_N       cluster size        (default 10000)
#   WARM_BENCH_ROUNDS  consensus rounds    (default 2000)
#   WARM_BENCH_OUT     artifact path       (default ARTIFACT_warm_bench.json)
set -u
REPO="$(cd "$(dirname "$0")/.." && pwd)"
cd "$REPO"

N="${WARM_BENCH_N:-10000}"
ROUNDS="${WARM_BENCH_ROUNDS:-2000}"
OUT="${WARM_BENCH_OUT:-$REPO/ARTIFACT_warm_bench.json}"
CACHE="${JAX_COMPILATION_CACHE_DIR:-$REPO/.jax_cache}"

entries() { ls -A "$CACHE" 2>/dev/null | wc -l; }

run_bench() {
    # no companion, so each run pays exactly one compile stage and the
    # cold/warm comparison is one executable's story
    JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
    BENCH_N="$N" BENCH_ROUNDS="$ROUNDS" BENCH_ROUNDS_SER=0 \
    python bench.py
    local rc=$?
    [ "$rc" -eq 0 ] || [ "$rc" -eq 3 ]  # 3 = rehearsal off the chip
}

e0="$(entries)"
echo "warm_bench: first run (N=$N, rounds=$ROUNDS, cache=$CACHE, $e0 entries)" >&2
cold_line="$(run_bench)" || { echo "warm_bench: first run failed" >&2; exit 1; }
e1="$(entries)"
echo "warm_bench: second run ($e1 entries)" >&2
warm_line="$(run_bench)" || { echo "warm_bench: second run failed" >&2; exit 1; }
e2="$(entries)"

COLD="$cold_line" WARM="$warm_line" N="$N" ROUNDS="$ROUNDS" CACHE="$CACHE" \
E0="$e0" E1="$e1" E2="$e2" OUT="$OUT" python - <<'EOF'
import json
import os

cold = json.loads(os.environ["COLD"].strip().splitlines()[-1])
warm = json.loads(os.environ["WARM"].strip().splitlines()[-1])
e0, e1, e2 = (int(os.environ[k]) for k in ("E0", "E1", "E2"))
cs, ws = cold.get("compile_s"), warm.get("compile_s")
rec = {
    "metric": "warm_bench_compile_s",
    "n": int(os.environ["N"]),
    "rounds": int(os.environ["ROUNDS"]),
    "cache_dir": os.environ["CACHE"],
    "cache_entries": {"before": e0, "after_first": e1, "after_second": e2},
    "device": cold.get("device"),
    "cold": {"compile_s": cs, "rounds": cold.get("rounds")},
    "warm": {"compile_s": ws, "rounds": warm.get("rounds")},
    "first_run_was_cold": e1 > e0,
}
with open(os.environ["OUT"], "w") as f:
    json.dump(rec, f, indent=1)
    f.write("\n")
print(json.dumps(rec))
ok = cs is not None and ws is not None and e2 == e1 and (e1 == e0 or ws < cs)
raise SystemExit(0 if ok else 1)
EOF
rc=$?
if [ "$rc" -ne 0 ]; then
    echo "warm_bench: the second run added cache entries, or its compile_s" \
         "did not improve on a cold first run (see $OUT)" >&2
fi
exit "$rc"
