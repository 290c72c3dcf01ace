"""Perf-trajectory tracker: ``BENCH_*.json`` round artifacts (plus an
optional ``runs.jsonl`` from utils/obs.py) become a machine-readable
per-metric history with a regression gate.

Each ``BENCH_rNN.json`` is a driver's record of one round's ``python
bench.py`` run: ``{"n": round, "cmd", "rc", "tail", "parsed"}`` where
``parsed`` is the bench's final JSON line (null when the round produced
none).  This script loads them all, prints a per-metric trajectory table,
and exits nonzero when the newest value regressed beyond ``--threshold``
relative to its predecessor.  An empty history is not an error: no record
is committed until a run on the chip produces one.

The default threshold is deliberately tolerant (50%): a history can mix
machine states, so small swings are environment noise — the gate exists to
catch order-of-magnitude losses, not 5% jitter.

Usage:
    python tools/bench_compare.py [BENCH.json ...] [--runs runs.jsonl]
                                  [--threshold 0.5]

With no positional files, every ``BENCH_*.json`` at the repo root is loaded.
Exit codes: 0 = no regression, 1 = regression beyond threshold, 2 = an
artifact failed to parse.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_bench_file(path: str) -> dict:
    """One BENCH artifact -> one trajectory row (value None for a failed
    round).  Raises on unparseable JSON — the smoke test's contract."""
    with open(path) as f:
        rec = json.load(f)
    parsed = rec.get("parsed")
    row = {
        "source": os.path.basename(path),
        "round": rec.get("n"),
        "rc": rec.get("rc"),
        "metric": None,
        "value": None,
        "backend": None,
    }
    if isinstance(parsed, dict):
        row["metric"] = parsed.get("metric")
        row["value"] = parsed.get("value")
        row["backend"] = parsed.get("backend")
        row["rounds"] = parsed.get("rounds")
        row["wall_s"] = parsed.get("wall_s")
        row["compile_s"] = parsed.get("compile_s")
    return row


def load_runs_jsonl(path: str) -> list[dict]:
    """runs.jsonl records (utils/obs.py finalize) -> trajectory rows.  Rows
    without a (metric, value) pair fall back to the manifest's uniform
    rounds/s keyed by config hash, so plain simulation runs chart too."""
    rows = []
    try:
        f = open(path)
    except OSError:
        return rows
    with f:
        for i, line in enumerate(f):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError:
                continue  # a torn append must not kill the trajectory
            if not isinstance(rec, dict):
                continue
            man = rec.get("manifest") or {}
            metric, value = rec.get("metric"), rec.get("value")
            if metric is None and man.get("rounds_per_s") is not None:
                metric = (
                    f"{man.get('protocol', 'run')}_"
                    f"{man.get('config_hash', 'unknown')}_rounds_per_sec"
                )
                value = man["rounds_per_s"]
            if metric is None:
                continue
            rows.append({
                "source": f"{os.path.basename(path)}:{i + 1}",
                "round": man.get("ts"),
                "rc": 0,
                "metric": metric,
                "value": value,
                "backend": rec.get("backend") or man.get("backend"),
                "rounds": rec.get("rounds"),
                "wall_s": rec.get("wall_s"),
                "compile_s": rec.get("compile_s",
                                     man.get("compile_plus_first_run_s")),
            })
    return rows


def trajectory(rows: list[dict]) -> dict[str, list[dict]]:
    by_metric: dict[str, list[dict]] = {}
    for row in rows:
        if row["metric"] is None:
            by_metric.setdefault("(no result)", []).append(row)
        else:
            by_metric.setdefault(row["metric"], []).append(row)
    return by_metric


# Lower-is-better counters (e.g. jaxlint's "jaxlint_new_findings") are
# charted but never gated here: the drop-means-regression rule below is for
# throughput metrics, and a findings INCREASE already fails the lint gate's
# own exit code — applying the throughput rule would flag *fixing* findings
# as a regression.  Same carve-out for compile_s trajectories: dropping
# compile wall (warm persistent-cache runs, utils/aotcache.py) is the GOAL,
# and the throughput rule would read it as a 10x regression.  The jaxgraph
# per-program cost trajectories ("graph_<program>_gflops"/"_bytes",
# lint/graph) are the same shape: shrinking a program is the goal, and
# growth is already gated against GRAPH_BASELINE.json by the lint.graph
# budget gate — chart, never gate.  Keyed on the "graph_" PREFIX, not the
# unit suffixes: a future bench metric like "peak_rss_bytes", where a drop
# IS meaningful, must stay under the throughput rule.  The chaos drill's
# counters ("chaos_invariant_violations"/"chaos_replay_divergence",
# tools/chaos_drill.py) are the same shape: zero is the goal, any rise
# already fails the drill's own exit code — chart, never gate.
# The durable-sweep series ("journal_*" from mesh_sweep_bench --journal,
# "resume_*" from tools/sweep_resume_drill.py) are the same shape again:
# overhead pct and recompute counts are lower-is-better with their own
# drill/bench exit codes, and a resume replaying MORE rows from the
# journal means a fuller journal, not a regression — chart, never gate.
# The telemetry series ("telemetry_*" from tools/telemetry_report.py —
# span-completeness misses, wall-time coverage pct, overhead pct) follow
# the same rule: the report's own gates are its exit code.
# The topology series ("topo_*" from tools/topo_bench.py — kregular ladder
# ticks/s, committee completion rates) are chart-only by prefix, PROMOTED
# to gated per metric through BENCH_BASELINES.json: a metric with a
# committed baseline row always gates (the baseline is its first
# trajectory point), prefix carve-out or not.  The shard_topo full-run
# series ("shard_topo_full_*" from tools/shard_topo_bench.py) follows the
# topo_ rationale — full-scale rungs vary with --env-n / box state and
# the bench's own acceptance is its exit code — while the smoke-scale
# "shard_topo_ticks_per_s" (lint.sh chain) gates by default.
UNGATED_SUFFIXES = ("_findings", "_compile_s", "_p50_ms")
UNGATED_PREFIXES = ("graph_", "comms_", "chaos_", "fleet_", "journal_",
                    "resume_", "telemetry_", "topo_", "shard_topo_full_",
                    "consobs_", "query_")

# Committed per-metric baselines: the first trajectory row of each listed
# metric, pinned in-repo so a series without a second runs.jsonl sample
# still has a predecessor to gate against.  Committing a baseline is the
# promotion act for an UNGATED_PREFIXES series.
BASELINES = os.path.join(REPO, "BENCH_BASELINES.json")

# Serving latency is lower-is-better AND gated: the serve smoke/bench land
# a p99 trajectory (serve_p99_ms) whose REGRESSION is an increase, so the
# gate inverts for these suffixes — last > (1 + threshold) * prev fails.
# p50 is charted only (the _p50_ms carve-out above): the median moves with
# the max_wait batching knob by design, while a p99 blow-up means the
# serving path itself got slower (KNOWN_ISSUES "batching/latency").
LOWER_IS_BETTER_SUFFIXES = ("_p99_ms",)


def compile_s_rows(rows: list[dict]) -> list[dict]:
    """Derived lower-is-better trajectory: one ``<metric>_compile_s`` row per
    result row that measured its compile stage (bench.py attempts, manifest
    ``compile_plus_first_run_s``).  Charted next to the throughput history,
    excluded from the regression gate by suffix."""
    return [
        dict(r, metric=f"{r['metric']}_compile_s", value=r["compile_s"])
        for r in rows
        if r.get("metric") and isinstance(r.get("compile_s"), (int, float))
    ]


def load_baselines(path: str = BASELINES) -> list[dict]:
    """Committed baseline rows (one per metric), or [] when the file is
    absent.  Each row charts as source ``BENCH_BASELINES.json`` and seeds
    its metric's trajectory, which also GATES the metric regardless of the
    prefix carve-outs (see check_regressions)."""
    try:
        with open(path) as f:
            rec = json.load(f)
    except OSError:
        return []
    return [
        {
            "source": os.path.basename(path),
            "round": None,
            "rc": 0,
            "metric": metric,
            "value": pin.get("value"),
            "backend": pin.get("backend"),
            "rounds": None,
            "wall_s": None,
            "compile_s": None,
        }
        for metric, pin in sorted(rec.get("baselines", {}).items())
    ]


def check_regressions(by_metric: dict, threshold: float,
                      baselined: frozenset = frozenset()) -> list[str]:
    """Newest numeric value vs its predecessor, per metric: regressed when
    ``last < (1 - threshold) * prev`` — inverted for the lower-is-better
    latency suffixes (``last > (1 + threshold) * prev``).  Metrics in
    ``baselined`` (committed BENCH_BASELINES.json pins) gate even under
    the prefix/suffix carve-outs — committing a baseline is the promotion
    act for a chart-only series."""
    failures = []
    for metric, rows in by_metric.items():
        if metric not in baselined and (
            metric.endswith(UNGATED_SUFFIXES)
            or metric.startswith(UNGATED_PREFIXES)
        ):
            continue
        vals = [r["value"] for r in rows if isinstance(r["value"], (int, float))]
        if len(vals) < 2:
            continue
        prev, last = vals[-2], vals[-1]
        if metric.endswith(LOWER_IS_BETTER_SUFFIXES):
            if prev > 0 and last > (1.0 + threshold) * prev:
                failures.append(
                    f"{metric}: {last} vs previous {prev} "
                    f"({last / prev:.1%} of prior; lower-is-better "
                    f"threshold {1 + threshold:.0%})"
                )
            continue
        if prev > 0 and last < (1.0 - threshold) * prev:
            failures.append(
                f"{metric}: {last} vs previous {prev} "
                f"({last / prev:.1%} of prior; threshold "
                f"{1 - threshold:.0%})"
            )
    return failures


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="bench_compare")
    p.add_argument("files", nargs="*",
                   help="BENCH artifacts (default: BENCH_*.json at repo root)")
    p.add_argument("--runs", default=None,
                   help="runs.jsonl manifest log to include (utils/obs.py)")
    p.add_argument("--threshold", type=float, default=0.5,
                   help="fractional drop vs the previous value that counts "
                        "as a regression (default 0.5 = halved)")
    args = p.parse_args(argv)

    files = args.files or sorted(glob.glob(os.path.join(REPO, "BENCH_*.json")))
    rows = []
    for path in files:
        try:
            rows.append(load_bench_file(path))
        except (OSError, json.JSONDecodeError, AttributeError) as e:
            print(f"bench_compare: cannot parse {path}: {e}", file=sys.stderr)
            return 2
    rows.sort(key=lambda r: (r["round"] is None, r["round"]))
    baseline_rows = load_baselines()
    rows = baseline_rows + rows
    if args.runs:
        rows.extend(load_runs_jsonl(args.runs))
    rows.extend(compile_s_rows(rows))

    by_metric = trajectory(rows)
    for metric, mrows in sorted(by_metric.items()):
        print(f"\n{metric}")
        print(f"  {'source':<24} {'round':>8} {'value':>12} "
              f"{'backend':>8} {'rounds':>8} {'wall_s':>9}")
        for r in mrows:
            print(
                f"  {r['source']:<24} {str(r['round']):>8} "
                f"{str(r['value']):>12} {str(r['backend']):>8} "
                f"{str(r.get('rounds')):>8} {str(r.get('wall_s')):>9}"
            )
    failures = check_regressions(
        by_metric, args.threshold,
        frozenset(r["metric"] for r in baseline_rows),
    )
    print()
    if failures:
        for msg in failures:
            print(f"REGRESSION: {msg}")
        return 1
    n_vals = sum(
        1 for rs in by_metric.values()
        for r in rs if isinstance(r["value"], (int, float))
    )
    print(f"ok: {n_vals} measurements across {len(by_metric)} metric(s), "
          f"no regression beyond {args.threshold:.0%}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
