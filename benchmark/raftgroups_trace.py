"""Reduce a profiler trace of multi-Raft (C independent Raft groups as one
committee stack, ``topo/committee.py`` around ``models/raft.step`` with
terms) for the readers of the cells the ``raftgroups_solo`` driver drives.

The parser and the reduction are ``committee_trace.summarize``'s (a
*tile-tick*, one tick of one tile with all its lanes, is the unit, counted
from the trace itself; "under a scope" and "innermost" as defined there),
called with this tier's scope prefixes: ``topo.``, ``raft.``, ``ops.``,
``gate.``.  What is added here is the split of a tile-tick by the Raft
tick's phases: an operation belongs to the first scope on its path after
the committee tier's own, ``raft.tick.<phase>`` (``summarize``'s
``by_phase_s``), and the phases group into the election (``vote_rx``,
``vote_reply_rx``, ``timer_vote`` and ``term``: what terms add) and the
replication (``heartbeat_rx``, ``ack_rx``, ``timer_heartbeat``).

A trace of a program without these scopes reduces to empty tables; the
readers in ``layer_metrics/`` then return nothing.

    python benchmark/raftgroups_trace.py <trace dir or .xplane.pb[.gz]>

prints the table ``PERF.md`` section 5 is written from.
"""

from __future__ import annotations

import statistics
import sys

import committee_trace

SCOPE_PREFIXES = ("topo.", "raft.", "ops.", "gate.")
SPAN_PREFIXES = ("topo.",)
DRIVER = "raftgroups_solo"
ELECTION = ("raft.tick.vote_rx", "raft.tick.vote_reply_rx",
            "raft.tick.timer_vote", "raft.tick.term")
REPLICATION = ("raft.tick.heartbeat_rx", "raft.tick.ack_rx",
               "raft.tick.timer_heartbeat")


def summarize(trace_dir_or_file: str) -> dict:
    return committee_trace.summarize(trace_dir_or_file, SCOPE_PREFIXES,
                                     SPAN_PREFIXES)


def of_run(run: dict):
    """The reduction of a traced run of a cell this driver drives, made once
    for all the readers of a process; ``None`` when the run was not traced,
    another driver ran it, or the trace cannot be reduced (said on stderr: a
    reader returns nothing, it does not raise)."""
    if run["traffic"].get("driver") != DRIVER or not run.get("trace"):
        return None
    if "_raftgroups_trace" not in run:
        try:
            run["_raftgroups_trace"] = summarize(run["trace"]["path"])
        except Exception as e:
            print(f"raftgroups_trace: {type(e).__name__}: {e}", file=sys.stderr)
            run["_raftgroups_trace"] = None
    return run["_raftgroups_trace"]


def tile_tick_us(run: dict):
    """Device self time under ``topo.committee.tile`` per tile-tick."""
    t = of_run(run)
    if not t or not t["tile_ticks"] or t["under_tile_s"] <= 0:
        return None
    return t["under_tile_s"] / t["tile_ticks"] * 1e6


def phases_us(run: dict, phases: tuple):
    """Device self time under the given phases of the Raft tick, per
    tile-tick; nothing where the trace holds none of them."""
    t = of_run(run)
    if not t or not t["tile_ticks"]:
        return None
    got = [t["by_phase_s"][p] for p in phases if p in t["by_phase_s"]]
    if not got:
        return None
    return sum(got) / t["tile_ticks"] * 1e6


def inner_us(run: dict, prefix: str):
    """Device self time whose innermost scope starts with ``prefix``, per
    tile-tick."""
    t = of_run(run)
    if not t or not t["tile_ticks"]:
        return None
    got = [v for k, v in t["by_inner_s"].items() if k.startswith(prefix)]
    if not got:
        return None
    return sum(got) / t["tile_ticks"] * 1e6


def scoped_pct(run: dict):
    t = of_run(run)
    if not t or t["busy_s"] <= 0 or t["scoped_s"] <= 0:
        return None
    return 100.0 * t["scoped_s"] / t["busy_s"]


def span_median_ms(run: dict, name: str):
    t = of_run(run)
    got = (t or {}).get("spans", {}).get(name)
    if not got:
        return None
    return statistics.median(s["dur_s"] * 1e3 for s in got)


def counter_ratio(run: dict, num: str, den: str):
    """One of the program's counters over another, over the window (the
    driver's difference of ``telemetry.metrics.snapshot()``)."""
    if run["traffic"].get("driver") != DRIVER:
        return None
    got = run["window"].get("counters") or {}
    if not got.get(den):
        return None
    return got.get(num, 0.0) / got[den]


if __name__ == "__main__":
    import json

    s = summarize(sys.argv[1])
    s["spans"] = {k: {"n": len(v), "median_ms": statistics.median(
        x["dur_s"] for x in v) * 1e3, "stats": v[0]["stats"]}
        for k, v in s["spans"].items()}
    for k in ("by_inner_s", "by_phase_s"):
        s[k] = dict(sorted(s[k].items(), key=lambda kv: -kv[1]))
        if s["tile_ticks"]:
            s[k.replace("_s", "_us_per_tile_tick")] = {
                n: v / s["tile_ticks"] * 1e6 for n, v in s[k].items()}
    print(json.dumps(s, indent=1))
