"""Reduce a profiler trace of multi-Raft under a crash schedule (C
independent Raft groups as one committee stack, every group's leader killed
again and again: ``topo/committee.py`` around ``models/raft.step`` with terms
and ``FaultConfig.crashes``) for the readers of the cells the
``raftcrash_solo`` driver drives.

The parser and the reduction are ``committee_trace.summarize``'s (a
*tile-tick*, one tick of one tile with all its lanes, is the unit, counted
from the trace itself; "under a scope" and "innermost" as defined there),
called with this tier's scope prefixes: ``topo.``, ``raft.``, ``ops.``,
``gate.``.  A tile-tick is split by the Raft tick's phases as
``raftgroups_trace.py`` splits it (an operation belongs to the first scope on
its path after the committee tier's own), grouped here into what this
deployment runs: the fault phase (``raft.tick.fault``: the kill and the
restart at the head of the tick, behind their gate, and the per-crash records
and oracles at its end), the election (``vote_rx``, ``vote_reply_rx``,
``timer_vote``, ``term``) and the heartbeat (``heartbeat_rx``, ``ack_rx``,
``timer_heartbeat``: failure detection; a leader of this deployment rarely
lives to propose).  The whole tile-tick is the device's BUSY time in the
window over the tile-ticks (as ``tick_step_us.*`` divide it): the time under
``topo.committee.tile`` leaves out the loops' own copies, which carry no
scope.

A trace of a program without these scopes reduces to empty tables; the
readers in ``layer_metrics/`` then return nothing.

    python benchmark/raftcrash_trace.py <trace dir or .xplane.pb[.gz]>

prints the table ``PERF.md`` section 5 is written from.
"""

from __future__ import annotations

import statistics
import sys

import raftgroups_trace

DRIVER = "raftcrash_solo"
FAULT = ("raft.tick.fault",)
ELECTION = raftgroups_trace.ELECTION
HEARTBEAT = raftgroups_trace.REPLICATION
# the same parser, reduction and scope prefixes as multi-Raft without faults
summarize = raftgroups_trace.summarize


def of_run(run: dict):
    """The reduction of a traced run of a cell this driver drives, made once
    for all the readers of a process; ``None`` when the run was not traced,
    another driver ran it, or the trace cannot be reduced (said on stderr: a
    reader returns nothing, it does not raise)."""
    if run["traffic"].get("driver") != DRIVER or not run.get("trace"):
        return None
    if "_raftcrash_trace" not in run:
        try:
            run["_raftcrash_trace"] = summarize(run["trace"]["path"])
        except Exception as e:
            print(f"raftcrash_trace: {type(e).__name__}: {e}", file=sys.stderr)
            run["_raftcrash_trace"] = None
    return run["_raftcrash_trace"]


def _per_tile_tick_us(run: dict, seconds):
    """``seconds(reduction)`` per tile-tick, in us; nothing where the trace
    has no tile scope (its tile-ticks cannot then be counted) or nothing to
    sum."""
    t = of_run(run)
    if not t or not t["tile_ticks"]:
        return None
    got = seconds(t)
    if not got:
        return None
    return sum(got) / t["tile_ticks"] * 1e6


def busy_tick_us(run: dict):
    """The device's busy time inside the traced window per tile-tick."""
    return _per_tile_tick_us(run, lambda t: [t["busy_s"]])


def phases_us(run: dict, phases: tuple):
    """Device self time under the given phases of the Raft tick."""
    return _per_tile_tick_us(run, lambda t: [
        t["by_phase_s"][p] for p in phases if p in t["by_phase_s"]])


def inner_us(run: dict, prefix: str):
    """Device self time whose innermost scope starts with ``prefix``."""
    return _per_tile_tick_us(run, lambda t: [
        v for k, v in t["by_inner_s"].items() if k.startswith(prefix)])


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
    driver's difference of ``telemetry.metrics.snapshot()``); nothing where
    the program has neither."""
    if run["traffic"].get("driver") != DRIVER:
        return None
    got = run["window"].get("counters") or {}
    if not got.get(den) or num not in got:
        return None
    return got[num] / got[den]


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
    if s["tile_ticks"]:
        s["busy_us_per_tile_tick"] = s["busy_s"] / s["tile_ticks"] * 1e6
    print(json.dumps(s, indent=1))
