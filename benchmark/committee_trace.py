"""Reduce a profiler trace of the committee tier (``topo/committee.py``): the
device time of a tile-tick under the program's ``topo.committee.tile`` scope,
the ops' shares of it, and the host spans ``topo.committee.readback`` /
``.outer``.

``program_trace.py`` and ``scope_table.py`` hold their scope prefixes as
module constants that do not know ``topo.`` or ``gate.``; ``mesh_trace.py``
parses the same file with prefixes as ARGUMENTS.  This module is that
parser (``mesh_trace.load`` / ``with_callers``, ``xplane.self_times``) with
the committee tier's prefixes and a reduction of its own: one device plane,
and a *tile-tick* as the unit.

**A tile-tick** is one tick of one tile, all its lanes: the stack runs tile
after tile, each a scan of ``ticks`` ticks over T committees.  The tile-ticks
in the traced window are counted from the trace itself, as ``mesh_trace``
counts ticks: every HLO instruction of the tile scan's body runs at most
once a tile-tick and most run on every one, so the largest number of events
any one instruction under ``topo.committee.tile`` has inside the window is
the number of tile-ticks the device made there.  The window then need not
hold whole runs.

**Under a scope** = anywhere on the operation's scope path, where an
operation without an ``op_name`` of its own (a fusion the compiler stripped,
a ``conditional``) takes the path of the event that encloses it in time
(``mesh_trace.with_callers``).  ``while`` loops carry no ``op_name`` on the
device plane and enclose everything, so the loops' own time (launch gaps,
carry copies) stays outside every scope: it is in ``busy_s`` and shows as
the gap between ``device_scoped_pct.committee`` and 100.  **Innermost** =
the last program scope on an operation's OWN path: the op that owns it.

A trace of a program without these scopes or spans (the parent of the PR
that added them) reduces to empty tables; the readers in ``layer_metrics/``
then return nothing.

    python benchmark/committee_trace.py <trace dir or .xplane.pb[.gz]>

prints the table ``PERF.md`` section 5 is written from.
"""

from __future__ import annotations

import os
import statistics
import sys

import mesh_trace
import xplane

SCOPE_PREFIXES = ("topo.", "pbft.", "ops.", "gate.")
SPAN_PREFIXES = ("topo.",)
TILE_SCOPE = "topo.committee.tile"
STACK_SCOPE = "topo.committee.stack"
UNSCOPED = "(no program scope)"
DRIVER = "committee_solo"


def summarize(trace_dir_or_file: str, scope_prefixes=SCOPE_PREFIXES,
              span_prefixes=SPAN_PREFIXES) -> dict:
    path = trace_dir_or_file if os.path.isfile(trace_dir_or_file) \
        else xplane.newest_xplane(trace_dir_or_file)
    raw = mesh_trace.load(path, scope_prefixes, span_prefixes)
    planes = sorted(raw["devices"])[:1]
    if not planes:
        raise ValueError(f"{path}: no TPU device plane")
    ops = raw["devices"][planes[0]]["ops"]
    if raw["window"]:
        w0, w1 = raw["window"]
    else:  # a trace taken outside the harness: everything it holds
        w0, w1 = min(e[3] for e in ops), max(e[4] for e in ops)
    inside = [e for e in ops if min(e[4], w1) > max(e[3], w0)]
    busy = xplane.Busy(xplane.merge(
        [(max(a, w0), min(b, w1)) for _, _, _, a, b in inside])
    ).covered(w0, w1)
    counts: dict = {}
    for instr, scopes, _, a, _ in inside:
        if a >= w0 and TILE_SCOPE in scopes:
            counts[instr] = counts.get(instr, 0) + 1
    table = xplane.self_times(mesh_trace.with_callers(inside), w0, w1)
    by_inner: dict = {}
    by_phase: dict = {}
    scoped = under_tile = under_stack = 0.0
    for (own, eff, _, _), ns in table.items():
        inner = own[-1] if own else UNSCOPED
        by_inner[inner] = by_inner.get(inner, 0.0) + ns
        # the engine phase: the first scope after the committee tier's own
        phase = next((s for s in eff if not s.startswith("topo.")), UNSCOPED)
        by_phase[phase] = by_phase.get(phase, 0.0) + ns
        if own:
            scoped += ns
        if TILE_SCOPE in eff:
            under_tile += ns
        if STACK_SCOPE in eff:
            under_stack += ns
    spans: dict = {}
    for name, a, b, stats in sorted(raw["host"], key=lambda e: e[1]):
        if a < w0 or b > w1:
            continue  # only a span wholly inside the window is a full account
        spans.setdefault(name, []).append(
            {"start_s": (a - w0) / 1e9, "dur_s": (b - a) / 1e9, "stats": stats})
    sec = lambda d: {k: v / 1e9 for k, v in d.items()}  # noqa: E731
    return {
        "path": path, "device": planes[0], "window_s": (w1 - w0) / 1e9,
        "busy_s": busy / 1e9, "scoped_s": scoped / 1e9,
        "tile_ticks": max(counts.values(), default=0),
        "under_tile_s": under_tile / 1e9, "under_stack_s": under_stack / 1e9,
        "by_inner_s": sec(by_inner), "by_phase_s": sec(by_phase),
        "events": len(inside), "spans": spans,
    }


# ------------------------------------------------- what the readers share ---


def of_run(run: dict):
    """The reduction of a traced run of a cell the ``committee_solo`` driver
    drives, made once for all the readers of a process; ``None`` when the
    run was not traced, another driver ran it, or the trace cannot be
    reduced (said on stderr: a reader returns nothing, it does not raise)."""
    if run["traffic"].get("driver") != DRIVER or not run.get("trace"):
        return None
    if "_committee_trace" not in run:
        try:
            run["_committee_trace"] = summarize(run["trace"]["path"])
        except Exception as e:
            print(f"committee_trace: {type(e).__name__}: {e}", file=sys.stderr)
            run["_committee_trace"] = None
    return run["_committee_trace"]


def tile_tick_us(run: dict):
    """Device self time under ``topo.committee.tile`` per tile-tick."""
    t = of_run(run)
    if not t or not t["tile_ticks"] or t["under_tile_s"] <= 0:
        return None
    return t["under_tile_s"] / t["tile_ticks"] * 1e6


def inner_us(run: dict, prefix: str):
    """Device self time whose innermost scope starts with ``prefix``, per
    tile-tick; nothing where the program has no tile scope (its tile-ticks
    cannot then be counted)."""
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


def tile_lanes(run: dict):
    """Lanes a tile by the program's own counters over the window:
    ``committee.tile_lanes`` over ``committee.tiles`` (lanes run, padding
    included, over tiles run)."""
    if run["traffic"].get("driver") != DRIVER:
        return None
    got = run["window"].get("counters") or {}
    tiles = got.get("committee.tiles")
    if not tiles:
        return None
    return got.get("committee.tile_lanes", 0.0) / tiles


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
