"""Device time *under* a program scope, for programs whose scopes nest
several deep (``mixed.prefix`` > ``mixed.tick.raft_shards`` >
``raft.tick.vote_rx`` > ``ops.delivery.push_bucket_counts``).

``program_trace.py`` knows the scope prefixes ``pbft.`` and ``ops.`` only and
keys its tables by the outermost and the innermost scope of a path.  The
mixed deployment's scopes start with ``mixed.`` and ``raft.`` as well, and
its readers ask for the time under a scope wherever it sits on the path.
This module reads the same file with ``program_trace``'s parser
(``_xspace_class``), window and whole-run rules (``_main_runs``) and
``xplane``'s self times, under its own prefix set, and changes nothing inside
either module.  (That the prefix set should be data is PERF.md section 7a.)

A trace of a program without such scopes reduces to empty tables; the readers
in ``layer_metrics/`` then return nothing.

    python benchmark/scope_table.py <trace dir or .xplane.pb[.gz]>

prints the table ``PERF.md`` section 5 is written from.
``tests/test_scope_table.py`` checks the reduction on
``fixtures/mixed_small.xplane.pb.gz``.
"""

from __future__ import annotations

import bisect
import gzip
import os
import re
import sys

import program_trace
import xplane

SCOPE_PREFIXES = ("mixed.", "raft.", "pbft.", "ops.")
_SCOPE = re.compile(
    r"(?:^|[/(])((?:%s)[A-Za-z0-9_.]+)" % "|".join(
        re.escape(p) for p in SCOPE_PREFIXES))


def scope_path(op_name: str) -> tuple:
    """The program scopes on an HLO ``op_name`` path, outermost first."""
    return tuple(_SCOPE.findall(op_name or ""))


def load(path: str) -> dict:
    """``{"devices": {plane: {"ops": [(scopes, start_ns, end_ns)],
    "modules": [(name, start_ns, end_ns)]}}, "window": (w0, w1) | None}``."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = program_trace._xspace_class()()
        space.ParseFromString(f.read())
    text = program_trace._text
    devices: dict = {}
    window = None
    for plane in space.planes:
        pname = text(plane.name)
        names = {e.key: text(e.value.name) for e in plane.event_metadata}
        if pname.startswith("/host:CPU"):
            want = {k for k, n in names.items() if n == xplane.WINDOW}
            for line in plane.lines:
                for e in line.events:
                    if e.metadata_id in want and window is None:
                        t0 = line.timestamp_ns + e.offset_ps / 1e3
                        window = (t0, t0 + e.duration_ps / 1e3)
            continue
        if not pname.startswith("/device:TPU:"):
            continue
        stat_names = {e.key: text(e.value.name) for e in plane.stat_metadata}
        scopes_of = {}
        for e in plane.event_metadata:
            tf_op = ""
            for s in e.value.stats:
                if stat_names.get(s.metadata_id) == "tf_op":
                    tf_op = (text(s.str_value) if s.str_value
                             else stat_names.get(s.ref_value, ""))
            scopes_of[e.key] = scope_path(tf_op)
        dev = devices.setdefault(pname, {"ops": [], "modules": []})
        for line in plane.lines:
            lname, t0 = text(line.name), line.timestamp_ns
            if lname == "XLA Ops":
                dev["ops"].extend(
                    (scopes_of.get(e.metadata_id, ()), t0 + e.offset_ps / 1e3,
                     t0 + (e.offset_ps + e.duration_ps) / 1e3)
                    for e in line.events)
            elif lname == "XLA Modules":
                dev["modules"].extend(
                    (names.get(e.metadata_id, "?").split("(")[0],
                     t0 + e.offset_ps / 1e3,
                     t0 + (e.offset_ps + e.duration_ps) / 1e3)
                    for e in line.events)
    return {"devices": devices, "window": window}


def _under(table: dict) -> dict:
    """A self-time table keyed by whole scope paths -> seconds under each
    scope, wherever on a path it sits (a path counts once for a scope)."""
    out: dict = {}
    for path, ns in table.items():
        for scope in set(path):
            out[scope] = out.get(scope, 0.0) + ns / 1e9
    return out


def summarize(trace_dir_or_file: str) -> dict:
    path = trace_dir_or_file if os.path.isfile(trace_dir_or_file) \
        else xplane.newest_xplane(trace_dir_or_file)
    raw = load(path)
    planes = sorted(raw["devices"])
    if not planes:
        raise ValueError(f"{path}: no TPU device plane")
    dev = raw["devices"][planes[0]]
    if raw["window"]:
        w0, w1 = raw["window"]
    else:  # a trace taken outside the harness: everything it holds
        w0 = min(a for _, a, _ in dev["ops"])
        w1 = max(b for _, _, b in dev["ops"])
    table = xplane.self_times(dev["ops"], w0, w1)
    busy = xplane.Busy(xplane.merge(
        [(max(a, w0), min(b, w1)) for _, a, b in dev["ops"]
         if min(b, w1) > max(a, w0)])).covered(w0, w1)
    main, runs = program_trace._main_runs(dev["modules"], w0, w1)
    ordered = sorted(dev["ops"], key=lambda e: e[1])
    starts = [e[1] for e in ordered]
    run_table: dict = {}
    for a, b in runs:
        inside = ordered[bisect.bisect_left(starts, a):
                         bisect.bisect_right(starts, b)]
        for k, ns in xplane.self_times(inside, a, b).items():
            run_table[k] = run_table.get(k, 0.0) + ns
    return {
        "path": path, "window_s": (w1 - w0) / 1e9, "busy_s": busy / 1e9,
        "scoped_s": sum(ns for p, ns in table.items() if p) / 1e9,
        "under_s": _under(table), "main_module": main,
        "main_runs": len(runs), "main_runs_s": sum(b - a for a, b in runs) / 1e9,
        "runs_busy_s": sum(run_table.values()) / 1e9,
        "runs_under_s": _under(run_table),
        "runs_by_path_s": {"/".join(p) or program_trace.UNSCOPED: ns / 1e9
                           for p, ns in run_table.items()},
    }


# ------------------------------------------------- what the readers share ---


def for_driver(run: dict, driver: str):
    """The reduction of a traced run of a cell that ``driver`` drives, made
    once for all the readers of a process; ``None`` when the run was not
    traced, another driver ran it, or the trace cannot be reduced (said on
    stderr: a reader returns nothing, it does not raise)."""
    if run["traffic"].get("driver") != driver or not run.get("trace"):
        return None
    if "_scope_table" not in run:
        try:
            run["_scope_table"] = summarize(run["trace"]["path"])
        except Exception as e:
            print(f"scope_table: {type(e).__name__}: {e}", file=sys.stderr)
            run["_scope_table"] = None
    return run["_scope_table"]


def under_per_step_us(run: dict, driver: str, scope: str, steps_key: str):
    """Device self time under ``scope`` inside the whole runs of the main
    program, over the steps those runs made (``run["setup"][steps_key]`` a
    run); nothing where the program has no such scope."""
    t = for_driver(run, driver)
    steps = run["setup"].get(steps_key)
    if not t or not t["main_runs"] or not steps \
            or scope not in t["runs_under_s"]:
        return None
    return t["runs_under_s"][scope] / (t["main_runs"] * steps) * 1e6


def under_pct(run: dict, driver: str, scope: str, family: str):
    """Share of the device's busy time that lies under ``scope``; nothing
    where the program has no scope of ``family`` at all (0.0 then means the
    scope never ran, not that it does not exist)."""
    t = for_driver(run, driver)
    if not t or t["busy_s"] <= 0 \
            or not any(k.startswith(family) for k in t["under_s"]):
        return None
    return 100.0 * t["under_s"].get(scope, 0.0) / t["busy_s"]


def scoped_pct(run: dict, driver: str):
    """Share of the device's busy time that lies under any program scope."""
    t = for_driver(run, driver)
    if not t or t["busy_s"] <= 0 or t["scoped_s"] <= 0:
        return None
    return 100.0 * t["scoped_s"] / t["busy_s"]


if __name__ == "__main__":
    import json

    s = summarize(sys.argv[1])
    for k in ("under_s", "runs_under_s", "runs_by_path_s"):
        s[k] = dict(sorted(s[k].items(), key=lambda kv: -kv[1])[:60])
    print(json.dumps(s, indent=1))
