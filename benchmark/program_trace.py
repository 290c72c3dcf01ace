"""Reduce a profiler trace to what the PROGRAM says about itself: device time
by the program's own scope names, its host spans with the device time inside
each, and the device's idle time split by the state of the serving batcher.

``xplane.py`` reads the harness's ``bench.*`` spans and names device work by
HLO instruction (``fusion.160``).  This module reads what the program writes
into the same trace:

- **scopes** — ``jax.named_scope`` names (``pbft.round.commit``,
  ``ops.delay.binom``; the program keeps them in ``SCOPES`` tuples).  On the
  TPU device plane they are in no event name and on no line of their own:
  the line ``XLA Ops`` holds one event per executed HLO operation, and the
  operation's *metadata* (``XEventMetadata.stats``) carries the stat
  ``tf_op``, which is the HLO ``op_name``: the scope path, e.g.
  ``jit(sim_round)/while/body/closed_call/pbft.round.commit/ops.delay.
  sample_bucket_counts/ops.delay.bucket_count_chain/mul``.
  ``jax.profiler.ProfileData`` exposes an event's own stats but not its
  metadata's, so the file is parsed here with ``google.protobuf`` against the
  few fields of the ``XSpace`` schema that are needed (declared below; no
  generated module).  The ``Framework Name Scope`` line that xplane.py skips
  is derived by the profiler's viewer and is not in the file.  A fusion has
  ONE ``op_name`` — that of the instruction the compiler made its root — so
  a fusion that spans two scopes is attributed whole to its root's scope.
  An operation's time is its *self* time (its children's taken out), so the
  tables sum to the device's busy time; *inner* is the last program scope on
  the path (the op that owns the operation), *outer* the first (the engine
  phase).  Inside the whole runs of the main program the inner table is also
  kept by HLO instruction (``runs_by_inner_instruction``: events and self
  seconds), for a reader that counts how often one operation ran
  (``byz_trace.ring_pop_hbm_pct``: an instruction runs at most once a tick,
  one inside a gate on the taken ticks only).
- **spans** — ``utils/telemetry.span`` twins: host events named ``sweep.*``
  and ``serve.*`` on the profiler's clock, with their stats (``span`` =
  ``<trace id>:<span id>``, ``rows``, ``lanes``, ``size``, ``bucket``,
  ``mode``).
- **batcher states** — ``serve.batcher.idle`` / ``.hold`` / ``.flush`` tile
  the batcher thread; each idle gap of the device is split over the states
  that cover it.

A trace of a program without scopes or spans (the parent of the PR that
added them) reduces to empty tables; the readers in ``layer_metrics/`` then
return nothing.

    python benchmark/program_trace.py <trace dir or file> [inner scope ...]

prints the whole table for one trace: what ``PERF.md`` section 5 is written
from (by instruction for the inner scopes named after it; the file is an
``.xplane.pb`` or ``.xplane.pb.gz``).  ``tests/test_program_trace.py``
checks the reduction on
``fixtures/served_small.xplane.pb.gz``.
"""

from __future__ import annotations

import bisect
import functools
import gzip
import os
import re
import statistics
import sys

import xplane  # Busy, merge, self_times, newest_xplane: imported, not copied

SCOPE_PREFIXES = ("pbft.", "ops.")
SPAN_PREFIXES = ("sweep.", "serve.")
BATCHER_STATES = ("serve.batcher.idle", "serve.batcher.hold",
                  "serve.batcher.flush")
UNSCOPED = "(no program scope)"
_SCOPE = re.compile(
    r"(?:^|[/(])((?:%s)[A-Za-z0-9_.]+)" % "|".join(
        re.escape(p) for p in SCOPE_PREFIXES))

@functools.lru_cache(maxsize=1)
def _xspace_class():
    """The message class for the part of ``XSpace`` that is read (field
    numbers as in tsl/profiler/protobuf/xplane.proto; unknown fields are
    skipped by the parser)."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    fd = descriptor_pb2.FieldDescriptorProto
    one, many = fd.LABEL_OPTIONAL, fd.LABEL_REPEATED
    i64, u64, f64 = fd.TYPE_INT64, fd.TYPE_UINT64, fd.TYPE_DOUBLE
    raw, msg = fd.TYPE_BYTES, fd.TYPE_MESSAGE
    schema = {
        "XStat": [("metadata_id", 1, i64, one), ("double_value", 2, f64, one),
                  ("uint64_value", 3, u64, one), ("int64_value", 4, i64, one),
                  ("str_value", 5, raw, one), ("ref_value", 7, u64, one)],
        "XEvent": [("metadata_id", 1, i64, one), ("offset_ps", 2, i64, one),
                   ("duration_ps", 3, i64, one), ("stats", 4, "XStat", many)],
        "XLine": [("id", 1, i64, one), ("name", 2, raw, one),
                  ("timestamp_ns", 3, i64, one), ("events", 4, "XEvent", many)],
        "XEventMetadata": [("id", 1, i64, one), ("name", 2, raw, one),
                           ("stats", 5, "XStat", many)],
        "XStatMetadata": [("id", 1, i64, one), ("name", 2, raw, one)],
        "EventMetadataEntry": [("key", 1, i64, one),
                               ("value", 2, "XEventMetadata", one)],
        "StatMetadataEntry": [("key", 1, i64, one),
                              ("value", 2, "XStatMetadata", one)],
        "XPlane": [("id", 1, i64, one), ("name", 2, raw, one),
                   ("lines", 3, "XLine", many),
                   ("event_metadata", 4, "EventMetadataEntry", many),
                   ("stat_metadata", 5, "StatMetadataEntry", many)],
        "XSpace": [("planes", 1, "XPlane", many)],
    }
    fp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane_subset.proto", package="bench_xplane",
        syntax="proto3")
    for mname, fields in schema.items():
        m = fp.message_type.add(name=mname)
        for fname, num, typ, label in fields:
            f = m.field.add(name=fname, number=num, label=label)
            if isinstance(typ, str):
                f.type, f.type_name = msg, f".bench_xplane.{typ}"
            else:
                f.type = typ
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _text(b) -> str:
    return b.decode("utf-8", "replace") if isinstance(b, bytes) else str(b)


def scope_path(op_name: str) -> tuple:
    """The program scopes on an HLO ``op_name`` path, outermost first
    (``vmap(...)`` wrappers of a component are looked through)."""
    return tuple(_SCOPE.findall(op_name or ""))


def load(path: str) -> dict:
    """``{"devices": {plane: {"ops": [(scopes, start_ns, end_ns)],
    "instructions": [name], "modules": [(name, start_ns, end_ns)]}}, "host":
    [(name, start_ns, end_ns, thread, stats)]}`` — ``instructions`` names the
    HLO instruction of each entry of ``ops``, index for index; ``host`` holds
    the program's spans and the harness's traced window."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = _xspace_class()()
        space.ParseFromString(f.read())
    devices: dict = {}
    host: list = []
    for plane in space.planes:
        pname = _text(plane.name)
        is_dev = pname.startswith("/device:TPU:")
        if not is_dev and not pname.startswith("/host:CPU"):
            continue
        stat_names = {e.key: _text(e.value.name) for e in plane.stat_metadata}

        def stat_value(s):
            if s.str_value:
                return _text(s.str_value)
            if s.ref_value:
                return stat_names.get(s.ref_value, "")
            return s.int64_value or s.uint64_value or s.double_value

        meta = {}
        for e in plane.event_metadata:
            stats = {stat_names.get(s.metadata_id): stat_value(s)
                     for s in e.value.stats}
            meta[e.key] = (_text(e.value.name), stats)
        if is_dev:
            dev = devices.setdefault(
                pname, {"ops": [], "instructions": [], "modules": []})
            scopes_of = {k: scope_path(str(st.get("tf_op", "")))
                         for k, (_, st) in meta.items()}
            for line in plane.lines:
                lname, t0 = _text(line.name), line.timestamp_ns
                if lname == "XLA Ops":
                    dev["ops"].extend(
                        (scopes_of.get(e.metadata_id, ()),
                         t0 + e.offset_ps / 1e3,
                         t0 + (e.offset_ps + e.duration_ps) / 1e3)
                        for e in line.events)
                    dev["instructions"].extend(
                        xplane.op_name(meta.get(e.metadata_id, ("?",))[0])
                        for e in line.events)
                elif lname == "XLA Modules":
                    dev["modules"].extend(
                        (meta.get(e.metadata_id, ("?",))[0].split("(")[0],
                         t0 + e.offset_ps / 1e3,
                         t0 + (e.offset_ps + e.duration_ps) / 1e3)
                        for e in line.events)
            continue
        wanted = {k for k, (n, _) in meta.items()
                  if n.startswith(SPAN_PREFIXES) or n == xplane.WINDOW}
        for i, line in enumerate(plane.lines):
            t0 = line.timestamp_ns
            for e in line.events:
                if e.metadata_id not in wanted:
                    continue
                stats = {stat_names.get(s.metadata_id): stat_value(s)
                         for s in e.stats}
                host.append((meta[e.metadata_id][0], t0 + e.offset_ps / 1e3,
                             t0 + (e.offset_ps + e.duration_ps) / 1e3,
                             (pname, line.id or i), stats))
    return {"devices": devices, "host": host}


def _by_scope(table: dict) -> tuple[dict, dict]:
    """A self-time table keyed by whole scope paths -> (by inner, by outer)."""
    inner: dict = {}
    outer: dict = {}
    for path, ns in table.items():
        a, b = (path[-1], path[0]) if path else (UNSCOPED, UNSCOPED)
        inner[a] = inner.get(a, 0.0) + ns / 1e9
        outer[b] = outer.get(b, 0.0) + ns / 1e9
    return inner, outer


def _main_runs(modules: list, w0: float, w1: float) -> tuple:
    """The module that holds most of the device's time in the window, and its
    executions that lie wholly inside it: a per-step figure divides by whole
    runs only, so a run cut by the window's edge does not skew it.  A run
    that was under way when tracing began is recorded from that moment on —
    inside the window, but short — so a run under nine tenths of the median
    duration is left out as well."""
    total: dict = {}
    for name, a, b in modules:
        total[name] = total.get(name, 0.0) + max(min(b, w1) - max(a, w0), 0.0)
    if not total:
        return None, []
    main = max(total, key=total.get)
    runs = sorted((a, b) for n, a, b in modules
                  if n == main and a >= w0 and b <= w1)
    if runs:
        typical = statistics.median(b - a for a, b in runs)
        runs = [(a, b) for a, b in runs if b - a >= 0.9 * typical]
    return main, runs


def summarize(trace_dir_or_file: str, n_devices: int = 1) -> dict:
    path = trace_dir_or_file if os.path.isfile(trace_dir_or_file) \
        else xplane.newest_xplane(trace_dir_or_file)
    raw = load(path)
    planes = sorted(raw["devices"])[:max(n_devices, 1)]
    if not planes:
        raise ValueError(f"{path}: no TPU device plane")
    windows = [(a, b) for n, a, b, _, _ in raw["host"] if n == xplane.WINDOW]
    if windows:
        w0, w1 = windows[0]
    else:  # a trace taken outside the harness: everything it holds
        evs = [e for p in planes for e in raw["devices"][p]["ops"]]
        w0 = min(a for _, a, _ in evs)
        w1 = max(b for _, _, b in evs)
    busies, table = [], {}
    for p in planes:
        ops = raw["devices"][p]["ops"]
        clipped = [(max(a, w0), min(b, w1)) for _, a, b in ops
                   if min(b, w1) > max(a, w0)]
        busies.append(xplane.Busy(xplane.merge(clipped)))
        for k, ns in xplane.self_times(ops, w0, w1).items():
            table[k] = table.get(k, 0.0) + ns / len(planes)
    # per-step figures: the first device's whole runs of its main program
    first = raw["devices"][planes[0]]
    main, runs = _main_runs(first["modules"], w0, w1)
    names = first.get("instructions") or ["?"] * len(first["ops"])
    ordered = sorted((((scopes, name), a, b) for (scopes, a, b), name
                      in zip(first["ops"], names)), key=lambda e: e[1])
    starts = [e[1] for e in ordered]
    run_table: dict = {}
    run_instr: dict = {}  # inner scope -> instruction -> [events, self s]
    for a, b in runs:
        inside = ordered[bisect.bisect_left(starts, a):
                         bisect.bisect_right(starts, b)]
        for (scopes, name), _, _ in inside:
            by = run_instr.setdefault(scopes[-1] if scopes else UNSCOPED, {})
            by.setdefault(name, [0, 0.0])[0] += 1
        for (scopes, name), ns in xplane.self_times(inside, a, b).items():
            run_table[scopes] = run_table.get(scopes, 0.0) + ns
            run_instr[scopes[-1] if scopes else UNSCOPED][name][1] += ns / 1e9
    n_runs, run_ns = len(runs), sum(b - a for a, b in runs)
    busy = busies[0]
    busy_ns = sum(b.covered(w0, w1) for b in busies) / len(busies)
    inner, outer = _by_scope(table)
    run_inner, run_outer = _by_scope(run_table)
    scoped = sum(v for k, v in inner.items() if k != UNSCOPED)

    spans: dict = {}
    for name, a, b, _, stats in sorted(raw["host"], key=lambda e: e[1]):
        if name == xplane.WINDOW or a < w0 or b > w1:
            continue  # only a span wholly inside the window is a full account
        spans.setdefault(name, []).append({
            "start_s": (a - w0) / 1e9, "dur_s": (b - a) / 1e9,
            "busy_s": sum(x.covered(a, b) for x in busies) / len(busies) / 1e9,
            "stats": stats})

    # the batcher thread: the one line that holds the state events
    states = sorted((a, b, n) for n, a, b, _, _ in raw["host"]
                    if n in BATCHER_STATES and b > w0 and a < w1)
    batcher = None
    if states:
        c0, c1 = max(states[0][0], w0), min(states[-1][1], w1)
        by_state = {n: 0.0 for n in BATCHER_STATES}
        idle_by = {n: 0.0 for n in BATCHER_STATES}
        gaps = busy.gaps(c0, c1)
        for a, b, n in states:
            a, b = max(a, c0), min(b, c1)
            by_state[n] += (b - a) / 1e9
            idle_by[n] += ((b - a) - busy.covered(a, b)) / 1e9
        idle_c = sum(b - a for a, b in gaps) / 1e9
        idle_by["(between states)"] = idle_c - sum(idle_by.values())
        batcher = {
            "covered_s": (c1 - c0) / 1e9, "states_s": by_state,
            "n": {n: sum(1 for s in states if s[2] == n)
                  for n in BATCHER_STATES},
            "idle_s": idle_c, "idle_by_state_s": idle_by,
            "threads": len({t for n, _, _, t, _ in raw["host"]
                            if n in BATCHER_STATES}),
        }
    return {
        "path": path, "devices": planes, "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9, "idle_s": (w1 - w0 - busy_ns) / 1e9,
        "scoped_s": scoped, "by_inner_s": inner, "by_outer_s": outer,
        "main_module": main, "main_runs": n_runs, "main_runs_s": run_ns / 1e9,
        "runs_by_inner_s": run_inner, "runs_by_outer_s": run_outer,
        "runs_by_inner_instruction": run_instr,
        "spans": spans, "batcher": batcher,
    }


# ------------------------------------------------- what the readers share ---


def of_run(run: dict):
    """The reduction of a traced run, made once for all the readers of a
    process; ``None`` when the run was not traced or the trace cannot be
    reduced (said on stderr: a reader returns nothing, it does not raise)."""
    if not run.get("trace"):
        return None
    if "_program_trace" not in run:
        try:
            run["_program_trace"] = summarize(run["trace"]["path"])
        except Exception as e:
            print(f"program_trace: {type(e).__name__}: {e}", file=sys.stderr)
            run["_program_trace"] = None
    return run["_program_trace"]


def for_driver(run: dict, driver: str):
    """``of_run`` for the cells that ``driver`` drives, else ``None``."""
    if run["traffic"].get("driver") != driver:
        return None
    return of_run(run)


def per_step_us(run: dict, driver: str, prefix: str, inner: bool):
    """Device self time under the scopes that start with ``prefix``, inside
    the whole runs of the main program, over the steps those runs made."""
    t = for_driver(run, driver)
    if not t or not t["main_runs"]:
        return None
    table = t["runs_by_inner_s" if inner else "runs_by_outer_s"]
    got = [v for k, v in table.items() if k.startswith(prefix)]
    if not got:
        return None
    steps = t["main_runs"] * run["window"]["steps_per_dispatch"]
    return sum(got) / steps * 1e6


def scoped_pct(run: dict, driver: str):
    """Share of the device's busy time that lies under any program scope."""
    t = for_driver(run, driver)
    if not t or t["busy_s"] <= 0 or t["scoped_s"] <= 0:
        return None
    return 100.0 * t["scoped_s"] / t["busy_s"]


def span_median_ms(run: dict, driver: str, name: str, minus_busy=False):
    """Median duration of the program's span ``name`` over the traced
    window (its device-busy time taken out with ``minus_busy``)."""
    t = for_driver(run, driver)
    got = (t or {}).get("spans", {}).get(name)
    if not got:
        return None
    return statistics.median(
        (s["dur_s"] - (s["busy_s"] if minus_busy else 0.0)) * 1e3
        for s in got)


if __name__ == "__main__":
    import json

    s = summarize(sys.argv[1])
    s["spans"] = {k: {"n": len(v), "dur_s": sum(x["dur_s"] for x in v),
                      "busy_s": sum(x["busy_s"] for x in v),
                      "median_ms": statistics.median(
                          x["dur_s"] for x in v) * 1e3}
                  for k, v in s["spans"].items()}
    order = lambda d: dict(sorted(d.items(), key=lambda kv: -kv[1]))  # noqa: E731
    for k in ("by_inner_s", "by_outer_s", "runs_by_inner_s",
              "runs_by_outer_s"):
        s[k] = order(s[k])
    # by instruction only under the scopes named after the trace, largest first
    s["runs_by_inner_instruction"] = {
        k: dict(sorted(v.items(), key=lambda kv: -kv[1][1]))
        for k, v in s["runs_by_inner_instruction"].items()
        if k in sys.argv[2:]}
    print(json.dumps(s, indent=1))
