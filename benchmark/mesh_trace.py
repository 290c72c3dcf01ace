"""Reduce a profiler trace of a program sharded over a device mesh: device
time by program scope averaged over the mesh's device planes, the collectives
by name, the skew between planes, and the program's ``shard.*`` host spans.

``program_trace.py`` and ``scope_table.py`` hold their scope and span
prefixes as module constants (``pbft.`` / ``ops.``, ``sweep.`` / ``serve.``)
and read one device plane; a later PR may not edit either.  This module
parses the same file with ``program_trace``'s schema (``_xspace_class``) and
``xplane``'s interval arithmetic (``Busy``, ``merge``, ``self_times``), takes
its prefixes as ARGUMENTS, and keeps every device plane.

What differs from the one-chip readers, and why:

- **Per tick, not per whole run.**  A sharded tick is hundreds of device
  events on each plane and the tracer's stop costs tens of seconds per
  million events, so a trace holds a stretch of a run, not a whole one.  The
  ticks in the window are counted from the trace itself: every HLO
  instruction of the tick loop's body runs at most once a tick, and most
  run on every tick, so the largest number of events any one instruction
  has inside the window is the number of ticks the plane made there.
- **Classes that partition the busy time**, so that a tick's parts add up:
  ``collective`` (innermost scope ``ops.mesh.*``, or, where a collective
  carries no ``op_name``, an HLO category or instruction name that says
  all-reduce / all-gather / all-to-all / collective-permute / reduce-
  scatter), ``ring`` (innermost ``ops.ring.*``), ``flood`` (anywhere under
  ``ops.delivery.gossip_fwd`` or inside the taken flood arm, its all-reduce
  not counted: that is a collective), the engine's phases by their
  outermost scope, and ``(no program scope)``.
- **An operation without an ``op_name`` takes its caller's.**  The compiler
  fuses the flood's zero-fill into its scatter-max and the fusion it makes
  carries no metadata at all, or the phase's alone (the largest operations
  of a taken arm: 0.8 ms each); a ``conditional`` carries none either.  But
  the ``conditional``'s event encloses its branch in time.  So for the class
  tables an event without scopes inherits those of the event that encloses
  it, and a ``conditional`` that encloses any operation under
  ``ops.delivery.gossip_fwd`` is a taken flood arm: whatever runs inside it
  is the flood.  ``scoped_s`` (``device_scoped_pct.mesh``) stays strict: an
  operation's own ``op_name`` only.
- **The flood's all-reduce against the interconnect's peak**: the bytes one
  chip moves for the flood's cross-chip max over the device time of its
  collectives.  The sharded flood reduces in one of two forms: the shards
  all-gather their senders' packets and each takes the max into its own rows
  (``ops.mesh.gather`` under the flood's scope; the gathered bytes are the
  program's own counter, ``setup.collectives.flood_allgather_bytes``), or, on
  a tick with more senders than a packet holds, a ring all-reduce of the
  scatter target in the global row space (``ops.mesh.pmax``; functions below,
  from the deployment's shapes).

A trace of a program without these scopes or spans reduces to empty tables;
the readers in ``layer_metrics/`` then return nothing.

    python benchmark/mesh_trace.py <trace dir or .xplane.pb[.gz]> [planes]

prints the table ``PERF.md`` section 5 is written from.
``tests/test_mesh_trace.py`` checks the reduction on
``fixtures/mesh_small.xplane.pb.gz``.
"""

from __future__ import annotations

import gzip
import os
import re
import statistics
import sys

import program_trace
import xplane

SCOPE_PREFIXES = ("paxos.", "ops.")
SPAN_PREFIXES = ("shard.",)
UNSCOPED = "(no program scope)"
COLLECTIVE_WORDS = ("all-reduce", "all-gather", "all-to-all",
                    "collective-permute", "reduce-scatter")
FLOOD_SCOPE = "ops.delivery.gossip_fwd"
GATHER_SCOPE = "ops.mesh.gather"


# ------------------------------------------------- counted from shapes ---


def flood_allreduce_operand_bytes(fields: dict) -> int:
    """What one taken flood arm all-reduces: the scatter target of
    ``ops/delivery.gossip_fwd`` in the global row space, ``[delay buckets,
    n, proposers]`` int32."""
    buckets = fields.get("paxos_delay_hi", 50) - fields.get("paxos_delay_lo", 0)
    return buckets * fields["n"] * fields.get("paxos_n_proposers", 3) * 4


def allgather_bytes_per_chip(gathered_bytes: int, shards: int) -> float:
    """Bytes one chip sends (and as many it receives) in an all-gather whose
    result is ``gathered_bytes`` on every chip: every packet but its own."""
    return (shards - 1) / shards * gathered_bytes


def ring_allreduce_bytes_per_chip(operand_bytes: int, shards: int) -> float:
    """Bytes one chip sends (and as many it receives) in a bandwidth-optimal
    all-reduce of ``operand_bytes`` over ``shards`` chips: a reduce-scatter
    and an all-gather of ``(shards - 1) / shards`` of the operand each."""
    return 2.0 * (shards - 1) / shards * operand_bytes


# ----------------------------------------------------------- the trace ---


def _scope_re(prefixes):
    return re.compile(r"(?:^|[/(])((?:%s)[A-Za-z0-9_.]+)" % "|".join(
        re.escape(p) for p in prefixes))


def load(path: str, scope_prefixes=SCOPE_PREFIXES,
         span_prefixes=SPAN_PREFIXES) -> dict:
    """``{"devices": {plane: {"ops": [(instruction, scopes, collective,
    start_ns, end_ns)], "modules": [(name, start_ns, end_ns)]}}, "host":
    [(name, start_ns, end_ns, stats)], "window": (w0, w1) | None}``."""
    text = program_trace._text
    scope_re = _scope_re(scope_prefixes)
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        space = program_trace._xspace_class()()
        space.ParseFromString(f.read())
    devices: dict = {}
    host: list = []
    window = None
    for plane in space.planes:
        pname = text(plane.name)
        is_dev = pname.startswith("/device:TPU:")
        if not is_dev and not pname.startswith("/host:CPU"):
            continue
        stat_names = {e.key: text(e.value.name) for e in plane.stat_metadata}

        def stat_value(s):
            if s.str_value:
                return text(s.str_value)
            if s.ref_value:
                return stat_names.get(s.ref_value, "")
            return s.int64_value or s.uint64_value or s.double_value

        meta = {}
        for e in plane.event_metadata:
            meta[e.key] = (text(e.value.name), {
                stat_names.get(s.metadata_id): stat_value(s)
                for s in e.value.stats})
        if is_dev:
            dev = devices.setdefault(pname, {"ops": [], "modules": []})
            of = {}
            for k, (name, st) in meta.items():
                instr = xplane.op_name(name)
                scopes = tuple(scope_re.findall(str(st.get("tf_op", ""))))
                what = f"{st.get('hlo_category', '')} {instr}".lower()
                of[k] = (instr, scopes,
                         any(w in what for w in COLLECTIVE_WORDS))
            for line in plane.lines:
                lname, t0 = text(line.name), line.timestamp_ns
                if lname == "XLA Ops":
                    dev["ops"].extend(
                        of.get(e.metadata_id, ("?", (), False))
                        + (t0 + e.offset_ps / 1e3,
                           t0 + (e.offset_ps + e.duration_ps) / 1e3)
                        for e in line.events)
                elif lname == "XLA Modules":
                    dev["modules"].extend(
                        (meta.get(e.metadata_id, ("?",))[0].split("(")[0],
                         t0 + e.offset_ps / 1e3,
                         t0 + (e.offset_ps + e.duration_ps) / 1e3)
                        for e in line.events)
            continue
        wanted = {k for k, (n, _) in meta.items()
                  if n.startswith(tuple(span_prefixes)) or n == xplane.WINDOW}
        for line in plane.lines:
            t0 = line.timestamp_ns
            for e in line.events:
                if e.metadata_id not in wanted:
                    continue
                name = meta[e.metadata_id][0]
                a = t0 + e.offset_ps / 1e3
                b = a + e.duration_ps / 1e3
                if name == xplane.WINDOW:
                    window = window or (a, b)
                    continue
                host.append((name, a, b, {
                    stat_names.get(s.metadata_id): stat_value(s)
                    for s in e.stats}))
    return {"devices": devices, "host": host, "window": window}


def classify(scopes: tuple, collective: bool, in_flood_arm: bool = False) -> str:
    """The one class an operation's self time is counted under."""
    inner = scopes[-1] if scopes else ""
    if inner.startswith("ops.mesh.") or collective:
        return "collective"
    if inner.startswith("ops.ring."):
        return "ring"
    if FLOOD_SCOPE in scopes or in_flood_arm:
        return "flood"
    return scopes[0] if scopes else UNSCOPED


def with_callers(events: list) -> list:
    """``[(instruction, scopes, collective, a, b)]`` -> ``[((own scopes,
    effective scopes, collective, in flood arm), a, b)]``.  An event without
    scopes takes those of the event that encloses it in time.  A
    ``conditional`` carries no ``op_name`` on the device plane, so the flood
    arm is told by what runs in it: a ``conditional`` that encloses an
    operation under ``ops.delivery.gossip_fwd`` is a taken flood arm, and
    everything it encloses is in the arm."""
    order = sorted(events, key=lambda e: (e[3], -e[4]))
    parent, eff, stack = [], [], []  # stack: indices of the open events
    for i, (_, scopes, _, a, _) in enumerate(order):
        while stack and order[stack[-1]][4] <= a:
            stack.pop()
        up = stack[-1] if stack else -1
        parent.append(up)
        eff.append(scopes or (eff[up] if up >= 0 else ()))
        stack.append(i)
    arms = set()
    for i, (_, scopes, _, _, _) in enumerate(order):
        if FLOOD_SCOPE in scopes:
            up = parent[i]
            while up >= 0:
                if order[up][0].startswith("conditional"):
                    arms.add(up)
                up = parent[up]
    out = []
    for i, (_, scopes, coll, a, b) in enumerate(order):
        up, arm = parent[i], False
        while up >= 0 and not arm:
            arm, up = up in arms, parent[up]
        out.append(((scopes, eff[i], coll, arm), a, b))
    return out


def summarize(trace_dir_or_file: str, n_devices: int = 4,
              scope_prefixes=SCOPE_PREFIXES,
              span_prefixes=SPAN_PREFIXES) -> dict:
    path = trace_dir_or_file if os.path.isfile(trace_dir_or_file) \
        else xplane.newest_xplane(trace_dir_or_file)
    raw = load(path, scope_prefixes, span_prefixes)
    planes = sorted(raw["devices"])[:max(n_devices, 1)]
    if not planes:
        raise ValueError(f"{path}: no TPU device plane")
    if raw["window"]:
        w0, w1 = raw["window"]
    else:  # a trace taken outside the harness: everything it holds
        evs = [e for p in planes for e in raw["devices"][p]["ops"]]
        w0, w1 = min(e[3] for e in evs), max(e[4] for e in evs)
    per_plane = []
    for p in planes:
        ops = raw["devices"][p]["ops"]
        inside = [e for e in ops if min(e[4], w1) > max(e[3], w0)]
        busy = xplane.Busy(xplane.merge(
            [(max(a, w0), min(b, w1)) for _, _, _, a, b in inside])
        ).covered(w0, w1)
        counts: dict = {}
        for instr, _, _, a, _ in inside:
            if a >= w0:
                counts[instr] = counts.get(instr, 0) + 1
        table = xplane.self_times(with_callers(inside), w0, w1)
        by_class: dict = {}
        by_inner: dict = {}
        scoped = flood_ar = flood_ag = 0.0
        for (own, scopes, coll, arm), ns in table.items():
            c = classify(scopes, coll, arm)
            by_class[c] = by_class.get(c, 0.0) + ns
            inner = own[-1] if own else UNSCOPED
            by_inner[inner] = by_inner.get(inner, 0.0) + ns
            if own:
                scoped += ns
            if c == "collective" and FLOOD_SCOPE in scopes:
                if scopes[-1] == GATHER_SCOPE:
                    flood_ag += ns
                else:
                    flood_ar += ns
        # the flood's collectives: the events themselves, for their count
        # (an asynchronous one is counted where it starts)
        flood_colls = [
            scopes[-1] == GATHER_SCOPE for instr, scopes, coll, a, _ in inside
            if a >= w0 and FLOOD_SCOPE in scopes and "-done" not in instr
            and (coll or scopes[-1].startswith("ops.mesh."))]
        n_flood_ag = sum(flood_colls)
        n_flood_ar = len(flood_colls) - n_flood_ag
        per_plane.append({
            "busy_ns": busy, "ticks": max(counts.values(), default=0),
            "by_class": by_class, "by_inner": by_inner, "scoped_ns": scoped,
            "flood_allreduce_ns": flood_ar, "flood_allreduces": n_flood_ar,
            "flood_allgather_ns": flood_ag, "flood_allgathers": n_flood_ag,
            "events": len(inside)})

    def mean(key):
        return sum(pp[key] for pp in per_plane) / len(per_plane)

    def mean_table(key):
        out: dict = {}
        for pp in per_plane:
            for k, ns in pp[key].items():
                out[k] = out.get(k, 0.0) + ns / len(per_plane) / 1e9
        return out

    spans: dict = {}
    for name, a, b, stats in sorted(raw["host"], key=lambda e: e[1]):
        if a < w0 or b > w1:
            continue  # only a span wholly inside the window is a full account
        spans.setdefault(name, []).append(
            {"start_s": (a - w0) / 1e9, "dur_s": (b - a) / 1e9, "stats": stats})
    busies = [pp["busy_ns"] for pp in per_plane]
    return {
        "path": path, "devices": planes, "window_s": (w1 - w0) / 1e9,
        "busy_s": mean("busy_ns") / 1e9,
        "busy_by_plane_s": [b / 1e9 for b in busies],
        "skew_s": (max(busies) - min(busies)) / 1e9,
        "ticks": mean("ticks"),
        "ticks_by_plane": [pp["ticks"] for pp in per_plane],
        "events": sum(pp["events"] for pp in per_plane),
        "scoped_s": mean("scoped_ns") / 1e9,
        "by_class_s": mean_table("by_class"),
        "by_inner_s": mean_table("by_inner"),
        "flood_allreduce_s": mean("flood_allreduce_ns") / 1e9,
        "flood_allreduces": mean("flood_allreduces"),
        "flood_allgather_s": mean("flood_allgather_ns") / 1e9,
        "flood_allgathers": mean("flood_allgathers"),
        "spans": spans,
    }


# ------------------------------------------------- what the readers share ---

DRIVER = "mesh_solo"


def of_run(run: dict):
    """The reduction of a traced run of a cell the ``mesh_solo`` driver
    drives, made once for all the readers of a process; ``None`` when the run
    was not traced, another driver ran it, or the trace cannot be reduced
    (said on stderr: a reader returns nothing, it does not raise)."""
    if run["traffic"].get("driver") != DRIVER or not run.get("trace"):
        return None
    if "_mesh_trace" not in run:
        try:
            run["_mesh_trace"] = summarize(
                run["trace"]["path"], len(run["trace"].get("devices", [1])))
        except Exception as e:
            print(f"mesh_trace: {type(e).__name__}: {e}", file=sys.stderr)
            run["_mesh_trace"] = None
    return run["_mesh_trace"]


def per_tick_us(run: dict, cls: str | None):
    """Device self time of class ``cls`` (the whole busy time for ``None``)
    per tick counted in the trace, averaged over the planes; nothing where
    the program has no paxos scope at all (then its ticks cannot be told
    from another program's operations)."""
    t = of_run(run)
    if not t or not t["ticks"] \
            or not any(k.startswith("paxos.") for k in t["by_class_s"]):
        return None
    s = t["busy_s"] if cls is None else t["by_class_s"].get(cls, 0.0)
    return s / t["ticks"] * 1e6


def allreduce_ici_pct(run: dict, ici_bits_per_s: float):
    """The bits one chip moves for the flood's cross-chip max in the trace
    (its packet all-gathers and its dense all-reduces), over their device
    time, against the chip's interconnect peak."""
    t = of_run(run)
    if not t:
        return None
    shards = int(run["setup"].get("shards", len(t["devices"])))
    moved = t["flood_allreduces"] * ring_allreduce_bytes_per_chip(
        flood_allreduce_operand_bytes(run["fields"]), shards)
    if t["flood_allgathers"]:
        gathered = run["setup"].get("collectives", {}).get(
            "flood_allgather_bytes")
        if not gathered:
            return None  # the program does not count what it gathers
        moved += t["flood_allgathers"] * allgather_bytes_per_chip(
            gathered, shards)
    seconds = t["flood_allreduce_s"] + t["flood_allgather_s"]
    if seconds <= 0 or not moved:
        return None
    return 100.0 * (moved * 8 / seconds) / ici_bits_per_s


def skew_pct(run: dict):
    t = of_run(run)
    if not t or t["window_s"] <= 0 or len(t["devices"]) < 2:
        return None
    return 100.0 * t["skew_s"] / t["window_s"]


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


if __name__ == "__main__":
    import json

    s = summarize(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 4)
    s["spans"] = {k: {"n": len(v), "median_ms": statistics.median(
        x["dur_s"] for x in v) * 1e3, "stats": v[0]["stats"]}
        for k, v in s["spans"].items()}
    for k in ("by_class_s", "by_inner_s"):
        s[k] = dict(sorted(s[k].items(), key=lambda kv: -kv[1]))
    if s["ticks"]:
        s["by_class_us_per_tick"] = {
            k: v / s["ticks"] * 1e6 for k, v in s["by_class_s"].items()}
    print(json.dumps(s, indent=1))
