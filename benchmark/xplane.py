"""Reduce a profiler trace (``.xplane.pb``) to busy/idle time, an operation
table, the harness's own spans with the device time inside each, and the
longest idle gaps by what the host was doing.

Read with ``jax.profiler.ProfileData`` alone.  What is read:

- device planes (``/device:TPU:<i>``; in a CPU rehearsal, which has none, the
  host's XLA executor threads stand in so that the path runs): the line
  ``XLA Ops`` holds one event per executed HLO operation, nested where an
  operation (a ``while``) contains others.  *Busy* is the union of those
  events' intervals, clipped to the traced window; an operation's table
  entry is its *self* time (its duration minus its children's), so the
  table sums to busy.
- the host plane: ``bench.*`` events are the harness's
  ``jax.profiler.TraceAnnotation`` spans, on the same clock;
  ``bench.trace_window`` is the traced window itself.

``tests/test_xplane.py`` checks the reduction on ``fixtures/``: busy + idle
= window, and the table sums to busy.
"""

from __future__ import annotations

import bisect
import glob
import os

WINDOW = "bench.trace_window"
_NOT_OPS = {"Steps", "XLA Modules", "XLA TraceMe", "Framework Name Scope",
            "Framework Ops", "Source code", "Host Offload Ops"}


def newest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> dict:
    """``{"devices": {plane: [(name, start_ns, end_ns)]}, "host": [...]}``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    host: list = []
    stand_in: list = []
    for plane in data.planes:
        is_dev = plane.name.startswith("/device:TPU:")
        has_ops_line = is_dev and any(ln.name == "XLA Ops" for ln in plane.lines)
        for line in plane.lines:
            if is_dev:
                if (has_ops_line and line.name != "XLA Ops") \
                        or line.name in _NOT_OPS:
                    continue
                devices.setdefault(plane.name, []).extend(
                    (op_name(e.name), e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif plane.name.startswith("/host:CPU"):
                ops = line.name.startswith(("tf_XLAPjRtCpuClient",
                                            "tf_XLAEigen"))
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name, e.start_ns,
                                     e.start_ns + e.duration_ns))
                    elif ops and e.duration_ns > 0:
                        stand_in.append((e.name, e.start_ns,
                                         e.start_ns + e.duration_ns))
    if not devices and stand_in:
        devices["/host:CPU (rehearsal stand-in)"] = stand_in
    return {"devices": devices, "host": host}


def op_name(event_name: str) -> str:
    """``%fusion.160 = f32[...] fusion(...)`` -> ``fusion.160``: the HLO
    instruction's own name, which is what the trace has for a kernel today."""
    return event_name.split(" = ", 1)[0].lstrip("%")[:64]


def merge(intervals: list) -> list:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


class Busy:
    """A merged interval set that answers "how much of [a, b) is covered"."""

    def __init__(self, merged: list):
        self.iv = merged
        self.starts = [a for a, _ in merged]
        self.cum = [0]
        for a, b in merged:
            self.cum.append(self.cum[-1] + (b - a))

    def covered(self, a: float, b: float) -> float:
        if b <= a or not self.iv:
            return 0.0
        i = bisect.bisect_right(self.starts, a) - 1
        j = bisect.bisect_left(self.starts, b)
        total = self.cum[j] - self.cum[max(i, 0)]
        if i >= 0:  # the part of interval i before a
            total -= min(max(a - self.iv[i][0], 0), self.iv[i][1] - self.iv[i][0])
        if j >= 1:  # the part of interval j-1 after b
            total -= max(self.iv[j - 1][1] - max(b, self.iv[j - 1][0]), 0)
        return float(total)

    def gaps(self, a: float, b: float) -> list:
        out, cur = [], a
        for s, e in self.iv:
            if e <= a:
                continue
            if s >= b:
                break
            if s > cur:
                out.append((cur, s))
            cur = max(cur, e)
        if cur < b:
            out.append((cur, b))
        return out


def self_times(events: list, w0: float, w1: float) -> dict:
    """Per-name exclusive time of nested events, clipped to [w0, w1)."""
    table: dict[str, float] = {}
    stack: list = []  # [name, end, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, own = stack.pop()
            table[name] = table.get(name, 0.0) + max(own, 0.0)

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        a, b = max(a, w0), min(b, w1)
        if b <= a:
            continue
        close(a)
        if stack:
            b = min(b, stack[-1][1])  # a child never outlives its parent
            stack[-1][2] -= b - a
        stack.append([name, b, b - a])
    close(float("inf"))
    return table


def summarize(trace_dir_or_file: str, n_devices: int = 1) -> dict:
    path = trace_dir_or_file if os.path.isfile(trace_dir_or_file) \
        else newest_xplane(trace_dir_or_file)
    raw = load(path)
    windows = [(a, b) for n, a, b in raw["host"] if n == WINDOW]
    if not windows:
        raise ValueError(f"{path}: no {WINDOW} span in the host plane")
    w0, w1 = windows[0]
    planes = sorted(raw["devices"])[:max(n_devices, 1)]
    if not planes:
        raise ValueError(f"{path}: no device plane")
    busies, ops = [], {}
    for p in planes:
        evs = raw["devices"][p]
        clipped = [(max(a, w0), min(b, w1)) for _, a, b in evs
                   if min(b, w1) > max(a, w0)]
        busies.append(Busy(merge(clipped)))
        for name, ns in self_times(evs, w0, w1).items():
            ops[name] = ops.get(name, 0.0) + ns / len(planes)
    busy_ns = sum(b.covered(w0, w1) for b in busies) / len(busies)
    spans: dict[str, list] = {}
    for name, a, b in sorted(raw["host"], key=lambda e: e[1]):
        if name == WINDOW or b <= w0 or a >= w1:
            continue
        inside = a >= w0 and b <= w1
        spans.setdefault(name, []).append({
            "start_s": (a - w0) / 1e9, "dur_s": (b - a) / 1e9,
            "busy_s": sum(x.covered(a, b) for x in busies) / len(busies) / 1e9,
            "whole": inside})
    # only spans wholly inside the window are a dispatch's full account
    spans = {k: [s for s in v if s["whole"]] for k, v in spans.items()}
    host_iv = sorted((a, b, n) for n, a, b in raw["host"] if n != WINDOW)
    host_starts = [a for a, _, _ in host_iv]
    gaps: dict[str, float] = {}
    for a, b in busies[0].gaps(w0, w1):
        # the harness's spans follow one another: the one open at the gap's
        # start, or the next one, holds most of it
        i = bisect.bisect_right(host_starts, a) - 1
        best, best_ov = "unattributed", 0.0
        for ha, hb, n in host_iv[max(i, 0):i + 3]:
            ov = min(b, hb) - max(a, ha)
            if ov > best_ov:
                best, best_ov = n, ov
        gaps[best] = gaps.get(best, 0.0) + (b - a)
    top = lambda d: [[k, v / 1e9] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "path": path, "devices": planes,
        "window_s": (w1 - w0) / 1e9, "busy_s": busy_ns / 1e9,
        "idle_s": (w1 - w0 - busy_ns) / 1e9,
        "op_total_s": sum(ops.values()) / 1e9,
        "n_events": sum(len(raw["devices"][p]) for p in planes),
        "spans": spans,
        "idle_by_span_s": {k: v / 1e9 for k, v in gaps.items()},
        "breakdown": {"device_ops": top(ops), "idle_gaps": top(gaps)},
    }


if __name__ == "__main__":  # python benchmark/xplane.py <dir-or-file>
    import json
    import sys

    s = summarize(sys.argv[1])
    s["spans"] = {k: {"n": len(v), "dur_s": sum(x["dur_s"] for x in v),
                      "busy_s": sum(x["busy_s"] for x in v)}
                  for k, v in s["spans"].items()}
    print(json.dumps(s, indent=1))
