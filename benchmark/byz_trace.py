"""What the ``.byzsweep`` readers share: the count function of the ring
pops' bytes, the pick of the pops that ran, and the arithmetic on a traced
call of the Byzantine-fault sweep.

A traced window of the ``byzsweep`` driver holds one whole call: one
``bench.dispatch`` span with the call's tiles inside it, each tile one run of
the main program over ``steps_per_dispatch`` ticks.  ``program_trace.
per_step_us`` already divides by the main program's runs times their ticks,
so a per-tick figure is a tick of ONE tile (all its lanes).
"""

from __future__ import annotations

import json
import os
import statistics
import sys

import program_trace

DRIVER = "byzsweep"
HERE = os.path.dirname(os.path.abspath(__file__))
POP_SCOPE = "ops.ring.ring_pop"
WHOLE_RING_SHARE = 0.25


def ring_pop_bytes(fields: dict, lanes: int) -> int:
    """Bytes ONE pop of one ``[D, n, slots]`` int32 ring (PRE_PREPARE,
    PREPARE_RES or COMMIT) must move for one tile: every lane reads one
    ``[n, slots]`` slice and writes it back as zeros."""
    return lanes * 2 * fields["n"] * fields["pbft_max_slots"] * 4


def ring_pops(run: dict):
    """``{HLO instruction: [events, self seconds]}`` of the operations whose
    innermost program scope is ``ops.ring.ring_pop``, inside the whole runs
    of the main program of a traced ``byzsweep`` run
    (``program_trace.summarize``'s ``runs_by_inner_instruction``); None where
    there is no such trace or no operation under that scope."""
    t = program_trace.for_driver(run, DRIVER)
    table = (t or {}).get("runs_by_inner_instruction", {})
    return table.get(POP_SCOPE) or None


def whole_ring_pops(pops: dict) -> dict:
    """The instructions of ``pops`` that pop a whole ``[D, n, slots]`` ring:
    those whose self time an event is at least ``WHOLE_RING_SHARE`` of the
    largest one's under the scope.  The three rings are one shape and take
    623 us an event each today (my chip run, PR 44); the ``[D, n]``
    view-change ring's pop moves 1/64 of the bytes in 58 us, and what else
    stands under the scope takes 3 us or less.  The rule knows no byte count
    and no peak: an instruction is a ring's pop by its time beside the
    others', never by how fast it would have to be."""
    per_event = {k: s / n for k, (n, s) in pops.items() if n}
    top = max(per_event.values(), default=0.0)
    return {k: pops[k] for k, v in per_event.items()
            if top > 0 and v >= WHOLE_RING_SHARE * top}


def tile_lanes(run: dict):
    """Lanes of a dispatched tile: the program's ``sweep.tile`` spans in the
    trace (median); None where it wrote none."""
    t = program_trace.for_driver(run, DRIVER)
    got = (t or {}).get("spans", {}).get("sweep.tile")
    if not got:
        return None
    return statistics.median(float(s["stats"]["lanes"]) for s in got)


def dispatch_spans(run: dict):
    """The harness's ``bench.dispatch`` spans of a traced ``byzsweep``
    window (each one whole call), or None."""
    t = run["trace"]
    if not t or run["traffic"].get("driver") != DRIVER:
        return None
    return t["spans"].get("bench.dispatch") or None


def tick_step_us(run: dict):
    spans = dispatch_spans(run)
    if not spans:
        return None
    w = run["window"]
    ticks = len(spans) * w["steps_per_dispatch"] * w["tiles_per_call"]
    return sum(s["busy_s"] for s in spans) / ticks * 1e6


def sweep_host_ms(run: dict):
    spans = dispatch_spans(run)
    if not spans:
        return None
    return statistics.median((s["dur_s"] - s["busy_s"]) * 1e3 for s in spans)


def ring_pop_hbm_pct(run: dict):
    """The pops' share of the HBM roofline: the bytes of the pops that ran
    (``ring_pop_bytes`` a pop, times the events, counted in the trace, of
    the instructions that ``whole_ring_pops`` picks) over ALL the device
    self time under innermost ``ops.ring.ring_pop``, as a share of
    ``peaks.json``'s ``hbm_bytes_per_s``.

    Counted by events, the share does not depend on the ticks on which the
    program pops: rings popped on the due ticks only move fewer bytes in less
    time.  The view-change ring's pop counts no bytes while its time stays in
    the divisor, so the share reads that little too low (58 of 1,930 us a tick).
    Nothing holds the share under 100: where the bytes are counted too high
    for what the program moves (rings packed into a narrower type, a pop of
    part of a slice, one pop split into two instructions of like time, each
    then counted as a whole ring's) it reads past the roofline, and the
    driver refuses a reading over 105 as impossible, which is the signal to
    mend ``ring_pop_bytes``."""
    pops, lanes = ring_pops(run), tile_lanes(run)
    if not pops or not lanes:
        return None
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["by_device_kind"]
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in peaks:
        return None
    events = sum(n for n, _ in whole_ring_pops(pops).values())
    under = sum(s for _, s in pops.values())
    if not events or under <= 0:
        return None
    moved = ring_pop_bytes(run["fields"], int(lanes)) * events
    return 100.0 * moved / under / peaks[kind]["hbm_bytes_per_s"]


if __name__ == "__main__":
    # the pops by instruction, largest first: events, self seconds, us an
    # event, and whether it counts as a whole ring's pop
    table = program_trace.summarize(sys.argv[1])[
        "runs_by_inner_instruction"].get(POP_SCOPE, {})
    whole = whole_ring_pops(table)
    for name in sorted(table, key=lambda k: -table[k][1]):
        n, sec = table[name]
        print(json.dumps({"instruction": name, "events": n, "self_s": sec,
                          "us_per_event": sec / n * 1e6,
                          "whole_ring": name in whole}))
