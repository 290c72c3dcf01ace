"""What the ``.byzsweep`` readers share: the count function of the ring
pops' bytes, and the arithmetic on a traced call of the Byzantine-fault
sweep.

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

import program_trace

DRIVER = "byzsweep"
HERE = os.path.dirname(os.path.abspath(__file__))


def ring_pop_bytes_per_tick(fields: dict, lanes: int) -> int:
    """Bytes the pops of one tick must move for one tile: every lane pops
    three ``[D, n, slots]`` int32 rings (PRE_PREPARE, PREPARE_RES, COMMIT),
    and a pop reads one ``[n, slots]`` slice and writes it back as zeros.
    The ``[D, n]`` view-change ring (1/64 of one of them) is left out, so
    the share reads that little too low."""
    return lanes * 3 * 2 * fields["n"] * fields["pbft_max_slots"] * 4


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
    """The pops' share of the HBM roofline: the bytes a tick's pops must
    move over the device self time under innermost ``ops.ring.ring_pop``, as
    a share of ``peaks.json``'s ``hbm_bytes_per_s``."""
    us = program_trace.per_step_us(run, DRIVER, "ops.ring.ring_pop", inner=True)
    lanes = tile_lanes(run)
    if not us or not lanes:
        return None
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)["by_device_kind"]
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in peaks:
        return None
    rate = ring_pop_bytes_per_tick(run["fields"], int(lanes)) / (us * 1e-6)
    return 100.0 * rate / peaks[kind]["hbm_bytes_per_s"]
