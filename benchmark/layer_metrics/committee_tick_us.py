"""Engines, tick, under the committee tier: device self time under the
program scope ``topo.committee.tile`` (one tile's scan: T committees as a lane
batch) per tile-tick counted in the trace (device trace, by scope;
``committee_trace.py``).  The loops' own time carries no scope and is not in
it."""

import committee_trace


def read(run: dict):
    return committee_trace.tile_tick_us(run)
