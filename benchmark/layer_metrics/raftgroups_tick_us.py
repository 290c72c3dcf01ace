"""Engines, tick, multi-Raft: device self time under the program scope
``topo.committee.tile`` (one tile's scan: the Raft groups of the tile as a
lane batch) per tile-tick counted in the trace (device trace, by scope;
``raftgroups_trace.py``).  The loops' own time carries no scope and is not
in it."""

import raftgroups_trace


def read(run: dict):
    return raftgroups_trace.tile_tick_us(run)
