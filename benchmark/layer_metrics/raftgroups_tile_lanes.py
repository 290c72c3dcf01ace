"""Committee tier under multi-Raft: the groups of one tile of the stack, by
the program's own counters over the window: ``committee.tile_lanes`` (lanes
run, padding included) over ``committee.tiles`` (tiles run); program
counter."""

import raftgroups_trace


def read(run: dict):
    return raftgroups_trace.counter_ratio(run, "committee.tile_lanes",
                                          "committee.tiles")
