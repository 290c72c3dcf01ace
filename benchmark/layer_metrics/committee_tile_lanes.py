"""Committee tier: the committees of one tile of the stack, by the program's
own counters over the window: ``committee.tile_lanes`` (lanes run, padding
included) over ``committee.tiles`` (tiles run); program counter.  A program
without the counters gives nothing."""

import committee_trace


def read(run: dict):
    return committee_trace.tile_lanes(run)
