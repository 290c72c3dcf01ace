"""Sweep layer: the lanes of a tile the sweep layer dispatched, as its
``sweep.tile`` spans say (program span; median over the traced call).  A
program that cuts no list to the device writes no such span."""

import byz_trace


def read(run: dict):
    return byz_trace.tile_lanes(run)
