"""Simulated consensus rounds final on all honest nodes, summed over every
run completed in the window, over the time from the window's start to the
last completion (host clock; a run is complete when its metrics dict has
been read back from the device and is in hand)."""

import readers


def read(run: dict):
    return readers.completed_per_s(run, "rounds")
