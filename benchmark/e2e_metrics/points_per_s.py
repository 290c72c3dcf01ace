"""Sweep rows returned to the caller as metrics dicts and checked, over the
time from the window's start to the last completed dispatch (host clock)."""

import readers


def read(run: dict):
    return readers.completed_per_s(run, "points")
