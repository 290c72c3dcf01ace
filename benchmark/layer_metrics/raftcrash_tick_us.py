"""Engines, tick, multi-Raft under a crash schedule: the device's busy time
inside the traced window per tile-tick counted in the trace (one tick of the
one tile: 20,000 groups as a lane batch), as ``tick_step_us.*`` divide it: the
loops' own copies, which carry no scope, are in it (device trace;
``raftcrash_trace.py``)."""

import raftcrash_trace


def read(run: dict):
    return raftcrash_trace.busy_tick_us(run)
