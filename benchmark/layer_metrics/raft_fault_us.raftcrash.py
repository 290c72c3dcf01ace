"""Engines, tick, multi-Raft under a crash schedule: device self time under the
fault phase of the Raft tick (``raft.tick.fault``: at the head of the tick the
kill of whoever leads and the restart, behind their gate; at its end the
per-crash records and the two oracles), the ops nested in it included, per
tile-tick (device trace, by scope).  A program without a schedule has no
operation under it and gives nothing."""

import raftcrash_trace


def read(run: dict):
    return raftcrash_trace.phases_us(run, raftcrash_trace.FAULT)
