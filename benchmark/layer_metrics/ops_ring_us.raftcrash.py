"""Ops under multi-Raft with a crash schedule: device self time of the
operations whose innermost program scope is a ring op (``ops.ring.*``: the
seven pops on every tick, the pushes inside their gates), per tile-tick
(device trace, by scope)."""

import raftcrash_trace


def read(run: dict):
    return raftcrash_trace.inner_us(run, "ops.ring.")
