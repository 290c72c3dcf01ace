"""Ops under multi-Raft: device self time of the operations whose innermost
program scope is a ring op (``ops.ring.*``: the seven pops on every tick,
the pushes inside their gates), per tile-tick (device trace, by scope)."""

import raftgroups_trace


def read(run: dict):
    return raftgroups_trace.inner_us(run, "ops.ring.")
