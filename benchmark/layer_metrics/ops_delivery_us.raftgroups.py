"""Ops under multi-Raft: device self time of the operations whose innermost
program scope is a delivery op (``ops.delivery.*``: the per-edge broadcast,
unicast and round-trip arms), per tile-tick (device trace, by scope)."""

import raftgroups_trace


def read(run: dict):
    return raftgroups_trace.inner_us(run, "ops.delivery.")
