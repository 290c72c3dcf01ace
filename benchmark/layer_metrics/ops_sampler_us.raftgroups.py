"""Ops under multi-Raft: device self time of the operations whose innermost
program scope is a delay sampler (``ops.delay.*``: the per-edge draws of a
taken push), per tile-tick (device trace, by scope)."""

import raftgroups_trace


def read(run: dict):
    return raftgroups_trace.inner_us(run, "ops.delay.")
