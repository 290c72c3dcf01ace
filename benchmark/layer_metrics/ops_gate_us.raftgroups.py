"""Ops under multi-Raft: device self time of the operations whose innermost
program scope is the gates' own work under a lane batch (``ops.gate.*``: the
"any lane active" reduction over the tile's groups, the per-lane select of a
taken arm), per tile-tick (device trace, by scope).  With 20,000 groups at
their own phases some lane is active on every tick, so every gate is taken
and this is what the gates cost, not what they save."""

import raftgroups_trace


def read(run: dict):
    return raftgroups_trace.inner_us(run, "ops.gate.")
