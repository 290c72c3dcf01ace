"""Ops under the committee tier: device self time of the operations whose
innermost program scope is a ring op (``ops.ring.*``: the pops on every
tick, the pushes inside their gates), per tile-tick (one tick of one tile,
all its lanes; device trace, by scope)."""

import committee_trace


def read(run: dict):
    return committee_trace.inner_us(run, "ops.ring.")
