"""Ops under a sweep: device self time of the operations whose innermost
program scope is a ring push or pop (``ops.ring.*``), per tick (all lanes),
over the whole dispatches inside the traced window (device trace, by
scope)."""

import program_trace


def read(run: dict):
    return program_trace.per_step_us(run, "sweep", "ops.ring.", inner=True)
