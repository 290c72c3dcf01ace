"""Ops under the Byzantine-fault sweep: device self time of the operations
whose innermost program scope is a delay sampler (``ops.delay.*``),
per tick of one tile (all its lanes), over the whole tiles inside the traced
call (device trace, by scope)."""

import program_trace


def read(run: dict):
    return program_trace.per_step_us(run, "byzsweep", "ops.delay.", inner=True)
