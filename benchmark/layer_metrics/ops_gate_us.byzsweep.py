"""Ops under the Byzantine-fault sweep: device self time of the operations
whose innermost program scope is the gate's own work under a lane batch
(``ops.gate.*``),
per tick of one tile (all its lanes), over the whole tiles inside the traced
call (device trace, by scope)."""

import program_trace


def read(run: dict):
    return program_trace.per_step_us(run, "byzsweep", "ops.gate.", inner=True)
