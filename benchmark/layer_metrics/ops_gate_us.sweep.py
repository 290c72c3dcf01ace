"""Ops under a sweep: device self time of the operations whose innermost
program scope is the gate's own work under a lane batch (``ops.gate.*``: the
"any lane active" reduction and the per-lane select of a taken arm), per tick
(all lanes), over the whole dispatches inside the traced window (device
trace, by scope).  A program without the scope reads nothing."""

import program_trace


def read(run: dict):
    return program_trace.per_step_us(run, "sweep", "ops.gate.", inner=True)
