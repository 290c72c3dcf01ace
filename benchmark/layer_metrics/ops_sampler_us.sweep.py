"""Ops under a sweep: device self time of the operations whose innermost
program scope is a sampler of ``ops/delay.py`` (``ops.delay.*``), per tick
(all lanes), over the whole dispatches inside the traced window (device
trace, by scope)."""

import program_trace


def read(run: dict):
    return program_trace.per_step_us(run, "sweep", "ops.delay.", inner=True)
