"""Ops under a sweep of a deployment with link classes: device self time of
the operations whose innermost program scope is a delay line's write or read
(``ops.linkclass.*``: the slot written a tick, the slices read back a class
pair's offset later), per tick (all lanes), over the whole dispatches inside
the traced window (device trace, by scope).  The jitter draws and the
class-by-class delivery they feed stay under ``ops.delay.*`` and
``ops.delivery.*``; a program without classes has no such scope and reads
nothing."""

import program_trace


def read(run: dict):
    return program_trace.per_step_us(run, "sweep", "ops.linkclass.",
                                     inner=True)
