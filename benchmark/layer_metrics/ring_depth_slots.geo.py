"""Ops under link classes: the slots of a ring (``SimConfig.ring_depth``),
which under the sender-side delay lines stay those of one scalar latency
whatever the regions' span, by the program's own counters:
``linkclass.ring_depth`` over ``linkclass.programs`` (program counter).  A
program without the counters gives nothing."""

import linkclass_trace


def read(run: dict):
    return linkclass_trace.per_program(run, "ring_depth")
