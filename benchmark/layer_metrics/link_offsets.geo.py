"""Ops under link classes: the distinct offsets of the one-way delay line,
which is the slices one read of a line costs (21 for six regions whose
matrix is symmetric with unlike entries), by the program's own counters:
``linkclass.offsets`` over ``linkclass.programs`` (program counter).  A
program without the counters gives nothing."""

import linkclass_trace


def read(run: dict):
    return linkclass_trace.per_program(run, "offsets")
