"""Ops under a mesh: device self time of the operations whose innermost
program scope is a ring push or pop (``ops.ring.*``), per tick, averaged over
the device planes: in a program sharded over an axis ``gated_push`` pushes
into every delay bucket's slice on every tick, taken or not (device trace, by
scope)."""

import mesh_trace


def read(run: dict):
    return mesh_trace.per_tick_us(run, "ring")
