"""Mesh: device self time of the tick's collectives (innermost scope
``ops.mesh.pmax`` / ``.psum`` / ``.gather``; where a collective carries no
``op_name``, its HLO category or instruction name), per tick, averaged over
the device planes: the time a chip spends in all-reduces, waiting for the
slowest chip included (device trace, by scope)."""

import mesh_trace


def read(run: dict):
    return mesh_trace.per_tick_us(run, "collective")
