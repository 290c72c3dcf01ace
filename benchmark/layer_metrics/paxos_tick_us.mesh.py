"""Tick engine, Paxos over a mesh: the device's busy time per tick of the
sharded scan, averaged over the mesh's device planes; the ticks are counted
in the trace itself (``mesh_trace.py``: a traced window holds a stretch of a
run, not a whole one) (device trace)."""

import mesh_trace


def read(run: dict):
    return mesh_trace.per_tick_us(run, None)
