"""Ops under a mesh: device self time under ``ops.delivery.gossip_fwd`` (the
TTL flood's delay draws, zero-fill, scatter-max into the global row space and
the slice back to local rows) per tick, averaged over the device planes.  Its
all-reduce is not in this number: ``mesh_collective_us`` holds it (device
trace, by scope)."""

import mesh_trace


def read(run: dict):
    return mesh_trace.per_tick_us(run, "flood")
