"""Mesh: median of the program's ``shard.readback`` span (one ``device_get``
of the leaves ``metrics`` reads, from four shards) over the traced window
(program span, on the profiler's clock)."""

import mesh_trace


def read(run: dict):
    return mesh_trace.span_median_ms(run, "shard.readback")
