"""Sweep layer: median of the program's ``sweep.readback`` span (one
``device_get`` of the metrics' leaves for every lane, then per row
``sim_metrics`` on host views and ``obs.record_run``) over the traced
dispatches (program span, on the profiler's clock)."""

import program_trace


def read(run: dict):
    return program_trace.span_median_ms(run, "sweep", "sweep.readback")
