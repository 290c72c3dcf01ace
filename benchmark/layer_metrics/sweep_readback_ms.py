"""Sweep layer: median of the program's ``sweep.readback`` span (per-row
slicing, ``sim_metrics``, ``obs.record_run``) over the traced dispatches
(program span, on the profiler's clock)."""

import program_trace


def read(run: dict):
    return program_trace.span_median_ms(run, "sweep", "sweep.readback")
