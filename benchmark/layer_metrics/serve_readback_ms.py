"""Serving: median of the program's ``serve.dispatch.readback`` span (the
final state read back into a metrics dict) over the traced requests (program
span, on the profiler's clock)."""

import program_trace


def read(run: dict):
    return program_trace.span_median_ms(run, "served",
                                        "serve.dispatch.readback")
