"""Serving: median over the traced flushes of the batcher's
``serve.batcher.flush`` state (``run_batch`` and the answers) minus the
device-busy time inside it: the host's part of a flush (program span and
device trace on one clock)."""

import program_trace


def read(run: dict):
    return program_trace.span_median_ms(run, "served", "serve.batcher.flush",
                                        minus_busy=True)
