"""Committee tier: median of the program's host span ``topo.committee.outer``
over the traced window: every committee's metrics from host arrays and the
outer aggregate (program span)."""

import committee_trace


def read(run: dict):
    return committee_trace.span_median_ms(run, "topo.committee.outer")
