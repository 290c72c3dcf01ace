"""Committee tier: median of the program's host span
``topo.committee.readback`` over the traced window: the ONE fetch of the
stacked finals' metric leaves (program span; the wait for the device is the
harness's, before it)."""

import committee_trace


def read(run: dict):
    return committee_trace.span_median_ms(run, "topo.committee.readback")
