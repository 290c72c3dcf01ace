"""Committee tier under multi-Raft: median of the program's host span
``topo.committee.readback`` over the traced window: the ONE fetch of the
stacked finals' metric leaves, 20,000 groups (program span; the wait for
the device is the harness's, before it)."""

import raftgroups_trace


def read(run: dict):
    return raftgroups_trace.span_median_ms(run, "topo.committee.readback")
