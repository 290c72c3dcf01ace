"""Committee tier under multi-Raft: median of the program's host span
``topo.committee.outer`` over the traced window: all 20,000 groups' metrics
from the fetched leaves at once (``models/raft.metrics_stacked``), their
counters, and the outer aggregate: the host's pass of every run, which
follows the readback and is not overlapped with the device's next run's end
(program span)."""

import raftgroups_trace


def read(run: dict):
    return raftgroups_trace.span_median_ms(run, "topo.committee.outer")
