"""Committee tier under multi-Raft with a crash schedule: median of the
program's host span ``topo.committee.outer`` over the traced window: all
20,000 groups' metrics from the fetched leaves at once
(``models/raft.metrics_stacked``, the per-crash columns among them), their
counters, and the outer aggregate: the host's pass of every run (program
span)."""

import raftcrash_trace


def read(run: dict):
    return raftcrash_trace.span_median_ms(run, "topo.committee.outer")
