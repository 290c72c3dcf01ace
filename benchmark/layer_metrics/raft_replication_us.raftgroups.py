"""Engines, tick, multi-Raft: device self time under the replication phases
of the Raft tick (``raft.tick.heartbeat_rx``, ``.ack_rx``,
``.timer_heartbeat``), the ops nested in them included, per tile-tick
(device trace, by scope)."""

import raftgroups_trace


def read(run: dict):
    return raftgroups_trace.phases_us(run, raftgroups_trace.REPLICATION)
