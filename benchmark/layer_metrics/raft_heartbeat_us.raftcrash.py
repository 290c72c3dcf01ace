"""Engines, tick, multi-Raft under a crash schedule: device self time under the
heartbeat phases of the Raft tick (``raft.tick.heartbeat_rx``, ``.ack_rx`` and
``.timer_heartbeat``: failure detection here, since a leader rarely lives to
propose), the ops nested in them included, per tile-tick (device trace, by
scope)."""

import raftcrash_trace


def read(run: dict):
    return raftcrash_trace.phases_us(run, raftcrash_trace.HEARTBEAT)
