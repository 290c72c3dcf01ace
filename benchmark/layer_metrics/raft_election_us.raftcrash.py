"""Engines, tick, multi-Raft under a crash schedule: device self time under the
election phases of the Raft tick (``raft.tick.vote_rx``, ``.vote_reply_rx``,
``.timer_vote`` and ``.term``), the ops nested in them included, per tile-tick
(device trace, by scope)."""

import raftcrash_trace


def read(run: dict):
    return raftcrash_trace.phases_us(run, raftcrash_trace.ELECTION)
