"""Engines, tick, multi-Raft: device self time under the election phases of
the Raft tick (``raft.tick.vote_rx``, ``.vote_reply_rx``, ``.timer_vote`` and
``.term``, which is what terms add: the step to a higher term and down from
a role, the oracle), the ops nested in them included, per tile-tick (device
trace, by scope)."""

import raftgroups_trace


def read(run: dict):
    return raftgroups_trace.phases_us(run, raftgroups_trace.ELECTION)
