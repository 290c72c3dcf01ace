"""Engines, tick, multi-Raft: terms a group went through in a run, by the
program's own counters over the window: ``raft.term_bumps`` (the sum over
the groups read of ``term_final``) over ``raft.groups`` (groups read);
program counter.  2 where every group elects once and fails over once; more
where votes split.  A program without the counters gives nothing."""

import raftgroups_trace


def read(run: dict):
    return raftgroups_trace.counter_ratio(run, "raft.term_bumps",
                                          "raft.groups")
