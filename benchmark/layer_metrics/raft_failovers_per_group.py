"""Engines, tick, multi-Raft under a crash schedule: crashed leaders replaced
by a leader of a higher term, a group and run, by the program's own counters
over the window: ``raft.failovers`` over ``raft.groups`` (groups read);
program counter.  The schedule's ``crashes`` (3.0 as the cell is cut, 4.0 uncut) where every kill hit a leader
and was replaced before the next.  A program without the counters gives
nothing."""

import raftcrash_trace


def read(run: dict):
    return raftcrash_trace.counter_ratio(run, "raft.failovers", "raft.groups")
