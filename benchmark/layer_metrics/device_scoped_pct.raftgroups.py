"""Device: the share of the device's busy time that lies under any of the
program's scopes (``topo.*``, ``raft.*``, ``ops.*``, ``gate.*``), in the cells
the ``raftgroups_solo`` driver drives (device trace, by scope)."""

import raftgroups_trace


def read(run: dict):
    return raftgroups_trace.scoped_pct(run)
