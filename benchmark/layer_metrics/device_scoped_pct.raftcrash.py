"""Device: the share of the device's busy time that lies under any of the
program's scopes (``topo.*``, ``raft.*``, ``ops.*``, ``gate.*``), in the cells
the ``raftcrash_solo`` driver drives (device trace, by scope)."""

import raftcrash_trace


def read(run: dict):
    return raftcrash_trace.scoped_pct(run)
