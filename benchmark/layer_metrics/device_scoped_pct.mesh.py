"""Device: the share of the devices' busy time that lies under any of the
program's scopes (``paxos.*``, ``ops.*``), averaged over the mesh's device
planes, in the cells the ``mesh_solo`` driver drives.  It falls when a
refactor drops scopes (device trace, by scope)."""

import mesh_trace


def read(run: dict):
    return mesh_trace.scoped_pct(run)
