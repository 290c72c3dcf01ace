"""Device: the share of the device's busy time that lies under any of the
program's scopes (``mixed.*``, ``raft.*``, ``pbft.*``, ``ops.*``), in the
cells the ``mixed_solo`` driver drives.  It falls when a refactor drops
scopes (device trace, by scope)."""

import scope_table


def read(run: dict):
    return scope_table.scoped_pct(run, "mixed_solo")
