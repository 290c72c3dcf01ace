"""Device: the share of the device's busy time that lies under any of the
program's scopes (``topo.*``, ``pbft.*``, ``ops.*``, ``gate.*``), in the cells
the ``committee_solo`` driver drives (device trace, by scope)."""

import committee_trace


def read(run: dict):
    return committee_trace.scoped_pct(run)
