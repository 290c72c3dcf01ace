"""Device: the share of the device's busy time that lies under any of the
program's scopes (``pbft.*``, ``ops.*``), in the cells the ``byzsweep``
driver drives (device trace, by scope)."""

import program_trace


def read(run: dict):
    return program_trace.scoped_pct(run, "byzsweep")
