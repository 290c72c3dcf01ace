"""Device: share of the traced window in which no operation ran on the
device, in the cells the ``raftcrash_solo`` driver drives (device trace)."""

import readers


def read(run: dict):
    return readers.idle_pct(run, "raftcrash_solo")
