"""Device: the share of the traced window in which no operation ran on the
device (device trace: 1 - union of device-op intervals / window), in the
cells the ``mixed_solo`` driver drives."""

import readers


def read(run: dict):
    return readers.idle_pct(run, "mixed_solo")
