"""Device: the share of the traced window in which no operation ran on a
device, averaged over the mesh's device planes (device trace: 1 - union of
device-op intervals / window), in the cells the ``mesh_solo`` driver
drives."""

import readers


def read(run: dict):
    return readers.idle_pct(run, "mesh_solo")
