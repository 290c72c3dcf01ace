"""Mesh: the busiest device plane's busy time less the idlest plane's, as a
share of the traced window: how unevenly the shards are loaded (the
proposers all live on shard 0) (device trace)."""

import mesh_trace


def read(run: dict):
    return mesh_trace.skew_pct(run)
