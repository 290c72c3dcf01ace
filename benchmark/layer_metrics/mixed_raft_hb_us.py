"""Heartbeat-blocked engine, mixed deployment: device self time under the
scope ``mixed.steady.raft_hb`` (``models/mixed.fast_finish``: the vmapped
``raft_hb.steady_scan`` of every shard and ``materialize``) per heartbeat
step, over the whole runs inside the traced window (device trace, by program
scope)."""

import scope_table


def read(run: dict):
    return scope_table.under_per_step_us(
        run, "mixed_solo", "mixed.steady.raft_hb", "hb_steps")
