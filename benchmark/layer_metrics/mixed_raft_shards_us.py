"""Tick engine, mixed deployment: device self time under the scope
``mixed.tick.raft_shards`` (``models/mixed.step``: ``raft.step`` under the
shard batch, where a ``gated`` arm that is taken runs for every shard) per
prefix tick, over the whole runs inside the traced window (device trace, by
program scope).  On a sound run the scope runs only inside ``mixed.prefix``;
``mixed_fallback_pct`` says when it did not."""

import scope_table


def read(run: dict):
    return scope_table.under_per_step_us(
        run, "mixed_solo", "mixed.tick.raft_shards", "prefix_ticks")
