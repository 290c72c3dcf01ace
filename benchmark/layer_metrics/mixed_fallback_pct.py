"""Tick engine, mixed deployment: the share of the device's busy time under
the scope ``mixed.fallback`` (``models/mixed.scan_fast``'s per-tick arm, taken
when one shard failed the handoff).  0 on a sound cell; a run that fell back
spends nine tenths of its time there (device trace, by program scope).  A
program without ``mixed.*`` scopes reads nothing."""

import scope_table


def read(run: dict):
    return scope_table.under_pct(run, "mixed_solo", "mixed.fallback", "mixed.")
