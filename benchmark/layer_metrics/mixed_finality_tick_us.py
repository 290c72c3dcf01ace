"""Tick engine, mixed deployment: device self time under the scope
``mixed.steady.finality`` (``models/mixed.fast_finish``: the lone PBFT tick
over the shard representatives after the handoff) per steady tick, over the
whole runs inside the traced window (device trace, by program scope).  The
scan's own ``while`` carries no ``op_name`` and is not in it."""

import scope_table


def read(run: dict):
    return scope_table.under_per_step_us(
        run, "mixed_solo", "mixed.steady.finality", "steady_ticks")
