"""Tick engine, mixed deployment: device self time under the scope
``mixed.prefix`` (``models/mixed.prefix_handoff``: the election-phase scan of
the whole 262,144-row engine) per prefix tick, over the whole runs inside the
traced window (device trace, by program scope)."""

import scope_table


def read(run: dict):
    return scope_table.under_per_step_us(run, "mixed_solo", "mixed.prefix",
                                         "prefix_ticks")
