"""What a cell under link classes reads of the program's own counters.

The classed programs count, where they are traced, what they hold
(``ops/linkclass.note_traced``: one increment a traced program and over them
the sums of their classes, of the distinct offsets of their one-way delay
lines, of their ring depths and of the bytes of state a lane carries); the
program's builders move the counts to its metrics registry as the
``linkclass.*`` counters.  The ``sweep`` driver keeps no counters of its own,
so the readers take them from the registry of the process that ran the cell,
after the window: every program of the cell (the lane batch, the solo twin
of the after-window check) was traced for the cell's one configuration, so a
sum over the traced programs is that configuration's number.  A program
without classes (the parent of the PR that brought them) has no such counter
and reads nothing.
"""

from __future__ import annotations

DRIVER = "sweep"


def per_program(run: dict, name: str):
    """The counter ``linkclass.<name>`` over ``linkclass.programs``: the
    mean over the classed programs this process traced."""
    if run["traffic"].get("driver") != DRIVER:
        return None
    if not run["fields"].get("link_classes"):
        return None
    try:
        from blockchain_simulator_tpu.utils import telemetry
    except ImportError:
        return None
    got = telemetry.metrics.snapshot()["counters"]
    programs = got.get("linkclass.programs")
    if not programs or f"linkclass.{name}" not in got:
        return None
    return got[f"linkclass.{name}"] / programs
