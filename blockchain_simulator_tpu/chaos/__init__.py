"""Deterministic fault injection for the serving stack itself.

The repo simulates Byzantine and crash faults *inside* the consensus
models; this package applies the same discipline to the framework around
them — the scenario server, the batched dispatch primitive, the health
gate.  Production code carries named chaos points
(:func:`~blockchain_simulator_tpu.chaos.inject.chaos_point`) that are
free when disarmed; a seeded
:class:`~blockchain_simulator_tpu.chaos.inject.ChaosController` arms
them with counted, reproducible faults (raise, hang, slow, poison), and
:mod:`~blockchain_simulator_tpu.chaos.invariants` checks that the stack
kept its accounting promises while the faults flew:

- **no request unaccounted** — every admission ends in exactly one of
  {response, typed rejection, replayed};
- **no lost manifest lines** — every terminal outcome has its access-log
  line in runs.jsonl;
- **registry stats monotone** — cache counters never run backwards.

``tools/chaos_drill.py`` scripts the scenarios (dispatch-fail/hang,
health-flap, batcher-kill, queue-storm, poison-request,
crash-restart) and pins that each runs identically twice under one chaos
seed; README "Chaos drills" is the operator doc.

The FLEET scenarios (:mod:`~blockchain_simulator_tpu.chaos.
fleet_scenarios`, ``tools/fleet_bench.py``) extend the same discipline to
the replicated serving tier: replica death mid-traffic with WAL handoff,
slow-replica hedged failover, router retry storms, and double-claim
races — checked by :func:`~blockchain_simulator_tpu.chaos.invariants.
check_fleet` (exactly one terminal outcome per admission fleet-wide, each
handed-off id replayed exactly once, WAL leases exclusive).
"""

from blockchain_simulator_tpu.chaos.inject import (  # noqa: F401
    ChaosController,
    ChaosFault,
    ChaosKill,
    chaos_point,
    controller,
)
from blockchain_simulator_tpu.chaos.invariants import (  # noqa: F401
    Ledger,
    check_fleet,
    check_server,
    registry_monotone,
)
