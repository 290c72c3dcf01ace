"""Where the benchmark touches the program's configuration types: a
configuration file's ``fields`` become the ``SimConfig`` the drivers run."""

from __future__ import annotations


def sim_config(fields: dict):
    from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig

    fields = dict(fields)
    faults = FaultConfig(**fields.pop("faults", {}))
    return SimConfig(**fields, faults=faults)


def schedule_of(cfg) -> str:
    """What ``schedule="auto"`` resolved to: ``round`` or ``tick``."""
    from blockchain_simulator_tpu import runner

    return "round" if runner.use_round_schedule(cfg) else "tick"
