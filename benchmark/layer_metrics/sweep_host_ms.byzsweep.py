"""Sweep layer under the Byzantine-fault sweep: the wall of the traced
``run_byzantine_sweep`` call minus the device-busy time inside it: operands,
the readback of each tile, building the rows (device trace + the harness's
span on the same clock)."""

import byz_trace


def read(run: dict):
    return byz_trace.sweep_host_ms(run)
