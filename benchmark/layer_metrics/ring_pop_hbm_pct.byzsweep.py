"""Ops: the ring pops' share of the HBM roofline under the Byzantine-fault
sweep: tile lanes x 3 rings x (one slice read + one written as zeros) x n x
slots x 4 bytes a tick (``byz_trace.ring_pop_bytes_per_tick``) over the
device self time under innermost ``ops.ring.ring_pop``, over
``peaks.json``'s ``hbm_bytes_per_s`` (device trace)."""

import byz_trace


def read(run: dict):
    return byz_trace.ring_pop_hbm_pct(run)
