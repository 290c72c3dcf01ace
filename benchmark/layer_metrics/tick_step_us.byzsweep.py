"""Tick engine under the Byzantine-fault sweep: device-busy time inside the
traced call over the ticks it scanned times its tiles, so one tick of one
tile, all its lanes (device trace)."""

import byz_trace


def read(run: dict):
    return byz_trace.tick_step_us(run)
