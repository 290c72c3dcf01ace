"""Ops: the ring pops' share of the HBM roofline under the Byzantine-fault
sweep: the pops that ran (the events, counted in the trace, of the
instructions under innermost ``ops.ring.ring_pop`` whose self time an event
is at least a quarter of the largest one's there: the three ``[D, n, slots]``
rings, not the ``[D, n]`` view-change ring) x tile lanes x (one slice read +
one written as zeros) x n x slots x 4 bytes a pop
(``byz_trace.ring_pop_bytes``) over all the device self time under that
scope, over ``peaks.json``'s ``hbm_bytes_per_s`` (device trace).  Today's
program pops three rings on every tick; one that pops on the due ticks only
reads the same share.  Nothing holds it under 100: bytes counted too high
for what the program moves read past the roofline."""

import byz_trace


def read(run: dict):
    return byz_trace.ring_pop_hbm_pct(run)
