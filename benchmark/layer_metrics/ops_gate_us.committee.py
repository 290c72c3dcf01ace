"""Ops under the committee tier: device self time of the operations whose
innermost program scope is the gates' own work under a lane batch
(``ops.gate.*``: the "any lane active" reduction over the tile's lanes, the
per-lane select of a taken arm), per tile-tick (device trace, by scope)."""

import committee_trace


def read(run: dict):
    return committee_trace.inner_us(run, "ops.gate.")
