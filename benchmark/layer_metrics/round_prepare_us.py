"""Round-blocked engine: device self time under the scope ``pbft.round.prepare``
(``models/pbft_round.step_round``) per round, over the whole runs of the
round program inside the traced window (device trace, by program scope)."""

import program_trace


def read(run: dict):
    return program_trace.per_step_us(run, "solo", "pbft.round.prepare",
                                     inner=False)
