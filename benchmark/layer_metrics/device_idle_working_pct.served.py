"""Device: the share of the traced window in which the device idled although
the batcher was NOT waiting for traffic — idle time outside the
``serve.batcher.idle`` state (holding a request, or the host's part of a
flush): the idle the program causes.  Taken over the part of the window that
the batcher's states cover (device trace split by program span)."""

import program_trace


def read(run: dict):
    t = program_trace.for_driver(run, "served")
    b = (t or {}).get("batcher")
    if not b or b["covered_s"] <= 0:
        return None
    working = b["idle_s"] - b["idle_by_state_s"]["serve.batcher.idle"]
    return 100.0 * working / b["covered_s"]
