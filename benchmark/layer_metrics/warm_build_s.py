"""Program builders: what the first call of the cell's programs costs beyond
their own run — trace, lower and compile or load from the persistent cache.
The driver's own figure where it has one (``solo``: building and the first
run minus a second lone run; ``served``: the first prewarm pass minus the
second), else the first warm-up call minus the median call of the window
(host clock)."""

import statistics


def read(run: dict):
    setup, w = run["setup"], run["window"]
    if "build_s" in setup:
        return setup["build_s"]
    calls = [s["t1"] - s["t0"] for s in w["samples"] if "t0" in s]
    if "first_call_s" not in setup or not calls:
        return None
    return max(setup["first_call_s"] - statistics.median(calls), 0.0)
