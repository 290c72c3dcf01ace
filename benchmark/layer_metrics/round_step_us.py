"""Round-blocked engine: the time from one run's completion to the next,
over the rounds in the scan, median over the window's runs (host clock; with
runs queued on the device the interval is the device's time for one run,
hundreds of milliseconds)."""

import statistics


def read(run: dict):
    w = run["window"]
    if run["setup"].get("schedule") != "round" or len(w["samples"]) < 2:
        return None
    done = [s["t1"] for s in w["samples"]]
    per = [(b - a) / w["steps_per_dispatch"] * 1e6
           for a, b in zip(done, done[1:])]
    return statistics.median(per)
