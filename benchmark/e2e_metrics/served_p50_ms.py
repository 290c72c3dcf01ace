"""Median latency of a scenario request, timed from when it was due (open
loop), over every request due in the window."""

import statistics


def read(run: dict):
    lat = run["window"].get("latencies_ms")
    return statistics.median(lat) if lat else None
