"""90th percentile (nearest rank) of the latency of a scenario request, timed
from when it was due, over every request due in the window."""

import readers


def read(run: dict):
    return readers.nearest_rank(run["window"].get("latencies_ms") or [], 90)
