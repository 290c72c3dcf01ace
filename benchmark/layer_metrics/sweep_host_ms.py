"""Sweep layer: per traced dispatch, the wall of the ``run_seed_sweep`` call
minus the device-busy time inside it — key build, the one fetch of the rows
and their metrics dicts (device trace + the harness's span on the same
clock); median over the traced dispatches."""

import statistics


def read(run: dict):
    t, w = run["trace"], run["window"]
    if not t or w.get("unit") != "points":
        return None
    spans = t["spans"].get("bench.dispatch") or []
    if not spans:
        return None
    return statistics.median(
        (s["dur_s"] - s["busy_s"]) * 1e3 for s in spans)
