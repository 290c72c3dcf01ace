"""Arithmetic the metric readers share.  Each metric keeps a file of its own
(``e2e_metrics/``, ``layer_metrics/``); what two of them compute alike is
here, so that it is computed one way."""

from __future__ import annotations

import math


def completed_per_s(run: dict, unit: str):
    """Units of work completed in the window over the time from the window's
    start to the last completion; ``None`` where the cell counts another
    unit."""
    w = run["window"]
    if w.get("unit") != unit or not w["samples"]:
        return None
    return sum(s["units"] for s in w["samples"]) / (
        w["samples"][-1]["t1"] - run["t_window"])


def histogram(values) -> str:
    """``value:count`` pairs in the order of the values, for a result's
    notes."""
    return " ".join(f"{v}:{values.count(v)}" for v in sorted(set(values)))


def nearest_rank(values, q: float):
    """The q-th percentile by nearest rank (no interpolation: a tail is one
    of the requests)."""
    v = sorted(values)
    return v[math.ceil(q / 100.0 * len(v)) - 1] if v else None


def idle_pct(run: dict, driver: str):
    """Share of the traced window in which no operation ran on the device,
    for cells driven by ``driver``."""
    t = run["trace"]
    if not t or run["traffic"]["driver"] != driver or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
