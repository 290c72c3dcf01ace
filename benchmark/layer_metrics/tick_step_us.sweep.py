"""Tick engine under a sweep: device-busy time inside the traced dispatches
over the ticks they scanned (device trace)."""


def read(run: dict):
    t, w = run["trace"], run["window"]
    if not t or w.get("unit") != "points":
        return None
    spans = t["spans"].get("bench.dispatch") or []
    if not spans:
        return None
    busy = sum(s["busy_s"] for s in spans)
    return busy / (len(spans) * w["steps_per_dispatch"]) * 1e6
