"""Serving: median of the requests' ``serve.dispatch`` segment (the flush's
executable run and readback), from the server's own monotonic stamps as its
telemetry spans carry them."""

import statistics


def read(run: dict):
    d = [s["dur_ms"] for s in run["window"].get("server_spans", [])
         if s["name"] == "serve.dispatch"]
    return statistics.median(d) if d else None
