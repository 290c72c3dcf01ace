"""Serving: median over requests of ``serve.queue_wait`` + ``serve.batch_wait``
(admitted but not yet flushed), from the server's telemetry spans."""

import statistics


def read(run: dict):
    per: dict = {}
    for s in run["window"].get("server_spans", []):
        if s["name"] in ("serve.queue_wait", "serve.batch_wait"):
            rid = (s.get("attrs") or {}).get("id")
            per[rid] = per.get(rid, 0.0) + s["dur_ms"]
    return statistics.median(per.values()) if per else None
