"""Tick engine under a served request: device-busy time inside the program's
``serve.dispatch.execute`` spans over the ticks they scanned (device trace
inside a program span): the lone-run figure."""

import program_trace


def read(run: dict):
    t = program_trace.for_driver(run, "served")
    spans = (t or {}).get("spans", {}).get("serve.dispatch.execute")
    if not spans:
        return None
    ticks = len(spans) * run["window"]["steps_per_dispatch"]
    return sum(s["busy_s"] for s in spans) / ticks * 1e6
