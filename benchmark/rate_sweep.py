"""Find the served cell's knee once, on the chip: one process, one warm
server, a ladder of fixed open-loop rates of ``--seconds`` each.

    python benchmark/rate_sweep.py --workload pbft1k.served --rates 2 3 4 5 6 8

Per rate: completions per second over the time to the last answer, p50 and
p90 from when each request was due, and how long the server needed after the
last due time to drain.  The knee is the highest rate the server sustains:
completions keep up with arrivals and the drain stays about one flush.  The
cell's ``rate_per_s`` is four fifths of it, written into the traffic file as
a number; ``PERF.md`` records the sweep.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import readers  # noqa: E402
import run as bench  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", type=float, nargs="+", required=True)
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--arrivals", nargs="+", default=["fixed"])
    p.add_argument("--seed", type=int, default=2_147_483_777)
    args = p.parse_args(argv)
    spec = bench.load_json(bench.ROOT, "BENCHMARK.json")
    backend = bench.open_backend(bench.resolve(spec, args.workload)["cell"]["chips"])
    if isinstance(backend, int):
        return backend
    devs, on_chip = backend
    ctx = bench.make_ctx(spec, args.workload, args.seed, False, on_chip)
    driver = bench.load_module("drivers", ctx["traffic"]["driver"]).Driver(ctx)
    try:
        print(json.dumps({"setup": driver.setup(),
                          "platform": devs[0].platform}), flush=True)
        for arrivals, rate in ((a, r) for a in args.arrivals
                               for r in args.rates):
            driver.rate, driver.arrivals = rate, arrivals
            t0 = time.monotonic()
            w = driver.window(t0, args.seconds)
            lat = w["latencies_ms"]
            print(json.dumps({
                "arrivals": arrivals, "rate_per_s": rate, "attempted": w["attempted"],
                "failed": w["failed"],
                "completed_per_s": (w["attempted"] - w["failed"])
                / (w["t_last_done"] - t0),
                "p50_ms": statistics.median(lat),
                "p90_ms": readers.nearest_rank(lat, 90),
                "max_ms": max(lat),
                "drain_s": w["notes"]["drain_s_after_window"],
                "occupancy": w["stats"]["served"] / max(w["stats"]["batches"], 1),
                "flush_sizes": w["occupancy"],
                "late_ms_max": w["notes"]["generator_late_ms_max"],
            }), flush=True)
    finally:
        driver.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
