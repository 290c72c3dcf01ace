"""Readings a limit is set from, taken on the chip at a cell's own size, in
one process: the program's sound runs over many seeds, then each control of
the configuration file (``controls``: the program run with one stated
guarantee broken) over a few.

    python benchmark/tests/chip_readings.py --workload pbft100k.solo \\
        --seeds 12 --control-seeds 3 --seconds 2

Prints one line per run: the seed, whether it came out correct, and every
number compared.  ``PERF.md`` records the readings the limits were set from.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--seed0", type=int, default=2_147_484_000)
    p.add_argument("--seconds", type=float, default=2.0)
    args = p.parse_args(argv)
    spec = bench.load_json(bench.ROOT, "BENCHMARK.json")
    got = bench.resolve(spec, args.workload)
    backend = bench.open_backend(got["cell"]["chips"])
    if isinstance(backend, int):
        return backend
    devs, on_chip = backend
    counter = bench.CompileCounter()
    plans = [("sound", None, args.seeds)] + [
        (c["name"], c, args.control_seeds)
        for c in got["config"].get("controls", [])
        if args.workload in c.get("workloads", [args.workload])]
    verdict = True
    for name, control, n in plans:
        for i in range(n):
            seed = args.seed0 + 7919 * i
            fields = control and (control["fields"] if on_chip else
                                  control.get("rehearsal_fields",
                                              control["fields"]))
            ctx = bench.make_ctx(spec, args.workload, seed, False, on_chip,
                                 program_fields=fields)
            try:
                _, comps = bench.drive(ctx, args.seconds, counter, len(devs))
            except Exception as e:  # a control that crashes has failed
                print(json.dumps({"run": name, "seed": seed,
                                  "crashed": repr(e)[:300]}), flush=True)
                verdict = verdict and control is not None
                continue
            correct = all(c["ok"] for c in comps)
            print(json.dumps({
                "run": name, "seed": seed, "correct": correct,
                "values": {c["name"]: c["value"] for c in comps},
                "failed": [c["name"] for c in comps if not c["ok"]],
                "platform": devs[0].platform}), flush=True)
            verdict = verdict and (correct if control is None else not correct)
    print(json.dumps({"sound_all_correct_and_controls_all_rejected": verdict}))
    return 0 if verdict else 1


if __name__ == "__main__":
    sys.exit(main())
