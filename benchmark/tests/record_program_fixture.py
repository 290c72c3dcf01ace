"""Record the small trace ``fixtures/served_small.xplane.pb.gz`` on the chip:
the served driver far below rehearsal size (16 nodes, 45 ticks a request, a
20 ms block interval so that one block is proposed and made final, 10 req/s),
about 0.3 s of traced window holding a few lone flushes.  The program's
scopes, its ``serve.*`` spans and the batcher's states are in it;
``test_program_trace.py`` checks ``program_trace.py`` on it.  Run through the
chip tool; the file comes back under ``chiprun_out/``.

    python benchmark/tests/record_program_fixture.py
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench  # noqa: E402
import program_trace  # noqa: E402

FIELDS = {"n": 16, "sim_ms": 45, "pbft_block_interval_ms": 20,
          "model_serialization": False}


def main() -> int:
    spec = bench.load_json(bench.ROOT, "BENCHMARK.json")
    backend = bench.open_backend(1)
    if isinstance(backend, int):
        return backend
    devs, _ = backend
    # on_chip=False selects the rehearsal sizes; FIELDS cut them further (the
    # comparisons against the reference are not looked at: a trace is wanted)
    ctx = bench.make_ctx(spec, "pbft1k.served", 11, True, False,
                         program_fields=FIELDS)
    ctx["traffic"].update(rate_per_s=10.0, verify_rows=1)
    ctx["tracer"] = bench.Tracer(True, 0.3, ctx["trace_dir"], delay_s=0.35)
    run, _ = bench.drive(ctx, 1.2, bench.CompileCounter(), 1)
    out = os.path.join(bench.ROOT, "chiprun_out", "fixture")
    os.makedirs(out, exist_ok=True)
    dst = os.path.join(out, "served_small.xplane.pb.gz")
    with open(run["trace"]["path"], "rb") as f, gzip.open(dst, "wb") as g:
        shutil.copyfileobj(f, g)
    x = run["trace"]
    print({k: x[k] for k in ("window_s", "busy_s", "idle_s", "n_events")},
          devs[0].device_kind, os.path.getsize(dst), "bytes")
    if devs[0].platform == "tpu":
        s = program_trace.summarize(dst)
        s["spans"] = {k: len(v) for k, v in s["spans"].items()}
        print(json.dumps(s, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
