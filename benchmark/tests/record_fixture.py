"""Record the small trace ``fixtures/solo_small.xplane.pb.gz`` on the chip:
the solo driver at the configuration's rehearsal size (4096 nodes, 20 rounds
a run), 50 ms of traced window.  ``test_xplane.py`` checks the reduction on
it.  Run through the chip tool; the file comes back under ``chiprun_out/``.

    python benchmark/tests/record_fixture.py
"""

from __future__ import annotations

import gzip
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run as bench  # noqa: E402
import xplane  # noqa: E402


def main() -> int:
    spec = bench.load_json(bench.ROOT, "BENCHMARK.json")
    backend = bench.open_backend(1)
    if isinstance(backend, int):
        return backend
    devs, _ = backend
    # on_chip=False selects the rehearsal sizes; the device is the real one
    ctx = bench.make_ctx(spec, "pbft100k.solo", 11, True, False)
    ctx["tracer"] = bench.Tracer(True, 0.05, ctx["trace_dir"], delay_s=0.3)
    run, _ = bench.drive(ctx, 0.8, bench.CompileCounter(), 1)
    out = os.path.join(bench.ROOT, "chiprun_out", "fixture")
    os.makedirs(out, exist_ok=True)
    with open(run["trace"]["path"], "rb") as f, gzip.open(
            os.path.join(out, "solo_small.xplane.pb.gz"), "wb") as g:
        shutil.copyfileobj(f, g)
    t = run["trace"]
    print({k: t[k] for k in ("window_s", "busy_s", "idle_s", "op_total_s",
                             "n_events", "devices")}, devs[0].device_kind)
    return 0


if __name__ == "__main__":
    sys.exit(main())
