"""Record the small trace ``fixtures/mixed_small.xplane.pb.gz`` on the chip:
the ``mixed_solo`` driver far below rehearsal size (2 shards of 32 nodes, 1 ms
links and send delays, a 5 ms heartbeat under a U[12,20) ms election timeout
so that the election prefix is 26 ticks, proposals 25 ms after the election,
60 ticks a run; every seed the driver draws hands off soundly), about 40 ms
of traced window holding a few whole runs on the fast path.  The ``mixed.*``,
``raft.*``, ``pbft.*`` and ``ops.*`` scopes are in it; ``test_scope_table.py``
checks ``scope_table.py`` on it.

A tick of the mixed engine is about 900 device events, so the file is cut to
what ``scope_table.py`` reads before it is kept: the device plane's ``XLA
Ops`` and ``XLA Modules`` lines without the events' own stats (the scope path
is in the event *metadata*), and the host's ``bench.trace_window`` event.
The cut and the uncut file reduce to the same table (checked here).  Run
through the chip tool; the file comes back under ``chiprun_out/``.

    python benchmark/tests/record_mixed_fixture.py
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
import scope_table  # noqa: E402

import program_trace  # noqa: E402
import xplane  # noqa: E402

FIELDS = {"n": 64, "mixed_shards": 2, "sim_ms": 60, "link_delay_ms": 1,
          "raft_delay_hi": 1, "pbft_delay_lo": 0, "pbft_delay_hi": 1,
          "raft_heartbeat_ms": 5, "raft_election_lo_ms": 12,
          "raft_election_hi_ms": 20, "raft_proposal_delay_ms": 25,
          "pbft_block_interval_ms": 10}


def cut(src: str, dst: str) -> None:
    """Keep of a trace what ``scope_table.load`` reads."""
    space = program_trace._xspace_class()()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    for plane in list(space.planes):
        name = program_trace._text(plane.name)
        names = {e.key: program_trace._text(e.value.name)
                 for e in plane.event_metadata}
        if name.startswith("/device:TPU:"):
            for line in list(plane.lines):
                if program_trace._text(line.name) not in ("XLA Ops",
                                                          "XLA Modules"):
                    plane.lines.remove(line)
                    continue
                for e in line.events:
                    del e.stats[:]
        elif name.startswith("/host:CPU"):
            for line in list(plane.lines):
                keep = [e for e in line.events
                        if names.get(e.metadata_id) == xplane.WINDOW]
                if not keep:
                    plane.lines.remove(line)
                    continue
                kept = [type(keep[0])() for _ in keep]
                for a, b in zip(kept, keep):
                    a.CopyFrom(b)
                del line.events[:]
                line.events.extend(kept)
            for entry in list(plane.event_metadata):
                if names[entry.key] != xplane.WINDOW:
                    plane.event_metadata.remove(entry)
        else:
            space.planes.remove(plane)
    space.DiscardUnknownFields()
    with gzip.open(dst, "wb") as g:
        g.write(space.SerializeToString())


def main() -> int:
    spec = bench.load_json(bench.ROOT, "BENCHMARK.json")
    backend = bench.open_backend(1)
    if isinstance(backend, int):
        return backend
    devs, _ = backend
    # on_chip=False selects the rehearsal sizes; FIELDS cut them further (the
    # comparisons against the reference are not looked at: a trace is wanted)
    ctx = bench.make_ctx(spec, "mixed256x1k.solo", 11, True, False,
                         program_fields=FIELDS)
    ctx["tracer"] = bench.Tracer(True, 0.04, ctx["trace_dir"], delay_s=0.1)
    run, _ = bench.drive(ctx, 0.5, bench.CompileCounter(), 1)
    out = os.path.join(bench.ROOT, "chiprun_out", "fixture")
    os.makedirs(out, exist_ok=True)
    dst = os.path.join(out, "mixed_small.xplane.pb.gz")
    src = run["trace"]["path"]
    cut(src, dst)
    x = run["trace"]
    print({k: x[k] for k in ("window_s", "busy_s", "idle_s", "n_events")},
          run["setup"], devs[0].device_kind, os.path.getsize(src), "->",
          os.path.getsize(dst), "bytes")
    if devs[0].platform == "tpu":
        whole, small = scope_table.summarize(src), scope_table.summarize(dst)
        whole.pop("path"), small.pop("path")
        print("the cut file reduces to the same table:", whole == small)
        small.pop("runs_by_path_s")
        print(json.dumps(small, indent=1))
    shutil.rmtree(ctx["trace_dir"], ignore_errors=True)  # what came back is the cut
    return 0


if __name__ == "__main__":
    sys.exit(main())
