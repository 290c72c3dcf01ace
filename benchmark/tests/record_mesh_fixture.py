"""Record the small four-plane trace ``fixtures/mesh_small.xplane.pb.gz`` on a
four-chip host: the ``mesh_solo`` driver far below rehearsal size (64 nodes on
a 4-out digraph, 1 ms links and send delays U{0..3} so that a ring has four
delay buckets and a tick is a hundred-odd device events a plane, a 70 ms
retry window, 1,500 ticks a run, two runs queued), twenty milliseconds of
traced window opened as a run completes.  The ``paxos.*``, ``ops.*`` and ``ops.mesh.*``
scopes and the ``shard.readback`` span are in it; ``test_mesh_trace.py``
checks ``mesh_trace.py`` and the mesh cell's readers on it.

The file is cut to what ``mesh_trace.load`` reads before it is kept: the
device planes' ``XLA Ops`` and ``XLA Modules`` lines without the events' own
stats (the scope path is in the event *metadata*), and of the host plane the
``bench.trace_window`` event and the ``shard.*`` spans with their stats.  The
cut and the uncut file reduce to the same table (checked here).  Run through
the chip tool on four chips; the file comes back under ``chiprun_out/``.

    python benchmark/tests/record_mesh_fixture.py
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import mesh_trace  # noqa: E402
import program_trace  # noqa: E402
import run as bench  # noqa: E402
import xplane  # noqa: E402

FIELDS = {"n": 64, "degree": 4, "gossip_hops": 6, "sim_ms": 1500,
          "link_delay_ms": 1, "paxos_delay_hi": 4,
          "paxos_retry_timeout_ms": 70}


def cut(src: str, dst: str) -> None:
    """Keep of a trace what ``mesh_trace.load`` reads."""
    text = program_trace._text
    space = program_trace._xspace_class()()
    with open(src, "rb") as f:
        space.ParseFromString(f.read())
    for plane in list(space.planes):
        name = text(plane.name)
        names = {e.key: text(e.value.name) for e in plane.event_metadata}
        if name.startswith("/device:TPU:"):
            for line in list(plane.lines):
                if text(line.name) not in ("XLA Ops", "XLA Modules"):
                    plane.lines.remove(line)
                    continue
                for e in line.events:
                    del e.stats[:]
        elif name.startswith("/host:CPU"):
            want = lambda k: names.get(k) == xplane.WINDOW or \
                names.get(k, "").startswith(mesh_trace.SPAN_PREFIXES)  # noqa: E731
            for line in list(plane.lines):
                keep = [e for e in line.events if want(e.metadata_id)]
                if not keep:
                    plane.lines.remove(line)
                    continue
                kept = [type(keep[0])() for _ in keep]
                for a, b in zip(kept, keep):
                    a.CopyFrom(b)
                del line.events[:]
                line.events.extend(kept)
            for entry in list(plane.event_metadata):
                if not want(entry.key):
                    plane.event_metadata.remove(entry)
        else:
            space.planes.remove(plane)
    space.DiscardUnknownFields()
    with gzip.open(dst, "wb") as g:
        g.write(space.SerializeToString())


def main() -> int:
    spec = bench.load_json(bench.ROOT, "BENCHMARK.json")
    backend = bench.open_backend(4)
    if isinstance(backend, int):
        return backend
    devs, on_chip = backend
    # on_chip=False selects the rehearsal sizes; FIELDS cut them further (the
    # comparisons against the reference are not looked at: a trace is wanted)
    ctx = bench.make_ctx(spec, "paxos10k.mesh4", 11, True, False,
                         program_fields=FIELDS)
    ctx["traffic"].update(in_flight=2, queue_s=0.0)
    ctx["tracer"] = bench.Tracer(True, 0.02, ctx["trace_dir"], delay_s=0.3)
    run, _ = bench.drive(ctx, 1.5, bench.CompileCounter(), len(devs))
    out = os.path.join(bench.ROOT, "chiprun_out", "fixture")
    os.makedirs(out, exist_ok=True)
    dst = os.path.join(out, "mesh_small.xplane.pb.gz")
    src = run["trace"]["path"]
    cut(src, dst)
    x = run["trace"]
    print({k: x[k] for k in ("window_s", "busy_s", "idle_s", "n_events")},
          {k: v for k, v in run["setup"].items() if k != "collectives"},
          devs[0].device_kind, len(devs), os.path.getsize(src), "->",
          os.path.getsize(dst), "bytes")
    if devs[0].platform == "tpu":
        whole = mesh_trace.summarize(src, len(devs))
        small = mesh_trace.summarize(dst, len(devs))
        whole.pop("path"), small.pop("path")
        print("the cut file reduces to the same table:", whole == small)
        small["spans"] = {k: len(v) for k, v in small["spans"].items()}
        print(json.dumps(small, indent=1))
        print("setup.collectives", json.dumps(run["setup"]["collectives"]))
    shutil.rmtree(ctx["trace_dir"], ignore_errors=True)  # what came back is the cut
    return 0


if __name__ == "__main__":
    sys.exit(main())
