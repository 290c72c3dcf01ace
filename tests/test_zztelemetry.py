"""Telemetry core (utils/telemetry.py) + its serving/sweep/chaos wiring.

Late-alphabet name per the tier-1 window rule (ROADMAP): the whole-stack
drills here compile serve executables and must not displace the early
suite inside the timeout window.
"""

import json
import os
import threading

import pytest

from blockchain_simulator_tpu.chaos import invariants
from blockchain_simulator_tpu.utils import obs, telemetry

TPL = {"protocol": "pbft", "n": 8, "sim_ms": 200, "stat_sampler": "exact"}


# ------------------------------------------------------------ ids/context


def test_trace_header_round_trip():
    ctx = telemetry.TraceContext(telemetry.new_trace_id(),
                                 telemetry.new_span_id())
    assert telemetry.parse_header(ctx.header()) == ctx
    # garbage never rejects a request — it reads as "no trace"
    for bad in (None, "", "nope", "xyz:", ":abc", "g!:12", 7):
        assert telemetry.parse_header(bad) is None


def test_span_nesting_parents_and_tls_restore():
    with telemetry.capture() as buf:
        assert telemetry.current() is None
        with telemetry.span("outer", a=1) as octx:
            assert telemetry.current() == octx
            with telemetry.span("inner") as ictx:
                assert telemetry.current() == ictx
        assert telemetry.current() is None
    inner, outer = buf
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert inner["parent"] == octx.span_id
    assert inner["trace"] == outer["trace"] == octx.trace_id
    assert outer["attrs"] == {"a": 1}


def test_span_error_status_and_reraise():
    with telemetry.capture() as buf:
        with pytest.raises(ValueError):
            with telemetry.span("boom"):
                raise ValueError("x")
    assert buf[0]["status"] == "error"


def test_span_log_file_armed_by_env(tmp_path, monkeypatch):
    path = tmp_path / "spans.jsonl"
    monkeypatch.setenv(telemetry.SPANS_ENV, str(path))
    telemetry.emit("probe.span", 0.0, 0.001, note="hi")
    recs = obs.read_jsonl(str(path))
    assert len(recs) == 1 and recs[0]["name"] == "probe.span"
    monkeypatch.delenv(telemetry.SPANS_ENV)
    telemetry.emit("probe.span2", 0.0, 0.001)
    assert len(obs.read_jsonl(str(path))) == 1  # disarmed = no write


# ---------------------------------------------------------------- metrics


def test_metrics_registry_counter_histogram_and_exposition():
    reg = telemetry.MetricsRegistry()
    c = reg.counter("x_total", kind="a")
    assert reg.counter("x_total", kind="a") is c  # get-or-create identity
    c.inc()
    c.inc(2)
    h = reg.histogram("lat_ms")
    for v in (3, 7, 40, 900):
        h.observe(v)
    expo = reg.exposition()
    assert "# TYPE x_total counter" in expo
    assert 'x_total{kind="a"} 3' in expo
    assert "# TYPE lat_ms histogram" in expo
    assert 'lat_ms_bucket{le="5"} 1' in expo        # cumulative
    assert 'lat_ms_bucket{le="+Inf"} 4' in expo
    assert "lat_ms_count 4" in expo and "lat_ms_sum 950" in expo
    snap = reg.snapshot()
    assert snap["counters"]['x_total{kind="a"}'] == 3
    assert snap["histograms"]["lat_ms"]["count"] == 4


def test_histogram_percentiles_bucket_resolution():
    h = telemetry.Histogram("h", {}, threading.Lock())
    assert h.percentile(99) == 0.0  # empty
    for v in (3, 7, 40, 900):
        h.observe(v)
    # rank-2 of 4 at q=50 falls in the le=10 bucket
    assert h.percentile(50) == 10.0
    # the +Inf tail answers the max observed, never infinity
    h2 = telemetry.Histogram("h2", {}, threading.Lock(), bounds=(1.0,))
    h2.observe(123456.0)
    assert h2.percentile(99) == 123456.0
    assert set(h.percentiles()) == {"p50", "p95", "p99"}


# --------------------------------------------------------- flight recorder


def test_flight_recorder_ring_bounded_and_dump(tmp_path, monkeypatch):
    fr = telemetry.FlightRecorder(capacity=4)
    for i in range(10):
        fr.note("e", i=i)
    snap = fr.snapshot()
    assert len(snap) == 4 and [r["i"] for r in snap] == [6, 7, 8, 9]
    # disarmed: no env, no path -> no file, returns None
    assert fr.dump("test") is None
    out = tmp_path / "flight.json"
    assert fr.dump("test", str(out)) == str(out)
    doc = json.loads(out.read_text())
    assert doc["reason"] == "test" and len(doc["records"]) == 4
    assert "metrics" in doc
    # env arms the directory form
    monkeypatch.setenv(telemetry.FLIGHT_ENV, str(tmp_path))
    path = fr.dump("shutdown")
    assert path and os.path.exists(path) and "shutdown" in path


# ----------------------------------------------------------- log rotation


def test_append_jsonl_rotates_at_size_cap(tmp_path, monkeypatch):
    path = tmp_path / "runs.jsonl"
    monkeypatch.setenv(obs.LOG_MAX_ENV, "200")
    # the size check is amortized (obs._ROTATE_EVERY appends between
    # stats), so write enough records to cross a check boundary well
    # past the cap
    for i in range(10 * obs._ROTATE_EVERY):
        obs.append_jsonl({"i": i, "pad": "x" * 20}, str(path))
    assert os.path.exists(str(path) + ".1")  # rotated generation
    assert os.path.getsize(str(path)) < 200 + 40 * obs._ROTATE_EVERY
    # the shared reader stitches the retained generation in front of the
    # live file, so a mid-drill rotation never severs a reader's history
    live = obs.read_jsonl(str(path))
    old = obs._read_jsonl_one(str(path) + ".1")
    assert old and live[-1]["i"] == 10 * obs._ROTATE_EVERY - 1
    assert len(live) > len(obs._read_jsonl_one(str(path)))
    # in-order across the generation seam
    idx = [r["i"] for r in live]
    assert idx == sorted(idx)
    # cap 0 disables rotation
    monkeypatch.setenv(obs.LOG_MAX_ENV, "0")
    before = os.path.getmtime(str(path) + ".1")
    for i in range(2 * obs._ROTATE_EVERY):
        obs.append_jsonl({"i": i, "pad": "x" * 20}, str(path))
    assert os.path.getmtime(str(path) + ".1") == before
    assert obs.rotate_if_over(str(path), max_bytes=0) is False


# -------------------------------------------------------- serving wiring


def test_server_emits_request_span_tree_and_latency_stats():
    from blockchain_simulator_tpu.serve import ScenarioServer

    with telemetry.capture() as spans:
        with ScenarioServer(max_batch=2, max_wait_ms=50.0) as srv:
            a = srv.submit(dict(TPL, seed=1, id="t1"))
            b = srv.submit(dict(TPL, seed=2, id="t2",
                                faults={"n_byzantine": 1}))
            ra, rb = a.result(300), b.result(300)
            stats = srv.stats()
    assert ra["status"] == "ok" and rb["status"] == "ok"
    roots = [s for s in spans if s["name"] == "serve.request"]
    assert {s["attrs"]["id"] for s in roots} == {"t1", "t2"}
    for root in roots:
        kids = [s for s in spans if s.get("parent") == root["id"]
                and s["trace"] == root["trace"]]
        names = {s["name"] for s in kids}
        assert {"serve.admit", "serve.queue_wait", "serve.batch_wait",
                "serve.dispatch", "serve.answer"} <= names
        # the segments tile the request: leaf wall ~== root wall
        leaf = sum(s["dur_ms"] for s in kids)
        assert leaf <= root["dur_ms"] * 1.05
        assert leaf >= root["dur_ms"] * 0.90
        disp = next(s for s in kids if s["name"] == "serve.dispatch")
        assert disp["attrs"]["bucket"] == 2  # pad-bucket provenance
    # /stats latency percentiles from the histograms (satellite 1)
    lat = stats["latency_ms"]
    assert set(lat) == {"request", "queue_wait", "batch_wait", "dispatch"}
    assert lat["request"]["p50"] >= lat["dispatch"]["p50"] > 0


def test_server_rejection_spans_and_counter_reconciliation():
    from blockchain_simulator_tpu.serve import ScenarioServer, ServeError

    before = telemetry.metrics.snapshot()
    with telemetry.capture() as spans:
        srv = ScenarioServer(max_batch=2, max_wait_ms=5.0, max_queue=1,
                             start=False)
        srv.submit(dict(TPL, seed=2, id="q-ok"))
        with pytest.raises(ServeError):
            srv.submit(dict(TPL, seed=3, id="q-over"))  # queue-full
        srv.start()
        srv.close()
    after = telemetry.metrics.snapshot()
    roots = {s["attrs"]["id"]: s for s in spans
             if s["name"] == "serve.request"}
    assert roots["q-over"]["status"] == "error"
    assert roots["q-over"]["attrs"]["outcome"] == "queue-full"
    # conservation holds across admit/reject/serve (satellite 3)
    assert invariants.check_telemetry(before, after) == []


def test_http_daemon_propagates_trace_header_and_serves_metrics():
    import urllib.request

    from blockchain_simulator_tpu.serve.__main__ import make_httpd
    from blockchain_simulator_tpu.serve.server import ScenarioServer

    server = ScenarioServer(max_batch=2, max_wait_ms=10.0)
    httpd = make_httpd(server, "127.0.0.1", 0)
    port = httpd.server_address[1]
    t = threading.Thread(target=httpd.serve_forever, daemon=True)
    t.start()
    base = f"http://127.0.0.1:{port}"
    try:
        ctx = telemetry.TraceContext("ab" * 8, "cd" * 4)
        req = urllib.request.Request(
            f"{base}/scenario",
            data=json.dumps(dict(TPL, seed=5, id="hdr-1")).encode(),
            headers={"Content-Type": "application/json",
                     telemetry.TRACE_HEADER: ctx.header()},
        )
        with telemetry.capture() as spans:
            with urllib.request.urlopen(req, timeout=300) as r:
                body = json.loads(r.read())
        assert body["status"] == "ok"
        root = next(s for s in spans if s["name"] == "serve.request")
        # the replica's tree hangs off the router's send span
        assert root["trace"] == ctx.trace_id
        assert root["parent"] == ctx.span_id
        # /metrics: Prometheus text exposition
        with urllib.request.urlopen(f"{base}/metrics", timeout=60) as r:
            assert r.headers["Content-Type"].startswith("text/plain")
            expo = r.read().decode()
        assert "blocksim_serve_request_ms_bucket" in expo
        assert "blocksim_serve_received_total" in expo
    finally:
        httpd.shutdown()
        httpd.server_close()
        server.close()


def test_access_log_lines_carry_trace_id(tmp_path, monkeypatch):
    from blockchain_simulator_tpu.serve import ScenarioServer

    log = tmp_path / "access.jsonl"
    monkeypatch.setenv(obs.RUNS_ENV, str(log))
    with ScenarioServer(max_batch=1, max_wait_ms=5.0) as srv:
        r = srv.request(dict(TPL, seed=9, id="logged-1"), wait_s=300)
    assert r["status"] == "ok"
    assert "trace" not in r  # responses stay trace-free (determinism)
    recs = [x for x in obs.read_jsonl(str(log))
            if x.get("id") == "logged-1"]
    assert recs and isinstance(recs[0].get("trace"), str)


def test_router_trace_tree_spans_fleet_and_stats_percentiles():
    from blockchain_simulator_tpu.chaos.fleet_scenarios import LocalReplica
    from blockchain_simulator_tpu.serve.router import FleetRouter

    rep = LocalReplica("tele-rep", max_batch=2, max_wait_ms=10.0)
    try:
        with telemetry.capture() as spans:
            router = FleetRouter([rep], probe=False)
            try:
                resp = router.request(dict(TPL, seed=21, id="fl-1"),
                                      wait_s=300)
                stats = router.stats()
            finally:
                router.close()
        assert resp["status"] == "ok"
        root = next(s for s in spans if s["name"] == "router.request")
        send = next(s for s in spans if s["name"] == "router.send")
        serve_root = next(s for s in spans if s["name"] == "serve.request")
        assert send["parent"] == root["id"]
        assert serve_root["trace"] == root["trace"]
        assert serve_root["parent"] == send["id"]
        assert serve_root["attrs"].get("replica") == "tele-rep"
        assert stats["latency_ms"]["request"]["p99"] > 0
    finally:
        rep.close()


# ------------------------------------------------------------ sweep wiring


def test_journaled_sweep_emits_chunk_spans(tmp_path):
    from blockchain_simulator_tpu.models.base import canonical_fault_cfg
    from blockchain_simulator_tpu.parallel.journal import SweepJournal
    from blockchain_simulator_tpu.parallel.sweep import run_dyn_points
    from blockchain_simulator_tpu.utils.config import SimConfig

    cfg = SimConfig(protocol="pbft", n=8, sim_ms=200, stat_sampler="exact")
    canon = canonical_fault_cfg(cfg)
    journal = SweepJournal(str(tmp_path / "sweep.jsonl"))
    points = [(cfg, 0), (cfg, 1), (cfg, 2), (cfg, 3)]
    with telemetry.capture() as spans:
        run_dyn_points(canon, points, record=False, journal=journal,
                       chunk_size=2)
    chunk_spans = [s for s in spans if s["name"] == "sweep.chunk"]
    assert len(chunk_spans) == 2
    assert {s["attrs"]["index"] for s in chunk_spans} == {0, 1}
    assert all(s["attrs"]["arm"] == "primary" for s in chunk_spans)
    # resumed chunks are reads, not dispatches: no new chunk spans
    with telemetry.capture() as spans2:
        run_dyn_points(canon, points, record=False,
                       journal=SweepJournal(str(tmp_path / "sweep.jsonl")),
                       chunk_size=2)
    assert [s for s in spans2 if s["name"] == "sweep.chunk"] == []


def test_supervisor_degrade_notes_flight_recorder():
    from blockchain_simulator_tpu.parallel import journal as journal_mod

    sup = journal_mod.ChunkSupervisor(deadline_s=None, retries=0,
                                      backoff_s=0.0)
    telemetry.flight.reset()

    def primary():
        raise RuntimeError("primary down")

    rows, events = journal_mod.run_supervised(primary, lambda: ["row"],
                                              sup, key="k1")
    assert rows == ["row"] and "degrade" in events
    kinds = [r.get("event") for r in telemetry.flight.snapshot()
             if r.get("kind") == "event"]
    assert "sweep.error" in kinds and "sweep.degrade" in kinds


# ----------------------------------------------------- determinism / rules


def test_same_drill_twice_normalizes_to_equal_span_trees():
    from blockchain_simulator_tpu.serve import ScenarioServer

    def run_once():
        with telemetry.capture() as spans:
            with ScenarioServer(max_batch=2, max_wait_ms=100.0) as srv:
                p1 = srv.submit(dict(TPL, seed=4, id="d1"))
                p2 = srv.submit(dict(TPL, seed=5, id="d2",
                                     faults={"n_byzantine": 1}))
                p1.result(300), p2.result(300)
        return invariants.normalize_spans(spans)

    assert run_once() == run_once()


def test_normalize_spans_excludes_sweep_and_strips_timing():
    spans = [
        {"kind": "span", "name": "sweep.chunk", "trace": "t", "id": "a",
         "parent": None, "dur_ms": 5, "status": "ok"},
        {"kind": "span", "name": "serve.request", "trace": "t2", "id": "b",
         "parent": None, "dur_ms": 17.3, "status": "ok",
         "attrs": {"id": "r1", "outcome": "served", "size": 3}},
    ]
    norm = invariants.normalize_spans(spans)
    assert norm == ["serve.request[id=r1;outcome=served]~ok"]


def test_no_telemetry_call_site_in_traced_code():
    """The host-side-only rule (ISSUE 14 satellite): traced code — the
    models and ops packages, whose functions run under jit/vmap/scan —
    must never touch utils/telemetry.py; spans and counters are host
    syncs.  Source-level pin, the telemetry corollary of the jaxlint
    host-sync-in-traced rule."""
    import blockchain_simulator_tpu

    pkg = os.path.dirname(blockchain_simulator_tpu.__file__)
    for sub in ("models", "ops"):
        for root, _dirs, files in os.walk(os.path.join(pkg, sub)):
            for fname in files:
                if not fname.endswith(".py"):
                    continue
                src = open(os.path.join(root, fname)).read()
                assert "telemetry" not in src, (
                    f"{sub}/{fname} references telemetry — traced code "
                    "is host-side-telemetry-free by rule")


# ------------------------------------- spans on the profiler's clock; scopes


def _host_events(trace_dir):
    """Every host event of a captured trace as ``(name, start_ns, dur_ns,
    stats, thread)`` (jax.profiler.ProfileData alone)."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for i, line in enumerate(plane.lines):
            out.extend((e.name, e.start_ns, e.duration_ns, dict(e.stats), i)
                       for e in line.events
                       if e.name.startswith(("t.", "serve.", "sweep.")))
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """ONE short profiler session (some 0.15 s of work, every program warmed
    before it) over: two recorded spans and an annotation-only one, two lone
    requests through a ScenarioServer, one run_seed_sweep.  Returns the span
    records, the flight ring's new entries and the trace's host events."""
    import time

    import jax

    from blockchain_simulator_tpu.parallel.sweep import run_seed_sweep
    from blockchain_simulator_tpu.serve import ScenarioServer
    from blockchain_simulator_tpu.utils.config import SimConfig

    cfg = SimConfig(protocol="pbft", n=8, sim_ms=200, stat_sampler="exact")
    seeds = [11, 12, 13]
    trace_dir = tmp_path_factory.mktemp("profile")
    # a lone flush long enough (some 55 ms at sim_ms=400) that the batcher
    # loop's own lines between two states, 0.4-0.5 ms a request whatever a
    # flush costs, stay under 1% of its thread: at sim_ms=200 a flush is
    # 11-17 ms and the states cover 96.7-97.6% of the stretch, parent and
    # PR 52 alike (test_batcher_states_tile_the_batcher_thread asks for 97%)
    served = dict(TPL, sim_ms=400)
    with ScenarioServer(max_batch=2, max_wait_ms=2.0) as srv:
        srv.request(dict(served, seed=1), wait_s=300)  # warm: the solo program
        run_seed_sweep(cfg, seeds)                  # warm: the 3-lane program
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        telemetry.flight.reset()
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
        try:
            with telemetry.capture() as spans:
                with telemetry.span("t.first", rows=3):
                    time.sleep(0.004)
                with telemetry.span("t.state", record=False, size=2):
                    time.sleep(0.002)
                with telemetry.span("t.second"):
                    time.sleep(0.006)
                for i in (2, 3):
                    r = srv.request(dict(served, seed=i, id=f"tr-{i}"),
                                    wait_s=300)
                    assert r["status"] == "ok"
                rows = run_seed_sweep(cfg, seeds)
        finally:
            jax.profiler.stop_trace()
    assert len(rows) == len(seeds)
    return {"spans": list(spans), "flight": telemetry.flight.snapshot(),
            "events": _host_events(trace_dir), "seeds": seeds}


def test_span_and_its_twin_agree_on_the_trace_clock(traced):
    recs = {r["name"]: r for r in traced["spans"] if r["name"].startswith("t.")}
    twins = {}
    for name, start, dur, stats, _ in traced["events"]:
        if "span" in stats:
            trace_id, _, sid = str(stats["span"]).partition(":")
            twins[sid] = (name, start, dur, trace_id)
    # the twin carries the span's name, trace and attrs
    name, _, _, trace_id = twins[recs["t.first"]["id"]]
    assert name == "t.first" and trace_id == recs["t.first"]["trace"]
    first = next(e for e in traced["events"] if e[0] == "t.first")
    assert first[3]["rows"] == 3
    # ONE conversion, from any pair, places every record on the trace clock
    offset = telemetry.trace_clock_offset_ns(
        [recs["t.first"]], {k: v[1] for k, v in twins.items()})
    for rec in traced["spans"]:
        if rec["id"] not in twins:
            continue  # synthesized at answer time: no twin
        a, b = telemetry.on_trace_clock(rec, offset)
        _, start, dur, _ = twins[rec["id"]]
        assert abs(a - start) < 1e6, rec["name"]          # within 1 ms
        assert abs((b - a) - dur) < 1e6, rec["name"]
    assert telemetry.trace_clock_offset_ns([], {}) is None


def test_annotation_only_span_leaves_no_record(traced):
    assert "t.state" in {e[0] for e in traced["events"]}  # the twin is there
    assert "t.state" not in {r["name"] for r in traced["spans"]}
    ring = {r.get("name") for r in traced["flight"]}
    assert "t.first" in ring and "t.state" not in ring
    assert not any(n and n.startswith("serve.batcher.") for n in ring)
    state = next(e for e in traced["events"] if e[0] == "t.state")
    assert state[3] == {"size": 2}  # no span id either


def test_solo_dispatch_children_tile_their_parent(traced):
    spans = traced["spans"]
    parents = [s for s in spans if s["name"] == "serve.dispatch"
               and s["attrs"]["id"].startswith("tr-")]
    assert len(parents) == 2
    for p in parents:
        kids = [s for s in spans if s.get("parent") == p["id"]
                and s["trace"] == p["trace"]]
        assert [k["name"] for k in kids] == [
            "serve.dispatch.operands", "serve.dispatch.execute",
            "serve.dispatch.readback"]
        total = sum(k["dur_ms"] for k in kids)
        assert p["dur_ms"] * 0.85 <= total <= p["dur_ms"] * 1.02 + 0.05
        # the segment stays a child of the request root: the root-level
        # tiling is untouched
        root = next(s for s in spans if s["id"] == p["parent"])
        assert root["name"] == "serve.request"


def test_solo_dispatch_children_carry_the_round_trip_counts(traced):
    """A lone flush's host link, counted on its spans: the operands ran no
    device program, the readback was ONE fetch of pbft's nine metric leaves.
    The attrs ride the records and the trace's twins alike."""
    spans = traced["spans"]
    for p in (s for s in spans if s["name"] == "serve.dispatch"
              and s["attrs"]["id"].startswith("tr-")):
        kids = sorted((s for s in spans if s.get("parent") == p["id"]),
                      key=lambda s: s["ts"])
        assert [k["name"] for k in kids] == [
            "serve.dispatch.operands", "serve.dispatch.execute",
            "serve.dispatch.readback"]
        operands, execute, readback = (k["attrs"] for k in kids)
        assert operands == {"id": p["attrs"]["id"], "device_programs": 0}
        assert execute == {"id": p["attrs"]["id"]}
        assert readback["leaves"] == 9 and readback["fetches"] == 1
        assert readback["bytes"] > 0
        # one after another inside the parent (how much of it they cover:
        # test_solo_dispatch_children_tile_their_parent)
        for a, b in zip(kids, kids[1:]):
            assert a["ts"] + a["dur_ms"] / 1e3 <= b["ts"] + 1e-4
    twins = [e[3] for e in traced["events"]
             if e[0] == "serve.dispatch.readback"]
    assert len(twins) == 2
    assert all(t["leaves"] == 9 and t["fetches"] == 1 for t in twins)


def test_batcher_states_tile_the_batcher_thread(traced):
    states = sorted((e for e in traced["events"]
                     if e[0].startswith("serve.batcher.")),
                    key=lambda e: e[1])
    assert {e[0] for e in states} == {
        "serve.batcher.idle", "serve.batcher.hold", "serve.batcher.flush"}
    assert len({e[4] for e in states}) == 1  # one thread
    for a, b in zip(states, states[1:]):
        assert a[1] + a[2] <= b[1] + 1e3  # in sequence, never nested
    covered = sum(e[2] for e in states)
    span = states[-1][1] + states[-1][2] - states[0][1]
    assert covered >= 0.97 * span
    flushes = [e for e in states if e[0] == "serve.batcher.flush"]
    assert len(flushes) == 2
    assert all(e[3] == {"size": 1, "bucket": 1, "mode": "solo"}
               for e in flushes)
    # a flush holds its request's dispatch
    execs = [e for e in traced["events"]
             if e[0] == "serve.dispatch.execute"]
    assert len(execs) == 2
    for f, x in zip(flushes, sorted(execs, key=lambda e: e[1])):
        assert f[1] <= x[1] and x[1] + x[2] <= f[1] + f[2]


def test_seed_sweep_emits_operands_execute_readback_with_rows(traced):
    n = len(traced["seeds"])
    for rec, name in zip(
            [s for s in traced["spans"] if s["name"].startswith("sweep.")],
            ["sweep.operands", "sweep.execute", "sweep.readback"]):
        assert rec["name"] == name
        # the readback also says what it fetched (tests/test_zsweep_readback.py)
        counts = {k: v for k, v in rec["attrs"].items()
                  if k not in ("leaves", "bytes")}
        assert counts == {"rows": n, "lanes": n}
    twins = [e for e in traced["events"] if e[0].startswith("sweep.")]
    assert [e[0] for e in sorted(twins, key=lambda e: e[1])] == [
        "sweep.operands", "sweep.execute", "sweep.readback"]
    assert all(e[3]["rows"] == n for e in twins)


def _all_scopes():
    from blockchain_simulator_tpu.models import (base, mixed, paxos, pbft,
                                                 pbft_round, raft, raft_hb)
    from blockchain_simulator_tpu.ops import (delay, delivery, linkclass, mesh,
                                              ring)

    return (pbft_round.SCOPES + pbft.SCOPES + delivery.SCOPES
            + delay.SCOPES + ring.SCOPES + base.SCOPES
            + mixed.SCOPES + raft.SCOPES + raft_hb.SCOPES
            + paxos.SCOPES + mesh.SCOPES + linkclass.SCOPES)


@pytest.fixture(scope="module")
def lowered_programs(shared):
    """The op_name metadata of the engines' lowered programs (nothing is
    compiled or run): the pbft tick engine on per-edge, stat and gossip
    delivery, the pbft round engine, and the raft and paxos tick engines
    for the delivery ops only they call, and the paxos tick engine on a
    gossip relay (its flood decode does nothing elsewhere); two ops no engine
    calls are lowered alone; the lane-batched pbft tick program, where
    ``gated`` does work of its own; the paxos engine sharded over a 2-device
    mesh, on the relay (``ops.mesh.pmax`` / ``.psum``) and on the full mesh
    (``ops.mesh.gather``); and last a small mixed program on its fast path
    (both arms
    of its cond are lowered), which holds the raft and pbft tick engines
    under ``mixed.*``; before it, the pbft tick engine under the forging
    attack.

    Each text is lowered once a run of the suite (tests/conftest.py
    ``shared``).  ``--dist load`` hands this test's cases to every worker
    at once, so each worker starts on another text and finds the others'
    done when it comes to them."""
    import jax
    import jax.numpy as jnp

    from blockchain_simulator_tpu import runner
    from blockchain_simulator_tpu.ops import delay, delivery
    from blockchain_simulator_tpu.parallel import shard, sweep
    from blockchain_simulator_tpu.parallel.mesh import make_mesh
    from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig

    cfgs = [
        SimConfig(protocol="pbft", n=8, sim_ms=200),
        SimConfig(protocol="pbft", n=8, sim_ms=200, delivery="stat",
                  schedule="tick"),
        SimConfig(protocol="pbft", n=8, sim_ms=200, delivery="stat",
                  schedule="round", model_serialization=False),
        SimConfig(protocol="pbft", n=16, sim_ms=200, topology="gossip",
                  degree=4),
        SimConfig(protocol="raft", n=8, sim_ms=200),
        SimConfig(protocol="raft", n=8, sim_ms=200, delivery="stat",
                  schedule="tick"),
        SimConfig(protocol="paxos", n=8, sim_ms=200),
        SimConfig(protocol="paxos", n=16, sim_ms=200, topology="gossip",
                  degree=4, paxos_retry_timeout_ms=600),
    ]

    def solo(cfg):
        return lambda: jax.jit(runner.make_sim_fn(cfg)).lower(
            jax.random.key(0)).as_text(debug_info=True)

    def sharded(cfg):
        mesh = make_mesh(n_node_shards=2, devices=jax.devices()[:2])
        return lambda: shard.make_sharded_sim_fn.__wrapped__(cfg, mesh).lower(
            jax.random.key(0)).as_text(debug_info=True)

    probs = delay.uniform_probs(3, 6)
    # the round engine took the stacked stat round trip until PR 45; it
    # takes the chain by rows now, and the op is the fused push's reference
    rt_probs = delay.roundtrip_probs(3, 6)
    builds = [solo(c) for c in cfgs] + [
        lambda: jax.jit(
            lambda k, m: delivery.bcast_slots_stat(k, m, probs)
        ).lower(jax.random.key(0), jnp.ones((8, 4), jnp.int32))
        .as_text(debug_info=True),
        lambda: jax.jit(
            lambda k, m: delivery.roundtrip_reply_counts_stat(
                k, m, 7, rt_probs)
        ).lower(jax.random.key(0), jnp.ones((8,), bool))
        .as_text(debug_info=True),
        lambda: sweep._batched_fn.__wrapped__(cfgs[0], None).lower(
            jax.vmap(jax.random.key)(jnp.arange(2, dtype=jnp.uint32))
        ).as_text(debug_info=True),
        # link classes (only a classed program holds the delay lines'
        # ``ops.linkclass.*`` and the ``ops.delivery.*_classed`` arms), [11]
        solo(SimConfig(protocol="pbft", n=8, sim_ms=200,
                       link_classes=(4, 3, 1),
                       link_class_delay_ms=((3, 12, 30), (10, 4, 25),
                                            (30, 20, 5)))),
    ]
    if len(jax.devices()) >= 2:
        builds += [sharded(cfgs[-1]), sharded(cfgs[-2])]
    builds += [
        # a crash schedule over Raft with terms (only a program under one
        # holds ``raft.tick.fault`` and its gate's taken trip), fourth to last
        solo(SimConfig(protocol="raft", n=8, sim_ms=200, raft_terms=True,
                       model_serialization=False,
                       faults=FaultConfig(crashes=2, first_ms=40,
                                          period_ms=80, downtime_ms=30))),
        # Raft with terms (only a ``raft_terms`` program holds
        # ``raft.tick.term`` and the denial's value-max unicast)
        solo(SimConfig(protocol="raft", n=8, sim_ms=200, raft_terms=True,
                       model_serialization=False)),
        # the forging attack (only a ``byz_forge`` program holds its scope),
        # second to last
        solo(SimConfig(protocol="pbft", n=8, sim_ms=200, delivery="stat",
                       schedule="tick",
                       faults=FaultConfig(n_byzantine=1, byz_forge=True))),
        solo(SimConfig(protocol="mixed", n=24, mixed_shards=4, sim_ms=400,
                       delivery="stat", model_serialization=False)),
    ]
    worker = int((os.environ.get("PYTEST_XDIST_WORKER") or "gw0")[2:])
    workers = int(os.environ.get("PYTEST_XDIST_WORKER_COUNT") or 1)
    n = len(builds)
    texts = [None] * n
    for i in ((worker * n // workers + k) % n for k in range(n)):
        texts[i] = shared(f"zztelemetry.lowered.{i}", builds[i])
    return texts


@pytest.mark.parametrize("scope", _all_scopes())
def test_lowered_programs_carry_the_scope(scope, lowered_programs):
    """Every name in the SCOPES tuples is on the op_name path of some
    operation of a lowered program (HLO metadata: nothing computed
    changes), as a whole path component."""
    assert any(f"{scope}/" in text for text in lowered_programs), scope
    if scope.startswith("ops.linkclass.") or scope.endswith("_classed"):
        # what link classes add: in the classed program alone
        assert f"{scope}/" in lowered_programs[11]
        assert not any(f"{scope}/" in t for t in
                       lowered_programs[:11] + lowered_programs[12:])
        return
    if scope == "pbft.tick.forge":
        assert f"pbft.tick.prepare/{scope}/" in lowered_programs[-2]
        assert not any(f"{scope}/" in t for t in lowered_programs[:-2])
        return
    if scope in ("raft.tick.fault", "gate.raft.fault_taken"):
        # what a crash schedule adds: in the program under one alone
        assert f"{scope}/" in lowered_programs[-4]
        assert not any(f"{scope}/" in t for t in
                       lowered_programs[:-4] + lowered_programs[-3:])
        return
    if scope == "raft.tick.term":
        # what terms add: in the two programs with terms alone (third and
        # fourth to last)
        assert all(f"{scope}/" in t for t in lowered_programs[-4:-2])
        assert not any(f"{scope}/" in t for t in
                       lowered_programs[:-4] + lowered_programs[-2:])
        return
    if scope.startswith("paxos.tick."):
        assert f"{scope}/" in lowered_programs[7]  # the relay, one device
    if scope.startswith("pbft.tick."):
        assert f"{scope}/" in lowered_programs[0]
    if scope.startswith("pbft.round."):
        assert f"{scope}/" in lowered_programs[2]
    if scope.startswith(("mixed.", "raft.", "pbft.tick.")):
        # nested, not renamed: raft.tick.* (inside the shard batch's
        # ``vmap(...)`` wrapper) and pbft.tick.* sit under mixed.*
        mixed_text = lowered_programs[-1]
        assert f"{scope}/" in mixed_text or f"({scope})/" in mixed_text


def test_telemetry_report_quick_cli(tmp_path):
    """Slow-marked end-to-end: the lint.sh-chained gate itself."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = tmp_path / "ARTIFACT_telemetry.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "telemetry_report.py"),
         "--quick", "--out", str(out)],
        capture_output=True, text=True, timeout=900, cwd=repo,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-1000:]
    doc = json.loads(out.read_text())
    assert doc["ok"] is True
    assert doc["completeness"]["misses"] == []
    assert doc["coverage"]["best_pct"] >= 95.0


test_telemetry_report_quick_cli = pytest.mark.slow(
    test_telemetry_report_quick_cli)
