"""Scenario serving (serve/): schema, canonicalization-based micro-batching,
typed rejections, the fault drill, and the HTTP daemon surface.

Late-alphabet file on purpose: the subprocess self-test runs outside the
tier-1 window (ROADMAP.md).  Compile cost is kept low by reusing ONE
canonical fault structure (pbft n=8, exact sampler) across most tests —
the process-wide executable registry serves the later ones warm; tests
that count compiles use a unique ``sim_ms`` so their canon is fresh.
"""

import json
import os
import pathlib
import subprocess
import sys
import time

import pytest

from blockchain_simulator_tpu import runner
from blockchain_simulator_tpu.models.base import canonical_fault_cfg
from blockchain_simulator_tpu.serve import (
    AdmissionPausedError,
    InvalidRequestError,
    QueueFullError,
    ScenarioServer,
    ServeError,
    UnbatchableRequestError,
    parse_request,
)
from blockchain_simulator_tpu.serve import dispatch as serve_dispatch
from blockchain_simulator_tpu.utils import aotcache, health, obs
from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig

REPO = pathlib.Path(__file__).resolve().parent.parent

# the shared warm template: most tests batch on this structure
TPL = {"protocol": "pbft", "n": 8, "sim_ms": 200, "stat_sampler": "exact"}


def _norm(m):
    return {k: str(v) for k, v in m.items()}


# ------------------------------------------------------------- schema ------

def test_parse_request_valid_and_canonical_group():
    req = parse_request(dict(TPL, seed=5, faults={"n_byzantine": 2},
                             id="x", timeout_s=3.5), "fallback")
    assert req.req_id == "x"
    assert req.timeout_s == 3.5
    assert req.seed == 5
    assert req.cfg.faults.n_byzantine == 2
    assert req.canon == canonical_fault_cfg(req.cfg)
    # counts AND seed are normalized out of the batch-group key
    other = parse_request(dict(TPL, seed=9, faults={"n_crashed": 1}), "y")
    assert other.canon == req.canon
    # structure splits the group
    dropped = parse_request(dict(TPL, faults={"drop_prob": 0.1}), "z")
    assert dropped.canon != req.canon


@pytest.mark.parametrize("obj,match", [
    (dict(TPL, bogus_field=1), "unknown request field"),
    (dict(TPL, faults={"bogus": 1}), "unknown fault field"),
    (dict(TPL, protocol="nope"), "unknown protocol"),
    (dict(TPL, faults="not-a-dict"), "faults must be"),
    (dict(TPL, faults=[]), "faults must be"),
    (dict(TPL, faults=False), "faults must be"),
    (dict(TPL, n="8"), "must be of type int"),
    (dict(TPL, faults={"drop_prob": "0.5"}), "must be of type float"),
    ("not-a-dict", "JSON object"),
    (dict(TPL, schedule="round", delivery="edge"), "schedule='round'"),
])
def test_parse_request_typed_invalid(obj, match):
    with pytest.raises(InvalidRequestError, match=match) as ei:
        parse_request(obj, "r1")
    assert ei.value.code == 400
    assert ei.value.kind == "invalid-request"


def test_unbatchable_is_typed_end_to_end():
    """The satellite contract: runner.check_batchable raises the typed
    UnbatchableConfigError (still a NotImplementedError for historical
    callers, message text kept), and the serve layer classifies it without
    string-matching."""
    cfg = SimConfig(protocol="mixed", n=32, mixed_shards=4)
    with pytest.raises(runner.UnbatchableConfigError, match="mixed"):
        runner.check_batchable(cfg)
    assert issubclass(runner.UnbatchableConfigError, NotImplementedError)
    with pytest.raises(runner.UnbatchableConfigError):
        runner.make_dyn_sim_fn(cfg)
    with pytest.raises(UnbatchableRequestError, match="mixed") as ei:
        parse_request({"protocol": "mixed", "n": 32, "mixed_shards": 4}, "r")
    assert ei.value.code == 422
    assert ei.value.kind == "unbatchable-config"


def test_bucket_size_powers_of_two():
    assert [serve_dispatch.bucket_size(b, 8) for b in (1, 2, 3, 5, 8)] \
        == [1, 2, 4, 8, 8]
    assert serve_dispatch.bucket_size(3, 4) == 4


# ------------------------------------------------- batching edge cases -----

def test_two_requests_one_executable_bit_equal():
    """Two requests differing only in (seed, fault count) batch into ONE
    vmapped dispatch — exactly one fresh compile — and each answer is
    bit-equal to a solo static run (exact sampler pinned)."""
    tpl = dict(TPL, sim_ms=210)  # unique canon: the compile count is exact
    s0 = aotcache.registry.stats()
    with ScenarioServer(max_batch=2, max_wait_ms=2000.0) as srv:
        p1 = srv.submit(dict(tpl, seed=3))
        p2 = srv.submit(dict(tpl, seed=7, faults={"n_byzantine": 2}))
        r1, r2 = p1.result(300), p2.result(300)
    s1 = aotcache.registry.stats()
    assert r1["status"] == r2["status"] == "ok"
    assert r1["batch"]["size"] == r2["batch"]["size"] == 2
    assert r1["batch"]["mode"] == "batched"
    assert r1["batch"]["group"] == r2["batch"]["group"]
    assert s1["misses"] - s0["misses"] == 1  # ONE executable for the batch
    solo1 = runner.run_simulation(SimConfig(**tpl), seed=3)
    solo2 = runner.run_simulation(
        SimConfig(**tpl, faults=FaultConfig(n_byzantine=2)), seed=7)
    assert _norm(r1["metrics"]) == _norm(solo1)
    assert _norm(r2["metrics"]) == _norm(solo2)


def test_differing_structure_splits_groups():
    tpl = dict(TPL, sim_ms=220)
    with ScenarioServer(max_batch=4, max_wait_ms=150.0) as srv:
        p1 = srv.submit(dict(tpl, seed=1))
        p2 = srv.submit(dict(tpl, seed=1, faults={"drop_prob": 0.25}))
        r1, r2 = p1.result(300), p2.result(300)
    assert r1["status"] == r2["status"] == "ok"
    assert r1["batch"]["group"] != r2["batch"]["group"]
    assert r1["batch"]["size"] == r2["batch"]["size"] == 1
    assert r1["batch"]["mode"] == r2["batch"]["mode"] == "solo"


def test_f0_bit_equal_solo_vs_batched():
    """The sweep.py caveat applied to serving: an f=0 request answers
    bit-equally whether served solo or padded into a batch with an f>0
    peer (exact sampler; the byz_forge sentinel analog of the sweep pin)."""
    tpl = dict(TPL, sim_ms=230)
    with ScenarioServer(max_batch=2, max_wait_ms=1.0) as srv:
        solo = srv.request(dict(tpl, seed=4), wait_s=300)
    assert solo["status"] == "ok" and solo["batch"]["mode"] == "solo"
    with ScenarioServer(max_batch=2, max_wait_ms=2000.0) as srv:
        p1 = srv.submit(dict(tpl, seed=4))
        p2 = srv.submit(dict(tpl, seed=8, faults={"n_byzantine": 2}))
        batched, _ = p1.result(300), p2.result(300)
    assert batched["status"] == "ok"
    assert batched["batch"]["mode"] == "batched"
    assert _norm(batched["metrics"]) == _norm(solo["metrics"])


def test_padding_lanes_do_not_change_answers():
    """3 live requests pad to a 4-lane bucket; every real lane still
    answers bit-equal to its solo run."""
    tpl = dict(TPL, sim_ms=240)
    with ScenarioServer(max_batch=4, max_wait_ms=2000.0) as srv:
        pends = [srv.submit(dict(tpl, seed=10 + i,
                                 faults={"n_byzantine": i}))
                 for i in range(3)]
        rs = [pd.result(300) for pd in pends]
    assert all(r["status"] == "ok" for r in rs)
    assert all(r["batch"]["size"] == 3 for r in rs)
    assert all(r["batch"]["padded"] == 4 for r in rs)
    for i, r in enumerate(rs):
        solo = runner.run_simulation(
            SimConfig(**tpl, faults=FaultConfig(n_byzantine=i)),
            seed=10 + i)
        assert _norm(r["metrics"]) == _norm(solo)


# ------------------------------------------------------- fault drill -------

def test_queue_backpressure_records_rejection(tmp_path, monkeypatch):
    """Overflow -> typed 429 AND a rejection manifest line: no silent
    drops (the acceptance drill's backpressure leg)."""
    runs = tmp_path / "runs.jsonl"
    monkeypatch.setenv(obs.RUNS_ENV, str(runs))
    srv = ScenarioServer(max_batch=2, max_wait_ms=5.0, max_queue=1,
                         start=False)
    srv.submit(dict(TPL, seed=1))
    with pytest.raises(QueueFullError) as ei:
        srv.submit(dict(TPL, seed=2, id="overflow"))
    assert ei.value.code == 429
    recs = [json.loads(ln) for ln in runs.read_text().splitlines()]
    rej = [r for r in recs if r.get("kind") == "queue-full"]
    assert rej and rej[0]["id"] == "overflow" and rej[0]["code"] == 429
    assert rej[0]["manifest"]["obs_schema"] == obs.OBS_SCHEMA
    assert srv.stats()["rejected"]["queue-full"] == 1
    srv.start()   # drain: the admitted request still gets served
    srv.close()
    assert srv.stats()["served"] == 1


def test_health_gate_pauses_then_resumes(tmp_path, monkeypatch):
    runs = tmp_path / "runs.jsonl"
    monkeypatch.setenv(obs.RUNS_ENV, str(runs))
    with ScenarioServer(max_batch=2, max_wait_ms=5.0) as srv:
        srv.set_health("sick")
        assert srv.paused
        with pytest.raises(AdmissionPausedError) as ei:
            srv.submit(dict(TPL, seed=1))
        assert ei.value.code == 503
        srv.set_health({"verdict": "healthy", "backend": "cpu"})
        assert not srv.paused
        assert srv.request(dict(TPL, seed=1), wait_s=300)["status"] == "ok"
    recs = [json.loads(ln) for ln in runs.read_text().splitlines()]
    assert any(r.get("kind") == "admission-paused" for r in recs)


def test_health_log_seeds_admission(tmp_path):
    log = tmp_path / "HEALTH.jsonl"
    log.write_text(json.dumps({"verdict": "healthy"}) + "\n"
                   + json.dumps({"verdict": "wedged"}) + "\n")
    assert health.latest_verdict(str(log))["verdict"] == "wedged"
    assert health.latest_verdict(str(tmp_path / "missing.jsonl")) is None
    srv = ScenarioServer(health_log=str(log), start=False)
    assert srv.paused
    srv.close()


def test_request_timeout_typed(tmp_path, monkeypatch):
    runs = tmp_path / "runs.jsonl"
    monkeypatch.setenv(obs.RUNS_ENV, str(runs))
    srv = ScenarioServer(max_batch=2, max_wait_ms=1.0, start=False)
    pend = srv.submit(dict(TPL, seed=1, timeout_s=0.01))
    time.sleep(0.05)
    srv.start()
    resp = pend.result(60)
    srv.close()
    assert resp["code"] == 504 and resp["kind"] == "timeout"
    assert srv.stats()["timeouts"] == 1
    assert any(json.loads(ln).get("kind") == "timeout"
               for ln in runs.read_text().splitlines())


def test_degrade_to_solo_on_batch_failure(monkeypatch):
    """A failed vmapped dispatch degrades to per-request solo dispatch:
    peers still answer, and the incident lands in degraded_batches."""
    from blockchain_simulator_tpu.parallel import sweep

    def boom(*a, **kw):
        raise RuntimeError("batch peer failed")

    monkeypatch.setattr(sweep, "run_dyn_points", boom)
    tpl = dict(TPL, sim_ms=250)
    with ScenarioServer(max_batch=2, max_wait_ms=2000.0) as srv:
        p1 = srv.submit(dict(tpl, seed=1))
        p2 = srv.submit(dict(tpl, seed=2, faults={"n_byzantine": 1}))
        r1, r2 = p1.result(300), p2.result(300)
        st = srv.stats()
    assert r1["status"] == r2["status"] == "ok"
    assert r1["batch"]["mode"] == r2["batch"]["mode"] == "degraded-solo"
    assert st["degraded_batches"] == 1
    solo = runner.run_simulation(SimConfig(**tpl), seed=1)
    assert _norm(r1["metrics"]) == _norm(solo)


def test_batcher_survives_unexpected_flush_error(monkeypatch):
    """Anything escaping the dispatch layer fails THAT group's futures
    with typed 500s — the batcher thread (and the daemon behind it) keeps
    serving instead of wedging every later client."""
    boom = lambda *a, **kw: (_ for _ in ()).throw(RuntimeError("bug"))  # noqa: E731
    with ScenarioServer(max_batch=2, max_wait_ms=1.0) as srv:
        monkeypatch.setattr(serve_dispatch, "run_batch", boom)
        r1 = srv.request(dict(TPL, seed=1), wait_s=60)
        assert r1["status"] == "error" and r1["code"] == 500
        assert "internal batcher error" in r1["error"]
        monkeypatch.undo()
        r2 = srv.request(dict(TPL, seed=1), wait_s=300)
        assert r2["status"] == "ok"  # the thread survived
        assert srv.stats()["errors"] == 1


def test_prewarm_covers_capped_bucket(monkeypatch):
    """A non-power-of-two max_batch still prewarms its capped bucket —
    bucket_size can dispatch it, so steady-state must never compile it
    inline."""
    seen = []

    def fake_run_batch(reqs, max_batch, **kw):
        seen.append(len(reqs))
        return [(r, {"status": "ok"}) for r in reqs]

    monkeypatch.setattr(serve_dispatch, "run_batch", fake_run_batch)
    srv = ScenarioServer(max_batch=6, start=False)
    srv.prewarm(dict(TPL))
    srv.close()
    assert seen == [1, 2, 4, 6]


def test_solo_dispatch_failure_is_typed_not_fatal(monkeypatch):
    monkeypatch.setattr(serve_dispatch, "_solo_metrics",
                        lambda req: (_ for _ in ()).throw(RuntimeError("x")))
    with ScenarioServer(max_batch=1, max_wait_ms=1.0) as srv:
        resp = srv.request(dict(TPL, seed=1), wait_s=60)
    assert resp["status"] == "error" and resp["code"] == 500
    assert "dispatch failed" in resp["error"]


# ------------------------------------------- the lone flush's host link ----

def _serve_spans(obj):
    """One lone request through a fresh server: ``(response, span records)``."""
    from blockchain_simulator_tpu.utils import telemetry

    with telemetry.capture() as spans:
        with ScenarioServer(max_batch=2, max_wait_ms=1.0) as srv:
            resp = srv.request(obj, wait_s=300)
    assert resp["status"] == "ok", resp
    return resp, list(spans)


@pytest.mark.parametrize("n_byzantine", [0, 1])
@pytest.mark.parametrize("protocol,sim_ms", [
    ("pbft", 200), ("raft", 600), ("paxos", 600)])
def test_lone_flush_equals_static_solo_from_one_fetch(protocol, sim_ms,
                                                      n_byzantine):
    """A lone served request builds its operands on the host (the key from
    host key data, the fault counts as numpy scalars) and reads the final
    state's metric leaves in one fetch: the answer is still
    ``sim_metrics(cfg, make_sim_fn(cfg)(jax.random.key(seed)))`` key for
    key, and ``base.host_final`` hands ``metrics`` the device state's own
    leaves in ``METRIC_FIELDS`` and None elsewhere."""
    import dataclasses

    import jax
    import numpy as np

    from blockchain_simulator_tpu.models import base

    seed = 2_147_483_659  # above int32: the key's low word, not a wrap
    tpl = dict(TPL, protocol=protocol, sim_ms=sim_ms)
    cfg = SimConfig(**tpl, faults=FaultConfig(n_byzantine=n_byzantine))
    resp, spans = _serve_spans(
        dict(tpl, seed=seed, faults={"n_byzantine": n_byzantine}))
    assert resp["batch"]["mode"] == "solo"
    static = base.sim_metrics(
        cfg, runner.make_sim_fn(cfg)(jax.random.key(seed)))
    assert resp["metrics"] == static

    by_name = {s["name"]: s["attrs"] for s in spans}
    assert by_name["serve.dispatch.operands"]["device_programs"] == 0
    fields = base.get_protocol(protocol).METRIC_FIELDS
    readback = by_name["serve.dispatch.readback"]
    assert readback["fetches"] == 1 and readback["bytes"] > 0

    final = serve_dispatch._solo_fn(canonical_fault_cfg(cfg))(
        serve_dispatch._key(seed), np.int32(0), np.int32(n_byzantine))
    picked = base.metric_leaves(cfg, final)
    assert set(picked) <= set(fields)
    assert readback["leaves"] == len(jax.tree.leaves(picked))
    host = base.host_final(cfg, final, picked)
    assert type(host) is type(final)
    for f in dataclasses.fields(final):
        got = getattr(host, f.name)
        if f.name not in picked:
            assert got is None, f.name
            continue
        for a, b in zip(jax.tree.leaves(got),
                        jax.tree.leaves(getattr(final, f.name))):
            assert isinstance(a, np.ndarray)
            np.testing.assert_array_equal(a, np.asarray(b))
    assert base.sim_metrics(cfg, host) == static


def test_lone_committee_flush_keeps_the_stack_own_readback():
    """A committee final is a stacked pytree that topo/committee.metrics
    fetches once itself: the lone flush leaves it to that readback (its
    span nests under ``serve.dispatch.readback``) and still answers as the
    static run does."""
    import jax

    from blockchain_simulator_tpu.models import base

    tpl = dict(TPL, n=16, topology="committee", committees=2)
    resp, spans = _serve_spans(dict(tpl, seed=3))
    cfg = SimConfig(**tpl)
    assert resp["metrics"] == base.sim_metrics(
        cfg, runner.make_sim_fn(cfg)(jax.random.key(3)))
    outer = next(s for s in spans if s["name"] == "serve.dispatch.readback")
    inner = next(s for s in spans if s["name"] == "topo.committee.readback")
    assert inner["parent"] == outer["id"]
    assert outer["attrs"]["fetches"] == 1
    assert outer["attrs"]["leaves"] == inner["attrs"]["leaves"]
    assert outer["attrs"]["bytes"] == inner["attrs"]["bytes"]


def test_lone_flush_refuses_a_prng_whose_key_it_cannot_build():
    """The served key is threefry's seeding done on the host.  Under another
    default PRNG implementation those words would be another key than the
    static run's, so the dispatch fails, typed, and runs nothing."""
    import jax

    with jax.default_prng_impl("rbg"):
        with pytest.raises(NotImplementedError, match="threefry2x32"):
            serve_dispatch._key(3)
        (_, resp), = serve_dispatch.run_batch(
            [parse_request(dict(TPL, seed=3), "r")], max_batch=1)
    assert resp["status"] == "error" and resp["code"] == 500
    assert "dispatch failed" in resp["error"]
    assert "threefry2x32" in resp["error"]


def test_lone_flush_runs_no_device_program_but_the_solo_executable():
    """The build log's view of a lone flush: on a shape the process has not
    seen, every backend compile it causes is the solo executable's (no
    eager one-primitive program for a key, an upload or a slice); on a
    warmed server, one more lone request builds nothing at all."""
    def fresh(before):
        return [r for r in aotcache.registry.builds()
                if r["id"] not in before]

    tpl = dict(TPL, n=12, sim_ms=170)  # a canon no other test compiles
    with ScenarioServer(max_batch=1, max_wait_ms=1.0) as srv:
        seen = {r["id"] for r in aotcache.registry.builds()}
        cold = srv.request(dict(tpl, seed=5, faults={"n_byzantine": 1}), 300)
        built = fresh(seen)
        seen |= {r["id"] for r in built}
        warm = srv.request(dict(tpl, seed=6, faults={"n_crashed": 2}), 300)
        late = fresh(seen)
    assert cold["status"] == warm["status"] == "ok"
    compiled = [r["attrs"]["fun"] for r in built
                if r["name"] == "build.compile"]
    assert compiled == ["jit(sim)"]
    assert [r["attrs"]["factory"] for r in built
            if r["name"] == "build.factory"] == ["serve-solo"]
    assert late == []


# ----------------------------------------------------- stats / registry ----

def test_registry_stats_snapshot():
    snap = aotcache.registry.stats_snapshot()
    for k in ("hits", "misses", "evictions", "by_factory"):
        assert k in snap
    assert sum(snap["by_factory"].values()) == snap["entries"]


def test_server_stats_and_access_log(tmp_path, monkeypatch):
    runs = tmp_path / "runs.jsonl"
    monkeypatch.setenv(obs.RUNS_ENV, str(runs))
    with ScenarioServer(max_batch=2, max_wait_ms=1.0) as srv:
        resp = srv.request(dict(TPL, seed=1), wait_s=300)
        st = srv.stats()
    assert resp["status"] == "ok"
    assert st["served"] == 1 and st["batches"] == 1
    assert st["occupancy"] == {"1": 1}
    assert st["knobs"]["max_batch"] == 2
    assert "by_factory" in st["cache"]  # the stats_snapshot satellite
    # access log: one finalized manifest line for the served request
    recs = [json.loads(ln) for ln in runs.read_text().splitlines()]
    served = [r for r in recs if r.get("status") == "ok"]
    assert served and served[0]["batch"]["mode"] == "solo"
    assert served[0]["manifest"]["config_hash"]
    assert "cache" in served[0]["manifest"]


# ---------------------------------------------------------- HTTP surface ---

def test_http_daemon_in_process():
    from blockchain_simulator_tpu.serve.__main__ import make_httpd
    import threading
    import urllib.error
    import urllib.request

    with ScenarioServer(max_batch=2, max_wait_ms=5.0) as srv:
        httpd = make_httpd(srv, "127.0.0.1", 0)
        port = httpd.server_address[1]
        t = threading.Thread(target=httpd.serve_forever, daemon=True)
        t.start()
        base = f"http://127.0.0.1:{port}"

        def call(path, obj=None):
            data = None if obj is None else json.dumps(obj).encode()
            req = urllib.request.Request(base + path, data=data)
            try:
                with urllib.request.urlopen(req, timeout=120) as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        code, body = call("/scenario", dict(TPL, seed=1))
        assert code == 200 and body["status"] == "ok"
        code, body = call("/scenario",
                          {"protocol": "mixed", "n": 32, "mixed_shards": 4})
        assert code == 422 and body["kind"] == "unbatchable-config"
        code, body = call("/scenario", dict(TPL, bogus=1))
        assert code == 400
        code, body = call("/stats")
        assert code == 200 and body["served"] >= 1
        code, body = call("/healthz")
        assert code == 200 and body["ready"]
        code, body = call("/health", {"verdict": "sick"})
        assert code == 200 and body["paused"]
        code, body = call("/healthz")
        assert code == 503
        code, body = call("/health", {"verdict": "healthy"})
        assert not body["paused"]
        # a garbled/empty health push must NOT flip admission: 400, still up
        code, body = call("/health", {})
        assert code == 400 and body["kind"] == "invalid-request"
        code, body = call("/healthz")
        assert code == 200 and body["ready"]
        code, body = call("/nope")
        assert code == 404
        httpd.shutdown()
        t.join(timeout=30)


@pytest.mark.slow
def test_serve_selftest_cli(tmp_path):
    """The lint.sh serve smoke end to end: subprocess daemon, HTTP drill,
    serve_rps/serve_p99_ms trajectory rows in runs.jsonl."""
    runs = tmp_path / "runs.jsonl"
    proc = subprocess.run(
        [sys.executable, "-m", "blockchain_simulator_tpu.serve",
         "--self-test", "--self-test-requests", "6"],
        capture_output=True, text=True, timeout=480, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "BLOCKSIM_RUNS_JSONL": str(runs)},
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["ok"] and all(summary["checks"].values())
    recs = [json.loads(ln) for ln in runs.read_text().splitlines()]
    metrics = {r.get("metric") for r in recs}
    assert {"serve_rps", "serve_p99_ms", "serve_p50_ms"} <= metrics
