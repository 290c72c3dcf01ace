"""Fleet serving: claim/lease semantics, the router, WAL handoff edge
cases, prewarm-from-observed-traffic, replica-labeled health verdicts.

Late-alphabet file on purpose (the tier-1 window rule, ROADMAP.md): the
handful of tests that really dispatch ride the same pbft n=8 exact-
sampler template tests/test_zchaos.py / test_zserve.py warm; everything
else runs against scripted stub replicas (chaos/fleet_scenarios.py) —
real sockets, zero compiles."""

import json
import os
import pathlib
import subprocess
import sys
import threading
import time

import pytest

from blockchain_simulator_tpu.chaos import fleet_scenarios, invariants
from blockchain_simulator_tpu.chaos.fleet_scenarios import (
    LocalReplica,
    StubReplica,
)
from blockchain_simulator_tpu.chaos.scenarios import TPL
from blockchain_simulator_tpu.serve import ScenarioServer, fleet
from blockchain_simulator_tpu.serve.router import FleetRouter
from blockchain_simulator_tpu.serve.wal import WriteAheadLog
from blockchain_simulator_tpu.utils import health, obs

REPO = pathlib.Path(__file__).resolve().parent.parent


# ------------------------------------------------------------- claims ------

def test_claim_race_exactly_one_winner(tmp_path):
    wal = str(tmp_path / "r.wal")
    wins = []
    ts = [threading.Thread(
        target=lambda i=i: wins.append(fleet.claim_wal(wal, f"o{i}")))
        for i in range(6)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert sum(wins) == 1
    assert fleet.claim_owner(wal) is not None
    assert fleet.claim_wal(wal, "latecomer") is False


def test_torn_claim_stolen_exactly_once(tmp_path):
    wal = str(tmp_path / "r.wal")
    # a claimant that died between create and write: claim exists, torn
    with open(fleet.claim_path(wal), "w"):
        pass
    assert fleet.claim_owner(wal) is None  # torn reads as unowned
    wins = []
    ts = [threading.Thread(
        target=lambda i=i: wins.append(fleet.claim_wal(wal, f"s{i}")))
        for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert sum(wins) == 1
    owner = fleet.claim_owner(wal)
    assert owner is not None and owner.startswith("s")
    # the steal lock is held: a torn claim can never be stolen twice —
    # even after the winner's claim were torn again, .steal blocks
    assert fleet.claim_wal(wal, "again") is False


def test_release_claim_reopens_the_lease(tmp_path):
    wal = str(tmp_path / "r.wal")
    assert fleet.claim_wal(wal, "one")
    fleet.release_claim(wal)
    assert fleet.claim_owner(wal) is None
    assert fleet.claim_wal(wal, "two")
    assert fleet.claim_owner(wal) == "two"


# ------------------------------------------------------------ handoff ------

def test_handoff_wal_replays_pending_in_order_and_retires(tmp_path,
                                                          monkeypatch):
    wal = str(tmp_path / "dead.wal")
    w = WriteAheadLog(wal, sync=True)
    w.append_admit("a", {"x": 1})
    w.append_admit("b", {"x": 2})
    w.append_done("a")  # answered before the crash: must NOT replay
    w.append_admit("c", {"x": 3})
    w.close()
    log = str(tmp_path / "access.jsonl")
    monkeypatch.setenv(obs.RUNS_ENV, log)
    posted, answered = [], []

    def post(obj):
        posted.append(obj["id"])
        return 200, {"id": obj["id"], "status": "ok", "code": 200}

    res = fleet.handoff_wal(wal, "router-A", post,
                            on_answer=lambda rid, b: answered.append(rid))
    assert res["claimed"] is True
    assert res["replayed"] == ["b", "c"] == posted == answered
    # done-marked + released: a second handoff claims but finds nothing
    res2 = fleet.handoff_wal(wal, "router-B", post)
    assert res2["claimed"] is True and res2["pending"] == 0
    # every replay has exactly one replayed-marked access-log line
    marked = [r["id"] for r in obs.read_jsonl(log)
              if r.get("replayed") is True]
    assert sorted(marked) == ["b", "c"]


def test_handoff_wal_loser_replays_nothing(tmp_path):
    wal = str(tmp_path / "dead.wal")
    w = WriteAheadLog(wal, sync=True)
    w.append_admit("a", {"x": 1})
    w.close()
    assert fleet.claim_wal(wal, "other-router")
    posted = []
    res = fleet.handoff_wal(wal, "me", lambda obj: posted.append(obj))
    assert res["claimed"] is False and res["owner"] == "other-router"
    assert posted == [] and res["replayed"] == []


def test_handoff_replay_of_invalid_answers_typed_rejection(tmp_path,
                                                           monkeypatch):
    """A pending admit that no longer parses replays as its typed 400 —
    through a REAL peer — and still retires (done-marked)."""
    wal = str(tmp_path / "dead.wal")
    w = WriteAheadLog(wal, sync=True)
    w.append_admit("bad", {"protocol": "pbft", "n": 8, "bogus_field": 1})
    w.close()
    log = str(tmp_path / "access.jsonl")
    monkeypatch.setenv(obs.RUNS_ENV, log)
    peer = LocalReplica("peer-x", max_batch=2, max_wait_ms=5.0)
    try:
        import urllib.request

        def post(obj):
            req = urllib.request.Request(
                f"{peer.base_url}/scenario", data=json.dumps(obj).encode(),
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=60) as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        answers = {}
        res = fleet.handoff_wal(wal, "me", post,
                                on_answer=answers.__setitem__)
        assert res["claimed"] and res["replayed"] == ["bad"]
        assert answers["bad"]["kind"] == "invalid-request"
        assert answers["bad"]["replayed"] is True
    finally:
        peer.close()
    assert WriteAheadLog(wal).pending() == []


def test_replica_restart_while_wal_claimed_skips_replay(tmp_path):
    """The restart-during-handoff edge: a replica coming back while a
    router holds its WAL lease must NOT replay (the claim holder owns the
    pending ids); after release, a restart replays them."""
    wal = str(tmp_path / "r.wal")
    w = WriteAheadLog(wal, sync=True)
    w.append_admit("p1", dict(TPL, seed=1, id="p1"))
    w.close()
    assert fleet.claim_wal(wal, "router-Z")
    srv = ScenarioServer(wal_path=wal, start=False)
    try:
        stats = srv.stats()
        assert stats["replayed"] == 0
        assert stats["wal"]["claimed_by"] == "router-Z"
        assert stats["wal"]["replayed_at_start"] == 0
    finally:
        srv.close()
    fleet.release_claim(wal)
    srv2 = ScenarioServer(wal_path=wal, start=False)
    try:
        assert srv2.stats()["replayed"] == 1
        assert srv2.stats()["wal"]["claimed_by"] is None
    finally:
        srv2.close()


# ------------------------------------------------------------- router ------

def test_router_retry_bounded_on_429(tmp_path):
    a = StubReplica("a", mode="reject-429")
    b = StubReplica("b", mode="reject-429")
    router = FleetRouter([a, b], retries=2, retry_backoff_s=0.01,
                         probe=False, validate=False, owner="t")
    try:
        resp = router.request({"id": "q1"}, wait_s=30)
        assert resp["kind"] == "queue-full"
        st = router.stats()
        assert st["retries"] == 2 and st["received"] == 1
        a.mode = b.mode = "ok"
        assert router.request({"id": "q2"}, wait_s=30)["status"] == "ok"
    finally:
        router.close()
        a.close()
        b.close()


def test_router_fails_over_on_refused_connection():
    a = StubReplica("a", mode="ok")
    b = StubReplica("b", mode="ok")
    a.die()  # connection refused: provably never admitted → safe retry
    router = FleetRouter([a, b], retries=2, retry_backoff_s=0.01,
                         probe=False, validate=False, route="rr",
                         owner="t")
    try:
        for i in range(3):  # rr lands on the dead one at least once
            resp = router.request({"id": f"f{i}"}, wait_s=30)
            assert resp["status"] == "ok"
    finally:
        router.close()
        b.close()


def test_router_hedge_answers_once_and_counts_the_late_loser():
    slow = StubReplica("slow", mode="slow", slow_s=0.6)
    fast = StubReplica("fast", mode="ok")
    router = FleetRouter([slow, fast], hedge_ms=50, probe=False,
                         validate=False, route="rr", owner="t")
    try:
        resp = router.request({"id": "h1"}, wait_s=30)
        assert resp["status"] == "ok" and resp.get("hedged") is True
        deadline = time.monotonic() + 10
        while router.stats()["late_answers"] < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        st = router.stats()
        assert st["hedges"] == 1 and st["late_answers"] == 1
        assert sum(st["answered"].values()) == 1  # one answer delivered
    finally:
        router.close()
        slow.close()
        fast.close()


def test_router_parks_broken_send_and_handoff_answers(tmp_path,
                                                      monkeypatch):
    """The fleet death path end to end over stubs: admit-then-die parks
    the send, probes declare the replica dead, the WAL handoff replays on
    the peer and resolves the parked future with the replayed mark."""
    monkeypatch.setenv(obs.RUNS_ENV, str(tmp_path / "access.jsonl"))
    wal = str(tmp_path / "victim.wal")
    victim = StubReplica("victim", mode="admit-die", wal_path=wal)
    peer = StubReplica("peer", mode="ok")
    router = FleetRouter([victim, peer], probe_interval_s=0.05,
                         dead_after=2, validate=False, route="rr",
                         owner="t", request_timeout_s=30)
    try:
        pends = [router.submit({"id": f"p{i}"}) for i in range(4)]
        deadline = time.monotonic() + 10
        while router.stats()["parked_total"] < 1 \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        victim.die()
        assert router.join_handoffs(1, timeout_s=30)
        answers = [p.result(30) for p in pends]
        assert all(a["status"] == "ok" for a in answers)
        assert any(a.get("replayed") for a in answers)
        st = router.stats()
        assert st["received"] == 4 == sum(st["answered"].values())
        assert st["handoffs"][0]["claimed"] is True
        assert st["replicas"]["victim"]["state"] == "dead"
        assert invariants.check_fleet(None, st) == []
    finally:
        router.close()
        peer.close()
        victim.close()


def test_router_validates_at_the_edge():
    a = StubReplica("a", mode="ok")
    router = FleetRouter([a], probe=False, owner="t")  # validate=True
    try:
        resp = router.request({"protocol": "pbft", "n": 8, "wat": 1},
                              wait_s=30)
        assert resp["kind"] == "invalid-request" and resp["code"] == 400
        resp = router.request(dict(TPL, protocol="mixed", n=32), wait_s=30)
        assert resp["kind"] == "unbatchable-config" and resp["code"] == 422
    finally:
        router.close()
        a.close()


# ---------------------------------------------------- fleet scenarios ------

@pytest.mark.parametrize("name", ["fleet-retry-storm",
                                  "fleet-double-claim"])
def test_fleet_scenarios_clean_and_deterministic(name, tmp_path):
    runs = [fleet_scenarios.run_fleet_scenario(
        name, seed=11, workdir=str(tmp_path / f"{name}-{i}"))
        for i in range(2)]
    assert runs[0]["violations"] == []
    assert runs[1]["violations"] == []
    assert runs[0] == runs[1]


def test_fleet_replica_death_scenario_clean(tmp_path):
    rep = fleet_scenarios.run_fleet_scenario(
        "fleet-replica-death", seed=11, workdir=str(tmp_path))
    assert rep["violations"] == []
    assert rep["replay_divergence"] == 0
    assert rep["outcomes"] == {"fcrash-0": ["ok"], "fcrash-1": ["ok"],
                               "fcrash-2": ["ok"]}


def test_fleet_slow_replica_scenario_clean(tmp_path):
    rep = fleet_scenarios.run_fleet_scenario(
        "fleet-slow-replica", seed=11, workdir=str(tmp_path))
    assert rep["violations"] == []
    assert rep["counts"]["hedges"] == 1
    assert rep["counts"]["late_answers"] == 1
    assert rep["chaos_schedule"] == ["fleet.send:slow"]


# ------------------------------------------------- prewarm-from / obs ------

def test_access_log_carries_resubmittable_scenario_template(tmp_path,
                                                            monkeypatch):
    from blockchain_simulator_tpu.serve import parse_request

    log = str(tmp_path / "runs.jsonl")
    monkeypatch.setenv(obs.RUNS_ENV, log)
    with ScenarioServer(max_batch=2, max_wait_ms=5.0) as srv:
        resp = srv.request(dict(TPL, seed=3, id="tpl-1"), wait_s=300)
    assert resp["status"] == "ok"
    recs = [r for r in obs.read_jsonl(log) if r.get("id") == "tpl-1"]
    assert len(recs) == 1
    tpl = recs[0]["scenario"]
    assert tpl["seed"] == 3 and tpl["sim_ms"] == 200
    assert "protocol" not in tpl  # defaults stay out: templates are diffs
    # the template round-trips onto the SAME batch group
    orig = parse_request(dict(TPL, seed=3), "a")
    back = parse_request(dict(tpl), "b")
    assert obs.config_hash(back.canon) == obs.config_hash(orig.canon)


def test_prewarm_from_warms_observed_groups_and_buckets(tmp_path):
    """prewarm_from reads the observed mix — most-frequent groups first,
    only the bucket sizes actually dispatched — not the fixed ladder."""
    log = str(tmp_path / "runs.jsonl")
    hot = {"protocol": "pbft", "n": 8, "sim_ms": 200,
           "stat_sampler": "exact"}
    cold = dict(hot, sim_ms=240)
    with open(log, "w") as f:
        for i in range(3):  # hot group seen at buckets {1, 2}
            f.write(json.dumps({
                "status": "ok", "id": f"h{i}", "scenario": dict(hot, seed=i),
                "batch": {"group": "g-hot", "padded": 1 if i else 2},
            }) + "\n")
        f.write(json.dumps({  # cold group seen once, solo
            "status": "ok", "id": "c0", "scenario": dict(cold, seed=9),
            "batch": {"group": "g-cold", "padded": 1},
        }) + "\n")
        f.write("torn {line\n")  # tolerant reader contract
    with ScenarioServer(max_batch=8, max_wait_ms=5.0) as srv:
        plan = srv.prewarm_from(log)
        assert list(plan) == ["g-hot", "g-cold"]  # frequency order
        assert sorted(plan["g-hot"]["buckets"]) == ["1", "2"]
        assert sorted(plan["g-cold"]["buckets"]) == ["1"]
        assert plan["g-hot"]["requests"] == 3
        # max_groups caps the plan at the most frequent
        assert list(srv.prewarm_from(log, max_groups=1)) == ["g-hot"]


# ------------------------------------------------- health replica label ----

def test_latest_verdict_filters_by_replica(tmp_path):
    log = str(tmp_path / "HEALTH.jsonl")
    with open(log, "w") as f:
        f.write(json.dumps({"verdict": "healthy"}) + "\n")
        f.write(json.dumps({"verdict": "sick", "replica": "r0"}) + "\n")
        f.write(json.dumps({"verdict": "healthy", "replica": "r1"}) + "\n")
    # unlabeled read: the single-daemon behavior — last verdict wins
    assert health.latest_verdict(log)["verdict"] == "healthy"
    # r0 reads its own sick verdict, not r1's healthy one
    assert health.latest_verdict(log, replica="r0")["verdict"] == "sick"
    assert health.latest_verdict(log, replica="r1")["verdict"] == "healthy"
    # a replica with no labeled lines falls back to the unlabeled global
    assert health.latest_verdict(log, replica="r9")["verdict"] == "healthy"
    with open(log, "a") as f:
        f.write(json.dumps({"verdict": "wedged"}) + "\n")
    # an unlabeled (global) verdict gates every replica
    assert health.latest_verdict(log, replica="r1")["verdict"] == "wedged"


def test_probe_backend_carries_replica_label():
    rec = health.probe_backend(replica="r7")
    assert rec["replica"] == "r7"
    assert rec["verdict"] == "healthy"


def test_server_health_seeding_is_replica_scoped(tmp_path):
    log = str(tmp_path / "HEALTH.jsonl")
    with open(log, "w") as f:
        f.write(json.dumps({"verdict": "sick", "replica": "r0"}) + "\n")
        f.write(json.dumps({"verdict": "healthy", "replica": "r1"}) + "\n")
    srv0 = ScenarioServer(health_log=log, replica="r0", start=False)
    srv1 = ScenarioServer(health_log=log, replica="r1", start=False)
    try:
        assert srv0.paused is True   # r0 sees ITS sick verdict
        assert srv1.paused is False  # r1 unaffected by r0's line
        assert srv0.stats()["replica"] == "r0"
    finally:
        srv0.close()
        srv1.close()


# ----------------------------------------------------------- slow legs -----

@pytest.mark.slow
def test_fleet_bench_quick_cli(tmp_path):
    """The CI chain end to end: drill (all four scenarios, twice each) +
    in-process micro-bench, one JSON summary, metrics in runs.jsonl."""
    runs = tmp_path / "runs.jsonl"
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "BLOCKSIM_RUNS_JSONL": str(runs),
           "PYTHONPATH": os.pathsep.join(
               p for p in (str(REPO), os.environ.get("PYTHONPATH")) if p)}
    proc = subprocess.run(
        [sys.executable, "tools/fleet_bench.py", "--quick"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is True
    assert last["invariant_violations"] == 0
    assert last["deterministic"] is True
    assert last["fleet_rps"] > 0
    metrics = {r.get("metric") for r in obs.read_jsonl(str(runs))}
    assert {"fleet_invariant_violations", "fleet_rps"} <= metrics


@pytest.mark.slow
def test_fleet_kill9_subprocess_replicas(tmp_path):
    """The real thing: 2 subprocess daemons, SIGKILL the one holding
    admitted requests, exactly-once replay on the peer, restart replays
    zero (the acceptance drill, also run by tools/fleet_bench.py full)."""
    sys.path.insert(0, str(REPO / "tools"))
    try:
        import fleet_bench
    finally:
        sys.path.pop(0)
    rec = fleet_bench.kill9_leg(seed=1, fleet_root=str(tmp_path))
    assert rec["violations"] == [], rec
    assert rec["replayed"] == 3
    assert rec["replay_divergence"] == 0
    assert rec["replayed_on_restart"] == 0
    assert rec["post_restart_ok"] is True
