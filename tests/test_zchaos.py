"""Chaos engineering for the serving stack (chaos/, serve/wal.py, the
hardened ScenarioServer, registry counters, health probe retries).

Late-alphabet file on purpose: the scenario-level tests compile the
shared pbft n=8 exact-sampler template (the same TPL tests/test_zserve.py
uses — whichever file runs first pays the one compile, the other rides
the warm registry) and the kill -9 drill is a slow-marked subprocess
pair outside the tier-1 window (ROADMAP.md)."""

import json
import os
import pathlib
import subprocess
import sys
import threading
import time

import pytest

from blockchain_simulator_tpu.chaos import inject, invariants, scenarios
from blockchain_simulator_tpu.serve import (
    CircuitBreaker,
    ScenarioServer,
    WriteAheadLog,
)
from blockchain_simulator_tpu.utils import aotcache, health, obs

REPO = pathlib.Path(__file__).resolve().parent.parent

TPL = scenarios.TPL


# ------------------------------------------------------------ inject -------

def test_chaos_point_is_noop_when_disarmed():
    inject.chaos_point("sweep.dyn_dispatch", canon=None)  # must not raise
    assert inject._controller is None


def test_controller_counted_fail_and_schedule():
    with inject.controller(3) as ctl:
        ctl.fail_next("site.a", n=2)
        with pytest.raises(inject.ChaosFault):
            inject.chaos_point("site.a")
        with pytest.raises(inject.ChaosFault):
            inject.chaos_point("site.a")
        inject.chaos_point("site.a")  # exhausted: disarmed again
        inject.chaos_point("site.b")  # other sites never armed
        assert ctl.schedule() == ["site.a:fail", "site.a:fail"]
    # uninstalled on exit
    assert inject._controller is None
    inject.chaos_point("site.a")


def test_controller_poison_matches_req_id_only():
    with inject.controller(4) as ctl:
        ctl.poison("solo", "bad-id")
        inject.chaos_point("solo", req_id="good-id")
        with pytest.raises(inject.ChaosFault):
            inject.chaos_point("solo", req_id="bad-id")
        with pytest.raises(inject.ChaosFault):  # poison persists (count=None)
            inject.chaos_point("solo", req_id="bad-id")
        assert ctl.schedule() == ["solo:poison", "solo:poison"]


def test_controller_hang_sleeps_then_disarms():
    with inject.controller(5) as ctl:
        ctl.hang_next("site", 0.05, n=1)
        t0 = time.monotonic()
        inject.chaos_point("site")
        assert time.monotonic() - t0 >= 0.05
        t1 = time.monotonic()
        inject.chaos_point("site")
        assert time.monotonic() - t1 < 0.05
        assert ctl.schedule() == ["site:hang"]


def test_controller_rng_is_seed_deterministic():
    a = inject.ChaosController(99).rng.random()
    b = inject.ChaosController(99).rng.random()
    assert a == b
    assert inject.ChaosController(100).rng.random() != a


# --------------------------------------------------------- invariants ------

def test_ledger_and_checker_clean():
    led = invariants.Ledger()
    led.submitted("a")
    led.record("a", {"status": "ok"})
    stats = {"received": 1, "served": 1, "errors": 0, "timeouts": 0,
             "replayed": 0, "rejected": {}, "queue_depth": 0}
    assert invariants.check_server(led, stats) == []


def test_checker_flags_lost_and_double_answers():
    led = invariants.Ledger()
    led.submitted("lost")
    led.submitted("double")
    led.record("double", {"status": "ok"})
    led.record("double", {"status": "ok"})
    stats = {"received": 2, "served": 2, "errors": 0, "timeouts": 0,
             "replayed": 0, "rejected": {}, "queue_depth": 0}
    v = invariants.check_server(led, stats)
    assert any("'lost'" in x and "0 terminal" in x for x in v)
    assert any("'double'" in x and "2 terminal" in x for x in v)


def test_ledger_retry_attempts_are_separate():
    led = invariants.Ledger()
    led.submitted("r")
    led.record("r", {"status": "error", "kind": "dispatch-failed"})
    led.submitted("r")
    led.record("r", {"status": "error", "kind": "dispatch-failed"})
    assert led.kinds() == {"r": ["dispatch-failed", "dispatch-failed"]}
    stats = {"received": 2, "served": 0, "errors": 2, "timeouts": 0,
             "replayed": 0, "rejected": {}, "queue_depth": 0}
    assert invariants.check_server(led, stats) == []


def test_checker_flags_unbalanced_stats_and_depth():
    stats = {"received": 3, "served": 1, "errors": 0, "timeouts": 0,
             "replayed": 0, "rejected": {}, "queue_depth": 1}
    v = invariants.check_server(None, stats)
    assert any("queue_depth" in x for x in v)
    assert any("accounting broken" in x for x in v)


def test_checker_flags_missing_access_log_lines(tmp_path):
    log = tmp_path / "access.jsonl"
    log.write_text(json.dumps({"id": "seen", "status": "ok"}) + "\n")
    led = invariants.Ledger()
    for rid in ("seen", "unseen"):
        led.submitted(rid)
        led.record(rid, {"status": "ok"})
    stats = {"received": 2, "served": 2, "errors": 0, "timeouts": 0,
             "replayed": 0, "rejected": {}, "queue_depth": 0}
    v = invariants.check_server(led, stats, log_path=str(log))
    assert v == ["request 'unseen' has no access-log line (manifest lost)"]
    # replayed ids demand a replayed-marked line
    v = invariants.check_server(None, stats, log_path=str(log),
                                replayed_ids=["seen"])
    assert any("replayed" in x for x in v)


def test_registry_monotone():
    before = {"hits": 5, "misses": 2, "evictions": 0}
    assert invariants.registry_monotone(before, dict(before, hits=9)) == []
    v = invariants.registry_monotone(before, dict(before, misses=1))
    assert v and "misses" in v[0]


def test_obs_read_jsonl_tolerates_torn_lines(tmp_path):
    p = tmp_path / "log.jsonl"
    p.write_text('{"a": 1}\n{"torn\n[1, 2]\n{"b": 2}\n')
    assert obs.read_jsonl(str(p)) == [{"a": 1}, {"b": 2}]
    assert obs.read_jsonl(str(tmp_path / "missing.jsonl")) == []


# ---------------------------------------------------------------- WAL ------

def test_wal_pending_dedup_and_done(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "wal.jsonl"))
    wal.append_admit("a", {"n": 8})
    wal.append_admit("b", {"n": 16})
    wal.append_admit("a", {"n": 8})    # client retry: one replay only
    wal.append_done("b", 200)
    wal.close()
    assert WriteAheadLog(wal.path).pending() == [("a", {"n": 8})]


def test_wal_quarantined_but_undone_still_replays(tmp_path):
    """A crash between the quarantine mark and the answer must not strand
    the admission: the id stays pending (the server's quarantine set —
    seeded from the log — keeps its replay solo), while a quarantined id
    that WAS answered is retired like any other."""
    wal = WriteAheadLog(str(tmp_path / "wal.jsonl"))
    wal.append_admit("poison-undone", {"n": 8})
    wal.append_quarantine("poison-undone")
    wal.append_admit("poison-done", {"n": 8})
    wal.append_quarantine("poison-done")
    wal.append_done("poison-done", 500)
    wal.append_admit("fine", {"n": 8})
    wal.close()
    w2 = WriteAheadLog(wal.path)
    assert w2.pending() == [("poison-undone", {"n": 8}),
                           ("fine", {"n": 8})]
    assert w2.quarantined_ids() == {"poison-undone", "poison-done"}


def test_wal_replay_of_quarantined_id_dispatches_solo(tmp_path, monkeypatch):
    """End to end: a quarantined-but-undone admit replays SOLO on restart
    — answered (poison gone: served), never batched."""
    runs = tmp_path / "runs.jsonl"
    monkeypatch.setenv(obs.RUNS_ENV, str(runs))
    wal = WriteAheadLog(str(tmp_path / "wal.jsonl"))
    wal.append_admit("q-pend", dict(TPL, seed=9))
    wal.append_quarantine("q-pend")
    wal.close()
    srv = ScenarioServer(max_batch=2, max_wait_ms=5.0, wal_path=wal.path)
    t0 = time.monotonic()
    while srv.stats()["queue_depth"] and time.monotonic() - t0 < 120:
        time.sleep(0.02)
    st = srv.stats()
    srv.close()
    assert st["replayed"] == 1 and st["served"] == 1
    assert st["quarantine_size"] == 1
    recs = obs.read_jsonl(str(runs))
    (rec,) = [r for r in recs if r.get("replayed") is True]
    assert rec["id"] == "q-pend"
    assert rec["batch"]["mode"] == "quarantined-solo"


def test_wal_torn_tail_and_compact(tmp_path):
    wal = WriteAheadLog(str(tmp_path / "wal.jsonl"))
    wal.append_admit("a", {"n": 8})
    wal.append_admit("b", {"n": 8})
    wal.append_done("a", 200)
    wal.append_quarantine("q")
    wal.close()
    with open(wal.path, "a") as f:
        f.write('{"wal": 1, "op": "admit", "id": "torn", "req"')  # mid-crash
    w2 = WriteAheadLog(wal.path)
    assert w2.pending() == [("b", {"n": 8})]
    assert w2.compact() == 1
    recs = w2.records()
    ops = sorted((r["op"], r["id"]) for r in recs)
    assert ops == [("admit", "b"), ("quarantine", "q")]
    # appends after compact land in the new file
    w2.append_done("b", 200)
    assert WriteAheadLog(wal.path).pending() == []


# ----------------------------------------------------- circuit breaker -----

def test_circuit_breaker_state_machine():
    br = CircuitBreaker(threshold=2, cooldown_s=10.0, max_cooldown_s=15.0)
    assert br.allow_batched(0.0) and br.state == "closed"
    br.record(True, 1.0)
    assert br.state == "closed"          # 1 failure < threshold
    br.record(True, 2.0)
    assert br.state == "open" and br.opens == 1
    assert not br.allow_batched(3.0)     # cooling down
    assert br.allow_batched(12.5)        # cooldown elapsed: half-open probe
    assert br.state == "half-open"
    br.record(True, 13.0)                # probe failed: reopen, doubled
    assert br.state == "open" and br.opens == 2
    assert br.cooldown == 15.0           # doubled 10 -> 20, capped at 15
    assert not br.allow_batched(20.0)
    assert br.allow_batched(30.0)
    br.record(False, 31.0)               # probe succeeded: closed, reset
    assert br.state == "closed" and br.failures == 0
    assert br.cooldown == 10.0
    snap = br.snapshot()
    assert snap["state"] == "closed" and snap["opens"] == 2


# --------------------------------------------- scenario-level drills -------

def _run_clean(name, **kw):
    rep = scenarios.run_scenario(name, seed=1234, **kw)
    assert rep["violations"] == [], rep["violations"]
    return rep


def test_scenario_dispatch_fail_breaker_trajectory():
    rep = _run_clean("dispatch-fail")
    assert rep["modes"] == ["degraded-solo", "degraded-solo",
                            "breaker-solo", "batched"]
    assert rep["breaker_states"] == ["closed"]
    assert rep["chaos_schedule"] == ["sweep.dyn_dispatch:fail"] * 2


def test_scenario_dispatch_hang_timeouts_are_typed():
    rep = _run_clean("dispatch-hang")
    assert rep["outcomes"]["stuck-c"] == ["timeout"]
    assert rep["outcomes"]["hung-a"] == ["ok"]
    assert rep["counts"]["timeouts"] == 2


def test_scenario_health_flap_matches_pattern():
    rep = _run_clean("health-flap")
    n_sick = rep["pattern"].count("sick")
    assert rep["counts"]["rejected"].get("admission-paused", 0) == n_sick
    assert rep["counts"]["served"] == 8 - n_sick


def test_scenario_batcher_kill_supervised_restart():
    rep = _run_clean("batcher-kill")
    assert rep["counts"]["batcher_restarts"] == 1
    assert all(k == ["ok"] for k in rep["outcomes"].values())


def test_scenario_queue_storm_accounts_overflow():
    rep = _run_clean("queue-storm", quick=True)
    assert rep["counts"]["rejected"] == {"queue-full": 3}
    assert rep["counts"]["served"] == 3


def test_scenario_poison_quarantined_never_rebatched():
    rep = _run_clean("poison-request")
    assert rep["outcomes"]["poison-1"] == ["dispatch-failed"] * 2
    assert rep["peer_modes"] == ["degraded-solo", "batched", "batched"]
    assert rep["counts"]["quarantined"] == 1


def test_scenario_crash_restart_replays_bit_equal():
    rep = _run_clean("crash-restart", quick=True)
    assert rep["replayed"] == 3
    assert rep["replay_divergence"] == 0
    assert rep["replay_again"] == 0  # second restart: exactly-once held


def test_scenario_sweep_kill9_resumes_without_recompute():
    rep = _run_clean("sweep-kill9")
    assert rep["killed"] is True
    assert rep["chunks_before_kill"] == 2
    assert rep["chunks_resumed"] == 2
    assert rep["resume_misses"] == 0
    assert rep["rows_bit_equal"] is True
    assert rep["chaos_schedule"] == ["sweep.chunk:fail"]


def test_scenario_query_kill9_resumes_without_recompute():
    rep = _run_clean("query-kill9")
    assert rep["generations_before_kill"] == 2
    assert rep["cached_steps_on_resume"] == 2
    assert rep["resume_misses"] == 0
    assert rep["answer_bit_equal"] is True
    assert rep["replay_again"] == 0
    assert rep["chaos_schedule"] == ["query.step:fail"]


def test_scenario_sweep_wedge_takes_degrade_path():
    rep = _run_clean("sweep-wedge")
    assert rep["events"] == ["deadline", "retry", "deadline", "degrade"]
    assert rep["rows_bit_equal"] is True
    assert rep["chaos_schedule"] == ["sweep.chunk:hang"] * 2


def test_scenario_determinism_same_seed_twice():
    """The drill's core claim at test scale: one chaos seed, two runs,
    byte-equal normalized summaries."""
    r1 = scenarios.run_scenario("health-flap", seed=77)
    r2 = scenarios.run_scenario("health-flap", seed=77)
    assert r1 == r2
    r3 = scenarios.run_scenario("queue-storm", seed=78, quick=True)
    r4 = scenarios.run_scenario("queue-storm", seed=78, quick=True)
    assert r3 == r4


# ------------------------------------------------ server hardening ---------

def test_shutdown_flushes_queued_as_typed_503(tmp_path, monkeypatch):
    """The vanish fix: a server whose batcher never ran (or died) still
    answers every admitted request at close() — typed 503 shutting-down
    WITH a rejection manifest line, never silence."""
    runs = tmp_path / "runs.jsonl"
    monkeypatch.setenv(obs.RUNS_ENV, str(runs))
    srv = ScenarioServer(max_batch=2, max_wait_ms=5.0, start=False)
    p1 = srv.submit(dict(TPL, seed=1, id="stranded-1"))
    p2 = srv.submit(dict(TPL, seed=2, id="stranded-2"))
    srv.close()
    r1, r2 = p1.result(10), p2.result(10)
    assert r1["code"] == r2["code"] == 503
    assert r1["kind"] == r2["kind"] == "shutting-down"
    st = srv.stats()
    assert st["rejected"]["shutting-down"] == 2
    assert st["queue_depth"] == 0
    recs = obs.read_jsonl(str(runs))
    flushed = [r for r in recs if r.get("kind") == "shutting-down"]
    assert {r["id"] for r in flushed} == {"stranded-1", "stranded-2"}
    assert all(r["manifest"]["obs_schema"] == obs.OBS_SCHEMA
               for r in flushed)
    assert invariants.check_server(None, st, log_path=str(runs)) == []


def test_close_drain_false_rejects_instead_of_dispatching():
    srv = ScenarioServer(max_batch=8, max_wait_ms=60000.0)
    pend = srv.submit(dict(TPL, seed=3, id="fast-exit"))
    srv.close(drain=False)
    resp = pend.result(10)
    assert resp["kind"] == "shutting-down"
    assert srv.stats()["served"] == 0


def test_wal_replay_served_and_marked(tmp_path, monkeypatch):
    """In-process crash: admitted requests survive into a new server via
    the WAL, answer exactly once with the replayed mark, and a third
    server replays nothing."""
    runs = tmp_path / "runs.jsonl"
    monkeypatch.setenv(obs.RUNS_ENV, str(runs))
    wal = str(tmp_path / "wal.jsonl")
    crashed = ScenarioServer(max_batch=2, max_wait_ms=5.0, wal_path=wal,
                             start=False)
    crashed.submit(dict(TPL, seed=5, id="pend-1"))
    crashed._wal.close()
    del crashed
    srv = ScenarioServer(max_batch=2, max_wait_ms=5.0, wal_path=wal)
    t0 = time.monotonic()
    while srv.stats()["queue_depth"] and time.monotonic() - t0 < 120:
        time.sleep(0.02)
    st = srv.stats()
    srv.close()
    assert st["replayed"] == 1 and st["served"] == 1
    assert st["wal"]["replayed_at_start"] == 1
    recs = obs.read_jsonl(str(runs))
    replayed = [r for r in recs if r.get("replayed") is True]
    assert len(replayed) == 1 and replayed[0]["id"] == "pend-1"
    assert replayed[0]["status"] == "ok"
    srv3 = ScenarioServer(max_batch=2, max_wait_ms=5.0, wal_path=wal,
                          start=False)
    assert srv3.stats()["wal"]["replayed_at_start"] == 0
    srv3.close()


def test_wal_replay_of_now_invalid_request_is_typed(tmp_path):
    """A WAL admit that no longer parses replays into a typed rejection
    (access-logged), not a crash or a silent drop."""
    wal = WriteAheadLog(str(tmp_path / "wal.jsonl"))
    wal.append_admit("bad-1", {"protocol": "nope", "n": 8})
    wal.close()
    srv = ScenarioServer(max_batch=2, max_wait_ms=5.0, wal_path=wal.path,
                         start=False)
    st = srv.stats()
    srv.close()
    assert st["replayed"] == 1
    assert st["rejected"].get("invalid-request") == 1
    assert invariants.check_server(None, st) == []


# ------------------------------------------ registry under thread storm ----

def test_registry_eviction_vs_inflight_builds_thread_storm(monkeypatch):
    """The satellite: a tiny-LRU registry being evicted while cached
    factory builds are in flight across a thread storm — every call gets
    the right value, counters stay consistent, nothing deadlocks."""
    reg = aotcache.ExecutableRegistry(maxsize=2)
    monkeypatch.setattr(aotcache, "registry", reg)

    build_calls = []

    @aotcache.cached_factory("storm-test")
    def factory(tag):
        build_calls.append(tag)
        time.sleep(0.002)  # keep builds in flight across evictions
        return ("value", tag)

    n_threads, n_rounds, keys = 8, 25, ["a", "b", "c", "d"]
    errors = []
    barrier = threading.Barrier(n_threads)

    def storm(tid):
        try:
            barrier.wait(timeout=30)
            for i in range(n_rounds):
                tag = keys[(tid + i) % len(keys)]
                got = factory(tag)
                if got != ("value", tag):
                    errors.append(f"wrong value for {tag}: {got}")
        except Exception as e:  # noqa: BLE001 - the test IS the guard
            errors.append(f"{type(e).__name__}: {e}")

    threads = [threading.Thread(target=storm, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors, errors[:5]
    stats = reg.stats()
    total = n_threads * n_rounds
    assert stats["hits"] + stats["misses"] == total
    assert stats["misses"] == len(build_calls)
    assert stats["misses"] >= len(keys)       # every key built at least once
    # builds happen OUTSIDE the lock (by design), so two threads may race
    # the same cold key and both build it — entry count and evictions stay
    # bounded regardless, which is the storm's actual contract
    assert stats["entries"] <= reg.maxsize
    assert stats["evictions"] > 0             # the LRU churned under fire


# ------------------------------------------------- health probe retry ------

def test_health_supervised_retries_before_wedged():
    """A silent probe is retried with backoff before the wedged verdict;
    the record carries the attempt count (the admission-gate satellite)."""
    t0 = time.monotonic()
    rec = health.probe_backend_supervised(
        patience_s=0.05, attempts=2, backoff_s=0.05, rng=lambda: 0.5,
    )
    assert rec["verdict"] == "wedged"
    assert rec["attempts"] == 2
    assert rec["supervised"] is True
    assert "killed and reaped" in rec["error"]  # no probe child left behind
    assert time.monotonic() - t0 >= 0.05 * 2 + 0.05  # two probes + backoff


def test_health_cli_has_attempts_flag():
    from blockchain_simulator_tpu.utils.health import main as health_main

    with pytest.raises(SystemExit):
        health_main(["--help"])


# ---------------------------------------------------------- slow drills ----

@pytest.mark.slow
def test_chaos_drill_quick_cli(tmp_path):
    """The lint.sh chaos gate end to end: subprocess drill, deterministic
    double-runs, chaos_* trajectory rows in runs.jsonl."""
    runs = tmp_path / "runs.jsonl"
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "chaos_drill.py"), "--quick"],
        capture_output=True, text=True, timeout=560, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "BLOCKSIM_RUNS_JSONL": str(runs)},
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    summary = json.loads(proc.stdout.strip().splitlines()[-1])
    assert summary["ok"] and summary["deterministic"]
    assert summary["invariant_violations"] == 0
    assert set(summary["scenarios"]) == set(scenarios.SCENARIOS)
    metrics = {r.get("metric") for r in obs.read_jsonl(str(runs))}
    assert {"chaos_invariant_violations", "chaos_replay_divergence"} \
        <= metrics


@pytest.mark.slow
def test_kill9_daemon_replays_admitted_requests(tmp_path):
    """The acceptance criterion: a daemon SIGKILLed mid-traffic with
    admitted-but-unanswered requests replays each exactly once on
    restart, bit-equal to references (the drill's kill -9 leg, via the
    full-mode crash-restart scenario run)."""
    out = tmp_path / "chaos.json"
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "chaos_drill.py"),
         "--scenarios", "crash-restart", "--out", str(out)],
        capture_output=True, text=True, timeout=560, cwd=REPO,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    artifact = json.loads(out.read_text())
    kill9 = artifact["kill9"]
    assert kill9["warm_ok"] == 8
    assert kill9["killed_with_pending"] == 3
    assert kill9["replayed_on_restart"] == 3      # exactly once each
    assert kill9["replayed_on_second_restart"] == 0
    assert kill9["replay_divergence"] == 0        # bit-equal to references
    assert kill9["violations"] == []
