"""ARTIFACT_fleet_bench.json generator: the serving fleet under load + fire.

Three legs, one artifact:

- **fleet chaos drill** — every fleet scenario (chaos/fleet_scenarios.py:
  replica death with WAL handoff, slow-replica hedged failover, router
  retry storm, double-claim race) runs TWICE under one seed and must be
  invariant-clean (chaos/invariants.check_fleet) and byte-equal across
  the two runs — the fleet extension of tools/chaos_drill.py's contract;
- **replica kill -9 leg** (full runs) — a REAL 2-replica subprocess fleet
  (serve/fleet.py FleetManager) takes SIGKILL on the replica holding
  admitted-but-unanswered requests mid-traffic; the router lease-claims the dead WAL and replays every
  pending id on the peer exactly once, answers bit-equal (exact sampler)
  to uninterrupted references, and the restarted replica replays ZERO
  (the handoff's done-records retired its backlog);
- **traffic-shaped scaling bench** (full runs) — a seeded generator
  synthesizes million-user-shaped load phases (overdriven capacity,
  diurnal ramp, burst, hot/cold scenario skew, adversarial group mix —
  the runs.jsonl access-log schema end to end) against 1/2/4 replicas,
  charting req/s vs replica count and the per-phase latency envelope; ``--mesh-sweep N`` adds a 1-replica
  mesh-dispatch comparison leg so the daemon default is measured, not
  guessed (ROADMAP item 1 follow-on).

Usage:
    JAX_PLATFORMS=cpu python tools/fleet_bench.py [--quick] [--seed N]
        [--replica-counts 1 2 4] [--mesh-sweep 2]

``--quick`` is the CI shape ``tools/lint.sh`` chains (``FLEET=0`` skips):
the drill plus a 2-replica IN-PROCESS micro-bench — no subprocess spawn,
no artifact (unless ``--out``).  Exit 0 only when the drill is clean AND
deterministic (and, full runs, the kill -9 leg verifies).  When
``$BLOCKSIM_RUNS_JSONL`` is set the run lands ``fleet_invariant_violations``
and ``fleet_rps`` rows (tools/bench_compare.py charts but never gates the
``fleet_`` prefix).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import random
import sys as _sys
import tempfile
import time

_sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARTIFACT = os.path.join(REPO, "ARTIFACT_fleet_bench.json")

# the fleet-wide hot template (the chaos TPL: pbft n=8, exact sampler) —
# compile-cheap, so the bench measures serving, not tracing
HOT = {"protocol": "pbft", "n": 8, "sim_ms": 200, "stat_sampler": "exact"}
# cold groups: structurally distinct (different sim_ms → different canon →
# different executables) for the skew/adversarial phases
COLDS = [dict(HOT, sim_ms=ms) for ms in (240, 280, 320)]


def _force_platform(platform: str | None) -> None:
    if not platform:
        return
    # the drill's daemon/sweep children inherit its platform through their
    # environment (the serve daemon has no platform flag of its own)
    os.environ["JAX_PLATFORMS"] = platform
    import jax

    jax.config.update("jax_platforms", platform)


# -------------------------------------------------------- traffic shapes ---


def synth_arrivals(shape: str, seed: int, count: int, peak_rps: float):
    """Seeded arrival schedule for one phase: ``[(t_offset_s, obj), ...]``
    shaped like real multi-tenant traffic.  Deterministic per (shape,
    seed, count, peak)."""
    # string seeding, not a tuple: tuple seeds go through hash() and are
    # randomized per-process by PYTHONHASHSEED — str uses the stable
    # sha512 path, so the schedule reproduces across invocations
    rng = random.Random(f"{seed}-{shape}-{count}")
    out = []
    t = 0.0
    for i in range(count):
        if shape == "capacity":
            # overdriven steady rate: the measured throughput IS the
            # fleet's sustained req/s (serve_bench's convention)
            dt = 1.0 / peak_rps
            obj = dict(HOT)
        elif shape == "diurnal":
            # a day compressed into the phase: rate ramps base→peak→base
            frac = i / max(1, count - 1)
            rate = 0.2 * peak_rps + 0.8 * peak_rps \
                * math.sin(math.pi * frac) ** 2
            dt = 1.0 / max(rate, 0.1)
            obj = dict(HOT)
        elif shape == "burst":
            # quiet baseline with synchronized bursts (every 8th request
            # opens a burst of arrivals at t+0)
            dt = 0.0 if i % 8 else 4.0 / peak_rps
            obj = dict(HOT)
        elif shape == "skew":
            # hot/cold scenario skew: ~85% one hot group, the tail over
            # structurally distinct cold groups
            dt = 1.0 / peak_rps
            obj = dict(HOT) if rng.random() < 0.85 \
                else dict(rng.choice(COLDS))
        elif shape == "adversarial":
            # anti-batching group mix: consecutive requests cycle
            # distinct canonical structures so no two neighbors share a
            # batch group, plus byzantine/crash operand churn
            dt = 1.0 / peak_rps
            obj = dict(([HOT] + COLDS)[i % (1 + len(COLDS))])
            if i % 3 == 1:
                obj["faults"] = {"n_byzantine": 1 + i % 3}
            elif i % 3 == 2:
                obj["faults"] = {"n_crashed": 1 + i % 2}
        else:
            raise ValueError(shape)
        t += dt
        obj["seed"] = rng.randrange(2 ** 20)
        obj["id"] = f"{shape}-{i}"
        out.append((t, obj))
    return out


def run_phase(router, shape: str, seed: int, count: int,
              peak_rps: float) -> dict:
    """Open-loop: submit on the synthetic schedule (never waiting for
    answers), then collect; router-side latency is the client view."""
    arrivals = synth_arrivals(shape, seed, count, peak_rps)
    t0 = time.monotonic()
    pending = []
    for t_off, obj in arrivals:
        delay = t0 + t_off - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        pending.append((time.monotonic(), router.submit(obj)))
    responses = []
    for t_sub, p in pending:
        resp = p.result(300.0)
        # answered_at is stamped at resolution: the client-view latency,
        # immune to this open-loop collection running long after
        lat = (p.answered_at or time.monotonic()) - t_sub
        responses.append((lat, resp))
    wall = time.monotonic() - t0
    ok = [lat for lat, r in responses if r.get("status") == "ok"]
    from blockchain_simulator_tpu.utils import obs

    lat_ms = sorted(x * 1000.0 for x in ok)
    return {
        "requests": count,
        "offered_rps": round(count / arrivals[-1][0], 2)
        if arrivals[-1][0] > 0 else None,
        "served": len(ok),
        "errors": len(responses) - len(ok),
        "wall_s": round(wall, 2),
        "served_rps": round(len(ok) / wall, 2) if wall > 0 else None,
        "p50_ms": round(obs.percentile(lat_ms, 50), 1),
        "p99_ms": round(obs.percentile(lat_ms, 99), 1),
    }


PHASES = (  # (shape, count, peak_rps) — the traffic-shaped envelope
    ("capacity", 60, 120.0),
    ("diurnal", 40, 25.0),
    ("burst", 32, 20.0),
    ("skew", 40, 25.0),
    ("adversarial", 24, 20.0),
)


# ----------------------------------------------------------- bench legs ---


def drill_leg(seed: int, quick: bool) -> dict:
    """Every fleet scenario twice under one seed: invariant-clean AND
    byte-equal (the determinism pin tools/chaos_drill.py established)."""
    from blockchain_simulator_tpu.chaos import fleet_scenarios

    report = {}
    violations = 0
    deterministic = True
    for name in fleet_scenarios.FLEET_SCENARIOS:
        t0 = time.monotonic()
        runs = [fleet_scenarios.run_fleet_scenario(name, seed=seed,
                                                   quick=quick)
                for _ in range(2)]
        det = runs[0] == runs[1]
        deterministic = deterministic and det
        n_viol = len(runs[0]["violations"]) + len(runs[1]["violations"])
        violations += n_viol
        report[name] = {
            "summary": runs[0],
            "deterministic": det,
            "violations": n_viol,
            "wall_s": round(time.monotonic() - t0, 2),
        }
        print(json.dumps({"scenario": name, "deterministic": det,
                          "violations": n_viol,
                          "wall_s": report[name]["wall_s"]}), flush=True)
    return {"scenarios": report, "deterministic": deterministic,
            "invariant_violations": violations}


def micro_bench(seed: int) -> dict:
    """The CI micro-bench: 2 in-process replicas behind the router, one
    overdriven capacity phase — fleet_rps without a subprocess spawn."""
    from blockchain_simulator_tpu.chaos.fleet_scenarios import LocalReplica
    from blockchain_simulator_tpu.serve.router import FleetRouter

    replicas = [LocalReplica(f"mb-{i}", max_batch=8, max_wait_ms=10.0,
                             max_queue=128) for i in range(2)]
    router = FleetRouter(replicas, owner="bench-router",
                         probe_interval_s=0.5)
    try:
        # warm the hot group across EVERY bucket out of the timed phase
        # (in-process replicas share one registry: one prewarm covers both)
        replicas[0].server.prewarm(dict(HOT))
        phase = run_phase(router, "capacity", seed, count=40,
                          peak_rps=100.0)
        stats = router.stats()
    finally:
        router.close()
        for r in replicas:
            r.close()
    return {"replicas": 2, "in_process": True, "phase": phase,
            "received": stats["received"]}


def scaling_leg(seed: int, replica_counts, fleet_root: str,
                mesh_sweep: int = 0) -> dict:
    """Subprocess fleets at 1/2/4 replicas, each driven through the full
    traffic-shaped phase set (later fleets — and replicas 2..N of each —
    warm from jax's compile cache, serve/__main__.py)."""
    from blockchain_simulator_tpu.serve.fleet import FleetManager
    from blockchain_simulator_tpu.serve.router import FleetRouter

    scaling: dict = {}
    legs = [(str(n), n, 0) for n in replica_counts]
    if mesh_sweep and mesh_sweep > 1:
        legs.append((f"1+mesh{mesh_sweep}", 1, mesh_sweep))
    for label, n, mesh in legs:
        fleet_dir = os.path.join(fleet_root, f"fleet-{label}")
        mgr = FleetManager(n, fleet_dir, max_batch=8, max_wait_ms=10.0,
                           max_queue=256, mesh_sweep=mesh, prewarm=HOT)
        t0 = time.monotonic()
        mgr.start()
        start_s = time.monotonic() - t0
        router = FleetRouter(mgr.replicas, owner="bench-router",
                             probe_interval_s=0.5)
        rec: dict = {"replicas": n, "mesh_sweep": mesh or None,
                     "start_s": round(start_s, 2), "phases": {}}
        try:
            for i in range(2 * n):  # touch every replica once, warm
                router.request(dict(HOT, seed=i, id=f"warm-{label}-{i}"),
                               wait_s=300)
            for shape, count, peak in PHASES:
                rec["phases"][shape] = run_phase(
                    router, shape, seed, count, peak)
                print(json.dumps({"fleet": label, "phase": shape,
                                  **rec["phases"][shape]}), flush=True)
            rec["capacity_rps"] = rec["phases"]["capacity"]["served_rps"]
        finally:
            router.close()
            mgr.close()
        scaling[label] = rec
    return scaling


def kill9_leg(seed: int, fleet_root: str) -> dict:
    """The acceptance leg: SIGKILL the subprocess replica holding admitted
    requests; the router's handoff replays each exactly once on the peer,
    bit-equal to references; the restarted replica replays zero."""
    from blockchain_simulator_tpu import runner
    from blockchain_simulator_tpu.serve.fleet import FleetManager
    from blockchain_simulator_tpu.serve.router import FleetRouter
    from blockchain_simulator_tpu.utils import obs
    from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig

    log = os.path.join(fleet_root, "kill9_access.jsonl")
    prev_log = os.environ.get(obs.RUNS_ENV)
    os.environ[obs.RUNS_ENV] = log
    violations: list[str] = []
    rec: dict = {"leg": "kill9"}
    try:
        # max_wait 5 s + max_batch 8: the victim HOLDS the admitted group
        # so the SIGKILL deterministically lands with pendings journaled
        mgr = FleetManager(2, os.path.join(fleet_root, "fleet-kill9"),
                           max_batch=8, max_wait_ms=5000.0,
                           env={obs.RUNS_ENV: log})
        mgr.start()
        router = FleetRouter(mgr.replicas, owner="bench-router",
                             probe_interval_s=0.2, dead_after=2,
                             request_timeout_s=120.0)
        try:
            victim_id = router.affinity_replica(dict(HOT, seed=0))
            victim = next(r for r in mgr.replicas if r.id == victim_id)
            peer = next(r for r in mgr.replicas if r.id != victim_id)
            rec["victim"] = victim_id
            crash_points = [
                ("fk-0", dict(HOT, seed=700, id="fk-0")),
                ("fk-1", dict(HOT, seed=701, id="fk-1",
                              faults={"n_byzantine": 1})),
                ("fk-2", dict(HOT, seed=702, id="fk-2",
                              faults={"n_crashed": 1})),
            ]
            pendings = [(rid, router.submit(obj))
                        for rid, obj in crash_points]
            time.sleep(1.5)  # admitted + WAL-fsynced, held in the group
            # the kill -9 IS the drill
            victim.kill()
            if not router.join_handoffs(1, timeout_s=120.0):
                violations.append("kill9 handoff never completed")
            answers = {rid: p.result(120.0) for rid, p in pendings}
            rec["replayed"] = sum(
                1 for a in answers.values() if a.get("replayed"))
            for rid, a in answers.items():
                if a.get("status") != "ok" or not a.get("replayed"):
                    violations.append(
                        f"kill9 {rid!r} not answered via replay: "
                        f"{a.get('kind') or a.get('status')}")
            stats = router.stats()
            rec["handoffs"] = [
                {"replica": h.get("replica"), "claimed": h.get("claimed"),
                 "replayed": h.get("replayed")}
                for h in stats["handoffs"]]
            from blockchain_simulator_tpu.chaos.invariants import check_fleet

            viol = check_fleet(None, stats, log_path=log,
                               handoff_ids=[rid for rid, _ in crash_points])
            violations += viol
            # bit-equality: replayed answers vs uninterrupted references
            divergence = 0
            for rid, obj in crash_points:
                a = answers[rid]
                if a.get("status") != "ok":
                    divergence += 1
                    continue
                kw = {k: v for k, v in obj.items()
                      if k not in ("id", "seed", "faults")}
                cfg = SimConfig(**kw,
                                faults=FaultConfig(**obj.get("faults", {})))
                ref = runner.run_simulation(cfg, seed=obj["seed"])
                if {k: str(v) for k, v in a["metrics"].items()} \
                        != {k: str(v) for k, v in ref.items()}:
                    violations.append(f"kill9 replay of {rid!r} diverged")
                    divergence += 1
            rec["replay_divergence"] = divergence
            # restart the victim on its WAL: every handed-off id is
            # done-marked, so the READY line must report replayed: 0
            ready = mgr.restart(victim_id)
            rec["replayed_on_restart"] = ready.get("replayed")
            if ready.get("replayed") != 0:
                violations.append(
                    f"restarted victim replayed {ready.get('replayed')} "
                    f"(want 0: the handoff owns its old backlog)")
            # the peer is untouched; both replicas serve again
            post_restart = router.request(dict(HOT, seed=800, id="fk-post"),
                                          wait_s=120.0)
            rec["post_restart_ok"] = post_restart.get("status") == "ok"
            if not rec["post_restart_ok"]:
                violations.append("fleet did not serve after restart")
            rec["peer"] = peer.id
        finally:
            router.close()
            mgr.close()
    finally:
        if prev_log is None:
            os.environ.pop(obs.RUNS_ENV, None)
        else:
            os.environ[obs.RUNS_ENV] = prev_log
    rec["violations"] = violations
    return rec


# ------------------------------------------------------------------ main ---


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="fleet_bench")
    p.add_argument("--seed", type=int, default=4321)
    p.add_argument("--quick", action="store_true",
                   help="CI shape (tools/lint.sh, FLEET=0 skips): fleet "
                        "drill + 2-replica in-process micro-bench, no "
                        "subprocess fleets, no artifact unless --out")
    p.add_argument("--replica-counts", type=int, nargs="*",
                   default=[1, 2, 4])
    p.add_argument("--mesh-sweep", type=int, default=2,
                   help="full runs add a 1-replica leg with this sweep-"
                        "mesh width for the daemon-default measurement "
                        "(0 disables the leg)")
    p.add_argument("--out", default=None,
                   help="artifact path (default: ARTIFACT_fleet_bench.json "
                        "on full runs, none on --quick)")
    p.add_argument("--platform", default="cpu")
    args = p.parse_args(argv)

    _force_platform(args.platform)
    from blockchain_simulator_tpu.utils import obs

    t_start = time.monotonic()
    drill = drill_leg(args.seed, args.quick)
    artifact: dict = {
        "metric": "fleet_bench",
        "seed": args.seed,
        "quick": args.quick,
        "drill": drill,
    }
    violations = drill["invariant_violations"]
    if args.quick:
        mb = micro_bench(args.seed)
        artifact["micro_bench"] = mb
        fleet_rps = mb["phase"]["served_rps"]
        if mb["phase"]["served"] != mb["phase"]["requests"]:
            violations += 1
    else:
        with tempfile.TemporaryDirectory(prefix="fleet_bench_") as root:
            artifact["scaling"] = scaling_leg(
                args.seed, args.replica_counts, root,
                mesh_sweep=args.mesh_sweep)
            kill9 = kill9_leg(args.seed, root)
        artifact["kill9"] = kill9
        violations += len(kill9["violations"])
        top = str(max(args.replica_counts))
        fleet_rps = artifact["scaling"][top]["capacity_rps"]
        if args.mesh_sweep and args.mesh_sweep > 1:
            plain = artifact["scaling"].get("1", {}).get("capacity_rps")
            meshed = artifact["scaling"].get(
                f"1+mesh{args.mesh_sweep}", {}).get("capacity_rps")
            artifact["mesh_sweep_decision"] = {
                "plain_rps": plain, "meshed_rps": meshed,
                "mesh": args.mesh_sweep,
                # the measured daemon default (README "Fleet serving"):
                # mesh dispatch must beat single-device by a real margin
                # (>20%) to displace the simpler default — this box's
                # run-to-run swing is easily ±10% (KNOWN_ISSUES #0j)
                "default": "mesh-sweep"
                if plain and meshed and meshed > 1.2 * plain
                else "single-device",
            }
    ok = violations == 0 and drill["deterministic"]
    artifact.update({
        "ok": ok,
        "fleet_rps": fleet_rps,
        "invariant_violations": violations,
        "deterministic": drill["deterministic"],
        "wall_s": round(time.monotonic() - t_start, 2),
    })
    print(json.dumps(obs.finalize(dict(artifact), None, append=False)),
          flush=True)
    # lower-is-better / charted-only trajectory rows: bench_compare never
    # gates the fleet_ prefix (the drill's own exit code is the gate)
    obs.finalize({"metric": "fleet_invariant_violations",
                  "value": violations, "unit": "violations"})
    obs.finalize({"metric": "fleet_rps", "value": fleet_rps,
                  "unit": "req/s"})
    out = args.out or (None if args.quick else ARTIFACT)
    if out:
        with open(out, "w") as f:
            json.dump(obs.finalize(artifact, None, append=False), f,
                      indent=1, default=str)
            f.write("\n")
    return 0 if ok else 1


if __name__ == "__main__":
    _sys.exit(main())
