"""ARTIFACT_chaos_drill.json generator: the serving stack under fire.

Runs every scripted chaos scenario (blockchain_simulator_tpu/chaos/
scenarios.py) TWICE with one chaos seed and demands three things of each:

- **invariant-clean** — zero violations from the checker (no request
  unaccounted, no lost manifest lines, registry counters monotone);
- **deterministic** — the two same-seed runs produce byte-equal
  normalized summaries (outcome kinds, terminal counters, the fired
  chaos schedule);
- **replay-faithful** — the crash-restart scenario's WAL replays answer
  bit-equal (exact sampler) to uninterrupted reference runs.

The full run (default) adds the **kill -9 leg**: a real daemon
subprocess (``python -m blockchain_simulator_tpu.serve --wal``) is
SIGKILLed mid-traffic with admitted-but-unanswered requests in its
queue; the restarted daemon must replay each exactly once (READY line
``replayed`` count, ``/stats``, access-log ``"replayed": true`` records
bit-equal to references) and a third start must replay zero.

Usage:
    JAX_PLATFORMS=cpu python tools/chaos_drill.py [--quick] [--seed N]

``--quick`` trims scenario sizes and skips the subprocess kill -9 leg
(covered by the slow-marked test) — the shape ``tools/lint.sh`` chains
(``CHAOS=0`` skips).  Exit 0 only when every scenario is clean AND
deterministic.  When ``$BLOCKSIM_RUNS_JSONL`` is set the drill lands
``chaos_invariant_violations`` and ``chaos_replay_divergence`` rows
(lower-is-better counters; tools/bench_compare.py charts but never gates
the ``chaos_`` prefix).  The artifact is written on full runs (or
whenever ``--out`` is given).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys as _sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

_sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARTIFACT = os.path.join(REPO, "ARTIFACT_chaos_drill.json")


def _force_platform(platform: str | None) -> None:
    """Pin the backend BEFORE any init: the drills are CPU rehearsals of
    host-side fault handling and must not claim a chip."""
    if not platform:
        return
    # the drill's daemon/sweep children inherit its platform through their
    # environment (the serve daemon has no platform flag of its own)
    os.environ["JAX_PLATFORMS"] = platform
    import jax

    jax.config.update("jax_platforms", platform)


# ------------------------------------------------------------- kill -9 leg


def _post(base: str, obj: dict, out: list, timeout: float = 120.0) -> None:
    data = json.dumps(obj).encode()
    req = urllib.request.Request(
        f"{base}/scenario", data=data,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            out.append(json.loads(r.read()))
    except urllib.error.HTTPError as e:
        out.append(json.loads(e.read()))
    except Exception as e:  # the killed daemon's connections die here
        out.append({"status": "dead", "error": type(e).__name__})


def _start_daemon(cmd: list, env: dict):
    """Spawn the daemon, wait for its READY line; returns (proc, ready)."""
    proc = subprocess.Popen(
        cmd, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, env=env, cwd=REPO,
    )
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            time.sleep(0.1)
            continue
        if line.startswith("READY "):
            return proc, json.loads(line[len("READY "):])
    # killing (and reaping) it on a failed start IS the cleanup
    proc.kill()
    proc.wait()
    raise RuntimeError("daemon never printed READY")


def _get(base: str, path: str) -> dict:
    with urllib.request.urlopen(f"{base}{path}", timeout=60) as r:
        return json.loads(r.read())


def kill9_drill(workdir: str) -> dict:
    """The acceptance leg: kill -9 a daemon mid-traffic, restart it on
    the same WAL, verify exactly-once replay with bit-equal answers."""
    from blockchain_simulator_tpu import runner
    from blockchain_simulator_tpu.chaos.scenarios import TPL, _norm
    from blockchain_simulator_tpu.utils import obs
    from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig

    wal = os.path.join(workdir, "daemon_wal.jsonl")
    log = os.path.join(workdir, "daemon_access.jsonl")
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "BLOCKSIM_RUNS_JSONL": log,
           "PYTHONPATH": os.pathsep.join(
               p for p in (REPO, os.environ.get("PYTHONPATH")) if p)}
    # max_wait 5 s + max_batch 8: a sub-batch group is HELD long enough
    # that the kill deterministically lands while it is still queued
    cmd = [_sys.executable, "-m", "blockchain_simulator_tpu.serve",
           "--port", "0", "--max-batch", "8", "--max-wait-ms", "5000",
           "--wal", wal]
    rec: dict = {"leg": "kill9"}
    violations: list[str] = []

    proc, ready = _start_daemon(cmd, env)
    base = f"http://127.0.0.1:{ready['port']}"
    # phase 1: a full batch of live traffic, answered before the kill
    warm_out: list = []
    threads = [
        threading.Thread(target=_post, args=(
            base, dict(TPL, seed=100 + i, id=f"warm-{i}"), warm_out))
        for i in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=300)
    rec["warm_ok"] = sum(r.get("status") == "ok" for r in warm_out)
    if rec["warm_ok"] != 8:
        violations.append(f"warm phase served {rec['warm_ok']}/8")
    # phase 2: three requests admitted into a held group, then SIGKILL
    crash_points = [
        ("crash-0", dict(TPL, seed=200, id="crash-0")),
        ("crash-1", dict(TPL, seed=201, id="crash-1",
                         faults={"n_byzantine": 1})),
        ("crash-2", dict(TPL, seed=202, id="crash-2",
                         faults={"n_crashed": 1})),
    ]
    dead_out: list = []
    pend_threads = [
        threading.Thread(target=_post, args=(base, obj, dead_out, 60))
        for _, obj in crash_points
    ]
    for t in pend_threads:
        t.start()
    time.sleep(1.0)  # admitted + WAL-fsynced, still held in the group
    # the kill -9 IS the drill
    os.kill(proc.pid, signal.SIGKILL)
    proc.wait(timeout=60)
    for t in pend_threads:
        t.join(timeout=60)
    rec["killed_with_pending"] = len(crash_points)

    # phase 3: restart on the same WAL — exactly-once replay
    proc2, ready2 = _start_daemon(cmd, env)
    base2 = f"http://127.0.0.1:{ready2['port']}"
    rec["replayed_on_restart"] = ready2.get("replayed")
    if ready2.get("replayed") != len(crash_points):
        violations.append(
            f"restart replayed {ready2.get('replayed')} != "
            f"{len(crash_points)} pending")
    deadline = time.monotonic() + 300
    stats = {}
    while time.monotonic() < deadline:
        stats = _get(base2, "/stats")
        if stats.get("queue_depth") == 0 \
                and stats.get("served", 0) >= len(crash_points):
            break
        time.sleep(0.2)
    rec["replay_served"] = stats.get("served")
    try:
        urllib.request.urlopen(
            urllib.request.Request(f"{base2}/shutdown", data=b"{}"),
            timeout=60).read()
    except Exception:
        pass
    proc2.wait(timeout=120)

    # phase 4: a third start replays nothing (idempotence)
    proc3, ready3 = _start_daemon(cmd, env)
    rec["replayed_on_second_restart"] = ready3.get("replayed")
    if ready3.get("replayed") != 0:
        violations.append(
            f"second restart replayed {ready3.get('replayed')} (want 0)")
    try:
        urllib.request.urlopen(
            urllib.request.Request(
                f"http://127.0.0.1:{ready3['port']}/shutdown", data=b"{}"),
            timeout=60).read()
    except Exception:
        pass
    proc3.wait(timeout=120)

    # bit-equality: each replayed access-log answer vs a reference run
    replay_recs = {r.get("id"): r for r in obs.read_jsonl(log)
                   if r.get("replayed") is True}
    divergence = 0
    for rid, obj in crash_points:
        r = replay_recs.get(rid)
        if r is None or r.get("status") != "ok":
            violations.append(f"kill9 replay of {rid!r} missing/failed")
            divergence += 1
            continue
        kw = {k: v for k, v in obj.items()
              if k not in ("id", "seed", "faults")}
        cfg = SimConfig(**kw, faults=FaultConfig(**obj.get("faults", {})))
        ref = runner.run_simulation(cfg, seed=obj["seed"])
        if _norm(r["metrics"]) != _norm(ref):
            violations.append(f"kill9 replay of {rid!r} diverged")
            divergence += 1
    rec["replay_divergence"] = divergence
    rec["violations"] = violations
    return rec


# ------------------------------------------------------------------ main


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="chaos_drill")
    p.add_argument("--seed", type=int, default=1234,
                   help="the chaos seed; every scenario runs twice with "
                        "it and must behave identically")
    p.add_argument("--quick", action="store_true",
                   help="CI shape: smaller storms, no subprocess kill -9 "
                        "leg (tools/lint.sh chains this; the slow test "
                        "covers the full leg)")
    p.add_argument("--scenarios", nargs="*", default=None,
                   help="subset to run (default: all)")
    p.add_argument("--out", default=None,
                   help="artifact path (default: ARTIFACT_chaos_drill.json "
                        "on full runs, none on --quick)")
    p.add_argument("--platform", default="cpu",
                   help="jax platform to pin ('' = environment default)")
    args = p.parse_args(argv)

    _force_platform(args.platform)
    from blockchain_simulator_tpu.chaos import scenarios
    from blockchain_simulator_tpu.utils import obs

    names = args.scenarios or list(scenarios.SCENARIOS)
    unknown = sorted(set(names) - set(scenarios.SCENARIOS))
    if unknown:
        print(f"unknown scenario(s): {', '.join(unknown)}",
              file=_sys.stderr)
        return 2
    t_start = time.monotonic()
    report: dict = {}
    total_violations = 0
    replay_divergence = 0
    all_deterministic = True
    for name in names:
        t0 = time.monotonic()
        runs = [scenarios.run_scenario(name, seed=args.seed,
                                       quick=args.quick)
                for _ in range(2)]
        deterministic = runs[0] == runs[1]
        all_deterministic = all_deterministic and deterministic
        n_viol = len(runs[0]["violations"]) + len(runs[1]["violations"])
        total_violations += n_viol
        replay_divergence += runs[0].get("replay_divergence", 0)
        report[name] = {
            "summary": runs[0],
            "deterministic": deterministic,
            "violations": n_viol,
            "wall_s": round(time.monotonic() - t0, 2),
        }
        print(json.dumps({
            "scenario": name, "deterministic": deterministic,
            "violations": n_viol,
            "wall_s": report[name]["wall_s"],
        }), flush=True)

    kill9 = None
    if not args.quick and "crash-restart" in names:
        with tempfile.TemporaryDirectory(prefix="chaos_kill9_") as wd:
            kill9 = kill9_drill(wd)
        total_violations += len(kill9["violations"])
        replay_divergence += kill9["replay_divergence"]
        print(json.dumps({
            "scenario": "crash-restart/kill9",
            "violations": len(kill9["violations"]),
            "replay_divergence": kill9["replay_divergence"],
        }), flush=True)

    ok = total_violations == 0 and all_deterministic
    artifact = {
        "metric": "chaos_drill",
        "ok": ok,
        "seed": args.seed,
        "quick": args.quick,
        "scenarios": report,
        "kill9": kill9,
        "invariant_violations": total_violations,
        "replay_divergence": replay_divergence,
        "deterministic": all_deterministic,
        "wall_s": round(time.monotonic() - t_start, 2),
    }
    print(json.dumps(obs.finalize(dict(artifact), None, append=False)),
          flush=True)
    # lower-is-better trajectory counters; bench_compare never gates the
    # chaos_ prefix (a drop is a FIX, a rise fails this drill's own exit)
    obs.finalize({"metric": "chaos_invariant_violations",
                  "value": total_violations, "unit": "violations"})
    obs.finalize({"metric": "chaos_replay_divergence",
                  "value": replay_divergence, "unit": "requests"})
    out = args.out or (None if args.quick else ARTIFACT)
    if out:
        with open(out, "w") as f:
            json.dump(obs.finalize(artifact, None, append=False), f,
                      indent=1, default=str)
            f.write("\n")
    return 0 if ok else 1


if __name__ == "__main__":
    _sys.exit(main())
