"""ARTIFACT_query.json generator: adaptive query vs dense grid + kill -9.

The acceptance drill of the adaptive-query engine (query/): the same
``max_f_surviving`` question answered two ways on the mesh-sweep bench
config, then killed and resumed mid-search — and the drill demands:

- **same answer** — the bisection engine and the dense grid (every
  domain value evaluated) report the identical boundary;
- **rows bit-equal** — every (value, seed) metrics row the adaptive
  search evaluated is bit-equal (exact sampler) to the dense grid's row
  for that point: the search dispatches the SAME cached executable on
  the same operands, it just asks for fewer of them;
- **>= 10x dispatch reduction** (full mode) — the search's simulation
  lanes vs the grid's; quick mode's 8-value domain can only save ~1.6x,
  so its gate relaxes to > 1x (the full artifact carries the real
  headroom);
- **kill -9 resume with 0 recomputed steps** — a REAL subprocess runs
  the query journaled and is SIGKILLed between durable step appends;
  rerunning the same command serves every completed generation from the
  journal (no chunk key ever reappears), dispatches only the missing
  generations, and answers bit-equal to the uninterrupted reference.

The kill window is widened deterministically the way the sweep resume
drill does it: the child chaos-slows every ``query.step`` firing, so the
parent's journal poll always finds the search mid-flight.

Usage:
    JAX_PLATFORMS=cpu python tools/query_drill.py [--quick]

``--quick`` is the tools/lint.sh chain shape (``QUERY=0`` skips): the
toy n=8 domain, no artifact write.  The full run uses the mesh-sweep
bench's n=256 round-path config over the whole [0, 255] domain and
writes ARTIFACT_query.json.  Exit 0 only with zero violations.  When
``$BLOCKSIM_RUNS_JSONL`` is set the drill lands
``query_dispatch_savings_x`` / ``query_invariant_violations``
(tools/bench_compare.py never gates the ``query_`` prefix — this
drill's exit code is the gate).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys as _sys
import tempfile
import time

_sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARTIFACT = os.path.join(REPO, "ARTIFACT_query.json")


def _force_platform(platform: str | None) -> None:
    if not platform:
        return
    # the drill's daemon/sweep children inherit its platform through their
    # environment (the serve daemon has no platform flag of its own)
    os.environ["JAX_PLATFORMS"] = platform
    import jax

    jax.config.update("jax_platforms", platform)


def _shape(quick: bool):
    """The drill shape: quick = the chaos-scenario toy config at the
    400 ms horizon (200 ms commits nothing — no cliff to find); full =
    the mesh-sweep bench's n=256 round-path config, whole domain.  Exact
    sampler pinned: resumed rows must be bit-stable across processes."""
    from blockchain_simulator_tpu.query import spec as qspec
    from blockchain_simulator_tpu.utils.config import SimConfig

    if quick:
        cfg = SimConfig(protocol="pbft", n=8, sim_ms=400,
                        stat_sampler="exact")
    else:
        cfg = SimConfig(protocol="pbft", n=256, sim_ms=600, delivery="stat",
                        schedule="round", model_serialization=False,
                        pbft_window=8, pbft_max_slots=48,
                        stat_sampler="exact")
    spec = qspec.parse_query({"kind": "max_f_surviving", "seeds": [0, 1]})
    return cfg, spec


def child_main(args) -> int:
    """The journaled query, as its own process (the SIGKILL target).
    Prints one final JSON summary line; a killed child never reaches it —
    the journal IS its record."""
    _force_platform(args.platform)
    from blockchain_simulator_tpu.chaos import inject
    from blockchain_simulator_tpu.parallel.journal import SweepJournal
    from blockchain_simulator_tpu.query import run_query
    from blockchain_simulator_tpu.utils import aotcache, obs

    cfg, spec = _shape(args.quick)
    steps_before = len(SweepJournal(args.journal).completed())
    ctl = None
    if args.slow_step_ms > 0:
        # widen the parent's kill window deterministically: every
        # generation sleeps before dispatching, so >= one step is always
        # in flight while the parent polls the journal
        ctl = inject.ChaosController(seed=0)
        ctl.slow_next("query.step", args.slow_step_ms / 1000.0, n=10_000)
        ctl.install()
    m0 = aotcache.registry.stats()["misses"]
    try:
        res = run_query(cfg, spec, journal=SweepJournal(args.journal))
    finally:
        if ctl is not None:
            ctl.uninstall()
    print(json.dumps({
        "steps_before": steps_before,
        "run": res["run"],
        "answer": res["answer"],
        "trail_json": obs.canonical_json(res["trail"]),
        "registry_misses": aotcache.registry.stats()["misses"] - m0,
    }), flush=True)
    return 0


def _spawn_child(args, journal_path: str, workdir: str, slow_ms: int):
    env = {**os.environ, "JAX_PLATFORMS": args.platform or "cpu",
           # hermetic: the drill's own rows stay out of the outer
           # trajectory, and an outer health log must not gate the child
           "BLOCKSIM_RUNS_JSONL": os.path.join(workdir, "child_runs.jsonl"),
           "PYTHONPATH": os.pathsep.join(
               p for p in (REPO, os.environ.get("PYTHONPATH")) if p)}
    env.pop("BLOCKSIM_HEALTH_JSONL", None)
    cmd = [_sys.executable, os.path.abspath(__file__), "--child",
           "--journal", journal_path,
           "--slow-step-ms", str(slow_ms),
           "--platform", args.platform or "cpu"]
    if args.quick:
        cmd.append("--quick")
    return subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True, env=env,
                            cwd=REPO)


def adaptive_vs_dense_leg(args) -> dict:
    """The search-efficiency evidence: one adaptive run, one dense grid,
    identical boundary, bit-equal rows at every shared point, and the
    lane-count savings the refinement loop exists for."""
    from blockchain_simulator_tpu.chaos import invariants
    from blockchain_simulator_tpu.models.base import canonical_fault_cfg
    from blockchain_simulator_tpu.parallel import sweep
    from blockchain_simulator_tpu.query import run_query
    from blockchain_simulator_tpu.query import spec as qspec
    from blockchain_simulator_tpu.utils import obs

    cfg, spec = _shape(args.quick)
    lo, hi = qspec.resolve_domain(spec, cfg)
    rec: dict = {"leg": "adaptive-vs-dense", "domain": [lo, hi]}
    violations: list[str] = []

    t0 = time.monotonic()
    res = run_query(cfg, spec)
    rec["adaptive_s"] = round(time.monotonic() - t0, 2)
    rec["answer"] = res["answer"]
    rec["run"] = res["run"]
    violations += invariants.check_query_trail(res)

    values = list(range(lo, hi + 1))
    pts = [(qspec.point_cfg(cfg, spec, v), s)
           for v in values for s in spec.seeds]
    t0 = time.monotonic()
    rows = sweep.run_dyn_points(canonical_fault_cfg(pts[0][0]), pts,
                                record=False)
    rec["dense_s"] = round(time.monotonic() - t0, 2)
    n_s = len(spec.seeds)
    oks = {v: qspec.verdict(cfg.protocol, rows[i * n_s:(i + 1) * n_s], spec)
           for i, v in enumerate(values)}
    passing = [v for v in values if oks[v]]
    failing = [v for v in values if not oks[v]]
    dense_answer = {"f_max": max(passing) if passing else None,
                    "first_failing": min(failing) if failing else None}
    rec["dense_answer"] = dense_answer
    if (res["answer"]["f_max"], res["answer"]["first_failing"]) != \
            (dense_answer["f_max"], dense_answer["first_failing"]):
        violations.append(
            f"adaptive answer {res['answer']} != dense {dense_answer}")

    # bit-equality at every point the search evaluated: same executable,
    # same operands -> the exact sampler leaves no room for drift
    dense_row = {(v, s): rows[i * n_s + j]
                 for i, v in enumerate(values)
                 for j, s in enumerate(spec.seeds)}
    mismatched = [
        (p["value"], p["seed"]) for p in res["points"]
        if obs.canonical_json(p["metrics"])
        != obs.canonical_json(dense_row[(p["value"], p["seed"])])
    ]
    rec["points_compared"] = len(res["points"])
    if mismatched:
        violations.append(
            f"{len(mismatched)} adaptive rows diverge from the dense "
            f"grid: {mismatched[:4]}")

    dense_lanes = len(pts)
    savings = dense_lanes / max(res["run"]["lanes"], 1)
    rec["dense_lanes"] = dense_lanes
    rec["adaptive_lanes"] = res["run"]["lanes"]
    rec["dispatch_savings_x"] = round(savings, 2)
    floor = 1.0 if args.quick else 10.0
    if savings <= floor:
        violations.append(
            f"dispatch savings {savings:.2f}x below the {floor:g}x floor "
            f"({dense_lanes} dense lanes vs {res['run']['lanes']})")
    rec["violations"] = violations
    return rec


def kill9_leg(args, workdir: str) -> dict:
    """SIGKILL a journaled-query child mid-search, resume with a second
    child, verify the journal and the answer in-process."""
    from blockchain_simulator_tpu.chaos import invariants
    from blockchain_simulator_tpu.parallel.journal import SweepJournal
    from blockchain_simulator_tpu.query import run_query
    from blockchain_simulator_tpu.utils import obs

    cfg, spec = _shape(args.quick)
    journal_path = os.path.join(workdir, "query.journal")
    rec: dict = {"leg": "kill9"}
    violations: list[str] = []

    # uninterrupted reference, in this process (its own journal so the
    # trail carries chunk keys exactly like the children's)
    reference = run_query(cfg, spec, journal=SweepJournal(
        os.path.join(workdir, "reference.journal")))
    total_steps = reference["run"]["steps"]

    # phase 1: child 1 searches journaled, slowed; SIGKILL once >= 2
    # generations are durable (and the search still has steps to go)
    proc = _spawn_child(args, journal_path, workdir, args.slow_step_ms)
    deadline = time.monotonic() + 600
    pre_keys: set = set()
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            break  # finished before the kill: recorded below, still valid
        pre_keys = set(SweepJournal(journal_path).completed())
        if len(pre_keys) >= 2:
            break
        time.sleep(0.01)
    killed = proc.poll() is None
    if killed:
        # the kill -9 IS the drill
        os.kill(proc.pid, signal.SIGKILL)
    proc.wait(timeout=60)
    pre_keys = set(SweepJournal(journal_path).completed())
    rec["killed"] = killed
    rec["steps_at_kill"] = len(pre_keys)
    if not killed:
        violations.append(
            f"child finished all {total_steps} steps before the kill "
            f"window (slow-step-ms too small)")
    if len(pre_keys) == 0:
        violations.append("no step survived the kill (nothing durable)")

    # phase 2: child 2 resumes the same command to completion
    proc2 = _spawn_child(args, journal_path, workdir, 0)
    out, _ = proc2.communicate(timeout=600)
    summary = None
    for line in out.splitlines()[::-1]:
        try:
            summary = json.loads(line)
            break
        except json.JSONDecodeError:
            continue
    if proc2.returncode != 0 or not isinstance(summary, dict):
        violations.append(f"resume child failed rc={proc2.returncode}")
        summary = {}
    run = summary.get("run") or {}
    rec["resume_run"] = run

    # 0 recomputed steps: every pre-kill generation is served from the
    # journal (its key never reappears), only the missing ones dispatch
    post = SweepJournal(journal_path)
    post_keys = set(post.completed())
    recomputed = [k for k in pre_keys
                  if sum(1 for line in post.chunk_lines()
                         if str(line.get("key")) == k) > 1]
    rec["recomputed_steps"] = len(recomputed)
    if recomputed:
        violations.append(
            f"{len(recomputed)} completed steps recomputed on resume "
            f"(recompute-zero broken): {sorted(recomputed)}")
    if run.get("cached_steps") != len(pre_keys):
        violations.append(
            f"resume served {run.get('cached_steps')} steps from the "
            f"journal, parent saw {len(pre_keys)} durable")
    if run.get("dispatches") != run.get("steps", 0) - len(pre_keys):
        violations.append(
            f"resume dispatched {run.get('dispatches')} generations, "
            f"want {run.get('steps', 0) - len(pre_keys)}")

    # the resumed answer and trail are bit-equal to the reference
    rec["answer"] = summary.get("answer")
    if summary.get("answer") != reference["answer"]:
        violations.append(
            f"resumed answer {summary.get('answer')} != reference "
            f"{reference['answer']}")
    trail_equal = (summary.get("trail_json")
                   == obs.canonical_json(reference["trail"]))
    rec["trail_bit_equal"] = trail_equal
    if not trail_equal:
        violations.append("resumed trail diverges from the uninterrupted "
                          "reference search")
    violations += invariants.check_sweep_journal(post)
    if post_keys != {k for t in reference["trail"] for k in t["keys"]}:
        violations.append("journaled keys differ from the reference "
                          "search's plan")
    rec["violations"] = violations
    return rec


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="query_drill")
    p.add_argument("--quick", action="store_true",
                   help="CI shape (tools/lint.sh, QUERY=0 skips): the "
                        "toy n=8 domain, no artifact write")
    p.add_argument("--child", action="store_true",
                   help="internal: run the journaled query in this "
                        "process (the SIGKILL target)")
    p.add_argument("--journal", default=None,
                   help="internal (--child): journal path")
    p.add_argument("--slow-step-ms", type=int, default=250,
                   help="chaos-slow every refinement step by this much "
                        "in the first child so the kill always lands "
                        "mid-search (0 disables; the resume child runs "
                        "unslowed)")
    p.add_argument("--out", default=None,
                   help="artifact path (default: ARTIFACT_query.json on "
                        "full runs, none on --quick)")
    p.add_argument("--platform", default="cpu",
                   help="jax platform to pin ('' = environment default)")
    args = p.parse_args(argv)

    if args.child:
        if not args.journal:
            print("--child requires --journal", file=_sys.stderr)
            return 2
        return child_main(args)

    _force_platform(args.platform)
    from blockchain_simulator_tpu.utils import obs

    t0 = time.monotonic()
    dense_rec = adaptive_vs_dense_leg(args)
    with tempfile.TemporaryDirectory(prefix="query_drill_") as wd:
        kill_rec = kill9_leg(args, wd)
    n_viol = len(dense_rec["violations"]) + len(kill_rec["violations"])
    ok = n_viol == 0
    artifact = {
        "metric": "query_drill",
        "ok": ok,
        "quick": args.quick,
        "adaptive_vs_dense": dense_rec,
        "kill9": kill_rec,
        "invariant_violations": n_viol,
        "wall_s": round(time.monotonic() - t0, 2),
    }
    print(json.dumps(obs.finalize(dict(artifact), None, append=False)),
          flush=True)
    # higher-is-better savings + lower-is-better violations; bench_compare
    # never gates the query_ prefix (this drill's own exit code is the gate)
    obs.finalize({"metric": "query_dispatch_savings_x",
                  "value": dense_rec.get("dispatch_savings_x"),
                  "unit": "x"})
    obs.finalize({"metric": "query_invariant_violations",
                  "value": n_viol, "unit": "violations"})
    out = args.out or (None if args.quick else ARTIFACT)
    if out:
        with open(out, "w") as f:
            json.dump(obs.finalize(artifact, None, append=False), f,
                      indent=1, default=str)
            f.write("\n")
    return 0 if ok else 1


if __name__ == "__main__":
    _sys.exit(main())
