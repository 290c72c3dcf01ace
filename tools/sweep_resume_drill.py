"""ARTIFACT_resume_sweep.json generator: kill -9 a journaled sweep, resume it.

The acceptance drill of the durable-sweep journal (parallel/journal.py):
a REAL subprocess runs a journaled Byzantine fault sweep
(``run_byzantine_sweep(journal=...)``) and is SIGKILLed mid-grid with
completed chunks on disk; rerunning the same command resumes — and the
drill demands:

- **recompute at most one chunk** — every chunk journaled before the
  kill is served from the journal (its key never reappears; the resumed
  process appends exactly the missing chunks, so only the one in-flight
  chunk's work is repeated);
- **rows bit-equal** — the final journal replayed in-process (a pure
  resume: zero dispatches, zero registry misses) produces rows
  bit-equal (exact sampler) to an uninterrupted reference sweep;
- **0 invariant violations** — chaos/invariants.check_sweep_journal
  (unique chunk keys, clean checksums, full coverage).

The kill window is widened deterministically the way the serve kill -9
drill holds its batch (max_wait 5000): the child arms a chaos
``slow_next`` on every ``sweep.chunk`` firing, so the parent's journal
poll always finds the grid mid-flight.

Usage:
    JAX_PLATFORMS=cpu python tools/sweep_resume_drill.py [--quick]

``--quick`` is the tools/lint.sh chain shape (``RESUME=0`` skips): the
toy n=8 grid, no artifact write.  The full run uses the mesh-sweep
bench's n=256 round-path grid and writes the artifact.  Exit 0 only
with zero violations.  When ``$BLOCKSIM_RUNS_JSONL`` is set the drill
lands ``resume_recomputed_chunks`` / ``resume_invariant_violations``
(lower-is-better counters; tools/bench_compare.py never gates the
``resume_`` prefix — this drill's exit code is the gate).
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
ARTIFACT = os.path.join(REPO, "ARTIFACT_resume_sweep.json")


def _force_platform(platform: str | None) -> None:
    if not platform:
        return
    # the drill's daemon/sweep children inherit its platform through their
    # environment (the serve daemon has no platform flag of its own)
    os.environ["JAX_PLATFORMS"] = platform
    import jax

    jax.config.update("jax_platforms", platform)


def _grid(quick: bool):
    """The drill grid: quick = the chaos-scenario toy shape; full = the
    mesh-sweep bench's round-path config at smoke n.  Exact sampler
    pinned — resumed rows must be bit-stable across processes."""
    from blockchain_simulator_tpu.utils.config import SimConfig

    if quick:
        cfg = SimConfig(protocol="pbft", n=8, sim_ms=200,
                        stat_sampler="exact")
        f_values = list(range(0, 2 * 2 + 1, 1))[:5]
        seeds = (0, 1)
    else:
        cfg = SimConfig(protocol="pbft", n=256, sim_ms=600, delivery="stat",
                        schedule="round", model_serialization=False,
                        pbft_window=8, pbft_max_slots=48,
                        stat_sampler="exact")
        f_values = list(range(0, 85, 8))[:11]
        seeds = (0, 1)
    return cfg, f_values, seeds


def child_main(args) -> int:
    """The journaled sweep, as its own process (the thing that gets
    SIGKILLed).  Prints one final JSON summary line; a killed child
    never reaches it — the journal IS its record."""
    _force_platform(args.platform)
    from blockchain_simulator_tpu.chaos import inject
    from blockchain_simulator_tpu.parallel.journal import SweepJournal
    from blockchain_simulator_tpu.parallel.sweep import run_byzantine_sweep
    from blockchain_simulator_tpu.utils import aotcache

    cfg, f_values, seeds = _grid(args.quick)
    journal = SweepJournal(args.journal)
    chunks_before = len(SweepJournal(args.journal).completed())
    ctl = None
    if args.slow_chunk_ms > 0:
        # widen the parent's kill window deterministically: every chunk
        # dispatch sleeps first, so >= one chunk is always in flight
        # while the parent polls the journal
        ctl = inject.ChaosController(seed=0)
        ctl.slow_next("sweep.chunk", args.slow_chunk_ms / 1000.0, n=10_000)
        ctl.install()
    m0 = aotcache.registry.stats()["misses"]
    try:
        rows = run_byzantine_sweep(cfg, f_values=f_values, seeds=seeds,
                                   forge=False, journal=journal)
    finally:
        if ctl is not None:
            ctl.uninstall()
    print(json.dumps({
        "rows": len(rows),
        "chunks_before": chunks_before,
        "chunks_after": len(SweepJournal(args.journal).completed()),
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
           "--slow-chunk-ms", str(slow_ms),
           "--platform", args.platform or "cpu"]
    if args.quick:
        cmd.append("--quick")
    return subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.DEVNULL, text=True, env=env,
                            cwd=REPO)


def kill9_leg(args, workdir: str) -> dict:
    """SIGKILL a journaled-sweep child mid-grid, resume with a second
    child, verify the journal in-process."""
    import dataclasses

    from blockchain_simulator_tpu.chaos import invariants
    from blockchain_simulator_tpu.parallel.journal import SweepJournal
    from blockchain_simulator_tpu.parallel.sweep import (
        dyn_chunk_keys,
        run_byzantine_sweep,
    )
    from blockchain_simulator_tpu.utils import aotcache, obs

    cfg, f_values, seeds = _grid(args.quick)
    n_levels = len(dict.fromkeys(f_values))
    n_points = n_levels * len(seeds)
    # the chunk keys the sweep WILL use, derived from the grid (the same
    # fault configs run_byzantine_sweep builds) — coverage evidence
    # independent of the journal's own content
    grid_fcs = list(dict.fromkeys(
        dataclasses.replace(cfg.faults, n_byzantine=f, byz_forge=False)
        for f in f_values
    ))
    expected_keys = dyn_chunk_keys(cfg, grid_fcs, seeds)
    journal_path = os.path.join(workdir, "sweep.journal")
    rec: dict = {"leg": "kill9", "points": n_points, "chunks": n_levels}
    violations: list[str] = []

    # uninterrupted reference, in this process (journal-less)
    reference = run_byzantine_sweep(cfg, f_values=f_values, seeds=seeds,
                                    forge=False)

    # phase 1: child 1 sweeps journaled, slowed; SIGKILL once >= 2 chunks
    # are durable (and the grid still has chunks to go)
    proc = _spawn_child(args, journal_path, workdir, args.slow_chunk_ms)
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
    rec["chunks_at_kill"] = len(pre_keys)
    if not killed:
        violations.append(
            f"child finished all {n_levels} chunks before the kill window "
            f"(slow-chunk-ms too small)")
    if len(pre_keys) == 0:
        violations.append("no chunk survived the kill (nothing durable)")

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
    rec["resume_summary"] = summary
    post = SweepJournal(journal_path)
    post_keys = set(post.completed())
    appended = post_keys - pre_keys
    recomputed = [k for k in pre_keys
                  if sum(1 for line in post.chunk_lines()
                         if str(line.get("key")) == k) > 1]
    rec["chunks_resumed"] = len(appended)
    rec["recomputed_completed_chunks"] = len(recomputed)
    if recomputed:
        violations.append(
            f"{len(recomputed)} completed chunks recomputed on resume "
            f"(recompute-at-most-one broken): {sorted(recomputed)}")
    if summary.get("chunks_before") != len(pre_keys):
        violations.append(
            f"resume child saw {summary.get('chunks_before')} chunks, "
            f"parent journal had {len(pre_keys)}")
    if len(post_keys) != n_levels:
        violations.append(
            f"final journal has {len(post_keys)} chunks, want {n_levels}")

    # phase 3: pure in-process resume — zero dispatches, zero misses —
    # must reproduce the reference bit-for-bit (exact sampler)
    m0 = aotcache.registry.stats()["misses"]
    resumed = run_byzantine_sweep(cfg, f_values=f_values, seeds=seeds,
                                  forge=False,
                                  journal=SweepJournal(journal_path))
    replay_misses = aotcache.registry.stats()["misses"] - m0
    rec["replay_misses"] = replay_misses
    if replay_misses != 0:
        violations.append(
            f"pure journal replay compiled {replay_misses} executables")
    bit_equal = (
        len(resumed) == len(reference) == n_points
        and all(obs.canonical_json(a) == obs.canonical_json(b)
                for a, b in zip(resumed, reference))
    )
    rec["rows_bit_equal"] = bit_equal
    if not bit_equal:
        violations.append("resumed rows diverge from the uninterrupted "
                          "reference sweep")
    violations += invariants.check_sweep_journal(
        post, expected_keys=expected_keys, expected_rows=n_points)
    if set(expected_keys) != post_keys:
        violations.append(
            f"journaled keys differ from the planned grid: "
            f"{sorted(post_keys ^ set(expected_keys))}")
    rec["violations"] = violations
    return rec


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="sweep_resume_drill")
    p.add_argument("--quick", action="store_true",
                   help="CI shape (tools/lint.sh, RESUME=0 skips): the "
                        "toy n=8 grid, no artifact write")
    p.add_argument("--child", action="store_true",
                   help="internal: run the journaled sweep in this "
                        "process (the SIGKILL target)")
    p.add_argument("--journal", default=None,
                   help="internal (--child): journal path")
    p.add_argument("--slow-chunk-ms", type=int, default=250,
                   help="chaos-slow every chunk dispatch by this much in "
                        "the first child so the kill always lands "
                        "mid-grid (0 disables; the resume child runs "
                        "unslowed)")
    p.add_argument("--out", default=None,
                   help="artifact path (default: ARTIFACT_resume_sweep."
                        "json on full runs, none on --quick)")
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
    with tempfile.TemporaryDirectory(prefix="sweep_resume_") as wd:
        rec = kill9_leg(args, wd)
    ok = not rec["violations"]
    artifact = {
        "metric": "resume_sweep_drill",
        "ok": ok,
        "quick": args.quick,
        "kill9": rec,
        "invariant_violations": len(rec["violations"]),
        "wall_s": round(time.monotonic() - t0, 2),
    }
    print(json.dumps(obs.finalize(dict(artifact), None, append=False)),
          flush=True)
    # lower-is-better counters; bench_compare never gates the resume_
    # prefix (this drill's own exit code is the gate)
    obs.finalize({"metric": "resume_invariant_violations",
                  "value": len(rec["violations"]), "unit": "violations"})
    obs.finalize({"metric": "resume_recomputed_chunks",
                  "value": rec.get("recomputed_completed_chunks"),
                  "unit": "chunks"})
    out = args.out or (None if args.quick else ARTIFACT)
    if out:
        with open(out, "w") as f:
            json.dump(obs.finalize(artifact, None, append=False), f,
                      indent=1, default=str)
            f.write("\n")
    return 0 if ok else 1


if __name__ == "__main__":
    _sys.exit(main())
