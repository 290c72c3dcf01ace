"""ARTIFACT_mesh_sweep.json generator: mesh-partitioned sweep vs single-device.

The acceptance measurement of the partition layer (parallel/partition.py +
sweep.mesh_dyn_batched_fn): an 11-level Byzantine fault grid with >= 8
seeds on the 8-virtual-device CPU mesh must

- compile exactly ONE mesh executable (asserted from the registry's miss
  count around the sweep — the one-executable-per-fault-structure contract,
  now per (structure, mesh)),
- produce rows bit-equal to the single-device PR 4 sweep path (exact
  sampler pinned — the normal CLT float caveat from parallel/sweep.py), and
- beat that single-device path by >= 2x on end-to-end wall, compile
  included.

Where the win comes from (measured on this box, 1 CPU core): the mesh arm's
per-device body is a ``lax.map`` of the UNVMAPPED dynamic-fault program, so
the per-tick dynamic-update-slice pushes stay plain DUS instead of vmap's
scatter lowering, which XLA:CPU serializes (KNOWN_ISSUES.md #0b; the graph
audit shows scatter x18 in the vmapped sweep program vs x0 in the mesh
body).  On real multi-device hardware the sweep axis additionally runs in
parallel — this artifact measures the floor, not the ceiling.

Both phases run in THIS process back to back; the mesh phase runs first so
the baseline cannot warm it.

Usage:
    python tools/mesh_sweep_bench.py [--quick]

``--quick`` is the tools/lint.sh smoke (MESH_SWEEP=0 skips there): a small
n=256 grid, same assertions minus the 2x gate (too noisy at smoke scale),
emitting ``sweep_points_per_s`` to runs.jsonl ($BLOCKSIM_RUNS_JSONL) where
tools/bench_compare.py gates it higher-is-better.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
ARTIFACT = os.path.join(REPO, "ARTIFACT_mesh_sweep.json")

N_MESH = 8  # virtual CPU devices (XLA_FLAGS), sweep-axis size


def _force_cpu_mesh() -> None:
    """CPU backend with 8 virtual devices BEFORE any backend init (the
    lint.graph/_conftest contract: env for the host-device-count flag,
    config in case jax was imported before the env var was set)."""
    if "jax" not in sys.modules:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={N_MESH}"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="mesh_sweep_bench")
    p.add_argument("--quick", action="store_true",
                   help="smoke scale (n=256, 2 seeds), no artifact write, "
                        "no 2x gate — the tools/lint.sh chain entry")
    p.add_argument("--journal", action="store_true",
                   help="add a journaled phase (parallel/journal.py): the "
                        "same grid through a fresh sweep journal (fsynced "
                        "chunk appends) measuring journal_overhead_pct "
                        "(target < 3%%), then a pure-resume pass replaying "
                        "every row from the journal with zero dispatches "
                        "(resume_points_per_s); both land in the artifact "
                        "and runs.jsonl under the never-gated journal_/"
                        "resume_ prefixes")
    args = p.parse_args(argv)

    _force_cpu_mesh()
    import jax

    from blockchain_simulator_tpu.parallel.mesh import make_mesh
    from blockchain_simulator_tpu.parallel.sweep import run_byzantine_sweep
    from blockchain_simulator_tpu.utils import aotcache, obs
    from blockchain_simulator_tpu.utils.config import SimConfig

    if len(jax.devices()) < N_MESH:
        print(f"mesh_sweep_bench: need {N_MESH} devices, have "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2

    # The PR 4 sweep-cache workload (tools/sweep_cache_bench.py) at the
    # same scale, now with a real seed axis: 11 passive-Byzantine levels x
    # 16 seeds on the 10k-node round path.  stat_sampler pinned to "exact"
    # so rows are bit-stable across the differently-compiled mesh and
    # single-device programs (the "normal" CLT float caveat).
    if args.quick:
        cfg = SimConfig(
            protocol="pbft", n=256, sim_ms=600, delivery="stat",
            schedule="round", model_serialization=False, pbft_window=8,
            pbft_max_slots=48, stat_sampler="exact",
        )
        f_values = list(range(0, 85, 8))[:11]
        seeds = (0, 1)
    else:
        cfg = SimConfig(
            protocol="pbft", n=10_000, sim_ms=600, delivery="stat",
            model_serialization=False, pbft_window=8, pbft_max_slots=48,
            stat_sampler="exact",
        )
        f_values = list(range(0, 3333, 333))
        seeds = tuple(range(16))
    n_points = len(f_values) * len(seeds)
    mesh = make_mesh(n_node_shards=1, n_sweep=N_MESH)

    # ---- mesh-partitioned sweep: ONE executable over (f, seed) ----------
    s0 = aotcache.registry.stats()
    t0 = time.perf_counter()
    rows_mesh = run_byzantine_sweep(cfg, f_values=f_values, seeds=seeds,
                                    forge=False, mesh=mesh)
    mesh_wall = time.perf_counter() - t0
    s1 = aotcache.registry.stats()
    mesh_executables = s1["misses"] - s0["misses"]

    # ---- single-device PR 4 baseline: the plain vmapped dyn sweep -------
    t0 = time.perf_counter()
    rows_single = run_byzantine_sweep(cfg, f_values=f_values, seeds=seeds,
                                      forge=False)
    single_wall = time.perf_counter() - t0
    s2 = aotcache.registry.stats()

    bit_equal = (
        len(rows_mesh) == len(rows_single) == n_points
        and all(
            {k: str(v) for k, v in a.items()}
            == {k: str(v) for k, v in b.items()}
            for a, b in zip(rows_mesh, rows_single)
        )
    )
    # ---- optional journaled + resume phases (--journal) -----------------
    journal_rec = None
    if args.journal:
        import tempfile

        from blockchain_simulator_tpu.parallel.journal import SweepJournal

        with tempfile.TemporaryDirectory(
                prefix="mesh_sweep_journal_") as jdir:
            jpath = os.path.join(jdir, "sweep.journal")
            # executables are warm (both phases above ran): the delta vs
            # the mesh phase is pure journal cost — chunk keying, fsynced
            # appends, row checksums
            t0 = time.perf_counter()
            rows_journal = run_byzantine_sweep(
                cfg, f_values=f_values, seeds=seeds, forge=False, mesh=mesh,
                journal=SweepJournal(jpath),
            )
            journal_wall = time.perf_counter() - t0
            t0 = time.perf_counter()
            rows_resume = run_byzantine_sweep(
                cfg, f_values=f_values, seeds=seeds, forge=False, mesh=mesh,
                journal=SweepJournal(jpath),
            )
            resume_wall = time.perf_counter() - t0
            n_chunks = len(SweepJournal(jpath).completed())

        def norm(rs):
            return [{k: str(v) for k, v in r.items()} for r in rs]
        journal_rec = {
            "wall_s": round(journal_wall, 2),
            "overhead_pct": (round(100.0 * (journal_wall - mesh_wall)
                                   / mesh_wall, 2)
                             if mesh_wall > 0 else None),
            "overhead_target_pct": 3.0,
            "resume_wall_s": round(resume_wall, 3),
            "resume_points_per_s": (round(n_points / resume_wall, 1)
                                    if resume_wall > 0 else None),
            "rows_bit_equal": norm(rows_journal) == norm(rows_mesh),
            "resume_rows_bit_equal": norm(rows_resume) == norm(rows_journal),
            "chunks": n_chunks,
        }

    speedup = single_wall / mesh_wall if mesh_wall > 0 else None
    points_per_s = round(n_points / mesh_wall, 3) if mesh_wall > 0 else None
    rec = {
        "metric": "mesh_sweep_e2e_wall_s",
        "config": {"protocol": cfg.protocol, "n": cfg.n, "sim_ms": cfg.sim_ms,
                   "delivery": cfg.delivery, "schedule": cfg.schedule,
                   "f_levels": len(f_values), "seeds": len(seeds),
                   "points": n_points},
        "mesh": {"sweep": N_MESH, "nodes": 1},
        "mesh_phase": {
            "wall_s": round(mesh_wall, 2),
            "executables_compiled": mesh_executables,
            "rows": len(rows_mesh),
            "points_per_s": points_per_s,
        },
        "single_device": {
            "wall_s": round(single_wall, 2),
            "registry_misses": s2["misses"] - s1["misses"],
            "points_per_s": (round(n_points / single_wall, 3)
                             if single_wall > 0 else None),
        },
        "speedup_e2e": round(speedup, 2) if speedup else None,
        "rows_bit_equal": bit_equal,
        "journal": journal_rec,
        "registry": aotcache.registry.stats_snapshot(),
    }
    if not args.quick:
        with open(ARTIFACT, "w") as f:
            json.dump(rec, f, indent=1)
            f.write("\n")
    print(json.dumps(rec))
    # the gated trajectory: quick runs share one workload (lint.sh), the
    # full artifact lands under its own name so scales never mix
    obs.record_run({
        "metric": ("sweep_points_per_s" if args.quick
                   else "mesh_sweep_bench_points_per_s"),
        "value": points_per_s,
        "unit": "points/s",
        "wall_s": round(mesh_wall, 2),
        "points": n_points,
        "speedup_e2e": round(speedup, 2) if speedup else None,
    }, cfg)
    if journal_rec is not None:
        # never-gated trajectories (bench_compare journal_/resume_
        # prefixes): overhead is environment-noisy on the 1-core box, and
        # the bit-equality booleans are the real gate (folded into ok)
        obs.record_run({
            "metric": "journal_overhead_pct",
            "value": journal_rec["overhead_pct"],
            "unit": "pct",
            "wall_s": journal_rec["wall_s"],
            "points": n_points,
        }, cfg)
        obs.record_run({
            "metric": "resume_points_per_s",
            "value": journal_rec["resume_points_per_s"],
            "unit": "points/s",
            "wall_s": journal_rec["resume_wall_s"],
            "points": n_points,
        }, cfg)
    ok = (mesh_executables == 1 and bit_equal
          and (args.quick or (speedup is not None and speedup >= 2.0))
          and (journal_rec is None
               or (journal_rec["rows_bit_equal"]
                   and journal_rec["resume_rows_bit_equal"])))
    if not ok:
        print(f"mesh_sweep_bench: ACCEPTANCE NOT MET (executables="
              f"{mesh_executables}, bit_equal={bit_equal}, "
              f"speedup={speedup:.2f})", file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
