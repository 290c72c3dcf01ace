"""ARTIFACT_shard_topo.json generator: mesh-sharded topology envelope.

The acceptance measurement of the node-dim-sharded overlay programs
(parallel/sweep.sharded_topo_sim_fn — ISSUE 16 / ROADMAP item 3's 10M-node
arm):

- **correctness pins** (also the ``--quick`` lint.sh smoke): per protocol
  (pbft/raft/paxos kregular, pbft committee), the sharded program on a
  2-device mesh must be bit-equal to the single-device PR 15 program at
  equal (n, k, faults, seed) under ``stat_sampler="exact"`` — including an
  UNEVEN n (tail-shard padding) and the mesh-size-1 identity arm;
- **one executable per fault structure**: running two fault counts of the
  same structure through ``run_sharded_topo`` must build exactly one
  registry entry (asserted from the ``shard-topo-sim`` miss counter);
- **sharded-vs-single ratio @100k**: the pbft kregular edge tick engine at
  n = 100k, single-device vs the 8-virtual-device CPU mesh, measured
  ticks/s both ways.  On this 1-core box virtual devices time-slice one
  core, so the ratio measures the partitioning MECHANISM's overhead/win,
  not real-hardware capacity (KNOWN_ISSUES #0n caveat);
- **>= 4M-node envelope**: a kregular run the single-device path has never
  attempted, completing its tick budget on the 8-device mesh, peak RSS
  recorded;
- **10M analytical bytes**: ``Lowered.cost_analysis`` of the
  tables-as-operands program traced at n = 10M (abstract avals — nothing
  allocated), the per-shard working-set claim as data.

Usage:
    python tools/shard_topo_bench.py            # full artifact
    python tools/shard_topo_bench.py --quick    # lint.sh smoke
    ... [--env-n 4000000] [--env-ticks 60]

``--quick`` emits ``shard_topo_ticks_per_s`` to runs.jsonl
($BLOCKSIM_RUNS_JSONL) where tools/bench_compare.py gates it
higher-is-better; the full run's ``shard_topo_full_*`` series stays
separate so smoke and full scales never mix in one trajectory.
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
ARTIFACT = os.path.join(REPO, "ARTIFACT_shard_topo.json")

N_MESH = 8  # virtual CPU devices (XLA_FLAGS)


def _force_cpu_mesh() -> None:
    """CPU backend with 8 virtual devices BEFORE any backend init (the
    mesh_sweep_bench contract: env for the host-device-count flag,
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


def _peak_rss_mb() -> float:
    return round(resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024, 1)


def equality_block(mesh2, mesh1) -> dict:
    """Sharded-vs-single bit-equality pins at small n, per protocol."""
    from blockchain_simulator_tpu.parallel.sweep import run_sharded_topo
    from blockchain_simulator_tpu.runner import run_simulation
    from blockchain_simulator_tpu.utils.config import SimConfig

    base = dict(fidelity="clean", stat_sampler="exact",
                edge_sampler="threefry")
    cases = {
        "pbft_kreg": SimConfig(protocol="pbft", n=12, sim_ms=400,
                               topology="kregular", degree=10, **base),
        "pbft_kreg_uneven": SimConfig(protocol="pbft", n=13, sim_ms=400,
                                      topology="kregular", degree=11, **base),
        "raft_kreg": SimConfig(protocol="raft", n=12, sim_ms=1000,
                               topology="kregular", degree=9,
                               delivery="stat", raft_proposal_delay_ms=300,
                               **base),
        "paxos_kreg": SimConfig(protocol="paxos", n=12, sim_ms=800,
                                topology="kregular", degree=8, **base),
        "pbft_comm": SimConfig(protocol="pbft", n=16, sim_ms=400,
                               topology="committee", committees=4, **base),
    }
    out = {}
    for name, cfg in cases.items():
        single = run_simulation(cfg)
        out[name] = {"bit_equal": single == run_sharded_topo(cfg, mesh2)}
    out["mesh1_identity"] = {
        "bit_equal": run_simulation(cases["pbft_kreg"])
        == run_sharded_topo(cases["pbft_kreg"], mesh1)
    }
    out["all_ok"] = all(v["bit_equal"] for v in out.values())
    return out


def one_executable_block(mesh2) -> dict:
    """Two fault counts of one structure -> exactly one registry build."""
    from blockchain_simulator_tpu.parallel.sweep import run_sharded_topo
    from blockchain_simulator_tpu.utils import aotcache
    from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig

    def entries() -> int:
        snap = aotcache.registry.stats_snapshot()
        return snap["by_factory"].get("shard-topo-sim", 0)

    before = entries()
    for nc in (2, 4):
        run_sharded_topo(
            SimConfig(protocol="pbft", n=12, sim_ms=400,
                      topology="kregular", degree=10, fidelity="clean",
                      stat_sampler="exact", edge_sampler="threefry",
                      faults=FaultConfig(n_crashed=nc)),
            mesh2,
        )
    added = entries() - before
    return {"fault_counts": [2, 4], "entries_added": added,
            "one_executable": added <= 1}


def _kreg_cfg(n: int, ticks: int, degree: int = 8):
    """The ladder config shape from tools/topo_bench.py — same knobs so the
    single-device leg here lines up with the committed topo_scale rungs."""
    from blockchain_simulator_tpu.utils.config import SimConfig

    return SimConfig(
        protocol="pbft", n=n, sim_ms=ticks, fidelity="clean",
        topology="kregular", degree=degree, delivery="edge",
        edge_sampler="rbg", stat_sampler="exact", schedule="tick",
        model_serialization=False, link_delay_ms=1,
        pbft_delay_lo=1, pbft_delay_hi=3, pbft_window=8,
    )


def _timed_sharded(cfg, mesh):
    """(metrics, compile_s, exec_s) of the mesh-sharded topo program."""
    import jax
    import jax.numpy as jnp

    from blockchain_simulator_tpu.models.base import (
        canonical_fault_cfg, sim_metrics,
    )
    from blockchain_simulator_tpu.parallel.sweep import sharded_topo_sim_fn
    from blockchain_simulator_tpu.utils import obs

    canon = canonical_fault_cfg(cfg)
    sim = sharded_topo_sim_fn(canon, mesh)
    nc = jnp.int32(cfg.faults.resolved_n_crashed(cfg.n))
    nb = jnp.int32(cfg.faults.n_byzantine)
    final, compile_s, exec_s = obs.timed_run(
        lambda key: sim(key, nc, nb), jax.random.key(cfg.seed)
    )
    return sim_metrics(cfg, final), compile_s, exec_s


def _timed_single(cfg):
    """(metrics, compile_s, exec_s) of the single-device PR 15 program."""
    import jax

    from blockchain_simulator_tpu.models.base import sim_metrics
    from blockchain_simulator_tpu.runner import make_sim_fn
    from blockchain_simulator_tpu.utils import obs

    sim = make_sim_fn(cfg)
    final, compile_s, exec_s = obs.timed_run(sim, jax.random.key(cfg.seed))
    return sim_metrics(cfg, final), compile_s, exec_s


def ratio_block(mesh, n: int, ticks: int) -> dict:
    """Sharded (8 virtual devices) vs single-device kregular ticks/s."""
    cfg = _kreg_cfg(n, ticks)
    out = {"n": n, "ticks": ticks, "degree": 8, "n_devices": N_MESH}
    for name, runner_ in (
        ("single", lambda: _timed_single(cfg)),
        ("sharded", lambda: _timed_sharded(cfg, mesh)),
    ):
        _m, compile_s, exec_s = runner_()
        out[name] = {
            "compile_s": round(compile_s, 2),
            "exec_s": round(exec_s, 3),
            "ticks_per_s": round(ticks / exec_s, 2) if exec_s > 0 else None,
        }
    s, sh = out["single"], out["sharded"]
    if s["ticks_per_s"] and sh["ticks_per_s"]:
        out["sharded_over_single"] = round(
            sh["ticks_per_s"] / s["ticks_per_s"], 2
        )
    return out


def envelope_row(mesh, n: int, ticks: int, degree: int = 8) -> dict:
    """The >= 4M-node kregular rung on the 8-device mesh — a node count the
    single-device ladder has never attempted."""
    cfg = _kreg_cfg(n, ticks, degree)
    t0 = time.monotonic()
    m, compile_s, exec_s = _timed_sharded(cfg, mesh)
    return {
        "n": n, "degree": degree, "ticks": ticks, "n_devices": N_MESH,
        "compile_s": round(compile_s, 2),
        "exec_s": round(exec_s, 3),
        "ticks_per_s": round(ticks / exec_s, 2) if exec_s > 0 else None,
        "wall_s": round(time.monotonic() - t0, 2),
        "peak_rss_mb": _peak_rss_mb(),
        "rounds_sent": m.get("rounds_sent"),
        "completed_tick_budget": m.get("rounds_sent") is not None,
    }


def analytical_block(n: int) -> dict:
    """Cost-analysis bytes of the tables-as-operands program traced at
    ``n`` — abstract avals only, nothing allocated (the 10M claim)."""
    import jax
    import jax.numpy as jnp

    from blockchain_simulator_tpu.models.base import canonical_fault_cfg
    from blockchain_simulator_tpu.runner import (
        make_topo_dyn_sim_fn, topo_tables_inslot,
    )

    cfg = canonical_fault_cfg(_kreg_cfg(n, 60))
    fn = make_topo_dyn_sim_fn(cfg)
    n_tables = 3 if topo_tables_inslot(cfg) else 2
    tab_sds = tuple(
        jax.ShapeDtypeStruct((cfg.n, cfg.degree + 1), jnp.int32)
        for _ in range(n_tables)
    )
    key_sds = jax.eval_shape(lambda: jax.random.key(0))
    cnt = jax.ShapeDtypeStruct((), jnp.int32)
    try:
        # trace-only (never executed): one call per bench run — the same
        # sanction tools/topo_bench._analytical_bytes carries
        cost = jax.jit(fn).lower(key_sds, cnt, cnt, *tab_sds).cost_analysis()  # jaxlint: disable=static-arg-recompile-hazard
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        bytes_ = float(cost.get("bytes accessed", 0.0)) or None
    except Exception:
        bytes_ = None
    table_mb = round(n_tables * n * (cfg.degree + 1) * 4 / 2**20, 1)
    return {
        "n": n, "degree": cfg.degree,
        "analytical_bytes": bytes_,
        "table_operand_mb": table_mb,
        "dense_edge_tensor_tb": round(n * n * 4 / 2**40, 1),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="shard_topo_bench")
    p.add_argument("--quick", action="store_true",
                   help="lint.sh smoke: equality + one-executable pins plus "
                        "one small sharded run; no artifact write")
    p.add_argument("--ratio-n", type=int, default=100_000)
    p.add_argument("--ratio-ticks", type=int, default=60)
    p.add_argument("--env-n", type=int, default=4_000_000,
                   help="envelope node count (>= 4M for the acceptance)")
    p.add_argument("--env-ticks", type=int, default=60)
    args = p.parse_args(argv)

    _force_cpu_mesh()
    import jax

    from blockchain_simulator_tpu.parallel.mesh import make_mesh
    from blockchain_simulator_tpu.utils import obs

    if len(jax.devices()) < N_MESH:
        print(f"shard_topo_bench: need {N_MESH} devices, have "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2

    mesh1 = make_mesh(n_node_shards=1, n_sweep=1, devices=jax.devices()[:1])
    mesh2 = make_mesh(n_node_shards=2, n_sweep=1, devices=jax.devices()[:2])
    mesh8 = make_mesh(n_node_shards=N_MESH, n_sweep=1)

    eq = equality_block(mesh2, mesh1)
    if not eq["all_ok"]:
        print(f"shard_topo_bench: EQUALITY PINS FAILED: {json.dumps(eq)}")
        return 1
    one = one_executable_block(mesh2)
    if not one["one_executable"]:
        print(f"shard_topo_bench: REGISTRY PIN FAILED: {json.dumps(one)}")
        return 1

    if args.quick:
        # one genuinely sharded rung, small: proves the pjit program
        # compiles + runs over the full 8-device mesh end to end
        row = envelope_row(mesh8, 4096, 120)
        rec = {"quick": True, "equality": eq, "one_executable": one,
               "kregular_4096": row}
        obs.finalize({"metric": "shard_topo_ticks_per_s",
                      "value": row["ticks_per_s"], "unit": "ticks/s"})
        print(json.dumps(obs.finalize(rec, None, append=False)))
        return 0 if row["ticks_per_s"] else 1

    ratio = ratio_block(mesh8, args.ratio_n, args.ratio_ticks)
    obs.finalize({"metric": f"shard_topo_full_ratio_{args.ratio_n}",
                  "value": ratio.get("sharded_over_single"), "unit": "x"})
    env = envelope_row(mesh8, args.env_n, args.env_ticks)
    obs.finalize({"metric": f"shard_topo_full_ticks_per_s_{args.env_n}",
                  "value": env["ticks_per_s"], "unit": "ticks/s"})
    analytical = analytical_block(10_000_000)

    rec = {
        "metric": "shard_topo_envelope_ticks_per_s",
        "value": env["ticks_per_s"],
        "unit": "ticks/s",
        "equality": eq,
        "one_executable": one,
        "ratio_100k": ratio,
        "envelope": env,
        "analytical_10m": analytical,
        "note": (
            "virtual CPU devices time-slice ONE core on this box: the "
            "ratio leg measures the sharding mechanism's overhead/win, not "
            "real-hardware capacity (each real device would hold 1/8th of "
            "the [K, N] working set and run concurrently).  The envelope "
            "row is a node count the single-device ladder never attempted; "
            "the 10M block is trace-only cost analysis of the "
            "tables-as-operands program (KNOWN_ISSUES #0n escape hatch, "
            "now implemented)."
        ),
    }
    with open(ARTIFACT, "w") as f:
        json.dump(rec, f, indent=1)
        f.write("\n")
    print(json.dumps(obs.finalize(dict(rec), None, append=False)))
    accept = (
        eq["all_ok"]
        and one["one_executable"]
        and ratio.get("sharded_over_single") is not None
        and env["n"] >= 4_000_000
        and env["completed_tick_budget"]
        and env["ticks_per_s"]
    )
    if not accept:
        print("shard_topo_bench: ACCEPTANCE NOT MET")
    return 0 if accept else 1


if __name__ == "__main__":
    raise SystemExit(main())
