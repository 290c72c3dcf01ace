"""ARTIFACT_tick_bench.json generator: tick-engine raw speed (ISSUE 13).

Every config the compiled fast paths refuse — windowed drops, view changes,
split elections, Byzantine fallbacks, async/lossy scenarios — lands on the
general per-tick engine, and KNOWN_ISSUES #5 established that its ~3 ms/tick
wall is sampling/delivery COMPUTE, not memory traffic (the DUS push chain
already runs ~75% of the bandwidth bound).  This tool measures the three
attacks this PR mounts on that wall, in ONE artifact so the before/after
ratio is a same-box, same-process comparison (the 1-core-box convention from
ROADMAP "Measured floors"):

- **multi-seed dispatch arms** (the headline ratio): B seeds of one tick
  config through

  * ``seq``        — B sequential solo dispatches of ``jit(make_dyn_sim_fn)``
                     (the pre-PR per-seed loop; also the bit-equality
                     reference),
  * ``vmapped``    — ONE ``sweep.dyn_batched_fn`` dispatch (the pre-PR
                     batched path every sweep/serve tile takes today), and
  * ``multi_seed`` — ONE ``sweep.multi_seed_fn`` dispatch (the new
                     ``lax.map``-over-unvmapped scatter-free arm).

  The acceptance gate is ``multi_seed`` rounds/s >= 1.5x ``vmapped`` at 10k
  nodes with per-seed rows bit-equal to ``seq`` (stat_sampler pinned
  "exact" — the parallel/sweep.py CLT float caveat).

- **compute split**: XLA cost analysis (flops / bytes accessed, via
  ``aotcache.cost_of``) of the vmapped vs multi-seed programs, per seed —
  the fusion work (ops/delivery.py fused pushes, vectorized bucket math)
  shows up as the bytes-per-seed delta, and the scatter elimination as the
  wall delta at ~equal flops.

- **sampler modes**: solo tick-engine rounds/s per stat sampler mode
  ("exact" vs "normal") at the headline n, and per edge sampler impl
  ("threefry" vs "rbg") on an edge-delivery config at a smaller n (the
  edge path is O(N^2) per active tick) — the trade-off table README's
  "Tick-engine performance" section quotes.

Usage:
    python tools/tick_bench.py [--quick] [--protocols pbft,raft,paxos]

``--quick`` is the tools/lint.sh smoke (TICK=0 skips there): n=256, two
seeds, pbft only, same bit-equality + ONE-executable assertions minus the
1.5x gate (noise at smoke scale), emitting ``tick_rounds_per_s`` to
runs.jsonl ($BLOCKSIM_RUNS_JSONL) where tools/bench_compare.py gates it
higher-is-better.  Full runs emit a separate ``tick_bench_*`` series so
quick/full scales never mix (the mesh_sweep_bench precedent).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
ARTIFACT = os.path.join(REPO, "ARTIFACT_tick_bench.json")


def _force_cpu() -> None:
    if "jax" not in sys.modules:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")


def _tick_cfg(protocol: str, n: int, sim_ms: int, **kw):
    from blockchain_simulator_tpu.utils.config import SimConfig

    base = dict(
        protocol=protocol, n=n, sim_ms=sim_ms, schedule="tick",
        delivery="stat" if protocol in ("pbft", "raft") else "edge",
        model_serialization=False, stat_sampler="exact",
    )
    if protocol == "pbft":
        rounds = max(sim_ms // 50 - 1, 1)
        base.update(pbft_max_rounds=rounds, pbft_max_slots=rounds + 8,
                    pbft_window=8)
    base.update(kw)
    return SimConfig(**base)


def _rounds(cfg) -> int:
    """Consensus rounds the config drives — the unit of the rounds/s
    metric (bench.py convention: pbft rounds; raft heartbeats; paxos has
    no round clock, so fall back to ticks/50 for a comparable scale)."""
    if cfg.protocol == "pbft":
        return max(cfg.sim_ms // cfg.pbft_block_interval_ms - 1, 1)
    if cfg.protocol == "raft":
        return max(cfg.sim_ms // cfg.raft_heartbeat_ms, 1)
    return max(cfg.sim_ms // 50, 1)


def _norm(rows):
    return [{k: str(v) for k, v in r.items()} for r in rows]


def _timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _metrics_rows(cfg, proto, finals, n_seeds):
    import jax

    return [
        proto.metrics(cfg, jax.tree.map(lambda x: x[i], finals))
        for i in range(n_seeds)
    ]


def bench_protocol(cfg, seeds):
    """The three dispatch arms for one tick config; returns the artifact
    record (rows checked bit-equal, ONE executable pinned)."""
    import jax
    import jax.numpy as jnp

    from blockchain_simulator_tpu.models.base import (
        canonical_fault_cfg,
        get_protocol,
    )
    from blockchain_simulator_tpu.parallel import sweep
    from blockchain_simulator_tpu.serve import dispatch
    from blockchain_simulator_tpu.utils import aotcache

    canon = canonical_fault_cfg(cfg)
    proto = get_protocol(cfg.protocol)
    b = len(seeds)
    keys = jax.vmap(jax.random.key)(jnp.asarray(seeds, jnp.uint32))
    nc = jnp.zeros((b,), jnp.int32)
    nb = jnp.zeros((b,), jnp.int32)
    rounds_total = _rounds(cfg) * b

    def _staged(fn, *example):
        """Compile ONCE via the AOT stage and time the compiled executable
        directly — jit's own call path would compile a second program."""
        t0 = time.perf_counter()
        compiled = fn.lower(*example).compile()
        compile_s = time.perf_counter() - t0
        _ = _timed(lambda: compiled(*example))  # warm (first-run constants)
        return compiled, compile_s, aotcache.cost_of(compiled)

    # --- seq: the pre-PR per-seed loop (and the bit-equality reference) —
    # the registry's serve-solo entry, the same program a serving degrade
    # or a solo run dispatches
    solo, solo_compile, _ = _staged(dispatch._solo_fn(canon), keys[0],
                                    nc[0], nb[0])
    t0 = time.perf_counter()
    seq_rows = []
    for i in range(b):
        final = jax.block_until_ready(solo(keys[i], nc[i], nb[i]))
        seq_rows.append(proto.metrics(cfg, final))
    seq_wall = time.perf_counter() - t0

    # --- vmapped: the pre-PR batched dispatch (sweeps/serve tiles) ------
    vfn, v_compile, vcost = _staged(sweep.dyn_batched_fn(canon), keys, nc, nb)
    finals, v_wall = _timed(lambda: vfn(keys, nc, nb))
    v_rows = _metrics_rows(cfg, proto, finals, b)

    # --- multi_seed: the new scatter-free lax.map arm -------------------
    s0 = aotcache.registry.stats()
    mfn, m_compile, mcost = _staged(sweep.multi_seed_fn(canon, b), keys, nc,
                                    nb)
    finals, m_wall = _timed(lambda: mfn(keys, nc, nb))
    m_rows = _metrics_rows(cfg, proto, finals, b)
    s1 = aotcache.registry.stats()
    ms_executables = s1["misses"] - s0["misses"]

    bit_equal_seq = _norm(m_rows) == _norm(seq_rows)
    bit_equal_vmap = _norm(m_rows) == _norm(v_rows)
    ratio = (v_wall / m_wall) if m_wall > 0 else None

    def _per_seed(cost):
        if not cost:
            return None
        return {"flops": round(cost["flops"] / b),
                "bytes": round(cost["bytes"] / b)}

    return {
        "protocol": cfg.protocol,
        "n": cfg.n,
        "sim_ms": cfg.sim_ms,
        "seeds": b,
        "rounds_total": rounds_total,
        "seq": {
            "wall_s": round(seq_wall, 3),
            "rounds_per_s": round(rounds_total / seq_wall, 2),
            "compile_s": round(solo_compile, 2),
        },
        "vmapped": {
            "wall_s": round(v_wall, 3),
            "rounds_per_s": round(rounds_total / v_wall, 2),
            "compile_s": round(v_compile, 2),
            "cost_per_seed": _per_seed(vcost),
        },
        "multi_seed": {
            "wall_s": round(m_wall, 3),
            "rounds_per_s": round(rounds_total / m_wall, 2),
            "compile_s": round(m_compile, 2),
            "cost_per_seed": _per_seed(mcost),
            "executables_compiled": ms_executables,
        },
        "speedup_vs_vmapped": round(ratio, 2) if ratio else None,
        "speedup_vs_seq": (round(seq_wall / m_wall, 2) if m_wall > 0
                           else None),
        "rows_bit_equal_seq": bit_equal_seq,
        "rows_bit_equal_vmapped": bit_equal_vmap,
    }


def bench_samplers(n: int, sim_ms: int, edge_n: int, edge_ms: int):
    """Sampler-mode trade-off rows: solo tick-engine walls per stat mode
    and per edge impl (fresh executables; rounds/s comparable only within
    one row pair)."""
    import jax

    from blockchain_simulator_tpu.runner import make_sim_fn

    rows = []
    for label, cfg in (
        ("stat_exact", _tick_cfg("pbft", n, sim_ms, stat_sampler="exact")),
        ("stat_normal", _tick_cfg("pbft", n, sim_ms, stat_sampler="normal")),
        ("edge_threefry", _tick_cfg("pbft", edge_n, edge_ms, delivery="edge",
                                    edge_sampler="threefry")),
        ("edge_rbg", _tick_cfg("pbft", edge_n, edge_ms, delivery="edge",
                               edge_sampler="rbg")),
    ):
        sim = make_sim_fn(cfg)
        key = jax.random.key(0)
        _timed(lambda: sim(key))  # warm (compile + first run, discarded)
        _, wall = _timed(lambda: sim(key))
        rows.append({
            "sampler": label,
            "n": cfg.n,
            "sim_ms": cfg.sim_ms,
            "wall_s": round(wall, 3),
            "rounds_per_s": round(_rounds(cfg) / wall, 2),
            "ticks_per_s": round(cfg.ticks / wall, 1),
        })
    return rows


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="tick_bench")
    p.add_argument("--quick", action="store_true",
                   help="smoke scale (n=256, pbft only), no artifact write, "
                        "no 1.5x gate — the tools/lint.sh chain entry")
    p.add_argument("--protocols", default="pbft,raft,paxos",
                   help="comma list for the full run (default all three)")
    p.add_argument("--n", type=int, default=10_000,
                   help="headline node count (default 10000)")
    p.add_argument("--seeds", type=int, default=4,
                   help="Monte Carlo batch width (default 4)")
    args = p.parse_args(argv)

    _force_cpu()
    from blockchain_simulator_tpu.utils import obs

    seeds = tuple(range(args.seeds))
    if args.quick:
        protocols, n, sim_ms = ["pbft"], 256, 400
        seeds = (0, 1)
    else:
        protocols, n, sim_ms = args.protocols.split(","), args.n, 600

    results = [
        bench_protocol(_tick_cfg(proto, n, sim_ms), seeds)
        for proto in protocols
    ]
    sampler_rows = (
        None if args.quick
        else bench_samplers(n, sim_ms, edge_n=1024, edge_ms=300)
    )

    head = results[0]  # pbft — the gated headline
    rec = {
        "metric": "tick_bench",
        "box_note": "1-core XLA:CPU box: every ratio is same-artifact, "
                    "same-process (ROADMAP measured-floors convention)",
        "headline": {
            "n": head["n"],
            "tick_rounds_per_s": head["multi_seed"]["rounds_per_s"],
            "speedup_vs_vmapped": head["speedup_vs_vmapped"],
            "rows_bit_equal": head["rows_bit_equal_seq"],
        },
        "protocols": results,
        "samplers": sampler_rows,
    }
    if not args.quick:
        with open(ARTIFACT, "w") as f:
            json.dump(rec, f, indent=1)
            f.write("\n")
    print(json.dumps(rec))

    cfg0 = _tick_cfg(protocols[0], n, sim_ms)
    obs.record_run({
        "metric": ("tick_rounds_per_s" if args.quick
                   else "tick_bench_rounds_per_s"),
        "value": head["multi_seed"]["rounds_per_s"],
        "unit": "rounds/s",
        "wall_s": head["multi_seed"]["wall_s"],
        "speedup_vs_vmapped": head["speedup_vs_vmapped"],
    }, cfg0)

    ok = all(
        r["rows_bit_equal_seq"] and r["rows_bit_equal_vmapped"]
        and r["multi_seed"]["executables_compiled"] == 1
        for r in results
    )
    if not args.quick:
        ok = ok and all(
            r["speedup_vs_vmapped"] is not None
            and r["speedup_vs_vmapped"] >= (1.5 if r["protocol"] == "pbft"
                                            else 1.0)
            for r in results
        )
    if not ok:
        print("tick_bench: ACCEPTANCE NOT MET "
              + json.dumps([{k: r[k] for k in
                             ("protocol", "speedup_vs_vmapped",
                              "rows_bit_equal_seq", "rows_bit_equal_vmapped")}
                            for r in results]),
              file=sys.stderr)
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
