"""Readings on the chip for the cell ``paxos10k.mesh4`` (BASELINE config 3),
at the cell's own size and through the cell's own seam
(``shard.make_sharded_sim_fn`` over ``node_shards`` chips, ``shard.readback``,
``sim_metrics``): what ``PERF.md`` sets ``sim_ms`` and the limits of
``configs/paxos-gossip-10k.json`` from.

    python benchmark/tests/paxos_chip_readings.py [--seeds 12] [--sim-ms 6000]
                                                  [--control-seeds 2] [--refs 0]

1. ``--seeds`` sound runs at ``--sim-ms`` (longer than the cell's, so that a
   late third proposer is seen, not cut): each proposer's commit time, the
   counts, the milestones, the run's wall time.
2. each control of the configuration file, ``--control-seeds`` runs at the
   cell's own ``sim_ms``.
3. ``--refs`` runs of the plain reference at ``--sim-ms`` (host only, about
   20 s each at 10,000 nodes: run them where no chip is held).

One JSON line per run on stdout, all of them again in
``chiprun_out/paxos_readings.jsonl``.  ``chip_readings.py`` reads the other
cells; nothing here is imported by the harness.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

KEEP = ("n_committed_proposers", "winner", "winner_commit_ms", "retries",
        "acceptor_executes", "first_execute_ms", "decided_command", "gave_up",
        "agreement_ok", "winner_window_ms", "commit_flood_ms",
        "first_execute_lag_ms", "solo_window_ms")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--sim-ms", type=int, default=6000)
    ap.add_argument("--control-seeds", type=int, default=2)
    ap.add_argument("--refs", type=int, default=0)
    ap.add_argument("--seed0", type=int, default=2_147_483_659)
    args = ap.parse_args()

    import run as bench

    spec = bench.load_json(ROOT, "BENCHMARK.json")
    got = bench.resolve(spec, "paxos10k.mesh4")
    config, traffic = got["config"], got["traffic"]
    out_path = os.path.join(ROOT, "chiprun_out", "paxos_readings.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    out = open(out_path, "a")

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        out.write(line + "\n")
        out.flush()

    fields = dict(config["fields"])
    if args.refs:
        import checks
        import paxos_checks

        engine = checks._engine(config["reference"]["engine"])
        f = {**fields, "sim_ms": args.sim_ms}
        nbrs = paxos_checks.overlay_of(f)
        for i in range(args.refs):
            t0 = time.monotonic()
            m = engine.run(f, args.seed0 + i, nbrs)
            emit({"what": "reference", "seed": args.seed0 + i,
                  "sim_ms": args.sim_ms, "wall_s": time.monotonic() - t0,
                  "events": m["events"], "last_commit_ms": m["last_commit_ms"],
                  "window_ms": m["window_ms"],
                  **{k: m[k] for k in KEEP}})
        if not args.seeds:
            return 0

    import jax
    import numpy as np

    import program
    from blockchain_simulator_tpu.models.base import sim_metrics
    from blockchain_simulator_tpu.parallel import shard
    from blockchain_simulator_tpu.parallel.mesh import make_mesh
    from blockchain_simulator_tpu.utils import aotcache

    aotcache.enable_xla_cache()
    devs = jax.devices()
    shards = min(int(traffic["node_shards"]), len(devs))
    mesh = make_mesh(n_node_shards=shards, devices=devs[:shards])
    emit({"what": "device", "platform": devs[0].platform,
          "kind": devs[0].device_kind, "count": len(devs), "shards": shards})

    def runs(what, f, seeds):
        cfg = program.sim_config(f)
        t0 = time.monotonic()
        sim = shard.make_sharded_sim_fn(cfg, mesh)
        jax.block_until_ready(sim(jax.random.key(1)))
        emit({"what": what + ".first_call_s", "s": time.monotonic() - t0})
        for s in seeds:
            t0 = time.monotonic()
            final = jax.block_until_ready(sim(jax.random.key(s)))
            wall = time.monotonic() - t0
            m = sim_metrics(cfg, shard.readback(cfg, mesh, final))
            p = cfg.paxos_n_proposers
            emit({"what": what, "seed": s, "sim_ms": cfg.sim_ms,
                  "wall_s": wall,
                  "commit_ms": np.asarray(final.commit_tick)[:p].tolist(),
                  "tickets": np.asarray(final.ticket)[:p].tolist(),
                  **{k: m[k] for k in KEEP}})

    seeds = [args.seed0 + 7919 * i for i in range(args.seeds)]
    runs("sound", {**fields, "sim_ms": args.sim_ms}, seeds)
    for c in config["controls"] if args.control_seeds else ():
        runs("control." + c["name"], {**fields, **c["fields"]},
             seeds[:args.control_seeds])
    return 0


if __name__ == "__main__":
    sys.exit(main())
