"""Command-line driver.

The reference's ``main`` (blockchain-simulator.cc:63) instantiates
``ns3::CommandLine`` but registers zero flags (SURVEY.md §5): N is hard-coded
to 8, the protocol is chosen by *editing two source files*
(network-helper.cc:17, blockchain-simulator.cc:72), and every operating
constant is a literal.  Here every one of those constants is a runtime flag
over the typed ``SimConfig`` (utils/config.py), the protocol is selected by
name, and the execution engine is switchable between the JAX/TPU backend and
the C++ CPU reference engine.

    python -m blockchain_simulator_tpu --protocol pbft --n 8 --sim-ms 2500
    python -m blockchain_simulator_tpu --protocol paxos --engine cpp --seeds 0 1 2
    python -m blockchain_simulator_tpu --protocol raft --n 64 --shards 8

Output: one JSON metrics line per run (the reference's NS_LOG measurement
surface as structured data, SURVEY.md §5).
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys

from blockchain_simulator_tpu.utils import obs
from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig


def build_parser() -> argparse.ArgumentParser:
    d = SimConfig()
    p = argparse.ArgumentParser(
        prog="blockchain_simulator_tpu",
        description="TPU-native blockchain-consensus simulation framework",
    )
    p.add_argument("--protocol", choices=["pbft", "raft", "paxos", "mixed"],
                   default=d.protocol)
    p.add_argument("--n", type=int, default=d.n, help="cluster size")
    p.add_argument("--sim-ms", type=int, default=d.sim_ms,
                   help="virtual-time window in ms")
    p.add_argument("--seed", type=int, default=d.seed)
    p.add_argument("--seeds", type=int, nargs="+", default=None,
                   help="seed sweep (batched on the JAX engine)")
    p.add_argument("--fidelity", choices=["reference", "clean"],
                   default=d.fidelity)
    p.add_argument("--delivery", choices=["edge", "stat"], default=d.delivery)
    p.add_argument("--schedule", choices=["tick", "round", "auto"],
                   default=d.schedule,
                   help="tick = general 1ms-tick engine; round = phase-"
                        "blocked fast path (PBFT: one step per block "
                        "interval; raft: per heartbeat behind a traced "
                        "checked election handoff; mixed: the heartbeat "
                        "scan inside every raft shard); auto = round when "
                        "eligible and n >= 4096 (mixed: whenever eligible)")
    p.add_argument("--stat-sampler", choices=["exact", "normal", "auto"],
                   default=d.stat_sampler,
                   help="binomial sampler for stat-delivery bucket counts: "
                        "exact = BTRS rejection; normal = Gaussian "
                        "approximation (fast at large n); auto = by n")
    p.add_argument("--engine", choices=["jax", "cpp"], default="jax",
                   help="jax = tensorized TPU backend; cpp = serial "
                        "per-message C++ reference engine")
    p.add_argument("--shards", type=int, default=0,
                   help="shard node state over this many devices (jax engine)")
    p.add_argument("--link-delay-ms", type=int, default=d.link_delay_ms)
    p.add_argument("--link-classes", type=int, nargs="+", default=(),
                   metavar="COUNT",
                   help="link classes (a rack, a region): the node count of "
                        "each class, ids contiguous, summing to --n; with "
                        "--link-class-delay-ms (pbft, edge delivery, full "
                        "mesh)")
    p.add_argument("--link-class-delay-ms", nargs="+", default=(),
                   metavar="ROW",
                   help="one-way propagation per class pair in ms, one "
                        "comma-separated ROW a sender class (K rows of K), "
                        "in --link-delay-ms's place: "
                        "--link-classes 6 5 3 --link-class-delay-ms "
                        "3,40,90 40,4,130 90,130,5")
    p.add_argument("--serialization", choices=["on", "off"],
                   default="on" if d.model_serialization else "off",
                   help="model per-message block serialization time "
                        "(bytes*8/link_rate; the reference's dominant "
                        "timing term) in addition to propagation delay")
    # topology axis (topo/): full mesh, gossip flood (BASELINE config 3),
    # kregular gather overlay, or two-level committee hierarchy
    p.add_argument("--topology",
                   choices=["full", "dense", "gossip", "kregular", "committee"],
                   default=d.topology,
                   help="full/dense = reference full mesh; gossip = TTL "
                        "flood over a random k-out digraph; kregular = "
                        "fixed-degree circulant overlay with gather-based "
                        "direct delivery (O(N*k) memory; bit-equal to the "
                        "mesh at --degree n-1); committee = the flat "
                        "protocol inside each of --committees committees "
                        "plus an outer representative aggregate")
    p.add_argument("--degree", type=int, default=d.degree,
                   help="out-degree k (gossip flood fan-out / kregular "
                        "overlay degree)")
    p.add_argument("--gossip-hops", type=int, default=d.gossip_hops,
                   help="flood TTL (gossip)")
    p.add_argument("--committees", type=int, default=d.committees,
                   help="committee count (topology=committee; must divide n)")
    p.add_argument("--topo-seed", type=int, default=d.topo_seed,
                   help="kregular overlay-builder seed (separate from the "
                        "run seed so sweeps share one overlay/executable)")
    p.add_argument("--paxos-timeout-ms", type=int, default=d.paxos_retry_timeout_ms,
                   help="clean-fidelity retry window timeout")
    p.add_argument("--paxos-client", nargs=2, type=int, default=None,
                   metavar=("NODE", "MS"),
                   help="CLIENT_PROPOSE hook (paxos-node.cc:357-361): proposer "
                        "lane NODE fires requireTicket at MS instead of t=0")
    # C++-engine-only transport/fidelity extras
    p.add_argument("--echo-back", action="store_true",
                   help="reflect every received packet to its sender once "
                        "(bounded quirk #1; --engine cpp only)")
    p.add_argument("--queued-links", action="store_true",
                   help="ns-3-exact serial-link transport: packets queue per "
                        "directed 3 Mbps link (--engine cpp only)")
    p.add_argument("--raft-terms", action="store_true",
                   help="Raft with terms (Figure 2 of the Raft paper on "
                        "upstream's message set): one vote a term, step-down "
                        "on a higher term; raft, clean fidelity, edge "
                        "delivery, topology full or committee")
    p.add_argument("--quorum-rule", choices=["n2", "2f1"], default=d.quorum_rule,
                   help="n2 = reference majority thresholds (no vote dedup); "
                        "2f1 = Byzantine-safe 2f+1 quorum with per-sender dedup")
    # faults
    p.add_argument("--crash", type=int, default=-1,
                   help="number of crashed nodes")
    p.add_argument("--byzantine", type=int, default=0,
                   help="number of vote-flipping nodes")
    p.add_argument("--byz-forge", action="store_true",
                   help="Byzantine nodes flood forged COMMIT votes for a "
                        "never-proposed slot (pbft)")
    p.add_argument("--byz-copies", type=int, default=3,
                   help="forged vote copies per sender under n2 counting")
    p.add_argument("--drop", type=float, default=0.0,
                   help="per-message drop probability")
    p.add_argument("--byz-sweep", action="store_true",
                   help="BASELINE config 4: sweep Byzantine f = 0..(n-1)//3 "
                        "with vote forging; one JSON line per (f, seed)")
    # per-protocol knobs (reference values as defaults)
    p.add_argument("--pbft-interval-ms", type=int, default=d.pbft_block_interval_ms)
    p.add_argument("--pbft-rounds", type=int, default=d.pbft_max_rounds)
    p.add_argument("--pbft-max-slots", type=int, default=d.pbft_max_slots,
                   help="vote-table slots; rounds are capped at "
                        "min(pbft_rounds, pbft_max_slots)")
    p.add_argument("--pbft-window", type=int, default=d.pbft_window,
                   help="live vote-state window W (0 = exact full table); "
                        "the O(N*W) memory lever at 100k nodes")
    p.add_argument("--pbft-tx-speed", type=int, default=d.pbft_tx_speed,
                   help="offered tx/s; with --pbft-tx-size sets the block "
                        "size (pbft-node.cc:104-105; 300 is the sustainable "
                        "rate on the 3 Mbps link the serialization-aware "
                        "round path needs, models/pbft_round.py)")
    p.add_argument("--pbft-tx-size", type=int, default=d.pbft_tx_size)
    p.add_argument("--raft-heartbeat-ms", type=int, default=d.raft_heartbeat_ms)
    p.add_argument("--raft-blocks", type=int, default=d.raft_max_blocks)
    p.add_argument("--raft-tx-speed", type=int, default=d.raft_tx_speed)
    p.add_argument("--raft-tx-size", type=int, default=d.raft_tx_size)
    p.add_argument("--paxos-proposers", type=int, default=d.paxos_n_proposers)
    p.add_argument("--mixed-shards", type=int, default=d.mixed_shards,
                   help="raft shard count for --protocol mixed")
    p.add_argument("--timing", action="store_true",
                   help="include wallclock timing in the output")
    # observability (utils/trace.py; the reference's NS_LOG surface as data)
    p.add_argument("--trace", metavar="FILE.npz",
                   help="record the probe series (committed blocks, views, "
                        "elections, ...) to an .npz next to the metrics "
                        "line — per tick on the general engine, per round/"
                        "heartbeat on the fast paths (utils/trace.py); "
                        "with --seeds, one FILE.<seed>.npz per seed")
    p.add_argument("--profile", metavar="LOGDIR",
                   help="capture a jax.profiler trace of the (pre-compiled) "
                        "run into LOGDIR (view with TensorBoard/perfetto)")
    return p


def config_from_args(args) -> SimConfig:
    return SimConfig(
        protocol=args.protocol,
        n=args.n,
        sim_ms=args.sim_ms,
        seed=args.seed,
        fidelity=args.fidelity,
        delivery=args.delivery,
        stat_sampler=args.stat_sampler,
        schedule=args.schedule,
        quorum_rule=args.quorum_rule,
        link_delay_ms=args.link_delay_ms,
        link_classes=args.link_classes,
        link_class_delay_ms=[[int(d) for d in row.split(",")]
                             for row in args.link_class_delay_ms],
        model_serialization=args.serialization == "on",
        topology=args.topology,
        degree=args.degree,
        gossip_hops=args.gossip_hops,
        committees=args.committees,
        topo_seed=args.topo_seed,
        paxos_retry_timeout_ms=args.paxos_timeout_ms,
        paxos_client_node=args.paxos_client[0] if args.paxos_client else -1,
        paxos_client_ms=args.paxos_client[1] if args.paxos_client else 0,
        echo_back=args.echo_back,
        queued_links=args.queued_links,
        raft_terms=args.raft_terms,
        pbft_block_interval_ms=args.pbft_interval_ms,
        pbft_max_rounds=args.pbft_rounds,
        pbft_max_slots=args.pbft_max_slots,
        pbft_window=args.pbft_window,
        pbft_tx_speed=args.pbft_tx_speed,
        pbft_tx_size=args.pbft_tx_size,
        raft_heartbeat_ms=args.raft_heartbeat_ms,
        raft_max_blocks=args.raft_blocks,
        raft_tx_speed=args.raft_tx_speed,
        raft_tx_size=args.raft_tx_size,
        paxos_n_proposers=args.paxos_proposers,
        mixed_shards=args.mixed_shards,
        faults=FaultConfig(
            n_crashed=args.crash,
            n_byzantine=args.byzantine,
            drop_prob=args.drop,
            byz_forge=args.byz_forge,
            byz_copies=args.byz_copies,
        ),
    )


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)

    schedule = None  # what the jax engine resolves cfg.schedule to

    def emit(record, cfg=None, **kw):
        """Every result line leaves through here: one JSON line with the
        obs manifest attached (and, when $BLOCKSIM_RUNS_JSONL is set, the
        same record appended there — utils/obs.py).  A jax-engine line
        names the program that ran: ``schedule`` is 'round' or 'tick'."""
        if schedule is not None:
            record["schedule"] = schedule
        print(json.dumps(obs.finalize(record, cfg, **kw)))

    try:
        cfg = config_from_args(args)
    except ValueError as e:
        # SimConfig validation (e.g. --paxos-client lane/ms range) — same
        # clean-UX contract as the flag checks below: message + exit code 2
        print(f"error: {e}", file=sys.stderr)
        return 2
    seeds = args.seeds if args.seeds is not None else [args.seed]

    if args.engine != "cpp" and args.echo_back:
        print("error: --echo-back requires --engine cpp (the tensorized "
              "backends design the echo away; see SimConfig docs)",
              file=sys.stderr)
        return 2
    if args.engine != "cpp" and (args.queued_links or args.raft_terms
                                 or args.link_classes):
        # pbft (serial-pipe registers) and paxos (ser = 0) run on the
        # tensorized backends; anything else gets the runner's message
        # (as does an arm of raft that has no terms, and any arm without
        # link classes)
        from blockchain_simulator_tpu.runner import _reject_cpp_only

        try:
            _reject_cpp_only(cfg)
        except (ValueError, NotImplementedError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2

    if args.engine == "cpp":
        if args.shards > 1:
            print("error: --shards requires the jax engine", file=sys.stderr)
            return 2
        if args.protocol == "mixed":
            print("error: --protocol mixed requires the jax engine "
                  "(the C++ engine implements pbft/raft/paxos only)",
                  file=sys.stderr)
            return 2
        if args.topology != "full":
            print(f"error: --topology {args.topology} requires the jax engine "
                  "(the C++ engine simulates the full mesh only)",
                  file=sys.stderr)
            return 2
        if args.byz_sweep:
            print("error: --byz-sweep requires the jax engine",
                  file=sys.stderr)
            return 2
        if args.trace or args.profile:
            print("error: --trace/--profile require the jax engine",
                  file=sys.stderr)
            return 2
        import time

        from blockchain_simulator_tpu.engine import run_cpp

        for s in seeds:
            t0 = time.perf_counter()
            try:
                m = run_cpp(cfg, seed=s)
            except (ValueError, NotImplementedError) as e:  # e.g. raft_terms
                print(f"error: {e}", file=sys.stderr)
                return 2
            if args.timing:
                m["wallclock_s"] = time.perf_counter() - t0
            emit(m, cfg)
        return 0

    from blockchain_simulator_tpu.runner import use_round_schedule

    try:
        schedule = "round" if use_round_schedule(cfg) else "tick"
    except ValueError as e:  # an ineligible explicit --schedule round
        print(f"error: {e}", file=sys.stderr)
        return 2

    if args.byz_sweep:
        from blockchain_simulator_tpu.parallel.sweep import run_byzantine_sweep

        for row in run_byzantine_sweep(cfg, seeds=seeds):
            # the row ran cfg with its OWN FaultConfig (sweep.py builds
            # n_byzantine=f, byz_forge=True per point): hash that config so
            # the manifest's join key matches what was simulated; the sweep
            # already appended the row to runs.jsonl (obs.record_run), so
            # the printed line must not append again
            row_cfg = cfg.with_(faults=dataclasses.replace(
                cfg.faults, n_byzantine=row["f"], byz_forge=True))
            emit(row, row_cfg, append=False)
        return 0

    if args.trace or args.profile:
        if args.shards > 1:
            print("error: --trace/--profile apply to unsharded jax runs",
                  file=sys.stderr)
            return 2
        if args.profile and len(seeds) > 1:
            print("error: --profile applies to single-seed jax runs "
                  "(--trace accepts --seeds: one FILE.<seed>.npz per seed)",
                  file=sys.stderr)
            return 2
        from blockchain_simulator_tpu.runner import _reject_cpp_only
        from blockchain_simulator_tpu.utils import trace as trace_mod

        try:
            # validate BEFORE any compile: cpp-only fidelity flags and
            # ineligible explicit schedule='round' fail here with the same
            # message + exit code 2 as every other path (run_traced
            # re-validates, but a multi-seed loop must not discover the
            # error on seed 0 after minutes of compile)
            _reject_cpp_only(cfg)
            if args.trace:
                import os as _os

                import numpy as _np

                for s in seeds:
                    m, series = trace_mod.run_traced(cfg, seed=s)
                    if len(seeds) == 1:
                        path = args.trace
                    else:
                        root, ext = _os.path.splitext(args.trace)
                        path = f"{root}.{s}{ext or '.npz'}"
                    _np.savez(path, **series)
                    m["trace_file"] = path
                    m["trace_series"] = sorted(series)
                    m["seed"] = s
                    emit(m, cfg)
            else:
                m = trace_mod.profile_run(cfg, args.profile, seed=seeds[0])
                m["profile_dir"] = args.profile
                emit(m, cfg)
        except (ValueError, NotImplementedError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 2
        return 0

    if args.timing and (args.shards > 1 or len(seeds) > 1):
        print("note: --timing applies to single-seed unsharded jax runs; "
              "ignoring", file=sys.stderr)

    if args.shards > 1:
        from blockchain_simulator_tpu.parallel.mesh import make_mesh
        from blockchain_simulator_tpu.parallel.shard import run_sharded
        from blockchain_simulator_tpu.parallel.sweep import run_seed_sweep

        mesh = make_mesh(n_node_shards=args.shards)
        if len(seeds) > 1:
            # append=False: run_seed_sweep already logged each row with
            # obs.record_run — one runs.jsonl record per run, not two
            for m in run_seed_sweep(cfg, seeds=seeds, mesh=mesh):
                emit(m, cfg, append=False)
        else:
            emit(run_sharded(cfg, mesh, seed=seeds[0]), cfg)
        return 0

    if len(seeds) > 1:
        from blockchain_simulator_tpu.parallel.sweep import run_seed_sweep

        for m in run_seed_sweep(cfg, seeds=seeds):
            emit(m, cfg, append=False)
        return 0

    from blockchain_simulator_tpu.runner import run_simulation

    m = run_simulation(cfg, seed=seeds[0], with_timing=args.timing)
    emit(
        m, cfg,
        compile_s=m.get("compile_plus_first_run_s"),
        run_s=m.get("wallclock_s"),
        rounds=m.get("blocks_final_all_nodes", m.get("blocks")),
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
