"""Multi-host (DCN) execution: the same SPMD program over processes.

The reference's "distributed backend" is simulated UDP in one thread
(SURVEY.md §5); the TPU-native equivalent scales out in two tiers:

- intra-host: ICI collectives inside ``shard_map`` (parallel/shard.py);
- multi-host: ``jax.distributed.initialize`` + a global mesh built from all
  processes' devices — the SAME PartitionSpecs then span DCN, with XLA
  routing ``all_gather``/``psum`` across hosts.  Nothing in the simulation
  code changes; this module only adds process bootstrap, the global-mesh
  runner, and result gathering.

Testable without a TPU pod: two localhost CPU processes, each with
``--xla_force_host_platform_device_count=K`` virtual devices, form a
2-process DCN group (tests/test_multihost.py); the milestone metrics are
bit-identical to a single-process run over the same mesh shape, because
every random draw is keyed by (seed, tick, channel, shard index) — the
process boundary is invisible to the program.

CLI: ``python -m blockchain_simulator_tpu.parallel.multihost --coordinator
HOST:PORT --num-processes N --process-id I [sim flags]`` — or pass
``--multihost`` flags to the main CLI.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def init_multihost(coordinator: str, num_processes: int, process_id: int) -> None:
    """Join (or start, for process 0) the distributed coordination service."""
    import jax

    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=num_processes,
        process_id=process_id,
    )


def run_sharded_multihost(cfg, n_node_shards: int | None = None, seed=None) -> dict:
    """Run one node-sharded simulation over ALL processes' devices.

    Must be called in every process of the group (it is one SPMD program);
    every process returns the full metrics dict (final state is allgathered
    host-side, so no process holds only its shard).
    """
    import jax
    from jax.experimental import multihost_utils

    from blockchain_simulator_tpu.models.base import get_protocol
    from blockchain_simulator_tpu.parallel.mesh import make_mesh
    from blockchain_simulator_tpu.parallel.shard import make_sharded_sim_fn

    proto = get_protocol(cfg.protocol)
    mesh = make_mesh(n_node_shards=n_node_shards)  # all global devices
    sim = make_sharded_sim_fn(cfg, mesh)
    final = sim(jax.random.key(cfg.seed if seed is None else seed))
    # shards live on different hosts; gather to replicated numpy everywhere
    # (tiled=True: reassemble the GLOBAL shape, no extra process axis — the
    # only mode supported for non-fully-addressable global arrays)
    final = multihost_utils.process_allgather(final, tiled=True)
    return proto.metrics(cfg, final)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="blockchain_simulator_tpu.parallel.multihost")
    p.add_argument("--coordinator", required=True, help="HOST:PORT of process 0")
    p.add_argument("--num-processes", type=int, required=True)
    p.add_argument("--process-id", type=int, required=True)
    p.add_argument("--force-cpu-devices", type=int, default=0,
                   help="force the CPU backend with this many virtual devices "
                        "per process (testing without accelerators)")
    p.add_argument("--protocol", default="pbft")
    p.add_argument("--n", type=int, default=64)
    p.add_argument("--sim-ms", type=int, default=2500)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--delivery", default="edge")
    p.add_argument("--serialization", choices=["on", "off"], default="on")
    p.add_argument("--schedule", choices=["tick", "round", "auto"],
                   default="auto", help="stepping granularity; 'round' pins "
                   "the PBFT round-blocked fast path (models/pbft_round.py)")
    args = p.parse_args(argv)

    if args.force_cpu_devices:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count={args.force_cpu_devices}"
            ).strip()
    import jax

    if args.force_cpu_devices:
        # `python -m` imported the package (and with it jax) before the
        # env var above was set, so pin the platform at the config level
        jax.config.update("jax_platforms", "cpu")

    from blockchain_simulator_tpu.utils.config import SimConfig

    init_multihost(args.coordinator, args.num_processes, args.process_id)
    cfg = SimConfig(
        protocol=args.protocol,
        n=args.n,
        sim_ms=args.sim_ms,
        seed=args.seed,
        delivery=args.delivery,
        model_serialization=args.serialization == "on",
        schedule=args.schedule,
    )
    m = run_sharded_multihost(cfg)
    if jax.process_index() == 0:
        print(json.dumps({"process_count": jax.process_count(),
                          "device_count": jax.device_count(), **m}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
