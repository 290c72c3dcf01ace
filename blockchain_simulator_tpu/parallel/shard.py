"""Node-shard SPMD execution: thin spec declarations over partition rules.

The entire simulation — state init, the full ``lax.scan`` over ticks, every
delivery collective — runs as one SPMD program over the mesh's ``nodes``
axis: node state ``[N, ...]`` and ring buffers ``[D, N, ...]`` are
row-sharded, and the delivery ops in ``ops/delivery.py`` globalize
sender-side quantities with ``all_gather``/``psum``/``pmax`` over ICI
(SURVEY.md §2: the TPU-native equivalent of the reference's simulated
point-to-point channels).

Since the partition layer landed, each wrapper here is just its *rule
declaration* (regex path patterns → PartitionSpecs, ``parallel/
partition.py``) plus the engine call: specs come from
``partition.match_partition_rules`` and the mesh meets the executable
through ``partition.partition`` — there is no direct ``shard_map`` call
site in this module (tests/test_zzpartition.py pins that).

All four factories here are traced over a 2-device mesh and budget-pinned
by the graph audit (lint/graph/programs.py ``shard.*`` specs).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from blockchain_simulator_tpu.models.base import get_protocol, sim_metrics
from blockchain_simulator_tpu.parallel import partition
from blockchain_simulator_tpu.parallel.mesh import NODES_AXIS
from blockchain_simulator_tpu.utils import aotcache
from blockchain_simulator_tpu.utils import prng
from blockchain_simulator_tpu.utils import telemetry
from blockchain_simulator_tpu.utils.config import SimConfig

# ----------------------------------------------------- rule declarations ---

# Node state [N, ...]: row-shard dim 0 — except the protocol's
# ``GLOBAL_FIELDS`` (per-slot accumulators): replicated, each shard carries
# a partial that the protocol's ``finalize`` combines.  The rule set itself
# lives in the partition layer (partition.node_dim_rules) — the sharded
# topo programs (parallel/sweep.sharded_topo_sim_fn) declare theirs from
# the same helper.
def state_rules(global_fields=()):
    return partition.node_dim_rules(global_fields)


# Ring/delivery buffers [D, N, ...]: the node axis is dim 1.  PBFT's ``due``
# bits ([rings, D], models/pbft.PbftBufs) have no node axis: every shard
# holds the same copy, which a sharded program never touches.
BUF_RULES = (
    (r"(^|/)due$", partition.REPLICATED),
    (r".*", P(None, NODES_AXIS)),
)

# Mixed shard-sim (models/mixed.py): raft leaves [S, ...] row-shard over
# the shard axis; the S-representative PBFT layer is replicated (every
# device steps an identical copy — see mixed.step).
MIXED_RULES = (
    (r"^raft(/|$)", P(NODES_AXIS)),
    (r"^pbft(/|$)", partition.REPLICATED),
)


def state_specs(state, global_fields=()):
    """PartitionSpecs for a state pytree (rule-matched; see state_rules)."""
    return partition.match_partition_rules(state_rules(global_fields), state)


def node_specs(state, bufs, global_fields=()):
    """(state specs, buffer specs) for a (state, bufs) pair."""
    return (
        state_specs(state, global_fields),
        partition.match_partition_rules(BUF_RULES, bufs),
    )


def mixed_specs(state, bufs):
    """PartitionSpecs for the mixed shard-sim's (state, bufs) pair."""
    return (
        partition.match_partition_rules(MIXED_RULES, state),
        partition.match_partition_rules(MIXED_RULES, bufs),
    )


def _partitioned(run, mesh, in_specs, out_specs):
    """The wrappers' one door to the mesh: per-shard specs → shard_map
    (partition.py's fallback path), unjitted — each wrapper embeds the
    result in its own ``@jax.jit`` sim exactly as before the layer
    existed, so the traced IR (and its pinned budget) is unchanged."""
    return partition.partition(
        run, mesh, in_specs=in_specs, out_specs=out_specs, wrap_jit=False
    )


# ------------------------------------------------------------- factories ---


@aotcache.cached_factory("shard-round")
def _make_sharded_round_fn(cfg: SimConfig, mesh: Mesh):
    """Node-sharded round-blocked PBFT fast path (models/pbft_round.py):
    one scan step per 50 ms block interval, node state row-sharded, the
    per-round reductions (slot max, commit-sender totals, trigger/lands)
    riding ``psum``/``pmax`` over ICI.  step_round is written against
    ``cfg.mesh_axis`` exactly like the tick engine's step."""
    from blockchain_simulator_tpu.models import pbft_round

    n_shards = mesh.shape[NODES_AXIS]
    if cfg.n % n_shards != 0:
        raise ValueError(f"n={cfg.n} not divisible by {n_shards} node shards")
    cfg_local = cfg.with_(mesh_axis=NODES_AXIS)

    state0, _ = jax.eval_shape(lambda: pbft_round.init(cfg, jax.random.key(0)))
    state_spec = state_specs(state0, pbft_round.GLOBAL_FIELDS)

    def run(key, state):
        state = pbft_round.scan_rounds(cfg_local, state, key)
        return pbft_round.finalize(state, NODES_AXIS)

    shmapped = _partitioned(
        run, mesh, in_specs=(P(), state_spec), out_specs=state_spec
    )

    @jax.jit
    def sim(key):
        state, _ = pbft_round.init(cfg, jax.random.fold_in(key, 0x1217))
        return shmapped(key, state)

    return sim


@aotcache.cached_factory("shard-raft-hb")
def _make_sharded_raft_hb_fn(cfg: SimConfig, mesh: Mesh):
    """Node-sharded heartbeat-blocked raft fast path (models/raft_hb.py):
    the tick-engine election prefix runs sharded exactly like the general
    engine; the checked handoff is a traced ``lax.cond`` whose predicate and
    leader scalars are psum/pmax-agreed across the mesh, so every device
    takes the same branch — either the replicated O(1) heartbeat scan (each
    shard materializes only its local rows) or a continuation of the sharded
    tick scan from the prefix carry."""
    from blockchain_simulator_tpu.models import raft as raft_tick
    from blockchain_simulator_tpu.models import raft_hb

    n_shards = mesh.shape[NODES_AXIS]
    if cfg.n % n_shards != 0:
        raise ValueError(f"n={cfg.n} not divisible by {n_shards} node shards")
    cfg_local = cfg.with_(mesh_axis=NODES_AXIS)

    state0, bufs0 = jax.eval_shape(lambda: raft_tick.init(cfg, jax.random.key(0)))
    state_spec, bufs_spec = node_specs(state0, bufs0)

    def run(key, state, bufs):
        return raft_hb.scan_from_init(cfg_local, state, bufs, key)

    shmapped = _partitioned(
        run, mesh, in_specs=(P(), state_spec, bufs_spec), out_specs=state_spec
    )

    @jax.jit
    def sim(key):
        state, bufs = raft_tick.init(cfg, jax.random.fold_in(key, 0x1217))
        return shmapped(key, state, bufs)

    return sim


@aotcache.cached_factory("shard-mixed")
def _make_sharded_mixed_fast_fn(cfg: SimConfig, mesh: Mesh):
    """Shard-sharded heartbeat-scheduled mixed sim (models/mixed.scan_fast):
    raft shard rows over the mesh axis, the S-representative PBFT layer
    replicated, the per-shard handoff verdict psum-agreed."""
    from blockchain_simulator_tpu.models import mixed

    n_shards = mesh.shape[NODES_AXIS]
    if cfg.mixed_shards % n_shards != 0:
        raise ValueError(
            f"mixed_shards={cfg.mixed_shards} not divisible by "
            f"{n_shards} mesh shards"
        )
    cfg_local = cfg.with_(mesh_axis=NODES_AXIS)

    state0, bufs0 = jax.eval_shape(lambda: mixed.init(cfg, jax.random.key(0)))
    state_spec, bufs_spec = mixed_specs(state0, bufs0)

    def run(key, state, bufs):
        return mixed.scan_fast(cfg_local, state, bufs, key)

    shmapped = _partitioned(
        run, mesh, in_specs=(P(), state_spec, bufs_spec), out_specs=state_spec
    )

    @jax.jit
    def sim(key):
        state, bufs = mixed.init(cfg, jax.random.fold_in(key, 0x1217))
        return shmapped(key, state, bufs)

    return sim


@aotcache.cached_factory("shard-sim")
def make_sharded_sim_fn(cfg: SimConfig, mesh: Mesh):
    """Jitted ``sim(key) -> final_state`` with node state sharded over the
    mesh's ``nodes`` axis.  ``cfg.n`` must divide by the axis size.

    Schedule resolution matches runner.make_sim_fn: the PBFT round-blocked
    fast path when eligible ('round' explicit, or 'auto' at n >= 4096), the
    raft heartbeat fast path (traced checked handoff — the prefix runs on
    the sharded tick engine, the steady scan is replicated O(1) work), the
    heartbeat-scheduled mixed sim, else the general per-tick engine."""
    from blockchain_simulator_tpu.runner import _reject_cpp_only, use_round_schedule

    _reject_cpp_only(cfg)
    if cfg.link_classes:
        # refused by name here: ``init`` below runs on the unsharded config
        from blockchain_simulator_tpu.ops import linkclass

        linkclass.check_arms(cfg.with_(mesh_axis=NODES_AXIS))
    if use_round_schedule(cfg):
        if cfg.protocol == "raft":
            return _make_sharded_raft_hb_fn(cfg, mesh)
        if cfg.protocol == "mixed":
            return _make_sharded_mixed_fast_fn(cfg, mesh)
        return _make_sharded_round_fn(cfg, mesh)
    n_shards = mesh.shape[NODES_AXIS]
    proto = get_protocol(cfg.protocol)
    cfg_local = cfg.with_(mesh_axis=NODES_AXIS)

    state0, bufs0 = jax.eval_shape(lambda: proto.init(cfg, jax.random.key(0)))
    if cfg.protocol == "mixed":
        # the sharded unit is the raft SHARD row, not the node
        if cfg.mixed_shards % n_shards != 0:
            raise ValueError(
                f"mixed_shards={cfg.mixed_shards} not divisible by "
                f"{n_shards} mesh shards"
            )
        state_spec, bufs_spec = mixed_specs(state0, bufs0)
    else:
        if cfg.n % n_shards != 0:
            raise ValueError(f"n={cfg.n} not divisible by {n_shards} node shards")
        state_spec, bufs_spec = node_specs(
            state0, bufs0, getattr(proto, "GLOBAL_FIELDS", ())
        )

    def run(key, state, bufs):
        def body(carry, t):
            st, bf = carry
            st, bf = proto.step(cfg_local, st, bf, t, prng.tick_key(key, t))
            return (st, bf), ()

        (state, bufs), _ = jax.lax.scan(body, (state, bufs), jnp.arange(cfg.ticks))
        if hasattr(proto, "finalize"):
            state = proto.finalize(state, NODES_AXIS)
        return state

    shmapped = _partitioned(
        run, mesh, in_specs=(P(), state_spec, bufs_spec), out_specs=state_spec
    )

    @jax.jit
    def sim(key):
        state, bufs = proto.init(cfg, jax.random.fold_in(key, 0x1217))
        return shmapped(key, state, bufs)

    return sim


def _span_attrs(cfg: SimConfig, mesh: Mesh) -> dict:
    n_shards = mesh.shape[NODES_AXIS]
    rows = cfg.mixed_shards if cfg.protocol == "mixed" else cfg.n
    return {"shards": n_shards, "rows_per_shard": rows // n_shards}


def readback(cfg: SimConfig, mesh: Mesh, final):
    """The ``shard.readback`` span of one sharded run: ONE ``jax.device_get``
    brings the leaves the protocol's ``metrics`` reads (its module's
    ``METRIC_FIELDS``; every field where a module declares none) from the
    mesh to the host, each shard's copy started before the first is awaited,
    as ``parallel/sweep._readback`` does for a batch.  Returns ``final``'s
    own state type with host arrays in the fetched fields and None in the
    others, which is all ``sim_metrics`` asks for.  The span's ``leaves``
    and ``bytes`` attrs say what was fetched; it waits for the run if the
    caller has not (``run_sharded`` has, under ``shard.execute``)."""
    import dataclasses

    names = [f.name for f in dataclasses.fields(final)]
    fields = getattr(get_protocol(cfg.protocol), "METRIC_FIELDS", names)
    picked = {f: getattr(final, f) for f in fields}
    leaves = jax.tree.leaves(picked)
    with telemetry.span(
        "shard.readback", **_span_attrs(cfg, mesh), leaves=len(leaves),
        bytes=sum(x.nbytes for x in leaves),
    ):
        host = jax.device_get(picked)
    return type(final)(**{f: host.get(f) for f in names})


def collective_counts(cfg: SimConfig, mesh: Mesh) -> dict:
    """What the compiled sharded program says of its own communication,
    read ONCE from the optimized post-SPMD module (``lint/comms/hlo.py``'s
    parser; with jax's compile cache on, the compile is a load of the
    executable the run itself uses).  A counter of the program, not a
    measurement:

    - ``collectives_per_tick`` / ``bytes_per_tick``: the collective
      instructions inside the tick loop and the bytes of their outputs on
      one device, as on a tick that takes every gate (a collective inside an
      untaken ``conditional`` arm does not run);
    - ``by_scope``: the same, keyed by the innermost ``ops.mesh.*`` scope on
      the instruction's ``op_name`` (``"(none)"`` where it has none);
    - ``flood_allreduces`` / ``flood_allreduce_bytes``: the all-reduces under
      ``ops.delivery.gossip_fwd`` and the operand bytes of ONE of them: what
      a flood arm all-reduces on a tick with more senders than its exchange
      holds (the dense arm);
    - ``flood_allgathers`` / ``flood_allgather_bytes``: the all-gathers under
      it and the gathered bytes of ONE of them: the senders' packets every
      taken flood arm exchanges."""
    import re

    from blockchain_simulator_tpu.lint.comms import hlo
    from blockchain_simulator_tpu.ops import mesh as mesh_ops

    sim = make_sharded_sim_fn(cfg, mesh)
    module = hlo.parse_module(sim.lower(jax.random.key(0)).compile().as_text())
    attrs = {ins.name: ins.attrs for instrs in module.computations.values()
             for ins in instrs}
    by_scope: dict = {}
    flood = {"all-reduce": [], "all-gather": []}
    in_loop = [c for c in hlo.collectives(module) if c.in_loop]
    for c in in_loop:
        op_name = "".join(re.findall(r'op_name="([^"]*)"', attrs[c.name]))
        scope = next((s for s in mesh_ops.SCOPES if f"{s}/" in op_name + "/"),
                     "(none)")
        got = by_scope.setdefault(scope, {"count": 0, "bytes": 0})
        got["count"] += 1
        got["bytes"] += c.bytes
        if c.opcode in flood and "ops.delivery.gossip_fwd" in op_name:
            flood[c.opcode].append(c.bytes)
    return {
        "collectives_per_tick": len(in_loop),
        "bytes_per_tick": sum(c.bytes for c in in_loop),
        "by_scope": by_scope,
        "flood_allreduces": len(flood["all-reduce"]),
        "flood_allreduce_bytes": max(flood["all-reduce"], default=0),
        "flood_allgathers": len(flood["all-gather"]),
        "flood_allgather_bytes": max(flood["all-gather"], default=0),
    }


def run_sharded(cfg: SimConfig, mesh: Mesh, seed: int | None = None):
    """Run one node-sharded simulation, return the protocol metrics dict.
    Two host states by name on the profiler's clock (utils/telemetry.py):
    ``shard.execute`` (dispatch and the wait for the mesh) and
    ``shard.readback`` (:func:`readback`)."""
    sim = make_sharded_sim_fn(cfg, mesh)
    key = jax.random.key(cfg.seed if seed is None else seed)
    with telemetry.span("shard.execute", **_span_attrs(cfg, mesh)):
        final = jax.block_until_ready(sim(key))
    return sim_metrics(cfg, readback(cfg, mesh, final))
