"""Config/fault-sweep parallelism: batch whole simulations.

The outer-axis analog of BASELINE config 4 ("Byzantine-fault sweep f=0..n/3,
pmap over fault configs"): many seeds of one config run as a single vmapped
program; over a mesh, the batch axis shards over ``sweep`` (``spmd_axis_name``)
while the node axis shards over ``nodes``.

Fault *counts* (crash counts, Byzantine counts) are traced per-run OPERANDS
(runner.make_dyn_sim_fn): an f-sweep over any number of fault levels is ONE
vmapped executable over the (fault level, seed) cross product — where it used
to compile one program per f value (~20 s of XLA per point on this box for
seconds of simulation).  Fault *structure* (drop_prob, byz_forge, byz_copies)
stays static: :func:`run_fault_sweep` groups its fault configs by canonical
structure (models/base.canonical_fault_cfg) and compiles once per group.
Results are bit-equal to the per-point static path (pinned in
tests/test_zsweep_cache.py); the mixed shard sim keeps the static path.

Bit-equality caveat: under ``stat_sampler="exact"`` (and the whole edge path)
equality is exact — integer draws whose arithmetic is identical in both
programs.  The ``"normal"`` CLT sampler (auto at n >= 4096) has a float path
that XLA may arrange differently in the two compiled programs: with the SAME
keys, one message can land one delay bucket over, moving a commit tail by ±1
tick (measured once across a 22-point 10k sweep, ``tools/sweep_cache_bench.py``
notes) — the same jitter class models/pbft_round.py documents vs the tick
engine; counts and milestones are unaffected.

Durability: every dynamic-operand sweep accepts ``journal=`` (a
parallel/journal.SweepJournal) — execution then chunks one fault level
(seed tile) per fsynced journal append, a restarted identical sweep
skips completed chunks (recompute <= the one in-flight chunk, rows
bit-equal under the exact sampler), and ``supervise=`` adds per-chunk
deadlines with bounded retry and a recorded degrade arm.  See
parallel/journal.py for the journal-vs-WAL semantics.

Compiled programs live in the unified executable registry
(utils/aotcache.py) — hit/miss stats land on every run manifest.  The
same-structure grouping below is pinned at the IR level by the graph
audit's divergence twins (lint/graph/programs.py ``sweep_dynf.*``): fault
configs differing only in counts must trace to ONE jaxpr fingerprint, or
``lint.graph`` fails ``registry-key-divergence`` in CI.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from blockchain_simulator_tpu.chaos import inject
from blockchain_simulator_tpu.models.base import (
    canonical_fault_cfg,
    get_protocol,
    lane_vmap,
    select_vmap,
    sim_metrics,
)
from blockchain_simulator_tpu.parallel import journal as journal_mod
from blockchain_simulator_tpu.parallel import partition
from blockchain_simulator_tpu.parallel.mesh import NODES_AXIS, SWEEP_AXIS
from blockchain_simulator_tpu.runner import (
    UnbatchableConfigError,
    check_batchable,
    make_dyn_sim_fn,
    make_sim_fn,
    make_topo_dyn_sim_fn,
    topo_tables_inslot,
    use_round_schedule,
)
from blockchain_simulator_tpu.utils import aotcache, obs, telemetry
from blockchain_simulator_tpu.utils.config import SimConfig


@aotcache.cached_factory("sweep-batched")
def _batched_fn(cfg: SimConfig, mesh=None):
    """Jitted ``batched(keys) -> finals`` for one (cfg, mesh): registry-
    cached so repeated sweeps of one config reuse the compiled program
    instead of building a fresh jit wrapper per call (jaxlint
    static-arg-recompile-hazard; runner.make_sim_fn convention).  On one
    device the batch is a lane batch (models/base.lane_vmap: ``gated``
    stays a conditional); over a mesh it is not, every cond is a select
    (models/base.select_vmap: ``gated_push`` keeps the ring out of it)."""
    if mesh is None:
        return jax.jit(lane_vmap(make_sim_fn(cfg)))
    from blockchain_simulator_tpu.parallel.shard import make_sharded_sim_fn

    return jax.jit(
        select_vmap(make_sharded_sim_fn(cfg, mesh), spmd_axis_name=SWEEP_AXIS)
    )


@aotcache.cached_factory("sweep-batched-dynf")
def dyn_batched_fn(cfg: SimConfig):
    """Jitted ``batched(keys, n_crashed[B], n_byzantine[B]) -> finals`` —
    THE one executable of a whole fault-count sweep (``cfg`` must already be
    canonical; one registry entry per fault structure).  Public: the
    scenario server's micro-batched dispatch (serve/dispatch.py) rides the
    same registry entry as the sweeps, so a sweep warms the server and
    vice versa.  A lane batch (models/base.lane_vmap): lanes differ in
    fault level, so ``gated`` takes an arm when any lane is active and
    selects per lane inside it."""
    return jax.jit(lane_vmap(make_dyn_sim_fn(cfg)))


# back-compat alias (pre-serve name; lint/graph/programs.py and external
# callers were updated, but keep the old spelling importable)
_dyn_batched_fn = dyn_batched_fn


@aotcache.cached_factory("partition-dyn-sweep")
def mesh_dyn_batched_fn(cfg: SimConfig, mesh):
    """Mesh-partitioned ``batched(keys[B], n_crashed[B], n_byzantine[B]) ->
    finals``: the (fault level, seed) batch axis sharded over the mesh's
    ``sweep`` axis, through the partition layer (parallel/partition.py).

    Three arms, all one registry entry per (fault structure, mesh) — the
    mesh rides the key, so the one-executable-per-fault-structure contract
    holds per mesh:

    - **mesh of size 1**: degenerates to :func:`dyn_batched_fn` — the
      PR 4 single-device program itself, so results are trivially
      bit-identical to the plain vmapped sweep (the registry serves the
      ``sweep-batched-dynf`` entry; sweeps and serving stay warm).
    - **sweep-only mesh** (nodes axis 1): shard_map over the batch axis
      with a per-device body of ``lax.map`` over the UNVMAPPED dyn sim.
      The unvmapped body keeps its dynamic-update-slice pushes as plain
      DUS instead of vmap's scatter lowering (KNOWN_ISSUES #0b: XLA:CPU
      serializes scatter) — measured ~2.3x per lane over the vmapped
      program at 10k nodes on the CPU mesh, before any device parallelism.
    - **nodes axis > 1**: the explicit-sharding pjit arm — batch over
      ``sweep``, each lane's node dim over ``nodes``
      (partition.batched_out_shardings), XLA GSPMD partitioning the scan:
      the "node axis optionally sharded for large n" option.  A
      ``select_vmap``: its conds are selects (KNOWN_ISSUES #0b).

    Callers must pad the batch to a multiple of the sweep axis size
    (partition.pad_points; run_dyn_points does).  Bit-equality to the
    single-device path is pinned under the exact sampler in
    tests/test_zzpartition.py — the normal CLT sampler keeps the module
    caveat's ±1-tick float latitude."""
    fn = make_dyn_sim_fn(cfg)
    if partition.mesh_size(mesh) == 1:
        return dyn_batched_fn(cfg)
    if int(dict(mesh.shape).get(NODES_AXIS, 1)) > 1:
        batched = select_vmap(fn)
        b = max(partition.sweep_axis_size(mesh), 1)
        keys_sds = jax.eval_shape(
            lambda: jax.vmap(jax.random.key)(jnp.arange(b, dtype=jnp.uint32))
        )
        cnt_sds = jax.ShapeDtypeStruct((b,), jnp.int32)
        outs = jax.eval_shape(batched, keys_sds, cnt_sds, cnt_sds)
        from jax.sharding import PartitionSpec as P

        lane = P(SWEEP_AXIS) if partition.sweep_axis_size(mesh) > 1 else P()
        return partition.partition(
            batched, mesh,
            in_shardings=(lane, lane, lane),
            out_shardings=partition.batched_out_shardings(cfg, mesh, outs),
        )

    # per-device: local lanes run SEQUENTIALLY through the unvmapped
    # program (lax.map = scan of the solo body, constant program size)
    body = partition.seq_map(fn)

    from jax.sharding import PartitionSpec as P

    lane = P(SWEEP_AXIS)
    return partition.partition(
        body, mesh, in_specs=(lane, lane, lane), out_specs=lane
    )


@aotcache.cached_factory("shard-topo-sim")
def sharded_topo_sim_fn(cfg: SimConfig, mesh, layout: str = "exchange"):
    """Node-dim mesh-sharded topology program: ``sim(key, n_crashed,
    n_byzantine) -> final_state`` for a kregular or committee config with
    the overlay partitioned over the mesh's ``nodes`` axis — the 10M-node
    arm of ROADMAP item 3 (the [N, K] tables and per-edge tensors stop
    living on one device).  ``cfg`` must already be fault-canonical
    (models/base.canonical_fault_cfg — the :func:`run_sharded_topo` /
    bench callers canonicalize): ONE registry entry per (protocol,
    topology, fault structure, mesh), fault counts ride the operands.

    Three arms:

    - **mesh of size 1**: ``jax.jit(make_dyn_sim_fn(cfg))`` — literally
      the single-device program (tables as trace constants, the PR 15
      path), so the degenerate case is bit-identical by construction.
    - **kregular, nodes > 1**: the explicit-sharding pjit arm.  The body
      is ``runner.make_topo_dyn_sim_fn`` — the tick engine with the
      ``[N, K]`` overlay tables as real OPERANDS (ops/gatherdeliv.
      table_operands; KNOWN_ISSUES #0n's escape hatch) — compiled through
      ``partition.partition`` with the tables and every node-dim final
      sharded ``P(NODES_AXIS)`` (partition.node_dim_rules; the protocol's
      ``GLOBAL_FIELDS`` replicate).  The model traces in global view
      (``cfg.mesh_axis`` stays None), so the traced computation — RNG
      draw shapes included — is identical to the single-device program,
      hence bit-equal results under the exact sampler
      (tests/test_zzshardtopo).  Two data-movement layouts:

      * ``layout="exchange"`` (the default): cross-shard neighbor reads
        route through a ``partition.NeighborExchange`` — owner-bucketed
        ``all_to_all`` islands (plans from topo/spec.owner_bucket_plan
        ride as extra ``P(NODES_AXIS)`` operands) — and the table rows
        pass through ``local_tables(ids=None)`` untaken, so no tensor is
        ever materialized at global shape: prologue and per-tick comms
        are O(N*K/D) per device instead of the full-table all-gather.
        The exchange is a pure permutation + local gather, bit-equal to
        the global gather by construction.
      * ``layout="regather"``: the pre-exchange behavior — neighbor
        reads stay plain ``jnp.take`` gathers for XLA GSPMD to
        partition, which re-gathers the ``P(nodes)`` tables/state on
        every device (the retired ``table-regather`` debt).  Kept so
        tools/gather_locality_bench.py can measure old-vs-new inside one
        artifact, and as the fallback if an exchange regression ever
        needs bisecting.

      The sharded tables (and exchange plans) are device_put once per
      factory call and closed over; ``sim.partitioned`` /
      ``sim.table_avals`` expose the inner pjit callable and its sharded
      operand avals so the graph/comms audits trace the
      operands-as-arguments jaxpr (zero large-jaxpr-constant findings).
      Uneven ``n % shards`` is fine: explicit NamedShardings must divide
      evenly in this jax, so the factory zero-pads the table rows to the
      next multiple (the wrapper slices them back before the engine sees
      them — padding rows are never read; exchange plans are built on the
      padded tables and stay padded) and any final whose node dim stays
      uneven replicates instead of sharding.
    - **committee, nodes > 1**: shard_map over the STACKED committee axis
      (``committees % shards == 0`` required): each device runs
      ``topo/committee.stacked_body`` — the same tiles of lanes as the
      single-device stack, cut to that device by the same rule
      (:func:`_device_tile`) — on its slice of the ``[C]`` key stack and
      ``[C, m]`` fault masks.  Committee bodies never communicate before
      the host-side outer aggregate, and the per-committee keys are
      computed from the GLOBAL committee index before the shard_map, so
      every lane's stream matches the single-device program bit for bit.
      ``cfg.mesh_axis`` stays None (utils/config.py pins committee configs
      unsharded at the NODE level — this arm shards the committee STACK,
      which is the hierarchy's node-dim analog)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from blockchain_simulator_tpu.models import base as base_model
    from blockchain_simulator_tpu.ops import gatherdeliv as gd

    if cfg.topology not in ("kregular", "committee"):
        raise ValueError(
            f"sharded_topo_sim_fn shards the sparse/hierarchical overlays; "
            f"topology={cfg.topology!r} has no node-dim topo structure "
            "(dense configs ride parallel/shard.py, gossip is unsharded)"
        )
    if partition.mesh_size(mesh) == 1:
        return jax.jit(make_dyn_sim_fn(cfg))
    n_shards = int(dict(mesh.shape).get(NODES_AXIS, 1))
    if n_shards <= 1:
        raise ValueError(
            "sharded_topo_sim_fn partitions the node dimension: the mesh "
            f"needs nodes > 1 (got shape {dict(mesh.shape)}); sweep-axis "
            "meshes belong to mesh_dyn_batched_fn"
        )

    if cfg.topology == "committee":
        from blockchain_simulator_tpu.topo import committee

        c, m = cfg.committees, cfg.n // cfg.committees
        if c % n_shards != 0:
            raise ValueError(
                f"committees={c} not divisible by {n_shards} node shards "
                "(the committee stack shards whole committees)"
            )

        def body(keys, alive_cm, honest_cm):
            return committee.stacked_body(cfg, keys, alive_cm, honest_cm)

        keys_sds = jax.eval_shape(
            lambda: committee._committee_keys(jax.random.key(0), c)
        )
        mask_sds = jax.eval_shape(
            lambda: jax.tree.map(
                lambda x: x.reshape(c, m),
                base_model.dyn_fault_masks(cfg.n, jnp.int32(0), jnp.int32(0)),
            )
        )
        outs = jax.eval_shape(body, keys_sds, *mask_sds)
        out_specs = partition.match_partition_rules(
            partition.node_dim_rules(), outs
        )
        lane = P(NODES_AXIS)
        shmapped = partition.partition(
            body, mesh, in_specs=(lane, lane, lane), out_specs=out_specs,
            wrap_jit=False,
        )

        @jax.jit
        def sim(key, n_crashed, n_byzantine):
            alive, honest = base_model.dyn_fault_masks(
                cfg.n, n_crashed, n_byzantine
            )
            keys = committee._committee_keys(key, c)
            return shmapped(keys, alive.reshape(c, m), honest.reshape(c, m))

        return sim

    if layout not in ("exchange", "regather"):
        raise ValueError(
            f"sharded_topo_sim_fn layout must be 'exchange' or 'regather', "
            f"got {layout!r}"
        )
    proto = base_model.get_protocol(cfg.protocol)
    tables = gd.table_operands(cfg, inslot=topo_tables_inslot(cfg))
    # explicit NamedShardings must divide evenly (jax 0.4 pjit aval
    # check) — zero-pad the table rows to the next multiple of the shard
    # count and slice back inside the program (pad rows are never read:
    # every gather indexes ids < n)
    pad = (-cfg.n) % n_shards
    if pad:
        tables = tuple(
            np.pad(t, ((0, pad),) + ((0, 0),) * (t.ndim - 1))
            for t in tables
        )
    n_tables = len(tables)
    if layout == "exchange":
        from blockchain_simulator_tpu.topo import spec as topo_spec

        # plans over the PADDED tables: pad rows only reference row 0 (an
        # extra shipped row at worst), and the exchange output is sliced
        # back to cfg.n rows inside NeighborExchange
        xspec = partition.ExchangeSpec(mesh, cfg.n)
        plans = ()
        for tab in (tables[0], tables[1]):  # "in", "out" — xspec.kinds
            plans += topo_spec.owner_bucket_plan(tab, n_shards)
        inner_fn = make_topo_dyn_sim_fn(cfg, exchange_spec=xspec)
    else:
        plans = ()
        inner_fn = make_topo_dyn_sim_fn(cfg)
    if pad:
        def fn(key, n_crashed, n_byzantine, *ops):
            return inner_fn(
                key, n_crashed, n_byzantine,
                *(t[: cfg.n] for t in ops[:n_tables]), *ops[n_tables:]
            )
    else:
        fn = inner_fn
    operands = tables + plans
    tab_sds = tuple(
        jax.ShapeDtypeStruct(t.shape, jnp.int32) for t in operands
    )
    key_sds = jax.eval_shape(lambda: jax.random.key(0))
    cnt_sds = jax.ShapeDtypeStruct((), jnp.int32)
    outs = jax.eval_shape(fn, key_sds, cnt_sds, cnt_sds, *tab_sds)
    out_shardings = partition.match_partition_rules(
        partition.node_dim_rules(getattr(proto, "GLOBAL_FIELDS", ())), outs
    )
    # finals whose node dim stays uneven can't carry an explicit sharded
    # spec either — replicate those leaves (uneven n only)
    out_shardings = jax.tree.map(
        lambda spec, aval: (
            P()
            if spec
            and spec[0] == NODES_AXIS
            and aval.shape[0] % n_shards != 0
            else spec
        ),
        out_shardings,
        outs,
        is_leaf=lambda x: x is None or isinstance(x, P),
    )
    table_spec = P(NODES_AXIS)
    p = partition.partition(
        fn, mesh,
        in_shardings=(P(), P(), P()) + (table_spec,) * len(operands),
        out_shardings=out_shardings,
    )
    ns = NamedSharding(mesh, table_spec)
    operands_dev = tuple(jax.device_put(t, ns) for t in operands)

    def sim(key, n_crashed, n_byzantine):
        return p(key, n_crashed, n_byzantine, *operands_dev)

    # audit hooks: the graph specs trace `partitioned` with `table_avals`
    # as arguments, so the audited jaxpr carries the tables (and, in
    # exchange layout, the pos/send plans) as operands — the runtime
    # closure above never re-bakes them either (device arrays)
    sim.partitioned = p
    sim.table_avals = tab_sds
    sim.exchange_layout = layout
    return sim


def run_sharded_topo(cfg: SimConfig, mesh, seed: int | None = None):
    """Run one kregular/committee simulation node-dim-sharded over
    ``mesh`` (:func:`sharded_topo_sim_fn`); returns the same metrics dict
    as ``runner.run_simulation`` — bit-equal to it under the exact sampler
    at any mesh size (the tables-as-operands computation is identical and
    the committee stack shards whole committees)."""
    canon = canonical_fault_cfg(cfg)
    sim = sharded_topo_sim_fn(canon, mesh)
    nc = cfg.faults.resolved_n_crashed(cfg.n)
    nb = cfg.faults.n_byzantine
    key = jax.random.key(cfg.seed if seed is None else seed)
    final = jax.block_until_ready(
        sim(key, jnp.int32(nc), jnp.int32(nb))
    )
    return sim_metrics(cfg, final)


@aotcache.cached_factory("multi-seed-tick")
def multi_seed_fn(cfg: SimConfig, n_seeds: int):
    """THE single-device lane-after-lane executable:
    ``batched(keys[B], n_crashed[B], n_byzantine[B]) -> finals`` running B
    lanes of one fault structure as ONE dispatch of a ``lax.map`` over the
    UNVMAPPED dyn program (partition.seq_map — the per-device body of the
    mesh sweep arm, without the mesh).  :func:`run_dyn_points` picks it over
    :func:`dyn_batched_fn` for lanes that are a large share of the device's
    memory (:func:`_device_place`); ``multi_seed=True`` forces it.

    When it beats the lane batch.  *On a TPU*: where ONE lane fills the
    chip.  Each lane is then the lone program, whose ``[D, n, slots]`` rings
    keep the one layout every ring op asks for (ops/ring.node_minor).  By
    the two traces of ``pbft100k.byzsweep`` (PERF.md section 5; PR 49, a call
    of 8 lanes of n = 100,000 as two four-lane tiles against the 8 in turn,
    3.466 against 1.985 s of device time): the lane batch's 1.84 GB layout
    copy of the COMMIT ring is gone (0.543 s a call; the pushes under
    ``push_bucket_counts`` 0.932 -> 0.263 s), a ring pop is a plain
    dynamic-update-slice of 82-86 us a lane where the tile's, with vmap's
    select over lanes, took 113-156 us a lane, and the vote tables' passes
    take 0.64 s for 1.04; it holds 1.9 GB of temporaries for a tile's 10.6.
    722 against 414 us a lane-tick there, and the other way round at n =
    1,024, where a lone lane leaves the chip idle (section 7 (h): the
    readings beside ``_MAP_LANE_SHARE``).  *On XLA:CPU* (ISSUE 13, no user's
    backend): at every size read, because vmap
    lowers each of the tick engine's ring pushes, a dynamic-update-slice on
    a scan-carried ring, to XLA generic scatter, which XLA:CPU serializes
    (KNOWN_ISSUES #0b/#0i, ARTIFACT_tick_bench.json); the ``lax.map`` body
    keeps every push a plain DUS.  Either way the whole batch costs one
    Python dispatch and one executable.

    ``cfg`` must already be canonical (models/base.canonical_fault_cfg):
    one registry entry per (fault structure, B) — seeds and fault counts
    ride the mapped operands, never the trace (divergence twins pin this,
    lint/graph/programs.py ``multi_seed.*``).  Rows are bit-equal per seed
    to sequential solo runs of ``jit(make_dyn_sim_fn(cfg))`` under the
    exact sampler (tests/test_ztick.py); the "normal" CLT float caveat in
    the module docstring applies unchanged."""
    # n_seeds only keys the registry entry (jit specializes on the operand
    # batch shape either way; keying it keeps hit/miss stats per-(cfg, B)
    # truthful — the one-executable pins count misses around dispatches)
    del n_seeds
    return jax.jit(partition.seq_map(make_dyn_sim_fn(cfg)))


def run_seed_sweep(cfg: SimConfig, seeds, mesh=None):
    """Run ``len(seeds)`` simulations of one config in a single vmapped
    program; returns a list of per-seed metrics dicts, read back in ONE
    device->host fetch of the leaves ``metrics`` reads (:func:`_readback`)."""
    # Every schedule is fully traceable — including round-schedule raft,
    # whose checked handoff is a lax.cond (models/raft_hb.scan_from_init)
    # that vmap lowers to a select: both branches run for the whole batch,
    # so a batched round-schedule raft sweep costs about one tick-engine
    # pass (the fallback continues the prefix carry), never more.  The tick
    # engines' gated deliveries are NOT such a select on one device: the
    # batch binds the lane axis (models/base.lane_vmap) and ``gated``
    # branches on "any lane active", so a tick on which no lane broadcasts
    # skips the arm as a lone run does.  Over a mesh they are selects.
    if mesh is not None:
        n_sweep = mesh.shape[SWEEP_AXIS]
        if len(seeds) % n_sweep != 0:
            raise ValueError(
                f"{len(seeds)} seeds not divisible by sweep axis size {n_sweep}"
            )
    # the sweep layer's three host states, by name, on the profiler's clock
    # (utils/telemetry.py): operands -> execute -> readback; ``rows`` is the
    # count of results read back, ``lanes`` the batch axis dispatched
    rows = len(seeds)
    with telemetry.span("sweep.operands", rows=rows, lanes=rows):
        batched = _batched_fn(cfg, mesh)
        keys = jax.vmap(jax.random.key)(jnp.asarray(seeds, jnp.uint32))
    with telemetry.span("sweep.execute", rows=rows, lanes=rows):
        finals = jax.block_until_ready(batched(keys))
    out = []
    with _readback(cfg, finals, rows) as states:
        for seed, state in zip(seeds, states):
            m = sim_metrics(cfg, state)
            # observability routing: a finalized COPY of every sweep row goes
            # to the optional runs.jsonl ($BLOCKSIM_RUNS_JSONL, utils/obs.py);
            # the returned dicts stay pure metrics — tests compare them
            # bit-for-bit against single runs; nothing below touches the device
            obs.record_run({"seed": int(seed), **m}, cfg)
            out.append(m)
    return out


def _dyn_operands(cfg: SimConfig, fc) -> tuple[int, int]:
    """The traced (n_crashed, n_byzantine) operand point of a fault config."""
    return fc.resolved_n_crashed(cfg.n), fc.n_byzantine


# the sweep layer's host spans, by name (utils/telemetry.py)
SPANS = ("sweep.operands", "sweep.execute", "sweep.readback", "sweep.chunk",
         "sweep.tile")

# What one lane of a vmapped tick program takes on the device, as a multiple
# of the state its scan carries.  XLA's ``memory_analysis`` of the programs
# compiled for a described v5e: 6.03 GB of temporaries over 3.8 GB of state
# at 32 lanes of pbft-fullmesh-1k (x1.58), 10.25 GB over 6.04 GB at 4 lanes of
# pbft-byzsweep-100k (x1.70: the scan's carry and what a taken arm draws
# beside it); on the chip that program reserved 7.91 GB (x1.31,
# ``peak_bytes_reserved``), all 8 lanes in one dispatch OF THE LANE BATCH
# 15.81 GB of the 16.43 GB the device lets a process reserve (and ran a tenth
# slower than two tiles), and the finals of the dispatch before stay on the
# device until they are read (x0.08).  2 leaves room over all of them: a
# dispatch that dies of memory loses the whole sweep, one dispatch more costs
# milliseconds (PERF.md section 6, PR 35).  The same 8 lanes one after
# another under ``lax.map`` (:func:`multi_seed_fn`) are 1.86 GB of temporaries
# over one lane's 1.51 GB (x1.23) and 0.96 GB of stacked results, 8.1 GB
# reserved on the chip with the lane batch's executable beside it (PERF.md
# section 7 (h), PR 47): the factor covers the one lane a map holds, too.
_TEMP_FACTOR = 2.0

# A lane that is more than this share of the device's memory runs ALONE: a
# one-device list of such lanes goes lane after lane through ``lax.map``
# (:func:`multi_seed_fn`) in place of the lane batch (:func:`_device_place`).
# A lane that is a large share of the chip fills it by itself and pays for
# company (a batch axis on every ``[D, n, slots]`` ring, a tile's temporaries,
# the dispatches between tiles); a small lane needs company to fill the chip.
# The readings it was set from, all on one v5e (``bytes_limit``
# 16,909,336,064: the share is 264.2 MB there, "fewer than 32 lanes would fit
# as a batch" under ``_TEMP_FACTOR`` = 2), warm, three dispatches each, the
# same operands to both programs, rows or leaves equal (my chip runs: PR 47,
# PERF.md section 7 (h); the starred rows read in PR 49, section 6: the three
# that PR 47 had came out the same to three digits, the two at n = 1,536 and
# 2,304 are new):
#
#    lane state   of the   shape (lanes)                  lane     lax.map
#                 device                                  batch
#       7.0 MB    1/2416   Paxos n=1,024 (32)            13.320 s   7.784 s  map x1.71 (a)
#      30.4 MB    1/556    byzsweep at n=2,016 (8)        0.0780    0.1198   batch x1.53
#     121.4 MB *  1/139    pbft-fullmesh-1k (32)          0.3713    0.6662   batch x1.79 (b)
#     121.7 MB    1/139    byzsweep at n=8,064 (8)        0.2522    0.1953   map x1.29 (c)
#     182.1 MB *  1/93     per-edge PBFT n=1,536 (32)     0.7928    1.0611   batch x1.34
#     273.1 MB *  1/62     per-edge PBFT n=2,304 (16)     1.1938    0.9374   map x1.27
#     278.7 MB *  1/61     tick Raft n=1,024 (32)        20.645    17.780    map x1.16
#     486.9 MB *  1/35     byzsweep at n=32,256 (8)       1.1720    0.6428   map x1.82
#    1509.4 MB    1/11     pbft-byzsweep-100k (8)         3.4886    1.9987   map x1.746
#
# (a) its engine's reason, not its size's; (b) per-edge delivery: the lane
# of ``pbft1k.mc`` and of the server's buckets; (c) stat delivery: bytes
# cannot tell it from (b).  Bytes sort seven of the nine: the per-edge
# engine crosses over between 1/93 and 1/62, which is where the constant
# lies; the stat engine crosses lower (between 1/556 and 1/139), so (c) and
# (a) are MISPLACED: they stay on the lane batch, as before this rule, and
# lose nothing they had.  The constant is provisional in that sense: a rule
# that also read the delivery would place (c), and needs a cell of stat lanes
# at mid n to be claimed in (PERF.md section 7 (h)).  It is a reading of the
# hardware, not an option: nothing sets it but this line.
_MAP_LANE_SHARE = 1 / 64


def _logical_bytes(shapes) -> int:
    """Elements times item size over a pytree of shapes, with no account of
    how a device tiles them."""
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(shapes))


@functools.lru_cache(maxsize=64)
def _lane_state_bytes(canon: SimConfig) -> int:
    """LOGICAL bytes of state one lane of ``make_dyn_sim_fn(canon)`` carries
    through its scan: ``eval_shape`` of the ``init`` that program calls
    (nothing is allocated), elements times item size, with no account of how
    the device tiles them.  At the lanes the rule was set from (``[D, N, W]``
    rings of 500 to 100,000 nodes) the two agree; a lane of narrow leaves (a
    Raft group of 5: 4,010 bytes, every minor dimension 5 or 50) would pad
    29-fold under an (8, 128) tile with the lane axis leading, but XLA:TPU
    lays such a batch out lane-minor by itself and 20,000 of them reserve 153
    MB for 83 MB logical (PERF.md section 6, PR 42), well inside the
    ``_TEMP_FACTOR`` of :func:`_device_tile`.  A committee stack counts as
    all its committees at once,
    which is the most a lane can hold (topo/committee.py runs them in tiles
    cut by the same rule, :func:`_device_tile` with ``outer`` lanes)."""
    if canon.topology == "committee":
        from blockchain_simulator_tpu.topo import committee

        return canon.committees * _lane_state_bytes(committee.inner_cfg(canon))
    if canon.protocol == "pbft" and use_round_schedule(canon):
        from blockchain_simulator_tpu.models import pbft_round as mod
    else:
        mod = get_protocol(canon.protocol)
    return _logical_bytes(
        jax.eval_shape(lambda: mod.init(canon, jax.random.key(0))))


@functools.lru_cache(maxsize=1)
def _device_bytes() -> int | None:
    """The memory the default device reports it may use; None where a
    backend reports none (XLA:CPU), and a list is then never tiled."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("bytes_limit")


def _device_tile(canon: SimConfig, n_points: int, outer: int = 1) -> dict | None:
    """How a point list that outgrows the device is cut: ``None`` where the
    whole list fits as one lane batch (or the device reports no memory),
    else the equal tile: as few dispatches as the device allows, the lanes
    of each the list's length over that count, rounded up.  The one rule of
    the sweeps' point lists and of a committee stack's committees
    (topo/committee.tile_plan: ``canon`` the inner configuration,
    ``n_points`` the committees, ``outer`` the lanes of a batch around the
    stack, each of which holds a tile of its own)."""
    device = _device_bytes()
    if device is None or n_points <= 1:
        return None
    state = _lane_state_bytes(canon) * outer
    most = max(int(device // (_TEMP_FACTOR * state)), 1)
    if n_points <= most:
        return None
    tiles = -(-n_points // most)
    return {"lanes": -(-n_points // tiles), "state_bytes": state,
            "device_bytes": int(device)}


@functools.lru_cache(maxsize=64)
def _lane_result_bytes(canon: SimConfig) -> int:
    """LOGICAL bytes of what one lane of ``make_dyn_sim_fn(canon)`` returns
    (``eval_shape`` of the program: nothing is allocated, nothing runs): a
    ``lax.map`` over lanes stacks these, one a lane, while it holds the
    state of one lane only.  The tick engines return the state without the
    rings, 0.12 GB of a 1.51 GB lane at n = 100,000."""
    key = jax.eval_shape(lambda: jax.random.key(0))
    cnt = jax.ShapeDtypeStruct((), jnp.int32)
    return _logical_bytes(
        jax.eval_shape(make_dyn_sim_fn(canon), key, cnt, cnt))


def _device_place(canon: SimConfig, n_points: int) -> dict | None:
    """Where a one-device point list runs, from what the code can observe (a
    lane's state bytes, the memory the device reports, the list's length):
    ``None`` for the whole list as ONE lane batch, which is every list where
    the device reports no memory (XLA:CPU), every list of one point, and
    every list of small lanes that fits; else the plan of the chunk loop,
    ``{"program", "lanes", "points", "state_bytes", "device_bytes"}`` with
    ``lanes`` the lanes the device holds at once and ``points`` the lanes of
    one dispatch:

    - ``"lax.map"``: a lane over ``_MAP_LANE_SHARE`` of the device runs
      alone, lane after lane in one dispatch of :func:`multi_seed_fn`
      (``lanes`` 1).  The dispatch holds one lane's state and temporaries
      (``_TEMP_FACTOR`` times its state) and every lane's results
      (:func:`_lane_result_bytes`), so a list whose stacked results outgrow
      what is left of the device is cut into as few equal dispatches as fit
      (the ceiling ran on a v5e: 115 lanes of n = 100,000 a dispatch,
      ``peak_bytes_in_use`` 13.8 of 16.9 GB, two such dispatches in a row:
      PERF.md section 6, PR 49).
    - ``"lane-batch"``: smaller lanes that outgrow the device as one batch
      run as the equal tiles of :func:`_device_tile` (``lanes`` ==
      ``points``)."""
    device = _device_bytes()
    if device is None or n_points <= 1:
        return None
    state = _lane_state_bytes(canon)
    if state <= _MAP_LANE_SHARE * device:
        cut = _device_tile(canon, n_points)
        return cut and {"program": "lane-batch", "points": cut["lanes"], **cut}
    room = device - _TEMP_FACTOR * state
    most = max(int(room // _lane_result_bytes(canon)), 1)
    dispatches = -(-n_points // most)
    return {"program": "lax.map", "lanes": 1,
            "points": -(-n_points // dispatches), "state_bytes": state,
            "device_bytes": int(device)}


def _dispatch_dyn_points(canon: SimConfig, points, record: bool = True,
                         n_out: int | None = None, mesh=None,
                         multi_seed: bool = False, probe=None):
    """ONE un-journaled batched dispatch of a same-structure point list —
    the body :func:`run_dyn_points` either calls directly (no journal) or
    wraps in chunked, supervised, durable execution.  ``multi_seed``
    selects the ``lax.map`` program (:func:`multi_seed_fn`: lane after
    lane) over the lane batch on the single-device path, by the caller's
    word or by :func:`_device_place`'s (through :func:`_run_chunk`); a mesh
    dispatch already maps sequentially per device, so the flag is a no-op
    there.
    ``probe`` (an obsim/schema.ProbeConfig) swaps in the armed twin of
    the same arm (obsim/build.py ``consobs-*`` registry entries) and
    attaches a per-row ``"probe"`` summary; monitor violations trip the
    flight recorder host-side (obsim/host.note_violations).  Rows are read
    back as in :func:`run_seed_sweep`: one fetch (:func:`_readback`)."""
    points = list(points)
    # the batched-dispatch chaos point: the drills inject raise/hang/slow
    # here — the exact exception path a real backend fault takes through
    # the sweeps AND the serving degrade machinery (chaos/inject.py)
    inject.chaos_point("sweep.dyn_dispatch", canon=canon, n=len(points))
    if probe is not None:
        from blockchain_simulator_tpu.obsim import build as obsim_build
    if mesh is not None and partition.mesh_size(mesh) > 1 \
            and len(points) == 1:
        # a 1-point list on the mesh path would pad to a full sweep-axis
        # width of duplicate lanes; the single-device program answers it
        # with zero pad waste and rows bit-equal under the exact sampler
        # (the same equivalence the supervised degrade arm relies on)
        mesh = None
    dispatch_points = points
    if mesh is not None and partition.mesh_size(mesh) > 1:
        lanes = max(partition.sweep_axis_size(mesh), 1)
        dispatch_points, _ = partition.pad_points(points, lanes)
        batched = (obsim_build.probed_mesh_fn(canon, probe, mesh)
                   if probe is not None else mesh_dyn_batched_fn(canon, mesh))
    elif multi_seed:
        batched = (obsim_build.probed_batched_fn(canon, probe,
                                                 multi_seed=True)
                   if probe is not None
                   else multi_seed_fn(canon, len(points)))
    else:
        batched = (obsim_build.probed_batched_fn(canon, probe)
                   if probe is not None else dyn_batched_fn(canon))
    # the same three spans as run_seed_sweep (children of the sweep.chunk
    # span where there is one; a batched serve flush carries them too)
    n_lanes = len(dispatch_points)
    rows = len(points) if n_out is None else min(n_out, len(points))
    with telemetry.span("sweep.operands", rows=rows, lanes=n_lanes):
        keys = jax.vmap(jax.random.key)(
            jnp.asarray([s for _, s in dispatch_points], jnp.uint32)
        )
        ops = [_dyn_operands(cfg, cfg.faults) for cfg, _ in dispatch_points]
        nc = jnp.asarray([o[0] for o in ops], jnp.int32)
        nb = jnp.asarray([o[1] for o in ops], jnp.int32)
    with telemetry.span("sweep.execute", rows=rows, lanes=n_lanes):
        outs = jax.block_until_ready(batched(keys, nc, nb))
    finals, probes = outs if probe is not None else (outs, None)
    out = []
    # one field set for all: the points share canon's protocol and topology
    with _readback(canon, finals, rows) as states:
        for i, ((cfg_i, seed), state) in enumerate(zip(points, states)):
            m = sim_metrics(cfg_i, state)
            if probe is not None:
                from blockchain_simulator_tpu.obsim import host as obsim_host

                m["probe"] = obsim_host.summarize_lane(cfg_i, probe, probes, i)
                obsim_host.note_violations(m["probe"], cfg_i, int(seed))
            if record:
                obs.record_run({"seed": int(seed), **m}, cfg_i)
            out.append(m)
    return out


def _run_chunk(canon, tile, record, n_out, mesh, supervise, journal, key,
               index, multi_seed=False, probe=None, placed=None):
    """Compute ONE chunk, optionally under the supervisor's deadline →
    retry → degrade state machine (parallel/journal.py).  The
    ``sweep.chunk`` chaos point fires once per ATTEMPT with the arm in
    its ctx, so a drill can wedge exactly the primary arm and watch the
    degrade arm answer.  Where the chunk is a dispatch of a list placed on
    the device (``placed``, :func:`_device_place`), each dispatch of
    it runs the placed program and stands under a ``sweep.tile`` span that
    says what was dispatched and what it was sized from: ``lanes`` the lanes
    the device holds AT ONCE (the tile's under the lane batch, 1 under
    ``lax.map``, where a device event is one lane's), ``points`` the lanes
    the dispatch runs in all."""

    def dispatch(mesh, multi_seed):
        span = contextlib.nullcontext()
        if placed is not None:
            # the placement holds on the degrade arm too: a chunk of lanes
            # sized to run one after another does not fit as a lane batch
            mapped = placed["program"] == "lax.map"
            multi_seed = multi_seed or mapped
            rows = len(tile) if n_out is None else n_out
            span = telemetry.span(
                "sweep.tile", tile=index, lanes=1 if mapped else len(tile),
                points=len(tile), pad=len(tile) - rows,
                state_bytes=placed["state_bytes"],
                device_bytes=placed["device_bytes"])
        with span:
            return _dispatch_dyn_points(canon, tile, record, n_out, mesh,
                                        multi_seed, probe)

    def primary():
        inject.chaos_point("sweep.chunk", key=key, index=index,
                           n=len(tile), arm="primary",
                           mesh=mesh is not None)
        # every chunk ATTEMPT is one span on a chunk-scoped trace
        # (utils/telemetry.py; the ISSUE 14 sweep-side mint point): the
        # post-mortem story "which chunk, which arm, how long" as data
        with telemetry.span("sweep.chunk", key=key, index=index,
                            n=len(tile), arm="primary"):
            return dispatch(mesh, multi_seed)

    if supervise is None:
        return primary()

    from blockchain_simulator_tpu.runner import use_round_schedule

    if supervise.checkpoint_dir and len(tile) == 1 \
            and not use_round_schedule(tile[0][0]):
        # the very-long-single-sim arm: tick-level mid-chunk checkpoints
        # (utils/checkpoint.py) — a re-kill resumes MID-chunk from the
        # last segment instead of restarting the whole sim
        cfg_pt, seed_pt = tile[0]

        def degrade():
            inject.chaos_point("sweep.chunk", key=key, index=index,
                               n=len(tile), arm="degrade-checkpoint",
                               mesh=False)
            import os as _os

            from blockchain_simulator_tpu import runner as runner_mod

            with telemetry.span("sweep.chunk", key=key, index=index,
                                n=len(tile), arm="degrade-checkpoint"):
                m, _ = runner_mod.run_dyn_checkpointed(
                    cfg_pt, supervise.checkpoint_every_ms,
                    _os.path.join(supervise.checkpoint_dir, key),
                    seed=seed_pt,
                )
            return [m]
    else:
        # the mesh-shrink arm (partition.py's size-1/no-mesh path): the
        # single-device program is bit-equal under the exact sampler, so
        # a degraded chunk's rows are indistinguishable from healthy ones
        def degrade():
            inject.chaos_point("sweep.chunk", key=key, index=index,
                               n=len(tile), arm="degrade", mesh=False)
            with telemetry.span("sweep.chunk", key=key, index=index,
                                n=len(tile), arm="degrade"):
                return dispatch(None, False)

    rows, _events = journal_mod.run_supervised(
        primary, degrade, supervise, journal=journal, key=key,
    )
    return rows


def run_dyn_points(canon: SimConfig, points, record: bool = True,
                   n_out: int | None = None, mesh=None, journal=None,
                   chunk_size: int | None = None, supervise=None,
                   multi_seed: bool = False, probe=None,
                   key_suffix: str = "", with_index: bool = False):
    """THE group-dispatch primitive: one vmapped executable over an
    arbitrary list of same-structure ``(cfg, seed)`` points.

    ``points`` is a sequence of ``(cfg, seed)`` pairs whose configs all
    canonicalize to ``canon`` (``canonical_fault_cfg``) — they may differ
    only in fault COUNTS, which become the traced per-lane operands.
    Returns one metrics dict per point, in order, each bit-equal (exact
    sampler; see the module caveat for the normal CLT path) to a solo run
    of the same ``(cfg, seed)``.

    Both the fault sweeps (:func:`run_fault_sweep`, a cross product of
    points) and the scenario server's micro-batched dispatch
    (serve/dispatch.py, whatever compatible requests are queued) route
    through here.  ``record=False`` skips the per-row runs.jsonl hook for
    callers that write their own access-log records (the server does);
    ``n_out`` computes host-side metrics for only the first ``n_out``
    points (the server's bucket-padded lanes are duplicates whose metrics
    would be discarded).

    With ``mesh`` set the batch axis shards over the mesh's sweep axis
    through :func:`mesh_dyn_batched_fn` (parallel/partition.py): the point
    list is padded to a multiple of the sweep axis size by repeating the
    last point (padding lanes ride at the tail, so real-point indices are
    unchanged and pad metrics are never computed).  A mesh of size 1 takes
    the single-device path verbatim.

    **On one device the code places the list** (:func:`_device_place`:
    derived from a lane's state bytes and the memory the device reports,
    not configured).  *Lanes that are a large share of the device* (over
    ``_MAP_LANE_SHARE`` of its memory: three 460 MB rings a lane for the
    PBFT tick engine at n = 100,000) run one after another through the
    ``lax.map`` executable (:func:`multi_seed_fn`), the whole list in one
    dispatch, cut into as few equal dispatches as fit only where the
    stacked results outgrow the device: such a lane fills the chip alone
    and a lane batch makes each of them dearer (722 against 414 us a
    lane-tick at four lanes of 100,000: my chip runs, PR 49, PERF.md
    section 6).
    *Smaller lanes* run as ONE lane batch, which holds every lane's state
    at once, so the most lanes a dispatch may have is the memory the device
    reports over ``_TEMP_FACTOR`` times one lane's state bytes
    (:func:`_device_tile`); a longer list is cut into as few equal tiles as
    that allows.  Either cut runs through the chunk loop below, every
    dispatch through the ONE executable (the tail padded by repeating its
    last point), each under a ``sweep.tile`` span; rows come back in
    order, entry for entry those of one dispatch (exact sampler; the
    module caveat for the normal one).  A list of small lanes that fits,
    a list of one point, and every list where the device reports no
    memory (XLA:CPU) dispatch as one lane batch, as they always did.
    ``meta["tile"]`` says what was chosen (``program``: ``"lax.map"`` or
    ``"lane-batch"``; ``lanes`` the device holds at once, ``points`` a
    dispatch, ``state_bytes``, ``device_bytes``), None where the list ran
    as one lane batch.

    **Durable execution** (``journal=``, a parallel/journal.SweepJournal):
    the point list splits into ``chunk_size``-point chunks (default: one
    chunk; the fault sweeps pass one chunk per fault level, aligned up to
    the mesh lanes), each chunk's rows are appended to the journal —
    fsynced, with per-row checksums and the registry ``cache`` block —
    BEFORE the next chunk dispatches, and chunks whose content-addressed
    key (parallel/journal.chunk_key) is already journaled are served from
    the journal without dispatching: a restarted sweep recomputes at most
    the one chunk that was in flight.  Resumed rows ride a JSON round
    trip (ints/floats exact) and are NOT re-recorded to runs.jsonl.
    ``supervise=`` (a journal.ChunkSupervisor) additionally wraps every
    computed chunk in the deadline → retry/backoff → degrade machine,
    with the transitions journaled as ``event`` lines — and works
    without a journal too (chunked + supervised, just not durable).

    The wedged-health fail-fast gate lives on the SWEEP entrypoints
    (:func:`run_fault_sweep` / :func:`run_byzantine_sweep`), not here:
    the scenario server's batched flushes route through this function
    and its admission is already health-gated — raising per flush would
    only be swallowed into an un-gated degrade-to-solo
    (serve/dispatch.run_batch's typed-error wrapper).

    ``multi_seed=True`` forces single-device batches through the
    ``lax.map`` executable (:func:`multi_seed_fn`) whatever the lanes'
    size, the whole list in one dispatch (``runner.run_multi_seed`` and
    the sweeps' ``multi_seed=`` kwarg pass it; rows bit-equal under the
    exact sampler).  The default, ``False``, means the code chooses, as
    above: where the device reports no memory that is the lane batch, so
    registry trajectories and pins on XLA:CPU are untouched.

    ``probe=`` (an obsim/schema.ProbeConfig) arms the in-program
    consensus taps: every row gains a ``"probe"`` summary
    (obsim/schema.summarize) and monitor violations trip the flight
    recorder (obsim/host.note_violations).  Primary metrics stay
    bit-equal to the disarmed dispatch — taps consume zero PRNG.  Armed
    flushes journal under a probe-suffixed chunk key, so a journal
    written disarmed never answers an armed flush (and vice versa);
    journal-cached armed rows serve their stored summaries as-written
    without re-firing the violation hook.

    ``key_suffix`` is appended verbatim to every chunk's journal key
    (after the probe suffix) — the namespace hook the query engine
    (query/engine.py) uses to keep refinement chunks (``+q<step>``)
    disjoint from grid chunks over the same canonical structure.

    ``with_index=True`` returns ``(rows, meta)`` instead of bare rows:
    ``meta["rows"][i]`` maps output row ``i`` back to its point —
    ``{"point": index into ``points``, "seed", "key" (journal chunk key
    or None un-journaled), "cached" (served from the journal without
    dispatching)}`` — and ``meta`` carries the dispatch accounting a
    refinement loop needs (``dispatches`` actually fired, ``lanes``
    dispatched including mesh padding, ``pad`` wasted lanes,
    ``chunks`` per-chunk trail).  A 1-point list never pads: it takes
    the single-device path even under a mesh (bit-equal, exact
    sampler)."""
    points = list(points)
    meta = {"rows": [], "chunks": [], "lanes": 0, "dispatches": 0, "pad": 0,
            "tile": None}
    # on one device the code places the list (:func:`_device_place`): lanes
    # that are a large share of the device run one after another under
    # ``lax.map``, smaller ones as a lane batch, tiled where it outgrows the
    # device.  A mesh arm already runs a device's lanes one after another,
    # and ``multi_seed=True`` is the caller's own choice of the map.
    placed = None
    if not multi_seed and (mesh is None or partition.mesh_size(mesh) == 1):
        placed = _device_place(canon, len(points))

    def _lanes(n: int) -> int:
        if n > 1 and mesh is not None and partition.mesh_size(mesh) > 1:
            axis = max(partition.sweep_axis_size(mesh), 1)
            return -(-n // axis) * axis
        return n

    def _done(rows):
        return (rows, meta) if with_index else rows

    if journal is None and supervise is None and placed is None:
        rows = _dispatch_dyn_points(canon, points, record, n_out, mesh,
                                    multi_seed, probe)
        if points:
            meta["dispatches"] = 1
            meta["lanes"] = _lanes(len(points))
            meta["pad"] = meta["lanes"] - len(points)
        pts_out = points if n_out is None else points[:n_out]
        meta["rows"] = [
            {"point": i, "seed": int(s), "key": None, "cached": False}
            for i, (_, s) in enumerate(pts_out)
        ]
        return _done(rows)
    if not points:
        return _done([])
    if chunk_size is None or n_out is not None:
        # n_out callers (serve's bucket-padded flushes) journal the whole
        # batch as ONE chunk: pad lanes never split across chunk keys
        chunk_size = len(points)
    if mesh is not None and partition.mesh_size(mesh) > 1:
        chunk_size = partition.align_chunk(
            chunk_size, max(partition.sweep_axis_size(mesh), 1)
        )
    if placed is not None:
        chunk_size = min(chunk_size, placed["points"])
        placed = {**placed, "points": chunk_size,
                  "lanes": min(chunk_size, placed["lanes"])}
        meta["tile"] = placed
    done = journal.completed() if journal is not None else {}
    out = []
    for index, start in enumerate(range(0, len(points), chunk_size)):
        tile = points[start:start + chunk_size]
        want = len(tile) if n_out is None \
            else max(0, min(len(tile), n_out - start))
        t_out = None if n_out is None else want
        if placed is not None and want == 0:
            break  # the tiles from here on hold bucket padding alone
        key = journal_mod.chunk_key(canon, index, tile, mesh, n_out=t_out)
        if probe is not None:
            # armed and disarmed flushes must never share a journal key:
            # a cached disarmed chunk has no "probe" summaries to serve
            key += f"+p{probe.windows}{'m' if probe.monitors else ''}"
        key += key_suffix
        cached = done.get(key)
        if cached is not None and len(cached) == want:
            meta["chunks"].append({"key": key, "index": index,
                                   "cached": True, "n": want})
            meta["rows"] += [
                {"point": start + j, "seed": int(tile[j][1]), "key": key,
                 "cached": True}
                for j in range(want)
            ]
            out.extend(cached)
            continue
        # every dispatch ATTEMPT runs record=False: only the winning
        # arm's rows (journaled below) reach runs.jsonl — an abandoned
        # slow attempt finishing late must not double-record its points
        lanes, l_out = tile, t_out
        if placed is not None and len(tile) < chunk_size:
            # the tail tile runs the executable of the others: the lanes it
            # lacks repeat its last point (as the mesh arm pads) and are
            # not read back into rows
            lanes = tile + [tile[-1]] * (chunk_size - len(tile))
            l_out = want
        rows = _run_chunk(canon, lanes, False, l_out, mesh, supervise,
                          journal, key, index, multi_seed, probe, placed)
        # durable BEFORE the next chunk dispatches — the recompute-at-
        # most-one contract the kill -9 drill pins
        if journal is not None:
            journal.append_chunk(key, index, rows,
                                 cache=aotcache.registry.manifest())
        if record:
            pts_out = tile if t_out is None else tile[:t_out]
            for (cfg_i, seed_i), m in zip(pts_out, rows):
                obs.record_run({"seed": int(seed_i), **m}, cfg_i)
        meta["dispatches"] += 1
        meta["lanes"] += _lanes(len(lanes))
        meta["pad"] += _lanes(len(lanes)) - len(tile)
        meta["chunks"].append({"key": key, "index": index,
                               "cached": False, "n": len(rows)})
        meta["rows"] += [
            {"point": start + j, "seed": int(tile[j][1]), "key": key,
             "cached": False}
            for j in range(len(rows))
        ]
        out.extend(rows)
    return _done(out)


def dyn_chunk_keys(cfg: SimConfig, fault_configs, seeds, mesh=None):
    """The chunk keys a journaled ``run_fault_sweep(cfg, fault_configs,
    seeds, mesh=..., journal=...)`` will use for ONE same-structure group
    — derived from the grid alone, never from a journal's content, so a
    drill's coverage check is independent evidence (a journal that
    silently lost a chunk fails it).  All ``fault_configs`` must share
    one canonical structure (the helper asserts it)."""
    fcs = list(fault_configs)
    canons = {canonical_fault_cfg(cfg.with_(faults=fc)) for fc in fcs}
    if len(canons) != 1:
        raise ValueError(
            f"dyn_chunk_keys covers one structure group, got {len(canons)}"
        )
    canon = next(iter(canons))
    chunk = len(seeds)
    if mesh is not None and partition.mesh_size(mesh) > 1:
        chunk = partition.align_chunk(
            chunk, max(partition.sweep_axis_size(mesh), 1)
        )
    points = [(cfg.with_(faults=fc), s) for fc in fcs for s in seeds]
    return [
        journal_mod.chunk_key(canon, i, points[st:st + chunk], mesh)
        for i, st in enumerate(range(0, len(points), chunk))
    ]


def _run_dyn_group(cfg: SimConfig, canon: SimConfig, fcs, seeds, mesh=None,
                   journal=None, supervise=None, multi_seed=False):
    """One compiled program for every (fault config, seed) point of a
    same-structure group; returns {fc: [metrics per seed]} with rows
    bit-equal to ``run_seed_sweep(cfg.with_(faults=fc), seeds)``.

    With a journal, the group chunks one-fault-level-per-chunk (the
    seed tile) — the ISSUE's canonical-structure-group × level tile —
    so a crash mid-grid loses at most one level's seed batch."""
    points = [(cfg.with_(faults=fc), seed) for fc in fcs for seed in seeds]
    tiled = journal is not None or supervise is not None
    rows = run_dyn_points(canon, points, mesh=mesh, journal=journal,
                          chunk_size=len(seeds) if tiled else None,
                          supervise=supervise, multi_seed=multi_seed)
    n_s = len(seeds)
    return {
        fc: rows[i * n_s:(i + 1) * n_s] for i, fc in enumerate(fcs)
    }


def run_fault_sweep(cfg: SimConfig, fault_configs, seeds, mesh=None,
                    journal=None, supervise=None, multi_seed=False):
    """BASELINE config 4: sweep fault configs with seeds vmapped inside.
    Returns {fault_config: [metrics per seed]}.

    Fault configs that differ only in their COUNTS (crash/Byzantine) batch
    into one dynamic-operand executable per structure group — the whole
    default sweep is ONE compile.  Structurally distinct configs (different
    drop_prob / byz_forge / byz_copies) land in separate groups, each with
    its own dynamic-operand compile — same compile count as the old
    per-config loop, and future same-structure sweeps reuse the entry.
    Un-batchable configs (today: the mixed shard sim — the typed
    ``runner.UnbatchableConfigError``, classified here without
    string-matching) take the static ``run_seed_sweep`` path
    (one static compile per fault config).

    ``mesh`` shards every dynamic-operand group's (fault config, seed)
    batch over the mesh's sweep axis (see :func:`run_dyn_points`); the
    static fallback stays single-device — its mesh story is
    ``run_seed_sweep(mesh=...)``'s node-sharded one, with different
    divisibility requirements.

    ``journal=`` (parallel/journal.SweepJournal) makes the sweep durable:
    each structure group chunks one fault level (seed tile) per journaled
    chunk, and a restarted identical sweep skips completed chunks —
    recompute is at most the one in-flight chunk, rows bit-equal under
    the exact sampler.  The static (un-batchable) fallback is NOT
    journaled — it has no dynamic-operand chunk identity.  ``supervise=``
    (journal.ChunkSupervisor) adds per-chunk deadlines with bounded
    retry and a recorded degrade arm.  Before any dispatch, a fresh
    ``wedged`` verdict in the rolling health log
    ($BLOCKSIM_HEALTH_JSONL) fails fast with the typed
    ``utils.health.BackendWedgedError`` instead of hanging on backend
    init — the bench.py ladder rule, now on the sweep tier.

    ``multi_seed=True`` routes every single-device dynamic-operand group
    through the scatter-free ``lax.map`` executable
    (:func:`multi_seed_fn`) — seed-replicated sweep tiles collapse into
    one dispatch of the tick-path throughput arm (ISSUE 13), rows
    bit-equal to the default vmapped dispatch under the exact sampler."""
    from blockchain_simulator_tpu.utils import health

    health.require_not_wedged()
    fault_configs = list(fault_configs)
    groups: dict[SimConfig, list] = {}
    order = {}
    for fc in fault_configs:
        try:
            check_batchable(cfg.with_(faults=fc))
        except UnbatchableConfigError:
            order[fc] = None
            continue
        canon = canonical_fault_cfg(cfg.with_(faults=fc))
        if fc not in groups.setdefault(canon, []):
            groups[canon].append(fc)
        order[fc] = canon
    done: dict = {}
    for canon, fcs in groups.items():
        done.update(_run_dyn_group(cfg, canon, fcs, seeds, mesh=mesh,
                                   journal=journal, supervise=supervise,
                                   multi_seed=multi_seed))
    results = {}
    for fc in fault_configs:
        if order[fc] is None:
            results[fc] = run_seed_sweep(cfg.with_(faults=fc), seeds)
        else:
            results[fc] = done[fc]
    return results


def run_byzantine_sweep(cfg: SimConfig, f_values=None, seeds=(0,), forge=True,
                        mesh=None, journal=None, supervise=None,
                        multi_seed=False):
    """BASELINE config 4 end-to-end: sweep the Byzantine count f over
    ``f_values`` (default 0..(n-1)//3), seeds batched per f — the whole
    sweep is ONE vmapped executable over (f, seed) (dynamic fault operands;
    the per-f recompile this loop used to pay is gone).  ``mesh`` shards
    the (f, seed) cross product over the mesh's sweep axis
    (:func:`run_dyn_points`; tools/mesh_sweep_bench.py is the artifact).

    Each entry reports the two safety-relevant outcomes next to the fault
    level: ``forged_commits`` (a slot finalized although no honest leader ever
    proposed it — possible under the reference's no-dedup "n2" counting, see
    utils/config.py quorum_rule) and ``agreement_ok``.  Returns a list of
    {"f": f, "seed": s, **metrics} dicts.
    """
    if forge and cfg.protocol != "pbft":
        raise ValueError(
            "the forging attack is implemented for pbft only; pass "
            "forge=False to sweep passive vote-flipping Byzantine nodes "
            f"for {cfg.protocol!r}"
        )
    if f_values is None:
        f_values = range(cfg.byz_f + 1)
    f_values = list(f_values)
    fcs = [
        dataclasses.replace(cfg.faults, n_byzantine=f, byz_forge=forge)
        for f in f_values
    ]
    # dedup: repeated f values share one fault config (and one batch row set)
    res = run_fault_sweep(cfg, list(dict.fromkeys(fcs)), seeds, mesh=mesh,
                          journal=journal, supervise=supervise,
                          multi_seed=multi_seed)
    out = []
    for f, fc in zip(f_values, fcs):
        for seed, m in zip(seeds, res[fc]):
            out.append({"f": int(f), "seed": int(seed), **m})
    return out


@contextlib.contextmanager
def _readback(cfg: SimConfig, finals, rows: int):
    """The ``sweep.readback`` span of one batched dispatch; yields the final
    states of its first ``rows`` lanes as HOST states, in lane order.

    ONE ``jax.device_get`` brings over the leaves the protocol's ``metrics``
    reads, its module's ``METRIC_FIELDS`` (every field where a module
    declares none, as models/mixed), each copy started before the first is
    awaited.  Row ``i`` is then the finals' own state type with
    ``host[f][i]`` (a numpy view) in the fetched fields and None in the
    others, which is all ``sim_metrics`` asks for.  A committee final is
    stacked ``[B, C, ...]`` and takes the inner protocol's fields;
    topo/committee.metrics slices numpy per committee.

    A slice per leaf and row on the device, with ``metrics`` blocking on
    every read, is (leaves + fields) x rows round trips for the same bytes,
    and was half of a 32-lane dispatch at n=1024 on the chip (PERF.md
    section 6, PR 29).  The ``[N, W]`` tables never cross the host link.
    Padded lanes (a server bucket's or a mesh's duplicates of the last
    point) do, unread: cutting them off on the device is an eager program
    per leaf shape, compiled at the first partial bucket, inside a serving
    window.  The span's ``leaves`` and ``bytes`` attrs say what was
    fetched."""
    from blockchain_simulator_tpu.models import base as base_model

    picked = base_model.metric_leaves(cfg, finals)
    leaves = jax.tree.leaves(picked)
    with telemetry.span(
        "sweep.readback", rows=rows, lanes=leaves[0].shape[0],
        leaves=len(leaves), bytes=sum(x.nbytes for x in leaves),
    ):
        yield base_model.host_rows(finals, jax.device_get(picked), rows)
