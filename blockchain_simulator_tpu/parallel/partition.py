"""Partition rules: the single place meshes meet executables.

Every mesh-partitioned program in the repo — the node-sharded sim wrappers
(parallel/shard.py), the mesh-partitioned fault sweeps (parallel/sweep.py)
and the scenario server's mesh-sharded batched dispatch (serve/dispatch.py)
— goes through one of two doors here:

- :func:`match_partition_rules` turns a declaration of ``(regex path
  pattern, PartitionSpec)`` rules into a full PartitionSpec pytree for any
  state/buffer tree (the SNIPPETS.md [2] pattern): first ``re.search``
  match on the ``/``-joined key path wins, scalar leaves are never
  partitioned, specs are rank-padded so ``shard_map`` sees full-rank specs.
- :func:`partition` compiles a function against a mesh (the SNIPPETS.md
  [3] pattern): explicit global-view shardings prefer **pjit** (``jax.jit``
  with ``NamedSharding``s — XLA GSPMD partitions the internals), per-shard
  specs fall back to a **shard_map-wrapped jit** (map-style named-axis
  collectives, the sim wrappers' delivery ops), and a **mesh of size 1
  degenerates** to a plain ``jax.jit`` under the mesh context so the
  compiled program is bit-identical to the unpartitioned one.

Why the sweep path partitions the BATCH axis with shard_map rather than
vmap sharding: a vmapped sim lowers its dynamic-update-slice pushes to
scatter (lint/graph found them; XLA:CPU serializes scatter —
KNOWN_ISSUES.md #0b), so the per-device body here is a ``lax.map`` of the
UNVMAPPED program — plain DUS, no batch lockstep.  Measured on the
8-virtual-device CPU mesh (tools/mesh_sweep_bench.py): ~2.3x per lane over
the single-device vmapped sweep program at 10k nodes, rows bit-equal under
the exact sampler.  On a real TPU mesh the devices additionally run in
parallel; on the 1-core CPU box the win is purely the scatter-free body.

Registry contract: partitioned executables live in the unified registry
(utils/aotcache.py) keyed on ``(factory, cfg, mesh)`` — the mesh IS part of
the key, so a mesh-sharded entry never collides with the single-device one
and the one-executable-per-fault-structure sweep contract survives per
mesh (tests/test_zzpartition.py pins it).
"""

from __future__ import annotations

import functools
import re

import numpy as np

REPLICATED = None  # sentinel alias: a rule spec of None means "replicate"

# The three compilation arms of :func:`partition`, as stable strings: every
# partitioned callable is tagged with ``partition_arm`` / ``partition_mesh``
# attributes (see _tag_arm) so the comms auditor (lint/comms) can report
# WHICH door a program went through without re-deriving the dispatch.
PJIT_ARM = "pjit"              # explicit shardings; XLA GSPMD partitions
SHARD_MAP_ARM = "shard_map"    # per-shard specs; map-style collectives
SINGLE_DEVICE_ARM = "single"   # size-1 mesh degenerate: plain jit


def _spec_cls():
    from jax.sharding import PartitionSpec

    return PartitionSpec


def mesh_size(mesh) -> int:
    """Total device count of a mesh."""
    return int(np.asarray(mesh.devices).size)


def path_name(path) -> str:
    """``/``-joined name of a pytree key path (dict keys, dataclass/struct
    field names, sequence indices)."""
    parts = []
    for p in path:
        if hasattr(p, "name"):
            parts.append(str(p.name))
        elif hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def match_partition_rules(rules, tree):
    """PartitionSpec pytree for ``tree`` from ``((regex, spec), ...)`` rules.

    The first rule whose pattern ``re.search``-matches the leaf's
    ``/``-joined path wins; its spec (a ``PartitionSpec`` or
    :data:`REPLICATED`) is padded with ``None`` to the leaf's rank, so
    ``P(NODES_AXIS)`` declares "shard dim 0, replicate the rest" for any
    rank.  Scalar (0-d or size-1) leaves are never partitioned.  A
    non-scalar leaf matching no rule raises — silent replication is how
    sharding bugs hide (SNIPPETS.md [2] raises the same way).
    """
    import jax

    P = _spec_cls()
    compiled = [(re.compile(pat), spec) for pat, spec in rules]

    def spec_for(path, leaf):
        shape = getattr(leaf, "shape", ())
        ndim = len(shape)
        if ndim == 0 or int(np.prod(shape)) == 1:
            return P()
        name = path_name(path)
        for pat, spec in compiled:
            if pat.search(name) is None:
                continue
            entries = tuple(spec) if spec is not None else ()
            if len(entries) > ndim:
                raise ValueError(
                    f"partition rule {pat.pattern!r} spec {spec} has "
                    f"{len(entries)} entries for rank-{ndim} leaf {name!r}"
                )
            return P(*entries, *([None] * (ndim - len(entries))))
        raise ValueError(f"no partition rule matched leaf {name!r} "
                         f"(shape {tuple(shape)})")

    return jax.tree_util.tree_map_with_path(spec_for, tree)


def mesh_tag(mesh) -> str:
    """Stable mesh descriptor for program/budget keys: axis names and
    sizes with size-1 axes elided (``"sweep2_nodes4"``), ``"single"`` for
    a 1-device mesh.  The comms baseline (COMMS_BASELINE.json) keys every
    budget on ``program@tag`` so a 2-device audit pin never collides with
    a 4-device one."""
    parts = [
        f"{name}{size}"
        for name, size in mesh_shape_dict(mesh).items() if int(size) > 1
    ]
    return "_".join(parts) if parts else "single"


def _tag_arm(fn, mesh, arm):
    """Best-effort arm/mesh metadata on a partitioned callable (jit
    wrappers accept attributes on this jax; a C-level wrapper that refuses
    just stays untagged — the metadata is advisory, never load-bearing)."""
    try:
        fn.partition_arm = arm
        fn.partition_mesh = mesh_shape_dict(mesh)
    except (AttributeError, TypeError):
        pass
    return fn


def _shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with replication checking waived: delivery ops mix
    gathered (unreplicated) and replicated values; correctness is covered
    by the sharded-vs-unsharded equivalence tests."""
    import jax

    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def _named_shardings(mesh, specs):
    """PartitionSpec pytree -> NamedSharding pytree over ``mesh`` (specs
    are the pytree leaves: a bare spec broadcasts as a jit prefix)."""
    import jax
    from jax.sharding import NamedSharding

    P = _spec_cls()
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda s: isinstance(s, P),
    )


def partition(fn, mesh, *, in_shardings=None, out_shardings=None,
              in_specs=None, out_specs=None, wrap_jit=True):
    """Compile ``fn`` against ``mesh`` — the one mesh↔executable door.

    Exactly one style may be given (the SNIPPETS.md [3] selection rule):

    - ``in_shardings``/``out_shardings`` (PartitionSpec pytrees, *global*
      view): explicit shardings are honoured via pjit — ``jax.jit`` with
      ``NamedSharding``s; XLA GSPMD partitions the function body.  Both
      must be given, or neither makes sense to honour.  A mesh of size 1
      degenerates to plain ``jax.jit(fn)`` run under the mesh context —
      bit-identical to the unpartitioned program (pinned in
      tests/test_zzpartition.py).
    - ``in_specs``/``out_specs`` (PartitionSpec pytrees, *per-shard*
      view): the shard_map-wrapped-jit fallback — map-style collectives
      over the mesh axis names (``psum``/``all_gather`` in
      ops/delivery.py).  Size-1 meshes keep the shard_map wrapper: the
      body's axis names must stay bound (a 1-device ``psum`` is identity).
      ``wrap_jit=False`` returns the bare shard_map-wrapped function for
      callers that embed it in a larger jitted program (the shard.py sim
      wrappers init state under their own jit) — the traced IR is then
      identical to a hand-rolled shard_map call.
    """
    explicit = in_shardings is not None or out_shardings is not None
    mapped = in_specs is not None or out_specs is not None
    if explicit and mapped:
        raise ValueError(
            "partition() takes either explicit shardings (pjit) or "
            "per-shard specs (shard_map), not both"
        )
    if explicit:
        if in_shardings is None or out_shardings is None:
            raise ValueError(
                "partition() requires both in_shardings and out_shardings "
                "when using explicit-sharding pjit; pass in_specs/out_specs "
                "for the shard_map fallback instead"
            )
        if not wrap_jit:
            raise ValueError(
                "wrap_jit=False is a shard_map-path option: jit IS the "
                "pjit mechanism for explicit shardings"
            )
        import jax

        # per-call jit is this layer's JOB: every caller is itself a
        # @cached_factory factory (shard.py wrappers, sweep.mesh_dyn_
        # batched_fn), so the registry memoizes the wrapper one level up —
        # the same sanctioning the rule grants those factories directly
        if mesh_size(mesh) == 1:
            @functools.wraps(fn)
            def single_device_fn(*args):
                with mesh:
                    return fn(*args)

            return _tag_arm(jax.jit(single_device_fn), mesh,  # jaxlint: disable=static-arg-recompile-hazard
                            SINGLE_DEVICE_ARM)
        return _tag_arm(
            jax.jit(  # jaxlint: disable=static-arg-recompile-hazard
                fn,
                in_shardings=_named_shardings(mesh, in_shardings),
                out_shardings=_named_shardings(mesh, out_shardings),
            ),
            mesh, PJIT_ARM,
        )
    if not mapped:
        raise ValueError(
            "partition() needs in_shardings/out_shardings (pjit) or "
            "in_specs/out_specs (shard_map)"
        )
    shmapped = _shard_map(fn, mesh, in_specs, out_specs)
    if not wrap_jit:
        return _tag_arm(shmapped, mesh, SHARD_MAP_ARM)
    import jax

    # cached one level up, same as the explicit-sharding arm above
    return _tag_arm(jax.jit(shmapped), mesh, SHARD_MAP_ARM)  # jaxlint: disable=static-arg-recompile-hazard


# ----------------------------------------------------- node-dim rule sets ---


def node_dim_rules(replicated_names=()):
    """``((regex, spec), ...)`` declaring: the named leaves replicate,
    every other non-scalar leaf shards dim 0 over the nodes axis.

    The one rule shape every node-dim consumer shares
    (:func:`match_partition_rules` turns it into full specs per tree):
    the sharded sim wrappers' per-node state (parallel/shard.state_rules
    passes the protocol's ``GLOBAL_FIELDS``), the kregular ``[N, K]``
    overlay-table operands and unbatched ``[N, ...]`` finals, and the
    committee path's ``[C, ...]`` stacked finals (dim 0 is the committee
    axis — the hierarchy's node-dim analog) in
    parallel/sweep.sharded_topo_sim_fn."""
    from blockchain_simulator_tpu.parallel.mesh import NODES_AXIS

    P = _spec_cls()
    rules = tuple(
        (rf"(^|/){re.escape(name)}$", REPLICATED)
        for name in replicated_names
    )
    return rules + ((r".*", P(NODES_AXIS)),)


# ------------------------------------------- shard-local neighbor exchange ---


class NeighborExchange:
    """Owner-bucketed cross-shard neighbor reads — the runtime half of
    ``topo.spec.owner_bucket_plan``.

    ``xg(x, kind="in")`` computes exactly ``jnp.take(x, table, axis=0)``
    for the kind's ``[N, K]`` overlay table, and ``xg(x, kind=..., col=c)``
    exactly ``jnp.take(x.reshape(-1), table * x.shape[1] + c)`` — but the
    only communication is ONE ``all_to_all`` of the static ``[D, C, ...]``
    owner buckets per call under ``shard_map``: no operand, intermediate,
    or gather result is ever materialized at global shape on any device.
    The result is a pure permutation + local gather of ``x``'s rows, so it
    is bit-equal to the global gather by construction (pinned in
    tests/test_zzexchange.py at mesh sizes 1/2/4/8).

    The plan arrays (``pos``/``send`` per table kind) are PROGRAM OPERANDS
    (traced, ``P(nodes)``-sharded), not constants: they ride the compiled
    program next to the table operands (sweep.sharded_topo_sim_fn), so the
    executable stays one-per-fault-structure and carries no O(N) consts
    (the <64KB jaxpr-consts pin in tests/test_zzshardtopo.py).
    """

    def __init__(self, mesh, n: int, plans: dict):
        if not plans:
            raise ValueError("NeighborExchange needs at least one plan")
        self.mesh = mesh
        self.n = int(n)
        self.plans = dict(plans)
        pos, send = next(iter(self.plans.values()))
        self.n_shards = int(send.shape[0])
        self.n_pad = int(pos.shape[0])

    def _pad(self, a):
        import jax.numpy as jnp

        pad = self.n_pad - int(a.shape[0])
        if pad:
            a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a

    def __call__(self, x, kind: str = "in", col=None):
        import jax.numpy as jnp
        from jax import lax

        from blockchain_simulator_tpu.parallel.mesh import NODES_AXIS

        pos, send = self.plans[kind]
        d, c = int(send.shape[0]), int(send.shape[2])
        P = _spec_cls()
        sliced = self.n_pad - int(x.shape[0])
        x = self._pad(x)

        if col is None:
            def body(x_loc, pos_loc, send_loc):
                sb = jnp.take(x_loc, send_loc[0], axis=0)     # [D, C, ...]
                rb = lax.all_to_all(sb, NODES_AXIS,
                                    split_axis=0, concat_axis=0)
                flat = rb.reshape((d * c,) + rb.shape[2:])
                return jnp.take(flat, pos_loc, axis=0)        # [n_loc, K, .]
            out = _shard_map(
                body, self.mesh,
                (P(NODES_AXIS), P(NODES_AXIS), P(NODES_AXIS)),
                P(NODES_AXIS),
            )(x, pos, send)
        else:
            w = int(x.shape[1])
            col = self._pad(col)

            def body(x_loc, pos_loc, send_loc, col_loc):
                sb = jnp.take(x_loc, send_loc[0], axis=0)     # [D, C, w]
                rb = lax.all_to_all(sb, NODES_AXIS,
                                    split_axis=0, concat_axis=0)
                flat = rb.reshape((d * c * w,))
                return jnp.take(flat, pos_loc * w + col_loc, axis=0)
            out = _shard_map(
                body, self.mesh,
                (P(NODES_AXIS), P(NODES_AXIS), P(NODES_AXIS), P(NODES_AXIS)),
                P(NODES_AXIS),
            )(x, pos, send, col)
        return out[: self.n] if sliced else out


class ExchangeSpec:
    """Static description of the exchange-plan operand block a sharded
    kregular program appends after its table operands: ``(pos, send)`` per
    table kind, in ``kinds`` order.  The factory (sweep.sharded_topo_sim_fn)
    builds the plan arrays once per executable from the PADDED tables
    (topo.spec.owner_bucket_plan) and threads this spec through
    runner.make_topo_dyn_sim_fn so the traced sim can rebind them into a
    :class:`NeighborExchange` at trace time."""

    def __init__(self, mesh, n: int, kinds=("in", "out")):
        self.mesh = mesh
        self.n = int(n)
        self.kinds = tuple(kinds)

    @property
    def n_operands(self) -> int:
        return 2 * len(self.kinds)

    def build(self, *plan_operands) -> NeighborExchange:
        if len(plan_operands) != self.n_operands:
            raise ValueError(
                f"ExchangeSpec.build: expected {self.n_operands} plan "
                f"operands ({'/'.join(self.kinds)} pos+send), got "
                f"{len(plan_operands)}"
            )
        plans = {
            k: (plan_operands[2 * i], plan_operands[2 * i + 1])
            for i, k in enumerate(self.kinds)
        }
        return NeighborExchange(self.mesh, self.n, plans)


# ----------------------------------------------------- mesh-sweep helpers ---


def sweep_axis_size(mesh) -> int:
    """Size of the mesh's sweep axis (0 when the mesh has none)."""
    from blockchain_simulator_tpu.parallel.mesh import SWEEP_AXIS

    return int(dict(mesh.shape).get(SWEEP_AXIS, 0))


def seq_map(fn):
    """``batched(*operands) -> finals`` running the batch SEQUENTIALLY
    through the UNVMAPPED ``fn`` via ``lax.map`` — the scatter-free batch
    body (KNOWN_ISSUES #0i): per-lane dynamic-update-slice pushes stay
    plain DUS instead of vmap's DUS→scatter lowering, which XLA:CPU
    serializes, and each lane is a batch-1-shaped program (the only shape
    ever observed to work on the TPU, issue #2).  Shared by the
    mesh-partitioned sweep's per-device body (sweep.mesh_dyn_batched_fn)
    and the single-device multi-seed tick executable
    (sweep.multi_seed_fn) so the two arms stay one mechanism."""
    import jax

    def batched(*operands):
        return jax.lax.map(lambda args: fn(*args), operands)

    return batched


def pad_points(points, lanes: int):
    """Pad ``points`` (any list) to a multiple of ``lanes`` by repeating the
    last element — the uneven-grid lanes of a mesh dispatch (a padded lane
    costs one discarded per-device map step, same trade as serve's bucket
    padding).  Returns ``(padded, n_real)``."""
    points = list(points)
    if not points:
        raise ValueError("pad_points needs at least one point")
    n_real = len(points)
    rem = n_real % lanes
    if rem:
        points = points + [points[-1]] * (lanes - rem)
    return points, n_real


def align_chunk(chunk_size: int, lanes: int) -> int:
    """Round a journal chunk size up to a multiple of the mesh's sweep
    lanes (parallel/journal.py chunking): every chunk then pads at most
    one partial tile through :func:`pad_points`, instead of every chunk
    paying ``lanes - (size % lanes)`` discarded lanes."""
    chunk_size = max(1, int(chunk_size))
    lanes = max(1, int(lanes))
    rem = chunk_size % lanes
    return chunk_size + (lanes - rem if rem else 0)


def mesh_shape_dict(mesh) -> dict:
    """``{axis name: size}`` of a mesh as plain JSON-able types — the one
    serialization every mesh-reporting surface shares (serve batch blocks,
    /stats, the daemon READY line)."""
    return {str(k): int(v) for k, v in dict(mesh.shape).items()}


def batched_out_shardings(cfg, mesh, out_avals):
    """Global-view out specs for a vmapped ``[B, ...]`` final-state pytree:
    batch dim over the sweep axis; when the mesh also has >1 node shards,
    a leaf whose dim 1 is ``cfg.n``-sized rides the nodes axis there (the
    "node axis optionally sharded for large n" option — GSPMD propagates
    the constraint into the scan).  Only dim 1 is considered: node state
    is ``[N, ...]`` by repo convention (shard.py), and shape-matching
    deeper dims could tag a same-sized non-node dim (e.g. a slot table at
    ``pbft_max_slots == n``) — such leaves just stay replicated.

    Topology rule (topo/): the committee path's finals are stacked
    ``[B, C, m, ...]`` (topo/committee.py) — there dim 1 is the COMMITTEE
    axis, the node-dim analog of the hierarchy, and it rides the nodes
    axis when it divides evenly; kregular finals keep the flat ``[B, N,
    ...]`` shape and the same dim-1 rule applies.  The UNBATCHED topo
    programs (sweep.sharded_topo_sim_fn) don't come through here: their
    node dim is dim 0 and their overlay tables are real operands —
    :func:`node_dim_rules` declares those."""
    import jax

    from blockchain_simulator_tpu.parallel.mesh import NODES_AXIS, SWEEP_AXIS

    P = _spec_cls()
    n_nodes = int(dict(mesh.shape).get(NODES_AXIS, 1))
    sweep = SWEEP_AXIS if sweep_axis_size(mesh) > 1 else None
    node_dim = (cfg.committees if cfg.topology == "committee" else cfg.n)

    def leaf_spec(leaf):
        shape = tuple(getattr(leaf, "shape", ()))
        entries = [sweep]
        for i, d in enumerate(shape[1:]):
            if (i == 0 and n_nodes > 1 and d == node_dim
                    and node_dim % n_nodes == 0):
                entries.append(NODES_AXIS)
            else:
                entries.append(None)
        return P(*entries)

    return jax.tree.map(leaf_spec, out_avals)
