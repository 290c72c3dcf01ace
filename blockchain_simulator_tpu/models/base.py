"""Protocol backend API.

The reference's plugin boundary is the per-protocol ``ns3::Application``
subclass, selected at *compile time* by editing network-helper.cc:17 and
blockchain-simulator.cc:72 (SURVEY.md §1).  Here a protocol backend is a
module-level triple of pure functions, selected at *runtime* by name:

- ``init(cfg, key) -> (state, bufs)``       — build the [N, ...] state pytree
  and the future-inbox ring buffers.
- ``step(cfg, state, bufs, t, tkey) -> (state, bufs)`` — one 1 ms tick for all
  N nodes at once (the tensorized equivalent of every event ns-3 would have
  dispatched in that interval: HandleRead FSM transitions + timer firings).
- ``metrics(cfg, state) -> dict``           — host-side structured metrics,
  reproducing the reference's NS_LOG measurement surface (SURVEY.md §5).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from blockchain_simulator_tpu.ops import mesh as mesh_ops


def get_protocol(name: str):
    """Runtime protocol selection (fixes the reference's compile-time switch)."""
    try:
        if name == "pbft":
            from blockchain_simulator_tpu.models import pbft as m
        elif name == "raft":
            from blockchain_simulator_tpu.models import raft as m
        elif name == "paxos":
            from blockchain_simulator_tpu.models import paxos as m
        elif name == "mixed":
            from blockchain_simulator_tpu.models import mixed as m
        else:
            raise ValueError(f"unknown protocol {name!r}")
    except ImportError as e:
        raise NotImplementedError(f"protocol backend {name!r} not available: {e}") from e
    return m


def sim_metrics(cfg, final) -> dict:
    """Host-side metrics for ONE final state, topology-aware: a committee
    final is a stacked [C, ...] pytree read as the two-level aggregate
    (topo/committee.py), any other is the flat protocol's own surface.  The
    one metrics door of runner, sweeps and server (none calls a protocol's
    ``metrics`` itself).  ``final`` is a lone device state, each read blocking,
    or a sweep row: host arrays in the protocol's ``METRIC_FIELDS``, all that
    ``metrics`` promises to read, None elsewhere (parallel/sweep._readback)."""
    if cfg.topology == "committee":
        from blockchain_simulator_tpu.topo import committee

        return committee.metrics(cfg, final)
    return get_protocol(cfg.protocol).metrics(cfg, final)


# the batch axis of the single-device vmapped programs (seed and fault sweeps,
# the server's buckets) and of the raft shards in models/mixed.step, bound by
# :func:`lane_vmap` alone.
LANES_AXIS = "lanes"

# the batch axis of the vmaps that cannot branch, bound by :func:`select_vmap`
# alone: the mesh arms' batches (parallel/sweep.py, obsim/build.py), whose
# per-lane predicate makes every cond a select of both arms.
SELECT_AXIS = "select_lanes"

# :func:`gated`'s own work under a lane batch, as a ``jax.named_scope`` (HLO
# metadata, ops/scopes.py): the predicate's lane reduction and the per-lane
# select of the taken arm.
GATE_SCOPE = "ops.gate.any_lane"
# the taken arm of :func:`gated_push`: the device events under it are the
# ticks on which a channel's push ran.
PUSH_SCOPE = "ops.gate.push_taken"
SCOPES = (GATE_SCOPE, PUSH_SCOPE)


def lane_vmap(fn):
    """``jax.vmap(fn)`` with the lane axis named, so that :func:`gated`,
    traced inside, can see that it runs under a lane batch."""
    return jax.vmap(fn, axis_name=LANES_AXIS)


def select_vmap(fn, **kwargs):
    """``jax.vmap(fn, **kwargs)`` for a batch whose conds lower to selects,
    named so that :func:`gated_push`, traced inside, keeps the ring out of
    them."""
    return jax.vmap(fn, axis_name=SELECT_AXIS, **kwargs)


def _under(axis_name: str) -> bool:
    try:
        jax.lax.axis_size(axis_name)
    except NameError:
        return False
    return True


def _any_lane(pred):
    with jax.named_scope(GATE_SCOPE):
        return jax.lax.pmax(pred.astype(jnp.int32), lane_axes()) > 0


def gated(pred, fn, zeros, axis=None):
    """Skip a delivery computation when no sender is active this tick.
    Sharded, the predicate must be globally agreed (the branch contains
    collectives), so it is pmax-reduced over the mesh axis first; a
    ``conditional`` is where a sharded arm's collectives live
    (:func:`gated_push` takes its contribution from here and loops over the
    push alone).

    Under a lane batch (:func:`lane_axes`) a cond on a per-lane predicate
    would lower to a select, both arms run for every lane on every tick.
    There the branch is taken on "any lane active", which is unbatched, so
    the cond stays a cond; inside the taken arm each lane keeps ``fn()``
    where its own predicate holds and ``zeros`` elsewhere, which is what the
    select gave.  A tick on which no lane is active touches nothing.

    ``zeros`` is an operand of that select and of the ``cond``, so it is
    never a ring: at the edge path's ``[lanes, D, N, W]`` one pass over a
    ring costs more than a whole tick, and XLA:TPU copies a ring that
    crosses a ``conditional``.  What is pushed into a ring goes through
    :func:`gated_push`, as every engine call site does."""
    if axis is not None:
        pred = mesh_ops.pmax(pred.astype(jnp.int32), axis) > 0
    if not lane_axes():
        return jax.lax.cond(pred, fn, lambda: zeros)
    any_lane = _any_lane(pred)

    def taken():
        out = fn()
        with jax.named_scope(GATE_SCOPE):
            return jax.tree.map(lambda o, z: jnp.where(pred, o, z), out, zeros)

    return jax.lax.cond(any_lane, taken, lambda: zeros)


def gated_push(pred, fn, zeros, bufs, push, axis=None):
    """``push(bufs, gated(pred, fn, zeros, axis))`` with the push inside the
    gate's branch: a tick with no sender leaves ``bufs`` (one ring or a
    tuple of rings) untouched, which is what pushing ``zeros`` (the identity
    of an add-ring, and of a max-ring whose empty value is 0) produced, and
    saves the read-modify-write of every delay bucket's slice.

    The branch is :func:`gated`'s: on ``pred``, reduced over the lanes under
    :func:`lane_vmap` and over the mesh under ``axis``, over both where both
    are bound.  The per-lane select
    stays on the contribution; the ring crosses the branch untouched and is
    never an operand of a select.  A call site whose
    push computes its own contribution (the stat arms' fused
    chain-into-ring) passes ``tuple`` and ``()``: there a lane without a
    sender must push zeros, as it does by drawing from zero counts.

    In a program sharded over ``axis`` the arm holds collectives, and those
    stay out of the loop: XLA:CPU runs a nested loop's collectives
    concurrently with the tick's own and aborts (KNOWN_ISSUES #0b').  So the
    helper is two stages on the one reduced predicate: the contribution
    comes out of :func:`gated`'s ``conditional``, as it always did (it
    yields ``zeros`` on a tick on which no shard sends), and the push,
    local slice arithmetic on a shard's own rows, runs in the loop with that
    contribution as the value the arm closes over.  What keeps the parent's
    form, a gated contribution and an unconditional push: a program under
    :func:`select_vmap`, where no branch survives, and a fused push under
    ``axis`` (``zeros == ()``), where push and collectives are one function
    that the helper cannot split.

    The branch is a ``while`` of at most one trip, not a ``cond``: XLA:TPU
    updates a ``while``'s carry in place, but around a ``conditional`` with
    a ring among its operands it copied whole rings on every tick (PERF.md
    section 6, PR 31: three ``[32, 152, 1024, 64]`` copies a tick).  A
    ``while`` body has a hazard of its own, which the carry below answers:
    whatever in it does not depend on the carry is hoisted out of the loop,
    and so runs on every tick."""
    separate = bool(jax.tree.leaves(zeros))
    if _under(SELECT_AXIS) or (axis is not None and not separate):
        # the parent's programs: a gated contribution and an unconditional
        # push, or the fused push behind the gate with its ring as ``zeros``
        if separate:
            return push(bufs, gated(pred, fn, zeros, axis))
        return gated(pred, lambda: push(bufs, fn()), bufs, axis)
    if axis is not None:
        # two stages on one globally agreed predicate: the contribution, with
        # the arm's collectives, out of the conditional it was always in; the
        # push, which has none, in the loop below
        pred = mesh_ops.pmax(pred.astype(jnp.int32), axis) > 0
        contrib = gated(pred, fn, zeros)
        return gated_push(pred, lambda: contrib, zeros, bufs, push)
    lanes = lane_axes()
    any_lane = _any_lane(pred) if lanes else pred

    def taken(bufs):
        with jax.named_scope(PUSH_SCOPE):
            out = fn()
            if lanes:
                with jax.named_scope(GATE_SCOPE):
                    out = jax.tree.map(
                        lambda o, z: jnp.where(pred, o, z), out, zeros)
            return push(bufs, out)

    return _one_trip(any_lane, taken, bufs)


def _one_trip(go, taken, carry):
    """``taken(carry)`` if ``go`` (an unbatched bool) else ``carry``, as a
    ``while`` of at most one trip whose carry XLA:TPU updates in place."""
    trips = go.astype(jnp.int32)
    # everything the arm closes over rides the loop's carry, behind a barrier
    # with the trip counter: loop-invariant code motion would otherwise lift
    # the whole arm but its last adds into the tick (measured: the samplers of
    # mixed256x1k.solo ran on every tick, 75.1 -> 14.0 rounds/s)
    arm = jax.make_jaxpr(taken)(carry)
    consts = [jnp.asarray(c) for c in arm.consts]
    treedef = jax.tree.structure(carry)

    def body(c):
        i, consts, leaves = c
        i, consts = jax.lax.optimization_barrier((i, consts))
        leaves = jax.core.eval_jaxpr(arm.jaxpr, consts, *leaves)
        return i + 1, consts, leaves

    leaves = jax.lax.while_loop(
        lambda c: c[0] < trips, body,
        (jnp.int32(0), consts, jax.tree.leaves(carry)))[2]
    return jax.tree.unflatten(treedef, leaves)


def can_branch(axis=None) -> bool:
    """Whether a program traced here can branch around a body that holds
    rings and collectives-free state: not under a mesh ``axis`` (the body's
    collectives may not sit in a nested loop, :func:`gated_push`) and not
    under :func:`select_vmap` (no branch survives)."""
    return axis is None and not _under(SELECT_AXIS)


def gated_body(pred, body, carry, scope):
    """``body(carry)`` on the ticks on which ``pred`` holds, else ``carry``
    untouched, for a ``body`` that is the identity on a lane whose ``pred``
    is false: under :func:`lane_vmap` the branch is taken on "any lane
    active" and no per-lane select is needed.  The same ``while`` of at most
    one trip as :func:`gated_push`'s, for its reasons, around a whole tick
    body; ``scope`` names the taken trip (a ``jax.named_scope``).  Only
    where :func:`can_branch` holds."""
    def taken(carry):
        with jax.named_scope(scope):
            return body(carry)

    return _one_trip(_any_lane(pred) if lane_axes() else pred, taken,
                     carry)


def fault_masks(cfg, n: int):
    """(alive[N], honest[N]) bool masks from the fault config.

    Crashed nodes occupy the last ``n_crashed`` ids, Byzantine the last
    ``n_byzantine`` alive ids before them — so node 0 (PBFT initial leader,
    Paxos proposer) stays honest/alive under small fault counts."""
    f = cfg.faults
    nc = f.resolved_n_crashed(n)
    ids = np.arange(n)
    alive = ids < (n - nc)
    honest = ids < (n - nc - f.n_byzantine)
    return jnp.asarray(alive), jnp.asarray(honest)


def dyn_fault_masks(n: int, n_crashed, n_byzantine):
    """:func:`fault_masks` with the counts as TRACED operands.

    Same id layout (crashed = last ids, Byzantine = last alive ids before
    them), same int comparisons — bit-identical to the static masks at equal
    counts — but ``n_crashed`` / ``n_byzantine`` are scalar arrays, so one
    compiled program serves every fault level of a sweep
    (runner.make_dyn_sim_fn / parallel/sweep.py)."""
    ids = jnp.arange(n)
    nc = jnp.asarray(n_crashed, jnp.int32)
    nb = jnp.asarray(n_byzantine, jnp.int32)
    alive = ids < (n - nc)
    honest = ids < (n - nc - nb)
    return alive, honest


def canonical_fault_cfg(cfg):
    """The ONE static config whose dynamic-operand trace serves every
    (n_crashed, n_byzantine) point of a count sweep: counts zeroed to the
    FaultConfig defaults so every sweep over the same fault *structure*
    (drop_prob, byz_forge, byz_copies) shares one registry key.  ``seed``
    is normalized too — it never enters the trace (the PRNG key is a
    per-lane operand), so scenario requests and sweeps differing only in
    seed must share one executable (the serve/ batch-group contract).

    ``byz_forge`` keeps a static ``n_byzantine=1`` sentinel: pbft.step
    includes the forge wave in the trace only when the static count is
    positive, and the wave is driven by the traced ``alive & ~honest``
    forger mask — all-false at a dynamic f=0, where adding zero forged
    votes is bit-identical to the static f=0 program that omits the wave
    (the forge block consumes no PRNG keys)."""
    import dataclasses

    f = cfg.faults
    return cfg.with_(
        seed=0,
        faults=dataclasses.replace(
            f,
            crash_frac=0.0,
            n_crashed=-1,
            n_byzantine=1 if f.byz_forge else 0,
        )
    )


def apply_fault_masks(cfg, state, alive, honest):
    """Install traced fault masks into a state freshly init'd at the
    canonical (fault-free) config — bit-equal to ``init`` at the static
    config with those counts.

    Every protocol carries the masks as plain ``alive``/``honest`` state
    fields; raft additionally derives its initial election schedule from
    them (crashed nodes never start an election, models/raft.py init), so
    the disarm is re-applied here against the traced mask.  The mixed shard
    sim distributes faults per shard at init and is NOT supported
    (runner.make_dyn_sim_fn refuses it)."""
    state = state.replace(alive=alive, honest=honest)
    if cfg.protocol == "raft":
        from blockchain_simulator_tpu.models.raft import DISARM

        state = state.replace(
            election_deadline=jnp.where(alive, state.election_deadline, DISARM)
        )
    return state


# ---- below: added at the file's end, so that no line above moves (the
# compile cache keys on the source lines of traced code, ROADMAP D11) ----

# the batch axis of one tile of a committee stack (topo/committee.py), bound
# by :func:`tile_vmap` alone.  A sweep or a served bucket over a committee
# configuration binds BOTH: ``lane_vmap`` around a body that is itself a lane
# batch.  A second ``vmap`` under the first's name would shadow it (a
# reduction then sees the inner batch alone, the predicate stays batched over
# the outer one and every cond is a select again), so the tile has a name of
# its own and :func:`gated`'s "any lane active" is reduced over every lane
# axis that is bound: B x T lanes, one branch.
TILE_AXIS = "tile_lanes"
LANE_AXES = (LANES_AXIS, TILE_AXIS)


def tile_vmap(fn):
    """``jax.vmap(fn)`` over the committees of one tile of a committee stack:
    a lane batch as :func:`lane_vmap`'s, under :data:`TILE_AXIS`."""
    return jax.vmap(fn, axis_name=TILE_AXIS)


def lane_axes() -> tuple:
    """The lane axes bound where this is traced (:data:`LANE_AXES`:
    :func:`lane_vmap`'s, :func:`tile_vmap`'s, or one around the other): empty
    in a lone program.  What :func:`gated`, :func:`gated_push` and
    :func:`gated_body` reduce "any lane active" over."""
    return tuple(a for a in LANE_AXES if _under(a))


def metric_leaves(cfg, finals) -> dict:
    """The leaves of a (stacked or batched) final state that the protocol's
    ``metrics`` reads, by field: its module's ``METRIC_FIELDS`` (every field
    where a module declares none, as models/mixed), those that are there."""
    import dataclasses

    names = [f.name for f in dataclasses.fields(finals)]
    fields = getattr(get_protocol(cfg.protocol), "METRIC_FIELDS", names)
    return {f: getattr(finals, f) for f in fields
            if getattr(finals, f) is not None}


def host_rows(finals, host: dict, rows: int) -> list:
    """The first ``rows`` entries of a final state's leading axis as states
    of the finals' own type, from the fetched leaves ``host`` (a
    :func:`metric_leaves` dict after ONE ``jax.device_get``): ``host[f][i]``
    (a numpy view) in the fetched fields and None in the others, which is
    all ``metrics`` asks for."""
    import dataclasses

    return [
        type(finals)(**{
            f.name: jax.tree.map(lambda x: x[i], host[f.name])
            if f.name in host else None
            for f in dataclasses.fields(finals)
        })
        for i in range(rows)
    ]


def host_final(cfg, final, picked: dict):
    """A LONE final state (no lane axis) as :func:`sim_metrics` takes it,
    from ONE fetch: ``picked`` (its :func:`metric_leaves`) crosses the host
    link in one ``jax.device_get``, each copy started before the first is
    awaited.  Returns the final's own state type with host arrays in the
    fetched fields and None in the others, which is all ``metrics`` asks for
    (parallel/shard.readback's return, :func:`host_rows` without the rows).
    A ``metrics`` call on the device state is one blocking read per leaf
    for the same bytes.  A committee stack is returned as it is:
    topo/committee.metrics makes that one fetch itself."""
    import dataclasses

    if cfg.topology == "committee":
        return final
    host = jax.device_get(picked)
    return type(final)(**{f.name: host.get(f.name)
                          for f in dataclasses.fields(final)})
