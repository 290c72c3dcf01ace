"""Two-level committee consensus (``topology="committee"``).

The hierarchy the scalable-BFT line runs in practice (PAPERS.md
2007.12637): N nodes split into ``cfg.committees`` equal committees of
m = N/C nodes; the FLAT protocol runs to quorum INSIDE each committee
(node c*m is that committee's node 0 — pbft initial leader / paxos
proposer lane 0), and an outer aggregate step over the committee
representatives declares the hierarchy's outcome once an outer quorum
(majority of committees) reports its inner milestone.

Execution shape: **tiles of lanes**.  A committee is a lane: its own key,
its own ``[m]`` fault masks, the same dyn program.  The stack runs the inner
tick engine over T committees at once under ``models/base.tile_vmap`` (a lane
batch: ``gated`` and the tick gates stay branches on "any lane active",
as under the sweeps' ``lane_vmap``), tile after tile under one ``lax.map``
inside the one executable.  T is :func:`tile_plan`'s: the rule that cuts a
sweep's point list to the device (parallel/sweep._device_tile: the memory
the device reports over twice a lane's state) applied to the committees, so
200 committees of 500 (58 MB of rings each, 11.7 GB a stack) run as 2 tiles
of 100 on a 16 GB chip, and a stack that fits runs as one tile.  A tail tile
is padded by repeating the last committee; the padding's finals are cut
off.  Per-tick memory is O(T * f(m)) where f is the inner engine's footprint
(edge mode: O(T*m*m) instead of O(N^2) — the committee-size memory lever).
Where nothing can branch (``models/base.can_branch``: under ``select_vmap``,
the mesh sweep arms) T is 1 and the tile is the lone, unbatched engine, one
committee after another: a lane batch would there pay T committees' memory
for selects.  T is 1 too where the device reports no memory (XLA:CPU):
nothing then says how many committees fit beside their temporaries, and one
committee after another is the form that ran there at every size (200 x 500
as one tile of 200 lanes took 50 GB of host memory).
Every committee's final state is bit-equal to the flat dyn program run with
that committee's key and masks, whatever T (tests/test_zzcommittee.py).

Fault layout: masks keep the global last-ids rule (models/base.dyn_fault_masks
over the FULL id space, reshaped [C, m]), so fault counts concentrate in the
tail committees; counts stay traced operands: ONE executable per (protocol,
committee structure).  A crash schedule (FaultConfig.crashes) is a lane's own:
its phase is drawn in the lane's ``init`` from the lane's key (models/raft.py).

One-committee contract (the pin in tests/test_zztopo.py): at C = 1 the
committee keys ARE the flat sim's key stream and the body IS the flat
dyn program, so the merged metrics dict contains the flat protocol's
metrics bit for bit, and the outer step adds zero latency (a single
representative has nobody to exchange with).

The outer aggregate is deterministic modeling, not a second simulated
consensus: representatives report their committee's inner milestone, and
the outer commit lands at the outer-quorum-th milestone plus one
worst-case representative round trip (``2*(one_way_hi - 1)``; 0 at
C = 1).  A simulated outer instance over the C representatives is the
natural extension (ROADMAP item 3 note).

Metrics come from ONE readback: :func:`metrics` fetches the stacked leaves
the inner protocol's ``metrics`` reads (its ``METRIC_FIELDS``) in one
``jax.device_get`` and computes every committee's dict from numpy views.

Names in a profiler trace (``SCOPES``, ``SPANS``, ``COUNTERS``): scope
``topo.committee.stack`` around the whole stacked program and
``topo.committee.tile`` around one tile's scan, with ``pbft.tick.*``,
``ops.*`` and ``gate.*`` nested inside under their own names; host spans
``topo.committee.readback`` (the one fetch) and ``topo.committee.outer``
(per-committee metrics and the outer aggregate); counters
``committee.tiles`` and ``committee.tile_lanes`` (tiles run, and lanes run
in them, padding included, by the lone stacks whose metrics were read: their
quotient is T; a stack of Raft groups with terms also counts them,
``telemetry.RAFT_COUNTERS``).  Both, and the readback span's ``tiles`` / ``tile_lanes``,
are the plan :func:`stacked_body` took where that stack was traced
(:func:`ran_as`), never the rule asked again.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from blockchain_simulator_tpu.models import base as base_model
from blockchain_simulator_tpu.ops.scopes import scoped
from blockchain_simulator_tpu.utils import prng, telemetry

_names: list[str] = []
_scoped = scoped("topo.committee", _names)

# the host spans and counters of :func:`metrics` (utils/telemetry.py)
SPANS = ("topo.committee.readback", "topo.committee.outer")
COUNTERS = ("committee.tiles", "committee.tile_lanes")

# What ``metrics`` reports for C > 1 beyond the outer aggregate, that a
# caller needs to count work and to be held to a plain reference
# (benchmark/reference/committee_engine.py yields the same keys; a caller
# that holds a run to it asks for this tuple first): the per-committee
# milestone list, the outer commit and its round trip, and ``per_committee``:
# every scalar of the inner protocol's own metrics dict as a list of C.
MILESTONES = ("inner_milestones_ms", "outer_round_ms", "outer_commit_ms",
              "per_committee")


def inner_cfg(cfg):
    """The flat per-committee config: n = committee size, full mesh inside
    the committee; everything else (protocol knobs, delivery, samplers,
    fault structure) inherits."""
    return cfg.with_(n=cfg.n // cfg.committees, topology="full")


def _committee_keys(key, c: int):
    """[C] stacked per-committee base keys.  C = 1 keeps the caller's key
    verbatim (the flat-protocol contract); C > 1 folds the committee index
    so committee streams decorrelate."""
    if c == 1:
        return key[None]
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(c))


def tile_plan(icfg, c: int, outer: int = 1) -> dict:
    """How a stack of ``c`` committees of the inner configuration ``icfg``
    runs where this is traced: ``{"lanes": T, "tiles": n}``, ``n * T >= c``.
    T is the sweeps' tile rule (parallel/sweep._device_tile) over the
    committees, with ``outer`` lanes of a batch around the stack each
    holding a tile of its own; 1 where nothing can branch, and where the
    device reports no memory to cut by."""
    from blockchain_simulator_tpu.parallel import sweep

    if (c == 1 or not base_model.can_branch()
            or sweep._device_bytes() is None):
        return {"lanes": 1, "tiles": c}
    cut = sweep._device_tile(icfg, c, outer)
    lanes = c if cut is None else cut["lanes"]
    return {"lanes": lanes, "tiles": -(-c // lanes)}


@_scoped
def tile(run, args):
    """One tile's scan: ``run`` over the tile's committees."""
    return run(args)


@_scoped
def stack(run, tiled):
    """The whole stacked program: tile after tile."""
    return jax.lax.map(lambda args: tile(run, args), tiled)


SCOPES = tuple(_names)

# What every stack traced in this process ran as, written by
# :func:`stacked_body` where it is traced: ``(cfg, committees traced, lanes
# of a batch around the stack, whether it could branch) -> tile_plan``.  The
# plan is baked into the executable at that moment; a second reckoning on
# the host could differ from it (another slice a device on the mesh arm,
# another batch around the stack).
_traced: dict = {}


def ran_as(cfg) -> dict | None:
    """The plan the LONE stack of ``cfg`` (``run_stacked`` with no batch and
    no mesh around it: ``run_simulation``'s path) was traced with in this
    process, None where none was."""
    return _traced.get((base_model.canonical_fault_cfg(cfg), cfg.committees,
                        1, True))


def stacked_body(cfg, keys, alive_cm, honest_cm, probe=None):
    """The committee batch body: the inner tick engine over whatever leading
    committee axis the inputs carry — ``keys [c']``, ``alive_cm/honest_cm
    [c', m]`` -> stacked final state ``[c', ...]`` — as :func:`tile_plan`'s
    tiles of lanes (module docstring).  Shared verbatim by
    :func:`run_stacked` (c' = C, one device) and the mesh arm
    (parallel/sweep.sharded_topo_sim_fn: shard_map hands each device its
    C/n_shards slice, cut to that device by the same rule — the body never
    needs to know, there is no cross-committee communication before the
    host-side outer aggregate in :func:`metrics`).  Under a lane batch of
    its own (a sweep or a served bucket over a committee configuration) the
    tile is cut for that batch's lanes and the gates reduce over both axes
    (models/base.LANE_AXES).

    ``probe`` (obsim/build.py, utils/trace.py) arms per-committee taps:
    a ``(sample_fn, finalize_fn)`` pair — ``sample_fn(icfg, state) ->
    {field: scalar}`` per tick, ``finalize_fn(icfg, final, series) ->
    pytree`` over the committee's per-tick series ``{field: [T]}``
    (identity for full traces, windowed reduction + monitors for obsim).
    The per-committee pytrees stack to leading-``[c', …]`` leaves; returns
    ``(finals, probes)``.  The state trajectory is bit-identical to the
    unprobed call (taps only read)."""
    proto = base_model.get_protocol(cfg.protocol)
    icfg = inner_cfg(cfg)
    sample_fn, finalize_fn = probe or (None, None)

    def body(args):
        kc, alive_c, honest_c = args
        state, bufs = proto.init(icfg, jax.random.fold_in(kc, 0x1217))
        state = base_model.apply_fault_masks(icfg, state, alive_c, honest_c)

        def tick(carry, t):
            st, bf = carry
            st, bf = proto.step(icfg, st, bf, t, prng.tick_key(kc, t))
            return (st, bf), (
                sample_fn(icfg, st) if sample_fn is not None else ()
            )

        (state, bufs), ys = jax.lax.scan(
            tick, (state, bufs), jnp.arange(icfg.ticks)
        )
        if probe is None:
            return state
        return state, finalize_fn(icfg, state, ys)

    c = keys.shape[0]
    outer = 1
    for axis in base_model.lane_axes():
        outer *= jax.lax.axis_size(axis)
    plan = tile_plan(icfg, c, outer)
    _traced[(cfg, c, outer, base_model.can_branch())] = plan
    lanes, tiles = plan["lanes"], plan["tiles"]
    operands = (keys, alive_cm, honest_cm)
    if lanes == 1:
        return stack(body, operands)
    pad = tiles * lanes - c
    if pad:  # the tail tile's spare lanes repeat the last committee
        operands = jax.tree.map(
            lambda x: jnp.concatenate([x, jnp.repeat(x[-1:], pad, 0)]),
            operands)
    tiled = jax.tree.map(
        lambda x: x.reshape((tiles, lanes) + x.shape[1:]), operands)
    out = stack(base_model.tile_vmap(body), tiled)
    return jax.tree.map(
        lambda x: x.reshape((tiles * lanes,) + x.shape[2:])[:c], out)


def run_stacked(cfg, key, n_crashed, n_byzantine, probe=None):
    """Traced committee sim: ``(key, n_crashed, n_byzantine) -> stacked
    final state [C, ...]`` — the dynamic-fault-operand program
    (runner.make_dyn_sim_fn committee arm; the static arm passes the
    config's own counts).  ``cfg`` must already be fault-canonical, like
    every dyn program (models/base.canonical_fault_cfg).  ``probe``
    threads through to :func:`stacked_body` (returns ``(finals,
    probes)`` when armed)."""
    c, m = cfg.committees, cfg.n // cfg.committees
    alive, honest = base_model.dyn_fault_masks(cfg.n, n_crashed, n_byzantine)
    keys = _committee_keys(key, c)
    return stacked_body(cfg, keys, alive.reshape(c, m), honest.reshape(c, m),
                        probe=probe)


def milestone_ms(protocol: str, inner_metrics: dict) -> float:
    """One committee's inner-consensus milestone: the tick its inner quorum
    completed the protocol's measured outcome, -1.0 if it never did."""
    m = inner_metrics
    if protocol == "pbft":
        return float(m["last_commit_ms"]) if m["blocks_final_all_nodes"] > 0 \
            else -1.0
    if protocol == "raft":
        return float(m["last_block_ms"]) if m["blocks"] > 0 else -1.0
    return float(m["winner_commit_ms"]) if m["n_committed_proposers"] > 0 \
        else -1.0


def _host_leaves(cfg, finals) -> dict:
    """The stacked finals' metric leaves on the host, from ONE fetch: the leaves the
    inner protocol's ``metrics`` reads (its ``METRIC_FIELDS``; every field
    where a module declares none) cross the host link in one
    ``jax.device_get``, each copy started before the first is awaited, as
    a dict by field of ``[C, ...]`` numpy arrays (parallel/sweep._readback's
    way; a sweep's row arrives here as host arrays already, fetched under
    ``sweep.readback``).  A slice per leaf and committee on the device, with
    ``metrics`` blocking on every read, is (leaves + fields) x C round trips
    for the same bytes."""
    picked = base_model.metric_leaves(cfg, finals)
    leaves = jax.tree.leaves(picked)
    host = picked
    if any(isinstance(x, jax.Array) for x in leaves):
        # device arrays: the one fetch.  On one device they are a lone
        # stack's (run_simulation's path), and what it ran as is what
        # stacked_body wrote down when it was traced; a stack spread over a
        # mesh ran each device's slice by a plan of its own and is not
        # counted here
        lone = all(len(x.sharding.device_set) == 1 for x in leaves
                   if isinstance(x, jax.Array))
        plan = (ran_as(cfg) if lone else None) or {}
        attrs = {"tiles": plan["tiles"], "tile_lanes": plan["lanes"]} \
            if plan else {}
        with telemetry.span(
            "topo.committee.readback", committees=cfg.committees,
            leaves=len(leaves), bytes=sum(x.nbytes for x in leaves), **attrs,
        ):
            host = jax.device_get(picked)
        if plan:
            telemetry.metrics.counter(COUNTERS[0]).inc(plan["tiles"])
            telemetry.metrics.counter(COUNTERS[1]).inc(
                plan["tiles"] * plan["lanes"])
    return host


def metrics(cfg, finals) -> dict:
    """Host-side metrics of a stacked committee final state, from one
    readback (:func:`_host_leaves`).

    C = 1: the flat protocol's full metrics dict (bit-equal to the flat
    run — the tests' contract) plus the ``outer_*`` keys.  C > 1: the
    outer aggregate plus the per-committee milestone list (hand-checkable
    against the formula: ``outer_commit_ms`` = outer-quorum-th smallest
    decided milestone + one representative round trip), and
    ``per_committee``: every scalar of the inner protocol's metrics as a
    list of C (for pbft ``blocks_final_all_nodes``, ``rounds_sent``,
    ``view_changes``, ``last_commit_ms``, ``mean_time_to_finality_ms``,
    ``agreement_ok`` among them), which is what a caller needs to count
    rounds and to hold a committee to a reference (:data:`MILESTONES`)."""
    proto = base_model.get_protocol(cfg.protocol)
    c = cfg.committees
    icfg = inner_cfg(cfg)
    host = _host_leaves(cfg, finals)
    # committee i as a host state: the finals' own state type with numpy
    # views in the fetched fields and None elsewhere.  Raft groups with terms
    # are read at once from the stacked leaves instead (20,000 groups:
    # models/raft.metrics_stacked)
    rows = None if icfg.raft_terms else base_model.host_rows(finals, host, c)
    with telemetry.span("topo.committee.outer", committees=c):
        inner = (proto.metrics_stacked(icfg, host, c) if rows is None
                 else [proto.metrics(icfg, row) for row in rows])
        telemetry.count_raft_groups(inner)
        miles = [milestone_ms(cfg.protocol, m) for m in inner]
        decided = sorted(t for t in miles if t >= 0)
        quorum = c // 2 + 1
        outer_round = 0.0 if c == 1 \
            else float(2 * (cfg.one_way_range()[1] - 1))
        outer_commit = (
            decided[quorum - 1] + outer_round if len(decided) >= quorum
            else -1.0
        )
        outer = {
            "topology": "committee",
            "committees": c,
            "committee_size": icfg.n,
            "outer_quorum": quorum,
            "committees_decided": len(decided),
            "inner_milestones_ms": miles,
            "outer_round_ms": outer_round,
            "outer_commit_ms": float(outer_commit),
            "inner_agreement_ok": all(
                bool(m.get("agreement_ok", True)) for m in inner
            ),
        }
        if c == 1:
            return {**inner[0], **outer}
        return {
            "protocol": cfg.protocol,
            "n": cfg.n,
            "agreement_ok": outer["inner_agreement_ok"],
            **outer,
            "per_committee": {
                k: [m[k] for m in inner] for k, v in inner[0].items()
                if isinstance(v, (bool, int, float)) and k != "n"
            },
        }
