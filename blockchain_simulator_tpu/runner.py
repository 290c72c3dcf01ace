"""Simulation runner: the tensorized replacement for ``Simulator::Run``.

The reference drives everything through ns-3's serial event dispatch
(blockchain-simulator.cc:57; SURVEY.md §3.1 "THE hot loop").  Here the whole
simulation is one ``jax.lax.scan`` over ticks, compiled once by XLA: per tick,
every node's FSM transition and every in-flight message delivery happen as
batched tensor ops.  Protocol selection is a runtime config field.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from blockchain_simulator_tpu.models import base as base_model
from blockchain_simulator_tpu.models.base import get_protocol
from blockchain_simulator_tpu.utils import aotcache, prng
from blockchain_simulator_tpu.utils.config import SimConfig


class UnbatchableConfigError(NotImplementedError):
    """A config whose faults cannot become traced per-run operands — it has
    no dynamic-fault-operand program (``make_dyn_sim_fn``), so it can join
    neither a compile-once sweep group (parallel/sweep.py) nor a micro-batched
    serving dispatch (serve/).

    Typed so the sweep layer and the scenario server classify the refusal
    without string-matching; subclasses ``NotImplementedError`` so historical
    ``except NotImplementedError`` call sites keep working."""


def check_batchable(cfg: SimConfig) -> None:
    """Raise :class:`UnbatchableConfigError` when ``cfg`` has no
    dynamic-fault-operand program.  Currently that is exactly the mixed
    shard sim: its faults are per-shard *init structure*, not maskable
    state (models/base.apply_fault_masks)."""
    if cfg.protocol == "mixed":
        raise UnbatchableConfigError(
            "dynamic fault operands are not implemented for the mixed shard "
            "sim (faults live at the raft-shard level, models/mixed.py); "
            "sweep it with one static compile per fault config"
        )


def use_round_schedule(cfg: SimConfig) -> bool:
    """Resolve cfg.schedule: does this config run a phase-blocked fast path
    (PBFT: one scan step per block interval; raft: per heartbeat; mixed: the
    heartbeat scan inside every raft shard)?"""
    if cfg.schedule == "tick":
        return False
    if cfg.topology in ("kregular", "committee"):
        # the phase-blocked fast paths are full-mesh aggregates
        # (pbft_round/raft_hb eligibility already pins topology == "full");
        # the sparse/hierarchical axes run the general tick engine — inside
        # each committee too (topo/committee.py runs proto.step per tick)
        if cfg.schedule == "round":
            raise ValueError(
                f"schedule='round' is a full-mesh fast path; topology="
                f"{cfg.topology!r} runs the tick engine (use schedule="
                "'tick' or 'auto')"
            )
        return False
    if cfg.protocol == "raft":
        from blockchain_simulator_tpu.models import raft_hb

        ok = raft_hb.eligible(cfg)
        if cfg.schedule == "round":
            if not ok:
                raise ValueError(
                    "schedule='round' for raft requires clean fidelity + "
                    "full mesh + stat delivery with no drops/queued links, "
                    "heartbeat < election_lo, and a window longer than the "
                    "election prefix (models/raft_hb.eligible)"
                )
            return True
        return ok and cfg.n >= 4096  # "auto"
    if cfg.protocol == "mixed":
        from blockchain_simulator_tpu.models import mixed

        ok = mixed.fast_eligible(cfg)
        if cfg.schedule == "round":
            if not ok:
                raise ValueError(
                    "schedule='round' for the mixed sim requires its raft "
                    "shards to be heartbeat-schedulable: clean fidelity + "
                    "stat delivery with no drops/queued links and a window "
                    "longer than the election prefix (models/raft_hb.eligible "
                    "on the shard sub-config)"
                )
            return True
        # "auto": no n-threshold — the handoff is checked per shard and the
        # fallback CONTINUES the tick scan from the prefix carry, so the
        # fast path is never slower than the tick engine it replaces
        return ok
    if cfg.protocol != "pbft":
        return False
    from blockchain_simulator_tpu.models import pbft_round

    ok = pbft_round.eligible(cfg)
    if cfg.schedule == "round":
        if not ok:
            raise ValueError(
                "schedule='round' requires pbft + full mesh + stat delivery "
                "with no byz_forge, no queued links, drops only when view "
                "changes are disabled AND the vote table is exact "
                "(pbft_window = 0 or >= pbft_max_slots), and a message "
                "horizon — including the constant block-serialization "
                "latency when modeled — inside one block interval "
                "(models/pbft_round.eligible)"
            )
        return True
    return ok and cfg.n >= 4096  # "auto"


def _reject_cpp_only(cfg: SimConfig) -> None:
    """Validate fidelity modes on the tensorized backends: refuse what only
    the C++ engine models, rather than silently returning constant-latency /
    echo-free numbers for it."""
    if cfg.echo_back:
        raise NotImplementedError(
            "echo_back (quirk #1) is modeled by the C++ engine only "
            "(engine.run_cpp): the tensorized backends design the echo away "
            "(models/pbft.py docstring).  Deliberate scope decision, "
            "re-evaluated round 5: a reflected packet is processed through "
            "the full FSM, so echoed PREPAREs spawn fresh replies that are "
            "themselves reflected — exact fidelity needs up-to-6-leg "
            "reflection-cascade delay convolutions per vote channel, at odds "
            "with the aggregate count-based channel design that makes these "
            "engines fast; the C++ engine covers the quirk and "
            "tests/test_fidelity.py pins the traffic delta"
        )
    if cfg.raft_terms or cfg.faults.crashes:
        # what has no terms, or cannot run a crash schedule, refuses it by
        # its name (models/raft.check_arms), before anything is built
        from blockchain_simulator_tpu.models import raft

        raft.check_arms(cfg)
    if cfg.link_classes:
        # what cannot run link classes refuses them by its name, before
        # anything is built
        from blockchain_simulator_tpu.ops import linkclass

        linkclass.check_arms(cfg)
    if cfg.queued_links:
        # pbft: per-destination serial-pipe registers (models/pbft.py).
        # paxos: every message is 3-4 bytes (ser = 0), the pipe is never
        # busy, and queued-link transport IS the constant-latency model —
        # accepted as-is (the C++ engine reduces identically,
        # tests/test_fidelity.py::test_queued_links_zero_serialization...).
        # pbft/raft: per-destination serial-pipe registers (models/pbft.py
        # FIFOs, models/raft.py widened rings).  paxos messages are all 3-4
        # bytes (ser = 0), the pipe is never busy, and queued-link transport
        # IS the constant-latency model — accepted as-is (the C++ engine
        # reduces identically, tests/test_fidelity.py).
        if cfg.protocol == "mixed":
            raise NotImplementedError(
                "queued_links is not modeled by the mixed shard sim (its "
                "raft shards are small full meshes whose timing the cross-"
                "shard PBFT layer aggregates); use pbft/raft/paxos directly"
            )
        if cfg.protocol in ("pbft", "raft"):
            if cfg.topology != "full":
                raise ValueError(
                    "queued_links (tensorized) requires topology='full': the "
                    "serial-pipe registers model the leader's direct links"
                )
            if cfg.faults.drop_prob != 0.0:
                raise ValueError(
                    "queued_links (tensorized) requires drop_prob = 0: with "
                    "drops, leader beliefs can diverge and the per-destination "
                    "busy registers assume a single block sender; use the C++ "
                    "engine (engine.run_cpp) for queued links with drops"
                )
        if cfg.protocol == "pbft":
            from blockchain_simulator_tpu.models import pbft

            _, hi = cfg.one_way_range()
            if pbft.eff_window(cfg) < cfg.pbft_max_slots:
                raise ValueError(
                    "queued_links (tensorized) requires the exact vote table "
                    "(pbft_window = 0 or >= pbft_max_slots): a backlogged "
                    "block can trail its slot's votes past a window re-tenancy"
                )
            if hi - 1 >= cfg.pbft_block_interval_ms:
                raise ValueError(
                    "queued_links (tensorized) requires the one-way delay to "
                    "fit inside one block interval so leadership rotations "
                    "settle between block sends"
                )


@aotcache.cached_factory("sim")
def make_sim_fn(cfg: SimConfig):
    """Build (and cache) the jitted end-to-end simulation function for a config.

    Returns ``sim(key) -> final_state`` running ``cfg.ticks`` ticks — the
    general per-tick engine or, when the config resolves to it, a phase-
    blocked fast path: round-blocked PBFT (one scan step per 50 ms block
    interval, models/pbft_round.py), heartbeat-blocked raft behind a traced
    checked handoff (models/raft_hb.py), or the heartbeat-scheduled mixed
    sim (models/mixed.scan_fast).  Every returned function is fully traced
    (no host branches), so it composes with vmap and shard_map.

    Caching lives in the unified executable registry (utils/aotcache.py,
    hit/miss stats on every run manifest) rather than a per-module
    ``lru_cache``; the callable per config is still built exactly once per
    process.  Every engine arm this factory can dispatch to is traced and
    budget-pinned by the graph audit (lint/graph/programs.py ``sim.*``
    specs; ``python -m blockchain_simulator_tpu.lint.graph``).
    """
    _reject_cpp_only(cfg)
    if cfg.topology == "committee":
        from blockchain_simulator_tpu.topo import committee

        use_round_schedule(cfg)  # validates schedule='round' (always tick)
        # static arm of the committee hierarchy: the config's own fault
        # counts ride the (traced) operand slots of the shared dyn body,
        # mirroring the static==dyn equality every protocol pins
        # (tests/test_zsweep_cache.py), so ONE body serves both doors
        canon = base_model.canonical_fault_cfg(cfg)
        nc = cfg.faults.resolved_n_crashed(cfg.n)
        nb = cfg.faults.n_byzantine

        @jax.jit
        def sim_committee(key):
            return committee.run_stacked(
                canon, key, jnp.int32(nc), jnp.int32(nb)
            )

        return sim_committee
    if use_round_schedule(cfg):
        if cfg.protocol == "raft":
            from blockchain_simulator_tpu.models import raft_hb

            # the checked handoff is a lax.cond inside the trace
            # (models/raft_hb.scan_from_init): the whole program lowers
            # under jit, vmap (sweeps) and shard_map — no host branch
            return jax.jit(functools.partial(raft_hb.run, cfg))
        if cfg.protocol == "mixed":
            from blockchain_simulator_tpu.models import mixed

            @jax.jit
            def sim_mixed(key):
                state, bufs = mixed.init(cfg, jax.random.fold_in(key, 0x1217))
                return mixed.scan_fast(cfg, state, bufs, key)

            return sim_mixed
        from blockchain_simulator_tpu.models import pbft_round

        @jax.jit
        def sim_round(key):
            state, _ = pbft_round.init(cfg, jax.random.fold_in(key, 0x1217))
            return pbft_round.scan_rounds(cfg, state, key)

        return sim_round

    proto = get_protocol(cfg.protocol)

    @jax.jit
    def sim(key):
        state, bufs = proto.init(cfg, jax.random.fold_in(key, 0x1217))

        def body(carry, t):
            st, bf = carry
            st, bf = proto.step(cfg, st, bf, t, prng.tick_key(key, t))
            return (st, bf), ()

        (state, bufs), _ = jax.lax.scan(body, (state, bufs), jnp.arange(cfg.ticks))
        return state

    return sim


def make_dyn_sim_fn(cfg: SimConfig):
    """Build the dynamic-fault-operand simulation function for a config:
    ``sim(key, n_crashed, n_byzantine) -> final_state`` with the fault
    COUNTS as traced scalars (fault masks computed inside the trace,
    models/base.dyn_fault_masks), so one compiled program serves every
    fault level of a sweep — the compile-once substrate of
    parallel/sweep.run_fault_sweep / run_byzantine_sweep.

    ``cfg`` is canonicalized (models/base.canonical_fault_cfg) so every
    sweep over the same fault *structure* shares one trace; at equal
    counts the result is bit-equal to ``make_sim_fn`` at the static config
    (pinned in tests/test_zsweep_cache.py).  Returns the UNJITTED function:
    the sweep layer owns the single ``jit(vmap(...))`` wrapper, so an
    f-sweep costs exactly one executable.  The mixed shard sim distributes
    faults per shard at init and is refused with a typed
    :class:`UnbatchableConfigError` (:func:`check_batchable`)."""
    cfg = base_model.canonical_fault_cfg(cfg)
    check_batchable(cfg)
    _reject_cpp_only(cfg)
    n = cfg.n

    if cfg.topology == "committee":
        from blockchain_simulator_tpu.topo import committee

        use_round_schedule(cfg)  # validates schedule='round' (always tick)
        return functools.partial(committee.run_stacked, cfg)

    if use_round_schedule(cfg):
        if cfg.protocol == "raft":
            from blockchain_simulator_tpu.models import raft as raft_tick
            from blockchain_simulator_tpu.models import raft_hb

            def sim_hb(key, n_crashed, n_byzantine):
                state, bufs = raft_tick.init(cfg, jax.random.fold_in(key, 0x1217))
                state = base_model.apply_fault_masks(
                    cfg, state, *base_model.dyn_fault_masks(n, n_crashed, n_byzantine)
                )
                return raft_hb.scan_from_init(cfg, state, bufs, key)

            return sim_hb
        from blockchain_simulator_tpu.models import pbft_round

        def sim_round(key, n_crashed, n_byzantine):
            state, _ = pbft_round.init(cfg, jax.random.fold_in(key, 0x1217))
            state = base_model.apply_fault_masks(
                cfg, state, *base_model.dyn_fault_masks(n, n_crashed, n_byzantine)
            )
            return pbft_round.scan_rounds(cfg, state, key)

        return sim_round

    proto = get_protocol(cfg.protocol)

    def sim(key, n_crashed, n_byzantine):
        state, bufs = proto.init(cfg, jax.random.fold_in(key, 0x1217))
        state = base_model.apply_fault_masks(
            cfg, state, *base_model.dyn_fault_masks(n, n_crashed, n_byzantine)
        )

        def body(carry, t):
            st, bf = carry
            st, bf = proto.step(cfg, st, bf, t, prng.tick_key(key, t))
            return (st, bf), ()

        (state, bufs), _ = jax.lax.scan(body, (state, bufs), jnp.arange(cfg.ticks))
        return state

    return sim


def topo_tables_inslot(cfg: SimConfig) -> bool:
    """Does this protocol's kregular arm consume the ``inslot`` cross-index
    (three tables) or just the in/out pair (two)?  The one place the
    operand-feeding callers (parallel/sweep.sharded_topo_sim_fn, the graph
    audit specs) learn the table arity."""
    return cfg.protocol == "raft"


def make_topo_dyn_sim_fn(cfg: SimConfig, exchange_spec=None):
    """The tables-as-operands twin of :func:`make_dyn_sim_fn` for the
    kregular overlay: ``sim(key, n_crashed, n_byzantine, *tables) ->
    final_state`` where ``tables`` are the full ``[N, K]`` int32 overlay
    tables (ops/gatherdeliv.table_operands — ``(in, out)``, plus
    ``inslot`` for raft; :func:`topo_tables_inslot`).  Feeding them as
    arguments instead of letting the trace bake them keeps multi-MB
    overlays out of the jaxpr (KNOWN_ISSUES #0n's escape hatch, the
    large-jaxpr-constant graph rule) and lets parallel/sweep.py's
    ``sharded_topo_sim_fn`` shard them over the mesh's node axis.

    With ``exchange_spec`` (a ``parallel.partition.ExchangeSpec``) the
    operand list grows by the owner-bucketed exchange plans —
    ``spec.n_operands`` extra arrays after the tables (pos+send per table
    kind, topo/spec.owner_bucket_plan) — and every cross-row neighbor
    read inside the tick body routes through the resulting
    ``NeighborExchange`` instead of a global gather (the shard-local
    layout of parallel/sweep.sharded_topo_sim_fn).  Values are bit-equal
    either way; only the data movement differs.

    Same trace contract as ``make_dyn_sim_fn``: ``cfg`` is canonicalized,
    the function is returned UNJITTED (the caller owns the jit/pjit
    wrapper), and at equal table values the computation is identical —
    ``jnp.take(tables[i], ids)`` sees the same numbers whether the table
    is an operand or a constant, so results are bit-equal under the exact
    sampler (pinned in tests/test_zzshardtopo.py)."""
    cfg = base_model.canonical_fault_cfg(cfg)
    check_batchable(cfg)
    _reject_cpp_only(cfg)
    if cfg.topology != "kregular":
        raise ValueError(
            f"make_topo_dyn_sim_fn is the kregular tables-as-operands "
            f"program; topology={cfg.topology!r} has no overlay tables "
            "(committee shards its stacked axis instead — parallel/sweep."
            "sharded_topo_sim_fn routes it)"
        )
    use_round_schedule(cfg)  # validates schedule='round' (kregular: tick)
    n = cfg.n
    n_tables = 3 if topo_tables_inslot(cfg) else 2
    proto = get_protocol(cfg.protocol)

    n_plans = exchange_spec.n_operands if exchange_spec is not None else 0

    def sim(key, n_crashed, n_byzantine, *operands):
        if len(operands) != n_tables + n_plans:
            raise ValueError(
                f"{cfg.protocol} kregular sim takes {n_tables} overlay "
                f"tables{f' + {n_plans} exchange plans' if n_plans else ''}"
                f", got {len(operands)}"
            )
        tables = operands[:n_tables]
        xg = (exchange_spec.build(*operands[n_tables:])
              if exchange_spec is not None else None)
        state, bufs = proto.init(cfg, jax.random.fold_in(key, 0x1217))
        state = base_model.apply_fault_masks(
            cfg, state, *base_model.dyn_fault_masks(n, n_crashed, n_byzantine)
        )

        def body(carry, t):
            st, bf = carry
            st, bf = proto.step(cfg, st, bf, t, prng.tick_key(key, t),
                                topo_tables=tables, exchange=xg)
            return (st, bf), ()

        (state, bufs), _ = jax.lax.scan(
            body, (state, bufs), jnp.arange(cfg.ticks)
        )
        return state

    return sim


def run_simulation(cfg: SimConfig, seed: int | None = None, with_timing: bool = False):
    """Run one simulation; returns the protocol's structured metrics dict
    (the reference's NS_LOG lines, SURVEY.md §5, as data).

    ``with_timing`` stages through ``utils/obs.timed_run`` — the one
    compile-vs-execution split every timing surface shares — and reports
    both ``compile_plus_first_run_s`` and the execution-only
    ``wallclock_s``."""
    sim = make_sim_fn(cfg)
    key = jax.random.key(cfg.seed if seed is None else seed)
    if with_timing:
        from blockchain_simulator_tpu.utils import obs

        final, compile_s, wall = obs.timed_run(sim, key)
        m = _metrics(cfg, final)
        m["wallclock_s"] = wall
        m["compile_plus_first_run_s"] = round(compile_s, 3)
        m["ticks"] = cfg.ticks
        return m
    final = jax.block_until_ready(sim(key))
    return _metrics(cfg, final)


def _metrics(cfg: SimConfig, final) -> dict:
    """``sim_metrics`` of one run; a flat Raft run with terms counts its one
    group (utils/telemetry.count_raft_groups; a stack of groups is counted
    by topo/committee.metrics, where its per-group dicts are)."""
    m = base_model.sim_metrics(cfg, final)
    if cfg.raft_terms and cfg.topology != "committee":
        from blockchain_simulator_tpu.utils import telemetry

        telemetry.count_raft_groups([m])
    return m


def final_state(cfg: SimConfig, seed: int | None = None):
    """Run and return the raw final state pytree (for tests/checkpointing)."""
    sim = make_sim_fn(cfg)
    key = jax.random.key(cfg.seed if seed is None else seed)
    return jax.block_until_ready(sim(key))


def run_multi_seed(cfg: SimConfig, seeds, record: bool = True):
    """Multi-seed Monte Carlo: run ``len(seeds)`` seeds of one config as ONE
    dispatch of the ``lax.map`` executable (parallel/sweep.multi_seed_fn:
    the lone program, seed after seed), whatever the lanes' size: the
    caller's own choice of the program that ``run_dyn_points`` otherwise
    picks from a lane's state bytes and the device's memory
    (parallel/sweep._device_place).  Returns one metrics dict per seed, in
    order, each bit-equal (exact sampler; parallel/sweep.py caveat for the
    "normal" CLT float path) to ``run_simulation(cfg, seed=s)``.

    Compared to looping :func:`run_simulation`: one executable per
    (fault structure, seed count) — seed values ride the key operand, so a
    fresh seed set never recompiles — and one Python dispatch + sync for
    the whole batch.  Compared to the lane batch (``run_seed_sweep``,
    ``sweep.dyn_batched_fn``): neither wins everywhere.  On the chip the
    map wins where one lane fills the device (x1.75 at n = 100,000) and
    loses where it does not (x1.79 the other way at n = 1,024 x 32:
    PERF.md section 7 (h)), which is the choice ``run_dyn_points`` makes
    by itself; on XLA:CPU, which serializes the scatters that vmap makes
    of the tick engine's ring pushes, the map wins on the tick path at
    every size read (KNOWN_ISSUES #0i, ARTIFACT_tick_bench.json).  Mixed
    (the one un-batchable protocol) raises the typed
    :class:`UnbatchableConfigError`."""
    from blockchain_simulator_tpu.parallel import sweep

    canon = base_model.canonical_fault_cfg(cfg)
    points = [(cfg, int(s)) for s in seeds]
    return sweep.run_dyn_points(canon, points, record=record,
                                multi_seed=True)


@aotcache.cached_factory("segment")
def make_segment_fn(cfg: SimConfig, n_ticks: int):
    """Jitted ``seg(key, state, bufs, t0) -> (state, bufs)`` advancing the
    simulation ``n_ticks`` ticks from traced start tick ``t0``.  Because tick
    keys derive from the absolute tick (utils/prng.py), segmented execution is
    bit-identical to one uninterrupted scan — the checkpoint/resume substrate
    (the reference has none, SURVEY.md §5)."""
    _reject_cpp_only(cfg)
    if cfg.topology == "committee":
        raise ValueError(
            "segmented/checkpointed execution steps the flat (state, bufs) "
            "pair; the committee path's stacked state has no segment form "
            "(topo/committee.py) — run it un-checkpointed"
        )
    proto = get_protocol(cfg.protocol)

    @jax.jit
    def seg(key, state, bufs, t0):
        def body(carry, t):
            st, bf = carry
            st, bf = proto.step(cfg, st, bf, t, prng.tick_key(key, t))
            return (st, bf), ()

        return jax.lax.scan(body, (state, bufs), t0 + jnp.arange(n_ticks))[0]

    return seg


def run_checkpointed(
    cfg: SimConfig,
    every_ms: int,
    ckpt_dir,
    seed: int | None = None,
    keep_all: bool = False,
):
    """Run to completion, writing a checkpoint every ``every_ms`` virtual ms.

    Returns ``(metrics, last_checkpoint_path)``.  ``keep_all`` retains every
    snapshot (``ckpt_<tick>.npz``); otherwise only the latest survives.
    """
    import pathlib

    from blockchain_simulator_tpu.utils.checkpoint import save_checkpoint

    if every_ms < 1:
        raise ValueError(f"every_ms must be >= 1, got {every_ms}")
    # Checkpointing segments the general per-tick engine (its carry is the
    # full (state, bufs) pytree); the round fast path has no tick-granular
    # segmentation, so pin the schedule rather than silently running a
    # different simulator than run_simulation would.
    if use_round_schedule(cfg):
        if cfg.schedule == "round":
            raise ValueError(
                "schedule='round' does not support checkpointing (the round "
                "fast path is not tick-segmentable); use schedule='tick'"
            )
        cfg = cfg.with_(schedule="tick")
    ckpt_dir = pathlib.Path(ckpt_dir)
    ckpt_dir.mkdir(parents=True, exist_ok=True)
    # bake the effective seed into the stored config so resume_simulation
    # continues the exact PRNG stream without needing the override repeated
    if seed is not None:
        cfg = cfg.with_(seed=seed)
    proto = get_protocol(cfg.protocol)
    key = jax.random.key(cfg.seed)
    state, bufs = proto.init(cfg, jax.random.fold_in(key, 0x1217))
    t, last_path = 0, None
    while t < cfg.ticks:
        n = min(every_ms, cfg.ticks - t)
        state, bufs = make_segment_fn(cfg, n)(key, state, bufs, jnp.int32(t))
        t += n
        jax.block_until_ready(state)
        path = ckpt_dir / f"ckpt_{t:08d}.npz"
        save_checkpoint(path, cfg, state, bufs, t)
        if last_path is not None and not keep_all:
            last_path.unlink()
        last_path = path
    return proto.metrics(cfg, state), last_path


def _dyn_checkpoint_cfg(cfg: SimConfig, seed: int | None) -> SimConfig:
    """Validate + normalize a config for dynamic-fault checkpointed
    execution: tick schedule pinned (the fast paths are not
    tick-segmentable — same rule as :func:`run_checkpointed`), effective
    seed baked in, batchability and cpp-only modes checked up front."""
    check_batchable(cfg)
    _reject_cpp_only(cfg)
    if use_round_schedule(cfg):
        if cfg.schedule == "round":
            raise ValueError(
                "schedule='round' does not support checkpointing (the round "
                "fast path is not tick-segmentable); use schedule='tick'"
            )
        cfg = cfg.with_(schedule="tick")
    if seed is not None:
        cfg = cfg.with_(seed=seed)
    return cfg


def run_dyn_checkpointed(
    cfg: SimConfig,
    every_ms: int,
    ckpt_dir,
    seed: int | None = None,
    keep_all: bool = False,
    resume: bool = True,
):
    """The dynamic-fault-operand analog of :func:`run_checkpointed` — and
    the sweep supervisor's tick-level degrade arm for very long
    single-sim chunks (parallel/journal.py): init at the CANONICAL fault
    structure, install the traced fault masks from ``cfg.faults``' counts
    (models/base.dyn_fault_masks — the masks then ride ``state`` as
    ordinary leaves, so the shared ``segment`` executable advances them),
    and checkpoint every ``every_ms`` virtual ms with the ``(n_crashed,
    n_byzantine)`` operands stored alongside state/bufs.

    ``resume=True`` (default): when ``ckpt_dir`` already holds a
    ``ckpt_*.npz`` from a crashed run of the SAME config, execution
    continues from the latest one instead of restarting — a re-killed
    chunk loses at most one segment.  A checkpoint for a different
    config (or a static-path archive with no ``__dyn__`` entry) raises
    rather than silently blending two runs.

    Rows are bit-equal to the un-checkpointed dyn program
    (``jit(make_dyn_sim_fn(cfg))``) — the tick keys derive from absolute
    ticks (utils/prng.py), pinned in tests/test_checkpoint.py.
    Returns ``(metrics, last_checkpoint_path)``."""
    import pathlib

    from blockchain_simulator_tpu.utils.checkpoint import (
        load_checkpoint,
        load_dyn_counts,
        save_checkpoint,
    )

    if every_ms < 1:
        raise ValueError(f"every_ms must be >= 1, got {every_ms}")
    cfg = _dyn_checkpoint_cfg(cfg, seed)
    canon = base_model.canonical_fault_cfg(cfg)
    nc = cfg.faults.resolved_n_crashed(cfg.n)
    nb = cfg.faults.n_byzantine
    proto = get_protocol(cfg.protocol)
    key = jax.random.key(cfg.seed)
    ckpt_dir = pathlib.Path(ckpt_dir)
    ckpt_dir.mkdir(parents=True, exist_ok=True)
    existing = sorted(ckpt_dir.glob("ckpt_*.npz")) if resume else []
    if existing:
        stored_cfg, state, bufs, t = load_checkpoint(existing[-1])
        if stored_cfg != cfg:
            raise ValueError(
                f"checkpoint {existing[-1]} belongs to a different config "
                f"(stored hash != requested); refusing to blend runs"
            )
        stored_dyn = load_dyn_counts(existing[-1])
        if stored_dyn != (nc, nb):
            raise ValueError(
                f"checkpoint {existing[-1]} stores dyn operands "
                f"{stored_dyn}, requested ({nc}, {nb})"
            )
        last_path = existing[-1]
    else:
        state, bufs = proto.init(canon, jax.random.fold_in(key, 0x1217))
        state = base_model.apply_fault_masks(
            cfg, state, *base_model.dyn_fault_masks(cfg.n, nc, nb)
        )
        t, last_path = 0, None
    while t < cfg.ticks:
        n = min(every_ms, cfg.ticks - t)
        state, bufs = make_segment_fn(canon, n)(key, state, bufs, jnp.int32(t))
        t += n
        jax.block_until_ready(state)
        path = ckpt_dir / f"ckpt_{t:08d}.npz"
        save_checkpoint(path, cfg, state, bufs, t, dyn_counts=(nc, nb))
        if last_path is not None and not keep_all:
            last_path.unlink()
        last_path = path
    return proto.metrics(cfg, state), last_path


def resume_dyn_simulation(ckpt_path):
    """Load a dynamic-fault checkpoint and run the remaining ticks through
    the canonical-structure ``segment`` executable; returns metrics
    bit-equal to the uninterrupted dyn run.  Raises on a static-path
    archive (no stored operands) — use :func:`resume_simulation`."""
    from blockchain_simulator_tpu.utils.checkpoint import (
        load_checkpoint,
        load_dyn_counts,
    )

    dyn = load_dyn_counts(ckpt_path)
    if dyn is None:
        raise ValueError(
            f"{ckpt_path} is a static-path checkpoint (no __dyn__ operands);"
            " use resume_simulation"
        )
    cfg, state, bufs, t = load_checkpoint(ckpt_path)
    canon = base_model.canonical_fault_cfg(cfg)
    proto = get_protocol(cfg.protocol)
    key = jax.random.key(cfg.seed)
    if t < cfg.ticks:
        state, bufs = make_segment_fn(canon, cfg.ticks - t)(
            key, state, bufs, jnp.int32(t)
        )
        jax.block_until_ready(state)
    return proto.metrics(cfg, state)


def resume_simulation(ckpt_path, seed: int | None = None):
    """Load a checkpoint and run the remaining ticks; returns metrics.

    ``seed`` must match the original run's (it defaults to the config's seed
    stored in the checkpoint); the tick stream continues bit-exactly.
    """
    from blockchain_simulator_tpu.utils.checkpoint import load_checkpoint

    cfg, state, bufs, t = load_checkpoint(ckpt_path)
    proto = get_protocol(cfg.protocol)
    key = jax.random.key(cfg.seed if seed is None else seed)
    if t < cfg.ticks:
        state, bufs = make_segment_fn(cfg, cfg.ticks - t)(
            key, state, bufs, jnp.int32(t)
        )
        jax.block_until_ready(state)
    return proto.metrics(cfg, state)
