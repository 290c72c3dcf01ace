"""Raft heartbeat-blocked fast path: one scan step = one 50 ms heartbeat.

The raft tick engine (models/raft.py) carries [N] state and [D, N] rings
through every 1 ms tick.  But steady-state raft replication is LEADER-
CENTRIC: one proposal broadcast per heartbeat, N-1 acks back, a majority
count — the followers are homogeneous (clean fidelity: ack unconditionally,
store the value, re-arm the timer).  Aggregated, a whole heartbeat is O(1)
work — a handful of scalar bucket draws and a short crossing loop —
INDEPENDENT OF N: the same multi-rate-stepping-to-the-limit design as the
PBFT round path (models/pbft_round.py), taken further because raft's steady
state has a single actor.

Two phases under one jit, joined by a TRACED checked handoff:

1. **Election prefix** (tick engine, ``prefix_ticks(cfg)`` = election_hi +
   2*roundtrip_hi ticks): elections are genuinely event-driven (randomized
   timers, races, retries), so the faithful tick machine runs them.  At the
   handoff the program CHECKS it reached the quiet window between the
   election settling and the first proposal (exactly one leader, its vote
   wave drained, proposals scheduled but not yet started) and emits an
   ``ok`` flag.
2. **Heartbeat scan**: per step, the leader's proposal (once
   ``proposal_tick`` passes), its ack wave as multinomial bucket counts over
   the round-trip distribution offset by the 20 KB serialization time, and
   the clean-mode ack-window bookkeeping at BIN granularity with the tick
   engine's exact ordering: arrivals on the heartbeat boundary tick count
   into the OLD window, then the new proposal resets it, then later
   arrivals fill the new one.  With the reference's 54-tick proposal
   serialization the whole wave lands one heartbeat behind its proposal —
   reproducing the tick engine's characteristic "49 of 50 blocks at
   defaults" pipeline (see .claude/skills/verify/SKILL.md).

The handoff is a ``jax.lax.cond``: when ``ok`` is false (e.g. a split first
election that re-ran past the prefix, or setProposal already fired inside
the prefix) the false branch CONTINUES the tick engine from the prefix's
(state, bufs) carry through the rest of the window.  Because tick keys
derive from the absolute tick (utils/prng.py), the continuation is
bit-identical to one uninterrupted tick-engine run — the fast path is
checked, never silently wrong, and the whole program lowers inside ``jit``,
``vmap`` (the cond batches to a select: both branches run, so a batched
sweep costs ~one tick-engine pass) and ``shard_map`` (the handoff reductions
ride ``psum``/``pmax`` over ``cfg.mesh_axis``; phase 2 is replicated O(1)
scalar work).

Timer suppression is structural: heartbeats every 50 ms re-arm 150-300 ms
election timers, so in the fault classes this path accepts (crash/Byzantine
from t=0, no drops) no election can fire after the handoff.

Milestone contract vs the tick engine (same reasoning as pbft_round): ack
COUNTS are deterministic (no drops — every follower acks every proposal
exactly once), so per-block commit counts are bit-equal; commit TICKS carry
the +/-1 bucket-quantile jitter of the independent per-engine draws.

Documented divergence — post-completion election churn: when replication
finishes INSIDE the window (blockNum hits raft_max_blocks), the reference
cancels the heartbeat (raft-node.cc:248-251); in clean fidelity the silenced
heartbeat un-suppresses every follower's election timer and the tick engine
then churns elections for the rest of the window (a real consequence of
completion silencing the failure detector; the gossip overlay keeps a
control heartbeat for exactly this reason — models/raft.py).  This path ends
at completion instead: every consensus milestone (leader, blocks, block
ticks, rounds, agreement over the replicated log) is identical — the churn
starts only after the log is complete — but the ``elections`` metric counts
the consensus phase only, and post-completion re-leaders are not simulated.
Configurations whose window ends before completion (e.g. the reference
default, where serialized acks leave 49/50 blocks at the 10 s mark) have no
churn phase and match on every metric including ``elections``.

Reference anchors: sendHeartBeat/SendTX (raft-node.cc:405-433,340-365), ack
counting + blockNum (raft-node.cc:234-251), setProposal (+1 s, :216,433),
stop conditions (:248-251, :361-365).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from blockchain_simulator_tpu.models import raft as raft_tick
from blockchain_simulator_tpu.ops import delay as delay_ops
from blockchain_simulator_tpu.ops import delivery as dv
from blockchain_simulator_tpu.utils import prng
from blockchain_simulator_tpu.utils.prng import Channel, chan_key

DISARM = raft_tick.DISARM

# one heartbeat step of :func:`steady_scan` as a ``jax.named_scope`` name
# (HLO metadata only — see models/pbft.SCOPES)
SCOPES = ("raft.hb.step",)


def prefix_ticks(cfg) -> int:
    """Static election-phase length: the last possible first-attempt election
    fires by election_hi; its request+reply wave drains within 2 round trips."""
    _, rt_hi = cfg.roundtrip_range()
    return cfg.raft_election_hi_ms + 2 * rt_hi


def n_hb_steps(cfg) -> int:
    """Static heartbeat-step count of the steady scan (also the length of a
    traced run's per-heartbeat probe series, utils/trace.run_traced)."""
    return max((cfg.ticks - prefix_ticks(cfg)) // cfg.raft_heartbeat_ms + 2, 1)


def eligible(cfg) -> bool:
    return (
        cfg.protocol == "raft"
        and cfg.fidelity == "clean"  # reference mode never re-arms timers and
        # gates commits on exactly N-1 replies — tick-machine territory
        and cfg.topology == "full"
        and cfg.delivery == "stat"
        and cfg.faults.drop_prob == 0.0  # a dropped ack changes counts; a
        # dropped heartbeat un-suppresses a timer (re-election mid-stream)
        and not cfg.queued_links
        and cfg.raft_heartbeat_ms < cfg.raft_election_lo_ms  # timer suppression
        and cfg.sim_ms > prefix_ticks(cfg) + cfg.raft_heartbeat_ms
    )


def _ack_bins(cfg):
    """Static (bin -> step offset, tick-within-step, boundary flag) layout of
    the ack round-trip distribution shifted by the proposal serialization."""
    rt_lo, rt_hi = cfg.roundtrip_range()
    ser = cfg.serialization_ticks(cfg.raft_block_bytes)
    hb = cfg.raft_heartbeat_ms
    offs = [ser + rt_lo + b for b in range(rt_hi - rt_lo)]
    return [(o // hb, o % hb) for o in offs]


def _psum(x, axis):
    return x if axis is None else jax.lax.psum(x, axis)


def _pmax(x, axis):
    return x if axis is None else jax.lax.pmax(x, axis)


class Handoff(NamedTuple):
    """Leader-global scalars the heartbeat scan consumes (replicated across
    the mesh axis when sharded; garbage-but-finite when ``ok`` is false —
    the cond's false branch never reads them, and under vmap's both-branch
    select they only have to be safe to compute with)."""

    lead: jax.Array     # global leader id (-1 if none)
    hb0: jax.Array      # leader's next heartbeat tick
    p_start: jax.Array  # leader's setProposal tick
    bn0: jax.Array      # leader's block_num at handoff (0 in the quiet window)
    rnd0: jax.Array     # leader's round at handoff (0 in the quiet window)
    bt0: jax.Array      # [B] leader's block_tick row
    ok_cnt: jax.Array   # honest alive followers (SUCCESS acks), float32


def handoff(cfg, state, axis=None):
    """Checked-handoff evaluation on the post-prefix tick-engine state.

    Returns ``(ok, Handoff)``; every value is a replicated scalar (or [B]
    row) under ``shard_map`` — the reductions ride psum/pmax over ``axis``.
    """
    t_e = prefix_ticks(cfg)
    hb = cfg.raft_heartbeat_ms
    rt_hi = cfg.roundtrip_range()[1]
    n_loc = state.is_leader.shape[0]
    ids = dv._global_ids(n_loc, axis)
    lead_mask = state.is_leader & state.alive
    n_leaders = _psum(lead_mask.sum(), axis)
    lead = _pmax(jnp.max(jnp.where(lead_mask, ids, -1)), axis)

    def lval(x, fill):
        """Leader-row value (max over the — singleton when ok — leader set)."""
        return _pmax(jnp.max(jnp.where(lead_mask, x, fill)), axis)

    p_start = lval(state.proposal_tick, -1)
    ok = (
        (n_leaders == 1)
        # the election wave has fully drained: stale grants/denials land
        # within one round trip of the winning fire (leader_tick is the
        # win tick, itself at most rt_hi past the fire — prefix_ticks
        # budgets 2*rt_hi past election_hi for exactly this)
        & (lval(state.leader_tick, -1) + rt_hi <= t_e)
        & (p_start > t_e + hb)  # not yet proposing
        # DISARM (= setProposal already fired inside the prefix, possible
        # when raft_proposal_delay_ms is small) trivially satisfies the
        # not-yet-proposing comparison but means proposal waves may already
        # be in flight in the rings phase 2 discards — fall back to the
        # tick engine instead of silently never proposing (ADVICE r5)
        & (p_start != DISARM)
    )
    ok_cnt = (
        _psum((state.alive & state.honest).sum(), axis)
        - lval((state.alive & state.honest).astype(jnp.int32), 0)
    ).astype(jnp.float32)
    bt0 = _pmax(
        jnp.max(jnp.where(lead_mask[:, None], state.block_tick, -1), axis=0),
        axis,
    )
    return ok, Handoff(
        lead=lead,
        hb0=lval(state.next_hb, -1),
        p_start=p_start,
        bn0=lval(state.block_num, 0),
        rnd0=lval(state.round, 0),
        bt0=bt0,
        ok_cnt=ok_cnt,
    )


def steady_scan(cfg, key, h: Handoff, with_probe: bool = False):
    """Heartbeat-blocked steady-state scan from the handoff scalars.

    Pure O(1)-per-step scalar work — no [N] state, no collectives — so it
    vmaps over shards (models/mixed.py) and replicates cheaply under
    shard_map.  Returns ``(hs, open_, bn, rnd, add_on, stopped, bt)``.

    ``with_probe=True`` (utils/trace.run_traced) additionally emits one
    probe sample per HEARTBEAT step — ``{"blocks", "rounds",
    "acks_in_window", "stopped"}``, the leader-global values after the
    step — and returns ``(scan_out, ys)``.  The carry trajectory is
    bit-identical either way (the probe only reads the carry).
    """
    hb = cfg.raft_heartbeat_ms
    b_max = cfg.raft_max_blocks
    bins = _ack_bins(cfg)
    b2 = len(bins)
    span = max(s for s, _ in bins) + 1
    # bin processing order within a step: tick-within-step ascending; ties by
    # bin index (same tick => one counter update, order irrelevant)
    order = sorted(range(b2), key=lambda i: bins[i][1])
    k_steps = n_hb_steps(cfg)
    rt_probs = delay_ops.roundtrip_probs(*cfg.one_way_range())
    smode = cfg.eff_stat_sampler
    need = cfg.majority_need

    @jax.named_scope("raft.hb.step")
    def hb_body(carry, k):
        pend, hs, open_, bn, rnd, add_on, stopped, bt = carry
        t_k = h.hb0 + k * hb

        def apply_bin(cnt, tick, hs, open_, bn, bt):
            """One ack bin through the window: count, threshold-cross,
            commit (clean latch) — the tick engine's per-tick rule."""
            hs = hs + cnt
            crossed = open_ & (cnt > 0) & (hs + 1 >= need)
            blk = jnp.clip(bn, 0, b_max - 1)
            bt = jnp.where(
                jax.nn.one_hot(blk, b_max, dtype=bool)
                & crossed & (bn < b_max),
                tick, bt,
            )
            return hs, open_ & ~crossed, bn + crossed, bt

        arrivals = pend[0]  # [B2] counts landing this step
        # boundary-tick arrivals (tick offset 0) hit the OLD window and
        # are fully folded — including into bn — BEFORE the proposal
        # gate below, matching the tick engine's within-tick order
        # (arrival processing, then the heartbeat timer section)
        for i in order:
            s_i, off_i = bins[i]
            if off_i != 0:
                continue
            # horizon mask: arrivals at or past the window end never land
            cnt = jnp.where(t_k + off_i < cfg.ticks, arrivals[i], 0)
            hs, open_, bn, bt = apply_bin(cnt, t_k + off_i,
                                          hs, open_, bn, bt)
        # heartbeat boundary: proposal + clean window reset
        # (raft-node.cc:405-433; raft.py step's timer section); a
        # boundary-tick commit that just hit b_max cancels it
        live = (t_k < cfg.ticks) & ~stopped
        p = live & (t_k >= h.p_start) & add_on & (bn < b_max)
        rnd = rnd + p
        add_on = add_on & ~(p & (rnd >= cfg.raft_max_rounds))
        hs = jnp.where(p, 0, hs)
        open_ = open_ | p
        # post-boundary arrivals fill the (possibly new) window
        for i in order:
            s_i, off_i = bins[i]
            if off_i == 0:
                continue
            cnt = jnp.where(t_k + off_i < cfg.ticks, arrivals[i], 0)
            hs, open_, bn, bt = apply_bin(cnt, t_k + off_i,
                                          hs, open_, bn, bt)
        # rotate the pending ring and enqueue this proposal's ack wave
        pend = jnp.concatenate(
            [pend[1:], jnp.zeros((1, b2), jnp.int32)], axis=0
        )
        cnts = delay_ops.sample_bucket_counts(
            jax.random.fold_in(chan_key(prng.tick_key(key, t_k),
                                        Channel.DELAY_ROUNDTRIP), 0x4B),
            jnp.where(p, h.ok_cnt, 0.0), rt_probs, smode,
        )  # [B2] scalar counts
        for i in range(b2):
            s_i, _ = bins[i]
            if s_i > 0:  # lands s_i steps later: row s_i-1 post-rotation
                pend = pend.at[s_i - 1, i].add(cnts[i])
        # s_i == 0 bins (ser + rt < heartbeat) land later THIS step,
        # which the rotated ring's row 0 has already passed — inject
        # them directly (offsets are > 0: acks always land strictly
        # after their proposal tick)
        if any(s == 0 for s, _ in bins):
            for i in order:
                s_i, off_i = bins[i]
                if s_i != 0:
                    continue
                cnt = jnp.where(t_k + off_i < cfg.ticks, cnts[i], 0)
                hs, open_, bn, bt = apply_bin(cnt, t_k + off_i,
                                              hs, open_, bn, bt)
        stopped = stopped | (bn >= b_max)  # blockNum>=50 cancels the
        # heartbeat (raft-node.cc:248-251)
        ys = (
            {"blocks": bn, "rounds": rnd, "acks_in_window": hs,
             "stopped": stopped.astype(jnp.int32)}
            if with_probe
            else ()
        )
        return (pend, hs, open_, bn, rnd, add_on, stopped, bt), ys

    carry0 = (
        jnp.zeros((span, b2), jnp.int32),
        jnp.int32(0),                       # hs (ack window count)
        jnp.bool_(False),                   # hb_open
        h.bn0,                              # 0 at handoff
        h.rnd0,                             # 0 at handoff
        jnp.bool_(True),                    # add_change_value (will set)
        jnp.bool_(False),                   # stopped
        h.bt0,                              # [B] commit ticks
    )
    (_, hs, open_, bn, rnd, add_on, stopped, bt), ys = jax.lax.scan(
        hb_body, carry0, jnp.arange(k_steps)
    )
    out = (hs, open_, bn, rnd, add_on, stopped, bt)
    return (out, ys) if with_probe else out


def materialize(cfg, state, h: Handoff, scan_out, axis=None):
    """Fold the steady-scan scalars back into the [N] state the metrics
    surface reads (each shard writes only its local leader/follower rows)."""
    hs, open_, bn, rnd, add_on, stopped, bt = scan_out
    n_loc = state.is_leader.shape[0]
    onehot = dv._global_ids(n_loc, axis) == h.lead
    return state.replace(
        block_num=jnp.where(onehot, bn, state.block_num),
        round=jnp.where(onehot, rnd, state.round),
        block_tick=jnp.where(onehot[:, None], bt[None, :],
                             state.block_tick),
        hb_succ=jnp.where(onehot, hs, state.hb_succ),
        hb_open=jnp.where(onehot, open_, state.hb_open),
        add_change_value=jnp.where(onehot, add_on, state.add_change_value),
        next_hb=jnp.where(onehot & stopped, DISARM, state.next_hb),
        # every alive follower stored the leader's proposal value once
        # replication ran (m_value = leader id, raft-node.cc:180-190)
        m_value=jnp.where(
            state.alive & ~onehot & (rnd > 0), h.lead, state.m_value
        ),
    )


def scan_from_init(cfg, state, bufs, key, probe=None):
    """Fully traced round-schedule raft simulation from an initial
    (state, bufs): tick-engine election prefix, traced checked handoff,
    ``lax.cond`` into either the heartbeat scan or a CONTINUATION of the
    tick engine from the prefix carry (bit-identical to one uninterrupted
    tick run — tick keys derive from the absolute tick).

    Shared by the single-chip runner (runner.make_sim_fn), vmapped sweeps
    (parallel/sweep.py) and the node-sharded path (parallel/shard.py, which
    calls it inside ``shard_map`` with ``cfg.mesh_axis`` set).

    ``probe`` (obsim/build.py) arms in-program taps without forking the
    engine: a ``(sample_fn, steady_map_fn, reduce_fn)`` triple —
    ``sample_fn(state) -> {field: scalar}`` per TICK, ``steady_map_fn(ys,
    handoff_state) -> {field: [K]}`` lifting the heartbeat scan's ys into
    the same fields, and ``reduce_fn(series) -> pytree`` collapsing a
    variable-length sample axis to a FIXED shape, so both ``lax.cond``
    branches (prefix+heartbeats vs prefix+ticks — different sample
    counts) merge on identical avals.  Returns ``(final, probes)``; the
    state trajectory is bit-identical to the unprobed call (taps only
    read; they consume zero PRNG)."""
    axis = cfg.mesh_axis
    t_e = prefix_ticks(cfg)
    sample_fn, steady_map_fn, reduce_fn = probe or (None, None, None)

    def tick_body(carry, t):
        st, bf = carry
        st, bf = raft_tick.step(cfg, st, bf, t, prng.tick_key(key, t))
        return (st, bf), sample_fn(st) if sample_fn is not None else ()

    def _cat(pre, post):
        return jax.tree.map(
            lambda a, b: jnp.concatenate([a, b], axis=0), pre, post
        )

    # ---- phase 1: election prefix on the tick engine -----------------------
    carry, pre_ys = jax.lax.scan(tick_body, (state, bufs), jnp.arange(t_e))
    ok, h = handoff(cfg, carry[0], axis)

    if probe is None:

        def fast_branch(carry):
            return materialize(cfg, carry[0], h, steady_scan(cfg, key, h),
                               axis)

        def tick_branch(carry):
            # the election prefix did not reach the quiet handoff window:
            # the faithful tick engine takes over from the prefix carry
            (st, _), _ = jax.lax.scan(
                tick_body, carry, t_e + jnp.arange(max(cfg.ticks - t_e, 0))
            )
            return st

        return jax.lax.cond(ok, fast_branch, tick_branch, carry)

    def fast_branch(carry):
        out, hb_ys = steady_scan(cfg, key, h, with_probe=True)
        st = materialize(cfg, carry[0], h, out, axis)
        series = _cat(pre_ys, steady_map_fn(hb_ys, carry[0]))
        return st, reduce_fn(series)

    def tick_branch(carry):
        (st, _), ys = jax.lax.scan(
            tick_body, carry, t_e + jnp.arange(max(cfg.ticks - t_e, 0))
        )
        return st, reduce_fn(_cat(pre_ys, ys))

    return jax.lax.cond(ok, fast_branch, tick_branch, carry)


def run(cfg, key):
    """``run(cfg, key) -> RaftState`` — init + scan_from_init (the
    single-device / vmap entry; jit-wrapped by runner.make_sim_fn)."""
    state, bufs = raft_tick.init(cfg, jax.random.fold_in(key, 0x1217))
    return scan_from_init(cfg, state, bufs, key)


def metrics(cfg, state) -> dict:
    return raft_tick.metrics(cfg, state)
