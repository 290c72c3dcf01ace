"""PBFT round-blocked fast path: one scan step = one 50 ms consensus round.

The general engine (models/pbft.py) advances 1 ms ticks, carrying [N, W] vote
state and [D, N, W] future-inbox rings.  That is the faithful, fully general
machine — but at N = 100k the compiled tick body rewrites each 57 MB ring
buffer several times per tick (round-3 HLO analysis: 13 full-buffer fusions,
~1.5 GB of HBM traffic per 1 ms tick), capping throughput near 8 simulated
rounds/s on a v5e chip.

This module exploits the protocol's structure instead (the TPU-first answer
to SURVEY.md §7 "hard parts" #2, multi-rate stepping, taken to its limit):
when no messages cross a round boundary, a whole PBFT round is a *closed*
static wave — propose at t0; PRE_PREPAREs land at t0+U{lo..hi-1}; each
receiver's PREPARE round-trip replies arrive as multinomial bucket counts
over the triangular two-leg distribution; vote counters cross thresholds by
a short cumulative loop over those buckets; COMMIT broadcasts group by send
tick and land as per-receiver multinomial counts again.  Everything is a
handful of ops on [N] vectors: no vote table, no rings, ~50 ticks of
simulation per scan step for less memory traffic than ONE tick of the
general engine.

Semantics match models/pbft.step for every configuration this path accepts
(`eligible` below): identical timer/threshold/fidelity logic, identical
view-change draw (same PRNG channel at the block tick), same metrics
surface; delivery randomness is drawn per round instead of per tick, so
results are distributionally — not bit — identical to the tick engine
(delivery="stat" is already an aggregate model).  Precisely, for DROP-FREE
configs: per-slot COUNTS (commits, proposals, view changes — every
milestone) are bit-equal, because both samplers deliver every message
exactly once; per-slot commit *ticks* carry +/-1-tick tail jitter (the
last threshold-crossing arrival falls in a different multinomial bucket
under different keys).  With drop_prob > 0 the thinning draws are
independent between engines, so counts agree only where thresholds make
the outcome deterministic (the drop tests pin such operating points, not
exact equality at intermediate rates).  Tests pin exactly these contracts
(tests/test_pbft_round.py).

Eligibility (checked statically from the config):
- protocol "pbft", topology "full", delivery "stat";
- per-message drops only with view changes disabled (each wave is then an
  independently thinned binomial, the tick engine's own stat-channel drop
  model; a dropped VIEW_CHANGE would diverge leader beliefs and rounds
  would stop being single-proposer);
- no byz_forge flood (targets the exact-window tick machine);
- the message horizon (including the constant block-serialization latency
  when modeled) must fit inside one block interval:
  ``ser + max_arrival_offset < pbft_block_interval_ms``, so rounds close.

Serialization (model_serialization=True) is a CONSTANT per-block offset in
the tick engine — only the PRE_PREPARE push carries it (pbft.py step:
``ring_push_max(pp, t, lo + ser, ...)``; votes/commits are 4-byte packets) —
so here it shifts the whole round wave rigidly by ``ser`` ticks: arrivals at
``t0 + ser + d_j``, commit sends at ``t0 + ser + d_j + rt``, commits landing
at most ``ser + max_arrival_offset`` after the block tick.  At the reference
default timing (50 KB blocks on 3 Mbps links -> ser = 134 ticks > the 50-tick
interval) rounds overlap and this path refuses.  Raising the interval alone
cannot fix that: the block size scales with the interval (num = tx_speed /
(1000/timeout), pbft-node.cc:377), and the reference's 1000 tx/s x 1 KB
offered load (8 Mbit/s) exceeds its own 3 Mbps link — the very overload that
makes its queues grow without bound (tests/test_fidelity.py).  A SUSTAINABLE
operating point (e.g. tx_speed=300 -> 2.4 Mbit/s, 80% utilization) with the
interval past ser + horizon (e.g. 200 ms -> ser = 160) is eligible, with
per-round cost identical to the serialization-free config (the offset is
arithmetic, not extra work).

Reference anchors: the round cadence being reproduced is SendBlock's 50 ms
self-rescheduling loop (pbft-node.cc:372-411); thresholds pbft-node.cc:231,
248; view change pbft-node.cc:294-303,401-403; finality log pbft-node.cc:259.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from flax import struct

from blockchain_simulator_tpu.models import pbft as pbft_tick
from blockchain_simulator_tpu.models.base import fault_masks
from blockchain_simulator_tpu.ops import delay as delay_ops
from blockchain_simulator_tpu.ops import delivery as dv
from blockchain_simulator_tpu.ops.delivery import _global_ids, _shard_key
from blockchain_simulator_tpu.utils.prng import Channel, chan_key

_NEVER = pbft_tick._NEVER

GLOBAL_FIELDS = pbft_tick.GLOBAL_FIELDS

# the sections of :func:`step_round` as ``jax.named_scope`` names (see
# pbft.SCOPES): device time per phase of a round, by name, in a trace
SCOPES = ("pbft.round.block", "pbft.round.prepare", "pbft.round.commit")


@struct.dataclass
class PbftRoundState:
    """Cross-round state only — all in-round vote bookkeeping is transient.

    Field names/meanings mirror models/pbft.PbftState so pbft.metrics() reads
    either; the [N, W] table fields simply do not exist here.
    """

    v: jax.Array             # [N]
    leader: jax.Array        # [N]
    next_n: jax.Array        # [N]
    rounds_sent: jax.Array   # [N]
    block_num: jax.Array     # [N]
    unattributed: jax.Array  # [N] (always 0 on this path: no vote table
    # windows exist to misattribute into, even under drops)
    view_changes: jax.Array  # [N]
    alive: jax.Array         # [N]
    honest: jax.Array        # [N]
    slot_commits: jax.Array      # [S]
    slot_commit_tick: jax.Array  # [S]
    slot_propose_tick: jax.Array  # [S]


def max_arrival_offset(cfg) -> int:
    """Latest in-round event offset: commit sent at (hi-1)+rt_hi-1 arriving
    +hi-1 later."""
    lo, hi = cfg.one_way_range()
    rt_lo, rt_hi = cfg.roundtrip_range()
    return (hi - 1) + (rt_hi - 1) + (hi - 1)


def eligible(cfg) -> bool:
    ser = cfg.serialization_ticks(cfg.pbft_block_bytes)
    return (
        cfg.protocol == "pbft"
        and cfg.topology == "full"
        and cfg.delivery == "stat"
        # drops are fine while the leader never changes: every wave is
        # independently thinned (same binomial model as the tick engine's
        # stat channels).  With view changes enabled, a dropped VIEW_CHANGE
        # diverges leader beliefs and rounds stop being single-proposer —
        # that combination stays on the tick engine.  Windowed mode also
        # stays there: a pp-dropped receiver's commit crossing lands in the
        # tick engine's stale-tenant/unattributed bookkeeping, which this
        # path (no vote table) cannot reproduce; exact mode credits by
        # window identity in both engines.
        and (
            cfg.faults.drop_prob == 0.0
            or (
                cfg.pbft_view_change_num == 0
                and pbft_tick.eff_window(cfg) >= cfg.pbft_max_slots
            )
        )
        and not cfg.faults.byz_forge
        and not cfg.queued_links  # serial-pipe backlog is cross-round state
        and ser + max_arrival_offset(cfg) < cfg.pbft_block_interval_ms
    )


def init(cfg, key=None):
    n, s = cfg.n, cfg.pbft_max_slots
    alive, honest = fault_masks(cfg, n)
    zi = lambda *sh: jnp.zeros(sh, jnp.int32)
    state = PbftRoundState(
        v=jnp.ones((n,), jnp.int32),
        leader=zi(n),
        next_n=zi(n),
        rounds_sent=zi(n),
        block_num=zi(n),
        unattributed=zi(n),
        view_changes=zi(n),
        alive=alive,
        honest=honest,
        slot_commits=zi(s),
        slot_commit_tick=jnp.full((s,), -1, jnp.int32),
        slot_propose_tick=jnp.full((s,), _NEVER, jnp.int32),
    )
    return state, ()


finalize = pbft_tick.finalize  # same GLOBAL_FIELDS partial-combining


def _psum(x, axis):
    return x if axis is None else jax.lax.psum(x, axis)


def _pmax(x, axis):
    return x if axis is None else jax.lax.pmax(x, axis)


def _crossing_loop(rows, need, clean: bool, start=None):
    """Threshold crossings of a vote counter fed bucket-by-bucket.

    ``rows``: B arrival-count rows ``[N]`` in tick order.  Replicates the tick
    engine's per-tick rule (pbft.step / pbft-node.cc:231,248): counter +=
    arrivals; crossed iff arrivals > 0 and counter >= need; on crossing the
    counter resets to 0 (reference fidelity; the whole batch is consumed) —
    ``clean`` latches instead, so only the first crossing fires (the counter
    never resets before it, so the running sum IS the counter up to there).
    ``start`` is the counter carried in.

    One elementwise pass over N with B unrolled, rows in and rows out: a
    ``[B, N]`` array assembled from the rows costs a round a write and a read
    of its own on the chip (PERF.md section 6, PR 45).

    Returns (crossed: B bool rows ``[N]``, n_crossings ``[N]``, first_bucket
    ``[N]`` — index of the first crossing, B if none).
    """
    b = len(rows)
    cnt = jnp.zeros_like(rows[0]) if start is None else start
    fired = jnp.zeros(rows[0].shape, bool)
    n_cross = jnp.zeros(rows[0].shape, jnp.int32)
    first = jnp.full(rows[0].shape, b, jnp.int32)
    crossed = []
    for k, arr in enumerate(rows):
        cnt = cnt + arr
        hit = (arr > 0) & (cnt >= need)
        if clean:
            hit = hit & ~fired
        else:
            cnt = jnp.where(hit, 0, cnt)
        first = jnp.where(hit & ~fired, k, first)
        fired = fired | hit
        n_cross = n_cross + hit
        crossed.append(hit)
    return crossed, n_cross, first


def step_round(cfg, state: PbftRoundState, r, key):
    """Advance one whole block interval starting at t0 = r * interval.

    Events are masked against the simulation window end (``cfg.ticks``): the
    tick engine truncates a final round's message wave mid-flight (sends
    happen at the block tick, but arrivals past the window never land), and
    the masks reproduce exactly that."""
    n, s = cfg.n, cfg.pbft_max_slots
    axis = cfg.mesh_axis
    bt = cfg.pbft_block_interval_ms
    lo, hi = cfg.one_way_range()
    rt_lo, rt_hi = cfg.roundtrip_range()
    b1 = hi - lo
    b2 = rt_hi - rt_lo
    clean = cfg.fidelity == "clean"
    smode = cfg.eff_stat_sampler
    ow_probs = delay_ops.uniform_probs(lo, hi)
    rt_probs = delay_ops.roundtrip_probs(lo, hi)
    # constant block-serialization offset: the tick engine pushes the
    # PRE_PREPARE at lo + ser (pbft.py), rigidly shifting the whole wave
    ser = cfg.serialization_ticks(cfg.pbft_block_bytes)
    t0 = r * bt
    n_loc = state.v.shape[0]
    ids = _global_ids(n_loc, axis)
    tkey = jax.random.fold_in(key, t0)

    with jax.named_scope("pbft.round.block"):
        # ---- A. block tick: SendBlock + view-change draw (pbft.step "timers") ---
        send = (
            (state.leader == ids)
            & (state.next_n < min(cfg.pbft_max_rounds, s))
            & state.alive
        )
        slot_p1 = _pmax(jnp.max(jnp.where(send, state.next_n + 1, 0)), axis)  # 0=none
        active = slot_p1 > 0
        slot = slot_p1 - 1
        rounds_sent = state.rounds_sent + send
        next_n = jnp.where(send, state.next_n + 1, state.next_n)
        # receivers learn the slot when the PRE_PREPARE lands (same round)
        next_n = jnp.maximum(next_n, slot_p1)
        slot_idx = jnp.where(active, slot, s)  # s = out-of-bounds drop
        slot_propose_tick = state.slot_propose_tick.at[slot_idx].min(
            jnp.where(active, jnp.int32(t0), _NEVER), mode="drop"
        )

        # view change: EXACTLY the tick engine's draw (same channel, same tick key)
        k_u = chan_key(tkey, Channel.VIEW_CHANGE)
        if axis is not None:
            k_u = jax.random.fold_in(k_u, jax.lax.axis_index(axis))
        u = jax.random.randint(k_u, (n_loc,), 0, cfg.pbft_view_change_den)
        trigger = send & (u < cfg.pbft_view_change_num)
        any_trigger = _pmax(jnp.max(trigger.astype(jnp.int32)), axis) > 0
        new_leader = _pmax(jnp.max(jnp.where(trigger, (state.leader + 1) % n, 0)), axis)
        view_changes = state.view_changes + trigger
        # no drops: every node (sender immediately, receivers within the round)
        # ends the round agreeing on (v+1, new_leader) — pbft-node.cc:271-280
        v = jnp.where(any_trigger, state.v + 1, state.v)
        leader = jnp.where(any_trigger, new_leader, state.leader)

    with jax.named_scope("pbft.round.prepare"):
        # ---- B. PRE_PREPARE arrivals + PREPARE round trips ----------------------
        # per-receiver arrival offset ser + d_j, d_j ~ U{lo..hi-1}; proposer excluded
        t_end = jnp.int32(cfg.ticks)  # arrivals at tick >= t_end never land
        k_pp = chan_key(tkey, Channel.DELAY_BCAST2)
        d_j = jax.random.randint(_shard_key(k_pp, axis), (n_loc,), lo, hi, jnp.int32)
        recv = active & state.alive & ~send & (t0 + ser + d_j < t_end)
        drop = cfg.faults.drop_prob
        if drop > 0.0:
            recv = recv & jax.random.bernoulli(
                _shard_key(jax.random.fold_in(k_pp, 0x0D0D), axis),
                1.0 - drop, (n_loc,),
            )
        # every receiver broadcasts PREPARE on arrival; honest alive peers reply
        # SUCCESS (short-circuited round trip, pbft-node.cc:212-221)
        voters = state.alive & state.honest
        n_voters = _psum(voters.astype(jnp.int32).sum(), axis)
        k_rt = chan_key(tkey, Channel.DELAY_ROUNDTRIP)
        # the tick engine's own stat round trip (per-receiver reply counts
        # with (1-p)^2 two-leg thinning under drops), its chain taken a bucket
        # at a time: B2 rows [N], bucket k -> tick t0 + ser + d_j + rt_lo + k
        k_rt, m_rt = dv._roundtrip_stat_m(
            k_rt, recv, n_voters - voters.astype(jnp.int32), drop, axis, smode
        )
        rt_counts = [
            c.astype(jnp.int32) * (t0 + ser + d_j + rt_lo + k < t_end)
            for k, c in enumerate(
                delay_ops.bucket_count_rows(k_rt, m_rt, rt_probs, smode)
            )
        ]
        crossed_p, _, _ = _crossing_loop(rt_counts, cfg.pbft_prepare_need, clean)
        commit_send = [c & voters for c in crossed_p]  # B2 rows [N]

    with jax.named_scope("pbft.round.commit"):
        # ---- C. COMMIT waves -> finality ---------------------------------------
        # sender j's k-th crossing happens at offset o = ser + d_j + rt_lo + k;
        # group send counts by absolute offset o = (d_j - lo) + k: row o of
        # send_at is the sum of the rows whose two indices add to o (a length-
        # b1 convolution along the tiny offset axis, integer sums)
        w_send = b1 + b2 - 1  # distinct send offsets
        off_base = ser + lo + rt_lo
        oh_d = [d_j == lo + e for e in range(b1)]  # b1 rows [N]
        send_at = [
            sum(
                (commit_send[o - e] & oh_d[e]).astype(jnp.int32)
                for e in range(b1) if 0 <= o - e < b2
            )
            for o in range(w_send)
        ]
        # [w_send] global commit senders, one collective under a mesh axis
        totals = _psum(jnp.stack([row.sum() for row in send_at]), axis)
        # receiver m hears, per send offset o, totals[o] - own sends at o,
        # spread multinomially over the one-way buckets: ONE chain over the
        # w_send rows (one draw of z words for all of them, as the stacked
        # [w_send, N] call made it), each row's sampler math left to fuse
        # into the sums that read it
        k_cm = chan_key(tkey, Channel.DELAY_BCAST)
        w_arr = w_send + b1 - 1
        m_all = [
            jnp.where(state.alive, totals[o] - send_at[o], 0)
            for o in range(w_send)
        ]
        if drop > 0.0:
            # the thinning draws over the whole [w_send, N] shape under one key
            m_all = list(jnp.round(delay_ops.binom(
                _shard_key(jax.random.fold_in(k_cm, 0x0D12), axis),
                jnp.stack(m_all), 1.0 - drop, smode,
            )).astype(jnp.int32))
        cnt_all = [
            [c.astype(jnp.int32) for c in bucket]
            for bucket in delay_ops.bucket_count_rows(
                _shard_key(k_cm, axis), m_all, ow_probs, smode
            )
        ]  # b1 buckets of w_send rows [N]
        # fold send offset + travel bucket into the arrival axis (i = o + e);
        # bucket i of `arrivals` lands at tick t0 + (off_base + o) + (lo + e)
        # = t0 + off_base + lo + i
        arrivals = [
            sum(cnt_all[e][i - e] for e in range(b1) if 0 <= i - e < w_send)
            * (t0 + off_base + lo + i < t_end)
            for i in range(w_arr)
        ]
        crossed_c, n_cross_c, _ = _crossing_loop(
            arrivals, cfg.pbft_commit_need, clean
        )
        first_commit = functools.reduce(jnp.logical_or, crossed_c) & active
        block_num = state.block_num + jnp.where(active, n_cross_c, 0)
        # last finalization tick of this slot (pbft.step scatters per-tick max)
        last_local = jnp.max(functools.reduce(jnp.maximum, [
            jnp.where(c, t0 + off_base + lo + i, -1)
            for i, c in enumerate(crossed_c)
        ]))
        last_tick = _pmax(last_local, axis)
        n_first = _psum(first_commit.astype(jnp.int32).sum(), axis)
        slot_commits = state.slot_commits.at[slot_idx].add(
            jnp.where(active, first_commit.astype(jnp.int32).sum(), 0), mode="drop"
        )
        slot_commit_tick = state.slot_commit_tick.at[slot_idx].max(
            jnp.where(active & (n_first > 0), last_tick, -1), mode="drop"
        )

    return state.replace(
        v=v,
        leader=leader,
        next_n=next_n,
        rounds_sent=rounds_sent,
        block_num=block_num,
        view_changes=view_changes,
        slot_commits=slot_commits,
        slot_commit_tick=slot_commit_tick,
        slot_propose_tick=slot_propose_tick,
    )


def scan_rounds(cfg, state, key, with_probe: bool = False):
    """Scan every block interval inside the simulation window.

    Shared by the single-chip runner (runner.make_sim_fn) and the node-
    sharded path (parallel/shard.py), so the truncation semantics — round
    r runs iff its block tick r*interval < cfg.ticks, with the round body
    masking arrivals past the window — live in exactly one place.

    ``with_probe=True`` (utils/trace.run_traced) additionally emits the
    standard pbft probe (utils/trace.probe reads the shared field names)
    as scan ``ys`` — one sample per ROUND, the state after that round's
    whole wave — and returns ``(state, ys)``.  A CALLABLE ``with_probe``
    (obsim/build.py) is used as the probe function ``state -> pytree``
    instead of the trace one, same contract.  The state trajectory is
    bit-identical either way (the probe only reads)."""
    from blockchain_simulator_tpu.utils import trace as trace_mod

    if with_probe is True:
        probe_fn = functools.partial(trace_mod.probe, cfg)
    else:
        probe_fn = with_probe or None

    bt = cfg.pbft_block_interval_ms
    r_last = (cfg.ticks - 1) // bt
    if r_last < 1:
        if probe_fn is not None:
            empty = jax.tree.map(
                lambda x: jnp.zeros((0,), x.dtype), probe_fn(state)
            )
            return state, empty
        return state

    def body(st, r):
        st = step_round(cfg, st, r, key)
        return st, probe_fn(st) if probe_fn is not None else ()

    state, ys = jax.lax.scan(body, state, jnp.arange(1, r_last + 1))
    return (state, ys) if probe_fn is not None else state


def metrics(cfg, state) -> dict:
    """Same measurement surface as the tick engine (pbft.metrics)."""
    return pbft_tick.metrics(cfg, state)
