"""The round engine as it stood before PR 45, kept as the plain reference of
tests/test_pbft_round.py: ``_crossing_loop`` and ``step_round`` of
models/pbft_round.py, moved here VERBATIM, over the stacked samplers they
called (``dv.roundtrip_reply_counts_stat``, ``delay_ops.sample_bucket_counts``,
which PR 45 leaves as they were).  Every per-bucket quantity is a stacked
``[B, N]`` array here; the engine keeps B rows.  Same keys, same draws, same
arithmetic: the final states must be bit-equal.

Not a test file: nothing here is collected.
"""

import jax
import jax.numpy as jnp

from blockchain_simulator_tpu.models.pbft_round import (
    PbftRoundState,
    _NEVER,
    _pmax,
    _psum,
)
from blockchain_simulator_tpu.ops import delay as delay_ops
from blockchain_simulator_tpu.ops import delivery as dv
from blockchain_simulator_tpu.ops.delivery import _global_ids, _shard_key
from blockchain_simulator_tpu.utils.prng import Channel, chan_key


def _crossing_loop(buckets, need, clean: bool, start=None):
    """Threshold crossings of a vote counter fed bucket-by-bucket.

    ``buckets``: [B, N] arrival counts in tick order.  Replicates the tick
    engine's per-tick rule (pbft.step / pbft-node.cc:231,248): counter +=
    arrivals; crossed iff arrivals > 0 and counter >= need; on crossing the
    counter resets to 0 (reference fidelity; the whole batch is consumed) —
    ``clean`` latches so only the first crossing fires.

    Returns (crossed [B, N] bool, n_crossings [N], first_bucket [N] — index
    of first crossing, B if none).
    """
    b, n = buckets.shape
    if clean:
        # latched first crossing only: the counter never resets before it
        # fires, so the running cumulative sum IS the counter up to the
        # crossing, and the crossing is the FIRST bucket with arrivals at or
        # past the threshold (argmax of a bool picks the first True).  The
        # running sums are built by an unrolled add chain — NOT jnp.cumsum,
        # whose XLA:CPU lowering measured ~2.5 ms/round slower — and the
        # latch collapses to ~4 [B, N] ops instead of ~6 [N] ops per bucket.
        run = jnp.zeros((n,), jnp.int32) if start is None else start
        csums = []
        for k in range(b):
            run = run + buckets[k]
            csums.append(run)
        csum = jnp.stack(csums)  # [B, N]
        qual = (buckets > 0) & (csum >= need)
        any_q = qual.any(axis=0)
        first = jnp.argmax(qual, axis=0)  # first qualifying bucket
        crossed_mat = (jnp.arange(b)[:, None] == first[None, :]) & any_q[None, :]
        n_cross = any_q.astype(jnp.int32)
        return crossed_mat, n_cross, jnp.where(any_q, first, b)
    cnt = jnp.zeros((n,), jnp.int32) if start is None else start
    crossed_list = []
    for k in range(b):
        arr = buckets[k]
        cnt = cnt + arr
        crossed = (arr > 0) & (cnt >= need)
        cnt = jnp.where(crossed, 0, cnt)
        crossed_list.append(crossed)
    crossed_mat = jnp.stack(crossed_list)  # [B, N]
    n_cross = crossed_mat.astype(jnp.int32).sum(axis=0)
    first = jnp.argmax(crossed_mat, axis=0)
    first = jnp.where(crossed_mat.any(axis=0), first, b)
    return crossed_mat, n_cross, first


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
        # the tick engine's own stat round-trip helper: per-receiver reply
        # counts with (1-p)^2 two-leg thinning under drops
        rt_counts = dv.roundtrip_reply_counts_stat(
            k_rt, recv, n_voters - voters.astype(jnp.int32), rt_probs, drop,
            axis=axis, mode=smode,
        )  # [B2, N] reply counts, bucket k -> tick t0 + ser + d_j + rt_lo + k
        rt_land = (t0 + ser + d_j[None, :] + rt_lo + jnp.arange(b2)[:, None]) < t_end
        rt_counts = rt_counts * rt_land.astype(jnp.int32)
        crossed_p, _, _ = _crossing_loop(rt_counts, cfg.pbft_prepare_need, clean)
        commit_send = crossed_p & (state.alive & state.honest)[None, :]  # [B2, N]

    with jax.named_scope("pbft.round.commit"):
        # ---- C. COMMIT waves -> finality ---------------------------------------
        # sender j's k-th crossing happens at offset o = ser + d_j + rt_lo + k;
        # group send counts by absolute offset o = (d_j - lo) + k: a length-b1
        # polynomial convolution along the tiny offset axis, materialized as b1
        # shifted pad-and-add terms instead of the former w_send x b2 nest of
        # masked [N] adds — dispatch count, not bytes, dominates the round step
        # on the CPU fallback path (VERDICT r5 weak-#4).  NOT a scatter-add:
        # XLA:CPU serializes scatter updates (measured 2.6x slower end-to-end).
        w_send = b1 + b2 - 1  # distinct send offsets
        off_base = ser + lo + rt_lo
        oh_d = d_j[None, :] == (lo + jnp.arange(b1))[:, None]  # [b1, N]
        cs = commit_send.astype(jnp.int32)
        send_at = sum(
            jnp.pad(cs * oh_d[e][None, :], ((e, b1 - 1 - e), (0, 0)))
            for e in range(b1)
        )  # [w_send, N]
        totals = _psum(send_at.sum(axis=1), axis)  # [w_send] global commit senders
        # receiver m hears, per send offset o, totals[o] - own sends at o,
        # spread multinomially over the one-way buckets.  One batched [W_send, N]
        # chain instead of W_send independent [N] chains: identical multinomial
        # statistics (sample_bucket_counts is elementwise over its leading
        # shape), ~W_send fewer PRNG/elementwise dispatches per round — the
        # dominant cost of a round step on the CPU fallback path.
        k_cm = chan_key(tkey, Channel.DELAY_BCAST)
        w_arr = w_send + b1 - 1
        m_all = jnp.where(state.alive[None, :], totals[:, None] - send_at, 0)
        if drop > 0.0:
            m_all = jnp.round(delay_ops.binom(
                _shard_key(jax.random.fold_in(k_cm, 0x0D12), axis),
                m_all, 1.0 - drop, smode,
            )).astype(jnp.int32)
        cnt_all = delay_ops.sample_bucket_counts(
            _shard_key(k_cm, axis), m_all, ow_probs, smode
        )  # [b1, w_send, N]
        # fold send offset + travel bucket into the arrival axis (i = o + e):
        # the same anti-diagonal pad-and-add convolution as send_at above,
        # replacing the b1 x w_send nest of [N] adds
        arrivals = sum(
            jnp.pad(cnt_all[e], ((e, b1 - 1 - e), (0, 0)))
            for e in range(b1)
        )  # [w_arr, N]
        arr_land = (t0 + off_base + lo + jnp.arange(w_arr)) < t_end  # [w_arr]
        arrivals = arrivals * arr_land.astype(jnp.int32)[:, None]
        crossed_c, n_cross_c, _ = _crossing_loop(
            arrivals, cfg.pbft_commit_need, clean
        )
        first_commit = crossed_c.any(axis=0) & active
        block_num = state.block_num + jnp.where(active, n_cross_c, 0)
        # last finalization tick of this slot (pbft.step scatters per-tick max;
        # arrival bucket tau -> tick t0 + off_base + lo + tau... offsets: bucket
        # index i of `arrivals` is send offset o + e, arrival tick = t0 + o_abs
        # + e_abs = t0 + (off_base + o) + (lo + e) -> t0 + off_base + lo + i
        bucket_idx = jnp.arange(w_arr, dtype=jnp.int32)[:, None]
        last_local = jnp.max(
            jnp.where(crossed_c, t0 + off_base + lo + bucket_idx, -1)
        )
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
