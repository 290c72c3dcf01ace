"""Message delivery: senders → per-bucket ring contributions.

Each function turns "who is sending what this tick" into a contribution tensor
``[B, ...receiver dims]`` to be ``ring_push``-ed, where ``B`` spans the delay
distribution's support (offset ``lo``).  The reference's per-message
``Simulator::Schedule(getRandomDelay(), ...)`` (SURVEY.md C8) becomes either an
exact per-edge sample (*dense*) or a statistically exact per-receiver bucket
count (*stat*, for full-mesh count-consumed channels at large N).

SPMD: every function takes ``axis`` — the name of a mesh axis over which the
node dimension is sharded (None = unsharded).  Inside ``shard_map`` the
receiver axis stays local while sender-side quantities are globalized with XLA
collectives (``all_gather`` for masks/values, ``psum`` for totals); this is the
TPU-native replacement for the reference's simulated UDP fan-out
(pbft-node.cc:350-368) — message exchange rides ICI, not a socket model.

Conventions: senders never deliver to themselves (the reference's peer lists
exclude self, blockchain-simulator.cc:44-45); ``send`` masks are already
fault-masked by the caller; ``drop_prob`` models lossy edges (a capability
absent in the reference — its simulated links never drop).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from blockchain_simulator_tpu.ops import mesh as mesh_ops
from blockchain_simulator_tpu.ops import scopes
from blockchain_simulator_tpu.ops.ring import node_minor
from blockchain_simulator_tpu.ops.delay import (
    binom,
    bucket_count_chain,
    sample_bucket_counts,
    sample_edge_delays,
)

_names: list = []
_scoped = scopes.scoped("ops.delivery", _names)


def _shard_key(key, axis):
    """Decorrelate per-shard sampling (each edge must be drawn exactly once,
    by the shard that consumes it)."""
    if axis is None:
        return key
    return jax.random.fold_in(key, lax.axis_index(axis))


def _gather(x, axis):
    """Local [n_loc, ...] -> global [N, ...] along the node axis."""
    if axis is None:
        return x
    return mesh_ops.gather(x, axis)


def _global_ids(n_loc: int, axis):
    """Global node ids of this shard's rows."""
    base = 0 if axis is None else lax.axis_index(axis) * n_loc
    return base + jnp.arange(n_loc)


def _bucket_iota(lo: int, hi: int, ndim: int):
    """``[B, 1, ...]`` bucket values ``lo..hi-1`` broadcastable against a
    rank-``ndim`` delay tensor — the vectorized replacement for the
    per-bucket ``d == lo + b`` python loops, which XLA:CPU compiled as B
    separate compare+select passes over the edge tensor; one broadcast
    compare fuses into a single traversal."""
    return jnp.arange(lo, hi, dtype=jnp.int32).reshape((-1,) + (1,) * ndim)


def _edge_hits(key, send, lo: int, hi: int, drop_prob: float = 0.0, axis=None,
               send_global=None, impl: str = "threefry"):
    """[B, N_send_global, N_recv_local] 0/1 delivery indicators, self-edges
    removed.  Delays are sampled receiver-side (each edge's delay is consumed
    by exactly one shard, so per-shard independent draws are exact).
    ``send_global`` lets callers reuse an already-gathered sender mask;
    ``impl`` selects the per-edge bit source (SimConfig.edge_sampler)."""
    n_loc = send.shape[0]
    send_g = _gather(send, axis) if send_global is None else send_global
    n_glob = send_g.shape[0]
    k = _shard_key(key, axis)
    d = sample_edge_delays(k, (n_glob, n_loc), lo, hi, impl)
    notself = (jnp.arange(n_glob)[:, None] != _global_ids(n_loc, axis)[None, :])
    mask = send_g.astype(jnp.int32)[:, None] * notself.astype(jnp.int32)
    if drop_prob > 0.0:
        keep = jax.random.bernoulli(
            jax.random.fold_in(k, 0x0D0D), 1.0 - drop_prob, (n_glob, n_loc)
        )
        mask = mask * keep.astype(jnp.int32)
    return (d[None] == _bucket_iota(lo, hi, d.ndim)).astype(jnp.int32) * mask[None]


# --------------------------------------------------------------------------- #
# dense (exact per-edge) delivery                                             #
# --------------------------------------------------------------------------- #


@_scoped
def bcast_counts_dense(key, send, lo, hi, drop_prob=0.0, axis=None,
                       impl="threefry"):
    """Broadcast → per-receiver arrival counts.  Returns [B, N_loc]."""
    return _edge_hits(key, send, lo, hi, drop_prob, axis, impl=impl).sum(1)


@_scoped
def bcast_value_max_dense(key, send, value, lo, hi, drop_prob=0.0, axis=None,
                          impl="threefry"):
    """Broadcast of a per-sender value (>0; 0 = empty), max-combined at the
    receiver.  Returns [B, N_loc]."""
    hits = _edge_hits(key, send, lo, hi, drop_prob, axis, impl=impl)
    value_g = _gather(value, axis)
    return (hits * value_g.astype(jnp.int32)[None, :, None]).max(1)


@_scoped
def bcast_slots_dense(key, slot_mat, lo, hi, drop_prob=0.0, axis=None,
                      impl="threefry"):
    """Slot-keyed broadcast (e.g. PBFT messages carrying seq no n): sender i
    broadcasts ``slot_mat[i, s]`` copies per slot (int counts; >1 only for
    Byzantine vote flooding).  Returns arrival counts per (receiver, slot):
    [B, N_loc, S].

    Note: when a sender is active in several slots (or copies) in the same
    tick, those broadcasts share one delay draw per edge (a documented
    simplification; the reference draws per message, pbft-node.cc:364)."""
    slot_g = _gather(slot_mat.astype(jnp.int32), axis)
    send = slot_mat.max(axis=1) > 0
    hits = _edge_hits(
        key, send, lo, hi, drop_prob, axis, send_global=slot_g.max(axis=1) > 0,
        impl=impl,
    )  # [B, N_glob, N_loc] 0/1
    return jnp.einsum("bij,is->bjs", hits, slot_g)


@_scoped
def bcast_window_value_max_dense(key, value_mat, lo, hi, drop_prob=0.0, axis=None,
                                 impl="threefry"):
    """Per-window value broadcast (PBFT PRE_PREPARE carrying the slot id):
    sender i announces ``value_mat[i, w]`` (>0; 0 = empty) for window w; the
    receiver max-combines per window.  Returns [B, N_loc, W].

    Windows of one sender share one delay draw per edge (same simplification
    as bcast_slots_dense)."""
    value_g = _gather(value_mat.astype(jnp.int32), axis)  # [N_glob, W]
    send = value_mat.max(axis=1) > 0
    hits = _edge_hits(
        key, send, lo, hi, drop_prob, axis, send_global=value_g.max(axis=1) > 0,
        impl=impl,
    )  # [B, N_glob, N_loc] 0/1
    return (hits[:, :, :, None] * value_g[None, :, None, :]).max(axis=1)


@_scoped
def bcast_window_value_max_stat(key, value_mat, probs: np.ndarray, drop_prob=0.0,
                                axis=None):
    """Stat version of bcast_window_value_max_dense for few senders per
    window (the PBFT leader): deliver each window's max announced value with
    one independent per-(receiver, window) delay draw.  A receiver whose own
    announcement equals the max is the sender — it gets nothing (the
    reference leader never hears its own PRE_PREPARE).  Returns [B, N_loc, W]."""
    k = _shard_key(key, axis)
    vm = value_mat.astype(jnp.int32)
    n, w = vm.shape
    vmax = vm.max(axis=0)  # [W]
    if axis is not None:
        vmax = lax.pmax(vmax, axis)
    nb = len(probs)
    d = jax.random.categorical(k, jnp.log(jnp.asarray(probs) + 1e-30), shape=(n, w))
    recv = (vmax[None, :] > 0) & (vm < vmax[None, :])
    if drop_prob > 0.0:
        keep = jax.random.bernoulli(
            jax.random.fold_in(k, 0x0D14), 1.0 - drop_prob, (n, w)
        )
        recv = recv & keep
    val = recv.astype(jnp.int32) * vmax[None, :]
    return (d[None] == _bucket_iota(0, nb, d.ndim)).astype(jnp.int32) * val[None]


@_scoped
def roundtrip_reply_counts_dense(
    key, send, lo, hi, drop_prob=0.0, peer_mask=None, axis=None,
    impl="threefry",
):
    """Short-circuited request/reply round trip: sender i broadcasts, every
    peer replies unconditionally and instantly, the reply travels back with an
    independent delay.  Used where the peer's state does not affect the reply
    (PBFT PREPARE → PREPARE_RES SUCCESS, pbft-node.cc:212-221; Raft HEARTBEAT →
    HEARTBEAT_RES SUCCESS, raft-node.cc:170-193).  ``peer_mask`` (local
    [n_loc]) restricts which peers reply (crashed/Byzantine exclusion).
    Returns reply counts at the original (local) sender: [B2, N_loc],
    offset 2*lo, B2 = 2*(hi-lo)-1.

    Sharded: the *sender* consumes both legs' delays, so delays are sampled
    sender-side over the gathered peer axis."""
    n_loc = send.shape[0]
    peers = jnp.ones((n_loc,), bool) if peer_mask is None else peer_mask
    peers_g = _gather(peers, axis)
    n_glob = peers_g.shape[0]
    k = _shard_key(key, axis)
    d1 = sample_edge_delays(jax.random.fold_in(k, 1), (n_loc, n_glob), lo, hi, impl)
    d2 = sample_edge_delays(jax.random.fold_in(k, 2), (n_loc, n_glob), lo, hi, impl)
    total = d1 + d2  # delay until the reply reaches the sender
    notself = (_global_ids(n_loc, axis)[:, None] != jnp.arange(n_glob)[None, :])
    mask = (
        send.astype(jnp.int32)[:, None]
        * notself.astype(jnp.int32)
        * peers_g.astype(jnp.int32)[None, :]
    )
    if drop_prob > 0.0:
        # either leg can drop
        keep = jax.random.bernoulli(
            jax.random.fold_in(k, 0x0D0E), (1.0 - drop_prob) ** 2, (n_loc, n_glob)
        )
        mask = mask * keep.astype(jnp.int32)
    lo2 = 2 * lo
    nb = 2 * (hi - lo) - 1
    # one broadcast compare + reduction instead of nb masked passes over the
    # [N_loc, N_glob] edge tensor (integer sums — bit-equal either way)
    return (
        (total[None] == _bucket_iota(lo2, lo2 + nb, total.ndim)).astype(jnp.int32)
        * mask[None]
    ).sum(2)


@_scoped
def unicast_reply_counts_dense(key, reply, lo, hi, drop_prob=0.0, axis=None,
                               impl="threefry"):
    """Route per-(replier, requester) reply counts back to each requester.
    ``reply[r, c]`` = number of (identical, count-consumed) replies local
    node r sends global node c this tick.  Returns [B, N_loc] indexed by
    *local* requester — sharded, the contribution must be summed across
    shards (the repliers), which is a ``psum`` over the axis."""
    n_loc, n_glob = reply.shape
    k = _shard_key(key, axis)
    d = sample_edge_delays(k, (n_loc, n_glob), lo, hi, impl)
    notself = (_global_ids(n_loc, axis)[:, None] != jnp.arange(n_glob)[None, :])
    mask = notself.astype(jnp.int32)
    if drop_prob > 0.0:
        keep = jax.random.bernoulli(
            jax.random.fold_in(k, 0x0D0F), 1.0 - drop_prob, (n_loc, n_glob)
        )
        mask = mask * keep.astype(jnp.int32)
    r = reply.astype(jnp.int32) * mask
    out_g = (
        r[None] * (d[None] == _bucket_iota(lo, hi, d.ndim)).astype(jnp.int32)
    ).sum(1)  # [B, N_glob]
    if axis is None:
        return out_g
    out_g = lax.psum(out_g, axis)
    # slice this shard's requesters
    start = lax.axis_index(axis) * n_loc
    return lax.dynamic_slice_in_dim(out_g, start, n_loc, axis=1)


@_scoped
def bcast_matrix_dense(key, send, value, lo, hi, drop_prob=0.0, axis=None,
                       impl="threefry"):
    """Identity-preserving broadcast for request channels whose handling
    depends on receiver state at arrival (Raft VOTE_REQ, Paxos REQUEST_*).
    ``value`` (>0 per sender; 0 = empty) lands at ``[b, receiver_local,
    sender_global]``.  Returns [B, N_loc, N_glob] (max-combined into a matrix
    ring)."""
    hits = _edge_hits(key, send, lo, hi, drop_prob, axis, impl=impl)  # [B, glob, loc]
    value_g = _gather(value, axis)
    return jnp.swapaxes(hits * value_g.astype(jnp.int32)[None, :, None], 1, 2)


# --------------------------------------------------------------------------- #
# stat (aggregated, statistically exact) delivery                             #
# --------------------------------------------------------------------------- #


@_scoped
def bcast_counts_stat(key, n_senders, is_sender, probs: np.ndarray, drop_prob=0.0, axis=None,
                      mode="exact"):
    """Full-mesh broadcast arrival counts without materializing edges.

    Each receiver j hears from ``n_senders - is_sender[j]`` peers; its arrival
    buckets are Multinomial over the delay distribution, independent across
    receivers (distinct edges ⇒ independent delays).  ``n_senders`` must be
    the *global* sender count (psum'ed by the caller when sharded).
    Returns [B, N_loc]."""
    k = _shard_key(key, axis)
    m = jnp.asarray(n_senders, jnp.int32) - is_sender.astype(jnp.int32)
    if drop_prob > 0.0:
        m = jnp.round(
            binom(jax.random.fold_in(k, 0x0D10), m, 1.0 - drop_prob, mode)
        ).astype(jnp.int32)
    return sample_bucket_counts(k, m, probs, mode)


def _slots_stat_m(key, slot_mat, drop_prob, axis, mode):
    """(shard key, per-(receiver, slot) sender counts) of the stat slot
    broadcast — the shared front half of :func:`bcast_slots_stat` and the
    fused :func:`push_bcast_slots_stat` (identical keys and arithmetic, so
    the two are bit-equal)."""
    k = _shard_key(key, axis)
    sm = slot_mat.astype(jnp.int32)
    totals = sm.sum(axis=0)
    if axis is not None:
        totals = lax.psum(totals, axis)
    m = totals[None, :] - sm  # [N_loc, S]
    if drop_prob > 0.0:
        m = jnp.round(
            binom(jax.random.fold_in(k, 0x0D12), m, 1.0 - drop_prob, mode)
        ).astype(jnp.int32)
    return k, m


@_scoped
def bcast_slots_stat(key, slot_mat, probs: np.ndarray, drop_prob=0.0, axis=None,
                     mode="exact"):
    """Stat version of bcast_slots_dense: receiver j hears, per slot s,
    from ``(Σ_i slot_mat[i,s]) - slot_mat[j,s]`` senders; arrival buckets are
    multinomial per (receiver, slot).  Returns [B, N_loc, S]."""
    k, m = _slots_stat_m(key, slot_mat, drop_prob, axis, mode)
    return sample_bucket_counts(k, m, probs, mode)


@_scoped
def bcast_value_max_stat(key, value, probs: np.ndarray, drop_prob=0.0, axis=None):
    """Stat version of bcast_value_max_dense for ≤-a-few senders (e.g. PBFT
    VIEW_CHANGE from the leader): deliver the max announced value to every
    receiver with one per-receiver delay draw.  Returns [B, N_loc]."""
    k = _shard_key(key, axis)
    n = value.shape[0]
    vmax = value.astype(jnp.int32).max()
    if axis is not None:
        vmax = lax.pmax(vmax, axis)
    nb = len(probs)
    d = jax.random.categorical(k, jnp.log(jnp.asarray(probs) + 1e-30), shape=(n,))
    sent = (vmax > 0).astype(jnp.int32)
    if drop_prob > 0.0:
        keep = jax.random.bernoulli(
            jax.random.fold_in(k, 0x0D13), 1.0 - drop_prob, (n,)
        )
        sent = sent * keep.astype(jnp.int32)
    # a node that announced the (same, max) value already applied it locally;
    # re-delivery to it is a harmless no-op, matching max-combine semantics
    return (
        (d[None] == _bucket_iota(0, nb, d.ndim)).astype(jnp.int32)
        * (sent * vmax)[None]
    )


def _roundtrip_stat_m(key, send, n_peers, drop_prob, axis, mode):
    """(shard key, per-sender reply counts) of the stat round trip — the
    shared front half of :func:`roundtrip_reply_counts_stat` and the fused
    :func:`push_roundtrip_reply_counts_stat`."""
    k = _shard_key(key, axis)
    m = send.astype(jnp.int32) * jnp.asarray(n_peers, jnp.int32)
    if drop_prob > 0.0:
        p_keep = (1.0 - drop_prob) ** 2
        m = jnp.round(
            binom(jax.random.fold_in(k, 0x0D11), m, p_keep, mode)
        ).astype(jnp.int32)
    return k, m


@_scoped
def roundtrip_reply_counts_stat(
    key, send, n_peers, rt_probs: np.ndarray, drop_prob=0.0, axis=None, mode="exact"
):
    """Stat version of roundtrip_reply_counts_dense: each active sender gets
    ``n_peers`` (global count, per local sender) replies multinomially spread
    over the round-trip distribution.  Returns [B2, N_loc]."""
    k, m = _roundtrip_stat_m(key, send, n_peers, drop_prob, axis, mode)
    return sample_bucket_counts(k, m, rt_probs, mode)


# --------------------------------------------------------------------------- #
# per-target reply totals (stat Raft's vote replies and gossip acks)          #
# --------------------------------------------------------------------------- #


# The largest ``n`` at which :func:`reply_count_by_target` counts by compare
# and sum; above it the scatter-add stays.  Set from chip readings of the two
# forms alone (tools/reply_count_readings.py on a TPU v5e, PR 41; PERF.md
# section 3), device us of one count, dense / scatter: at n = 4,096 lone
# 11.1 / 28.1 and under 256 lanes 3,011 / 7,055; at n = 16,384 lone 193.9 /
# 109.8 and under 256 lanes 150,680 / 40,531.  (At 1,024 under 256 lanes,
# the mixed deployment's shards: 172.5 / 2,296.)
REPLY_COUNT_DENSE_MAX_N = 4096


def _reply_count_dense(wire, target, n: int):
    """Every id against every row's target, hits summed: ``n_loc * n``
    compare-adds in one fusion (no ``[n_loc, n]`` array is ever stored),
    which vectorizes, and which a lane batch widens."""
    aimed = jnp.where(wire, target, -1)
    return (aimed[:, None] == jnp.arange(n, dtype=jnp.int32)).sum(
        0, dtype=jnp.int32)


def _reply_count_scatter(wire, target, n: int):
    """A scatter-add, the engine's own until PR 41 and letter for letter:
    XLA:TPU runs it one update after another (6.7-8.7 ns each, a lane batch
    flattened into the one scatter), but it grows with ``n_loc`` and not
    with ``n_loc * n``.  ``mode="drop"`` drops a target of ``n`` or more;
    a negative one ``.at[]`` wraps first, so its wire must be clear (masking
    it here read +0.3% on a whole standalone run at n = 100,000, PERF.md
    section 6, PR 41)."""
    return jnp.zeros((n,), jnp.int32).at[target].add(
        wire.astype(jnp.int32), mode="drop"
    )


@_scoped
def reply_count_by_target(wire, target, n: int, axis=None):
    """Per-target reply totals on the full mesh: entry ``c`` of the result is
    the number of local rows ``j`` with ``wire[j]`` set and ``target[j] ==
    c`` (``wire [N_loc]`` bool, ``target [N_loc]`` int32 global ids; a
    target outside ``[0, n)`` counts nowhere: a row with no target carries
    ``n``, or a negative id with its wire clear, as both callers do), summed
    over the shards under ``axis``.  Returns ``[N_loc]`` int32, this
    shard's rows.

    One count, two forms, chosen from the static ``n`` alone: compare and
    sum up to :data:`REPLY_COUNT_DENSE_MAX_N`, the scatter-add above it.
    Integer counts either way: the forms are bit-equal."""
    dense = n <= REPLY_COUNT_DENSE_MAX_N
    c = (_reply_count_dense if dense else _reply_count_scatter)(wire, target, n)
    if axis is not None:
        n_loc = wire.shape[0]
        c = lax.psum(c, axis)
        c = lax.dynamic_slice_in_dim(c, lax.axis_index(axis) * n_loc, n_loc)
    return c


# --------------------------------------------------------------------------- #
# fused sample-and-push (stat chains combined straight into the rings)        #
# --------------------------------------------------------------------------- #


@_scoped
def push_bucket_counts(buf, t, push_lo: int, key, m, probs: np.ndarray,
                       mode: str = "exact", expand=None):
    """Sample ``Multinomial(m, probs)`` bucket counts and combine each bucket
    into its ring slice AS IT IS PRODUCED — the cost-analysis-driven fusion
    of the tick engine's delivery math (ISSUE 13 / KNOWN_ISSUES #5: the tick
    wall is sampler/delivery compute).  Equivalent unfused form::

        ring_push_add(buf, t, push_lo, expand*(sample_bucket_counts(...)))

    materializes the stacked ``[B, ...]`` tensor between two unfusable op
    islands (the chain's stack and the push's unstack); here bucket ``b``'s
    ~5 elementwise chain ops fuse directly into its dynamic-update-slice,
    so XLA never round-trips the intermediate through memory.  Bit-equal to
    the unfused form: same keys (delay.bucket_count_chain yields exactly
    what sample_bucket_counts stacks), same integer adds, same bucket
    order.  ``expand`` (optional) maps a bucket's int32 counts to its ring
    contribution (e.g. broadcasting per-window activity masks)."""
    d = buf.shape[0]
    buf = node_minor(buf)
    for b, c in enumerate(bucket_count_chain(key, m, probs, mode)):
        cb = c.astype(jnp.int32)
        contrib = cb if expand is None else expand(cb)
        idx = jnp.mod(t + push_lo + b, d)
        cur = lax.dynamic_index_in_dim(buf, idx, 0, keepdims=False)
        buf = lax.dynamic_update_index_in_dim(buf, cur + contrib, idx, 0)
    return buf


@_scoped
def push_bcast_slots_stat(buf, t, push_lo: int, key, slot_mat,
                          probs: np.ndarray, drop_prob=0.0, axis=None,
                          mode="exact"):
    """Fused ``ring_push_add(buf, t, push_lo, bcast_slots_stat(...))`` —
    bit-equal to the compose (shared key/count helper), without the stacked
    [B, N_loc, S] intermediate."""
    k, m = _slots_stat_m(key, slot_mat, drop_prob, axis, mode)
    return push_bucket_counts(buf, t, push_lo, k, m, probs, mode)


@_scoped
def push_roundtrip_reply_counts_stat(buf, t, push_lo: int, key, send, n_peers,
                                     rt_probs: np.ndarray, drop_prob=0.0,
                                     axis=None, mode="exact", expand=None):
    """Fused ``ring_push_add(buf, t, push_lo, expand*(roundtrip_reply_counts_
    stat(...)))`` — bit-equal to the compose, without the stacked [B2, N_loc]
    (or expanded [B2, N_loc, W]) intermediate."""
    k, m = _roundtrip_stat_m(key, send, n_peers, drop_prob, axis, mode)
    return push_bucket_counts(buf, t, push_lo, k, m, rt_probs, mode, expand)


# --------------------------------------------------------------------------- #
# gossip flood forwarding (gossip topology)                                 #
# --------------------------------------------------------------------------- #


# A sharded flood exchanges its senders, not the row space.  The most live
# (row, lane) pairs one shard may hold for each size of the exchange's scatter;
# a tick with more on any shard takes the dense arm.  On most ticks of a flood
# a few dozen of a shard's rows forward (single-decree Paxos at 10,000 nodes
# over four shards: 27 at the median, 603 at most), and a scatter costs by the
# update, taken or not.
FLOOD_TIERS = (64, 256, 1024)


def _flood_exchange(fwd_vals, d, vals, nbrs_loc, n_glob, lo, hi, axis, tiers,
                    bits, dense):
    """The sharded arm of :func:`gossip_fwd` without the global row space:
    every shard packs its live (row, lane) pairs' updates, the packets are
    all-gathered, and each shard scatters into its OWN rows the updates that
    land there.  Equal to ``dense()`` entry for entry (a max over the same
    updates).  A packet holds ``tiers[-1]`` pairs; ``dense`` is taken when a
    shard has more.  An update travels as ``receiver | bucket << bits``."""
    n_loc, p = fwd_vals.shape
    deg, nb = nbrs_loc.shape[1], hi - lo
    n_shards = n_glob // n_loc
    kmax = tiers[-1]
    live = (fwd_vals > 0).reshape(-1)  # pairs, row-major
    upto = jnp.cumsum(live.astype(jnp.int32))
    count = upto[-1]
    # before the k-th live pair (from 0) lie the pairs whose running count is
    # k or less
    k = jnp.arange(kmax, dtype=jnp.int32)
    pair = (upto[None, :] <= k[:, None]).sum(axis=1)
    held = k < count
    pair = jnp.where(held, pair, 0)
    row, lane = pair // p, pair % p
    code = ((d[row, :, lane] - lo) << bits) | nbrs_loc[row]  # [kmax, deg]
    val = vals[row, :, lane] * held[:, None]
    packet = jnp.concatenate([code, val, lane[:, None]], axis=1)
    packet = jnp.concatenate(
        [packet, jnp.full((1, 2 * deg + 1), count, jnp.int32)])
    got = mesh_ops.gather(packet, axis).reshape(n_shards, kmax + 1, 2 * deg + 1)
    most = got[:, kmax, 0].max()
    start = lax.axis_index(axis) * n_loc

    def scatter(size):
        u = got[:, :size].reshape(n_shards * size, 2 * deg + 1)
        code, val, lane = u[:, :deg], u[:, deg:2 * deg], u[:, 2 * deg]
        loc = (code & ((1 << bits) - 1)) - start
        mine = (loc >= 0) & (loc < n_loc) & (val > 0)
        idx = jnp.where(mine, (code >> bits) * n_loc + loc, nb * n_loc)
        flat = jnp.zeros((nb * n_loc, p), jnp.int32)
        flat = flat.at[idx, lane[:, None]].max(val, mode="drop")
        return flat.reshape(nb, n_loc, p)

    arms = [lambda size=size: scatter(size) for size in tiers] + [dense]
    return lax.switch((most > jnp.asarray(tiers)).sum(), arms)


@_scoped
def gossip_fwd(key, fwd_vals, nbrs_loc, n_glob, lo, hi, drop_prob=0.0, axis=None,
               fold=0x0D22, impl="threefry", tiers=FLOOD_TIERS):
    """TTL-flood forwarding: ``fwd_vals [N_loc, P]`` (>0 TTL-encoded values
    held by local rows; P = any per-value lane — Paxos proposers, PBFT
    windows) → ``[B, N_loc, P]`` scatter-max contributions at each sender's
    out-neighbors (``nbrs_loc [N_loc, deg]`` global ids), one fresh delay draw
    per (sender, edge, lane).  Sharded: the shards exchange their senders'
    updates (:func:`_flood_exchange`) where every shard's fit ``tiers``, else
    scatter into the global row space, pmax across shards (each shard
    contributes its senders' forwards), slice the local rows back out."""
    n_loc, p = fwd_vals.shape
    deg = nbrs_loc.shape[1]
    k = _shard_key(key, axis)
    d = sample_edge_delays(k, (n_loc, deg, p), lo, hi, impl)
    vals = jnp.broadcast_to(fwd_vals[:, None, :], (n_loc, deg, p))
    if drop_prob > 0.0:
        keep = jax.random.bernoulli(
            jax.random.fold_in(k, fold), 1.0 - drop_prob, (n_loc, deg, p)
        )
        vals = vals * keep

    def dense():
        # one scatter-max over a flattened (bucket, receiver) index — XLA
        # handles a single big scatter far better than hi-lo separate ones
        flat_idx = (d - lo) * n_glob + nbrs_loc[:, :, None]  # [n_loc, deg, p]
        flat = jnp.zeros(((hi - lo) * n_glob, p), jnp.int32)
        flat = flat.at[flat_idx, jnp.arange(p)[None, None, :]].max(vals)
        out = flat.reshape(hi - lo, n_glob, p)
        if axis is not None:
            out = mesh_ops.pmax(out, axis)
            start = lax.axis_index(axis) * n_loc
            out = lax.dynamic_slice_in_dim(out, start, n_loc, axis=1)
        return out

    bits = max(n_glob - 1, 1).bit_length()
    tiers = tuple(t for t in tiers if t < n_loc * p)
    if axis is None or not tiers or (hi - lo) << bits >= 2 ** 31:
        return dense()
    return _flood_exchange(fwd_vals, d, vals, nbrs_loc, n_glob, lo, hi, axis,
                           tiers, bits, dense)


@_scoped
def unicast_reply_value_max_dense(key, reply, lo, hi, drop_prob=0.0,
                                  impl="threefry"):
    """:func:`unicast_reply_counts_dense` for replies that carry a VALUE the
    requester max-combines (Raft with terms: a denied vote's reply carries
    the replier's term, and the candidate needs the highest it hears).
    ``reply[r, c]`` = the value (> 0; 0 = no reply) node r sends node c
    this tick, one delay drawn per edge.  Returns [B, N] indexed by
    requester, 0 where nothing lands.  Unsharded only: no program with terms
    runs under a mesh axis (models/raft.check_terms)."""
    n = reply.shape[0]
    d = sample_edge_delays(key, (n, n), lo, hi, impl)
    r = reply.astype(jnp.int32) * (1 - jnp.eye(n, dtype=jnp.int32))
    if drop_prob > 0.0:
        keep = jax.random.bernoulli(
            jax.random.fold_in(key, 0x0D0F), 1.0 - drop_prob, (n, n)
        )
        r = r * keep.astype(jnp.int32)
    return (
        r[None] * (d[None] == _bucket_iota(lo, hi, d.ndim)).astype(jnp.int32)
    ).max(1)  # [B, N]


# --------------------------------------------------------------------------- #
# classed delivery (link classes, ops/linkclass.py): the dense arms with a   #
# sender tensor a receiver class                                              #
# --------------------------------------------------------------------------- #
#
# Under link classes what a node "sends now" differs by the class of the
# receiver (``ops/linkclass.line_get``: entry ``[k, i]`` is what reaches the
# receivers of class ``k`` from node ``i`` this tick, the class pair's share
# of the propagation already behind it).  Each arm below is its dense twin
# above with that leading class axis: ONE jitter draw an edge on the same key
# and of the same shape, the bucket axis the jitter's own, the receivers'
# columns taken class by class (``bounds``: K contiguous row ranges), so the
# work is the dense arm's and one class is the dense arm itself, number for
# number.  Unsharded: no classed program runs under a mesh axis
# (ops/linkclass.check_arms).


@_scoped
def bcast_value_max_classed(key, value_k, bounds, lo, hi, drop_prob=0.0,
                            impl="threefry"):
    """:func:`bcast_value_max_dense` of ``value_k [K, N]``.  Returns
    [B, N]."""
    value_k = value_k.astype(jnp.int32)
    send = value_k.max(axis=0) > 0
    hits = _edge_hits(key, send, lo, hi, drop_prob, impl=impl)
    return jnp.concatenate(
        [(hits[:, :, a:b] * value_k[k][None, :, None]).max(1)
         for k, (a, b) in enumerate(bounds)], axis=1)


@_scoped
def bcast_slots_classed(key, slot_k, bounds, lo, hi, drop_prob=0.0,
                        impl="threefry"):
    """:func:`bcast_slots_dense` of ``slot_k [K, N, S]``.  Returns
    [B, N, S]."""
    slot_k = slot_k.astype(jnp.int32)
    send = slot_k.max(axis=(0, 2)) > 0
    hits = _edge_hits(key, send, lo, hi, drop_prob, impl=impl)
    return jnp.concatenate(
        [jnp.einsum("bij,is->bjs", hits[:, :, a:b], slot_k[k])
         for k, (a, b) in enumerate(bounds)], axis=1)


@_scoped
def bcast_window_value_max_classed(key, value_k, bounds, lo, hi, drop_prob=0.0,
                                   impl="threefry"):
    """:func:`bcast_window_value_max_dense` of ``value_k [K, N, W]``.
    Returns [B, N, W]."""
    value_k = value_k.astype(jnp.int32)
    send = value_k.max(axis=(0, 2)) > 0
    hits = _edge_hits(key, send, lo, hi, drop_prob, impl=impl)
    return jnp.concatenate(
        [(hits[:, :, a:b, None] * value_k[k][None, :, None, :]).max(axis=1)
         for k, (a, b) in enumerate(bounds)], axis=1)


@_scoped
def roundtrip_reply_counts_classed(key, send, bounds, lo, hi, drop_prob=0.0,
                                   peer_mask=None, impl="threefry"):
    """:func:`roundtrip_reply_counts_dense` with the replies counted by the
    PEER's class: [B2, K, N], entry ``[b, k, i]`` the replies that reach
    sender ``i`` from its peers of class ``k`` with ``2*lo + b`` ticks of
    jitter and base propagation.  ``send [N]``: the senders with a
    broadcast whose replies any peer class starts now; which of its
    broadcasts a peer class answers is the caller's to weigh the counts
    by."""
    n = send.shape[0]
    peers = jnp.ones((n,), bool) if peer_mask is None else peer_mask
    d1 = sample_edge_delays(jax.random.fold_in(key, 1), (n, n), lo, hi, impl)
    d2 = sample_edge_delays(jax.random.fold_in(key, 2), (n, n), lo, hi, impl)
    total = d1 + d2
    notself = jnp.arange(n)[:, None] != jnp.arange(n)[None, :]
    mask = (
        send.astype(jnp.int32)[:, None]
        * notself.astype(jnp.int32)
        * peers.astype(jnp.int32)[None, :]
    )
    if drop_prob > 0.0:
        keep = jax.random.bernoulli(
            jax.random.fold_in(key, 0x0D0E), (1.0 - drop_prob) ** 2, (n, n)
        )
        mask = mask * keep.astype(jnp.int32)
    lo2 = 2 * lo
    nb = 2 * (hi - lo) - 1
    landed = (
        (total[None] == _bucket_iota(lo2, lo2 + nb, total.ndim)).astype(jnp.int32)
        * mask[None]
    )  # [B2, N sender, N peer]
    return jnp.stack([landed[:, :, a:b].sum(2) for a, b in bounds], axis=1)


# every scope above, by name (ops/scopes.py)
SCOPES = tuple(_names)
