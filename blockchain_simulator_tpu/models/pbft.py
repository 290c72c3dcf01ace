"""PBFT consensus — tensorized state machine.

Re-design of the reference's ``PbftNode`` (pbft/pbft-node.h:19, pbft-node.cc):
a leader-driven 3-phase commit where the leader broadcasts PRE_PREPARE blocks
every 50 ms (SendBlock, pbft-node.cc:372-411), replicas broadcast PREPARE on
receipt (pbft-node.cc:193-211), every PREPARE is answered with a unicast
PREPARE_RES SUCCESS (pbft-node.cc:212-221), a node crossing the prepare
quorum broadcasts COMMIT (pbft-node.cc:223-239), and a node crossing the
commit quorum finalizes the block (pbft-node.cc:241-264 — the finality
measurement point, line 259).  A leader round has a 1/100 chance of a view
change rotating the leader (pbft-node.cc:294-303,401-403).

Tensorization (SURVEY.md §7): one tick = 1 ms for all N nodes at once.

- The per-``(v,n)`` vote table ``TX tx[1000]`` (pbft-node.h:50-56) becomes a
  **slot window**: live vote state is ``[N, W]`` keyed by ``slot % W``
  (``W = pbft_window``; default = ``pbft_max_slots`` = exact mode).  A slot's
  messages are all in flight within ``ring_depth`` ticks (≪ ``W`` block
  intervals), so by the time window ``w`` is re-tenanted by slot ``s + W``
  the old tenant's traffic has drained; the PRE_PREPARE channel carries the
  slot id, and a higher id evicts (zeroes) the window.  This caps the
  per-tick HBM footprint at O(N·W) instead of O(N·S) — the difference
  between ~20 and hundreds of simulated consensus rounds/sec at N = 100k.
- Per-slot outcomes (finality counts, commit/propose ticks) fold into tiny
  ``[S]`` accumulators via per-window scatter-reductions; sharded, these are
  per-shard partials combined once after the scan (``finalize``).
- PREPARE handling is *short-circuited*: a peer's reply never depends on its
  state, so a PREPARE broadcast by node i at tick t directly schedules N-1
  PREPARE_RES arrivals at i over the request+reply delay distribution.
- The reference's process-global ``v, n, val, n_round`` (pbft-node.cc:24-30,
  quirk #10 in SURVEY.md §2) become per-node state; a new leader infers the
  next sequence number from the highest PRE_PREPARE slot it has seen.
- Echo-back (quirk #1) is a deliberate divergence shared by the JAX backend
  and the C++ reference engine (engine.cpp:29-31): every echoed packet lands
  in the reference's "wrong msg" default branch, so dropping the echoes
  changes traffic volume but no protocol outcome; differential tests pin the
  echo-off behavior on both backends.

Fidelity modes: ``reference`` keeps N/2 thresholds and reset-on-threshold
counters (quirks #2, #4 — duplicate commits possible); ``clean`` latches each
(node, slot) so a slot commits exactly once.  ``quorum_rule="2f1"`` swaps in
Byzantine-safe 2f+1 thresholds (utils/config.py).

Windowed-mode preconditions (checked in init): the PRE_PREPARE for a slot
always lands before any of that slot's COMMIT votes (first commit arrival is
>= 4 one-way-lo after the proposal vs. <= one-way-hi for the PRE_PREPARE),
so counters are never attributed to a stale tenant; per-message drops can
break that ordering for an unlucky node, in which case its votes land in an
``unattributed`` counter instead of a slot (reported in metrics).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from blockchain_simulator_tpu.models.base import (
    can_branch,
    fault_masks,
    gated_body,
    gated_push,
)
from blockchain_simulator_tpu.ops import delay as delay_ops
from blockchain_simulator_tpu.ops import delivery as dv
from blockchain_simulator_tpu.ops import gatherdeliv as gd
from blockchain_simulator_tpu.ops import linkclass as lc
from blockchain_simulator_tpu.ops import topology
from blockchain_simulator_tpu.ops.ring import (
    ring_pop,
    ring_push_add,
    ring_push_max,
    slice_node_minor,
)
from blockchain_simulator_tpu.utils.prng import Channel, chan_key

# propose-tick sentinel (min-reduced); np, not jnp: same int either way
# (iinfo is pure dtype metadata), and module scope stays trivially free of
# jax calls (jaxlint module-scope-backend-touch)
_NEVER = np.iinfo(np.int32).max

# state fields that are per-slot accumulators, NOT node-sharded: every shard
# holds a partial that ``finalize`` combines (parallel/shard.py keeps them
# replicated-spec and calls finalize after the scan)
GLOBAL_FIELDS = ("slot_commits", "slot_commit_tick", "slot_propose_tick")

# the phases of :func:`step` as ``jax.named_scope`` names: HLO metadata only
# (nothing computed changes), so a profiler trace attributes device time to
# a phase that keeps its name across refactors.  ops/ scopes nest inside.
# the taken trip of :func:`step`'s quiet-tick gate, around every phase after
# the pops: the device events of one operation under it are the ticks that
# ran.  Outside the ``pbft.`` / ``ops.`` families on purpose, so that a phase
# stays the outermost program scope of what runs inside
TAKEN_SCOPE = "gate.pbft.tick_taken"
SCOPES = (
    "pbft.tick.pop",
    "pbft.tick.view_change",
    "pbft.tick.pre_prepare",
    "pbft.tick.prepare",
    # the forged COMMIT wave, inside ``prepare``; only a ``byz_forge``
    # program has it
    "pbft.tick.forge",
    "pbft.tick.commit",
    "pbft.tick.timers",
    TAKEN_SCOPE,
)


@struct.dataclass
class PbftState:
    v: jax.Array            # [N] current view (init 1, pbft-node.cc:101)
    leader: jax.Array       # [N] believed leader (init 0)
    next_n: jax.Array       # [N] next sequence number a leader would use
    rounds_sent: jax.Array  # [N] blocks broadcast as leader (global n_round analog)
    slot_id: jax.Array      # [N, W] tenant slot of each window, -1 unknown
    prepare_vote: jax.Array  # [N, W]
    commit_vote: jax.Array   # [N, W]
    prep_sent: jax.Array     # [N, W] bool — COMMIT already broadcast (clean latch)
    committed_w: jax.Array   # [N, W] bool — tenant finalized at this node
    block_num: jax.Array     # [N] commits counted (duplicates possible in
    # reference fidelity, matching pbft-node.cc:260)
    unattributed: jax.Array  # [N] commits that crossed with an unknown tenant
    view_changes: jax.Array  # [N] view changes initiated
    alive: jax.Array         # [N] bool fault mask
    honest: jax.Array        # [N] bool fault mask
    # gossip (topology="gossip") dedup state; zeros on the full mesh
    seen_pp: jax.Array       # [N, W] highest TTL-encoded PRE_PREPARE seen
    seen_vc: jax.Array       # [N] highest TTL-encoded VIEW_CHANGE seen
    # queued-link transport registers (cfg.queued_links; [N,1] dummies off).
    # ns-3 models each directed link as a serial 3 Mbps pipe
    # (blockchain-simulator.cc:22-24): a block transmits when the link is
    # free, occupies it for its serialization time, then propagates — blocks
    # depart every 50 ms but serialize ~136 ms, so per-link queues grow
    # ~86 ms/round (engine.cpp:198-215 is the C++ twin).  Blocks only ever
    # flow from the current leader, so the busy state is per DESTINATION —
    # a [N] tensor, not [N,N]; the registers reset on a view change (a
    # first-time leader's links are vote-only, hence free, in both engines;
    # divergence only if a leader is RE-elected, which takes N rotations).
    # Delivery offsets grow without bound, so queued blocks bypass the ring
    # into a per-destination FIFO of (arrival tick, slot value) pairs.
    link_busy: jax.Array     # [N] tick until which (leader -> j) is busy
    ppq_tick: jax.Array      # [N, Q] queued-block arrival ticks (_NEVER free)
    ppq_val: jax.Array       # [N, Q] queued-block slot+1 values
    # --- per-slot accumulators (GLOBAL_FIELDS; per-shard partials) ----------
    slot_commits: jax.Array      # [S] nodes that finalized slot s (first time)
    slot_commit_tick: jax.Array  # [S] last finalization tick, -1 never
    slot_propose_tick: jax.Array  # [S] first proposal tick, _NEVER sentinel


# the rows of ``PbftBufs.due``
_RINGS = _PP, _PREP_RT, _COMMIT, _VC = range(4)


@struct.dataclass
class PbftBufs:
    pp: jax.Array       # [D, N, W] PRE_PREPARE slot-id+1 values, max-combined
    prep_rt: jax.Array  # [D, N, W] PREPARE_RES (round-trip) reply counts
    commit: jax.Array   # [D, N, W] COMMIT arrival counts
    vc: jax.Array       # [D, N] VIEW_CHANGE, encoded v*N + leader + 1, max
    # [4, D] bool, a row per ring in the order above: slot k is *due* iff a
    # push arm ran for it since it was last popped.  A due slot may hold
    # zeros; a slot that is not due holds nothing (:func:`step`'s gate rests
    # on that).  Kept only by the programs that gate (``can_branch``)
    due: jax.Array
    # the sender-side delay lines of a program with link classes
    # (:class:`LinkLines`); None without classes: no leaf
    lines: "LinkLines | None" = None


@struct.dataclass
class LinkLines:
    """What every node sent on each channel over the last ticks
    (``ops/linkclass.DelayLine``), read back a class pair's offset later.
    The broadcasts keep the value a node sends (a leader sends one slot a
    tick, so its PRE_PREPARE is the slot id + 1 and not a row of windows);
    ``prepare`` keeps the (node, window) pairs that broadcast PREPARE, a bit
    a window (``ops/linkclass.pack_bits``), and is read over the round trip's
    offsets: its replies are short-circuited (module docstring)."""

    pp: lc.DelayLine       # [T, N] int32 slot id + 1 a leader announced
    prepare: lc.DelayLine  # [T2, N, W / 32] uint32 PREPARE broadcast, packed
    commit: lc.DelayLine   # [T, N, W] int32 COMMIT votes broadcast
    vc: lc.DelayLine       # [T, N] int32 VIEW_CHANGE, encoded as the ring's


def eff_window(cfg) -> int:
    w = getattr(cfg, "pbft_window", 0)
    if w <= 0 or w >= cfg.pbft_max_slots:
        return cfg.pbft_max_slots
    return w


def _queued(cfg) -> bool:
    """Queued-link transport (cfg.queued_links): blocks ride per-destination
    serial-pipe FIFOs instead of the ring (see PbftState field comments);
    with ser == 0 the pipe is never busy and queued == constant-latency
    bit-exactly, so the plain ring path runs (engine.cpp behaves the same)."""
    return cfg.queued_links and cfg.serialization_ticks(cfg.pbft_block_bytes) > 0


def queue_len(cfg) -> int:
    """Static per-destination block-FIFO depth for queued-link mode: sized to
    r = min(pbft_max_rounds, pbft_max_slots) outright — cheap at the n=8-ish
    scales queued fidelity runs at, and together with the free-slot enqueue
    in ``step`` it makes silently clobbering an undelivered block impossible
    (the former steady-state backlog estimate undersized the FIFO under
    adversarial view-change timing, which both re-proposes stale slots and
    resets link_busy — ADVICE r5)."""
    if not _queued(cfg):
        return 1  # dummy registers; the ring path carries the blocks
    return min(cfg.pbft_max_rounds, cfg.pbft_max_slots)


def init(cfg, key=None):
    n, s = cfg.n, cfg.pbft_max_slots
    w = eff_window(cfg)
    d = cfg.ring_depth
    lc.check_arms(cfg)
    if cfg.topology == "gossip" and w < s:
        raise ValueError(
            "pbft gossip (topology='gossip') requires exact vote-table mode "
            "(pbft_window = 0 or >= pbft_max_slots): a multi-hop PRE_PREPARE "
            "can trail its slot's direct-unicast COMMIT votes, which exact "
            "mode attributes by window identity while a window would misfile"
        )
    if w < s:
        lo, hi = cfg.one_way_range()
        if 4 * lo <= hi:
            raise ValueError(
                f"pbft_window={w} < max_slots requires 4*delay_lo > delay_hi "
                f"(got lo={lo}, hi={hi}): a slot's PRE_PREPARE must land "
                "before its first COMMIT vote"
            )
        if w * cfg.pbft_block_interval_ms <= d + hi:
            raise ValueError(
                f"pbft_window={w} re-tenants a window every "
                f"{w * cfg.pbft_block_interval_ms} ms, inside the message "
                f"horizon (~{d + hi} ms); raise pbft_window"
            )
        if cfg.faults.byz_forge:
            raise ValueError(
                "byz_forge targets a concrete never-proposed slot; it "
                "requires exact mode (pbft_window = 0 or >= pbft_max_slots)"
            )
    alive, honest = fault_masks(cfg, n)
    zi = lambda *sh: jnp.zeros(sh, jnp.int32)
    zb = lambda *sh: jnp.zeros(sh, bool)
    state = PbftState(
        v=jnp.ones((n,), jnp.int32),
        leader=zi(n),
        next_n=zi(n),
        rounds_sent=zi(n),
        slot_id=jnp.full((n, w), -1, jnp.int32),
        prepare_vote=zi(n, w),
        commit_vote=zi(n, w),
        prep_sent=zb(n, w),
        committed_w=zb(n, w),
        block_num=zi(n),
        unattributed=zi(n),
        view_changes=zi(n),
        alive=alive,
        honest=honest,
        seen_pp=zi(n, w),
        seen_vc=zi(n),
        link_busy=zi(n),
        ppq_tick=jnp.full((n, queue_len(cfg)), _NEVER, jnp.int32),
        ppq_val=zi(n, queue_len(cfg)),
        slot_commits=zi(s),
        slot_commit_tick=jnp.full((s,), -1, jnp.int32),
        slot_propose_tick=jnp.full((s,), _NEVER, jnp.int32),
    )
    bufs = PbftBufs(pp=zi(d, n, w), prep_rt=zi(d, n, w), commit=zi(d, n, w),
                    vc=zi(d, n), due=zb(len(_RINGS), d))
    if cfg.link_classes:
        holds = {"pp": ((n,), jnp.int32),
                 "prepare": ((n, -(-w // 32)), jnp.uint32),
                 "commit": ((n, w), jnp.int32), "vc": ((n,), jnp.int32)}
        bufs = bufs.replace(lines=LinkLines(**{
            f: lc.line_init(plan, *holds[f])
            for f, plan in _line_plans(cfg).items()}))
        lc.note_traced(cfg, (state, bufs))
    return state, bufs


def _line_plans(cfg) -> dict:
    """The static plan of each line, by the line's field in
    :class:`LinkLines`."""
    ow, rt = lc.one_way_plan(cfg), lc.roundtrip_plan(cfg)
    return {"pp": ow, "prepare": rt, "commit": ow, "vc": ow}


def finalize(state: PbftState, axis) -> PbftState:
    """Combine per-shard slot accumulators (call once, after the scan)."""
    if axis is None:
        return state
    return state.replace(
        slot_commits=jax.lax.psum(state.slot_commits, axis),
        slot_commit_tick=jax.lax.pmax(state.slot_commit_tick, axis),
        slot_propose_tick=jax.lax.pmin(state.slot_propose_tick, axis),
    )


def _scatter_window_events(acc_add, acc_max, acc_min, events, eff_sid, t, s):
    """Fold [N, W] first-commit / propose events into [S] accumulators via a
    per-window reduction: all nodes crossing a window this tick share its
    tenant, so per-window (count, slot-id) pairs are exact and the scatter is
    W updates, not N·W.  Invalid slot ids route out of bounds and drop."""
    ev = events.astype(jnp.int32)
    cnt_w = ev.sum(axis=0)                                   # [W]
    sid_w = jnp.max(jnp.where(events, eff_sid, -1), axis=0)  # [W]
    idx = jnp.where((sid_w >= 0) & (cnt_w > 0), sid_w, s)    # s = out of bounds
    out = []
    if acc_add is not None:
        out.append(acc_add.at[idx].add(cnt_w, mode="drop"))
    if acc_max is not None:
        out.append(acc_max.at[idx].max(jnp.where(cnt_w > 0, jnp.int32(t), -1),
                                       mode="drop"))
    if acc_min is not None:
        out.append(acc_min.at[idx].min(jnp.where(cnt_w > 0, jnp.int32(t), _NEVER),
                                       mode="drop"))
    return out


def _is_block_tick(cfg, t):
    return (t % cfg.pbft_block_interval_ms == 0) & (t > 0)


def step(cfg, state: PbftState, bufs: PbftBufs, t, tkey, *, topo_tables=None,
         exchange=None):
    """One tick: pop the four rings, then run the phases (:func:`_phases`).

    Pops and phases are the identity on a tick on which no ring's slot
    ``t % D`` is due, no queued block lands and no block is due to be sent:
    a slot that is not due holds nothing (``PbftBufs.due``), so its pop reads
    zeros and writes zeros back, every crossing, send and trigger is false
    and every vote table gets ``+ 0``.  So where the program can branch
    (``can_branch``: one device, no ``select_vmap``) both run inside one
    ``while`` of at most one trip, taken on ``is_block_tick | any ring's slot
    t is due | a queued block lands``, under a lane batch on "any lane
    active" with no per-lane select, the body being the identity for a lane
    with nothing due.  A quiet tick then touches no ring and no ``[N, W]``
    table: the predicate and the clearing of slot ``t``'s due bits are all it
    runs.  Under a mesh axis the phases hold collectives, and under
    ``select_vmap`` no branch survives: both pop and run the phases on every
    tick (KNOWN_ISSUES #0b')."""
    gate = can_branch(cfg.mesh_axis)
    # the gate's loop is laid out in isolation: the popped slices and the
    # tables they meet keep the rings' node-minor order inside it (the
    # programs that cannot branch see the whole tick and need no pin)
    pin = slice_node_minor if gate else (lambda x: x)

    def tick(carry):
        st, bf = carry
        with jax.named_scope("pbft.tick.pop"):
            pp_t, pp = ring_pop(bf.pp, t)
            prep_t, prep_rt = ring_pop(bf.prep_rt, t)
            com_t, commit = ring_pop(bf.commit, t)
            vc_t, vc = ring_pop(bf.vc, t)
        popped = jax.tree.map(pin, (pp_t, prep_t, com_t, vc_t))
        bf = bf.replace(pp=pp, prep_rt=prep_rt, commit=commit, vc=vc)
        st, bf = _phases(cfg, st, bf, popped, t, tkey, topo_tables, exchange,
                         mark_due=gate)
        return jax.tree.map(pin, st), bf

    if not gate:
        return tick((state, bufs))
    with jax.named_scope("pbft.tick.pop"):
        d = bufs.due.shape[1]
        now = jnp.arange(d) == t % d
        active = _is_block_tick(cfg, t) | (bufs.due & now).any()
        if _queued(cfg):  # those arrivals bypass the rings
            active = active | (state.ppq_tick == t).any()
        bufs = bufs.replace(due=bufs.due & ~now)
        if cfg.link_classes:  # what a delay line holds for this tick's readers
            lines = {}
            for f, plan in _line_plans(cfg).items():
                lines[f] = lc.line_clear(getattr(bufs.lines, f), t)
                active = active | lc.line_any(lines[f], t, plan)
            bufs = bufs.replace(lines=LinkLines(**lines))
    return gated_body(active, tick, (state, bufs), TAKEN_SCOPE)


def _phases(cfg, state: PbftState, bufs: PbftBufs, popped, t, tkey,
            topo_tables, exchange, mark_due: bool):
    """Everything of a tick after the pops, from the alive masks of the
    ``popped`` slices to the end of the timers.  ``mark_due`` keeps
    ``bufs.due`` at every push (the gated programs)."""
    n, s = cfg.n, cfg.pbft_max_slots
    w = eff_window(cfg)
    exact = w == s
    axis = cfg.mesh_axis
    lo, hi = cfg.one_way_range()
    rt_lo, rt_hi = cfg.roundtrip_range()
    drop = cfg.faults.drop_prob
    clean = cfg.fidelity == "clean"
    stat = cfg.delivery == "stat"
    smode = cfg.eff_stat_sampler
    eimpl = cfg.eff_edge_sampler
    ow_probs = delay_ops.uniform_probs(lo, hi)
    rt_probs = delay_ops.roundtrip_probs(lo, hi)
    n_loc = state.v.shape[0]
    # global node ids of this shard's rows (== arange(N) unsharded)
    ids = dv._global_ids(n_loc, axis)
    windows = jnp.arange(w)

    ser = cfg.serialization_ticks(cfg.pbft_block_bytes)
    queued = _queued(cfg)
    prop = cfg.link_delay_ms

    pp_t, prep_t, com_t, vc_t = popped
    pp, prep_rt, commit, vc = bufs.pp, bufs.prep_rt, bufs.commit, bufs.vc
    # link classes: each channel's sends go into its delay line and what the
    # receivers of each class read back out of it is delivered, with the
    # jitter draw and the ring push of a program without classes
    classed = bool(cfg.link_classes)
    lines, plans = bufs.lines, _line_plans(cfg) if classed else None
    due = list(bufs.due) if mark_due else None
    d = bufs.due.shape[1]

    def push(ring, pred, fn, zeros, buf, into, lo_, n_buckets):
        """``gated_push`` into ring ``ring``, whose slots ``t + lo_ + b``,
        ``b < n_buckets``, the push arm writes: marked due on the lane's own
        predicate."""
        if mark_due:
            ahead = jnp.mod(jnp.arange(d) - (t + lo_), d) < n_buckets
            due[ring] = due[ring] | (pred & ahead)
        return gated_push(pred, fn, zeros, buf, into, axis)

    def push_classed(ring, name, sending, deliver, zeros, buf, into, lo_,
                     n_buckets):
        """``push`` under link classes: ``sending [N, ...]`` (what the nodes
        send on channel ``name`` this tick) goes into the channel's delay
        line, and ``deliver`` gets what the receivers of each class read back
        out of it (``[K, N, ...]``, ops/linkclass.line_get).  The read stands
        outside the push's gate, on every taken tick: a line that crossed the
        gate (closed over, or riding its carry beside the ring) was laid out
        anew in the gate's loop and copied whole on every tick (PERF.md
        section 6, PR 51); what crosses is the read, K x N rows."""
        nonlocal lines
        line = lc.line_put(getattr(lines, name), t, sending)
        lines = lines.replace(**{name: line})
        sent_k = lc.line_get(line, t, plans[name])
        return push(ring, lc.line_any(line, t, plans[name]),
                    lambda: deliver(sent_k), zeros, buf, into, lo_, n_buckets)

    with jax.named_scope("pbft.tick.pop"):
        # ---- this tick's arrivals; crashed nodes process nothing ----------------
        am = state.alive.astype(jnp.int32)
        pp_t, prep_t, com_t = pp_t * am[:, None], prep_t * am[:, None], com_t * am[:, None]
        vc_t = vc_t * am

        # queued mode: this tick's serial-link block deliveries (exact mode is
        # enforced by runner._reject_cpp_only, so window == slot identity).  A
        # destination can receive TWO blocks in one tick — a view change frees
        # the new leader's links while an old-leader block is still backlogged —
        # so every hit scatters into its own window (same-window collisions are
        # impossible: exact mode keys windows by slot identity), matching the
        # C++ engine delivering both events.
        if queued:
            hits = state.ppq_tick == t  # [N, Q]
            vals = jnp.where(hits & state.alive[:, None], state.ppq_val, 0)
            ppq_tick = jnp.where(hits, _NEVER, state.ppq_tick)
            oh_arr = (
                ((vals - 1) % w)[:, :, None] == windows[None, None, :]
            ) & (vals > 0)[:, :, None]  # [N, Q, W]
            pp_t = jnp.maximum(
                pp_t, jnp.max(jnp.where(oh_arr, vals[:, :, None], 0), axis=1)
            )
        else:
            ppq_tick = state.ppq_tick

        # ---- gossip decode (topology="gossip"): the block-carrying channels
        # (PRE_PREPARE) and the control channel (VIEW_CHANGE) flood over the k-out
        # digraph with a hop TTL; votes stay direct unicast — they are 4-byte
        # packets, and flooding them would need per-sender dedup state (O(N^2)),
        # defeating the sparse path.  Channel values carry encoded*H + hops_left
        # (H = gossip_hops+1); a node processes each base value once (first
        # sighting) but forwards any strictly better TTL copy, so a nearly-expired
        # first arrival cannot truncate the flood (same scheme as models/paxos.py).
        gossip = cfg.topology == "gossip"
        # kregular gather overlay (topo/spec.py): every channel delivers DIRECT
        # to the circulant in/out neighbor tables through the O(N*K) gather
        # primitives (ops/gatherdeliv.py) — no relay, no dedup state, and at
        # degree k = N-1 bit-equal to the dense/full-mesh arms below (the sorted
        # full-overlay table is the identity, so the same keys draw the same
        # tensors).  With k below the commit quorum a node can never hear enough
        # votes — a stalling-but-valid scenario (KNOWN_ISSUES topo note).
        kreg = cfg.topology == "kregular"
        nbr_in_loc = nbr_out_loc = None
        if kreg:
            # topo_tables=None bakes the tables as trace constants (audit
            # scale); the sharded programs pass them as operands instead.  In
            # exchange mode the operands ARE this trace's rows already —
            # ids=None skips the take that GSPMD would turn into a full-table
            # all-gather (the retired table-regather debt)
            nbr_in_loc, nbr_out_loc = gd.local_tables(
                cfg, None if exchange is not None else ids, tables=topo_tables)
        seen_pp, seen_vc = state.seen_pp, state.seen_vc
        pp_fwd = vc_fwd = None
        nbrs_loc = None
        if gossip:
            h_enc = cfg.gossip_hops + 1
            nbrs_loc = jnp.take(
                jnp.asarray(topology.kregular_out_neighbors(n, cfg.degree, cfg.seed)),
                ids, axis=0,
            )
            pp_base, pp_hops = pp_t // h_enc, pp_t % h_enc
            better = (pp_t > seen_pp) & state.alive[:, None]
            new_base = (pp_base > seen_pp // h_enc) & state.alive[:, None]
            seen_pp = jnp.maximum(seen_pp, pp_t * better)
            pp_fwd = (pp_base * h_enc + jnp.maximum(pp_hops - 1, 0)) * (
                better & (pp_hops > 0)
            )
            pp_t = pp_base * new_base  # first sighting processes (value = slot+1)
            vc_base, vc_hops = vc_t // h_enc, vc_t % h_enc
            vbetter = (vc_t > seen_vc) & state.alive
            vnew = (vc_base > seen_vc // h_enc) & state.alive
            seen_vc = jnp.maximum(seen_vc, vc_t * vbetter)
            vc_fwd = (vc_base * h_enc + jnp.maximum(vc_hops - 1, 0)) * (
                vbetter & (vc_hops > 0)
            )
            vc_t = vc_base * vnew

    with jax.named_scope("pbft.tick.view_change"):
        # ---- VIEW_CHANGE arrivals: adopt (v, leader) (pbft-node.cc:271-280) -----
        has_vc = vc_t > 0
        v = jnp.where(has_vc, (vc_t - 1) // n, state.v)
        leader = jnp.where(has_vc, (vc_t - 1) % n, state.leader)
        if queued:
            # leadership rotated: the NEW leader's links are vote-only, hence
            # free (votes never occupy the pipe — ser 0) in both engines; its
            # busy registers start fresh.  VC arrivals all land before the next
            # block tick (one-way hi <= interval, enforced by the runner), so
            # the reset settles strictly between block sends.
            any_vc = jnp.max(has_vc.astype(jnp.int32))
            if axis is not None:
                any_vc = jax.lax.pmax(any_vc, axis)
            link_busy = jnp.where(any_vc > 0, 0, state.link_busy)
        else:
            link_busy = state.link_busy

    with jax.named_scope("pbft.tick.pre_prepare"):
        # ---- PRE_PREPARE arrivals: evict stale tenant, store, broadcast PREPARE -
        got_pp = pp_t > 0  # [N, W]  (any arrival re-broadcasts PREPARE — the
        # reference PRE_PREPARE handler has no dedup, pbft-node.cc:193-211)
        arr_sid = pp_t - 1  # announced slot id
        new_tenant = got_pp & (arr_sid > state.slot_id)
        slot_id = jnp.where(new_tenant, arr_sid, state.slot_id)
        if exact:
            # windows ARE slot identities — nothing is ever re-tenanted, so a
            # learned tenant must not wipe the counters: votes can legitimately
            # precede the PRE_PREPARE (gossip: direct-unicast COMMITs outrun the
            # multi-hop block flood; drops: the pp may never come at all) and
            # were already attributed to this window by identity
            prepare_vote, commit_vote = state.prepare_vote, state.commit_vote
            prep_sent, committed_w = state.prep_sent, state.committed_w
        else:
            # windowed mode: a higher slot id evicts the stale tenant's state
            prepare_vote = jnp.where(new_tenant, 0, state.prepare_vote)
            commit_vote = jnp.where(new_tenant, 0, state.commit_vote)
            prep_sent = state.prep_sent & ~new_tenant
            committed_w = state.committed_w & ~new_tenant
        seen_hi = jnp.max(jnp.where(got_pp, arr_sid + 1, 0), axis=1)
        next_n = jnp.maximum(state.next_n, seen_hi)

        # PREPARE broadcast → short-circuited round-trip PREPARE_RES replies.
        # Only honest, alive peers contribute SUCCESS votes (Byzantine nodes flip
        # their votes to FAILED, which the counter ignores, pbft-node.cc:227).
        voters = state.alive & state.honest
        k_rt = chan_key(tkey, Channel.DELAY_ROUNDTRIP)
        prep_active = got_pp.any(axis=1)
        got_pp_i = got_pp.astype(jnp.int32)
        if stat:
            # fused sample-and-push (ops/delivery.push_roundtrip_reply_counts_
            # stat): each reply bucket's chain math lands straight in its ring
            # slice — bit-equal to the unfused sample → expand → ring_push_add
            # compose, without the [B2, N, W] stacked intermediate.  There is
            # no separate contribution (``()``): the gate skips the whole
            # push, and under a lane batch a lane without a sender adds an
            # all-zero draw, which leaves its ring as it was.  The kregular overlay swaps ONLY
            # the per-sender peer count — a gather over the out-table instead
            # of total-minus-self — and rides the same fused chain on the same
            # key (equal counts at k = N-1, hence bit-equal).
            if kreg:
                n_peers = gd.out_counts(voters, nbr_out_loc, ids, axis, exchange)
            else:
                n_voters = voters.astype(jnp.int32).sum()
                if axis is not None:
                    n_voters = jax.lax.psum(n_voters, axis)
                n_peers = n_voters - voters.astype(jnp.int32)
            prep_rt = push(
                _PREP_RT,
                prep_active.any(),
                tuple,
                (),
                prep_rt,
                lambda buf, _: dv.push_roundtrip_reply_counts_stat(
                    buf, t, rt_lo, k_rt, prep_active,
                    n_peers, rt_probs, drop,
                    axis=axis, mode=smode,
                    # replies are per broadcast, i.e. per active (node, window)
                    expand=lambda c: c[:, None] * got_pp_i,
                ),
                rt_lo, len(rt_probs),
            )
        elif classed:
            # the replies of peer class k to a broadcast of ``rt``'s offset
            # ago start their jittered way back now
            def prepare_replies(words_k):  # [K, N, W / 32] packed
                sent_k = lc.unpack_bits(words_k, w)
                rt_counts = dv.roundtrip_reply_counts_classed(
                    k_rt, sent_k.any(axis=(0, 2)), plans["prepare"].bounds,
                    lo, hi, drop, peer_mask=voters, impl=eimpl)
                return jnp.einsum("bki,kiw->biw", rt_counts,
                                  sent_k.astype(jnp.int32))

            prep_rt = push_classed(
                _PREP_RT, "prepare", lc.pack_bits(got_pp), prepare_replies,
                jnp.zeros((len(rt_probs), n_loc, w), jnp.int32), prep_rt,
                lambda buf, c: ring_push_add(buf, t, rt_lo, c),
                rt_lo, len(rt_probs),
            )
        else:
            prep_rt = push(
                _PREP_RT,
                prep_active.any(),
                lambda: (
                    gd.roundtrip_reply_counts_kreg(
                        k_rt, prep_active, nbr_out_loc, ids, lo, hi, drop,
                        peer_mask=voters, axis=axis, impl=eimpl, xg=exchange,
                    ) if kreg else dv.roundtrip_reply_counts_dense(
                        k_rt, prep_active, lo, hi, drop, peer_mask=voters,
                        axis=axis, impl=eimpl,
                    )
                ),
                jnp.zeros((len(rt_probs), n_loc), jnp.int32),
                prep_rt,
                # replies are per broadcast, i.e. per active (node, window)
                lambda buf, rt_counts: ring_push_add(
                    buf, t, rt_lo, rt_counts[:, :, None] * got_pp_i[None, :, :]
                ),
                rt_lo, len(rt_probs),
            )

    with jax.named_scope("pbft.tick.prepare"):
        # ---- PREPARE_RES arrivals → prepare_vote → COMMIT broadcast -------------
        pv = prepare_vote + prep_t
        crossed_p = (prep_t > 0) & (pv >= cfg.pbft_prepare_need)  # pbft-node.cc:231
        if clean:
            crossed_p = crossed_p & ~prep_sent
        prep_sent = prep_sent | crossed_p
        prepare_vote = jnp.where(crossed_p, 0, pv)  # reset on threshold (quirk #4)

        bt = cfg.pbft_block_interval_ms
        is_block_tick = _is_block_tick(cfg, t)
        commit_send = crossed_p & (state.alive & state.honest)[:, None]
        commit_mat = commit_send.astype(jnp.int32)
        if cfg.faults.byz_forge and cfg.faults.n_byzantine > 0:
            # Active attack: Byzantine nodes flood COMMIT votes for the
            # never-proposed last slot (exact mode: window == slot).  Under "n2"
            # there is no per-sender dedup (quirk #2): every copy of every
            # re-send lands in the accumulating counter, so f forgers cross any
            # threshold eventually.  A "2f1" receiver counts at most one vote per
            # sender *ever*, equivalent to the flood collapsing to a single send.
            with jax.named_scope("pbft.tick.forge"):
                if cfg.quorum_rule == "2f1":
                    fire, copies = jnp.equal(t, bt), 1
                else:
                    fire, copies = is_block_tick, cfg.faults.byz_copies
                forgers = (state.alive & ~state.honest).astype(jnp.int32) * jnp.int32(fire)
                commit_mat = commit_mat.at[:, w - 1].add(forgers * copies)
        k_cm = chan_key(tkey, Channel.DELAY_BCAST)
        zeros_w = jnp.zeros((hi - lo, n_loc, w), jnp.int32)
        if stat:
            # fused chain-into-ring (see the prep_rt channel above); the
            # kregular twin gathers the per-(receiver, slot) sender counts
            # over the in-table instead of totals-minus-own
            commit = push(
                _COMMIT,
                (commit_mat > 0).any(),
                tuple,
                (),
                commit,
                lambda buf, _: (
                    gd.push_bcast_slots_stat_kreg(
                        buf, t, lo, k_cm, commit_mat, nbr_in_loc, ids,
                        ow_probs, drop, axis=axis, mode=smode, xg=exchange,
                    ) if kreg else dv.push_bcast_slots_stat(
                        buf, t, lo, k_cm, commit_mat, ow_probs, drop,
                        axis=axis, mode=smode,
                    )
                ),
                lo, hi - lo,
            )
        elif classed:
            commit = push_classed(
                _COMMIT, "commit", commit_mat,
                lambda slot_k: dv.bcast_slots_classed(
                    k_cm, slot_k, plans["commit"].bounds, lo, hi, drop,
                    impl=eimpl),
                zeros_w, commit,
                lambda buf, c: ring_push_add(buf, t, lo, c),
                lo, hi - lo,
            )
        else:
            commit = push(
                _COMMIT,
                (commit_mat > 0).any(),
                lambda: (
                    gd.bcast_slots_kreg(k_cm, commit_mat, nbr_in_loc, ids, lo,
                                        hi, drop, axis=axis, impl=eimpl,
                                        xg=exchange)
                    if kreg else
                    dv.bcast_slots_dense(k_cm, commit_mat, lo, hi, drop,
                                         axis=axis, impl=eimpl)
                ),
                zeros_w,
                commit,
                lambda buf, c: ring_push_add(buf, t, lo, c),
                lo, hi - lo,
            )

    with jax.named_scope("pbft.tick.commit"):
        # ---- COMMIT arrivals → commit_vote → finality ---------------------------
        cv = commit_vote + com_t
        crossed_c = (com_t > 0) & (cv >= cfg.pbft_commit_need)  # pbft-node.cc:248
        if clean:
            crossed_c = crossed_c & ~committed_w
        commit_vote = jnp.where(crossed_c, 0, cv)
        first_commit = crossed_c & ~committed_w
        committed_w = committed_w | crossed_c
        block_num = state.block_num + crossed_c.sum(axis=1)
        # exact mode: an unknown tenant can only be window w itself (identity map)
        eff_sid = jnp.where(slot_id >= 0, slot_id, windows[None, :] if exact else -1)
        unattributed = state.unattributed + (first_commit & (eff_sid < 0)).sum(axis=1)
        slot_commits, slot_commit_tick = _scatter_window_events(
            state.slot_commits, state.slot_commit_tick, None,
            first_commit, eff_sid, t, s,
        )

    with jax.named_scope("pbft.tick.timers"):
        # ---- timers: leader block broadcast every 50 ms (SendBlock) -------------
        # stop at 40 rounds (pbft-node.cc:407). The reference's n_round is
        # process-global (quirk #10); the per-node analog of global round progress
        # is the sequence number next_n, so a post-view-change leader continues
        # the count instead of restarting it.
        send_block = (
            is_block_tick
            & (leader == ids)
            & (next_n < min(cfg.pbft_max_rounds, s))
            & state.alive
        )
        own_w = next_n % w
        own_onehot = (windows[None, :] == own_w[:, None]) & send_block[:, None]
        # the proposer learns its own window's tenant (it never hears its own
        # PRE_PREPARE); in exact mode the counters survive for the same reason
        # as at pp arrival above (identity windows — e.g. a post-view-change
        # leader re-proposing an in-flight slot must not discard its votes)
        slot_id = jnp.where(own_onehot, next_n[:, None], slot_id)
        if not exact:
            prepare_vote = jnp.where(own_onehot, 0, prepare_vote)
            commit_vote = jnp.where(own_onehot, 0, commit_vote)
            prep_sent = prep_sent & ~own_onehot
            committed_w = committed_w & ~own_onehot
        pp_val = own_onehot.astype(jnp.int32) * (next_n[:, None] + 1)
        k_pp = chan_key(tkey, Channel.DELAY_BCAST2)
        if queued:
            # serial-pipe send (engine.cpp link_enqueue): the packet reaches the
            # (leader -> j) link after its random scheduling delay d_j - prop,
            # transmission starts when the link frees, occupies it for ser, then
            # propagates.  A single block sender is guaranteed (no drops ->
            # consistent leader beliefs; enforced by runner._reject_cpp_only),
            # so sender-side scalars globalize with pmax.
            val_sent = jnp.max(jnp.where(send_block, next_n + 1, 0))
            sender = jnp.max(jnp.where(send_block, ids, -1))
            if axis is not None:
                val_sent = jax.lax.pmax(val_sent, axis)
                sender = jax.lax.pmax(sender, axis)
            dest = (val_sent > 0) & (ids != sender)  # crashed peers still get
            # the packet (C++ bcast sends to all); they ignore it at pop time
            d_j = jax.random.randint(
                dv._shard_key(k_pp, axis), (n_loc,), lo, hi, jnp.int32
            )
            link_at = t + d_j - prop
            start = jnp.maximum(link_at, link_busy)
            delivery = start + ser + prop
            link_busy = jnp.where(dest, start + ser, link_busy)
            # enqueue into the first FREE slot (post-pop), never an occupied one:
            # with the FIFO sized to min(max_rounds, max_slots) the occupancy —
            # bounded by the serial-pipe backlog divided by ser, plus in-flight
            # entries — can never fill it, so no undelivered block is ever
            # silently clobbered (delivery matches on ppq_tick == t, so slot
            # order is irrelevant)
            q = ppq_tick.shape[1]
            free = ppq_tick == _NEVER  # [N, Q]
            first_free = jnp.argmax(free, axis=1)
            oh_q = (
                (jnp.arange(q)[None, :] == first_free[:, None])
                & dest[:, None]
                & free
            )
            ppq_tick = jnp.where(oh_q, delivery[:, None], ppq_tick)
            ppq_val = jnp.where(oh_q, val_sent, state.ppq_val)
        else:
            ppq_val = state.ppq_val

        def push_pp(buf, contrib):
            return ring_push_max(buf, t, lo + ser, contrib)

        if queued:
            pass  # blocks already enqueued on the serial pipes; ring untouched
        elif gossip:
            # origin injection (TTL = gossip_hops) + this tick's relays, one
            # flood push over the out-edges; every hop re-serializes the block
            # (store-and-forward), hence the ser term on each leg
            h_enc = cfg.gossip_hops + 1
            origin_enc = (pp_val * h_enc + cfg.gossip_hops) * (pp_val > 0)
            # the proposer must never process its own announcement (the reference
            # leader never hears its own PRE_PREPARE); self-loop edges exist in
            # the random digraph, so mark the origin's copy as already seen
            seen_pp = jnp.maximum(seen_pp, origin_enc)
            pp_out = jnp.maximum(origin_enc, pp_fwd)
            pp = push(
                _PP,
                (pp_out > 0).any(),
                lambda: dv.gossip_fwd(k_pp, pp_out, nbrs_loc, n, lo, hi, drop,
                                      axis=axis, impl=eimpl),
                zeros_w,
                pp,
                push_pp,
                lo + ser, hi - lo,
            )
        elif kreg:
            pp = push(
                _PP,
                send_block.any(),
                lambda: (
                    gd.bcast_window_value_max_stat_kreg(
                        k_pp, pp_val, nbr_in_loc, ow_probs, drop, axis=axis,
                        xg=exchange)
                    if stat else
                    gd.bcast_window_value_max_kreg(
                        k_pp, pp_val, nbr_in_loc, ids, lo, hi, drop, axis=axis,
                        impl=eimpl, xg=exchange)
                ),
                zeros_w,
                pp,
                push_pp,
                lo + ser, hi - lo,
            )
        elif classed:
            def pre_prepares(val_k):  # [K, N] slot id + 1
                in_w = windows[None, None, :] == ((val_k - 1) % w)[:, :, None]
                return dv.bcast_window_value_max_classed(
                    k_pp, jnp.where(in_w, val_k[:, :, None], 0),
                    plans["pp"].bounds, lo, hi, drop, impl=eimpl)

            pp = push_classed(
                _PP, "pp", jnp.where(send_block, next_n + 1, 0), pre_prepares,
                zeros_w, pp, push_pp, lo + ser, hi - lo,
            )
        elif stat:
            pp = push(
                _PP,
                send_block.any(),
                lambda: dv.bcast_window_value_max_stat(k_pp, pp_val, ow_probs, drop,
                                                       axis=axis),
                zeros_w,
                pp,
                push_pp,
                lo + ser, hi - lo,
            )
        else:
            pp = push(
                _PP,
                send_block.any(),
                lambda: dv.bcast_window_value_max_dense(k_pp, pp_val, lo, hi, drop,
                                                        axis=axis, impl=eimpl),
                zeros_w,
                pp,
                push_pp,
                lo + ser, hi - lo,
            )
        rounds_sent = state.rounds_sent + send_block
        (slot_propose_tick,) = _scatter_window_events(
            None, None, state.slot_propose_tick,
            own_onehot, jnp.where(own_onehot, next_n[:, None], -1), t, s,
        )
        next_n = next_n + send_block

        # ---- random view change (P = 1/100 per leader round) --------------------
        k_u = chan_key(tkey, Channel.VIEW_CHANGE)
        if axis is not None:
            k_u = jax.random.fold_in(k_u, jax.lax.axis_index(axis))
        u = jax.random.randint(k_u, (n_loc,), 0, cfg.pbft_view_change_den)
        trigger = send_block & (u < cfg.pbft_view_change_num)
        new_leader = (leader + 1) % n  # rotation (pbft-node.cc:297)
        new_v = v + 1
        leader = jnp.where(trigger, new_leader, leader)
        v = jnp.where(trigger, new_v, v)
        view_changes = state.view_changes + trigger
        enc = jnp.where(trigger, new_v * n + new_leader + 1, 0)
        k_vc = chan_key(tkey, Channel.DELAY_REPLY)
        zeros_flat = jnp.zeros((hi - lo, n_loc), jnp.int32)

        def push_vc(buf, contrib):
            return ring_push_max(buf, t, lo, contrib)

        if gossip:
            h_enc = cfg.gossip_hops + 1
            vc_origin = (enc * h_enc + cfg.gossip_hops) * (enc > 0)
            seen_vc = jnp.maximum(seen_vc, vc_origin)  # self-loop guard
            vc_out = jnp.maximum(vc_origin, vc_fwd)
            vc = push(
                _VC,
                (vc_out > 0).any(),
                lambda: dv.gossip_fwd(k_vc, vc_out[:, None], nbrs_loc, n, lo, hi,
                                      drop, axis=axis, impl=eimpl)[:, :, 0],
                zeros_flat,
                vc,
                push_vc,
                lo, hi - lo,
            )
        elif kreg:
            vc = push(
                _VC,
                trigger.any(),
                lambda: (
                    gd.bcast_value_max_stat_kreg(k_vc, enc, nbr_in_loc, ow_probs,
                                                 drop, axis=axis, xg=exchange)
                    if stat else
                    gd.bcast_value_max_kreg(k_vc, trigger, enc, nbr_in_loc, ids,
                                            lo, hi, drop, axis=axis, impl=eimpl,
                                            xg=exchange)
                ),
                zeros_flat,
                vc,
                push_vc,
                lo, hi - lo,
            )
        elif classed:
            vc = push_classed(
                _VC, "vc", enc,
                lambda enc_k: dv.bcast_value_max_classed(
                    k_vc, enc_k, plans["vc"].bounds, lo, hi, drop, impl=eimpl),
                zeros_flat, vc, push_vc, lo, hi - lo,
            )
        elif stat:
            vc = push(
                _VC,
                trigger.any(),
                lambda: dv.bcast_value_max_stat(k_vc, enc, ow_probs, drop, axis=axis),
                zeros_flat,
                vc,
                push_vc,
                lo, hi - lo,
            )
        else:
            vc = push(
                _VC,
                trigger.any(),
                lambda: dv.bcast_value_max_dense(k_vc, trigger, enc, lo, hi, drop,
                                                 axis=axis, impl=eimpl),
                zeros_flat,
                vc,
                push_vc,
                lo, hi - lo,
            )

    state = state.replace(
        seen_pp=seen_pp,
        seen_vc=seen_vc,
        link_busy=link_busy,
        ppq_tick=ppq_tick,
        ppq_val=ppq_val,
        v=v,
        leader=leader,
        next_n=next_n,
        rounds_sent=rounds_sent,
        slot_id=slot_id,
        prepare_vote=prepare_vote,
        commit_vote=commit_vote,
        prep_sent=prep_sent,
        committed_w=committed_w,
        block_num=block_num,
        unattributed=unattributed,
        view_changes=view_changes,
        slot_commits=slot_commits,
        slot_commit_tick=slot_commit_tick,
        slot_propose_tick=slot_propose_tick,
    )
    bufs = PbftBufs(pp=pp, prep_rt=prep_rt, commit=commit, vc=vc,
                    due=jnp.stack(due) if mark_due else bufs.due, lines=lines)
    return state, bufs


def metrics(cfg, state: PbftState) -> dict:
    """Reproduce the reference's measurement surface (SURVEY.md §5): per-block
    commit events with times (pbft-node.cc:259), rounds sent (:408), view
    changes (:278) — as structured host-side values, recomputed from the
    per-slot accumulators (identical to the per-(node,slot) bookkeeping in
    exact mode; windowed mode trades the full table for O(S) summaries)."""
    alive = np.asarray(state.alive)
    n_alive = int(alive.sum())
    commits = np.asarray(state.slot_commits)
    commit_tick = np.asarray(state.slot_commit_tick)
    propose_tick = np.asarray(state.slot_propose_tick)
    proposed = propose_tick < int(_NEVER)
    # a slot is final when every alive node finalized it (>= guards the
    # mixed sim's fluctuating membership: a node can finalize, then die)
    per_slot_done = (commits >= max(n_alive, 1)) & (n_alive > 0) & proposed
    n_final = int(per_slot_done.sum())
    last = commit_tick[per_slot_done].max() if n_final else -1
    # time-to-finality per block: last commit tick − the tick the block was
    # actually proposed (a view change stalls the pipeline, so
    # (slot+1)*interval would undercount after one)
    rounds = int(np.asarray(state.next_n).max())
    ttf = [
        float(commit_tick[s] - propose_tick[s])
        for s in range(min(rounds, len(commits)))
        if per_slot_done[s]
    ]
    # safety: a slot some alive node finalized although NO node ever proposed
    # it can only come from forged votes reaching quorum (quirk #2: the
    # reference's no-dedup counting lets f Byzantine nodes muster f*copies
    # votes; the 2f1 rule makes this impossible for f <= (n-1)//3)
    forged = (commits > 0) & ~proposed
    forged_commits = int(forged.sum())
    unattributed = int(np.asarray(state.unattributed).sum())
    out = {
        "protocol": "pbft",
        "n": cfg.n,
        "rounds_sent": rounds,
        "forged_commits": forged_commits,
        "unattributed_commits": unattributed,
        "leader_rounds_max": int(np.asarray(state.rounds_sent).max()),
        "blocks_final_all_nodes": n_final,
        "block_num_max": int(np.asarray(state.block_num).max()),
        "view_changes": int(np.asarray(state.view_changes).sum()),
        "last_commit_ms": float(last),
        "mean_time_to_finality_ms": float(np.mean(ttf)) if ttf else -1.0,
        # agreement is structural in this design: the PRE_PREPARE channel
        # carries the slot id (= the reference's val, generateTX
        # pbft-node.cc:92) and commits bind to it; the failure modes that
        # remain observable are forged/unattributed commits, reported above
        "agreement_ok": bool(forged_commits == 0 and unattributed == 0),
    }
    if cfg.faults.byz_forge:
        # how soon the attack won: the tick at which the last node so far
        # finalized a never-proposed slot (-1: none did), and how many nodes
        # did.  Only a forging deployment's rows carry the two.
        out["forged_commit_ms"] = (
            float(commit_tick[forged].max()) if forged_commits else -1.0)
        out["forged_commit_nodes"] = int(commits[forged].max()) \
            if forged_commits else 0
    return out


# the state fields :func:`metrics` reads, and the only ones: a batched
# dispatch fetches these leaves alone (parallel/sweep._readback) and hands
# ``metrics`` a state whose other fields are None
METRIC_FIELDS = (
    "alive", "next_n", "rounds_sent", "block_num", "unattributed",
    "view_changes", "slot_commits", "slot_commit_tick", "slot_propose_tick",
)
