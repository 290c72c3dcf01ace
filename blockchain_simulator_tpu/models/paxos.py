"""Single-decree Paxos — tensorized state machine.

Re-design of the reference's ``PaxosNode`` (paxos/paxos-node.h:19,
paxos-node.cc): a ticket (ballot) / propose / commit three-phase protocol where
every node is an acceptor and nodes 0..2 concurrently act as proposers from
t=0 (paxos-node.cc:136-138).  Reference call stack (SURVEY.md §3.4):

- ``requireTicket`` (paxos-node.cc:511-518): ``ticket += 1``, broadcast
  REQUEST_TICKET ``[0, ticket]`` with per-peer random delay U[0,50) ms
  (paxos-node.cc:397-400).
- acceptor REQUEST_TICKET: promise iff ``t > t_max`` (then ``t_max = t``),
  reply ``[RESPONSE_TICKET, SUCCESS, command]`` / ``[.., FAILED]``
  (paxos-node.cc:177-197).
- acceptor REQUEST_PROPOSE ``[1, t, c]``: accept iff ``t == t_max`` (then
  ``command = c; t_store = t``) (paxos-node.cc:199-221).
- acceptor REQUEST_COMMIT ``[2, t, c]``: execute iff ``t == t_store &&
  c == command`` (latch ``isCommit``; keeps replying SUCCESS)
  (paxos-node.cc:222-247).
- proposer RESPONSE_*: one *shared* ``vote_success``/``vote_failed`` counter
  pair counts replies of *all three* response types; the window closes when
  ``vote_success + vote_failed == N-2`` exactly and the action (send next
  phase's request / log CLIENT COMMIT SUCCESS / retry ``requireTicket``) is
  chosen by the *type of the reply that closed the window* with threshold
  ``vote_success >= N/2`` (paxos-node.cc:248-353).

Quirk fidelity (SURVEY.md §2 quirks #7/#8): the reference's broadcast loop
increments the peer iterator *before* use (paxos-node.cc:478-496), skipping the
first peer (node 0 for senders > 0, node 1 for sender 0) and dereferencing
``end()`` — so every broadcast reaches exactly N-2 valid peers, which is why
the ``N-2`` reply window closes at all.  ``fidelity="reference"`` models
exactly that: requests skip the sender's first peer, shared cross-phase
counters, ``>= N/2`` threshold, window closes on crossing ``N-2`` cumulative
replies (the strict ``==`` of the serial original is relaxed to a crossing
check because a tick can deliver several replies at once — documented
divergence).  ``fidelity="clean"`` fixes the protocol: full N-1 broadcast,
per-phase counters keyed to the proposer's phase register, the proposer
processes its own request as an acceptor (self-promise/self-accept — real
Paxos; the reference only gets this accidentally through its echo loop),
advance as soon as supporters reach ``N/2 + 1`` (a true majority of all N
acceptors including self, so any two quorums intersect), retry only on a
jittered per-window timeout (``paxos_retry_timeout_ms`` — without a timeout a
single dropped reply wedges a proposer forever; timeout-only retry also keeps
windows temporally disjoint so stale replies never pollute a fresh quorum
count), and promise replies carry ``t_store`` so the proposer
adopts the command with the *highest* store ticket (real Paxos adoption; the
reference adopts whatever command byte rides the window-closing reply,
paxos-node.cc:264-266, including FAILED replies whose command byte is
uninitialized stack memory — behavior we do not reproduce).

Echo-back (quirk #1, paxos-node.cc:158) is not modeled anywhere in this
framework — neither here nor in the C++ reference engine (engine.cpp:29-31
lists it as a deliberate, shared divergence): reflecting every packet to its
sender makes packets ping-pong forever (each reflection is itself reflected),
so the upstream event queue never drains, and nothing meaningful depends on
the echoes (they land in the "wrong msg" default branch).  Differential tests
therefore compare both backends with echo off (tests/test_differential.py).

Tensorization: proposer fan-in is O(P) with P = ``paxos_n_proposers`` (3), so
all channels are identity-preserving ``[.., N, P]`` tensors and delivery is
O(N·P) per tick in *both* delivery modes (``cfg.delivery`` is ignored — there
is no O(N²) structure to aggregate away).  Acceptor processing of concurrent
same-tick requests is serialized in proposer order 0..P-1 (statically
unrolled), a deterministic stand-in for the reference's arrival-order
processing.  Retries cap at ``paxos_max_ticket`` (the reference's single-char
codec would corrupt beyond '0'+9 anyway, quirk #11).

Gossip topology (``topology="gossip"``, BASELINE config 3): requests are not
broadcast — they *flood* over a random k-out digraph (ops/topology.py) with a
hop TTL.  Channel values carry ``encoded * H + hops_left`` (H = gossip_hops+1,
so a higher ticket always dominates in the max-combine regardless of TTL); a
node that sees a new request value (per-proposer monotone ``seen`` table —
request encodings strictly increase per proposer, which is what makes
value-dedup sound) processes it as an acceptor and replies *directly* to the
proposer (response overlay — replies are point-to-point in the protocol;
gossip is for dissemination).  Forwarding triggers on any strictly better
*TTL-encoded* copy (same value, more hops left), so a fast many-hop path
delivering a nearly-expired copy first cannot permanently truncate the flood
— the later fresher copy still propagates.  Per-tick cost is O(N·deg·P).
Clean-fidelity window timeouts must cover the full flood + reply horizon
``(gossip_hops+2) * delay_hi`` — up to gossip_hops+1 flood legs (arrival TTLs
gossip_hops..0) plus the reply leg — validated in ``init`` so the
temporal-separation argument still holds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct

from blockchain_simulator_tpu.models.base import fault_masks, gated_push
from blockchain_simulator_tpu.ops import delay as delay_ops
from blockchain_simulator_tpu.ops import delivery as dv
from blockchain_simulator_tpu.ops import mesh as mesh_ops
from blockchain_simulator_tpu.ops import topology
from blockchain_simulator_tpu.ops import gatherdeliv as gd
from blockchain_simulator_tpu.ops.ring import ring_pop, ring_push_add, ring_push_max
from blockchain_simulator_tpu.utils.prng import Channel, chan_key

# the phases of :func:`step` as ``jax.named_scope`` names, after its own
# section comments (HLO metadata only — see models/pbft.SCOPES); ops/ scopes
# nest inside unrenamed.  ``flood_fwd`` is the push of this tick's requests:
# the TTL flood on a gossip overlay, the direct broadcast elsewhere.
SCOPES = (
    "paxos.tick.pop",
    "paxos.tick.flood_decode",
    "paxos.tick.acceptor",
    "paxos.tick.reply",
    "paxos.tick.proposer",
    "paxos.tick.timers",
    "paxos.tick.flood_fwd",
)

# what :func:`metrics` reports of a run's timing, beside the counts: spans
# of one window or one flood, which depend on the delay distribution and the
# overlay and not on which proposer's window won (benchmark/paxos_checks.py
# compares them with the per-message reference's).  All in ms; -1.0 where
# no proposer committed.
#   winner_window_ms      the winner's ticket request sent -> its CLIENT
#                         COMMIT SUCCESS: three floods, three reply quorums
#   commit_flood_ms       the winner's commit request sent -> the last
#                         acceptor's first execute: one flood to its last node
#   first_execute_lag_ms  first_execute_ms - the winner's commit request sent
#   solo_window_ms        as winner_window_ms, of the committed proposer whose
#                         window opened after every other proposer was done
#                         (no competing ticket in flight); -1.0 without one
MILESTONES = (
    "winner_window_ms", "commit_flood_ms", "first_execute_lag_ms",
    "solo_window_ms",
)

# proposer phase register
PH_TICKET, PH_PROPOSE, PH_COMMIT, PH_DONE = 0, 1, 2, 3
PH_IDLE = -1  # non-proposer rows


@struct.dataclass
class PaxosState:
    # acceptor state (paxos-node.h:40-43)
    t_max: jax.Array      # [N] highest ticket promised
    command: jax.Array    # [N] stored command; -1 = 'e' empty sentinel
    t_store: jax.Array    # [N] ticket of the stored command
    is_commit: jax.Array  # [N] bool — command executed (latch)
    exec_tick: jax.Array  # [N] first execute tick, -1 = never
    # proposer state (paxos-node.h:45-52); rows >= P are inert
    ticket: jax.Array        # [N] current ticket (0 until first requireTicket)
    phase: jax.Array         # [N] PH_*; informational in reference fidelity
    vote_success: jax.Array  # [N]
    vote_failed: jax.Array   # [N]
    proposal: jax.Array      # [N] command to propose (init own id, may adopt)
    adopt_val: jax.Array     # [N] max promise encoding seen this window
    commit_tick: jax.Array   # [N] CLIENT COMMIT SUCCESS tick (-1 = never)
    gave_up: jax.Array       # [N] bool — retry budget exhausted
    window_deadline: jax.Array  # [N] clean-fidelity retry timeout tick
    # send ticks of the current window's ticket and commit requests (-1 =
    # none yet): what MILESTONES are counted from.  Written, never read by
    # the protocol.
    tk_send_tick: jax.Array  # [N]
    cm_send_tick: jax.Array  # [N]
    seen_req: jax.Array      # [N, 3, P] gossip dedup: highest TTL-encoded
    # request copy seen per (channel, proposer); zeros and unused on full mesh
    alive: jax.Array
    honest: jax.Array


@struct.dataclass
class PaxosBufs:
    # requests, value-encoded and max-combined (0 = empty):
    #   req_ticket[d, i, p] = ticket
    #   req_propose/req_commit[d, i, p] = ticket*(n+1) + command + 1
    req_ticket: jax.Array   # [D, N, P]
    req_propose: jax.Array  # [D, N, P]
    req_commit: jax.Array   # [D, N, P]
    # responses, landing at proposer rows; last axis = response type
    # (0 ticket, 1 propose, 2 commit)
    resp_ok: jax.Array      # [D, N, 3] SUCCESS counts (add)
    resp_no: jax.Array      # [D, N, 3] FAILED counts (add)
    # promise payloads: t_store*(n+1) + command + 1, max-combined (0 = empty /
    # empty-command 'e' promise)
    resp_cmd: jax.Array     # [D, N]


def init(cfg, key=None):
    n, d, p = cfg.n, cfg.ring_depth, cfg.paxos_n_proposers
    if cfg.fidelity == "clean":
        _, rt_hi = cfg.roundtrip_range()
        horizon = rt_hi
        if cfg.topology == "gossip":
            # an origin send with TTL=gossip_hops can traverse gossip_hops+1
            # flood legs (arrival TTLs gossip_hops..0 all processed + replied)
            # plus the direct reply leg, each up to hi-1 ms
            horizon = (cfg.gossip_hops + 2) * cfg.one_way_range()[1]
        if cfg.paxos_retry_timeout_ms < horizon:
            raise ValueError(
                f"paxos_retry_timeout_ms={cfg.paxos_retry_timeout_ms} must be "
                f">= the max reply horizon ({horizon} ms): clean-fidelity "
                "correctness relies on abandoned windows draining before retry"
            )
    alive, honest = fault_masks(cfg, n)
    ids = jnp.arange(n)
    zi = lambda *sh: jnp.zeros(sh, jnp.int32)
    zb = lambda *sh: jnp.zeros(sh, bool)
    state = PaxosState(
        t_max=zi(n),
        command=jnp.full((n,), -1, jnp.int32),  # 'e' (paxos-node.cc:63)
        t_store=zi(n),
        is_commit=zb(n),
        exec_tick=jnp.full((n,), -1, jnp.int32),
        ticket=zi(n),
        phase=jnp.where(ids < p, PH_TICKET, PH_IDLE).astype(jnp.int32),
        vote_success=zi(n),
        vote_failed=zi(n),
        proposal=ids.astype(jnp.int32),  # proposal = '0'+m_id (paxos-node.cc:66)
        adopt_val=zi(n),
        commit_tick=jnp.full((n,), -1, jnp.int32),
        gave_up=zb(n),
        window_deadline=jnp.full((n,), 1 << 30, jnp.int32),
        tk_send_tick=jnp.full((n,), -1, jnp.int32),
        cm_send_tick=jnp.full((n,), -1, jnp.int32),
        seen_req=zi(n, 3, p),
        alive=alive,
        honest=honest,
    )
    bufs = PaxosBufs(
        req_ticket=zi(d, n, p),
        req_propose=zi(d, n, p),
        req_commit=zi(d, n, p),
        resp_ok=zi(d, n, 3),
        resp_no=zi(d, n, 3),
        resp_cmd=zi(d, n),
    )
    return state, bufs


def _req_contrib(key, val_local, lo, hi, drop, axis, ids, p, ref_skip,
                 impl="threefry", inmask=None):
    """Broadcast contribution for one request channel: local per-node request
    values (nonzero only at proposer rows) → [B, N_loc, P] value tensor for
    ``ring_push_max``.  ``ref_skip`` drops the sender's first peer (the
    reference's iterator bug, paxos-node.cc:478-496).  ``inmask`` ([N_loc,
    P] bool) restricts delivery to receivers whose kregular in-table
    contains the proposer (topo/spec.py) — paxos delivery is already
    O(N*P), so the overlay is a static reachability mask on the SAME delay
    draws: all-true at degree k = N-1, hence bit-equal to the full mesh."""
    n_loc = val_local.shape[0]
    val_g = dv._gather(val_local, axis)[:p]  # [P] global proposer values
    k = dv._shard_key(key, axis)
    d = delay_ops.sample_edge_delays(k, (n_loc, p), lo, hi, impl)
    prop_ids = jnp.arange(p)
    mask = (val_g[None, :] > 0) & (ids[:, None] != prop_ids[None, :])
    if inmask is not None:
        mask = mask & inmask
    if ref_skip:
        first_peer = jnp.where(prop_ids == 0, 1, 0)
        mask = mask & (ids[:, None] != first_peer[None, :])
    if drop > 0.0:
        keep = jax.random.bernoulli(
            jax.random.fold_in(k, 0x0D20), 1.0 - drop, (n_loc, p)
        )
        mask = mask & keep
    m = mask.astype(jnp.int32)
    return (
        (d[None] == dv._bucket_iota(lo, hi, d.ndim)).astype(jnp.int32)
        * (m * val_g[None, :])[None]
    )


def _gossip_fwd_contrib(key, fwd_vals, nbrs_loc, n_glob, lo, hi, drop, axis,
                        impl="threefry"):
    """TTL-flood forwarding for the three request channels — shared op
    (ops/delivery.gossip_fwd), P = proposer lanes here."""
    return dv.gossip_fwd(key, fwd_vals, nbrs_loc, n_glob, lo, hi, drop, axis,
                         impl=impl)


def _reply_contribs(key, ok_wire, no_wire, cmd_wire, lo, hi, drop, axis, ids, p,
                    impl="threefry"):
    """Unicast acceptor→proposer replies: per-(acceptor, proposer, type) wires
    → (ok [B, N_loc, 3], no [B, N_loc, 3], cmd [B, N_loc]) contributions at
    the *local* proposer rows.  Each reply is its own packet with its own delay
    draw (paxos-node.cc:405-446); the promise payload rides the type-0 reply.
    Sharded, counts psum / payloads pmax across shards (the repliers)."""
    n_loc = ok_wire.shape[0]
    k = dv._shard_key(key, axis)
    d = delay_ops.sample_edge_delays(k, (n_loc, p, 3), lo, hi, impl)
    if drop > 0.0:
        keep = jax.random.bernoulli(
            jax.random.fold_in(k, 0x0D21), 1.0 - drop, (n_loc, p, 3)
        ).astype(jnp.int32)
        ok_wire = ok_wire * keep
        no_wire = no_wire * keep
        cmd_wire = cmd_wire * keep[:, :, 0]
    # one broadcast compare per channel instead of nb masked passes over the
    # [N_loc, P, 3] wire tensors (integer reductions — bit-equal either way)
    hits = (d[None] == dv._bucket_iota(lo, hi, d.ndim)).astype(jnp.int32)
    ok_b = (hits * ok_wire[None]).sum(1)  # [B, P, 3]
    no_b = (hits * no_wire[None]).sum(1)
    cmd_b = (hits[:, :, :, 0] * cmd_wire[None]).max(1)  # [B, P]
    if axis is not None:
        ok_b = mesh_ops.psum(ok_b, axis)
        no_b = mesh_ops.psum(no_b, axis)
        cmd_b = mesh_ops.pmax(cmd_b, axis)
    take = jnp.clip(ids, 0, p - 1)
    is_prop = (ids < p).astype(jnp.int32)
    return (
        ok_b[:, take, :] * is_prop[None, :, None],
        no_b[:, take, :] * is_prop[None, :, None],
        cmd_b[:, take] * is_prop[None, :],
    )


def step(cfg, state: PaxosState, bufs: PaxosBufs, t, tkey, *,
         topo_tables=None, exchange=None):
    n, p = cfg.n, cfg.paxos_n_proposers
    axis = cfg.mesh_axis
    lo, hi = cfg.one_way_range()
    drop = cfg.faults.drop_prob
    clean = cfg.fidelity == "clean"
    eimpl = cfg.eff_edge_sampler
    c_enc = n + 1  # encoding base: val = ticket * c_enc + command + 1
    n_loc = state.t_max.shape[0]
    ids = dv._global_ids(n_loc, axis)
    nb = hi - lo

    with jax.named_scope("paxos.tick.pop"):
        # ---- pop arrivals; crashed nodes process nothing ------------------------
        rt_t, req_ticket = ring_pop(bufs.req_ticket, t)
        rp_t, req_propose = ring_pop(bufs.req_propose, t)
        rc_t, req_commit = ring_pop(bufs.req_commit, t)
        ok_t, resp_ok = ring_pop(bufs.resp_ok, t)
        no_t, resp_no = ring_pop(bufs.resp_no, t)
        cmd_t, resp_cmd = ring_pop(bufs.resp_cmd, t)
        am = state.alive.astype(jnp.int32)
        rt_t, rp_t, rc_t = rt_t * am[:, None], rp_t * am[:, None], rc_t * am[:, None]
        ok_t, no_t = ok_t * am[:, None], no_t * am[:, None]
        cmd_t = cmd_t * am

    with jax.named_scope("paxos.tick.flood_decode"):
        # ---- gossip decode: TTL values → new-request dedup + forward set --------
        gossip = cfg.topology == "gossip"
        # kregular overlay: requests reach only receivers whose in-table holds
        # the proposer (static [N_loc, P] reachability mask over the SAME
        # O(N*P) delivery — paxos has no N x N structure to sparsify); replies
        # stay point-to-point on the reverse edge, the same response-overlay
        # rule the gossip arm documents.  Clean-fidelity windows that cannot
        # reach a majority simply time out and retry until gave_up.
        kreg = cfg.topology == "kregular"
        inmask = None
        if kreg:
            # paxos never reads cross-row state through the tables (the inmask
            # below is row-local), so exchange mode only switches the row
            # indexing to the ids=None operand pass-through
            nbr_in_loc, _ = gd.local_tables(
                cfg, None if exchange is not None else ids, tables=topo_tables)
            inmask = (
                nbr_in_loc[:, :, None] == jnp.arange(p)[None, None, :]
            ).any(axis=1)  # [N_loc, P]
        seen_req = state.seen_req
        fwd_vals = None
        if gossip:
            h_enc = cfg.gossip_hops + 1
            nbrs_loc = jnp.take(
                jnp.asarray(topology.kregular_out_neighbors(n, cfg.degree, cfg.seed)),
                ids, axis=0,
            )
            fwd_vals, proc = [], []
            for ci, arr in enumerate((rt_t, rp_t, rc_t)):
                base, hops = arr // h_enc, arr % h_enc
                seen = seen_req[:, ci, :]
                # acceptors process each base value once (first sighting) ...
                new_base = (base > seen // h_enc) & state.alive[:, None]
                # ... but forward any strictly better TTL-encoded copy, so a
                # nearly-expired first arrival can't truncate the flood
                better = (arr > seen) & state.alive[:, None]
                proc.append(base * new_base)
                seen_req = seen_req.at[:, ci, :].max(arr * better)
                fwd_vals.append(
                    (base * h_enc + jnp.maximum(hops - 1, 0)) * (better & (hops > 0))
                )
            rt_t, rp_t, rc_t = proc

    with jax.named_scope("paxos.tick.acceptor"):
        # ---- acceptor FSM: concurrent requests serialized in proposer order -----
        t_max, command, t_store = state.t_max, state.command, state.t_store
        is_commit, exec_tick = state.is_commit, state.exec_tick
        tk_ok, tk_no, prom = [], [], []
        for q in range(p):  # REQUEST_TICKET (paxos-node.cc:177-197)
            tk = rt_t[:, q]
            ok = (tk > 0) & (tk > t_max)
            prom.append(jnp.where(ok & (command >= 0), t_store * c_enc + command + 1, 0))
            t_max = jnp.where(ok, tk, t_max)
            tk_ok.append(ok)
            tk_no.append((tk > 0) & ~ok)
        pr_ok, pr_no = [], []
        for q in range(p):  # REQUEST_PROPOSE (paxos-node.cc:199-221)
            v = rp_t[:, q]
            tkt, cmd = v // c_enc, v % c_enc - 1
            ok = (v > 0) & (tkt == t_max)
            command = jnp.where(ok, cmd, command)
            t_store = jnp.where(ok, tkt, t_store)
            pr_ok.append(ok)
            pr_no.append((v > 0) & ~ok)
        cm_ok, cm_no = [], []
        for q in range(p):  # REQUEST_COMMIT (paxos-node.cc:222-247)
            v = rc_t[:, q]
            tkt, cmd = v // c_enc, v % c_enc - 1
            ok = (v > 0) & (tkt == t_store) & (cmd == command)
            exec_tick = jnp.where(ok & (exec_tick < 0), jnp.int32(t), exec_tick)
            is_commit = is_commit | ok
            cm_ok.append(ok)
            cm_no.append((v > 0) & ~ok)
        ok_wire = jnp.stack(
            [jnp.stack(tk_ok, 1), jnp.stack(pr_ok, 1), jnp.stack(cm_ok, 1)], axis=2
        ).astype(jnp.int32)  # [N_loc, P, 3]
        no_wire = jnp.stack(
            [jnp.stack(tk_no, 1), jnp.stack(pr_no, 1), jnp.stack(cm_no, 1)], axis=2
        ).astype(jnp.int32)
        # Byzantine acceptors flip their votes; only honest promises carry payloads
        hn = state.honest[:, None, None]
        ok_w = jnp.where(hn, ok_wire, no_wire)
        no_w = jnp.where(hn, no_wire, ok_wire)
        cmd_wire = jnp.stack(prom, 1) * state.honest[:, None].astype(jnp.int32)

    with jax.named_scope("paxos.tick.reply"):
        any_req = (rt_t > 0).any() | (rp_t > 0).any() | (rc_t > 0).any()
        k_r = chan_key(tkey, Channel.DELAY_REPLY)
        zeros_ok = jnp.zeros((nb, n_loc, 3), jnp.int32)
        zeros_cmd = jnp.zeros((nb, n_loc), jnp.int32)
        resp_ok, resp_no, resp_cmd = gated_push(
            any_req,
            lambda: _reply_contribs(k_r, ok_w, no_w, cmd_wire, lo, hi, drop, axis,
                                    ids, p, impl=eimpl),
            (zeros_ok, zeros_ok, zeros_cmd),
            (resp_ok, resp_no, resp_cmd),
            lambda rings, c: (
                ring_push_add(rings[0], t, lo, c[0]),
                ring_push_add(rings[1], t, lo, c[1]),
                ring_push_max(rings[2], t, lo, c[2]),
            ),
            axis,
        )

    with jax.named_scope("paxos.tick.proposer"):
        # ---- proposer FSM: response counting ------------------------------------
        adopt_val = jnp.maximum(state.adopt_val, cmd_t)
        vs, vf = state.vote_success, state.vote_failed
        active = (ids < p) & state.alive & ~state.gave_up

        if clean:
            # per-phase counters: only replies of the current phase's type count;
            # vs/vf include the proposer's own acceptor vote (cast at send time)
            ph = state.phase
            waiting = active & (ph >= PH_TICKET) & (ph <= PH_COMMIT)
            sel = jnp.clip(ph, 0, 2)
            arr_ok = jnp.take_along_axis(ok_t, sel[:, None], 1)[:, 0] * waiting
            arr_no = jnp.take_along_axis(no_t, sel[:, None], 1)[:, 0] * waiting
            vs, vf = vs + arr_ok, vf + arr_no
            majority = cfg.quorum + 1  # true majority of all n acceptors (incl.
            # self): any two quorums intersect
            advance = waiting & (vs >= majority)
            # retry ONLY by window timeout, never early on failure counts: the
            # timeout exceeds the maximum reply round trip (asserted in init), so
            # an abandoned window's in-flight replies have fully drained before
            # the next same-type window opens — stale replies can never
            # double-count into a fresh window's quorum (exactness by temporal
            # separation; reply channels carry no ticket identity to filter by)
            want_retry = waiting & ~advance & (jnp.int32(t) >= state.window_deadline)
            adv0 = advance & (ph == PH_TICKET)
            adv1 = advance & (ph == PH_PROPOSE)
            adv2 = advance & (ph == PH_COMMIT)
        else:
            # shared counters, window closes crossing N-2 cumulative replies, the
            # closing reply's type picks the action (paxos-node.cc:248-353);
            # intra-tick reply order is fixed ticket → propose → commit
            win = n - 2
            before = vs + vf
            arr = ok_t + no_t  # [N_loc, 3]
            cum0 = before + arr[:, 0]
            cum1 = cum0 + arr[:, 1]
            cum2 = cum1 + arr[:, 2]
            crossed = active & (before < win) & (cum2 >= win)
            ctype = jnp.where(cum0 >= win, 0, jnp.where(cum1 >= win, 1, 2))
            vs_at = (
                vs
                + ok_t[:, 0]
                + jnp.where(ctype >= 1, ok_t[:, 1], 0)
                + jnp.where(ctype >= 2, ok_t[:, 2], 0)
            )
            success = vs_at >= cfg.quorum  # vote_success >= N/2 (paxos-node.cc:259)
            adv0 = crossed & success & (ctype == 0)
            adv1 = crossed & success & (ctype == 1)
            adv2 = crossed & success & (ctype == 2)
            want_retry = crossed & ~success
            # counters reset at the crossing; replies of later types keep counting
            left_ok = jnp.where(
                ctype == 0, ok_t[:, 1] + ok_t[:, 2], jnp.where(ctype == 1, ok_t[:, 2], 0)
            )
            left_no = jnp.where(
                ctype == 0, no_t[:, 1] + no_t[:, 2], jnp.where(ctype == 1, no_t[:, 2], 0)
            )
            vs = jnp.where(crossed, left_ok, vs + ok_t.sum(1))
            vf = jnp.where(crossed, left_no, vf + no_t.sum(1))

        # adoption at ticket→propose: highest-t_store promise wins (clean Paxos);
        # the reference's adopt-from-closing-reply (paxos-node.cc:264-266) is
        # order-dependent UB we determinize the same way
        adopted_cmd = adopt_val % c_enc - 1
        proposal = jnp.where(adv0 & (adopt_val > 0), adopted_cmd, state.proposal)

        # CLIENT COMMIT SUCCESS (paxos-node.cc:339) — the measurement point
        commit_tick = jnp.where(
            adv2 & (state.commit_tick < 0), jnp.int32(t), state.commit_tick
        )

    with jax.named_scope("paxos.tick.timers"):
        # retry: requireTicket (paxos-node.cc:281,511) — ticket += 1, bounded
        can_retry = state.ticket < cfg.paxos_max_ticket
        retry = want_retry & can_retry
        gave_up = state.gave_up | (want_retry & ~can_retry)

        # first firing: nodes 0..P-1 schedule requireTicket at t=0
        # (paxos-node.cc:136-138); a designated client lane instead fires when
        # the simulated external client sends CLIENT_PROPOSE
        # (paxos-node.cc:357-361, cfg.paxos_client_node/_ms)
        fire0 = (jnp.int32(t) == 0) & (ids < p) & state.alive
        cn = cfg.paxos_client_node
        if cn >= 0:
            is_client = ids == cn
            fire0 = (fire0 & ~is_client) | (
                (jnp.int32(t) == cfg.paxos_client_ms) & is_client & state.alive
            )
        send_tk = fire0 | retry
        ticket = jnp.where(send_tk, state.ticket + 1, state.ticket)

        new_window = send_tk | adv0 | adv1
        if clean:
            phase = jnp.where(
                adv0, PH_PROPOSE, jnp.where(adv1, PH_COMMIT, jnp.where(adv2, PH_DONE, state.phase))
            )
            phase = jnp.where(retry, PH_TICKET, phase)
            # the proposer is an acceptor too: process own request locally (real
            # Paxos self-promise/accept; the reference gets this only via echo).
            # The three windows are mutually exclusive per row this tick.
            self_tk_ok = send_tk & (ticket > t_max)
            self_enc = jnp.where(
                self_tk_ok & (command >= 0), t_store * c_enc + command + 1, 0
            )
            t_max = jnp.where(self_tk_ok, ticket, t_max)
            self_pp_ok = adv0 & (state.ticket == t_max)
            command = jnp.where(self_pp_ok, proposal, command)
            t_store = jnp.where(self_pp_ok, state.ticket, t_store)
            self_cm_ok = adv1 & (state.ticket == t_store) & (proposal == command)
            exec_tick = jnp.where(self_cm_ok & (exec_tick < 0), jnp.int32(t), exec_tick)
            is_commit = is_commit | self_cm_ok
            self_ok = self_tk_ok | self_pp_ok | self_cm_ok
            vs = jnp.where(new_window, self_ok.astype(jnp.int32), vs)
            vf = jnp.where(new_window, (~self_ok).astype(jnp.int32), vf)
            adopt_val = jnp.where(send_tk, self_enc, adopt_val)
            # jittered deadline: identical timeouts would make dueling proposers
            # retry in lockstep at the same tick forever (symmetric livelock);
            # the earliest retrier sweeps every acceptor's t_max and wins
            k_to = chan_key(tkey, Channel.ELECTION)
            if axis is not None:
                k_to = jax.random.fold_in(k_to, jax.lax.axis_index(axis))
            jitter = jax.random.randint(
                k_to, (n_loc,), 0, max(cfg.paxos_retry_timeout_ms // 2, 1),
                dtype=jnp.int32,
            )
            window_deadline = jnp.where(
                new_window, jnp.int32(t) + cfg.paxos_retry_timeout_ms + jitter,
                state.window_deadline,
            )
        else:
            # reference proposers have no phase register (actions are driven by
            # reply types alone) and no timeout; counters were already reset to
            # the post-crossing carryover (left_ok/left_no) in the counting block
            phase = jnp.where(adv2, PH_DONE, jnp.where(retry, PH_TICKET, state.phase))
            adopt_val = jnp.where(send_tk, 0, adopt_val)
            window_deadline = state.window_deadline

    with jax.named_scope("paxos.tick.flood_fwd"):
        # ---- push this tick's requests ------------------------------------------
        ref_skip = not clean
        tk_val = ticket * send_tk.astype(jnp.int32)
        pp_val = (state.ticket * c_enc + proposal + 1) * adv0.astype(jnp.int32)
        cm_val = (state.ticket * c_enc + state.proposal + 1) * adv1.astype(jnp.int32)
        zeros_req = jnp.zeros((nb, n_loc, p), jnp.int32)
        channels = (
            (tk_val, Channel.DELAY_BCAST, req_ticket),
            (pp_val, Channel.DELAY_BCAST2, req_propose),
            (cm_val, Channel.DELAY_BCAST3, req_commit),
        )
        pushed = []

        def push_req(buf, contrib):
            return ring_push_max(buf, t, lo, contrib)

        if gossip:
            # a proposer's own send is the flood origin: full TTL, own column,
            # marked seen so the loopback copy is not re-forwarded
            own = (ids[:, None] == jnp.arange(p)[None, :]).astype(jnp.int32)
            for ci, (val, chan, ring) in enumerate(channels):
                init_mat = val[:, None] * own
                init_enc = (init_mat * h_enc + cfg.gossip_hops) * (init_mat > 0)
                # the origin marks its own full-TTL copy seen, so no loopback
                # copy (necessarily fewer hops) is ever re-forwarded
                seen_req = seen_req.at[:, ci, :].max(init_enc)
                enc = jnp.maximum(fwd_vals[ci], init_enc)
                pushed.append(gated_push(
                    (enc > 0).any(),
                    lambda e=enc, c=chan: _gossip_fwd_contrib(
                        chan_key(tkey, c), e, nbrs_loc, n, lo, hi, drop, axis,
                        impl=eimpl,
                    ),
                    zeros_req,
                    ring,
                    push_req,
                    axis,
                ))
        else:
            for val, chan, ring in channels:
                pushed.append(gated_push(
                    (val > 0).any(),
                    lambda v=val, c=chan: _req_contrib(
                        chan_key(tkey, c), v, lo, hi, drop, axis, ids, p, ref_skip,
                        impl=eimpl, inmask=inmask,
                    ),
                    zeros_req,
                    ring,
                    push_req,
                    axis,
                ))
        req_ticket, req_propose, req_commit = pushed

    state = state.replace(
        t_max=t_max,
        command=command,
        t_store=t_store,
        is_commit=is_commit,
        exec_tick=exec_tick,
        ticket=ticket,
        phase=phase,
        vote_success=vs,
        vote_failed=vf,
        proposal=proposal,
        adopt_val=adopt_val,
        commit_tick=commit_tick,
        gave_up=gave_up,
        window_deadline=window_deadline,
        tk_send_tick=jnp.where(send_tk, jnp.int32(t), state.tk_send_tick),
        cm_send_tick=jnp.where(adv1, jnp.int32(t), state.cm_send_tick),
        seen_req=seen_req,
    )
    bufs = PaxosBufs(
        req_ticket=req_ticket,
        req_propose=req_propose,
        req_commit=req_commit,
        resp_ok=resp_ok,
        resp_no=resp_no,
        resp_cmd=resp_cmd,
    )
    return state, bufs


def metrics(cfg, state: PaxosState) -> dict:
    """The reference's measurement surface (SURVEY.md §5): CLIENT COMMIT
    SUCCESS with ticket/id/time (paxos-node.cc:339), ticket requests (:518),
    plus safety invariants the reference never checks."""
    p = cfg.paxos_n_proposers
    alive = np.asarray(state.alive)
    commit_tick = np.asarray(state.commit_tick)[:p]
    ticket = np.asarray(state.ticket)[:p]
    is_commit = np.asarray(state.is_commit)
    command = np.asarray(state.command)
    exec_tick = np.asarray(state.exec_tick)
    proposal = np.asarray(state.proposal)[:p]
    winners = np.flatnonzero(commit_tick >= 0)
    winner = int(winners[np.argmin(commit_tick[winners])]) if winners.size else -1
    executed = np.flatnonzero(is_commit & alive)
    exec_cmds = np.unique(command[executed]) if executed.size else np.array([])
    # safety: all executed acceptors executed the same command, and every
    # committed proposer's value is that command.  A committed proposer with
    # zero executed acceptors is itself an inconsistency (its commit quorum
    # claimed executions that nobody holds), not vacuous agreement.
    if winners.size and not exec_cmds.size:
        # a committed proposer whose commit quorum left zero executed alive
        # acceptors claimed executions nobody holds — an inconsistency
        agreement = False
    else:
        agreement = len(exec_cmds) <= 1 and all(
            proposal[w] == exec_cmds[0] for w in winners
        )
    # MILESTONES: spans counted from the send ticks of the winner's own
    # window, so they say how long a window and a flood take, not when the
    # winning window happened to open
    tk_send = np.asarray(state.tk_send_tick)[:p]
    cm_send = np.asarray(state.cm_send_tick)[:p]
    ms = dict.fromkeys(MILESTONES, -1.0)
    if winner >= 0:
        ms["winner_window_ms"] = float(commit_tick[winner] - tk_send[winner])
        if executed.size:
            ms["commit_flood_ms"] = float(
                exec_tick[executed].max() - cm_send[winner])
            ms["first_execute_lag_ms"] = float(
                exec_tick[executed].min() - cm_send[winner])
        # a committed proposer whose window opened after every other one
        # had committed met no competing ticket: at most the last committer
        for w in winners:
            rest = np.arange(p) != w
            if (commit_tick[rest] >= 0).all() and \
                    (commit_tick[rest] < tk_send[w]).all():
                ms["solo_window_ms"] = float(commit_tick[w] - tk_send[w])
    return {
        **ms,
        "protocol": "paxos",
        "n": cfg.n,
        "n_committed_proposers": int(winners.size),
        "winner": winner,
        "winner_commit_ms": float(commit_tick[winner]) if winner >= 0 else -1.0,
        "winner_ticket": int(ticket[winner]) if winner >= 0 else -1,
        "max_ticket": int(ticket.max()) if p else 0,
        "retries": int((ticket - 1).clip(min=0).sum()),
        "acceptor_executes": int(executed.size),
        "first_execute_ms": float(exec_tick[executed].min()) if executed.size else -1.0,
        "decided_command": int(exec_cmds[0]) if exec_cmds.size else -1,
        "gave_up": int(np.asarray(state.gave_up).sum()),
        "agreement_ok": bool(agreement),
    }


# the state fields :func:`metrics` reads, and the only ones (see
# pbft.METRIC_FIELDS; parallel/sweep._readback fetches these leaves alone)
METRIC_FIELDS = (
    "alive", "cm_send_tick", "command", "commit_tick", "exec_tick", "gave_up",
    "is_commit", "proposal", "ticket", "tk_send_tick",
)
