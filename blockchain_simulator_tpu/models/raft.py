"""Raft(-like) consensus — tensorized state machine.

Re-design of the reference's ``RaftNode`` (raft/raft-node.h:19, raft-node.cc):
randomized-timeout leader election (150-300 ms, raft-node.cc:69-72,114),
50 ms heartbeats (raft-node.cc:80,405-429), proposal-carrying heartbeats as log
replication (SendTX, raft-node.cc:340-365), majority acks advance ``blockNum``
(raft-node.cc:234-251), stop at 50 blocks / 50 proposal rounds.  As SURVEY.md
§2 notes, the reference has no terms, no log array, no commit index — it is
Raft-flavored leader election + heartbeat replication, and this backend
reproduces exactly that protocol.

Reference call stack being tensorized (SURVEY.md §3.3):

- election timer U[150,300) ms → ``sendVote`` (raft-node.cc:114,392-401):
  self-vote latch ``has_voted=1``, VOTE_REQ broadcast, timer re-armed.
- VOTE_REQ at a peer: grant iff ``has_voted==0`` (consuming the vote), unicast
  VOTE_RES SUCCESS/FAILED back (raft-node.cc:154-167).
- VOTE_RES at a candidate (raft-node.cc:196-232): per-arrival majority check
  ``vote_success + 1 > N/2`` → become leader (cancel own timer, schedule
  ``setProposal`` +1 s, send first heartbeat immediately); minority check
  ``vote_failed >= N/2`` → reset counters and ``has_voted=0`` (retry on the
  re-armed timer).
- leader every 50 ms: plain HEARTBEAT, or 20 KB proposal block once
  ``add_change_value`` is set (raft-node.cc:405-433); ``round==50`` clears
  ``add_change_value`` (raft-node.cc:361-365); ``blockNum>=50`` cancels the
  heartbeat (raft-node.cc:248-251).
- follower: heartbeat cancels the election timer; proposal also stores
  ``m_value``; always replies HEARTBEAT_RES SUCCESS (raft-node.cc:170-193).
- leader counts proposal acks; exactly when ``vote_success+vote_failed==N-1``
  it checks ``vote_success+1 > N/2`` → ``blockNum++`` (raft-node.cc:234-247).

Tensorization: one tick = 1 ms for all N nodes.  Timers become per-node
deadline registers compared against the tick counter (SURVEY.md §7).  Vote
requests need receiver state at arrival (the ``has_voted`` latch), so they ride
an identity-preserving matrix channel in ``edge`` mode, or a max-combined
candidate-id channel in ``stat`` mode (ties between candidates arriving at the
same receiver in the same tick resolve to one candidate — a documented
large-N simplification).  Heartbeat acks never depend on follower state, so
they are short-circuited round trips.  Echo-back (quirk #1) is not modeled.

The per-block commit ticks (``RaftState.block_tick``, ``[N, B]``) are the one
table of the state, and it changes on the few ticks on which a leader
commits: at most one tick a block, none before proposals start (1 s after
an election).  A one-hot select over it on every tick was the largest device
operation of the mixed deployment's election prefix (256 shards:
``[256, 1024, 50]``, 45% of a tick on which no entry changed; PERF.md
section 6, PR 39), so the stamp runs inside ``base.gated_body``'s ``while``
of at most one trip, taken when some node's commit lands (under a lane
batch: some lane's), scope ``gate.raft.commit_taken``.  The stamp is the
identity where nothing lands, so no per-lane select is needed and results
are bit-equal.  Programs that cannot branch (a mesh ``axis``,
``select_vmap``) keep the unconditional select (KNOWN_ISSUES #0b').

Fidelity modes:
- ``reference``: a plain heartbeat cancels the election timer *permanently*
  (the re-arm is commented out, raft-node.cc:177-178 — quirk #5), and a block
  commits only when exactly all N-1 acks arrive (stalls under drops, as the
  reference would).
- ``clean``: heartbeats re-arm the election timer (real failure detection) and
  a block commits as soon as acks reach the majority, latched once per round.

Terms (``cfg.raft_terms``; clean fidelity, edge delivery, full mesh — flat
or inside a committee stack): Raft's Figure 2 (Ongaro & Ousterhout, USENIX
ATC 2014, sections 5.1-5.2) on upstream's message set.  Every node has a
``term`` (0 at start) and every message carries its sender's:

- *timer*: a follower or candidate whose election timer fires takes the next
  term, votes for itself (its one vote of that term), zeroes its counts,
  broadcasts VOTE_REQ(term, id) and re-arms the timer.  A leader has none.
- *any message of a higher term*: the node takes that term and is a
  follower of it (a leader stops its heartbeat and its proposal schedule,
  abandons its open ack window and arms an election timer; a candidate
  stops counting); its vote of the new term is free; then the message is
  handled.  Several messages of one tick are handled highest term first.
- *VOTE_REQ(T, c)*: ``T < term`` is denied; ``T == term`` is granted iff no
  vote has been given in ``term``, and a grant re-arms the election timer.
  A reply carries the replier's term.
- *VOTE_RES*: a grant counts only at a node that is a candidate of the term
  it was asked in; ``vote_success + 1 > N/2`` makes it that term's leader as
  upstream (timer off, first heartbeat now, proposals 1 s later).  A
  candidate denied by a majority waits for its timer: upstream's ``lose``
  rule released the vote latch inside the term and is dropped (a departure).
- *HEARTBEAT(T)*, plain or carrying a proposal: ``T < term`` is ignored by
  the receiver; else the receiver is a follower of term T (a candidate of T
  steps down), re-arms its timer and stores the value.
- the oracle ``term_conflicts``: raised at a node that becomes leader of a
  term in which another node is leader.  Election safety says it stays 0.

How the rings carry it: ``vreq[d, i, j]`` holds the term of candidate j's
request (it held 1), ``hb_plain`` the heartbeat's term max-combined (it held
a count), ``hb_prop`` ``term * (n + 1) + leader + 1`` (it held ``leader +
1``), ``vres_no`` the highest term among the denials landing (it held their
count, which nothing reads once ``lose`` is gone).  ``vres_ok``, ``hb_ok``
and ``hb_bad`` stay term-less counts: a reply lands within ``rt_hi - 1``
ticks of its request, a node asks again or proposes again no sooner than an
election timeout later, so every count that lands belongs to the candidacy
(``is_cand``) or the ack window that is open, or to none (:func:`init`
checks ``raft_election_lo_ms`` against that horizon, as ``pbft.init`` checks
its window).  Not modeled: upstream has no log, so Figure 2's log-matching
check and section 5.4.1's up-to-date restriction have nothing to compare; no
PreVote; HEARTBEAT_RES is no message of its own (the short-circuited round
trip below), so a leader learns of a higher term from the VOTE_REQ that
made it, broadcast to the leader too, not from a rejected heartbeat; the
stop rule stays upstream's, as a state (a leader with 50 blocks sends no
heartbeat, so its followers time out and elect in a higher term: legal
Raft).  ``benchmark/reference/raft_terms_engine.py`` is the per-message
twin, a term on every message.

Crash schedule (``cfg.faults.crashes`` > 0; only with terms, so per-edge,
full mesh, clean — flat or inside a committee stack; :func:`check_schedule`
refuses every other arm by name): faults that happen during the run.  A
group draws one ``phase`` from U{0..period_ms-1} off its init key
(``Channel.FAULT``: part of the state, as the election deadlines are, and no
other stream moves).  Crash k, k = 0..crashes-1, falls at the head of tick
``first_ms + k * period_ms + phase`` (scope ``raft.tick.fault``, before
anything is popped) and hits the node that is an alive leader then (the
leader of the highest term, lowest id, should there be two); where none
leads it is recorded as having found no leader and kills nobody.  Raft's
crash, on upstream's message set:

- from its crash to its restart a node sends nothing, handles nothing and
  fires no timer; what it sent before the crash still arrives; what arrives
  for it while it is down is lost (the rings' slots are popped and masked).
- ``term`` and the vote of that term (``has_voted``) survive: Raft persists
  them.  ``is_leader``, ``is_cand``, the vote counts, the heartbeat and
  proposal schedules and the ack window do not.
- ``downtime_ms`` later it is back, a follower with an election deadline
  ``now + U[lo, hi)`` off the channel a deposed leader's re-arm uses.
- the majority stays ``N // 2 + 1`` whoever is down.

Records beside PR 42's ``last_hb``, a group's own ``[crashes]`` leaves:
``crash_tick`` (-1 until it falls), ``crash_node`` (-1: found no leader),
``replaced_tick`` (the tick on which a node of the group next wins an
election, necessarily in a higher term; only the newest crash can be
replaced, so one that is still open when the next falls stays unreplaced)
and ``crash_elections`` (timers that fired from the crash up to that win:
1 where the first election succeeds).  Two oracles that stay 0:
``dead_acts`` (a node that is down fired a timer, won, took a term or a
heartbeat, or voted) and ``double_votes`` (a node voted twice in one term,
against its own record ``voted_term``, which no restart touches).
Departures, each also ``benchmark/reference/raft_crash_engine.py``'s but the
last: upstream has no crash at all; there is no log, so the paper's
"candidates with shorter logs are not eligible" (section 9.3) has nothing
to compare and every alive server is eligible; the paper forces a heartbeat
before each kill, the schedule does not (a uniform ``phase`` against a
group's own heartbeat phase is the paper's "uniformly within the heartbeat
interval"); and, the program's alone, a proposal's acks are decided where
it is sent (the short-circuited round trip), so a follower that is down at
the send and back before the arrival acks in the reference and not here:
with three followers alive no commit hinges on it.

Gossip topology (``topology="gossip"``, clean + stat only): the three
broadcast channels — VOTE_REQ, plain HEARTBEAT, proposal HEARTBEAT — flood
over a random k-out digraph with a hop TTL (time-monotone value encodings,
per-channel ``seen`` dedup registers, same overlay as models/paxos.py);
votes and proposal acks stay direct unicast to the decoded originator, with
acks generated at flood arrival (the full-mesh short-circuited round trip
has no meaning over multi-hop paths).  Clean-mode majority counting is
arrival-time based, so multi-hop ack latency only shifts commit times.
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
from blockchain_simulator_tpu.ops import topology
from blockchain_simulator_tpu.ops.ring import ring_pop, ring_push_add, ring_push_max
from blockchain_simulator_tpu.utils.prng import Channel, chan_key

# Timer sentinel: "canceled" (Simulator::Cancel).  Any tick comparison against
# it is false for the whole simulation horizon.  np, not jnp: a jnp scalar
# here would create a device array AT IMPORT TIME — a backend init that
# claims the chip from whoever merely imports the package (jaxlint
# module-scope-backend-touch); the np.int32 promotes identically inside
# traces.
DISARM = np.int32(1 << 30)

# the taken trip of the commit gate inside ``raft.tick.ack_rx`` (see
# ``RaftState.block_tick``): the device events of an operation under it are
# the ticks on which the table was written.  Outside the ``raft.`` / ``ops.``
# families, as models/pbft.TAKEN_SCOPE is, so the phase stays the outermost
# program scope of what runs inside
COMMIT_SCOPE = "gate.raft.commit_taken"
# the taken trip of the fault gate inside ``raft.tick.fault``: the ticks on
# which some group's crash or some node's restart was due
FAULT_SCOPE = "gate.raft.fault_taken"

# the phases of :func:`step` as ``jax.named_scope`` names, after its own
# section comments (HLO metadata only — see models/pbft.SCOPES); ops/ scopes
# nest inside, and under models/mixed.py these sit below ``mixed.tick.*``.
# ``raft.tick.term`` is what terms add (``cfg.raft_terms``: the step to a
# higher term and down from a role, the oracle); a program without terms
# has no operation under it; ``raft.tick.fault`` is what a crash schedule
# adds (``cfg.faults.crashes``: the kill and the restart at the head of the
# tick, the per-crash records and the two oracles at its end), likewise
SCOPES = (
    "raft.tick.fault",
    "raft.tick.pop",
    "raft.tick.term",
    "raft.tick.heartbeat_rx",
    "raft.tick.vote_rx",
    "raft.tick.vote_reply_rx",
    "raft.tick.ack_rx",
    "raft.tick.timer_vote",
    "raft.tick.timer_heartbeat",
    COMMIT_SCOPE,
    FAULT_SCOPE,
)


@struct.dataclass
class RaftState:
    is_leader: jax.Array      # [N] bool
    has_voted: jax.Array      # [N] bool — single vote latch (no terms, quirk
    # #6); with ``cfg.raft_terms`` the vote of ``term``, free again in a new one
    election_deadline: jax.Array  # [N] tick of next sendVote; DISARM = canceled
    vote_success: jax.Array   # [N] election SUCCESS replies received
    vote_failed: jax.Array    # [N] election FAILED replies received
    next_hb: jax.Array        # [N] next heartbeat tick (leader); DISARM = off
    proposal_tick: jax.Array  # [N] when setProposal fires; DISARM = unscheduled
    add_change_value: jax.Array  # [N] bool — heartbeats carry proposals
    m_value: jax.Array        # [N] last proposal value stored (-1 = unset)
    block_num: jax.Array      # [N] blocks committed (leader counts)
    round: jax.Array          # [N] proposal rounds broadcast (leader)
    hb_succ: jax.Array        # [N] proposal-ack SUCCESS count, current round
    hb_cnt: jax.Array         # [N] proposal-ack total count, current round
    hb_open: jax.Array        # [N] bool — current round not yet committed
    leader_tick: jax.Array    # [N] tick this node became leader (-1 = never)
    elections: jax.Array      # [N] sendVote firings (metrics)
    # [N, B] commit tick per block at the leader (-1 = not committed).  An
    # entry is written once, on the tick on which that node's commit lands:
    # :func:`step` stamps the table through ``base.gated_body`` on "some
    # node's (some lane's) commit lands this tick" and passes over it on no
    # other tick; under a mesh axis or ``select_vmap`` the one-hot select
    # runs on every tick (``base.can_branch``).  models/raft_hb.materialize
    # writes the leader's row once a run.
    block_tick: jax.Array
    alive: jax.Array          # [N] bool fault mask
    honest: jax.Array         # [N] bool fault mask
    # gossip (topology="gossip") dedup registers: highest TTL-encoded copy
    # seen per flooded channel (vote requests / plain heartbeats / proposals);
    # zeros and unused on the full mesh
    seen_vreq: jax.Array      # [N]
    seen_hb: jax.Array        # [N]
    seen_prop: jax.Array      # [N]
    # gossip elections: multi-hop flood latency (~hops*delay) spans many
    # nodes' election deadlines, so votes fragment across a storm of
    # concurrent candidates; with the reference's permanent single-vote
    # latch (quirk #6) nobody ever reaches a majority and elections deadlock
    # at n >~ 100.  Clean-fidelity gossip therefore votes for the NEWEST
    # election seen — the ``seen_vreq`` dedup register IS the term
    # comparison (bases are time-monotone and a node only processes strictly
    # newer ones), so every processed request is granted; a candidate
    # restarts its count at each fire (reply horizons << election timeouts,
    # so stale replies drain first — the models/paxos.py temporal-separation
    # argument).  Stale in-flight grants can still hand majorities to
    # SEVERAL storm candidates, so leaders also step down on observing a
    # newer election than their own (real Raft's step-down-on-higher-term)
    # — ``my_base`` remembers the election a leader won.
    my_base: jax.Array        # [N] last election base this node fired with
    # queued-link transport (cfg.queued_links; zeros when off): per-
    # destination busy-until register for the CURRENT leader's serial links
    # (same design as models/pbft.py — blocks only flow leader -> follower,
    # so the busy state is [N] by destination, reset on leadership change; a
    # 20 KB proposal serializes ~54 ms against the 50 ms heartbeat, so the
    # backlog grows ~4 ms/round, bounded by (ser - hb) * raft_max_rounds —
    # small enough that queued deliveries stay ON the rings, whose depth
    # config.ring_depth widens accordingly; engine.cpp:198-215 is the twin).
    link_busy: jax.Array      # [N]
    # Raft with terms (``cfg.raft_terms``; module docstring "Terms").  None
    # without: a program that has no terms carries no leaf for them
    term: jax.Array | None = None         # [N] current term (0 at start)
    is_cand: jax.Array | None = None      # [N] bool — candidate of ``term``
    lead_term0: jax.Array | None = None   # [N] term of the first win (0 = none)
    won_tick: jax.Array | None = None     # [N] tick of the latest win (-1)
    # [N] tick of the last heartbeat sent in the term this node FIRST led
    # (-1 = none): a group's failover is measured from its first leader's
    last_hb: jax.Array | None = None
    step_downs: jax.Array | None = None   # [N] leader/candidate -> follower
    term_conflicts: jax.Array | None = None  # [N] the election-safety oracle


    # A crash schedule (``cfg.faults.crashes`` = K; module docstring "Crash
    # schedule").  None without: no schedule, no leaf
    crash_phase: jax.Array | None = None    # () the group's draw, U{0..P-1}
    crash_tick: jax.Array | None = None     # [K] tick crash k fell (-1)
    crash_node: jax.Array | None = None     # [K] node it killed (-1 = none)
    replaced_tick: jax.Array | None = None  # [K] tick of the next win (-1)
    crash_elections: jax.Array | None = None  # [K] timers fired until then
    restart_tick: jax.Array | None = None   # [N] when a dead node is back
    restarts: jax.Array | None = None       # [N] times it came back
    voted_term: jax.Array | None = None     # [N] last term it voted in
    dead_acts: jax.Array | None = None      # [N] oracle: acted while down
    double_votes: jax.Array | None = None   # [N] oracle: two votes a term


# the leaves above, in order
TERM_FIELDS = ("term", "is_cand", "lead_term0", "won_tick", "last_hb",
               "step_downs", "term_conflicts")
CRASH_FIELDS = ("crash_phase", "crash_tick", "crash_node", "replaced_tick",
                "crash_elections", "restart_tick", "restarts", "voted_term",
                "dead_acts", "double_votes")


@struct.dataclass
class RaftBufs:
    # vote requests: edge mode keeps sender identity [D, N_recv, N_glob];
    # stat mode max-combines candidate id + 1 into [D, N_recv].
    vreq: jax.Array
    vres_ok: jax.Array   # [D, N] granted-vote arrivals at the candidate (add)
    vres_no: jax.Array   # [D, N] denial arrivals at the candidate (add)
    hb_plain: jax.Array  # [D, N] plain-heartbeat arrival counts (add)
    hb_prop: jax.Array   # [D, N] proposal value + 1, max-combined (0 = empty)
    hb_ok: jax.Array     # [D, N] proposal-ack SUCCESS arrivals at leader (add)
    hb_bad: jax.Array    # [D, N] proposal-ack FAILED arrivals (Byzantine
    # repliers flip to FAILED; disjoint peer set from hb_ok, so the two
    # channels' independent delay draws cover disjoint edges)


def init(cfg, key=None):
    check_arms(cfg)
    n, d = cfg.n, cfg.ring_depth
    b = cfg.raft_max_blocks
    alive, honest = fault_masks(cfg, n)
    zi = lambda *sh: jnp.zeros(sh, jnp.int32)
    zb = lambda *sh: jnp.zeros(sh, bool)
    # initial election timeouts U[150,300) ms (raft-node.cc:69-72,114), drawn
    # from the *init* key so the schedule is part of the state, not the tick
    # stream
    k = jax.random.key(cfg.seed) if key is None else key
    deadline = jax.random.randint(
        jax.random.fold_in(k, Channel.ELECTION),
        (n,),
        cfg.raft_election_lo_ms,
        cfg.raft_election_hi_ms,
        dtype=jnp.int32,
    )
    # crashed nodes never start an election
    deadline = jnp.where(alive, deadline, DISARM)
    state = RaftState(
        is_leader=zb(n),
        has_voted=zb(n),
        election_deadline=deadline,
        vote_success=zi(n),
        vote_failed=zi(n),
        next_hb=jnp.full((n,), DISARM),
        proposal_tick=jnp.full((n,), DISARM),
        add_change_value=zb(n),
        m_value=jnp.full((n,), -1, jnp.int32),
        block_num=zi(n),
        round=zi(n),
        hb_succ=zi(n),
        hb_cnt=zi(n),
        hb_open=zb(n),
        leader_tick=jnp.full((n,), -1, jnp.int32),
        elections=zi(n),
        block_tick=jnp.full((n, b), -1, jnp.int32),
        alive=alive,
        honest=honest,
        seen_vreq=zi(n),
        seen_hb=zi(n),
        seen_prop=zi(n),
        my_base=zi(n),
        link_busy=zi(n),
    )
    if cfg.raft_terms:
        state = state.replace(
            term=zi(n), is_cand=zb(n), lead_term0=zi(n),
            won_tick=jnp.full((n,), -1, jnp.int32),
            last_hb=jnp.full((n,), -1, jnp.int32),
            step_downs=zi(n), term_conflicts=zi(n),
        )
    if cfg.faults.crashes:
        kk = cfg.faults.crashes
        state = state.replace(
            crash_phase=jax.random.randint(
                jax.random.fold_in(k, Channel.FAULT), (), 0,
                cfg.faults.period_ms, dtype=jnp.int32),
            crash_tick=jnp.full((kk,), -1, jnp.int32),
            crash_node=jnp.full((kk,), -1, jnp.int32),
            replaced_tick=jnp.full((kk,), -1, jnp.int32),
            crash_elections=zi(kk),
            restart_tick=jnp.full((n,), DISARM),
            restarts=zi(n), voted_term=zi(n), dead_acts=zi(n),
            double_votes=zi(n),
        )
    if cfg.delivery == "stat":
        vreq = zi(d, n)
    elif cfg.topology == "kregular":
        # edge-mode overlay: sender identity is the IN-slot, not a global
        # column — [D, N, K] instead of [D, N, N] (the O(N*k) memory win)
        vreq = zi(d, n, cfg.degree + 1)
    else:
        vreq = zi(d, n, n)
    bufs = RaftBufs(
        vreq=vreq,
        vres_ok=zi(d, n),
        vres_no=zi(d, n),
        hb_plain=zi(d, n),
        hb_prop=zi(d, n),
        hb_ok=zi(d, n),
        hb_bad=zi(d, n),
    )
    return state, bufs




def check_terms(cfg):
    """``cfg.raft_terms``: what the term-less count channels stand on (module
    docstring "Terms"), and every arm without terms refused by its name
    rather than silently running the one-vote-a-run election.  The one place
    the arms are listed: :func:`init` and :func:`step` ask it, and
    runner.py's validation before anything is built (there ``topology`` may
    still be ``"committee"``, whose groups are full meshes).  Protocol and
    fidelity are ``SimConfig``'s own refusals; the C++ engine's is
    engine.run_cpp's."""
    if cfg.delivery != "edge":
        raise NotImplementedError(
            "raft_terms is not implemented for delivery='stat': its "
            "vote-request channel max-combines candidate ids and keeps "
            "no sender to carry a term (models/raft.py stat arm; "
            "models/raft_hb is stat-only and has none either); use "
            "delivery='edge'"
        )
    if cfg.topology not in ("full", "committee"):
        raise NotImplementedError(
            f"raft_terms is not implemented for topology="
            f"{cfg.topology!r}: the gossip arm votes for the newest "
            "election on time-monotone bases (models/raft.py my_base), "
            "which is not Raft's integer term, and the kregular overlay "
            "has no term channel; use topology='full' or 'committee'"
        )
    if cfg.queued_links:
        raise NotImplementedError(
            "raft_terms is not implemented with queued_links: the "
            "serial-pipe registers follow one block sender and reset on "
            "a change of leader they find without terms (models/raft.py "
            "link_busy)"
        )
    if cfg.mesh_axis is not None:
        raise NotImplementedError(
            "raft_terms is not implemented under a mesh axis (the node-"
            "sharded programs of parallel/shard.py): the step to a higher "
            "term and the oracle reduce over a group's nodes on one device"
        )
    if cfg.topology == "committee":
        return  # the constants below are a group's: asked again on its config
    _, rt_hi = cfg.roundtrip_range()
    horizon = rt_hi - 1 + cfg.serialization_ticks(cfg.raft_block_bytes)
    if min(cfg.raft_election_lo_ms, cfg.raft_proposal_delay_ms) <= horizon:
        raise ValueError(
            f"raft_terms: a reply lands up to {horizon} ticks after its "
            f"request, and raft_election_lo_ms={cfg.raft_election_lo_ms} / "
            f"raft_proposal_delay_ms={cfg.raft_proposal_delay_ms} must exceed "
            "that, or a count of an older term could reach a node that "
            "counts again (the reply channels carry no term)"
        )
    if cfg.sim_ms // cfg.raft_election_lo_ms * cfg.n * (cfg.n + 1) >= 2**31:
        raise ValueError(
            "raft_terms: hb_prop encodes term * (n + 1) + leader + 1, and "
            "sim_ms / raft_election_lo_ms * n terms of it overflow int32"
        )


def step(cfg, state: RaftState, bufs: RaftBufs, t, tkey, *, topo_tables=None,
         exchange=None):
    n = cfg.n
    axis = cfg.mesh_axis
    terms, sched = cfg.raft_terms, cfg.faults.crashes > 0
    if terms or sched:
        check_arms(cfg)
    lo, hi = cfg.one_way_range()
    rt_lo, rt_hi = cfg.roundtrip_range()
    drop = cfg.faults.drop_prob
    clean = cfg.fidelity == "clean"
    stat = cfg.delivery == "stat"
    smode = cfg.eff_stat_sampler
    eimpl = cfg.eff_edge_sampler
    ow_probs = delay_ops.uniform_probs(lo, hi)
    rt_probs = delay_ops.roundtrip_probs(lo, hi)
    n_loc = state.is_leader.shape[0]
    ids = dv._global_ids(n_loc, axis)
    zeros_flat = jnp.zeros((hi - lo, n_loc), jnp.int32)
    zeros_rt = jnp.zeros((len(rt_probs), n_loc), jnp.int32)
    ser = cfg.serialization_ticks(cfg.raft_block_bytes)
    # queued-link transport (see RaftState.link_busy): with ser == 0 the pipe
    # is never busy and queued == constant-latency, so the plain path runs
    queued = cfg.queued_links and ser > 0

    def rearm_on(channel):
        """A fresh election deadline a node, on a stream of its own."""
        return t + jax.random.randint(
            chan_key(tkey, channel), (n_loc,),
            cfg.raft_election_lo_ms, cfg.raft_election_hi_ms,
            dtype=jnp.int32)

    if sched:
        with jax.named_scope("raft.tick.fault"):
            state = _crash_and_restart(cfg, state, t, rearm_on)
        term_in, voted_in = state.term, state.has_voted

    with jax.named_scope("raft.tick.pop"):
        # ---- pop arrivals; crashed nodes process nothing ------------------------
        vreq_t, vreq = ring_pop(bufs.vreq, t)
        ok_t, vres_ok = ring_pop(bufs.vres_ok, t)
        no_t, vres_no = ring_pop(bufs.vres_no, t)
        plain_t, hb_plain = ring_pop(bufs.hb_plain, t)
        prop_t, hb_prop = ring_pop(bufs.hb_prop, t)
        hbok_t, hb_ok = ring_pop(bufs.hb_ok, t)
        hbbad_t, hb_bad = ring_pop(bufs.hb_bad, t)
        am = state.alive.astype(jnp.int32)
        ok_t, no_t = ok_t * am, no_t * am
        plain_t, prop_t = plain_t * am, prop_t * am
        hbok_t, hbbad_t = hbok_t * am, hbbad_t * am
        hbtot_t = hbok_t + hbbad_t
        if stat:
            vreq_t = vreq_t * am
        else:
            vreq_t = vreq_t * am[:, None]

        # ---- gossip decode (topology="gossip"): the three broadcast channels
        # (VOTE_REQ, plain HEARTBEAT, proposal HEARTBEAT) flood over the k-out
        # digraph with a hop TTL; replies (votes, proposal acks) stay direct
        # unicast to the decoded originator — the same overlay as models/paxos.py.
        # Flood values are time-monotone encodings (dedup by per-channel ``seen``
        # register): vreq (t+1)*n + cand + 1; plain hb t+1; proposal
        # (t+1)*(n+1) + leader + 1 (the +1 keeps 0 = empty).  A node processes
        # each base value once (first sighting) but forwards any strictly better
        # TTL copy, so a nearly-expired first arrival cannot truncate the flood.
        gossip = cfg.topology == "gossip"
        # kregular gather overlay (topo/spec.py + ops/gatherdeliv.py): every
        # channel delivers DIRECT over the circulant in/out tables — broadcasts
        # reach out-neighbors, replies gather back requester-side through the
        # inslot cross-index (scatter-free) — O(N*K) per tick, bit-equal to the
        # dense arms at degree k = N-1.  A candidate only ever hears its
        # in-neighbors' votes, so elections need k >= majority_need - 1 to be
        # winnable (stalling below that is a valid modeled outcome).
        kreg = cfg.topology == "kregular"
        nbr_in_loc = nbr_out_loc = inslot_loc = None
        if kreg:
            # exchange mode: operands are already this trace's rows (ids=None
            # pass-through — re-taking a sharded operand would regather it)
            nbr_in_loc, nbr_out_loc, inslot_loc = gd.local_tables(
                cfg, None if exchange is not None else ids, inslot=True,
                tables=topo_tables)
        seen_vreq, seen_hb, seen_prop = state.seen_vreq, state.seen_hb, state.seen_prop
        vreq_fwd = hb_fwd = prop_fwd = None
        nbrs_loc = None
        if gossip:
            h_enc = cfg.gossip_hops + 1
            nbrs_loc = jnp.take(
                jnp.asarray(topology.kregular_out_neighbors(n, cfg.degree, cfg.seed)),
                ids, axis=0,
            )

            def _decode(arr, seen):
                base, hops = arr // h_enc, arr % h_enc
                new = (base > seen // h_enc) & state.alive
                better = (arr > seen) & state.alive
                seen = jnp.maximum(seen, arr * better)
                fwd = (base * h_enc + jnp.maximum(hops - 1, 0)) * (better & (hops > 0))
                return base * new, seen, fwd

            vreq_t, seen_vreq, vreq_fwd = _decode(vreq_t, seen_vreq)
            plain_t, seen_hb, hb_fwd = _decode(plain_t, seen_hb)
            prop_t, seen_prop, prop_fwd = _decode(prop_t, seen_prop)

    if terms:
        with jax.named_scope("raft.tick.term"):
            # ---- the highest term this tick's arrivals carry: a node behind
            # it takes it and is its follower BEFORE any of them is handled
            # (arrivals at a crashed node were masked above)
            t_in = jnp.maximum(
                jnp.maximum(plain_t, prop_t // (n + 1)),
                jnp.maximum(vreq_t.max(axis=1), no_t))
            step_up = t_in > state.term
            deposed = step_up & state.is_leader
            state = state.replace(
                term=jnp.maximum(state.term, t_in),
                step_downs=state.step_downs
                + (step_up & (state.is_leader | state.is_cand)),
                is_leader=state.is_leader & ~step_up,
                is_cand=state.is_cand & ~step_up,
                # the vote of the new term is free; counts start over
                has_voted=state.has_voted & ~step_up,
                vote_success=jnp.where(step_up, 0, state.vote_success),
                vote_failed=jnp.where(step_up, 0, state.vote_failed),
                # a deposed leader: heartbeat and proposal schedule off, the
                # open ack window abandoned (in-flight acks keep landing and
                # must not commit for a later leadership), a follower's
                # election timer armed
                next_hb=jnp.where(deposed, DISARM, state.next_hb),
                proposal_tick=jnp.where(deposed, DISARM, state.proposal_tick),
                add_change_value=state.add_change_value & ~deposed,
                hb_succ=jnp.where(deposed, 0, state.hb_succ),
                hb_cnt=jnp.where(deposed, 0, state.hb_cnt),
                hb_open=state.hb_open & ~deposed,
                election_deadline=jnp.where(
                    deposed, rearm_on(Channel.ELECTION + 200),
                    state.election_deadline),
            )
            term, is_cand = state.term, state.is_cand
            step_downs = state.step_downs

    with jax.named_scope("raft.tick.heartbeat_rx"):
        # ---- heartbeat arrivals (follower side, raft-node.cc:170-193) -----------
        if terms:
            # a heartbeat of an older term is ignored; one of this term makes
            # a candidate of it a follower (a leader never hears one)
            prop_ok = (prop_t > 0) & (prop_t // (n + 1) == term)
            got_hb = (((plain_t > 0) & (plain_t == term)) | prop_ok) \
                & ~state.is_leader
            step_downs = step_downs + (got_hb & is_cand)
            is_cand = is_cand & ~got_hb
            prop_t = jnp.where(prop_ok, prop_t % (n + 1), 0)  # leader + 1
        else:
            got_hb = (plain_t > 0) | (prop_t > 0)
        if gossip:
            # proposal value = the leader id riding the flood encoding
            m_value = jnp.where(prop_t > 0, (prop_t - 1) % (n + 1), state.m_value)
        else:
            m_value = jnp.where(prop_t > 0, prop_t - 1, state.m_value)
        if clean:
            # re-arm the election timer: real failure detection
            k_e = chan_key(tkey, Channel.ELECTION)
            if axis is not None:
                k_e = jax.random.fold_in(k_e, jax.lax.axis_index(axis))
            rearm = t + jax.random.randint(
                k_e, (n_loc,), cfg.raft_election_lo_ms, cfg.raft_election_hi_ms,
                dtype=jnp.int32,
            )
            election_deadline = jnp.where(got_hb, rearm, state.election_deadline)
        else:
            # quirk #5: Simulator::Cancel with the re-arm commented out
            # (raft-node.cc:177-178) — one heartbeat pacifies a follower forever
            election_deadline = jnp.where(got_hb, DISARM, state.election_deadline)

        # ---- gossip proposal acks: a follower acks the proposal when the flood
        # lands (direct unicast to the decoded leader); replaces the full-mesh
        # short-circuited round trip, which has no meaning over multi-hop paths
        if gossip:
            got_prop = prop_t > 0
            ack_to = jnp.where(got_prop, (prop_t - 1) % (n + 1), n)  # n = drop
            k_ack = chan_key(tkey, Channel.DELAY_REPLY2)

            def _push_acks(rings, _):
                # fused chain-into-ring (ops/delivery.push_bucket_counts):
                # bit-equal to the former stacked sample → ring_push_add pair
                # (same keys, same chain, same adds), minus the [2, B, N]
                # intermediate; there is no separate contribution: the gate
                # skips the whole push, and a lane without a sender adds
                # all-zero counts, which leave its rings as they were
                mok = dv.reply_count_by_target(
                    got_prop & state.honest & state.alive, ack_to, n, axis)
                mbad = dv.reply_count_by_target(
                    got_prop & ~state.honest & state.alive, ack_to, n, axis)
                if drop > 0.0:
                    kd = jax.random.fold_in(k_ack, 0x0D18)
                    mok = jnp.round(delay_ops.binom(
                        kd, mok, 1.0 - drop, smode)).astype(jnp.int32)
                    mbad = jnp.round(delay_ops.binom(
                        jax.random.fold_in(kd, 1), mbad, 1.0 - drop,
                        smode)).astype(jnp.int32)
                return (
                    dv.push_bucket_counts(
                        rings[0], t, lo, jax.random.fold_in(k_ack, 1), mok,
                        ow_probs, smode),
                    dv.push_bucket_counts(
                        rings[1], t, lo, jax.random.fold_in(k_ack, 2), mbad,
                        ow_probs, smode),
                )

            hb_ok, hb_bad = gated_push(
                got_prop.any(), tuple, (), (hb_ok, hb_bad), _push_acks, axis,
            )

    with jax.named_scope("raft.tick.vote_rx"):
        # ---- vote requests (acceptor side, raft-node.cc:154-167) ---------------
        can_grant = ~state.has_voted & state.alive
        my_base = state.my_base
        if stat:
            # full mesh: vreq_t[i] = max candidate id + 1 seen this tick (the
            # stat broadcast reaches the sender too — drop the self-request);
            # gossip: the candidate id rides the flood encoding
            grant_to = (vreq_t - 1) % n if gossip else vreq_t - 1
            has_req = (vreq_t > 0) & (grant_to != ids)
            if gossip:
                # term-style release: the dedup register admits only strictly
                # newer elections (see the my_base field comment), so every
                # processed request is a grant — the permanent latch would
                # deadlock the storm
                grant = has_req & state.alive
                # granting a vote resets the election timeout (standard Raft):
                # during the candidacy storm every node keeps re-arming, so no
                # timer fires into the winner's first heartbeat window and the
                # post-storm leader is not spuriously deposed
                k_gr = chan_key(tkey, Channel.ELECTION + 300)
                if axis is not None:
                    k_gr = jax.random.fold_in(k_gr, jax.lax.axis_index(axis))
                rearm_gr = t + jax.random.randint(
                    k_gr, (n_loc,), cfg.raft_election_lo_ms,
                    cfg.raft_election_hi_ms, dtype=jnp.int32,
                )
                election_deadline = jnp.where(grant, rearm_gr, election_deadline)
            else:
                grant = has_req & can_grant
            deny = has_req & ~grant
            has_voted = state.has_voted | grant
            # Byzantine receivers flip their replies (grant<->deny on the wire)
            ok_wire = (grant & state.honest) | (deny & ~state.honest)
            no_wire = (deny & state.honest) | (grant & ~state.honest)
            # per-candidate reply counts, multinomially spread.  On the full
            # mesh ops/delivery.reply_count_by_target: every id compared with
            # every replier's target and summed up to its bound on n (no
            # scatter: one fusion, which a lane batch widens), a global
            # scatter-add above it; the overlay routes them requester-side
            # instead — candidate c gathers its out-neighbors' wires and
            # keeps those addressed to it (ops/gatherdeliv.
            # reply_counts_by_target_kreg: equal counts at k = N-1, and the
            # kregular program stays scatter-free, KNOWN_ISSUES #0i)
            def reply_counts(wire):
                if kreg:
                    return gd.reply_counts_by_target_kreg(
                        wire, grant_to, nbr_out_loc, ids, axis, exchange
                    )
                return dv.reply_count_by_target(wire, grant_to, n, axis)

            any_req = has_req.any()
            k_vr = chan_key(tkey, Channel.DELAY_REPLY)

            def push_replies(rings, _):
                # fused chain-into-ring — see the gossip ack block above
                mok = reply_counts(ok_wire)
                mno = reply_counts(no_wire)
                if drop > 0.0:
                    kd = jax.random.fold_in(k_vr, 0x0D17)
                    mok = jnp.round(delay_ops.binom(
                        kd, mok, 1.0 - drop, smode)).astype(jnp.int32)
                    mno = jnp.round(delay_ops.binom(
                        jax.random.fold_in(kd, 1), mno, 1.0 - drop,
                        smode)).astype(jnp.int32)
                return (
                    dv.push_bucket_counts(
                        rings[0], t, lo, jax.random.fold_in(k_vr, 7), mok,
                        ow_probs, smode),
                    dv.push_bucket_counts(
                        rings[1], t, lo, jax.random.fold_in(k_vr, 8), mno,
                        ow_probs, smode),
                )

            vres_ok, vres_no = gated_push(
                any_req, tuple, (), (vres_ok, vres_no), push_replies, axis,
            )
        else:
            # vreq_t[i, j] = 1 iff candidate j's request reaches i this tick.
            # Concurrent same-tick requests: the vote goes to the lowest candidate
            # id (the reference grants in serial arrival order; within one tick the
            # order is undefined, so we fix a deterministic choice).
            has_req = vreq_t > 0
            any_req = has_req.any(axis=1)
            # with terms a request holds its candidate's term, and only one
            # of the receiver's own term (no arrival is ahead of it any
            # more) can be granted; an older one is denied
            grantable = has_req & (vreq_t == term[:, None]) if terms else has_req
            any_grantable = grantable.any(axis=1) if terms else any_req
            first = jnp.argmax(grantable, axis=1)  # lowest j with a request
            grant_mask = (
                jax.nn.one_hot(first, vreq_t.shape[1], dtype=jnp.int32)
                * (any_grantable & can_grant).astype(jnp.int32)[:, None]
            )
            deny_mask = has_req.astype(jnp.int32) - grant_mask
            has_voted = state.has_voted | (any_grantable & can_grant)
            hn = state.honest.astype(jnp.int32)[:, None]
            ok_wire = grant_mask * hn + deny_mask * (1 - hn)
            no_wire = deny_mask * hn + grant_mask * (1 - hn)
            if terms:
                # a grant re-arms the election timer (Figure 2, Followers);
                # a denial carries the denier's term
                granted = any_grantable & can_grant
                election_deadline = jnp.where(
                    granted,
                    rearm_on(Channel.ELECTION + 300), election_deadline)
                no_wire = no_wire * term[:, None]
            k_vr = chan_key(tkey, Channel.DELAY_REPLY)
            if kreg:
                # slot-indexed wires route back requester-side through the
                # inslot cross-index gather — no scatter, same keys/folds as
                # the dense unicast (bit-equal at k = N-1)
                def _unicast(kk, wire):
                    return gd.unicast_reply_counts_kreg(
                        kk, wire, nbr_in_loc, nbr_out_loc, inslot_loc, ids,
                        lo, hi, drop, axis=axis, impl=eimpl, xg=exchange)
            else:
                def _unicast(kk, wire):
                    return dv.unicast_reply_counts_dense(
                        kk, wire, lo, hi, drop, axis=axis, impl=eimpl)
            if terms:
                def _unicast_no(kk, wire):
                    return dv.unicast_reply_value_max_dense(
                        kk, wire, lo, hi, drop, impl=eimpl)
            else:
                _unicast_no = _unicast
            vres_ok, vres_no = gated_push(
                any_req.any(),
                lambda: jnp.stack([
                    _unicast(jax.random.fold_in(k_vr, 7), ok_wire),
                    _unicast_no(jax.random.fold_in(k_vr, 8), no_wire),
                ]),
                jnp.zeros((2, hi - lo, n_loc), jnp.int32),
                (vres_ok, vres_no),
                lambda rings, both: (
                    ring_push_add(rings[0], t, lo, both[0]),
                    (ring_push_max if terms else ring_push_add)(
                        rings[1], t, lo, both[1]),
                ),
                axis,
            )

    with jax.named_scope("raft.tick.vote_reply_rx"):
        # ---- vote responses (candidate side, raft-node.cc:196-232) --------------
        if terms:
            # a grant counts at the candidate of the term it was asked in
            # (module docstring "Terms": none of another term can land here);
            # a majority of denials changes nothing until the timer fires
            vs = state.vote_success + ok_t * is_cand
            vf = state.vote_failed
            win = is_cand & (ok_t > 0) & (vs + 1 >= cfg.majority_need) & state.alive
            lose = jnp.zeros((n_loc,), bool)
        else:
            vs = state.vote_success + ok_t * (~state.is_leader)
            vf = state.vote_failed + no_t * (~state.is_leader)
            win = ~state.is_leader & (ok_t > 0) & (vs + 1 >= cfg.majority_need) & state.alive
            lose = ~win & (no_t > 0) & (vf >= cfg.raft_lose_need) & ~state.is_leader
        vote_success = jnp.where(win | lose, 0, vs)
        vote_failed = jnp.where(win | lose, 0, vf)
        # winner: cancel own timer, first heartbeat NOW, proposals in +1 s
        is_leader = state.is_leader | win
        election_deadline = jnp.where(win, DISARM, election_deadline)
        next_hb = jnp.where(win, jnp.int32(t), state.next_hb)
        proposal_tick = jnp.where(
            win, jnp.int32(t) + cfg.raft_proposal_delay_ms, state.proposal_tick
        )
        leader_tick = jnp.where(win & (state.leader_tick < 0), jnp.int32(t),
                                state.leader_tick)
        # loser: majority denied — release the vote latch and retry on the timer
        has_voted = has_voted & ~lose
        if queued:
            # leadership changed: the new leader's links are vote-only, hence
            # free, in both engines (votes never occupy the pipe); its busy
            # registers start fresh.  Already-scheduled deliveries from the old
            # leader keep their ring slots, exactly like the C++ engine's
            # in-flight events.
            lead_prev = jnp.max(jnp.where(state.is_leader & state.alive, ids, -1))
            lead_new = jnp.max(jnp.where(is_leader & state.alive, ids, -1))
            if axis is not None:
                lead_prev = jax.lax.pmax(lead_prev, axis)
                lead_new = jax.lax.pmax(lead_new, axis)
            link_busy = jnp.where(lead_new != lead_prev, 0, state.link_busy)
        else:
            link_busy = state.link_busy

        # ---- gossip: leader step-down on a newer election (see my_base) ---------
        if gossip:
            newest = seen_vreq // h_enc
            resign = is_leader & (newest > state.my_base) & state.alive
            is_leader = is_leader & ~resign
            next_hb = jnp.where(resign, DISARM, next_hb)
            proposal_tick = jnp.where(resign, DISARM, proposal_tick)
            # back to follower: re-arm the election timer (clean fidelity —
            # gossip requires it) so the node can detect the new leader failing
            k_rs = chan_key(tkey, Channel.ELECTION + 200)
            if axis is not None:
                k_rs = jax.random.fold_in(k_rs, jax.lax.axis_index(axis))
            rearm_rs = t + jax.random.randint(
                k_rs, (n_loc,), cfg.raft_election_lo_ms, cfg.raft_election_hi_ms,
                dtype=jnp.int32,
            )
            election_deadline = jnp.where(resign, rearm_rs, election_deadline)
        else:
            resign = jnp.zeros((n_loc,), bool)
        # a resigned leader abandons its open ack window: in-flight acks keep
        # arriving at the ex-leader (unicast), and without this a later
        # re-election could latch a phantom commit from pre-resignation acks
        hb_succ_in = jnp.where(resign, 0, state.hb_succ)
        hb_cnt_in = jnp.where(resign, 0, state.hb_cnt)
        hb_open_in = state.hb_open & ~resign

    if terms:
        with jax.named_scope("raft.tick.term"):
            # ---- the winner's records, and the oracle: a node that becomes
            # leader of a term in which another node is leader
            is_cand = is_cand & ~win
            lead_term0 = jnp.where(win & (state.lead_term0 == 0), term,
                                   state.lead_term0)
            won_tick = jnp.where(win, jnp.int32(t), state.won_tick)
            same_term_leaders = (
                (term[:, None] == term[None, :]) & is_leader[None, :]
            ).sum(axis=1)
            term_conflicts = state.term_conflicts + (
                win & (same_term_leaders > 1))

    with jax.named_scope("raft.tick.ack_rx"):
        # ---- proposal acks (leader side, raft-node.cc:234-251) ------------------
        hs = hb_succ_in + hbok_t
        hc = hb_cnt_in + hbtot_t
        if clean:
            commit = hb_open_in & (hs + 1 >= cfg.majority_need) & is_leader
            hb_open = hb_open_in & ~commit
            hb_succ, hb_cnt = hs, hc
        else:
            # reference: the check runs only at exactly N-1 responses in
            done = (hbtot_t > 0) & (hc == n - 1)
            commit = done & (hs + 1 >= cfg.majority_need)
            hb_succ = jnp.where(done, 0, hs)
            hb_cnt = jnp.where(done, 0, hc)
            hb_open = hb_open_in
        blk = jnp.clip(state.block_num, 0, cfg.raft_max_blocks - 1)

        def stamp(block_tick):
            return jnp.where(
                (jax.nn.one_hot(blk, cfg.raft_max_blocks, dtype=bool)
                 & commit[:, None]
                 & (state.block_num < cfg.raft_max_blocks)[:, None]),
                jnp.int32(t),
                block_tick,
            )

        if can_branch(axis):
            # the stamp is the identity unless some node's commit lands, on
            # a lane (a shard of models/mixed.step, a seed of a sweep) as on
            # a lone run: a quiet tick passes over no [N, B] table
            lands = commit & (state.block_num < cfg.raft_max_blocks)
            block_tick = gated_body(lands.any(), stamp, state.block_tick,
                                    COMMIT_SCOPE)
        else:
            block_tick = stamp(state.block_tick)
        block_num = state.block_num + commit
        # blockNum >= 50 cancels the heartbeat (raft-node.cc:248-251).  Gossip
        # divergence: completion must NOT silence the failure detector — with
        # term-style vote release, heartbeat silence triggers a fresh election
        # whose winner re-replicates from scratch (per-leader counters, no
        # shared log); the completed leader keeps the 4-byte control heartbeat
        # and simply stops proposing (add_change_value already cleared).
        if not gossip:
            next_hb = jnp.where(block_num >= cfg.raft_max_blocks, DISARM, next_hb)

    with jax.named_scope("raft.tick.timer_vote"):
        # ---- timer: sendVote (raft-node.cc:392-401) -----------------------------
        fire = (
            (jnp.int32(t) >= election_deadline)
            & (election_deadline != DISARM)
            & ~is_leader
            & state.alive
        )
        has_voted = has_voted | fire  # self-vote latch
        if terms:
            # the next term, whose candidate this node is; its one vote of
            # that term is the self-vote above
            term = term + fire
            is_cand = is_cand | fire
        if gossip or terms:
            # fresh election: restart the reply count (stale replies from the
            # previous election drained long ago — reply horizon << timeout)
            vote_success = jnp.where(fire, 0, vote_success)
            vote_failed = jnp.where(fire, 0, vote_failed)
        k_e2 = chan_key(tkey, Channel.ELECTION + 100)
        if axis is not None:
            k_e2 = jax.random.fold_in(k_e2, jax.lax.axis_index(axis))
        rearm2 = t + jax.random.randint(
            k_e2, (n_loc,), cfg.raft_election_lo_ms, cfg.raft_election_hi_ms,
            dtype=jnp.int32,
        )
        election_deadline = jnp.where(fire, rearm2, election_deadline)
        elections = state.elections + fire
        k_vq = chan_key(tkey, Channel.DELAY_BCAST)

        def push_vreq(buf, contrib):
            return ring_push_max(buf, t, lo, contrib)

        if gossip:
            # flood origin: full TTL, marked seen so the self-loop copy is inert
            base_v = ((jnp.int32(t) + 1) * n + ids + 1) * fire.astype(jnp.int32)
            origin_v = (base_v * h_enc + cfg.gossip_hops) * (base_v > 0)
            seen_vreq = jnp.maximum(seen_vreq, origin_v)
            # the candidate backs its own (newest) election
            my_base = jnp.maximum(my_base, base_v)
            out_v = jnp.maximum(origin_v, vreq_fwd)
            vreq = gated_push(
                (out_v > 0).any(),
                lambda: dv.gossip_fwd(k_vq, out_v[:, None], nbrs_loc, n, lo, hi,
                                      drop, axis=axis, impl=eimpl)[:, :, 0],
                zeros_flat,
                vreq,
                push_vreq,
                axis,
            )
        elif stat:
            vreq = gated_push(
                fire.any(),
                lambda: (
                    gd.bcast_value_max_stat_kreg(
                        k_vq, (ids + 1) * fire.astype(jnp.int32), nbr_in_loc,
                        ow_probs, drop, axis=axis, xg=exchange)
                    if kreg else
                    dv.bcast_value_max_stat(
                        k_vq, (ids + 1) * fire.astype(jnp.int32), ow_probs, drop,
                        axis=axis)
                ),
                zeros_flat,
                vreq,
                push_vreq,
                axis,
            )
        elif kreg:
            vreq = gated_push(
                fire.any(),
                lambda: gd.bcast_matrix_kreg(
                    k_vq, fire, fire.astype(jnp.int32), nbr_in_loc, ids, lo, hi,
                    drop, axis=axis, impl=eimpl, xg=exchange),
                jnp.zeros((hi - lo, n_loc, cfg.degree + 1), jnp.int32),
                vreq,
                push_vreq,
                axis,
            )
        else:
            vreq = gated_push(
                fire.any(),
                lambda: dv.bcast_matrix_dense(
                    k_vq, fire,
                    # VOTE_REQ(term, id): the request's value is its term
                    term * fire if terms else fire.astype(jnp.int32),
                    lo, hi, drop, axis=axis, impl=eimpl),
                jnp.zeros((hi - lo, n_loc, n), jnp.int32),
                vreq,
                push_vreq,
                axis,
            )

    with jax.named_scope("raft.tick.timer_heartbeat"):
        # ---- timer: sendHeartBeat (raft-node.cc:405-433) ------------------------
        hb_fire = (
            is_leader & (jnp.int32(t) >= next_hb) & (next_hb != DISARM) & state.alive
        )
        # setProposal fires exactly once (raft-node.cc:216,431-433) — round==50
        # clears add_change_value for good, so the trigger must not re-fire
        set_prop = (jnp.int32(t) >= proposal_tick) & (proposal_tick != DISARM)
        add_change_value = (state.add_change_value | set_prop) & ~resign
        proposal_tick = jnp.where(set_prop, DISARM, proposal_tick)
        prop_send = hb_fire & add_change_value
        # Full mesh: either/or, like the reference (raft-node.cc:405-433).
        # Gossip: the leader ALWAYS floods the 4-byte plain heartbeat — a 20 KB
        # proposal store-and-forwards ~hops*(delay+ser) (~460 ms at defaults),
        # far beyond the 150-300 ms election window, so using the block channel
        # as the failure detector deposes a healthy leader every proposal phase;
        # separating the control heartbeat from block dissemination is the
        # documented gossip divergence.
        plain_send = hb_fire if gossip else (hb_fire & ~add_change_value)
        next_hb = jnp.where(hb_fire, next_hb + cfg.raft_heartbeat_ms, next_hb)
        if terms:
            last_hb = jnp.where(hb_fire & (term == lead_term0), jnp.int32(t),
                                state.last_hb)
        # SendTX: round++; at round==50 stop adding proposals (raft-node.cc:361-365)
        round_ = state.round + prop_send
        add_change_value = add_change_value & ~(
            prop_send & (round_ >= cfg.raft_max_rounds)
        )
        # new proposal round opens the ack window
        hb_succ = jnp.where(prop_send, 0, hb_succ) if clean else hb_succ
        hb_cnt = jnp.where(prop_send, 0, hb_cnt) if clean else hb_cnt
        hb_open = (hb_open | prop_send) if clean else hb_open

        k_hb = chan_key(tkey, Channel.DELAY_BCAST2)

        def push_plain(buf, contrib):
            # the gossip flood carries a value, and so does a heartbeat
            # with terms (its term); the other direct arms a count
            push = ring_push_max if gossip or terms else ring_push_add
            return push(buf, t, lo, contrib)

        def push_prop(buf, contrib):
            return ring_push_max(buf, t, lo + ser, contrib)

        if queued:
            # serial-pipe send (engine.cpp link_enqueue): the packet reaches the
            # (leader -> j) link after its scheduling delay d_j - prop, transmits
            # when the link frees (proposals occupy it for ser; 4-byte plain
            # heartbeats queue behind but occupy nothing), then propagates.
            # Deliveries land on the rings at dynamic per-destination offsets —
            # bounded by the (ser - hb) * rounds backlog that config.ring_depth
            # reserves — via scatter (fidelity-mode path; scatter cost is
            # irrelevant at the n=8-ish scales queued fidelity runs at).
            prop_ms = cfg.link_delay_ms
            prop_val = jnp.max(jnp.where(prop_send, ids + 1, 0))
            plain_on = jnp.max(plain_send.astype(jnp.int32))
            sender = jnp.max(jnp.where(prop_send | plain_send, ids, -1))
            if axis is not None:
                prop_val = jax.lax.pmax(prop_val, axis)
                plain_on = jax.lax.pmax(plain_on, axis)
                sender = jax.lax.pmax(sender, axis)
            any_send = (prop_val > 0) | (plain_on > 0)
            dest = any_send & (ids != sender)  # crashed peers still reserve the
            # pipe (C++ run_loop kind-2: reservation is sender-side)
            d_j = jax.random.randint(
                dv._shard_key(jax.random.fold_in(k_hb, 7), axis), (n_loc,), lo,
                hi, jnp.int32,
            )
            ser_s = jnp.where(prop_val > 0, ser, 0)
            start = jnp.maximum(t + d_j - prop_ms, link_busy)
            delivery = start + ser_s + prop_ms
            link_busy = jnp.where(dest, start + ser_s, link_busy)
            dd = hb_prop.shape[0]
            cols = jnp.arange(n_loc)
            didx = jnp.where(dest, delivery % dd, dd)  # dd = out-of-bounds drop
            hb_prop = hb_prop.at[didx, cols].max(
                jnp.where(dest, prop_val, 0), mode="drop")
            hb_plain = hb_plain.at[didx, cols].add(
                (dest & (plain_on > 0)).astype(jnp.int32), mode="drop")
        elif gossip:
            # plain heartbeats: tiny control messages, flooded with the tick as
            # the monotone base (concurrent leaders dedup to one — got_hb only
            # pacifies timers); proposals carry the 20 KB block, so every hop
            # re-serializes (store-and-forward), hence ser on each leg
            base_h = (jnp.int32(t) + 1) * plain_send.astype(jnp.int32)
            origin_h = (base_h * h_enc + cfg.gossip_hops) * (base_h > 0)
            seen_hb = jnp.maximum(seen_hb, origin_h)
            out_h = jnp.maximum(origin_h, hb_fwd)
            hb_plain = gated_push(
                (out_h > 0).any(),
                lambda: dv.gossip_fwd(
                    jax.random.fold_in(k_hb, 2), out_h[:, None], nbrs_loc, n, lo,
                    hi, drop, axis=axis, impl=eimpl)[:, :, 0],
                zeros_flat,
                hb_plain,
                push_plain,
                axis,
            )
            base_p = (
                (jnp.int32(t) + 1) * (n + 1) + ids + 1
            ) * prop_send.astype(jnp.int32)
            origin_p = (base_p * h_enc + cfg.gossip_hops) * (base_p > 0)
            seen_prop = jnp.maximum(seen_prop, origin_p)
            out_p = jnp.maximum(origin_p, prop_fwd)
            hb_prop = gated_push(
                (out_p > 0).any(),
                lambda: dv.gossip_fwd(
                    jax.random.fold_in(k_hb, 3), out_p[:, None], nbrs_loc, n, lo,
                    hi, drop, axis=axis, impl=eimpl)[:, :, 0],
                zeros_flat,
                hb_prop,
                push_prop,
                axis,
            )
        elif kreg:
            if stat:
                hb_plain = gated_push(
                    plain_send.any(),
                    # mode stays exact for the same O(1)-sender reason as the
                    # full-mesh stat arm below
                    lambda: gd.bcast_counts_stat_kreg(
                        k_hb, plain_send, nbr_in_loc, ids, ow_probs, drop,
                        axis=axis, mode="exact", xg=exchange),
                    zeros_flat,
                    hb_plain,
                    push_plain,
                    axis,
                )
                hb_prop = gated_push(
                    prop_send.any(),
                    lambda: gd.bcast_value_max_stat_kreg(
                        jax.random.fold_in(k_hb, 1),
                        (ids + 1) * prop_send.astype(jnp.int32), nbr_in_loc,
                        ow_probs, drop, axis=axis, xg=exchange),
                    zeros_flat,
                    hb_prop,
                    push_prop,
                    axis,
                )
            else:
                hb_plain = gated_push(
                    plain_send.any(),
                    lambda: gd.bcast_counts_kreg(
                        k_hb, plain_send, nbr_in_loc, ids, lo, hi, drop,
                        axis=axis, impl=eimpl, xg=exchange),
                    zeros_flat,
                    hb_plain,
                    push_plain,
                    axis,
                )
                hb_prop = gated_push(
                    prop_send.any(),
                    lambda: gd.bcast_value_max_kreg(
                        jax.random.fold_in(k_hb, 1), prop_send,
                        (ids + 1) * prop_send.astype(jnp.int32), nbr_in_loc,
                        ids, lo, hi, drop, axis=axis, impl=eimpl, xg=exchange),
                    zeros_flat,
                    hb_prop,
                    push_prop,
                    axis,
                )
        elif stat:
            hb_plain = gated_push(
                plain_send.any(),
                lambda: dv.bcast_counts_stat(
                    k_hb,
                    _psum_scalar(plain_send.astype(jnp.int32).sum(), axis),
                    # mode stays exact here: this channel has O(1) senders (the
                    # leader), and the Gaussian binomial approximation is biased
                    # for count-1 draws (~9% on a p=1/3 bucket); the sampler-cost
                    # argument for "normal" only applies to O(N)-count channels
                    plain_send, ow_probs, drop, axis=axis, mode="exact"),
                zeros_flat,
                hb_plain,
                push_plain,
                axis,
            )
            hb_prop = gated_push(
                prop_send.any(),
                lambda: dv.bcast_value_max_stat(
                    jax.random.fold_in(k_hb, 1),
                    (ids + 1) * prop_send.astype(jnp.int32), ow_probs, drop,
                    axis=axis),
                zeros_flat,
                hb_prop,
                push_prop,
                axis,
            )
        else:
            hb_plain = gated_push(
                plain_send.any(),
                # HEARTBEAT(term) with terms, an arrival count without
                lambda: dv.bcast_value_max_dense(
                    k_hb, plain_send, term * plain_send, lo, hi, drop,
                    impl=eimpl) if terms else
                dv.bcast_counts_dense(k_hb, plain_send, lo, hi, drop,
                                      axis=axis, impl=eimpl),
                zeros_flat,
                hb_plain,
                push_plain,
                axis,
            )
            hb_prop = gated_push(
                prop_send.any(),
                lambda: dv.bcast_value_max_dense(
                    jax.random.fold_in(k_hb, 1), prop_send,
                    # with terms: term * (n + 1) + leader + 1
                    (term * (n + 1) + ids + 1 if terms else ids + 1)
                    * prop_send.astype(jnp.int32), lo, hi, drop,
                    axis=axis, impl=eimpl),
                zeros_flat,
                hb_prop,
                push_prop,
                axis,
            )

        # proposal acks: follower state never affects the SUCCESS reply
        # (raft-node.cc:170-193), so the round trip is short-circuited; Byzantine
        # followers flip to FAILED.  The SUCCESS (honest) and FAILED (Byzantine)
        # channels cover *disjoint* peer sets, so their independent delay draws
        # cover disjoint edges — each ack lands in exactly one channel at one tick,
        # and the leader's total count is their sum.  (Gossip acks are generated
        # at flood arrival instead — see the gossip block above.)
        k_rt = chan_key(tkey, Channel.DELAY_ROUNDTRIP)
        voters = state.alive & state.honest
        liars = state.alive & ~state.honest
        if gossip:
            pass
        elif queued:
            # the follower's ack is a 4-byte reply over the (follower -> leader)
            # link, which is never busy (followers send no blocks): it departs at
            # the proposal's queued DELIVERY tick and lands one one-way delay
            # later.  Ack ticks are per-destination, the receiver is the single
            # leader row: bucket them into a [D] histogram (psum'd across shards)
            # and add it into the leader's ring column on the owning shard.
            d2 = jax.random.randint(
                dv._shard_key(jax.random.fold_in(k_rt, 9), axis), (n_loc,), lo,
                hi, jnp.int32,
            )
            ack_arr = delivery + d2
            prop_on = prop_val > 0
            okd = dest & prop_on & voters
            badd = dest & prop_on & liars
            dd = hb_ok.shape[0]
            hist_ok = jnp.zeros((dd,), jnp.int32).at[
                jnp.where(okd, ack_arr % dd, dd)].add(1, mode="drop")
            hist_bad = jnp.zeros((dd,), jnp.int32).at[
                jnp.where(badd, ack_arr % dd, dd)].add(1, mode="drop")
            if axis is not None:
                hist_ok = jax.lax.psum(hist_ok, axis)
                hist_bad = jax.lax.psum(hist_bad, axis)
            col = sender - ids[0]
            owned = prop_on & (col >= 0) & (col < n_loc)
            col_c = jnp.clip(col, 0, n_loc - 1)
            hb_ok = hb_ok.at[:, col_c].add(jnp.where(owned, hist_ok, 0))
            hb_bad = hb_bad.at[:, col_c].add(jnp.where(owned, hist_bad, 0))
        elif stat:
            # fused chain-into-ring (ops/delivery.push_roundtrip_reply_counts_
            # stat) — bit-equal to the former sample → ring_push_add compose.
            # The kregular overlay swaps only the per-sender peer counts for
            # out-table gathers (equal at k = N-1, same keys/chain).
            if kreg:
                ok_peers = gd.out_counts(voters, nbr_out_loc, ids, axis, exchange)
                bad_peers = gd.out_counts(liars, nbr_out_loc, ids, axis, exchange)
            else:
                n_voters = _psum_scalar(voters.astype(jnp.int32).sum(), axis)
                n_liars = _psum_scalar(liars.astype(jnp.int32).sum(), axis)
                ok_peers = n_voters - voters.astype(jnp.int32)
                bad_peers = n_liars - liars.astype(jnp.int32)
            hb_ok, hb_bad = gated_push(
                prop_send.any(),
                tuple,
                (),
                (hb_ok, hb_bad),
                lambda rings, _: (
                    dv.push_roundtrip_reply_counts_stat(
                        rings[0], t, rt_lo + ser, k_rt, prop_send,
                        ok_peers, rt_probs, drop,
                        axis=axis, mode=smode),
                    dv.push_roundtrip_reply_counts_stat(
                        rings[1], t, rt_lo + ser, jax.random.fold_in(k_rt, 1),
                        prop_send, bad_peers, rt_probs,
                        drop, axis=axis, mode=smode),
                ),
                axis,
            )
        else:
            if kreg:
                def _rt(kk, peers):
                    return gd.roundtrip_reply_counts_kreg(
                        kk, prop_send, nbr_out_loc, ids, lo, hi, drop,
                        peer_mask=peers, axis=axis, impl=eimpl, xg=exchange)
            else:
                def _rt(kk, peers):
                    return dv.roundtrip_reply_counts_dense(
                        kk, prop_send, lo, hi, drop, peer_mask=peers, axis=axis,
                        impl=eimpl)
            def push_ack(buf, counts):
                return ring_push_add(buf, t, rt_lo + ser, counts)

            hb_ok = gated_push(
                prop_send.any(), lambda: _rt(k_rt, voters), zeros_rt, hb_ok,
                push_ack, axis,
            )
            hb_bad = gated_push(
                prop_send.any(),
                lambda: _rt(jax.random.fold_in(k_rt, 1), liars),
                zeros_rt,
                hb_bad,
                push_ack,
                axis,
            )

    state = state.replace(
        is_leader=is_leader,
        has_voted=has_voted,
        election_deadline=election_deadline,
        vote_success=vote_success,
        vote_failed=vote_failed,
        next_hb=next_hb,
        proposal_tick=proposal_tick,
        add_change_value=add_change_value,
        m_value=m_value,
        block_num=block_num,
        round=round_,
        hb_succ=hb_succ,
        hb_cnt=hb_cnt,
        hb_open=hb_open,
        leader_tick=leader_tick,
        elections=elections,
        block_tick=block_tick,
        seen_vreq=seen_vreq,
        seen_hb=seen_hb,
        seen_prop=seen_prop,
        my_base=my_base,
        link_busy=link_busy,
    )
    if terms:
        state = state.replace(
            term=term, is_cand=is_cand, lead_term0=lead_term0,
            won_tick=won_tick, last_hb=last_hb, step_downs=step_downs,
            term_conflicts=term_conflicts,
        )
    if sched:
        with jax.named_scope("raft.tick.fault"):
            state = _crash_records(
                state, t, win=win, fire=fire, granted=granted,
                acted=win | fire | hb_fire | got_hb | granted
                | (term != term_in) | (has_voted & ~voted_in))
    bufs = RaftBufs(
        vreq=vreq, vres_ok=vres_ok, vres_no=vres_no, hb_plain=hb_plain,
        hb_prop=hb_prop, hb_ok=hb_ok, hb_bad=hb_bad,
    )
    return state, bufs


def _psum_scalar(x, axis):
    return x if axis is None else jax.lax.psum(x, axis)


def metrics(cfg, state: RaftState) -> dict:
    """The reference's measurement surface (SURVEY.md §5): leader-elected time
    (raft-node.cc:212), per-block processed time (:246), final Blocks/Rounds
    summary (:122-123), election starts (:399)."""
    if state.term is not None:  # with terms: a stack of one group
        return metrics_stacked(cfg, {
            f: np.asarray(getattr(state, f))[None] for f in METRIC_FIELDS
            if getattr(state, f) is not None}, 1)[0]
    alive = np.asarray(state.alive)
    is_leader = np.asarray(state.is_leader)
    leader_tick = np.asarray(state.leader_tick)
    block_num = np.asarray(state.block_num)
    block_tick = np.asarray(state.block_tick)
    m_value = np.asarray(state.m_value)
    leaders = np.flatnonzero(is_leader & alive)
    # without terms (the default) a split brain is possible: under Byzantine
    # double-voting, and in any small group once the first leader stops at
    # 50 blocks and the second election deposes nobody (KNOWN_ISSUES #0r;
    # ``cfg.raft_terms`` is the repair); report the earliest-elected leader
    # as "the" leader
    lead = int(leaders[np.argmin(leader_tick[leaders])]) if leaders.size else -1
    blocks = int(block_num[lead]) if lead >= 0 else 0
    bt = block_tick[lead][: blocks] if lead >= 0 else np.array([])
    # agreement: every alive follower that stored a value stored the leader's
    stored = m_value[alive]
    stored = stored[stored >= 0]
    return {
        "protocol": "raft",
        "n": cfg.n,
        "n_leaders": int(len(leaders)),
        "leader": lead,
        "leader_elected_ms": float(leader_tick[lead]) if lead >= 0 else -1.0,
        "blocks": blocks,
        "rounds": int(np.asarray(state.round).max()),
        "elections": int(np.asarray(state.elections).sum()),
        "last_block_ms": float(bt.max()) if bt.size else -1.0,
        "mean_block_interval_ms": (
            float(np.diff(bt).mean()) if bt.size > 1 else -1.0
        ),
        "agreement_ok": bool(
            lead < 0 or (stored.size == 0) or (stored == lead).all()
        ),
    }


# the state fields :func:`metrics` reads, and the only ones (see
# pbft.METRIC_FIELDS; parallel/sweep._readback fetches these leaves alone;
# the leaves of terms are fetched where a state has them)
METRIC_FIELDS = (
    "alive", "block_num", "block_tick", "elections", "is_leader",
    "leader_tick", "m_value", "round",
    "term", "lead_term0", "won_tick", "last_hb", "step_downs",
    "term_conflicts",
    "crash_tick", "crash_node", "replaced_tick", "crash_elections",
    "restarts", "dead_acts", "double_votes",
)


def metrics_stacked(cfg, host: dict, groups: int) -> list:
    """:func:`metrics` of ``groups`` stacked groups that ran with terms
    (``cfg.raft_terms``), at once: the list of their dicts from their
    fetched leaves ``host[field]`` of shape ``[groups, N, ...]`` (numpy;
    topo/committee.metrics' one readback), computed as array reductions
    over the node axis.  The one implementation: a lone group's
    :func:`metrics` is a stack of one.  20,000 groups of 5 take a tenth of a
    second this way where a dict a call took 2.8 s of every run's host time
    (PERF.md section 6, PR 42).

    "The" leader is the leader of the highest term; ``blocks``, ``rounds``,
    ``last_block_ms`` and ``mean_block_interval_ms`` are over every block
    committed in the group, whichever node led (a node counts what it
    committed while it led): after the first leader's stop at 50 blocks the
    group fails over, and the leader at the end of a run is as a rule not
    the one that committed them.  Beyond the keys of a run without terms:
    ``term_final`` (the group's highest term), ``leader_term`` (0 where no
    node leads), ``n_leaders_term_final``, ``term_conflicts`` and
    ``step_downs`` (summed), ``first_leader_ms`` / ``first_leader_term`` /
    ``first_leader_blocks`` (the node that won first), and ``failover_ms``:
    from the first leader's last heartbeat of the term it first led to the
    next election anyone wins (-1.0 where none did).  ``agreement_ok``:
    there is no log to compare, so every value an alive node stored names a
    node that won an election and proposed, and no term had two leaders.

    With a crash schedule (``cfg.faults.crashes`` = K; the keys above keep
    their meaning, ``failover_ms`` included) the failover is reported as a
    distribution over the group's crashes: ``crashes`` (that fell inside
    the run), ``crashes_found_no_leader``, ``crashes_unreplaced`` (hit a
    leader, no win before the next crash or the end), ``failovers`` (the
    rest: a crashed leader replaced by a leader of a higher term),
    ``failovers_multi_election`` (of those, more than one timer fired
    first), ``failover_mean_ms`` / ``_median_ms`` / ``_p90_ms`` (nearest
    rank) / ``_max_ms`` over the group's failovers (-1.0 where it has
    none), per crash k ``crash{k}_failover_ms`` (-1.0 where it was not
    replaced) and ``crash{k}_elections``, ``restarts``, and the oracles
    ``dead_acts`` and ``double_votes`` (summed; 0)."""
    never = np.int64(1) << 40
    alive, term = host["alive"], host["term"]
    leader_tick, won_tick = host["leader_tick"], host["won_tick"]
    block_num, rounds = host["block_num"], host["round"]
    m_value = host["m_value"]
    at = lambda a, i: np.take_along_axis(a, i[:, None], axis=1)[:, 0]  # noqa: E731
    conflicts = host["term_conflicts"].sum(axis=1)
    leaders = host["is_leader"] & alive
    n_leaders = leaders.sum(axis=1)
    lead = np.where(n_leaders > 0,
                    np.argmax(np.where(leaders, term, -1), axis=1), -1)
    lead_at = np.maximum(lead, 0)
    term_final = term.max(axis=1)
    led = leader_tick >= 0
    has_first = led.any(axis=1)
    first = np.argmin(np.where(led, leader_tick, never), axis=1)
    others = led & (np.arange(term.shape[1])[None, :] != first[:, None])
    first_tick, again = at(leader_tick, first), at(won_tick, first)
    later = np.minimum(
        np.where(others, leader_tick, never).min(axis=1),
        np.where(again > first_tick, again, never))
    last_hb = at(host["last_hb"], first)
    failover = np.where(has_first & (later < never) & (last_hb >= 0),
                        later - last_hb, -1).astype(np.float64)
    committed = (np.arange(host["block_tick"].shape[2])[None, None, :]
                 < block_num[:, :, None])
    blocks = block_num.sum(axis=1)
    bt_last = np.where(committed, host["block_tick"], -1).max(axis=(1, 2))
    bt_first = np.where(committed, host["block_tick"], never).min(axis=(1, 2))
    stored = alive & (m_value >= 0)
    named = np.maximum(m_value, 0)
    unfounded = stored & ~(
        (np.take_along_axis(leader_tick, named, axis=1) >= 0)
        & (np.take_along_axis(rounds, named, axis=1) > 0))
    columns = {
        "n_leaders": n_leaders,
        "leader": lead,
        "leader_elected_ms": np.where(
            lead >= 0, at(won_tick, lead_at), -1).astype(np.float64),
        "blocks": blocks,
        "rounds": rounds.sum(axis=1),
        "elections": host["elections"].sum(axis=1),
        "last_block_ms": np.where(blocks > 0, bt_last, -1).astype(np.float64),
        "mean_block_interval_ms": np.where(
            blocks > 1, (bt_last - bt_first) / np.maximum(blocks - 1, 1), -1.0),
        "agreement_ok": (conflicts == 0) & ~unfounded.any(axis=1),
        "term_final": term_final,
        "leader_term": np.where(lead >= 0, at(term, lead_at), 0),
        "n_leaders_term_final": (
            leaders & (term == term_final[:, None])).sum(axis=1),
        "term_conflicts": conflicts,
        "step_downs": host["step_downs"].sum(axis=1),
        "first_leader_ms": np.where(
            has_first, first_tick, -1).astype(np.float64),
        "first_leader_term": np.where(
            has_first, at(host["lead_term0"], first), 0),
        "first_leader_blocks": np.where(has_first, at(block_num, first), 0),
        "failover_ms": failover,
    }
    if "crash_tick" in host:
        columns.update(_crash_columns(host))
    keys = ("protocol", "n") + tuple(columns)
    return [dict(zip(keys, ("raft", cfg.n) + row))
            for row in zip(*(v[:groups].tolist() for v in columns.values()))]



# ---- the crash schedule (``cfg.faults.crashes``; module docstring "Crash
# schedule"): below the metrics, so that no line of :func:`step`'s callees
# above moves but those the schedule's three call sites add


def check_arms(cfg):
    """What a state or a program of ``cfg`` needs of its arm: a crash
    schedule's refusals first (they name every arm, whatever else is off),
    then those of terms."""
    check_schedule(cfg)
    if cfg.raft_terms:
        check_terms(cfg)


def check_schedule(cfg, engine: str = "jax"):
    """A crash schedule (``cfg.faults.crashes``) runs where terms do, and
    every other arm refuses it by its name rather than running on with a
    leader that never dies.  The one place the arms are listed:
    :func:`init` and :func:`step` ask it, runner.py's validation before
    anything is built (any protocol; ``topology`` may still be
    ``"committee"``), and engine.run_cpp (``engine="cpp"``)."""
    f = cfg.faults
    if not f.crashes:
        return
    arms = (
        (engine == "cpp", "the C++ engine (--engine cpp)",
         "engine.cpp's nodes crash from t = 0 or never; the per-message "
         "reference with a schedule is "
         "benchmark/reference/raft_crash_engine.py"),
        (cfg.protocol == "pbft", "protocol='pbft'",
         "a view change under a dead leader is upstream's 1-in-100 coin "
         "here (ROADMAP R1's remainder)"),
        (cfg.protocol == "paxos", "protocol='paxos'",
         "its proposers have no leader to kill"),
        (cfg.protocol == "mixed", "protocol='mixed'",
         "its shards run the stat arm of models/raft.py and the heartbeat-"
         "blocked models/raft_hb, which have no terms"),
        (cfg.fidelity != "clean", f"fidelity={cfg.fidelity!r}",
         "upstream's Raft never re-arms an election timer, so a dead "
         "leader is never detected"),
        (cfg.topology not in ("full", "committee"),
         f"topology={cfg.topology!r}",
         "the gossip and kregular arms have no terms"),
        (cfg.delivery == "stat", "delivery='stat'",
         "the stat arm of models/raft.py and the heartbeat-blocked "
         "models/raft_hb keep no per-node sender to silence"),
        (cfg.queued_links, "queued_links",
         "the serial-pipe registers follow one block sender"),
        (cfg.mesh_axis is not None, "a mesh axis",
         "picking the leader to kill reduces over a group's nodes on one "
         "device"),
        (not cfg.raft_terms, "raft_terms=False",
         "without terms a restarted leader is never deposed and a second "
         "election is not bound to one winner (KNOWN_ISSUES #0r)"),
    )
    for refused, arm, why in arms:
        if refused:
            raise NotImplementedError(
                f"a crash schedule (faults.crashes={f.crashes}) is not "
                f"implemented for {arm}: {why}; it runs on protocol='raft' "
                "with raft_terms=True, delivery='edge', topology='full' or "
                "'committee' (models/raft.check_schedule)")
    if cfg.topology == "committee":
        return  # the horizon below is a group's: asked again on its config
    _, rt_hi = cfg.roundtrip_range()
    horizon = rt_hi - 1 + cfg.serialization_ticks(cfg.raft_block_bytes)
    if f.downtime_ms <= horizon:
        raise ValueError(
            f"a crash schedule: a reply lands up to {horizon} ticks after "
            f"its request, and downtime_ms={f.downtime_ms} must exceed that, "
            "or a count sent to the node before its crash could reach it "
            "after its restart (the reply channels carry no term)")


def _crash_and_restart(cfg, state: RaftState, t, rearm_on) -> RaftState:
    """The head of a tick under a crash schedule: the crash that is due
    kills the group's alive leader, and a node whose downtime is over is
    back as a follower.  Behind ``base.gated_body`` on "a crash or a restart
    is due" (under a lane batch: in some group), the body being the identity
    on a group where neither is; where nothing can branch it runs on every
    tick.  ``rearm_on`` is :func:`step`'s draw of a fresh election deadline:
    a restart takes it off the channel a deposed leader's re-arm uses."""
    f = cfg.faults
    n_loc = state.alive.shape[0]
    since = jnp.int32(t) - f.first_ms - state.crash_phase
    k_due = since // f.period_ms
    due = (since >= 0) & (since % f.period_ms == 0) & (k_due < f.crashes)
    names = ("alive", "is_leader", "is_cand", "vote_success", "vote_failed",
             "next_hb", "proposal_tick", "add_change_value", "hb_succ",
             "hb_cnt", "hb_open", "election_deadline", "restart_tick",
             "restarts", "crash_tick", "crash_node")

    def body(c):
        leads = c["is_leader"] & c["alive"]
        target = jnp.argmax(jnp.where(leads, state.term, -1))
        hit = due & leads & (jnp.arange(n_loc) == target)
        back = c["restart_tick"] == jnp.int32(t)
        this = due & (jnp.arange(f.crashes) == k_due)
        off = lambda x: jnp.where(hit, DISARM, x)   # noqa: E731
        zero = lambda x: jnp.where(hit, 0, x)       # noqa: E731
        return {
            "alive": (c["alive"] & ~hit) | back,
            # volatile state is lost; term and has_voted are persistent
            "is_leader": c["is_leader"] & ~hit,
            "is_cand": c["is_cand"] & ~hit,
            "vote_success": zero(c["vote_success"]),
            "vote_failed": zero(c["vote_failed"]),
            "next_hb": off(c["next_hb"]),
            "proposal_tick": off(c["proposal_tick"]),
            "add_change_value": c["add_change_value"] & ~hit,
            "hb_succ": zero(c["hb_succ"]),
            "hb_cnt": zero(c["hb_cnt"]),
            "hb_open": c["hb_open"] & ~hit,
            "election_deadline": jnp.where(
                back, rearm_on(Channel.ELECTION + 200),
                off(c["election_deadline"])),
            "restart_tick": jnp.where(
                hit, jnp.int32(t) + f.downtime_ms,
                jnp.where(back, DISARM, c["restart_tick"])),
            "restarts": c["restarts"] + back,
            "crash_tick": jnp.where(this, jnp.int32(t), c["crash_tick"]),
            "crash_node": jnp.where(this & leads.any(), target,
                                    c["crash_node"]),
        }

    carry = {k: getattr(state, k) for k in names}
    if can_branch(cfg.mesh_axis):
        pred = due | (state.restart_tick == jnp.int32(t)).any()
        carry = gated_body(pred, body, carry, FAULT_SCOPE)
    else:
        carry = body(carry)
    return state.replace(**carry)


def _crash_records(state: RaftState, t, *, win, fire, granted,
                   acted) -> RaftState:
    """The end of a tick under a crash schedule: the newest crash, if it
    hit a leader and is not replaced yet, is replaced by this tick's win or
    else counts this tick's timers; and the two oracles."""
    fell = state.crash_tick >= 0
    newest = jnp.arange(fell.shape[0]) == fell.sum() - 1
    is_open = newest & (state.crash_node >= 0) & (state.replaced_tick < 0)
    won = win.any()
    # votes of this tick, each against the node's own record: a grant in
    # the term the request found it in, then the self-vote of a timer
    term_grant = state.term - fire
    twice = (granted & (state.voted_term == term_grant)).astype(jnp.int32) \
        + (fire & (jnp.where(granted, term_grant, state.voted_term)
                   == state.term))
    return state.replace(
        replaced_tick=jnp.where(is_open & won, jnp.int32(t),
                                state.replaced_tick),
        crash_elections=state.crash_elections
        + (is_open & ~won) * fire.sum(),
        voted_term=jnp.where(granted | fire, state.term, state.voted_term),
        double_votes=state.double_votes + twice,
        dead_acts=state.dead_acts + (acted & ~state.alive),
    )


def _crash_columns(host: dict) -> dict:
    """:func:`metrics_stacked`'s columns of a crash schedule, from the
    per-crash leaves ``[groups, K]``."""
    tick, node = host["crash_tick"], host["crash_node"]
    won, fired = host["replaced_tick"], host["crash_elections"]
    fell, hit = tick >= 0, node >= 0
    done = hit & (won >= 0)
    count = done.sum(axis=1)
    ms = np.where(done, won - tick, -1).astype(np.float64)
    ranked = np.sort(np.where(done, ms, np.inf), axis=1)
    rank = lambda i: np.where(count > 0, np.take_along_axis(  # noqa: E731
        ranked, np.clip(i, 0, None)[:, None], axis=1)[:, 0], -1.0)
    columns = {
        "crashes": fell.sum(axis=1),
        "crashes_found_no_leader": (fell & ~hit).sum(axis=1),
        "crashes_unreplaced": (hit & ~done).sum(axis=1),
        "failovers": count,
        "failovers_multi_election": (done & (fired > 1)).sum(axis=1),
        "failover_mean_ms": np.where(
            count > 0, np.where(done, ms, 0.0).sum(axis=1)
            / np.maximum(count, 1), -1.0),
        "failover_median_ms": np.where(
            count > 0, (rank((count - 1) // 2) + rank(count // 2)) / 2, -1.0),
        "failover_p90_ms": rank(-(-9 * count // 10) - 1),
        "failover_max_ms": rank(count - 1),
        "restarts": host["restarts"].sum(axis=1),
        "dead_acts": host["dead_acts"].sum(axis=1),
        "double_votes": host["double_votes"].sum(axis=1),
    }
    for k in range(tick.shape[1]):
        columns[f"crash{k}_failover_ms"] = ms[:, k]
        columns[f"crash{k}_elections"] = fired[:, k]
    return columns
