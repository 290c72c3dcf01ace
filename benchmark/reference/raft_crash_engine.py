"""The plain reference of Raft WITH terms under a crash schedule: a
per-message event heap, one group at a time, a term on every message, the
crash and the restart of a leader as events of their own.

Independent of the program: nothing is imported from
``blockchain_simulator_tpu`` (nor from ``raft_terms_engine.py``, whose rules
are restated here); a deployment arrives as the plain ``fields`` dict of its
configuration file (``SimConfig``'s field names, the schedule under
``faults``; what a file leaves out is upstream's constant, :data:`UPSTREAM`),
and randomness is Python's own ``random.Random``, one stream a group.  Time
is integer milliseconds.  A message is an event ``(arrival, order, seq, dst,
kind, term, src, payload)`` on one heap; its delay is the link's delay plus
the sender's send delay U{0,1,2} ms (``raft-node.cc:63-66``), drawn per
message, plus the serialization of a 20 KB proposal where the deployment
models it.

The experiment is section 9.3 of Ongaro & Ousterhout, "In Search of an
Understandable Consensus Algorithm", USENIX ATC 2014 (Figure 16, "the time
to detect and replace a crashed leader"): a cluster of five whose leader is
crashed again and again, uniformly at random within its heartbeat interval.
The rules are the paper's Figure 2 (sections 5.1-5.2) on upstream's message
set (``raft-node.cc``: VOTE_REQ, VOTE_RES, HEARTBEAT plain or carrying a
proposal, HEARTBEAT_RES):

- All servers: a message whose term T exceeds ``term`` sets ``term = T`` and
  makes the node a follower (a leader cancels its heartbeat and its proposal
  schedule and arms an election timer; the vote of the new term is free)
  BEFORE the message is handled.
- Followers and candidates: the election timer U[lo, hi) ms fires ->
  ``term += 1``, vote for self, zero the count, broadcast VOTE_REQ(term,
  id), re-arm.  A leader has no such timer.
- VOTE_REQ(T, c): ``T < term`` -> VOTE_RES(term, denied).  ``T == term`` ->
  granted iff no vote was given in ``term``; a grant re-arms the election
  timer.  The reply carries the replier's term.
- VOTE_RES(T, granted) counts only at a candidate whose term is T;
  ``votes + 1 > N/2`` (``raft-node.cc:209``; N counts the dead too) ->
  leader of T: timer off, first heartbeat now, proposals
  ``raft_proposal_delay_ms`` later, a heartbeat every ``raft_heartbeat_ms``.
- HEARTBEAT(T): ``T < term`` -> HEARTBEAT_RES(term, rejected).  Else the
  receiver is a follower of T (a candidate of T steps down), re-arms its
  timer, stores the proposal's value, replies HEARTBEAT_RES(T, success).
- HEARTBEAT_RES(T, success, round) counts only at the leader of term T whose
  open round it answers; a majority commits the round's block once.

The schedule (``fields["faults"]``: ``crashes`` K, ``first_ms`` T0,
``period_ms`` P, ``downtime_ms``): a group draws ``phase`` from U{0..P-1};
crash k, k = 0..K-1, is an event at ``T0 + k * P + phase``, BEFORE the
messages and timers of that millisecond.  It hits the node that is an alive
leader then (of the highest term, lowest id, should there be two); where
none leads, the crash is recorded as having found no leader and kills
nobody.  Raft's crash:

- from its crash to its restart a node sends nothing, handles nothing and
  fires no timer; what it sent before still arrives; what arrives for it
  while it is down is lost.
- ``term`` and the vote of that term survive (Figure 2's persistent state);
  its role, vote count, heartbeat and proposal schedules and ack window do
  not.
- ``downtime_ms`` later (an event of its own, again before that
  millisecond's messages) it is back: a follower with a fresh election
  timer.

Records, a crash: its millisecond, the node it killed (-1: none), the
millisecond on which a node of the group next wins an election (necessarily
in a higher term; only the newest crash can be replaced, so one still open
when the next falls stays unreplaced), and the election timers that fired in
between (1 where the first election succeeds).  Two oracles that stay 0:
``dead_acts`` (a handler or a timer ran at a node that is down) and
``double_votes`` (a node voted twice in one term, against a record of its
own that no restart touches).

Departures from the paper's experiment, each also the program's
(``blockchain_simulator_tpu/models/raft.py`` "Crash schedule"): upstream's
Raft has no log, so the paper's "servers with different log lengths, some
candidates not eligible" cannot be modelled and every alive server is
eligible; the paper forces a synchronized heartbeat before each kill, the
schedule does not (a uniform ``phase`` against a group's own heartbeat phase
is "uniformly at random within its heartbeat interval").  Departures from
upstream, as ``raft_terms_engine.py``'s: terms (upstream has none), no
``lose`` rule, a heartbeat re-arms the follower's timer; upstream's stop
rule stays.  No PreVote.  Where the program departs from THIS file (it
decides a heartbeat's ack where the heartbeat is sent, so a follower down at
the send and back before the arrival acks here and not there; it handles the
messages of one millisecond highest term first) the comparison of the two is
the test.

``run(fields, seed, groups)`` runs ``groups`` independent groups of
``n / committees`` nodes and returns, a list entry a group, what
``models/raft.metrics`` reports under a schedule, under the same keys
(:func:`keys`).
"""

from __future__ import annotations

import heapq
import random

UPSTREAM = {
    "link_delay_ms": 3, "link_rate_mbps": 3.0, "model_serialization": True,
    "raft_heartbeat_ms": 50, "raft_election_lo_ms": 150,
    "raft_election_hi_ms": 300, "raft_delay_lo": 0, "raft_delay_hi": 3,
    "raft_proposal_delay_ms": 1000, "raft_max_blocks": 50,
    "raft_max_rounds": 50, "raft_tx_size": 200, "raft_tx_speed": 2000,
    "sim_ms": 10_000, "committees": 1,
}
NO_SCHEDULE = {"crashes": 0, "first_ms": 0, "period_ms": 1, "downtime_ms": 0}

KEYS = ("n_leaders", "blocks", "rounds", "elections", "last_block_ms",
        "agreement_ok", "term_final", "leader_term", "n_leaders_term_final",
        "term_conflicts", "step_downs", "first_leader_ms",
        "first_leader_term", "first_leader_blocks", "failover_ms",
        "leaders_of_one_term_max",
        "crashes", "crashes_found_no_leader", "crashes_unreplaced",
        "failovers", "failovers_multi_election", "failover_mean_ms",
        "failover_median_ms", "failover_p90_ms", "failover_max_ms",
        "restarts", "dead_acts", "double_votes")


def keys(crashes: int) -> tuple:
    """What a group's dict holds under a schedule of ``crashes`` kills."""
    return KEYS + tuple(f"crash{k}_{what}" for k in range(crashes)
                        for what in ("failover_ms", "elections"))


FOLLOWER, CANDIDATE, LEADER = 0, 1, 2
# events of one millisecond: the schedule's crash and restarts first, then
# arrivals, then the election timer, then the heartbeat timer (a node handles
# what reached it before it acts)
CRASH, RESTART, MSG, T_ELECTION, T_HEARTBEAT = -2, -1, 0, 1, 2


class Node:
    def __init__(self, i: int):
        self.id = i
        self.alive = True
        self.voted_term = 0         # the last term it voted in: an oracle's
        self.double_votes = 0       # record, which no restart touches
        self.restarts = 0
        self.term = 0
        self.role = FOLLOWER
        self.voted = False          # the vote of ``term`` is given
        self.votes = 0
        self.deadline = -1          # election timer; -1 = off
        self.next_hb = -1           # heartbeat timer; -1 = off
        self.proposal_at = -1       # when heartbeats start to carry proposals
        self.proposing = False
        self.round = 0
        self.acks = 0
        self.open = False           # the current round is not committed yet
        self.blocks = 0
        self.block_ms: list = []
        self.value = -1
        self.first_win_ms = -1
        self.first_win_term = 0
        self.last_win_ms = -1
        self.last_hb_first_term = -1
        self.step_downs = 0
        self.conflicts = 0
        self.elections = 0


class Group:
    def __init__(self, p: dict, m: int, rng: random.Random,
                 schedule: dict = NO_SCHEDULE):
        self.p, self.m, self.rng = p, m, rng
        self.nodes = [Node(i) for i in range(m)]
        self.heap: list = []
        self.seq = 0
        self.need = m // 2 + 1      # votes + self > N/2
        ser = 0
        if p["model_serialization"]:
            nbytes = (p["raft_tx_speed"] * p["raft_heartbeat_ms"] // 1000
                      * p["raft_tx_size"])
            ser = int(nbytes * 8 / (p["link_rate_mbps"] * 1e6) * 1000 + 0.999)
        self.ser = ser
        self.leaders_of_one_term_max = 0
        self.dead_acts = 0
        # a crash: [millisecond, node killed or -1, replaced at or -1,
        # election timers fired in between]
        self.crashes: list = []
        self.downtime = schedule["downtime_ms"]
        for nd in self.nodes:
            self.arm(nd, 0)
        phase = rng.randrange(schedule["period_ms"])
        for k in range(schedule["crashes"]):
            self.at(schedule["first_ms"] + k * schedule["period_ms"] + phase,
                    CRASH, -1)

    # ---------------------------------------------------------------- events
    def at(self, when: int, order: int, dst: int, kind: str = "",
           term: int = 0, src: int = -1, payload=None):
        self.seq += 1
        heapq.heappush(self.heap,
                       (when, order, self.seq, dst, kind, term, src, payload))

    def send(self, now: int, src: Node, dst: int, kind: str, payload=None,
             extra: int = 0):
        p = self.p
        self.dead_acts += not src.alive
        d = p["link_delay_ms"] + self.rng.randrange(
            p["raft_delay_lo"], p["raft_delay_hi"])
        self.at(now + max(d, 1) + extra, MSG, dst, kind, src.term, src.id,
                payload)

    def arm(self, nd: Node, now: int):
        nd.deadline = now + self.rng.randrange(
            self.p["raft_election_lo_ms"], self.p["raft_election_hi_ms"])
        self.at(nd.deadline, T_ELECTION, nd.id)

    # -------------------------------------------------------------- schedule
    def on_crash(self, now: int):
        leaders = [nd for nd in self.nodes if nd.alive and nd.role == LEADER]
        if not leaders:
            self.crashes.append([now, -1, -1, 0])
            return
        nd = max(leaders, key=lambda x: (x.term, -x.id))
        self.crashes.append([now, nd.id, -1, 0])
        nd.alive = False
        # volatile state is lost; term and the vote of it are persistent
        nd.role = FOLLOWER
        nd.votes = nd.acks = 0
        nd.deadline = nd.next_hb = nd.proposal_at = -1
        nd.proposing = nd.open = False
        self.at(now + self.downtime, RESTART, nd.id)

    def on_restart(self, nd: Node, now: int):
        nd.alive = True
        nd.restarts += 1
        self.arm(nd, now)

    def open_crash(self):
        """The newest crash, if it hit a leader and is not replaced yet."""
        if self.crashes and self.crashes[-1][1] >= 0 \
                and self.crashes[-1][2] < 0:
            return self.crashes[-1]
        return None

    def vote(self, nd: Node):
        nd.double_votes += nd.voted_term == nd.term
        nd.voted_term = nd.term
        nd.voted = True

    # ----------------------------------------------------------------- rules
    def see_term(self, nd: Node, term: int, now: int):
        """All servers: a higher term makes a follower of it."""
        if term <= nd.term:
            return
        nd.term = term
        nd.voted = False
        nd.votes = 0
        if nd.role != FOLLOWER:
            nd.step_downs += 1
        if nd.role == LEADER:
            nd.next_hb = nd.proposal_at = -1
            nd.proposing = nd.open = False
            nd.acks = 0
            self.arm(nd, now)
        nd.role = FOLLOWER

    def on_election_timer(self, nd: Node, now: int):
        if nd.role == LEADER or nd.deadline != now:
            return                  # off, or re-armed since it was scheduled
        nd.term += 1
        nd.role = CANDIDATE
        self.vote(nd)
        nd.votes = 0
        nd.elections += 1
        crash = self.open_crash()
        if crash:
            crash[3] += 1
        self.arm(nd, now)
        for j in range(self.m):
            if j != nd.id:
                self.send(now, nd, j, "VOTE_REQ")

    def on_vote_req(self, nd: Node, now: int, term: int, src: int):
        granted = term == nd.term and not nd.voted
        if granted:
            self.vote(nd)
            self.arm(nd, now)
        self.send(now, nd, src, "VOTE_RES", granted)

    def on_vote_res(self, nd: Node, now: int, term: int, granted: bool):
        if nd.role != CANDIDATE or term != nd.term or not granted:
            return
        nd.votes += 1
        if nd.votes + 1 < self.need:
            return
        nd.role = LEADER
        nd.deadline = -1
        same = sum(1 for o in self.nodes
                   if o.role == LEADER and o.term == nd.term)
        self.leaders_of_one_term_max = max(self.leaders_of_one_term_max, same)
        if same > 1:
            nd.conflicts += 1
        if nd.first_win_ms < 0:
            nd.first_win_ms, nd.first_win_term = now, nd.term
        nd.last_win_ms = now
        crash = self.open_crash()
        if crash:
            crash[2] = now
        nd.next_hb = now
        nd.proposal_at = now + self.p["raft_proposal_delay_ms"]
        self.at(now, T_HEARTBEAT, nd.id)

    def on_heartbeat_timer(self, nd: Node, now: int):
        p = self.p
        if nd.role != LEADER or nd.next_hb != now:
            return
        if nd.blocks >= p["raft_max_blocks"]:
            nd.next_hb = -1         # the stop rule: silent from here on
            return
        if nd.proposal_at >= 0 and now >= nd.proposal_at:
            nd.proposing, nd.proposal_at = True, -1
        carries = nd.proposing
        if carries:
            nd.round += 1
            nd.acks, nd.open = 0, True
            if nd.round >= p["raft_max_rounds"]:
                nd.proposing = False
        if nd.term == nd.first_win_term:
            nd.last_hb_first_term = now
        for j in range(self.m):
            if j != nd.id:
                self.send(now, nd, j, "HEARTBEAT",
                          (nd.round, nd.id) if carries else None,
                          self.ser if carries else 0)
        nd.next_hb = now + p["raft_heartbeat_ms"]
        self.at(nd.next_hb, T_HEARTBEAT, nd.id)

    def on_heartbeat(self, nd: Node, now: int, term: int, src: int, payload):
        if term < nd.term:
            self.send(now, nd, src, "HEARTBEAT_RES", (False, None))
            return
        if nd.role == CANDIDATE:
            nd.step_downs += 1
        nd.role = FOLLOWER
        self.arm(nd, now)
        if payload is not None:
            nd.value = payload[1]
        self.send(now, nd, src, "HEARTBEAT_RES",
                  (True, payload[0] if payload is not None else None))

    def on_heartbeat_res(self, nd: Node, now: int, term: int, payload):
        ok, rnd = payload
        if (nd.role != LEADER or term != nd.term or not ok or rnd is None
                or rnd != nd.round or not nd.open):
            return
        nd.acks += 1
        if nd.acks + 1 >= self.need:
            nd.open = False
            if nd.blocks < self.p["raft_max_blocks"]:
                nd.block_ms.append(now)
            nd.blocks += 1

    # ------------------------------------------------------------------- run
    def run(self, sim_ms: int) -> dict:
        heap = self.heap
        while heap and heap[0][0] < sim_ms:
            now, order, _, dst, kind, term, src, payload = heapq.heappop(heap)
            if order == CRASH:
                self.on_crash(now)
                continue
            nd = self.nodes[dst]
            if order == RESTART:
                self.on_restart(nd, now)
            elif not nd.alive:
                pass                # lost: a message, or a timer of its past
            elif order == T_ELECTION:
                self.on_election_timer(nd, now)
            elif order == T_HEARTBEAT:
                self.on_heartbeat_timer(nd, now)
            else:
                self.see_term(nd, term, now)
                if kind == "VOTE_REQ":
                    self.on_vote_req(nd, now, term, src)
                elif kind == "VOTE_RES":
                    self.on_vote_res(nd, now, term, payload)
                elif kind == "HEARTBEAT":
                    self.on_heartbeat(nd, now, term, src, payload)
                else:
                    self.on_heartbeat_res(nd, now, term, payload)
        return self.metrics()

    def metrics(self) -> dict:
        nodes = self.nodes
        leaders = [nd for nd in nodes if nd.role == LEADER and nd.alive]
        lead = max(leaders, key=lambda nd: nd.term, default=None)
        term_final = max(nd.term for nd in nodes)
        led = [nd for nd in nodes if nd.first_win_ms >= 0]
        first = min(led, key=lambda nd: nd.first_win_ms, default=None)
        failover = -1.0
        if first is not None:
            later = [nd.first_win_ms for nd in led if nd is not first]
            if first.last_win_ms > first.first_win_ms:
                later.append(first.last_win_ms)
            if later and first.last_hb_first_term >= 0:
                failover = float(min(later) - first.last_hb_first_term)
        block_ms = sorted(t for nd in nodes for t in nd.block_ms)
        conflicts = sum(nd.conflicts for nd in nodes)
        stored = [nd.value for nd in nodes if nd.alive and nd.value >= 0]
        return {
            "n_leaders": len(leaders),
            "blocks": sum(min(nd.blocks, self.p["raft_max_blocks"])
                          for nd in nodes),
            "rounds": sum(nd.round for nd in nodes),
            "elections": sum(nd.elections for nd in nodes),
            "last_block_ms": float(block_ms[-1]) if block_ms else -1.0,
            "agreement_ok": conflicts == 0 and all(
                nodes[v].first_win_ms >= 0 and nodes[v].round > 0
                for v in stored),
            "term_final": term_final,
            "leader_term": lead.term if lead else 0,
            "n_leaders_term_final": sum(
                1 for nd in leaders if nd.term == term_final),
            "term_conflicts": conflicts,
            "step_downs": sum(nd.step_downs for nd in nodes),
            "first_leader_ms": float(first.first_win_ms) if first else -1.0,
            "first_leader_term": first.first_win_term if first else 0,
            "first_leader_blocks": first.blocks if first else 0,
            "failover_ms": failover,
            "leaders_of_one_term_max": self.leaders_of_one_term_max,
            **self.crash_metrics(),
        }

    def crash_metrics(self) -> dict:
        done = [c for c in self.crashes if c[1] >= 0 and c[2] >= 0]
        ms = sorted(float(c[2] - c[0]) for c in done)
        n = len(ms)
        rank = lambda i: ms[i] if n else -1.0   # noqa: E731
        out = {
            "crashes": len(self.crashes),
            "crashes_found_no_leader": sum(
                1 for c in self.crashes if c[1] < 0),
            "crashes_unreplaced": sum(
                1 for c in self.crashes if c[1] >= 0 and c[2] < 0),
            "failovers": n,
            "failovers_multi_election": sum(1 for c in done if c[3] > 1),
            "failover_mean_ms": sum(ms) / n if n else -1.0,
            "failover_median_ms": (rank((n - 1) // 2) + rank(n // 2)) / 2,
            "failover_p90_ms": rank(-(-9 * n // 10) - 1),
            "failover_max_ms": rank(n - 1),
            "restarts": sum(nd.restarts for nd in self.nodes),
            "dead_acts": self.dead_acts,
            "double_votes": sum(nd.double_votes for nd in self.nodes),
        }
        for k, c in enumerate(self.crashes):
            out[f"crash{k}_failover_ms"] = \
                float(c[2] - c[0]) if c[1] >= 0 and c[2] >= 0 else -1.0
            out[f"crash{k}_elections"] = c[3]
        return out


def run(fields: dict, seed: int, groups: int, sim_ms: int | None = None) -> dict:
    """``groups`` independent groups of this deployment's size, each on a
    stream of its own drawn from ``seed``: ``{"groups", "group_size",
    "sim_ms", "per_group": {key: [one a group]}}``."""
    p = {**UPSTREAM, **{k: v for k, v in fields.items() if k in UPSTREAM}}
    schedule = {**NO_SCHEDULE, **{k: v for k, v in fields.get(
        "faults", {}).items() if k in NO_SCHEDULE}}
    m = int(fields["n"]) // int(p["committees"])
    sim_ms = int(p["sim_ms"] if sim_ms is None else sim_ms)
    rows = [Group(p, m, random.Random(f"{seed}/{g}"), schedule).run(sim_ms)
            for g in range(groups)]
    # a crash scheduled past the end of the run never fell: it has no entry
    return {"groups": groups, "group_size": m, "sim_ms": sim_ms,
            "per_group": {k: [r.get(k, -1.0 if k.endswith("_ms") else 0)
                              for r in rows]
                          for k in keys(schedule["crashes"])}}


if __name__ == "__main__":
    import json
    import sys

    out = run(json.loads(sys.argv[1]) if len(sys.argv) > 1
              else {"n": 5, "model_serialization": False, "sim_ms": 5700,
                    "raft_heartbeat_ms": 75, "link_delay_ms": 7,
                    "faults": {"crashes": 4, "first_ms": 1000,
                               "period_ms": 1000, "downtime_ms": 500}},
              int(sys.argv[2]) if len(sys.argv) > 2 else 0,
              int(sys.argv[3]) if len(sys.argv) > 3 else 1000)
    pg = out.pop("per_group")
    print(json.dumps(out), {k: (min(v), sum(v) / len(v), max(v))
                            for k, v in pg.items()})
