// The plain reference for the mixed deployment (BASELINE config 5): S Raft
// groups of m nodes, each with its own random stream, and one PBFT instance
// over the S group representatives, of which representative s takes part at
// time t iff group s has an elected, live leader at t.
//
// Every message is one heap event with its own delay draw: a vote request to
// each peer, each vote reply, a heartbeat to each follower, each ack, every
// PRE_PREPARE, PREPARE, PREPARE_RES and COMMIT.  No tensors, no count
// channels, no short-circuited round trips, no heartbeat-blocked fast path.
// Nothing here is shared with the program under test or with engine.cpp; the
// upstream constants arrive as plain numbers from mixed_engine.py.
//
// Time is whole milliseconds, as upstream's timers and delays are.  Within
// one millisecond a node first takes in what arrived (heartbeats, then the
// vote request, then vote replies, then acks) and then runs its timers, and
// the counts of one millisecond are added before a threshold is tested.
//
// The coupling is one-way (a Raft group never hears of the PBFT layer), so
// the groups are simulated first, one at a time, each leaving the timeline
// of "has an elected, live leader"; the PBFT layer then asks that timeline
// at the time of every event.  Membership is simulated, not assumed.
//
// Departures from a literal per-message Raft, each of them the deployment's
// own stated semantics (its delivery and fidelity = "clean"):
//  - vote requests that meet in one millisecond.  Under delivery = "stat": of
//    the candidates whose election timers fire in one millisecond, the
//    highest id's vote request is the one sent, and of the requests that
//    reach one node in one millisecond the highest id's is the one seen.  A
//    literal upstream Raft has no terms and one vote latch, and at 1,024
//    nodes (7 timers per millisecond) it never elects: PERF.md section 6.
//    Under delivery = "edge" every candidate's request is sent, and a node
//    that several reach in one millisecond grants the lowest id's (if it
//    still can) and denies the rest.
//  - clean fidelity: a heartbeat re-arms the follower's election timer, and a
//    block commits once, as soon as the acks reach the majority.
//  - a group's simulation ends when its log is complete (upstream cancels
//    the heartbeat there); the election churn after that is no milestone.
//  - PBFT: clean fidelity (one commit per node and slot), no echo-back, view
//    changes as configured (the checks run it with none).
//
// Build: g++ -O2 -std=c++17 -shared -fPIC (mixed_engine.py); one extern "C"
// call, a flat struct in, JSON out.

#include <algorithm>
#include <cstdint>
#include <cstdio>
#include <queue>
#include <random>
#include <string>
#include <utility>
#include <vector>

namespace {

struct Cfg {  // field order is _Cfg's in mixed_engine.py
  int32_t shards, m, sim_ms;
  int64_t seed;
  int32_t raft_lo, raft_hi;  // one-way delay [lo, hi), link included
  int32_t raft_hb, raft_elo, raft_ehi, raft_prop_delay;
  int32_t raft_max_blocks, raft_max_rounds, raft_ser;
  int32_t n_crashed, n_byzantine;  // per group: the last ids, as the program
  int32_t pbft_lo, pbft_hi;
  int32_t pbft_interval, pbft_max_rounds, pbft_slots;
  int32_t pbft_vc_num, pbft_vc_den, pbft_ser;
  int32_t edge_ties;  // 1: delivery = "edge"'s rule for requests that meet
};

constexpr int32_t NEVER = 1 << 30;

struct Rng {
  std::mt19937_64 g;
  explicit Rng(uint64_t s) : g(s) {}
  int32_t in(int32_t lo, int32_t hi) {  // uniform in [lo, hi); hi <= lo -> lo
    if (hi <= lo) return lo;
    return lo + static_cast<int32_t>(g() % static_cast<uint64_t>(hi - lo));
  }
};

// (time, phase, seq): within one millisecond arrivals (0) come before a
// node's resolution of them (1), that before timers (2), those before the
// group's flush of this millisecond's candidates (3)
struct Ev {
  int32_t t, phase;
  int64_t seq;
  int32_t kind, node, a, b;
};
struct EvCmp {
  bool operator()(const Ev& x, const Ev& y) const {
    if (x.t != y.t) return x.t > y.t;
    if (x.phase != y.phase) return x.phase > y.phase;
    return x.seq > y.seq;
  }
};
using Heap = std::priority_queue<Ev, std::vector<Ev>, EvCmp>;

// ---------------------------------------------------------------------------
// one Raft group (raft-node.cc)
// ---------------------------------------------------------------------------
namespace raft {
enum { VOTE_REQ, VOTE_OK, VOTE_NO, HB_PLAIN, HB_PROP, ACK_OK, ACK_BAD,
       RESOLVE, T_ELECTION, T_HEARTBEAT, FLUSH };

struct Node {
  bool alive = true, honest = true, is_leader = false, has_voted = false;
  bool add_change = false, hb_open = false;
  int32_t deadline = NEVER, vote_ok = 0, vote_no = 0, next_hb = NEVER;
  int32_t proposal_at = NEVER, m_value = -1, block_num = 0, round = 0;
  int32_t hb_succ = 0, leader_tick = -1, last_block_tick = -1;
  // what arrived in the millisecond `staged_t`
  int32_t staged_t = -1, in_ok = 0, in_no = 0, in_hb = 0;
  int32_t in_prop = -1, in_ack_ok = 0;
  std::vector<int32_t> in_reqs;  // the candidates whose requests arrived
};

struct Group {
  const Cfg& c;
  Rng rng;
  Heap q;
  int64_t seq = 0, events = 0;
  int32_t now = 0;
  bool done = false;
  std::vector<int32_t> fired;  // this millisecond's candidates
  std::vector<Node> nodes;
  std::vector<std::pair<int32_t, bool>> timeline;  // (t, has a live leader)

  Group(const Cfg& cfg, int32_t shard)
      : c(cfg), rng(static_cast<uint64_t>(cfg.seed) * 0x9E3779B97F4A7C15ull +
                    static_cast<uint64_t>(shard) + 1) {
    nodes.resize(c.m);
    for (int32_t i = 0; i < c.m; ++i) {
      Node& nd = nodes[i];
      nd.alive = i < c.m - c.n_crashed;
      nd.honest = i < c.m - c.n_crashed - c.n_byzantine;
      if (nd.alive) arm(i, rng.in(c.raft_elo, c.raft_ehi));
    }
  }

  void push(int32_t t, int32_t phase, int32_t kind, int32_t node,
            int32_t a = 0, int32_t b = 0) {
    q.push(Ev{t, phase, seq++, kind, node, a, b});
  }
  int32_t delay() { return rng.in(c.raft_lo, c.raft_hi); }
  void arm(int32_t i, int32_t at) {
    nodes[i].deadline = at;
    push(at, 2, T_ELECTION, i);
  }
  bool has_leader() const {
    for (const Node& nd : nodes)
      if (nd.is_leader && nd.alive) return true;
    return false;
  }

  void stage(int32_t i, const Ev& e) {
    Node& nd = nodes[i];
    if (!nd.alive) return;  // a crashed node takes nothing in
    if (nd.staged_t != now) {
      nd.staged_t = now;
      nd.in_prop = -1;
      nd.in_ok = nd.in_no = nd.in_hb = nd.in_ack_ok = 0;
      nd.in_reqs.clear();
      push(now, 1, RESOLVE, i);
    }
    switch (e.kind) {
      case VOTE_REQ: nd.in_reqs.push_back(e.a); break;
      case VOTE_OK: nd.in_ok++; break;
      case VOTE_NO: nd.in_no++; break;
      case HB_PLAIN: nd.in_hb++; break;
      case HB_PROP: nd.in_prop = std::max(nd.in_prop, e.a); break;
      case ACK_OK: nd.in_ack_ok++; break;
      case ACK_BAD: break;  // counted by nothing in clean fidelity
    }
  }

  void resolve(int32_t i) {
    Node& nd = nodes[i];
    const int32_t need = c.m / 2 + 1, lose_need = c.m / 2;
    // heartbeats (raft-node.cc:170-193): store the value, re-arm the timer,
    // ack a proposal (a Byzantine follower acks FAILED)
    if (nd.in_hb > 0 || nd.in_prop >= 0) {
      if (nd.in_prop >= 0) {
        nd.m_value = nd.in_prop;
        push(now + delay(), 0, nd.honest ? ACK_OK : ACK_BAD, nd.in_prop);
      }
      arm(i, now + rng.in(c.raft_elo, c.raft_ehi));
    }
    // vote requests (raft-node.cc:154-167): grant iff not yet voted; a
    // Byzantine node answers the opposite of what it did.  Of requests that
    // meet here, stat sees the highest id's alone; edge answers them all,
    // the lowest id's first
    if (!nd.in_reqs.empty()) {
      std::sort(nd.in_reqs.begin(), nd.in_reqs.end());
      if (!c.edge_ties) nd.in_reqs.erase(nd.in_reqs.begin(), nd.in_reqs.end() - 1);
      for (int32_t cand : nd.in_reqs) {
        if (cand == i) continue;
        bool grant = !nd.has_voted;
        nd.has_voted = true;
        push(now + delay(), 0, grant == nd.honest ? VOTE_OK : VOTE_NO, cand);
      }
    }
    // vote replies (raft-node.cc:196-232)
    if (!nd.is_leader && (nd.in_ok > 0 || nd.in_no > 0)) {
      nd.vote_ok += nd.in_ok;
      nd.vote_no += nd.in_no;
      bool win = nd.in_ok > 0 && nd.vote_ok + 1 >= need;
      bool lose = !win && nd.in_no > 0 && nd.vote_no >= lose_need;
      if (win || lose) nd.vote_ok = nd.vote_no = 0;
      if (win) {
        bool before = has_leader();
        nd.is_leader = true;
        nd.deadline = NEVER;
        nd.next_hb = now;  // the first heartbeat goes out at once
        push(now, 2, T_HEARTBEAT, i);
        nd.proposal_at = now + c.raft_prop_delay;
        if (nd.leader_tick < 0) nd.leader_tick = now;
        if (!before) timeline.emplace_back(now, true);
      }
      if (lose) nd.has_voted = false;  // retry on the re-armed timer
    }
    // acks (raft-node.cc:234-251), clean: commit once at the majority
    if (nd.is_leader && nd.in_ack_ok > 0) {
      nd.hb_succ += nd.in_ack_ok;
      if (nd.hb_open && nd.hb_succ + 1 >= need) {
        nd.hb_open = false;
        nd.last_block_tick = now;
        if (++nd.block_num >= c.raft_max_blocks) {
          nd.next_hb = NEVER;  // raft-node.cc:248-251
          done = true;
        }
      }
    }
  }

  void election_timer(int32_t i) {
    Node& nd = nodes[i];
    if (nd.deadline != now || nd.is_leader || !nd.alive) return;  // stale
    nd.has_voted = true;  // the self-vote latch (raft-node.cc:392-401)
    arm(i, now + rng.in(c.raft_elo, c.raft_ehi));
    if (fired.empty()) push(now, 3, FLUSH, 0);
    fired.push_back(i);
  }

  void flush() {  // this millisecond's candidates send their requests
    if (!c.edge_ties)  // stat: the highest id's is the one sent
      fired.assign(1, *std::max_element(fired.begin(), fired.end()));
    for (int32_t cand : fired)
      for (int32_t j = 0; j < c.m; ++j)
        if (j != cand) push(now + delay(), 0, VOTE_REQ, j, cand);
    fired.clear();
  }

  void heartbeat_timer(int32_t i) {  // sendHeartBeat (raft-node.cc:405-433)
    Node& nd = nodes[i];
    if (!nd.is_leader || !nd.alive || nd.next_hb != now) return;
    if (now >= nd.proposal_at) {  // setProposal fires once
      nd.add_change = true;
      nd.proposal_at = NEVER;
    }
    bool prop = nd.add_change;
    nd.next_hb = now + c.raft_hb;
    push(nd.next_hb, 2, T_HEARTBEAT, i);
    if (prop) {  // SendTX (raft-node.cc:340-365): opens the ack window
      if (++nd.round >= c.raft_max_rounds) nd.add_change = false;
      nd.hb_succ = 0;
      nd.hb_open = true;
    }
    for (int32_t j = 0; j < c.m; ++j)
      if (j != i)
        push(now + delay() + (prop ? c.raft_ser : 0), 0,
             prop ? HB_PROP : HB_PLAIN, j, i);
  }

  void run() {
    while (!q.empty() && !done) {
      Ev e = q.top();
      if (e.t >= c.sim_ms) break;
      q.pop();
      now = e.t;
      events++;
      switch (e.kind) {
        case RESOLVE: resolve(e.node); break;
        case T_ELECTION: election_timer(e.node); break;
        case T_HEARTBEAT: heartbeat_timer(e.node); break;
        case FLUSH: flush(); break;
        default: stage(e.node, e);
      }
    }
  }
};
}  // namespace raft

// membership of the representatives, from the groups' timelines
struct Membership {
  std::vector<std::vector<std::pair<int32_t, bool>>> tl;
  bool alive(int32_t s, int32_t t) const {
    bool on = false;
    for (const auto& p : tl[s]) {
      if (p.first > t) break;
      on = p.second;
    }
    return on;
  }
};

// ---------------------------------------------------------------------------
// PBFT over the representatives (pbft-node.cc)
// ---------------------------------------------------------------------------
namespace pbft {
enum { PRE_PREPARE, PREPARE, PREPARE_RES, COMMIT, VIEW_CHANGE, T_SENDBLOCK };

struct Node {
  int32_t v = 1, leader = 0, next_n = 0, view_changes = 0;
  std::vector<int32_t> val, prepare_vote, commit_vote, commit_tick;
  std::vector<uint8_t> prep_sent, committed;
};

struct Engine {
  const Cfg& c;
  const Membership& mem;
  Rng rng;
  Heap q;
  int64_t seq = 0, events = 0;
  int32_t now = 0;
  std::vector<Node> nodes;
  std::vector<int32_t> propose_tick;

  Engine(const Cfg& cfg, const Membership& m)
      : c(cfg), mem(m),
        rng(static_cast<uint64_t>(cfg.seed) * 0xD1B54A32D192ED03ull + 7) {
    nodes.resize(c.shards);
    propose_tick.assign(c.pbft_slots, -1);
    for (int32_t i = 0; i < c.shards; ++i) {
      Node& nd = nodes[i];
      nd.val.assign(c.pbft_slots, -1);
      nd.prepare_vote.assign(c.pbft_slots, 0);
      nd.commit_vote.assign(c.pbft_slots, 0);
      nd.commit_tick.assign(c.pbft_slots, -1);
      nd.prep_sent.assign(c.pbft_slots, 0);
      nd.committed.assign(c.pbft_slots, 0);
      push(c.pbft_interval, 2, T_SENDBLOCK, i);  // pbft-node.cc:155,406
    }
  }

  void push(int32_t t, int32_t phase, int32_t kind, int32_t node,
            int32_t a = 0, int32_t b = 0) {
    q.push(Ev{t, phase, seq++, kind, node, a, b});
  }
  void bcast(int32_t from, int32_t kind, int32_t a, int32_t extra = 0) {
    for (int32_t to = 0; to < c.shards; ++to)
      if (to != from)
        push(now + rng.in(c.pbft_lo, c.pbft_hi) + extra, 0, kind, to, a, from);
  }

  void send_block(int32_t i) {  // SendBlock (pbft-node.cc:372-411)
    Node& nd = nodes[i];
    push(now + c.pbft_interval, 2, T_SENDBLOCK, i);
    if (!mem.alive(i, now) || nd.leader != i ||
        nd.next_n >= std::min(c.pbft_max_rounds, c.pbft_slots))
      return;
    bcast(i, PRE_PREPARE, nd.next_n, c.pbft_ser);
    if (propose_tick[nd.next_n] < 0) propose_tick[nd.next_n] = now;
    nd.next_n++;
    if (rng.in(0, c.pbft_vc_den) < c.pbft_vc_num) {  // pbft-node.cc:401-403
      nd.v++;
      nd.leader = (nd.leader + 1) % c.shards;
      nd.view_changes++;
      bcast(i, VIEW_CHANGE, nd.v * c.shards + nd.leader);
    }
  }

  void on_msg(const Ev& e) {
    int32_t i = e.node, quorum = c.shards / 2;
    if (!mem.alive(i, now)) return;  // no leader, no part in the quorum
    Node& nd = nodes[i];
    int32_t slot = e.a;
    switch (e.kind) {
      case PRE_PREPARE:  // pbft-node.cc:193-211
        nd.val[slot] = slot;
        nd.next_n = std::max(nd.next_n, slot + 1);
        bcast(i, PREPARE, slot);
        break;
      case PREPARE:  // the unconditional SUCCESS reply (pbft-node.cc:212-221)
        push(now + rng.in(c.pbft_lo, c.pbft_hi), 0, PREPARE_RES, e.b, slot, i);
        break;
      case PREPARE_RES:  // pbft-node.cc:223-240
        if (++nd.prepare_vote[slot] >= quorum && !nd.prep_sent[slot]) {
          nd.prep_sent[slot] = 1;
          nd.prepare_vote[slot] = 0;
          bcast(i, COMMIT, slot);
        }
        break;
      case COMMIT:  // pbft-node.cc:241-265: the finality measurement point
        if (++nd.commit_vote[slot] > quorum && !nd.committed[slot]) {
          nd.commit_vote[slot] = 0;
          nd.committed[slot] = 1;
          nd.commit_tick[slot] = now;
        }
        break;
      case VIEW_CHANGE:  // pbft-node.cc:271-280
        nd.v = e.a / c.shards;
        nd.leader = e.a % c.shards;
        break;
    }
  }

  void run() {
    while (!q.empty()) {
      Ev e = q.top();
      if (e.t >= c.sim_ms) break;
      q.pop();
      now = e.t;
      events++;
      if (e.kind == T_SENDBLOCK) send_block(e.node);
      else on_msg(e);
    }
  }
};
}  // namespace pbft

std::string run_all(const Cfg& c) {
  Membership mem;
  int32_t with_leader = 0, elected_max = -1, elected_min = NEVER;
  int32_t blocks_min = NEVER, tail_max = -1, tail_min = NEVER;
  int64_t blocks_total = 0, events = 0;
  bool agree = true;
  for (int32_t s = 0; s < c.shards; ++s) {
    raft::Group g(c, s);
    g.run();
    events += g.events;
    mem.tl.push_back(g.timeline);
    // "the" leader: the earliest elected live one, as the program reports it
    const raft::Node* lead = nullptr;
    for (const raft::Node& nd : g.nodes)
      if (nd.is_leader && nd.alive &&
          (!lead || nd.leader_tick < lead->leader_tick))
        lead = &nd;
    if (!lead) continue;
    int32_t id = static_cast<int32_t>(lead - g.nodes.data());
    with_leader++;
    elected_max = std::max(elected_max, lead->leader_tick);
    elected_min = std::min(elected_min, lead->leader_tick);
    blocks_min = std::min(blocks_min, lead->block_num);
    blocks_total += lead->block_num;
    if (lead->block_num > 0) {
      int32_t tail = lead->last_block_tick - lead->leader_tick;
      tail_max = std::max(tail_max, tail);
      tail_min = std::min(tail_min, tail);
    }
    for (const raft::Node& nd : g.nodes)
      if (nd.alive && nd.m_value >= 0 && nd.m_value != id) agree = false;
  }
  pbft::Engine p(c, mem);
  p.run();
  events += p.events;
  int32_t n_alive = 0, rounds = 0, vcs = 0;
  for (int32_t i = 0; i < c.shards; ++i) {
    n_alive += mem.alive(i, c.sim_ms - 1);
    rounds = std::max(rounds, p.nodes[i].next_n);
    vcs += p.nodes[i].view_changes;
  }
  // a slot is final when as many representatives finalized it as are alive
  // at the end (one may finalize and then lose its leader)
  int32_t final_all = 0, last = -1, first_propose = -1;
  double ttf_sum = 0;
  for (int32_t s = 0; s < std::min(rounds, c.pbft_slots); ++s) {
    int32_t n_done = 0, mx = -1;
    for (const pbft::Node& nd : p.nodes)
      if (nd.committed[s]) {
        n_done++;
        mx = std::max(mx, nd.commit_tick[s]);
      }
    if (p.propose_tick[s] >= 0 &&
        (first_propose < 0 || p.propose_tick[s] < first_propose))
      first_propose = p.propose_tick[s];
    if (n_alive > 0 && n_done >= n_alive && p.propose_tick[s] >= 0) {
      final_all++;
      ttf_sum += mx - p.propose_tick[s];
      last = std::max(last, mx);
    }
  }
  char buf[1024];
  std::snprintf(
      buf, sizeof(buf),
      "{\"shards\": %d, \"shard_size\": %d, \"shards_with_leader\": %d, "
      "\"leader_elected_ms_max\": %.1f, \"leader_elected_ms_min\": %.1f, "
      "\"raft_blocks_min\": %d, \"raft_blocks_total\": %lld, "
      "\"raft_commit_tail_ms_max\": %.1f, \"raft_commit_tail_ms_min\": %.1f, "
      "\"global_rounds_sent\": %d, \"global_blocks_final\": %d, "
      "\"global_mean_ttf_ms\": %.6g, \"global_first_propose_ms\": %.1f, "
      "\"global_last_commit_ms\": %.1f, \"global_view_changes\": %d, "
      "\"agreement_ok\": %s, \"events\": %lld}",
      c.shards, c.m, with_leader, static_cast<double>(elected_max),
      with_leader ? static_cast<double>(elected_min) : -1.0,
      with_leader ? blocks_min : 0, static_cast<long long>(blocks_total),
      static_cast<double>(tail_max),
      tail_max >= 0 ? static_cast<double>(tail_min) : -1.0, rounds, final_all,
      final_all ? ttf_sum / final_all : -1.0,
      static_cast<double>(first_propose), static_cast<double>(last), vcs,
      agree ? "true" : "false", static_cast<long long>(events));
  return buf;
}

}  // namespace

extern "C" int run_mixed(const Cfg* cfg, char* out, int out_len) {
  if (!cfg || cfg->shards < 1 || cfg->m < 3 || cfg->pbft_slots < 1 ||
      cfg->raft_lo < 1 || cfg->pbft_lo < 1)
    return 2;
  std::string s = run_all(*cfg);
  if (static_cast<int>(s.size()) + 1 > out_len) return 3;
  std::copy(s.begin(), s.end(), out);
  out[s.size()] = '\0';
  return 0;
}
