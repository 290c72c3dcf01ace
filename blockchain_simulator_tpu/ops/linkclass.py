"""Link classes: a one-way delay per pair of node classes (ROADMAP R4).

``SimConfig.link_classes`` puts the nodes into K contiguous classes (a rack,
a region, a continent) and ``link_class_delay_ms`` gives every ordered pair
of classes its one-way propagation.  A message from ``i`` to ``j`` then
arrives ``matrix[class(i)][class(j)]`` + the protocol's jitter draw (+ a
block's serialization) after it was sent.  With region pairs 9 to 350 ms
apart a channel's delays span hundreds of ticks, and the delivery layer's
bucket axis (``ops/delivery._edge_hits``: one indicator plane, one ring
update and one ``due`` mark per delay value) cannot be widened to hold them.

So the span is taken out on the SENDER's side, by a **delay line**: a short
history ``[T, N, ...]`` of what every node sent on a channel, one slot a
tick.  The matrix is split into what every message pays
(``SimConfig.link_base_ms``, its smallest entry, which stays in
``one_way_range``) and what a class pair adds to that (:class:`Plan`
``offsets``).  On tick ``t`` the receivers of class ``k`` read, for every
sender class ``a``, the rows of class ``a`` out of the slot written
``offset[a][k]`` ticks ago (:func:`line_get`): what "is sent now" as class
``k`` sees it.  From there on the delivery is today's: one jitter draw an
edge over the protocol's three values, one contiguous push into the ring.
Nothing grows with the delay span but the line's depth (and no ring does:
``ring_depth`` stays that of one scalar latency); a read is K x K row blocks
out of the slots the DISTINCT offsets name (21 for six regions), and the
delivery arms run their receivers class by class
(``ops/delivery.*_classed``).

A round trip (PBFT's PREPARE and its short-circuited replies) has the sum of
both directions as its offset (:func:`roundtrip_plan`), and reads the
sender's own line: the replies of peer class ``k`` to a sender of class ``a``
"start now" ``offset[a][k] + offset[k][a]`` ticks after its broadcast.

A slot holds what was written on the tick it stands for only if that tick
ran: the tick engines skip quiet ticks (``models/pbft.step``'s gate), and a
skipped tick writes nothing.  So a line keeps one bit a slot, ``sent``: set
by :func:`line_put` when the tick's value holds anything, cleared by
:func:`line_clear` on every tick, taken or not.  A read masks by it, and
:func:`line_any` (the predicate of the push gates and of the tick gate) reads
nothing else.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
from flax import struct
from jax.experimental.layout import Layout, with_layout_constraint

from blockchain_simulator_tpu.ops import scopes
from blockchain_simulator_tpu.ops.ring import node_minor

_names: list = []
_scoped = scopes.scoped("ops.linkclass", _names)


@dataclasses.dataclass(frozen=True)
class Plan:
    """The static shape of one channel's delay line: the classes' row ranges,
    the distinct offsets a class pair adds to the base propagation, and for
    every (sender class, reader class) the index of its offset."""

    bounds: tuple   # K x (first row, one past the last) of each class
    offsets: tuple  # distinct offsets in ticks, ascending
    index: tuple    # K x K into ``offsets``: [sender class][reader class]

    @property
    def depth(self) -> int:
        """Slots of the line: the longest offset and the tick itself."""
        return self.offsets[-1] + 1


def _plan(cfg, added) -> Plan:
    counts = cfg.link_classes
    ends = list(itertools.accumulate(counts))
    offsets = sorted({d for row in added for d in row})
    return Plan(
        bounds=tuple((e - c, e) for c, e in zip(counts, ends)),
        offsets=tuple(offsets),
        index=tuple(tuple(offsets.index(d) for d in row) for row in added),
    )


@functools.lru_cache(maxsize=64)
def one_way_plan(cfg) -> Plan:
    """A broadcast's line: what the pair (sender class, receiver class) adds
    to ``cfg.link_base_ms``."""
    base = cfg.link_base_ms
    return _plan(cfg, [[d - base for d in row]
                       for row in cfg.link_class_delay_ms])


@functools.lru_cache(maxsize=64)
def roundtrip_plan(cfg) -> Plan:
    """A request and its reply: [sender class][peer class], the sum of both
    directions' additions."""
    m, base = cfg.link_class_delay_ms, cfg.link_base_ms
    k = len(m)
    return _plan(cfg, [[m[a][b] + m[b][a] - 2 * base for b in range(k)]
                       for a in range(k)])


@struct.dataclass
class DelayLine:
    buf: jax.Array   # [T, N, ...] what each node sent, a slot a tick
    sent: jax.Array  # [T] bool: the slot holds something of its own tick


def line_init(plan: Plan, shape, dtype) -> DelayLine:
    return DelayLine(buf=jnp.zeros((plan.depth, *shape), dtype),
                     sent=jnp.zeros((plan.depth,), bool))


def _pin(buf):
    """One physical layout for a line wherever an op touches it, as every
    ring op asks for one (``ops/ring.node_minor``, whose rule a ``[T, N, W]``
    line takes): slot major-most, nodes minor-most.  The tick's body and
    each push gate's loop are laid out in isolation; left to choose, XLA:TPU
    gave the PREPARE line one order in the body and another in its gate's
    carry, and copied all of it from the one to the other on every taken
    tick (1.2 GB at 128 lanes: PERF.md section 6, PR 51)."""
    if buf.ndim == 2:
        return with_layout_constraint(buf, Layout(major_to_minor=(0, 1)))
    return node_minor(buf)


def _slot(line: DelayLine, t, back: int = 0):
    return jnp.mod(t - back, line.sent.shape[0])


def line_clear(line: DelayLine, t) -> DelayLine:
    """Tick ``t`` begins: its slot holds nothing yet.  Runs on every tick,
    beside the clearing of the rings' due bits."""
    now = jnp.arange(line.sent.shape[0]) == _slot(line, t)
    return line.replace(sent=line.sent & ~now)


@_scoped
def line_put(line: DelayLine, t, value) -> DelayLine:
    """Write what the nodes send on tick ``t`` (``value [N, ...]``, zero or
    False where a node sends nothing) into the tick's slot, once a tick."""
    idx = _slot(line, t)
    buf = jax.lax.dynamic_update_index_in_dim(
        _pin(line.buf), value.astype(line.buf.dtype), idx, 0)
    sent = jax.lax.dynamic_update_index_in_dim(
        line.sent, (value != 0).any(), idx, 0)
    return DelayLine(buf=buf, sent=sent)


def line_any(line: DelayLine, t, plan: Plan):
    """Whether any slot a reader of tick ``t`` looks at holds something: a
    scalar bool, from the ``sent`` bits alone."""
    depth = line.sent.shape[0]
    # slot s was written (t - s) mod T ticks ago, and is read now if that is
    # one of the offsets: the static mask "s ticks AHEAD of slot 0 is read",
    # rolled to where slot t is (a dynamic slice, not a gather)
    ahead = np.zeros((depth,), bool)
    ahead[[-back % depth for back in plan.offsets]] = True
    return (line.sent & jnp.roll(jnp.asarray(ahead), jnp.mod(t, depth))).any()


@_scoped
def line_get(line: DelayLine, t, plan: Plan):
    """``[K, N, ...]``: entry ``[k, i]`` is what node ``i`` sent
    ``offset[class(i)][k]`` ticks ago, i.e. what reaches the readers of
    class ``k`` from ``i`` with the base propagation still to go; zero where
    that tick wrote nothing.  A read is K x K row blocks, each the rows of
    one sender class out of the slot its pair's offset names (K x N rows in
    all; the slots it touches are one a distinct offset)."""
    where = [_slot(line, t, back) for back in plan.offsets]
    ok = [jax.lax.dynamic_index_in_dim(line.sent, idx, 0, keepdims=False)
          for idx in where]
    buf = _pin(line.buf)
    rest = buf.shape[2:]

    def rows(o, lo, hi):
        # the rows of one sender class out of one slot, nothing else of it
        cur = jax.lax.dynamic_slice(
            buf, (where[o], lo, *(0 for _ in rest)), (1, hi - lo, *rest))[0]
        return jnp.where(ok[o], cur, jnp.zeros_like(cur))

    return jnp.stack([
        jnp.concatenate([rows(row[k], lo, hi)
                         for row, (lo, hi) in zip(plan.index, plan.bounds)])
        for k in range(len(plan.bounds))
    ])


def pack_bits(flags):
    """``[..., W]`` bool -> ``[..., ceil(W / 32)]`` uint32, bit ``b`` of word
    ``j`` the flag ``32 j + b``: how a line keeps a row of flags (PBFT's
    PREPARE broadcasts, a bit a window).  A line of bools was 8 times the
    bytes, and XLA:TPU read a slot of it back through a transposed copy of
    the whole line on every tick (PERF.md section 6, PR 51)."""
    w = flags.shape[-1]
    pad = -w % 32
    bits = jnp.pad(flags.astype(jnp.uint32),
                   [(0, 0)] * (flags.ndim - 1) + [(0, pad)])
    bits = bits.reshape(*flags.shape[:-1], (w + pad) // 32, 32)
    return (bits << jnp.arange(32, dtype=jnp.uint32)).sum(-1, dtype=jnp.uint32)


def unpack_bits(words, w: int):
    """:func:`pack_bits` undone: ``[..., ceil(w / 32)]`` uint32 ->
    ``[..., w]`` bool."""
    bits = (words[..., None] >> jnp.arange(32, dtype=jnp.uint32)) & 1
    return bits.reshape(*words.shape[:-1], -1)[..., :w].astype(bool)


# what the classed programs traced so far hold, counted where a program is
# traced: plain Python, never a traced value (as ops/ring.lane_pinned).  The
# program builders move it to the ``linkclass.*`` counters
# (utils/aotcache.py)
traced = {"programs": 0, "classes": 0, "offsets": 0, "ring_depth": 0,
          "lane_state_bytes": 0}


def note_traced(cfg, state_and_bufs) -> None:
    """A classed program's ``init`` was traced: its classes, the distinct
    offsets of its one-way line, its ring depth and the bytes of state one
    lane carries (elements times item size)."""
    traced["programs"] += 1
    traced["classes"] += len(cfg.link_classes)
    traced["offsets"] += len(one_way_plan(cfg).offsets)
    traced["ring_depth"] += cfg.ring_depth
    traced["lane_state_bytes"] += sum(
        x.size * x.dtype.itemsize for x in jax.tree.leaves(state_and_bufs))


def check_arms(cfg, engine: str = "jax") -> None:
    """Link classes run on per-edge PBFT on the full mesh, flat and under
    the lane batch; every other arm refuses them by its name rather than
    running on with one scalar latency.  The one place the arms are listed:
    ``models/pbft.init`` asks it, runner.py's validation before anything is
    built, and engine.run_cpp (``engine="cpp"``)."""
    if not cfg.link_classes:
        return
    arms = (
        (engine == "cpp", "the C++ engine (--engine cpp)",
         "engine.cpp gives every channel one Delay; the per-message "
         "reference with classes is benchmark/reference/pbft_geo_engine.py"),
        (cfg.protocol != "pbft", f"protocol={cfg.protocol!r}",
         "Raft's, Paxos' and the mixed sim's channels have no delay lines "
         "(ROADMAP R4's remainder)"),
        (cfg.fidelity != "clean", f"fidelity={cfg.fidelity!r}",
         "upstream has one channel Delay, so there is no reference to hold "
         "its quirks to under classes"),
        (cfg.topology != "full", f"topology={cfg.topology!r}",
         "the gossip, kregular and committee arms deliver over their own "
         "tables and tiers (committees as regions: ROADMAP R4's remainder)"),
        (cfg.delivery == "stat", "delivery='stat'",
         "its bucket counts are drawn for all senders at once and need a "
         "probability vector per class pair (ROADMAP R4's stat half)"),
        (cfg.schedule == "round", "schedule='round'",
         "models/pbft_round closes a message wave inside one block "
         "interval, which no ocean does"),
        (cfg.queued_links, "queued_links",
         "the serial-pipe registers follow one leader's links with one "
         "propagation term"),
        (cfg.mesh_axis is not None, "a mesh axis",
         "the delay lines and the class-by-class delivery read a cluster's "
         "nodes on one device"),
        (0 < cfg.pbft_window < cfg.pbft_max_slots,
         f"pbft_window={cfg.pbft_window}",
         "a window stands on a slot's PRE_PREPARE landing before its first "
         "COMMIT vote, which unlike links do not keep"),
    )
    for refused, arm, why in arms:
        if refused:
            raise NotImplementedError(
                f"link classes (link_classes={cfg.link_classes}) are not "
                f"implemented for {arm}: {why}; they run on protocol='pbft' "
                "with delivery='edge', topology='full', fidelity='clean' "
                "(ops/linkclass.check_arms)")


# every scope above, by name (ops/scopes.py)
SCOPES = tuple(_names)
