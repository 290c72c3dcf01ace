"""Future-inbox ring buffers.

The reference's entire concurrency model is the ns-3 event queue: every send is
``Simulator::Schedule(delay, SendPacket, ...)`` (pbft-node.cc:345,365; SURVEY.md
§3.5).  The tensorized equivalent is a ring buffer over future ticks: a channel
buffer has shape ``[D, N, ...]``; a message scheduled at tick ``t`` with delay
``d`` lands in slice ``(t + d) % D``; at tick ``t`` the simulator *pops* slice
``t % D`` (read + zero).  ``D`` need only exceed the maximum scheduling horizon
(config.ring_depth), so memory is O(D·N·channel-width) — never O(events).

Channels come in two flavors (SURVEY.md §7 "variable-size inboxes"):
- **aggregate** channels combine concurrent deliveries with a commutative op
  (add for vote counts, max for value announcements) — exploiting that the
  protocols consume most messages as counts;
- **matrix** channels keep sender identity ``[D, N_recv, N_send]`` for the few
  request types whose replies must be routed back to the requester.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap
from jax.experimental.layout import Layout, with_layout_constraint

from blockchain_simulator_tpu.ops import scopes

_names: list = []
_scoped = scopes.scoped("ops.ring", _names)


# a TPU tile's lane width: a minor dimension narrower than this is padded to it
_LANES = 128


# ring values pinned by the lane rule (:func:`node_minor`) so far, counted
# where a program is traced: plain Python, never a traced value.  The program
# builders move it to the ``ring.lane_pinned`` counter (utils/aotcache.py)
lane_pinned = [0]


@functools.cache
def _lane_pin(lanes: int):
    """The identity on a value with ``lanes`` leading lane axes, constrained
    to the physical order "its own axes, then the lanes": slot major-most,
    lanes minor-most.  A plain ``with_layout_constraint`` under ``vmap``
    makes the batch axis major-most (``jax/_src/pjit.get_layout_for_vmap``),
    which pads a ring's 5 nodes to 128; a ``custom_vmap`` sees the batch
    axis arrive (at the front, one a ``vmap`` around it) and places it."""

    @custom_vmap
    def pin(x):
        if not lanes:
            return x
        order = (*range(lanes, x.ndim), *range(lanes))
        return with_layout_constraint(x, Layout(major_to_minor=order))

    @pin.def_vmap
    def _(axis_size, in_batched, x):
        return _lane_pin(lanes + 1)(x), True

    return pin


def _lane_batched() -> bool:
    # models/base.py imports this package
    from blockchain_simulator_tpu.models.base import lane_axes

    return bool(lane_axes())


def node_minor(buf):
    """Pin a ``[D, N, W]`` ring whose rows are narrower than a lane tile to
    the node-minor layout.  XLA:TPU picks that layout itself where it sees
    the whole tick, but lays out a computation called from control flow (the
    gate of models/base.gated_push) in isolation, where it fell back to the
    W-minor default for one ring: twice the bytes (W = 64 padded to 128) and
    a transposing copy of the whole ring on every tick (PERF.md section 6,
    PR 31).  With every ring op asking for the one layout, none is left to
    guess.

    A ring ``[D, N]`` or ``[D, N, W]`` of fewer nodes than a lane tile has
    no axis of its own to fill one: under a lane batch (models/base.lane_axes)
    the lanes do, and the ring is pinned slot-major, lane-minor
    (:func:`_lane_pin`).  Left alone XLA:TPU put the slot axis second-minor
    in five of a multi-Raft stack's seven rings, a slot one sublane row of
    every tile, and every pop copied the whole ring to the slot-major order
    on every tick (PERF.md section 6, PR 50).  A lone program binds no lane
    axis and keeps its text."""
    if buf.ndim == 3 and buf.shape[2] < _LANES <= buf.shape[1]:
        return with_layout_constraint(buf, Layout(major_to_minor=(0, 2, 1)))
    if buf.ndim in (2, 3) and buf.shape[1] < _LANES and _lane_batched():
        lane_pinned[0] += 1
        return _lane_pin(0)(buf)
    return buf


@_scoped
def ring_pop(buf, t):
    """Read and clear the current tick's slice. Returns (slice, buf').

    The barrier makes the slice a value of its own before the ring goes on:
    a consumer that fused the read of the OLD ring into itself, phases later,
    kept the old ring alive across the in-place pushes and cost a copy of
    the whole ring on every tick (PERF.md section 6, PR 31)."""
    idx = jnp.mod(t, buf.shape[0])
    buf = node_minor(buf)
    cur = jax.lax.dynamic_index_in_dim(buf, idx, 0, keepdims=False)
    return jax.lax.optimization_barrier((cur, jax.lax.dynamic_update_index_in_dim(
        buf, jnp.zeros_like(cur), idx, 0
    )))


def _push(buf, t, lo: int, contrib, op: str):
    """Combine ``contrib[b, ...]`` into slices ``t+lo+b``, b in [0, B).

    A DUS chain: unrolled dynamic-slice / dynamic-update-slice pairs over
    the (small, static) bucket axis.  A ``buf.at[idx_vec].add`` would lower
    to XLA generic scatter, which TPUs execute catastrophically slowly —
    the round-3 ablation (tools/ablate.py) measured the scatter form ~30x
    slower than this chain.

    Every bucket read-modify-writes one slice of the ring whatever
    ``contrib`` holds, so a call site whose contribution comes out of a gate
    pushes through models/base.gated_push, inside the gate's branch, and a
    tick with no sender touches no slice (sharded over a mesh axis too: the
    chain holds no collective, so it goes into the gate's loop while the
    contribution stays in its ``conditional``).  A ring is never the ``zeros`` of
    models/base.gated: that is an operand of a select and of a
    ``conditional``, and either costs passes over the whole ring.
    """
    combine = jnp.add if op == "add" else jnp.maximum
    d = buf.shape[0]
    buf = node_minor(buf)
    for b in range(contrib.shape[0]):
        idx = jnp.mod(t + lo + b, d)
        cur = jax.lax.dynamic_index_in_dim(buf, idx, 0, keepdims=False)
        buf = jax.lax.dynamic_update_index_in_dim(buf, combine(cur, contrib[b]), idx, 0)
    return buf


@_scoped
def ring_push_add(buf, t, lo: int, contrib):
    """Add ``contrib[b, ...]`` into slices ``t+lo+b``, b in [0, B)."""
    return _push(buf, t, lo, contrib, "add")


@_scoped
def ring_push_max(buf, t, lo: int, contrib):
    """Max-combine (for value channels where 0 == empty)."""
    return _push(buf, t, lo, contrib, "max")


def slice_node_minor(x):
    """:func:`node_minor` for one ``[N, W]`` slice of such a ring, or a table
    of a slice's shape that meets slices elementwise: a ``while`` whose carry
    holds them (the tick gate of models/pbft.step) is laid out in isolation
    too, and where it chose another order for its carry every popped slice
    was copied into it on every tick and back out inside the body."""
    if x.ndim == 2 and x.shape[1] < _LANES <= x.shape[0]:
        return with_layout_constraint(x, Layout(major_to_minor=(1, 0)))
    return x


# every scope above, by name (ops/scopes.py)
SCOPES = tuple(_names)
