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

import jax
import jax.numpy as jnp

from blockchain_simulator_tpu.ops import scopes

_names: list = []
_scoped = scopes.scoped("ops.ring", _names)


@_scoped
def ring_pop(buf, t):
    """Read and clear the current tick's slice. Returns (slice, buf')."""
    idx = jnp.mod(t, buf.shape[0])
    cur = jax.lax.dynamic_index_in_dim(buf, idx, 0, keepdims=False)
    return cur, jax.lax.dynamic_update_index_in_dim(
        buf, jnp.zeros_like(cur), idx, 0
    )


def _push(buf, t, lo: int, contrib, op: str):
    """Combine ``contrib[b, ...]`` into slices ``t+lo+b``, b in [0, B).

    A DUS chain: unrolled dynamic-slice / dynamic-update-slice pairs over
    the (small, static) bucket axis.  A ``buf.at[idx_vec].add`` would lower
    to XLA generic scatter, which TPUs execute catastrophically slowly —
    the round-3 ablation (tools/ablate.py) measured the scatter form ~30x
    slower than this chain.
    """
    combine = jnp.add if op == "add" else jnp.maximum
    d = buf.shape[0]
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


# every scope above, by name (ops/scopes.py)
SCOPES = tuple(_names)
