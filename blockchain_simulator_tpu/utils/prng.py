"""Threaded PRNG discipline.

The reference calls libc ``rand()`` with no ``srand`` anywhere (SURVEY.md §5:
every run uses the same default seed, so runs are accidentally reproducible).
Here reproducibility is by design: one base key per simulation, folded with the
tick index once per step, and with a small static channel id per use site.
Every random draw is therefore a pure function of (seed, tick, channel, shape).
"""

from __future__ import annotations

import jax


# Static channel ids — one per independent randomness consumer per tick.
class Channel:
    DELAY_BCAST = 0      # broadcast one-way delays
    DELAY_ROUNDTRIP = 1  # request+reply round-trip delays
    DELAY_REPLY = 2      # unicast reply delays
    VIEW_CHANGE = 3      # PBFT rand()%100 view-change draw
    ELECTION = 4         # Raft election timeout draws
    DROP = 5             # fault injection: per-edge message drops
    DELAY_BCAST2 = 6     # second broadcast channel in the same tick
    DELAY_REPLY2 = 7
    STAT = 8             # statistical-delivery binomial chains
    DELAY_BCAST3 = 9     # third broadcast channel (Paxos commit requests)


def tick_key(base: jax.Array, tick) -> jax.Array:
    """Key for one simulation tick."""
    return jax.random.fold_in(base, tick)


def chan_key(tkey: jax.Array, channel: int) -> jax.Array:
    """Key for one use site within a tick."""
    return jax.random.fold_in(tkey, channel)


# added at the file's end, so that no line above moves (the compile cache
# keys on the source lines of traced code, ROADMAP D11): fault-schedule
# draws (a Raft group's crash phase, models/raft.init)
Channel.FAULT = 10
