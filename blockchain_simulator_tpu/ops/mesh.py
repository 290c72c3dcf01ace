"""The collectives of a node-sharded tick, under stable names.

A program sharded over a mesh axis (``parallel/shard.py``) globalizes what
its shards hold with ``pmax`` / ``psum`` / ``all_gather`` over that axis.
On the device each is an ``all-reduce`` or ``all-gather`` with an ``op_name``
of its own; calling them through this module puts that name under
``ops.mesh.<pmax|psum|gather>`` (``ops/scopes.py``: HLO metadata, nothing
computed changes), innermost, so a trace can sum a tick's collectives by
name whatever engine phase issued them.  Call sites pass the axis name they
were given (``cfg.mesh_axis``); nothing here names one.
"""

from __future__ import annotations

from jax import lax

from blockchain_simulator_tpu.ops import scopes

_names: list = []
_scoped = scopes.scoped("ops.mesh", _names)


@_scoped
def pmax(x, axis):
    return lax.pmax(x, axis)


@_scoped
def psum(x, axis):
    return lax.psum(x, axis)


@_scoped
def gather(x, axis):
    """Local ``[n_loc, ...]`` -> global ``[N, ...]`` along the node axis."""
    return lax.all_gather(x, axis, tiled=True)


# every scope above, by name (ops/scopes.py)
SCOPES = tuple(_names)
