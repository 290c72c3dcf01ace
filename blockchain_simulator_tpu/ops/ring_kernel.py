"""Pallas TPU kernel for the ring-buffer push (SURVEY.md §7 L7).

The tick engines' dominant cost at N = 100k is pushing delivery contributions
into the future-inbox rings (round-3 ablation, tools/ablate.py: ~2.0 of
2.24 ms/tick).  The XLA forms both lose bandwidth:

- ``buf.at[idx_vec].add`` lowers to generic scatter — catastrophic on TPU
  (~30x slower than the DUS chain, per the round-3 rewrite);
- the DUS chain (ops/ring._push) is a dynamic-slice + dynamic-update-slice
  pair per delay bucket; inside a ``lax.scan`` body XLA cannot always prove
  the carried buffer dead, so each pair costs a slice-sized (or worse,
  buffer-sized) copy, B times per channel per tick.

This kernel fuses the whole push into ONE in-place pass: the ring flattens to
``[D, L]``, the grid runs over ``(bucket, L-tile)``, and a scalar-prefetched
tick index lets the BlockSpec index_map address exactly the ``B`` ring slices
the push touches — nothing else is read or written (``input_output_aliases``
pins in-place semantics; untouched slices keep their values).  Traffic is the
information-theoretic floor: read+write of B slices plus read of the
contribution.

Availability: compiled path on TPU only (Mosaic does not lower elsewhere);
``interpret=True`` runs anywhere and is used by the CPU correctness tests
(tests/test_ops.py).  Selection: env ``BLOCKSIM_RING_KERNEL`` in {"dus"
(default), "pallas"}.  ``pallas`` is an explicit request and is honoured or
refused, never quietly replaced: on a backend other than tpu, or for a ring
whose flattened row does not tile (L has no usable 128-multiple divisor —
e.g. the ``[D, N]`` rings at N = 100000), the trace raises.

Measured on a TPU v5e (KNOWN_ISSUES.md #5): Mosaic compiles the kernel in
~14 s and it is bit-equal to the DUS chain at the tick engine's
``[18, 100000, 8]`` shapes, but one 5-bucket push takes ~1.19 ms against an
HBM floor of ~59 us for the bytes it moves — a one-sublane ``(1, 1, TL)``
block fills an eighth of each VMEM tile.  It moves the same bytes as the
chain and cannot materially beat it; it stays opt-in only (ROADMAP D5).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM budget per block: 3 live blocks (buf in, contrib in, out) with double
# buffering; 512 KB each stays well inside ~16 MB/core.
_MAX_TILE_BYTES = 512 * 1024
_MIN_TILE = 128


def enabled() -> bool:
    """Is the fused kernel requested (``BLOCKSIM_RING_KERNEL=pallas``)?
    Read at trace time.  The request raises where it cannot be honoured
    instead of quietly running the DUS chain."""
    m = os.environ.get("BLOCKSIM_RING_KERNEL", "dus")
    if m == "dus":
        return False
    if m != "pallas":
        raise ValueError(
            f"BLOCKSIM_RING_KERNEL={m!r}: expected 'dus' or 'pallas'")
    if jax.default_backend() != "tpu":
        raise RuntimeError(
            "BLOCKSIM_RING_KERNEL=pallas needs the tpu backend (Mosaic does "
            f"not lower to {jax.default_backend()!r}); tests call "
            "fused_push(..., interpret=True) directly")
    return True


@functools.lru_cache(maxsize=None)
def _pick_tile(l: int, itemsize: int) -> int | None:
    """Largest divisor of ``l`` of the form 128*k fitting the VMEM budget."""
    best = None
    limit = _MAX_TILE_BYTES // itemsize
    k = 1
    # divisors of l/128 (l is a few hundred thousand at most — trial division
    # over k up to l/128 is trace-time only and cached)
    if l % _MIN_TILE != 0:
        return None
    m = l // _MIN_TILE
    for k in range(1, m + 1):
        if m % k == 0:
            tl = _MIN_TILE * k
            if tl <= limit:
                best = tl
            else:
                break
    return best


def _kernel(combine):
    def body(t_ref, buf_blk, c_blk, out_blk):
        del t_ref  # consumed by the index_maps
        out_blk[...] = combine(buf_blk[...], c_blk[...])

    return body


def fused_push(buf, t, lo: int, contrib, op: str, interpret: bool = False):
    """In-place ``buf[(t+lo+b) % D] op= contrib[b]`` for all buckets b.

    ``buf``: [D, ...rest]; ``contrib``: [B, ...rest] (same rest), B <= D.
    ``op``: "add" | "max".  Returns the updated buffer (donated input).
    """
    d = buf.shape[0]
    b = contrib.shape[0]
    rest = buf.shape[1:]
    l = int(np.prod(rest)) if rest else 1
    if not pushable(buf, contrib):
        raise ValueError(
            f"the fused ring push cannot tile buf {buf.shape} / contrib "
            f"{contrib.shape}: it needs B <= D and a flattened row length "
            f"that is a multiple of {_MIN_TILE}")
    tl = _pick_tile(l, buf.dtype.itemsize)
    # [D, 1, L] so block (1, 1, TL) satisfies the TPU tiling rule: the
    # sublane (second-to-last) block dim equals the full array dim (1) and
    # the lane dim TL is a 128-multiple
    buf2 = buf.reshape(d, 1, l)
    c2 = contrib.reshape(b, 1, l)
    combine = jnp.add if op == "add" else jnp.maximum
    t_arr = jnp.asarray(t, jnp.int32).reshape(1)

    def idx_ring(bi, i, t_ref):
        return ((t_ref[0] + lo + bi) % d, 0, i)

    def idx_contrib(bi, i, t_ref):
        del t_ref
        return (bi, 0, i)

    out = pl.pallas_call(
        _kernel(combine),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, l // tl),
            in_specs=[
                pl.BlockSpec((1, 1, tl), idx_ring),
                pl.BlockSpec((1, 1, tl), idx_contrib),
            ],
            out_specs=pl.BlockSpec((1, 1, tl), idx_ring),
        ),
        out_shape=jax.ShapeDtypeStruct((d, 1, l), buf.dtype),
        # out aliases the ring input: the kernel is a true in-place update and
        # the D-B untouched slices keep their values
        input_output_aliases={1: 0},
        interpret=interpret,
    )(t_arr, buf2, c2)
    return out.reshape(buf.shape)


def pushable(buf, contrib) -> bool:
    """Static eligibility of the fused kernel for this push."""
    if contrib.shape[0] > buf.shape[0]:
        return False
    rest = buf.shape[1:]
    l = int(np.prod(rest)) if rest else 1
    return _pick_tile(l, buf.dtype.itemsize) is not None
