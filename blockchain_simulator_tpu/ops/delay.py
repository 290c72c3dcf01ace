"""Delay models.

The reference defers every send by ``Simulator::Schedule(getRandomDelay(), ...)``
with per-protocol uniform distributions (pbft-node.cc:66-69 U{3..5} ms,
raft-node.cc:63-66 U{0..2} ms, paxos-node.cc:397-400 U[0,50) ms) on top of the
3 ms point-to-point channel delay (blockchain-simulator.cc:24).  Here a delay is
an integer number of ticks; two families of samplers:

- *edge* samplers draw one delay per (sender, receiver) edge — exact.
- *stat* samplers draw per-receiver bucket **counts** directly from the induced
  binomial/multinomial distribution — statistically exact for full-mesh
  channels whose receivers only consume counts, and O(N·B) instead of O(N²).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from blockchain_simulator_tpu.ops import scopes

_names: list = []
_scoped = scopes.scoped("ops.delay", _names)


def uniform_probs(lo: int, hi: int) -> np.ndarray:
    """Bucket probabilities of U{lo..hi-1}, indexed 0..hi-lo-1 (offset lo)."""
    b = hi - lo
    return np.full((b,), 1.0 / b)


def roundtrip_probs(lo: int, hi: int) -> np.ndarray:
    """Distribution of the sum of two independent U{lo..hi-1} draws
    (request delay + reply delay), indexed 0..2*(hi-lo)-2 (offset 2*lo)."""
    p = uniform_probs(lo, hi)
    return np.convolve(p, p)


def _rbg_key(key: jax.Array) -> jax.Array:
    """Derive an ``rbg``-impl key (XLA's RngBitGenerator — far cheaper bit
    generation than threefry on XLA:CPU) from WHATEVER impl the caller's key
    uses: threefry keys hold 2 words, rbg/unsafe_rbg 4; tile-then-slice
    reduces to the identity for 4-word keys and to ``tile(kd, 2)`` for
    threefry.  Shared by :func:`_fast_normal` and the ``"rbg"`` edge
    sampler; the source key is already per-channel/per-tick folded, so
    streams stay decorrelated."""
    kd = jnp.ravel(jax.random.key_data(key))
    return jax.random.wrap_key_data(jnp.tile(kd, 4)[:4], impl="rbg")


@_scoped
def sample_edge_delays(key: jax.Array, shape, lo: int, hi: int,
                       impl: str = "threefry") -> jax.Array:
    """One delay per edge, in [lo, hi).

    ``impl`` selects the bit source (``SimConfig.edge_sampler``):

    - ``"threefry"`` (default): ``jax.random.randint`` on the caller's key —
      the historical stream every seed-pinned edge-path test rides.
    - ``"rbg"``: the same *exact-uniform integer* map fed by cheap
      RngBitGenerator words (the ``_fast_normal`` trick, minus the CLT):
      when the span ``hi - lo`` is a power of two <= 2^16, each 32-bit word
      bit-slices into TWO independent 16-bit fields and a mask — exactly
      uniform at half the generated bits; otherwise full 32-bit words map
      through the same shift-and-remainder construction
      ``jax.random.randint`` uses (bias <= span * 2^-32, identical class).
      Either way the map is pure integer arithmetic, so the repo's bit
      contract holds across differently-compiled UNBATCHED programs: the
      SAME key gives the SAME delays under jit, eager, ``lax.map`` lanes
      and mesh per-device bodies (the multi-seed/mesh sweep arms) — unlike
      the float ``"normal"`` stat mode's reassociation latitude
      (parallel/sweep.py).  One scoped caveat, shared with
      :func:`_fast_normal`: XLA's RngBitGenerator is NOT batch-invariant
      under ``vmap`` — a vmapped lane (other than lane 0) draws different
      bits than the same key unbatched, so vmap-vs-solo bit-equality pins
      must keep ``edge_sampler="threefry"`` exactly as they must keep
      ``stat_sampler="exact"``.  The stream DIFFERS from ``"threefry"``
      (same distribution), so the toggle is a config field, never an
      implicit swap.
    """
    if impl == "threefry":
        return jax.random.randint(key, shape, lo, hi, dtype=jnp.int32)
    if impl != "rbg":
        raise ValueError(f"unknown edge sampler impl {impl!r}")
    span = hi - lo
    rbg = _rbg_key(key)
    if span & (span - 1) == 0 and span <= (1 << 16):
        # power-of-two span: mask 16-bit fields — exactly uniform, and each
        # generated word yields two independent draws (disjoint bit fields)
        if not shape:
            return sample_edge_delays(key, (1,), lo, hi, impl)[0]
        r = shape[0]
        words = jax.random.bits(
            rbg, ((r + 1) // 2,) + tuple(shape[1:]), jnp.uint32
        )
        fields = jnp.concatenate(
            [words & jnp.uint32(0xFFFF), words >> 16], axis=0
        )[:r]
        return (lo + (fields & jnp.uint32(span - 1))).astype(jnp.int32)
    # general span: full 32-bit words through randint's own construction
    # (remainder over the word range) — bias <= span * 2^-32, the same
    # class jax.random.randint documents for non-power-of-two spans
    words = jax.random.bits(rbg, tuple(shape), jnp.uint32)
    return (lo + (words % jnp.uint32(span))).astype(jnp.int32)


def _fast_normal(key: jax.Array, shape) -> jax.Array:
    """Cheap standard-normal draws for the "normal"-mode sampler: one
    Philox word (``rbg`` impl — XLA's RngBitGenerator, far cheaper than
    threefry on XLA:CPU) yields TWO z values via 16-bit popcounts —
    ``(popcount(u16) - 8) / 2`` is a centered Binomial(16, 1/2), the CLT
    normal with mean 0 / variance exactly 1 — skipping the uniform->erfinv
    pipeline of ``jax.random.normal`` entirely (integer ops until the
    final scale) and halving the generated bits.

    Quality is deliberately CLT-level: the Gaussian binomial approximation
    this feeds is itself O(1/sqrt(n)) off, and the z lattice (step 0.5,
    first two moments exact) disappears into the round-to-integer-counts
    that follows.  Everything bit-contract-sensitive (per-edge delays,
    elections, view changes) stays on exact threefry draws.  The rbg key
    derives from the caller's (already per-channel/per-tick folded)
    threefry key, so streams stay decorrelated; the two halves of a word
    are disjoint bit fields, hence independent.  Profiled on the CPU
    fallback bench (VERDICT r5 weak-#4): the threefry
    ``jax.random.normal`` variant put the 10k-node round step at ~70%
    PRNG time (155 rounds/s); this form more than doubles end-to-end
    throughput (424 rounds/s single-core)."""
    if not shape:
        return _fast_normal(key, (1,))[0]
    rbg = _rbg_key(key)
    r = shape[0]
    words = jax.random.bits(rbg, ((r + 1) // 2,) + tuple(shape[1:]), jnp.uint32)
    lo = jax.lax.population_count(words & jnp.uint32(0xFFFF))
    hi = jax.lax.population_count(words >> 16)
    z = jnp.concatenate([lo, hi], axis=0)[:r]
    return (z.astype(jnp.float32) - 8.0) * 0.5


def _fast_normal_rows(key: jax.Array, shape, at):
    """``_fast_normal(key, shape)`` read by rows: returns ``z`` with
    ``z(b, *o)`` bit-equal to ``_fast_normal(key, shape)[(b, *o)]`` for every
    index ``o`` in ``at``.  The words are drawn in the one call of the whole
    shape (XLA's RngBitGenerator is not batch-invariant: a row drawn alone
    holds other bits) and sliced BEFORE the popcount, so the two halves are
    never concatenated.  The barrier keeps each row of words a value of its
    own: XLA:TPU otherwise fuses every slice with its popcount into one
    fusion that re-lays the draw's tiling into ``[N]`` rows 128 lanes at a
    time, the largest operation of the 100k round (111.7 us a round without
    the barrier, 92.9 with it: PERF.md section 6, PR 45)."""
    half = (shape[0] + 1) // 2
    words = jax.random.bits(_rbg_key(key), (half,) + tuple(shape[1:]), jnp.uint32)
    rows = jax.lax.optimization_barrier(
        {(h, *o): words[(h, *o)] for h in range(half) for o in at}
    )

    def z(b, *o):
        w = rows[(b % half, *o)]
        field = w & jnp.uint32(0xFFFF) if b < half else w >> 16
        return (jax.lax.population_count(field).astype(jnp.float32) - 8.0) * 0.5

    return z


@_scoped
def binom(key: jax.Array, n: jax.Array, p: float, mode: str = "exact") -> jax.Array:
    """Binomial(n, p) draw (float32 out, same shape as ``n``).

    ``mode="normal"``: Gaussian approximation, ~6 elementwise passes instead
    of the ~40 of BTRS rejection sampling — see sample_bucket_counts."""
    n = jnp.asarray(n, jnp.float32)
    if mode == "normal":
        z = _fast_normal(key, n.shape)
        mu = n * p
        sigma = jnp.sqrt(jnp.maximum(mu * (1.0 - p), 0.0))
        return jnp.clip(jnp.round(mu + sigma * z), 0.0, n)
    return jax.random.binomial(key, n, p)


@_scoped
def sample_bucket_counts(key: jax.Array, n: jax.Array, probs: np.ndarray,
                         mode: str = "exact") -> jax.Array:
    """Split ``n`` (int array, any shape) into bucket counts ~ Multinomial(n, probs).

    Implemented as a chain of conditional binomials over the (small, static)
    bucket axis.  Returns int32 of shape ``(len(probs),) + n.shape``.

    ``mode`` selects the per-bucket binomial sampler:

    - ``"exact"``: ``jax.random.binomial`` (BTRS rejection sampling) — exact,
      but ~40 elementwise passes per bucket; the round-2 tick loop spent much
      of its time here.
    - ``"normal"``: Gaussian approximation ``round(mu + sigma*z)`` clipped to
      ``[0, remaining]``.  Counts still sum exactly to ``n`` (the chain
      construction guarantees it), so every message is delivered exactly
      once; only the spread across delay buckets is approximate, with
      relative error O(1/sqrt(n·p)) — negligible at the 10k-100k-node scales
      this mode is selected for (SimConfig.stat_sampler = "auto" picks it
      only at large n).  All buckets' z-draws come from ONE
      ``jax.random.normal`` call over a leading bucket axis: a single fused
      threefry pass instead of a fold_in + draw per bucket — the chain's
      per-bucket work is then ~5 cheap elementwise ops, which is what makes
      the sampler-bound round fast path viable on the XLA:CPU fallback
      (the per-bucket variant measured ~3x slower end-to-end there).

    The ``"exact"`` chain mirrors the single-derivation trick at the key
    level: per-bucket keys come from ONE batched ``vmap(fold_in)`` pass
    over the bucket axis instead of a scalar ``fold_in(key, b)`` inside the
    loop — one fused threefry dispatch for the whole chain.  ``vmap`` of
    ``fold_in`` is bit-identical to the per-bucket scalar calls (fold_in is
    an elementwise threefry of the folded constant), so the exact stream —
    and every seed-pinned bit-equality test riding it — is unchanged; a
    ``jax.random.split``-based hoist would have been equally fused but
    minted a brand-new stream, moving every pinned trajectory for zero
    additional win (moments are identical either way — the per-bucket keys
    are independent uniforms in both constructions).  Only the BTRS
    rejection passes themselves remain per-bucket; they are inherently
    sequential (each bucket's ``n`` is the previous bucket's remainder).
    """
    return jnp.stack(list(bucket_count_chain(key, n, probs, mode))).astype(
        jnp.int32
    )


@_scoped
def bucket_count_chain(key: jax.Array, n: jax.Array, probs: np.ndarray,
                       mode: str = "exact"):
    """The conditional-binomial chain behind :func:`sample_bucket_counts`,
    yielded one bucket at a time (float32, shape ``n.shape``) so callers can
    fuse each bucket's sampler math into its consumer without materializing
    the stacked ``[B, ...]`` tensor — ops/delivery.py's fused ring pushes
    combine bucket ``b`` into its ring slice as it is produced.  Yields the
    EXACT values :func:`sample_bucket_counts` stacks (same keys, same
    arithmetic, same order), so fused and unfused consumers are bit-equal."""
    n = jnp.asarray(n, jnp.float32)
    nb = len(probs)
    # the last bucket is always the remainder — it never consumes a draw
    z_all = (
        _fast_normal(key, (max(nb - 1, 1),) + n.shape)
        if mode == "normal" else None
    )
    keys = (
        jax.vmap(lambda b: jax.random.fold_in(key, b))(jnp.arange(max(nb - 1, 1)))
        if mode != "normal" and nb > 1 else None
    )
    remaining = n
    p_left = 1.0
    for b, pb in enumerate(probs):
        frac = float(min(max(pb / max(p_left, 1e-9), 0.0), 1.0))
        if b == nb - 1 or frac >= 1.0:
            c = remaining
        elif mode == "normal":
            mu = remaining * frac
            sigma = jnp.sqrt(jnp.maximum(mu * (1.0 - frac), 0.0))
            c = jnp.clip(jnp.round(mu + sigma * z_all[b]), 0.0, remaining)
        else:
            c = binom(keys[b], remaining, frac, mode)
        yield c
        remaining = remaining - c
        p_left -= pb


@_scoped
def bucket_count_rows(key: jax.Array, n, probs: np.ndarray, mode: str = "exact"):
    """:func:`bucket_count_chain` for a consumer that keeps rows
    (models/pbft_round.py): ``n`` is one array, or a list of equal-shaped
    arrays standing for ``jnp.stack(n)`` (the commit wave, a row per send
    offset), and each bucket comes as ``n`` came: an array, or the list of its
    rows.  Bit-equal to the chain over the stacked ``n``: the same key, the
    same draws in the same shapes, the same float arithmetic in the same
    order.  Under ``"normal"`` nothing stacked is built on the way: the z
    words are one draw of the stacked shape, read row by row
    (:func:`_fast_normal_rows`).  The ``"exact"`` binomial draws over the
    whole stacked shape under one key, so there the rows are stacked for each
    draw and handed back as rows."""
    rows = isinstance(n, (list, tuple))
    remaining = [jnp.asarray(x, jnp.float32) for x in (n if rows else (n,))]
    # where each part sits in the stacked shape, after the bucket index
    at = [(o,) for o in range(len(remaining))] if rows else [()]
    shape = ((len(remaining),) if rows else ()) + remaining[0].shape
    nb = len(probs)
    z = (
        _fast_normal_rows(key, (nb - 1,) + shape, at)
        if mode == "normal" and nb > 1 else None
    )
    keys = (
        jax.vmap(lambda b: jax.random.fold_in(key, b))(jnp.arange(nb - 1))
        if mode != "normal" and nb > 1 else None
    )
    p_left = 1.0
    for b, pb in enumerate(probs):
        frac = float(min(max(pb / max(p_left, 1e-9), 0.0), 1.0))
        if b == nb - 1 or frac >= 1.0:
            c = remaining
        elif mode == "normal":
            c = []
            for rem, o in zip(remaining, at):
                mu = rem * frac
                sigma = jnp.sqrt(jnp.maximum(mu * (1.0 - frac), 0.0))
                c.append(jnp.clip(jnp.round(mu + sigma * z(b, *o)), 0.0, rem))
        else:
            whole = jnp.stack(remaining) if rows else remaining[0]
            c = binom(keys[b], whole, frac, mode)
            c = list(c) if rows else [c]
        yield c if rows else c[0]
        remaining = [rem - x for rem, x in zip(remaining, c)]
        p_left -= pb


# every scope above, by name (ops/scopes.py)
SCOPES = tuple(_names)
