"""Micro-benchmark: what does an empty lax.scan iteration cost on this chip?

Separates per-iteration loop overhead from carry-size effects, and measures
whether nesting (outer scan x unrolled inner steps) amortizes it — the
design question for the round-blocked scheduler.
"""

from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import json
import time

import jax
import jax.numpy as jnp



def timed(fn, *args):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    jax.block_until_ready(fn(*args))
    return time.perf_counter() - t0


def report(name, wall, iters):
    print(json.dumps({"variant": name, "wall_s": round(wall, 4),
                      "us_per_iter": round(wall / iters * 1e6, 1)}), flush=True)


def main():
    t_iters = 2100

    for label, shape in (("small_carry_1k", (1000,)),
                         ("big_carry_18x100kx8", (18, 100_000, 8))):
        carry0 = jnp.zeros(shape, jnp.int32)

        @jax.jit
        def empty(carry):
            def body(c, t):
                return c, ()
            return jax.lax.scan(body, carry, jnp.arange(t_iters))[0]

        report(f"empty_{label}", timed(empty, carry0), t_iters)

        @jax.jit
        def touch(carry):
            def body(c, t):
                return c + 1, ()
            return jax.lax.scan(body, carry, jnp.arange(t_iters))[0]

        report(f"touch_{label}", timed(touch, carry0), t_iters)

    # nested: outer scan of 42, inner unrolled 50 adds — same total adds as
    # touch_2100 but 50x fewer loop iterations
    carry0 = jnp.zeros((100_000, 8), jnp.int32)

    @jax.jit
    def nested(carry):
        def body(c, r):
            for _ in range(50):
                c = c + 1
            return c, ()
        return jax.lax.scan(body, carry, jnp.arange(42))[0]

    report("nested_42x50_unrolled_100kx8", timed(nested, carry0), 2100)

    @jax.jit
    def flat(carry):
        def body(c, t):
            return c + 1, ()
        return jax.lax.scan(body, carry, jnp.arange(2100))[0]

    report("flat_2100_100kx8", timed(flat, carry0), 2100)

    # dynamic-slice + DUS pair per iteration on a ring-sized buffer (the pop
    # pattern) to price DUS round trips per tick
    buf0 = jnp.zeros((18, 100_000, 8), jnp.int32)

    @jax.jit
    def popper(buf):
        def body(b, t):
            idx = jnp.mod(t, 18)
            cur = jax.lax.dynamic_index_in_dim(b, idx, 0, keepdims=False)
            b = jax.lax.dynamic_update_index_in_dim(b, cur + 1, idx, 0)
            return b, ()
        return jax.lax.scan(body, buf0, jnp.arange(2100))[0]

    report("pop_push_pair_18x100kx8", timed(popper, buf0), 2100)

    # multi-seed batching of the SAME pop/push pattern (ISSUE 13 tick-path
    # arms, n scaled to 10k so the 4-lane batch fits the micro budget):
    # vmap over the batch lowers each DUS pair to XLA generic scatter
    # (KNOWN_ISSUES #0b/#0i — the cost the sweeps' vmapped dispatch pays
    # per tick), while lax.map of the unvmapped body (partition.seq_map,
    # the multi-seed tick executable's shape) keeps plain DUS at the same
    # total work.  NOTE the measured micro gap here is small (~7%): ONE
    # batched scatter on an otherwise-empty scan body is cheap.  The real
    # tick engine batches 3-4 ring pushes per tick PLUS the gather/compare
    # chains feeding them, and there the same lowering inflates XLA's own
    # cost model 4.6x flops/seed (pbft, ARTIFACT_tick_bench.json
    # cost_per_seed) — these rows pin the MECHANISM's direction at the
    # floor, tick_bench prices its full-engine magnitude.
    lanes, iters = 4, 2100
    buf_s = jnp.zeros((18, 10_000, 8), jnp.int32)
    bufs_b = jnp.zeros((lanes, 18, 10_000, 8), jnp.int32)

    def ring_scan(buf):
        def body(b, t):
            idx = jnp.mod(t, 18)
            cur = jax.lax.dynamic_index_in_dim(b, idx, 0, keepdims=False)
            b = jax.lax.dynamic_update_index_in_dim(b, cur + 1, idx, 0)
            return b, ()
        return jax.lax.scan(body, buf, jnp.arange(iters))[0]

    # one-shot micro-bench jits, one call each — recompile hazard is moot
    report(f"pop_push_vmap_{lanes}x18x10kx8",
           timed(jax.jit(jax.vmap(ring_scan)), bufs_b), iters * lanes)  # jaxlint: disable=static-arg-recompile-hazard
    report(f"pop_push_seqmap_{lanes}x18x10kx8",
           timed(jax.jit(lambda bs: jax.lax.map(ring_scan, bs)), bufs_b),  # jaxlint: disable=static-arg-recompile-hazard
           iters * lanes)
    report("pop_push_solo_18x10kx8", timed(jax.jit(ring_scan), buf_s), iters)  # jaxlint: disable=static-arg-recompile-hazard


if __name__ == "__main__":
    main()
