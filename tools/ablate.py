"""Ablation profile of the PBFT tick loop on the real chip.

Measures where the per-tick wall (N=100k) goes by monkeypatching
pieces of the step out and re-timing the whole 2100-tick run.  Each variant
changes results (that is fine — only wall time is being measured); every
variant runs in-process with a fresh make_sim_fn cache entry via a distinct
config field tweak where possible, or cache_clear.

CAVEAT (round-4 finding, KNOWN_ISSUES.md #5): ablation-by-removal
OVERSTATES the removed piece's cost.  Patching the ring pushes out also
lets XLA dead-code-eliminate the samplers and delivery math whose only
consumers they were, so the "no_push" delta (~2.0 ms/tick) bundled most of
the sampling pipeline into the pushes.  An isolation measurement (a
push-only scan, KNOWN_ISSUES.md #5) put the pushes alone at ~128 us/tick
(~75% of the HBM bandwidth bound).  Read deltas here as "this stage AND its
exclusive producers", not as the stage's own cost.

Usage: python tools/ablate.py [N] [TICKS]
"""

from __future__ import annotations

import os as _os
import sys as _sys

_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from blockchain_simulator_tpu import runner
from blockchain_simulator_tpu.ops import delay as delay_ops
from blockchain_simulator_tpu.ops import delivery as dv
from blockchain_simulator_tpu.ops import ring
from blockchain_simulator_tpu.utils.config import SimConfig

N = int(sys.argv[1]) if len(sys.argv) > 1 else 100_000
TICKS = int(sys.argv[2]) if len(sys.argv) > 2 else 2100


def cfg(window=8):
    return SimConfig(
        protocol="pbft", n=N, sim_ms=TICKS, pbft_max_rounds=40,
        pbft_max_slots=48, pbft_window=window, delivery="stat",
        schedule="tick",  # this tool profiles the TICK engine specifically
    )


def timed(c) -> float:
    runner.make_sim_fn.cache_clear()
    sim = runner.make_sim_fn(c)
    jax.block_until_ready(sim(jax.random.key(1)))
    t0 = time.perf_counter()
    jax.block_until_ready(sim(jax.random.key(2)))
    return time.perf_counter() - t0


_orig = {
    "sample_bucket_counts": delay_ops.sample_bucket_counts,
    "categorical": jax.random.categorical,
    "ring_push_add": ring.ring_push_add,
    "ring_push_max": ring.ring_push_max,
    "ring_pop": ring.ring_pop,
}


def det_bucket_counts(key, n, probs, mode="exact"):
    """Deterministic expected-value split: no binomial sampling at all."""
    n = jnp.asarray(n, jnp.int32)
    out, remaining = [], n
    for b, pb in enumerate(np.asarray(probs)):
        c = remaining if b == len(probs) - 1 else jnp.asarray(
            jnp.floor(n.astype(jnp.float32) * pb), jnp.int32)
        out.append(c)
        remaining = remaining - c
    return jnp.stack(out)


def report(name, wall):
    print(json.dumps({"variant": name, "wall_s": round(wall, 3),
                      "us_per_tick": round(wall / TICKS * 1e6, 1)}), flush=True)


def main():
    import blockchain_simulator_tpu.models.pbft as pbft_mod

    report("baseline_w8", timed(cfg()))
    report("baseline_w2", timed(cfg(window=2)))

    # no binomial chains (stat sampler -> deterministic split)
    delay_ops.sample_bucket_counts = det_bucket_counts
    # pbft.py imports `delay as delay_ops` (module object) so patching the
    # module attribute is enough; delivery.py imported the function directly:
    dv.sample_bucket_counts = det_bucket_counts
    report("no_binomial_w8", timed(cfg()))
    report("no_binomial_w2", timed(cfg(window=2)))

    # additionally: no categorical draws (pp/vc value delivery delays -> lo)
    def det_categorical(key, logits, axis=-1, shape=None):
        return jnp.zeros(shape, jnp.int32)
    jax.random.categorical = det_categorical
    report("no_binom_no_categorical_w8", timed(cfg()))
    jax.random.categorical = _orig["categorical"]

    # additionally: ring pushes become no-ops (keep pops)
    ring.ring_push_add = lambda buf, t, lo, contrib: buf
    ring.ring_push_max = lambda buf, t, lo, contrib: buf
    pbft_mod.ring_push_add = ring.ring_push_add
    pbft_mod.ring_push_max = ring.ring_push_max
    report("no_binom_no_push_w8", timed(cfg()))

    # additionally: pops read without clearing (pure dynamic-slice)
    ring.ring_pop = lambda buf, t: (buf[jnp.mod(t, buf.shape[0])], buf)
    pbft_mod.ring_pop = ring.ring_pop
    report("no_binom_no_push_no_clear_w8", timed(cfg()))

    # floor: empty scan body over the same carry (scan overhead itself)
    def empty_sim(c):
        proto_state = pbft_mod.init(c)

        @jax.jit
        def sim(key):
            def body(carry, t):
                return carry, ()
            out, _ = jax.lax.scan(body, proto_state, jnp.arange(c.ticks))
            return out[0]
        return sim

    sim = empty_sim(cfg())
    jax.block_until_ready(sim(jax.random.key(1)))
    t0 = time.perf_counter()
    jax.block_until_ready(sim(jax.random.key(2)))
    report("empty_scan_w8", time.perf_counter() - t0)


if __name__ == "__main__":
    main()
