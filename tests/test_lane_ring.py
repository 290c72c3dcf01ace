"""``ops/ring.node_minor``'s lane rule (PR 50): a ring ``[D, N]`` or
``[D, N, W]`` of fewer nodes than a lane tile, traced under a lane batch
(``tile_vmap``, ``lane_vmap``, one around the other), is pinned slot-major,
lane-minor; its values are the lone ops'; a ring of ``N >= 128`` keeps the
node-minor constraint, a lone small ring none; ``ring.lane_pinned`` counts
the pins; and the text XLA:TPU compiles for a multi-Raft stack holds no copy
and no bare update of a ring (a described v5e, no chip)."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blockchain_simulator_tpu.models import base
from blockchain_simulator_tpu.ops import ring
from blockchain_simulator_tpu.utils import aotcache, telemetry

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
import ring_layout_text  # noqa: E402

D = 12
SHAPES = {"flat": (D, 5), "matrix": (D, 5, 5)}
# the lane batches around a ring op, outermost first, with their sizes
BATCHES = {
    "tile": ((base.tile_vmap, 6),),
    "lanes": ((base.lane_vmap, 4),),
    "lanes-around-tile": ((base.lane_vmap, 3), (base.tile_vmap, 4)),
    "tile-around-lanes": ((base.tile_vmap, 2), (base.lane_vmap, 5)),
}


def _ops(buf, t):
    """A tick's worth of ring work: a pop, an add-push of two buckets from
    what was popped and a max-push of one (three passes through
    ``node_minor``)."""
    cur, buf = ring.ring_pop(buf, t)
    buf = ring.ring_push_add(buf, t, 1, jnp.stack([cur + 1, 2 * cur + 3]))
    return cur, ring.ring_push_max(buf, t, 4, (cur * 7)[None])


def _batched(fn, batch):
    for vmap, _ in reversed(batch):
        fn = vmap(fn)
    return fn


def _rings(shape, batch, seed=0):
    sizes = tuple(size for _, size in batch)
    rng = np.random.default_rng(seed)
    bufs = rng.integers(0, 1000, sizes + shape).astype(np.int32)
    ts = rng.integers(0, 3 * D, sizes).astype(np.int32)
    return jnp.asarray(bufs), jnp.asarray(ts)


def _constraints(jaxpr) -> list:
    """Every ``layout_constraint``'s ``major_to_minor`` in ``jaxpr`` and in
    the jaxprs its equations hold (a ``custom_vmap_call``'s ``call``)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "layout_constraint":
            found.append(tuple(eqn.params["layout"].major_to_minor))
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _constraints(sub)
    return found


# ----------------------------------------------------------- (a) the values


@pytest.mark.parametrize("batch", list(BATCHES))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_lane_batched_ring_ops_equal_the_lone_ops_lane_by_lane(shape, batch):
    bufs, ts = _rings(SHAPES[shape], BATCHES[batch])
    cur, out = jax.jit(_batched(_ops, BATCHES[batch]))(bufs, ts)
    lone = jax.jit(_ops)
    for lane in np.ndindex(ts.shape):
        want_cur, want_out = lone(bufs[lane], ts[lane])
        np.testing.assert_array_equal(cur[lane], want_cur)
        np.testing.assert_array_equal(out[lane], want_out)


# ------------------------------------------------------------ (b) the jaxpr


@pytest.mark.parametrize("batch", list(BATCHES))
@pytest.mark.parametrize("shape", list(SHAPES))
def test_lane_batched_small_ring_is_pinned_slot_major_lane_minor(shape, batch):
    bufs, ts = _rings(SHAPES[shape], BATCHES[batch])
    got = _constraints(
        jax.make_jaxpr(_batched(_ops, BATCHES[batch]))(bufs, ts).jaxpr)
    lanes = len(BATCHES[batch])
    # the ring's own axes, slot first, then the lanes: none of them padded
    want = (*range(lanes, bufs.ndim), *range(lanes))
    assert got == [want] * 3


@pytest.mark.parametrize("shape", list(SHAPES))
def test_lone_small_ring_holds_no_constraint(shape):
    bufs, ts = _rings(SHAPES[shape], ())
    assert _constraints(jax.make_jaxpr(_ops)(bufs, ts).jaxpr) == []
    # nor under a batch that is no lane batch: the rule asks for a lane axis
    for vmap in (jax.vmap, base.select_vmap):
        bufs, ts = _rings(SHAPES[shape], ((vmap, 3),))
        assert _constraints(jax.make_jaxpr(vmap(_ops))(bufs, ts).jaxpr) == []


@pytest.mark.parametrize("batch", [None, "lanes"])
def test_ring_of_a_lane_tile_of_nodes_keeps_the_node_minor_constraint(batch):
    """``N >= 128``: PR 31's rule, lone and under a lane batch (where jax's
    own batching rule puts the batch axis major-most), letter for letter."""
    batch = BATCHES[batch] if batch else ()
    bufs, ts = _rings((D, 128, 8), batch)
    got = _constraints(jax.make_jaxpr(_batched(_ops, batch))(bufs, ts).jaxpr)
    assert got == [(0, 1, 3, 2) if batch else (0, 2, 1)] * 3
    # and a flat ring of that many nodes is left to the compiler
    bufs, ts = _rings((D, 128), batch)
    assert _constraints(
        jax.make_jaxpr(_batched(_ops, batch))(bufs, ts).jaxpr) == []


# ---------------------------------------------------------- (c) the counter


@pytest.mark.parametrize("batch,pins", [
    ("tile", 3), ("lanes-around-tile", 3), (None, 0)])
def test_lane_pinned_counts_the_pinned_ring_values(batch, pins):
    aotcache._listen()
    # the count moves when a trace closes: one closes here, so that what
    # earlier tests pinned before anything listened is behind us
    jax.jit(lambda x: x).lower(0)
    counter = telemetry.metrics.counter(telemetry.RING_COUNTER)
    batch = BATCHES[batch] if batch else ()
    bufs, ts = _rings(SHAPES["matrix"], batch)
    before, seen = counter.value, ring.lane_pinned[0]
    jax.jit(_batched(_ops, batch)).lower(bufs, ts)
    assert ring.lane_pinned[0] - seen == pins
    assert counter.value - before == pins


# ------------------------------------------------- the compiled text (v5e)

# what the parent's stack of 256 groups compiled to (this host, a described
# v5e): the pop's whole-ring relayout, a slot-second-minor ring updated bare,
# and the change's in-place update inside a fusion
_PARENT_LINES = """\
%wide.region_1.110.sunk (wide.arg: (s32[], s32[256,12,5,5])) -> (s32[]) {
  %copy.273 = s32[256,12,5,5]{0,3,2,1:T(8,128)S(1)} copy(%get-tuple-element.5506), backend_config={}
  %dynamic-update-slice.310 = s32[256,12,5,5]{0,1,3,2:T(8,128)} dynamic-update-slice(%a, %b, %c)
}
"""
_CHANGE_LINES = """\
%fused_computation.12 (param_0: s32[256,12,5]) -> s32[256,12,5] {
  %param_0 = s32[256,12,5]{0,2,1:T(8,128)} parameter(0)
  ROOT %dynamic-update-slice.7 = s32[256,12,5]{0,2,1:T(8,128)} dynamic-update-slice(%param_0, %b, %c)
}
%wide.region_1.110.sunk (wide.arg: (s32[], s32[256,12,5])) -> (s32[]) {
  %fusion.9 = s32[256,12,5]{0,2,1:T(8,128)} fusion(%get-tuple-element.1), kind=kLoop, calls=%fused_computation.12
  %copy.108 = s32[256,5,50]{0,2,1:T(8,128)} copy(%get-tuple-element.2557)
}
"""


@pytest.mark.parametrize("text,found", [
    (_PARENT_LINES, {"ring_copies": 1, "bare_ring_updates": 1,
                     "not_slot_major": 1, "ok": False}),
    (_CHANGE_LINES, {"ring_copies": 0, "bare_ring_updates": 0,
                     "not_slot_major": 0, "ok": True}),
    ("", {"ring_copies": 0, "bare_ring_updates": 0, "not_slot_major": 0,
          "ok": False}),  # a text without a ring proves nothing
], ids=["parent", "change", "no-ring"])
def test_the_reader_of_compiled_text_tells_the_two_layouts_apart(text, found):
    got = ring_layout_text.read(text, 256, 12)
    assert {k: v if k == "ok" else len(v) for k, v in got.items()
            if k in found} == found


@pytest.mark.parametrize("crashes", [0, 3])
def test_compiled_stack_holds_no_copy_and_no_bare_update_of_a_ring(crashes):
    """256 groups of 5 (``raft-groups-20kx5``'s and, with the crash schedule,
    ``raft-leadercrash-20kx5``'s inner configuration) compiled for a described
    v5e in a process of its own (~10 s): libtpu stays out of this one."""
    p = subprocess.run(
        [sys.executable, ring_layout_text.__file__, "--groups", "256",
         "--crashes", str(crashes)],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"})
    if p.returncode == 3:
        pytest.skip("libtpu cannot describe a v5e here: " + p.stderr[-200:])
    report = json.loads(p.stdout.strip().splitlines()[-1])
    assert report["ok"] and p.returncode == 0, report
    assert report["ring_depth"] == (20 if crashes else 12)
    # the seven rings, each through its pops' and pushes' fusions
    assert len(report["rings"]) == 2 and not report["ring_copies"]
