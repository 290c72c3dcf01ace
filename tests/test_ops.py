"""Unit tests for the transport substrate (delay models, rings, delivery)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from blockchain_simulator_tpu.ops import delay as delay_ops
from blockchain_simulator_tpu.ops import delivery as dv
from blockchain_simulator_tpu.ops.ring import ring_pop, ring_push_add, ring_push_max


def test_uniform_probs():
    p = delay_ops.uniform_probs(3, 6)
    assert p.shape == (3,)
    np.testing.assert_allclose(p.sum(), 1.0)


def test_roundtrip_probs_support():
    # sum of two U{3..5}: support 6..10, triangular
    p = delay_ops.roundtrip_probs(3, 6)
    assert p.shape == (5,)
    np.testing.assert_allclose(p.sum(), 1.0)
    np.testing.assert_allclose(p[2], 3 / 9)  # mode at 8


def test_edge_delays_in_range():
    d = delay_ops.sample_edge_delays(jax.random.key(0), (50, 50), 3, 6)
    assert int(d.min()) >= 3 and int(d.max()) <= 5


def test_bucket_counts_conserve_total():
    probs = delay_ops.roundtrip_probs(0, 3)
    n = jnp.array([[7, 0], [100, 3]], jnp.int32)
    c = delay_ops.sample_bucket_counts(jax.random.key(1), n, probs)
    assert c.shape == (len(probs), 2, 2)
    np.testing.assert_array_equal(np.asarray(c.sum(0)), np.asarray(n))
    assert int(c.min()) >= 0


def test_bucket_counts_distribution():
    probs = delay_ops.uniform_probs(0, 4)
    n = jnp.full((2000,), 40, jnp.int32)
    c = delay_ops.sample_bucket_counts(jax.random.key(2), n, probs)
    frac = np.asarray(c.sum(1) / c.sum())
    np.testing.assert_allclose(frac, 0.25, atol=0.01)


def test_ring_push_pop_timing():
    buf = jnp.zeros((8, 4), jnp.int32)
    contrib = jnp.stack([jnp.full((4,), b + 1, jnp.int32) for b in range(3)])
    buf = ring_push_add(buf, 2, 3, contrib)  # lands at ticks 5,6,7
    for t in (3, 4):
        got, buf = ring_pop(buf, t)
        assert int(got.sum()) == 0
    for i, t in enumerate((5, 6, 7)):
        got, buf = ring_pop(buf, t)
        np.testing.assert_array_equal(np.asarray(got), i + 1)
    # pop clears: wrap around and check emptiness
    got, buf = ring_pop(buf, 5 + 8)
    assert int(got.sum()) == 0


def test_ring_wraparound():
    buf = jnp.zeros((4, 1), jnp.int32)
    buf = ring_push_add(buf, 6, 3, jnp.ones((1, 1), jnp.int32))  # tick 9 -> idx 1
    got, buf = ring_pop(buf, 9)
    assert int(got[0]) == 1


def test_ring_push_max_combines():
    buf = jnp.zeros((8, 2), jnp.int32)
    buf = ring_push_max(buf, 0, 2, jnp.array([[5, 1]], jnp.int32))
    buf = ring_push_max(buf, 0, 2, jnp.array([[3, 9]], jnp.int32))
    got, _ = ring_pop(buf, 2)
    np.testing.assert_array_equal(np.asarray(got), [5, 9])


def test_bcast_counts_dense_totals():
    n = 16
    send = jnp.zeros((n,), bool).at[jnp.array([0, 5])].set(True)
    c = dv.bcast_counts_dense(jax.random.key(3), send, 3, 6)
    total = np.asarray(c.sum(0))
    # every non-sender receives 2, senders receive 1 (not from self)
    assert total[0] == 1 and total[5] == 1
    assert (np.delete(total, [0, 5]) == 2).all()


def test_bcast_slots_dense_slot_routing():
    n, s = 8, 4
    slot_mat = jnp.zeros((n, s), jnp.int32).at[2, 3].set(1)
    c = dv.bcast_slots_dense(jax.random.key(4), slot_mat, 3, 6)
    total = np.asarray(c.sum(0))  # [N, S]
    assert (total[:, :3] == 0).all()
    assert total[2, 3] == 0  # sender does not hear itself
    assert (np.delete(total[:, 3], 2) == 1).all()


def test_roundtrip_reply_counts_dense():
    n = 10
    send = jnp.zeros((n,), bool).at[4].set(True)
    c = dv.roundtrip_reply_counts_dense(jax.random.key(5), send, 3, 6)
    total = np.asarray(c.sum(0))
    assert total[4] == n - 1 and np.delete(total, 4).sum() == 0


def test_roundtrip_peer_mask_excludes_byzantine():
    n = 10
    send = jnp.zeros((n,), bool).at[0].set(True)
    peers = jnp.arange(n) < 7  # 3 byzantine/crashed peers don't vote
    c = dv.roundtrip_reply_counts_dense(jax.random.key(6), send, 3, 6, peer_mask=peers)
    assert int(c.sum()) == 6  # peers 1..6


def test_stat_matches_dense_totals():
    n = 64
    send = jnp.ones((n,), bool)
    probs = delay_ops.uniform_probs(3, 6)
    c = dv.bcast_counts_stat(jax.random.key(7), n, send, probs)
    total = np.asarray(c.sum(0))
    assert (total == n - 1).all()


def test_bcast_matrix_dense_identity():
    n = 6
    send = jnp.zeros((n,), bool).at[1].set(True)
    value = jnp.zeros((n,), jnp.int32).at[1].set(42)
    c = dv.bcast_matrix_dense(jax.random.key(8), send, value, 3, 6)
    total = np.asarray(c.max(0))  # [recv, send]
    assert (total[:, [0, 2, 3, 4, 5]] == 0).all()
    assert total[1, 1] == 0
    assert sorted(np.unique(total[:, 1]).tolist()) in ([0, 42], [[0, 42]], [0, 42])


def test_drop_prob_thins_traffic():
    n = 32
    send = jnp.ones((n,), bool)
    c_full = dv.bcast_counts_dense(jax.random.key(9), send, 3, 6, 0.0)
    c_half = dv.bcast_counts_dense(jax.random.key(9), send, 3, 6, 0.5)
    assert int(c_half.sum()) < int(c_full.sum())


# --- fast samplers (ISSUE 13: rbg edge sampler, hoisted exact chain) -------


def test_rbg_edge_delays_in_range_and_uniform():
    # general span (3): remainder map over full rbg words
    d = np.asarray(delay_ops.sample_edge_delays(
        jax.random.key(0), (400, 400), 3, 6, impl="rbg"))
    assert d.min() >= 3 and d.max() <= 5
    frac = np.bincount(d.ravel() - 3, minlength=3) / d.size
    np.testing.assert_allclose(frac, 1 / 3, atol=0.005)


def test_rbg_edge_delays_pow2_span_bit_sliced_uniform():
    # power-of-two span: 16-bit slices + mask — exactly uniform
    d = np.asarray(delay_ops.sample_edge_delays(
        jax.random.key(1), (401, 400), 0, 4, impl="rbg"))
    assert d.min() >= 0 and d.max() <= 3
    frac = np.bincount(d.ravel(), minlength=4) / d.size
    np.testing.assert_allclose(frac, 0.25, atol=0.005)


def test_rbg_edge_delays_bit_contract():
    """The integer bit contract, scoped as documented: same key -> same
    delays across differently-compiled UNBATCHED programs (eager, jit,
    lax.map lanes — the multi-seed/mesh arm bodies).  vmap is explicitly
    OUT of scope (RngBitGenerator is not batch-invariant under vmap; pins
    that vmap must keep edge_sampler='threefry')."""
    key = jax.random.key(7)
    eager = np.asarray(delay_ops.sample_edge_delays(key, (13, 9), 3, 6, impl="rbg"))
    jitted = np.asarray(jax.jit(
        lambda k: delay_ops.sample_edge_delays(k, (13, 9), 3, 6, impl="rbg")
    )(key))
    np.testing.assert_array_equal(eager, jitted)
    mapped = np.asarray(jax.lax.map(
        lambda k: delay_ops.sample_edge_delays(k, (13, 9), 3, 6, impl="rbg"),
        jnp.stack([key, key]),
    ))
    np.testing.assert_array_equal(mapped[0], eager)
    np.testing.assert_array_equal(mapped[1], eager)


def test_rbg_edge_delays_differ_from_threefry_stream():
    key = jax.random.key(3)
    a = np.asarray(delay_ops.sample_edge_delays(key, (64, 64), 3, 6))
    b = np.asarray(delay_ops.sample_edge_delays(key, (64, 64), 3, 6, impl="rbg"))
    assert (a != b).any()  # distinct streams, same distribution


def test_rbg_edge_delays_rejects_unknown_impl():
    with pytest.raises(ValueError):
        delay_ops.sample_edge_delays(jax.random.key(0), (4,), 3, 6, impl="philox")


def test_exact_chain_hoisted_keys_bit_equal_per_bucket_fold_in():
    """The satellite pin: hoisting the exact chain's key derivation to one
    vmapped fold_in pass is BIT-PRESERVING vs the historical per-bucket
    scalar fold_in (chosen over jax.random.split exactly so every
    seed-pinned exact-sampler trajectory survives the hoist)."""
    probs = delay_ops.roundtrip_probs(3, 6)
    key = jax.random.key(11)
    n = jnp.array([3, 40, 1000], jnp.int32)
    got = delay_ops.sample_bucket_counts(key, n, probs)
    # the pre-hoist construction, replayed literally
    nf = jnp.asarray(n, jnp.float32)
    counts, remaining, p_left = [], nf, 1.0
    for b, pb in enumerate(probs):
        frac = float(min(max(pb / max(p_left, 1e-9), 0.0), 1.0))
        if b == len(probs) - 1 or frac >= 1.0:
            c = remaining
        else:
            c = jax.random.binomial(jax.random.fold_in(key, b), remaining, frac)
        counts.append(c)
        remaining = remaining - c
        p_left -= pb
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp.stack(counts).astype(jnp.int32)))


@pytest.mark.parametrize("mode", ["exact", "normal"])
def test_bucket_counts_moments(mode):
    """Statistical moment pin for both chain modes: per-bucket mean matches
    the multinomial n*p within 3 sigma of the sample mean, totals conserve."""
    probs = delay_ops.uniform_probs(0, 3)
    trials, n_each = 4000, 60
    n = jnp.full((trials,), n_each, jnp.int32)
    c = np.asarray(delay_ops.sample_bucket_counts(
        jax.random.key(5), n, probs, mode=mode))
    np.testing.assert_array_equal(c.sum(0), n_each)
    p = 1 / 3
    se = np.sqrt(n_each * p * (1 - p) / trials)
    for b in range(3):
        assert abs(c[b].mean() - n_each * p) < 4 * se, (mode, b, c[b].mean())


@pytest.mark.parametrize("shape", [(4, 33), (5, 33), (1, 7, 33), (2, 7, 33)])
def test_fast_normal_rows_are_the_stacked_draw_by_index(shape):
    # the same words, sliced before the popcount: bit-equal, for an even and
    # an odd count of rows, by one index and by two
    key = jax.random.key(45)
    whole = np.asarray(delay_ops._fast_normal(key, shape))
    at = [(o,) for o in range(shape[1])] if len(shape) == 3 else [()]
    z = delay_ops._fast_normal_rows(key, shape, at)
    for b in range(shape[0]):
        for o in at:
            np.testing.assert_array_equal(np.asarray(z(b, *o)), whole[(b, *o)])


@pytest.mark.parametrize("mode", ["normal", "exact"])
@pytest.mark.parametrize("nb", [1, 3, 5])
def test_bucket_count_rows_equal_the_stacked_chain(mode, nb):
    # a list of rows stands for their stack: bucket b comes as the list of
    # its rows, bit-equal to the chain's over the stack (models/
    # pbft_round.py's commit wave, a row per send offset); one array comes
    # back as arrays (its prepare wave)
    key = jax.random.key(7)
    rows = [jnp.asarray(np.random.default_rng(o).integers(0, 5000, 41), jnp.int32)
            for o in range(7)]
    probs = np.convolve(np.full(3, 1 / 3), np.full(3, 1 / 3))[:nb]
    probs = probs / probs.sum()
    want = list(delay_ops.bucket_count_chain(key, jnp.stack(rows), probs, mode))
    got = list(delay_ops.bucket_count_rows(key, rows, probs, mode))
    assert len(got) == len(want) == nb
    for g, w in zip(got, want):
        assert isinstance(g, list) and len(g) == 7
        np.testing.assert_array_equal(np.stack(g), np.asarray(w))
    np.testing.assert_array_equal(
        sum(np.stack(g) for g in got), np.stack(rows).astype(np.float32))
    want = list(delay_ops.bucket_count_chain(key, rows[0], probs, mode))
    got = list(delay_ops.bucket_count_rows(key, rows[0], probs, mode))
    assert len(got) == nb
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_bucket_count_chain_yields_what_sample_stacks():
    probs = delay_ops.roundtrip_probs(0, 3)
    key = jax.random.key(9)
    n = jnp.array([[7, 0], [100, 3]], jnp.int32)
    stacked = delay_ops.sample_bucket_counts(key, n, probs)
    chained = jnp.stack(
        list(delay_ops.bucket_count_chain(key, n, probs))
    ).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(stacked), np.asarray(chained))


# --- fused sample-and-push (ops/delivery.py push_* family) -----------------


def test_push_bucket_counts_bit_equal_unfused_compose():
    probs = delay_ops.roundtrip_probs(3, 6)
    key = jax.random.key(2)
    m = jnp.array([40, 0, 7, 100], jnp.int32)
    buf0 = jnp.arange(12 * 4, dtype=jnp.int32).reshape(12, 4)
    fused = dv.push_bucket_counts(buf0, 3, 6, key, m, probs)
    unfused = ring_push_add(
        buf0, 3, 6, delay_ops.sample_bucket_counts(key, m, probs))
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))


def test_push_bucket_counts_expand_matches_expanded_compose():
    probs = delay_ops.uniform_probs(0, 3)
    key = jax.random.key(4)
    m = jnp.array([9, 30], jnp.int32)
    mask = jnp.array([[1, 0, 1], [0, 1, 1]], jnp.int32)  # [N, W]
    buf0 = jnp.zeros((8, 2, 3), jnp.int32)
    fused = dv.push_bucket_counts(
        buf0, 1, 2, key, m, probs, expand=lambda c: c[:, None] * mask)
    cnt = delay_ops.sample_bucket_counts(key, m, probs)
    unfused = ring_push_add(buf0, 1, 2, cnt[:, :, None] * mask[None])
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))


def test_push_roundtrip_stat_bit_equal_compose():
    rt_probs = delay_ops.roundtrip_probs(3, 6)
    key = jax.random.key(6)
    send = jnp.array([True, False, True, True])
    buf0 = jnp.zeros((14, 4), jnp.int32)
    fused = dv.push_roundtrip_reply_counts_stat(
        buf0, 0, 6, key, send, 3, rt_probs)
    unfused = ring_push_add(
        buf0, 0, 6,
        dv.roundtrip_reply_counts_stat(key, send, 3, rt_probs))
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))


def test_push_bcast_slots_stat_bit_equal_compose():
    probs = delay_ops.uniform_probs(3, 6)
    key = jax.random.key(8)
    slot_mat = jnp.zeros((6, 4), jnp.int32).at[2, 3].set(1).at[0, 1].set(2)
    buf0 = jnp.zeros((9, 6, 4), jnp.int32)
    fused = dv.push_bcast_slots_stat(buf0, 2, 3, key, slot_mat, probs)
    unfused = ring_push_add(
        buf0, 2, 3, dv.bcast_slots_stat(key, slot_mat, probs))
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(unfused))


# --- the ring push (ops/ring._push: the DUS chain) --------------------------


def _np_push(buf, t, lo, contrib, op):
    out = np.array(buf)
    d = out.shape[0]
    for b in range(contrib.shape[0]):
        idx = (t + lo + b) % d
        out[idx] = (out[idx] + contrib[b] if op == "add"
                    else np.maximum(out[idx], contrib[b]))
    return out


@pytest.mark.parametrize("where", ["eager", "scan"])
@pytest.mark.parametrize("op", ["add", "max"])
def test_ring_push_matches_numpy_model(op, where):
    from blockchain_simulator_tpu.ops import ring

    push = ring.ring_push_add if op == "add" else ring.ring_push_max
    rng = np.random.default_rng(7)
    d, b, lo, l = 5, 3, 2, 256
    buf0 = rng.integers(0, 1000, (d, l), dtype=np.int32)
    ticks = (0, 1, 2, 3, 4, 123)  # t+lo+b crosses d from t=1: wrap-around
    contribs = rng.integers(0, 1000, (len(ticks), b, l), dtype=np.int32)
    if where == "eager":  # every push on the same ring
        befores = [buf0] * len(ticks)
        afters = [
            np.asarray(push(jnp.asarray(buf0), jnp.int32(t), lo,
                            jnp.asarray(c)))
            for t, c in zip(ticks, contribs)
        ]
    else:  # the production call site: pushes on a scan-carried ring
        def body(buf, x):
            new = push(buf, x[0], lo, x[1])
            return new, new

        _, ys = jax.lax.scan(
            body, jnp.asarray(buf0),
            (jnp.asarray(ticks, jnp.int32), jnp.asarray(contribs)))
        afters = list(np.asarray(ys))
        befores = [buf0] + afters[:-1]
    for t, c, before, after in zip(ticks, contribs, befores, afters):
        np.testing.assert_array_equal(after, _np_push(before, t, lo, c, op))
        rest = sorted(set(range(d)) - {(t + lo + i) % d for i in range(b)})
        assert len(rest) == d - b
        np.testing.assert_array_equal(after[rest], before[rest])
