"""Compile-once sweeps: unified executable registry + dynamic fault operands
+ jax's compile cache (utils/aotcache.py, runner.make_dyn_sim_fn,
parallel/sweep.py).

Pins the three contracts of the compile-amortization layer:

- **Registry semantics**: keyed memoization with hit/miss/eviction stats,
  the ``cached_factory`` decorator (the sanctioned replacement for the old
  per-module ``lru_cache`` factories), and the ``cache`` block on every run
  manifest.
- **Dynamic-f bit-equality**: a fault-count sweep through ONE vmapped
  executable (fault masks computed inside the trace from traced counts)
  returns metrics bit-equal to the static per-fault-config path, and
  compiles exactly one program per fault structure.
- **The persistent cache is jax's own** (``enable_xla_cache``): a second
  process adds no entry, and unreadable entries cost a compile, never the
  run.

Late-alphabet file on purpose: the tier-1 870 s window fills from the front
of the alphabet (ROADMAP.md), so the compile-heavy pins here must not
displace the early suites.
"""

import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from blockchain_simulator_tpu.models import base as base_model
from blockchain_simulator_tpu.parallel.sweep import (
    run_byzantine_sweep,
    run_fault_sweep,
    run_seed_sweep,
)
from blockchain_simulator_tpu.runner import make_dyn_sim_fn
from blockchain_simulator_tpu.utils import aotcache, obs
from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig

REPO = pathlib.Path(__file__).resolve().parent.parent


# ------------------------------------------------------ registry mechanics --


def test_registry_hit_miss_and_eviction():
    reg = aotcache.ExecutableRegistry(maxsize=2)
    built = []

    def build(x):
        built.append(x)
        return f"v{x}"

    assert reg.get("k", (1,), {}, build) == "v1"
    assert reg.get("k", (1,), {}, build) == "v1"  # hit: no rebuild
    assert built == [1]
    assert reg.hits == 1 and reg.misses == 1
    reg.get("k", (2,), {}, build)
    reg.get("k", (3,), {}, build)  # maxsize=2: evicts the LRU entry (1)
    assert reg.evictions == 1 and len(reg) == 2
    reg.get("k", (1,), {}, build)  # evicted: builds again
    assert built == [1, 2, 3, 1]
    # distinct factory names never collide on equal args
    assert reg.get("other", (1,), {}, build) == "v1" and built[-1] == 1
    s = reg.stats()
    assert s["entries"] == 2  # still capped
    assert set(s) == {"hits", "misses", "evictions", "entries", "last_key"}


def test_cached_factory_memoizes_in_shared_registry():
    calls = []

    @aotcache.cached_factory("test-zcache-factory")
    def fac(tag):
        calls.append(tag)
        return object()

    a, b = fac("x"), fac("x")
    assert a is b and calls == ["x"]
    assert fac("y") is not a and calls == ["x", "y"]
    assert fac.__wrapped__ is not None  # lru_cache-style introspection


def test_manifest_carries_cache_block():
    cfg = SimConfig(protocol="pbft", n=8, sim_ms=100)
    rec = obs.manifest(cfg)
    cache = rec["cache"]
    assert isinstance(cache["hits"], int) and isinstance(cache["misses"], int)
    assert set(cache) == {"hits", "misses", "key", "mesh"}


# ------------------------------------------------- dynamic fault operands ---


def test_dyn_fault_masks_match_static():
    import numpy as np

    for nc, nb in [(0, 0), (2, 0), (0, 3), (2, 3), (8, 0)]:
        cfg = SimConfig(
            protocol="pbft", n=8, sim_ms=100,
            faults=FaultConfig(n_crashed=nc, n_byzantine=nb),
        )
        alive_s, honest_s = base_model.fault_masks(cfg, 8)
        alive_d, honest_d = base_model.dyn_fault_masks(8, nc, nb)
        assert np.array_equal(np.asarray(alive_s), np.asarray(alive_d))
        assert np.array_equal(np.asarray(honest_s), np.asarray(honest_d))


def test_canonical_fault_cfg_groups_by_structure():
    cfg = SimConfig(protocol="pbft", n=8, sim_ms=100)
    a = base_model.canonical_fault_cfg(cfg.with_(faults=FaultConfig(n_crashed=3)))
    b = base_model.canonical_fault_cfg(cfg.with_(faults=FaultConfig(n_byzantine=2)))
    assert a == b  # counts are operands, not structure
    c = base_model.canonical_fault_cfg(
        cfg.with_(faults=FaultConfig(drop_prob=0.1, n_crashed=3))
    )
    assert c != a  # drop_prob is structure: separate trace


def test_make_dyn_sim_fn_refuses_mixed():
    cfg = SimConfig(protocol="mixed", n=12, mixed_shards=4, sim_ms=1000)
    with pytest.raises(NotImplementedError, match="mixed"):
        make_dyn_sim_fn(cfg)


# The bit-equality pin (acceptance criterion): the dynamic-operand sweep and
# the static per-point path must agree BIT-FOR-BIT on every metric at every
# pinned (cfg, seed, f) point — runner.make_dyn_sim_fn consumes the same
# PRNG channels, and the canonical-trace trick (forge wave statically
# included, dynamically masked) must be numerically invisible.
PIN_CFG = SimConfig(
    protocol="pbft", n=8, sim_ms=1000, pbft_max_rounds=16, pbft_max_slots=32
)


def test_dynamic_byz_sweep_bit_equal_to_static():
    rows = run_byzantine_sweep(PIN_CFG, f_values=[0, 1, 2], seeds=(0, 1))
    assert len(rows) == 6
    import dataclasses

    for f in (0, 1, 2):
        fc = dataclasses.replace(PIN_CFG.faults, n_byzantine=f, byz_forge=True)
        static = run_seed_sweep(PIN_CFG.with_(faults=fc), seeds=[0, 1])
        dyn = [r for r in rows if r["f"] == f]
        for s_m, d_row in zip(static, dyn):
            got = {k: d_row[k] for k in s_m}
            assert got == s_m, (f, d_row["seed"])
    # the separation the sweep exists to show survives the dynamic path
    assert all(r["forged_commits"] >= 1 for r in rows if r["f"] >= 1)
    assert all(r["forged_commits"] == 0 for r in rows if r["f"] == 0)


def test_dynamic_raft_crash_sweep_bit_equal_to_static():
    """The raft arm of apply_fault_masks (election-deadline re-disarm
    against the traced alive mask, models/base.py) — crashed nodes must
    never start an election on the dynamic path, exactly as in the static
    init."""
    cfg = SimConfig(protocol="raft", n=12, sim_ms=1500)
    fcs = [FaultConfig(n_crashed=3), FaultConfig(n_crashed=2, n_byzantine=2)]
    res = run_fault_sweep(cfg, fcs, seeds=[0])
    for fc in fcs:
        ref = run_seed_sweep(cfg.with_(faults=fc), seeds=[0])[0]
        got = {k: res[fc][0][k] for k in ref}
        assert got == ref, fc


def test_cached_factory_cache_clear_is_per_factory():
    """lru_cache API parity (tools/ablate.py patches ops and rebuilds via
    make_sim_fn.cache_clear()): clearing one factory rebuilds it without
    evicting the other factories sharing the registry."""
    builds = {"a": 0, "b": 0}

    @aotcache.cached_factory("test-zcache-clear-a")
    def fac_a(tag):
        builds["a"] += 1
        return object()

    @aotcache.cached_factory("test-zcache-clear-b")
    def fac_b(tag):
        builds["b"] += 1
        return object()

    a1, b1 = fac_a(1), fac_b(1)
    fac_a.cache_clear()
    assert fac_a(1) is not a1 and builds["a"] == 2  # rebuilt
    assert fac_b(1) is b1 and builds["b"] == 1      # untouched
    from blockchain_simulator_tpu.runner import make_sim_fn

    assert callable(make_sim_fn.cache_clear)  # the ablate.py contract


def test_fault_sweep_crash_group_single_executable():
    # fresh structure (unique sim_ms) -> a cold registry key for this test
    cfg = SimConfig(
        protocol="pbft", n=8, sim_ms=1050, pbft_max_rounds=16,
        pbft_max_slots=32,
    )
    fcs = [FaultConfig(n_crashed=c) for c in (0, 1, 2, 3)]
    s0 = aotcache.registry.stats()
    res = run_fault_sweep(cfg, fcs, seeds=[0])
    s1 = aotcache.registry.stats()
    # ONE miss for the whole 4-level sweep: the dynamic batched executable
    assert s1["misses"] - s0["misses"] == 1
    assert [m["blocks_final_all_nodes"] for fc in fcs for m in res[fc]]
    # a repeat sweep of the same structure is a pure registry hit
    res2 = run_fault_sweep(cfg, fcs, seeds=[0])
    s2 = aotcache.registry.stats()
    assert s2["misses"] == s1["misses"]
    assert s2["hits"] == s1["hits"] + 1
    assert res2 == res  # deterministic replay through the cached executable


# ------------------------------------------------------ jax's compile cache ---

_XLA_CACHE_CHILD = """
import jax, jax.numpy as jnp
from blockchain_simulator_tpu.utils import aotcache
assert aotcache.enable_xla_cache() == {path!r}
f = jax.jit(lambda x: (x * 2 + 1).sum())
print(int(f(jnp.arange(16, dtype=jnp.int32))))
"""


def test_xla_cache_fills_once_and_survives_unreadable_entries(tmp_path):
    """The one persistent cache (``enable_xla_cache``) on a toy function,
    each run a fresh process: the first fills the directory, the second
    adds no entry, and with every entry truncated or bit-flipped a third
    still exits 0 with the same answer — jax warns and compiles."""
    cache = tmp_path / "xla"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache), PYTHONPATH=str(REPO))

    def run():
        proc = subprocess.run(
            [sys.executable, "-c", _XLA_CACHE_CHILD.format(path=str(cache))],
            env=env, cwd=tmp_path, capture_output=True, text=True,
            timeout=120)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return proc.stdout.strip()

    first = run()
    filled = sorted(p.name for p in cache.iterdir())
    assert first == "256" and filled
    assert run() == first
    assert sorted(p.name for p in cache.iterdir()) == filled
    for i, name in enumerate(filled):
        blob = bytearray((cache / name).read_bytes())
        if i % 2:
            blob = blob[:len(blob) // 2]
        else:
            blob[len(blob) // 2] ^= 0x40
        (cache / name).write_bytes(bytes(blob))
    assert run() == first


def test_aot_cached_registry_hit_skips_recompile():
    cfg = SimConfig(protocol="pbft", n=8, sim_ms=330)
    from blockchain_simulator_tpu.runner import make_sim_fn

    sim = make_sim_fn(cfg)
    key = jax.random.key(0)
    built = []

    def build():
        built.append(1)
        return sim

    c1, _ = aotcache.aot_cached("t-hit", build, (key,), cfg=cfg)
    c2, _ = aotcache.aot_cached("t-hit", build, (key,), cfg=cfg)
    assert c1 is c2 and built == [1]


# -------------------------------------------------- compare + CI plumbing ---


def test_bench_compare_never_gates_compile_s(tmp_path):
    """A 40x compile_s IMPROVEMENT (warm cache) must not trip the
    drop-means-regression throughput rule (same carve-out as *_findings)."""
    for i, (val, comp) in enumerate([(100.0, 20.0), (101.0, 0.5)], start=1):
        (tmp_path / f"BENCH_r{i:02d}.json").write_text(json.dumps({
            "n": i, "rc": 0,
            "parsed": {"metric": "m_rounds_per_sec", "value": val,
                       "compile_s": comp, "backend": "cpu"},
        }))
    proc = subprocess.run(
        [sys.executable, str(REPO / "tools" / "bench_compare.py"),
         str(tmp_path / "BENCH_r01.json"), str(tmp_path / "BENCH_r02.json")],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "m_rounds_per_sec_compile_s" in proc.stdout  # charted...
    assert "REGRESSION" not in proc.stdout              # ...never gated


@pytest.mark.slow
def test_warm_bench_script_cold_vs_warm(tmp_path):
    """tools/warm_bench.sh end-to-end at toy scale: two bench rehearsals
    against one persistent cache placed with jax's own variable; the
    artifact records both compile_s, the second run adds no cache entries
    and — the first being cold — compiles faster (the lint.sh-chained CI
    shape; unset, the cache is the fixed <repo>/.jax_cache)."""
    out = tmp_path / "warm.json"
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.update({
        "WARM_BENCH_N": "128", "WARM_BENCH_ROUNDS": "10",
        "WARM_BENCH_OUT": str(out), "JAX_PLATFORMS": "cpu",
        "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "xla"),
    })
    proc = subprocess.run(
        ["bash", str(REPO / "tools" / "warm_bench.sh")],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    rec = json.loads(out.read_text())
    assert rec["cache_dir"] == str(tmp_path / "xla")
    assert rec["first_run_was_cold"]
    ents = rec["cache_entries"]
    assert ents["before"] == 0 < ents["after_first"] == ents["after_second"]
    assert rec["warm"]["compile_s"] < rec["cold"]["compile_s"]
    assert rec["device"]["platform"] == "cpu"
