"""The audit surface: every registered executable factory, as traceable specs.

Two halves keep each other honest:

- :func:`discover_factories` finds every ``@aotcache.cached_factory("name")``
  registration in the source tree by AST (reusing the jaxlint alias
  machinery — the same no-import contract: discovery must not trigger what
  it polices);
- :func:`build_catalog` constructs one or more :class:`ProgramSpec` per
  factory name — tiny audit-scale configs (n=8, a few hundred ticks) chosen
  so every engine arm the factory can dispatch to gets traced: tick engines
  for all four protocols, the round/heartbeat fast paths, the vmapped sweep
  programs (static and dynamic-fault-operand), the shard_map wrappers, and
  the probe-traced variants.

A factory name discovered in source with no covering spec is an
``unaudited-factory`` finding (lint/graph/audit.py), so growing a new
factory without growing its audit fails the gate — the completeness
analog of jaxlint's whole-repo sweep.

Specs are traced at aval level only (``jax.eval_shape`` for states,
``ShapeDtypeStruct`` keys): building the catalog never runs a simulation.
Configs deliberately pin ``stat_sampler="exact"`` where sampling appears so
the traced IR is identical across the jax float-path variations the normal
CLT sampler is allowed (parallel/sweep.py bit-equality caveat).
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Callable

from blockchain_simulator_tpu.lint import common

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

# cached_factory resolutions the discovery matcher accepts (the same set the
# AST static-arg-recompile-hazard rule sanctions).
_FACTORY_CALLS = frozenset({
    "aotcache.cached_factory",
    "blockchain_simulator_tpu.utils.aotcache.cached_factory",
    "utils.aotcache.cached_factory",
    "cached_factory",
})


def discover_factories(paths: list[str] | None = None) -> dict[str, list[str]]:
    """{factory name: [repo-relative files registering it]} over ``paths``
    (default: the package tree).  Pure AST — nothing is imported."""
    if paths is None:
        paths = [os.path.join(REPO_ROOT, "blockchain_simulator_tpu")]
    found: dict[str, list[str]] = {}
    for root in paths:
        files = []
        if os.path.isfile(root):
            files = [root]
        else:
            for dirpath, dirnames, filenames in os.walk(root):
                dirnames[:] = sorted(
                    d for d in dirnames
                    if d != "__pycache__" and not d.startswith(".")
                )
                files.extend(
                    os.path.join(dirpath, fn)
                    for fn in sorted(filenames) if fn.endswith(".py")
                )
        for fp in files:
            try:
                with open(fp, encoding="utf-8") as f:
                    tree = ast.parse(f.read())
            except (OSError, SyntaxError):
                continue
            aliases = common.import_aliases(tree)
            rel = os.path.relpath(fp, REPO_ROOT).replace(os.sep, "/")
            for node in ast.walk(tree):
                if not (isinstance(node, ast.Call) and node.args):
                    continue
                r = common.resolve(node.func, aliases)
                if r not in _FACTORY_CALLS:
                    continue
                arg = node.args[0]
                if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
                    found.setdefault(arg.value, [])
                    if rel not in found[arg.value]:
                        found[arg.value].append(rel)
    return found


def _walk_py_files(paths: list[str] | None) -> list[str]:
    if paths is None:
        paths = [os.path.join(REPO_ROOT, "blockchain_simulator_tpu")]
    files = []
    for root in paths:
        if os.path.isfile(root):
            files.append(root)
            continue
        for dirpath, dirnames, filenames in os.walk(root):
            dirnames[:] = sorted(
                d for d in dirnames
                if d != "__pycache__" and not d.startswith(".")
            )
            files.extend(
                os.path.join(dirpath, fn)
                for fn in sorted(filenames) if fn.endswith(".py")
            )
    return files


def discover_mesh_factories(paths: list[str] | None = None) -> dict:
    """{factory name: [repo-relative files]} of every ``cached_factory``
    registration whose decorated function takes a ``mesh`` parameter —
    the mesh-capable subset of :func:`discover_factories`, and the
    completeness surface of the comms audit (lint/comms): a mesh factory
    with no comms spec is an ``unaudited-mesh-factory`` finding, the
    post-SPMD analog of ``unaudited-factory``.  Pure AST, same no-import
    contract."""
    found: dict = {}
    for fp in _walk_py_files(paths):
        try:
            with open(fp, encoding="utf-8") as f:
                tree = ast.parse(f.read())
        except (OSError, SyntaxError):
            continue
        aliases = common.import_aliases(tree)
        rel = os.path.relpath(fp, REPO_ROOT).replace(os.sep, "/")
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            a = node.args
            params = {
                arg.arg for arg in
                list(a.posonlyargs) + list(a.args) + list(a.kwonlyargs)
            }
            if "mesh" not in params:
                continue
            for dec in node.decorator_list:
                if not (isinstance(dec, ast.Call) and dec.args):
                    continue
                if common.resolve(dec.func, aliases) not in _FACTORY_CALLS:
                    continue
                arg = dec.args[0]
                if isinstance(arg, ast.Constant) and isinstance(
                    arg.value, str
                ):
                    found.setdefault(arg.value, [])
                    if rel not in found[arg.value]:
                        found[arg.value].append(rel)
    return found


@dataclasses.dataclass
class ProgramSpec:
    """One traceable program of the audit surface.

    ``build()`` (lazy — first jax touch) returns ``(fn, example_args)``
    where ``fn`` is jitted or plain and ``example_args`` may be aval-level
    (``ShapeDtypeStruct`` pytrees).  ``factory`` is the registry name this
    spec covers; specs sharing a ``divergence_group`` must trace to ONE
    fingerprint (the registry-key-divergence contract — one key, one
    executable).  ``budget=False`` skips the FLOP/byte pin (divergence
    twins re-measure a primary program's graph).  ``memory=True``
    additionally COMPILES the program and pins its memory_analysis axes
    (peak temp + argument bytes) — compilation costs real minutes across
    the catalog, so only the representative programs whose RSS stories
    the ROADMAP tracks opt in."""

    program: str
    factory: str
    build: Callable[[], tuple]
    divergence_group: str | None = None
    budget: bool = True
    memory: bool = False


# ------------------------------------------------------------- aval helpers

def _key_sds():
    import jax

    return jax.eval_shape(lambda: jax.random.key(0))


def _keys_sds(b: int):
    import jax
    import jax.numpy as jnp

    return jax.eval_shape(
        lambda: jax.vmap(jax.random.key)(jnp.arange(b, dtype=jnp.uint32))
    )


def _i32_sds(shape=()):
    import jax
    import jax.numpy as jnp

    return jax.ShapeDtypeStruct(shape, jnp.int32)


def _raw(factory_wrapper):
    """The undecorated factory (``functools.wraps`` sets ``__wrapped__``):
    audit builds must not populate the process-wide executable registry —
    registry hit/miss stats land on run manifests, and an audit is not a
    run."""
    return getattr(factory_wrapper, "__wrapped__", factory_wrapper)


# ------------------------------------------------------------ audit configs

def audit_configs() -> dict[str, "object"]:
    """The named audit-scale SimConfigs, one per engine arm.  Centralized so
    tests and the catalog agree on the exact traced surface."""
    from blockchain_simulator_tpu.utils.config import SimConfig

    return {
        # tick engines, one per protocol (schedule resolves to 'tick' at n=8)
        "pbft_tick": SimConfig(protocol="pbft", n=8, sim_ms=200,
                               stat_sampler="exact"),
        "raft_tick": SimConfig(protocol="raft", n=8, sim_ms=200,
                               stat_sampler="exact"),
        "paxos_tick": SimConfig(protocol="paxos", n=8, sim_ms=200,
                                stat_sampler="exact"),
        "mixed_tick": SimConfig(protocol="mixed", n=8, mixed_shards=2,
                                sim_ms=200, schedule="tick",
                                stat_sampler="exact"),
        # topology axis (topo/): kregular gather overlays — edge and stat
        # delivery — and the two-level committee hierarchy.  Degree 3 keeps
        # K = 4 < N = 8 so the traced gathers are REAL sparse gathers, not
        # the identity full-overlay case the bit-equality tests pin.
        "pbft_kreg": SimConfig(protocol="pbft", n=8, sim_ms=200,
                               fidelity="clean", topology="kregular",
                               degree=3, stat_sampler="exact"),
        "pbft_kreg_stat": SimConfig(protocol="pbft", n=8, sim_ms=200,
                                    fidelity="clean", topology="kregular",
                                    degree=3, delivery="stat",
                                    stat_sampler="exact"),
        "raft_kreg": SimConfig(protocol="raft", n=8, sim_ms=200,
                               fidelity="clean", topology="kregular",
                               degree=3, stat_sampler="exact"),
        "raft_kreg_stat": SimConfig(protocol="raft", n=8, sim_ms=200,
                                    fidelity="clean", topology="kregular",
                                    degree=3, delivery="stat",
                                    stat_sampler="exact"),
        "paxos_kreg": SimConfig(protocol="paxos", n=8, sim_ms=200,
                                fidelity="clean", topology="kregular",
                                degree=3, stat_sampler="exact"),
        "pbft_comm": SimConfig(protocol="pbft", n=8, sim_ms=200,
                               topology="committee", committees=2,
                               stat_sampler="exact"),
        # Raft with terms (SimConfig.raft_terms): the per-edge full-mesh
        # clean arm, flat and as a committee stack of independent groups
        "raft_terms": SimConfig(protocol="raft", n=8, sim_ms=200,
                                raft_terms=True, model_serialization=False,
                                stat_sampler="exact"),
        "raft_terms_comm": SimConfig(protocol="raft", n=10, sim_ms=200,
                                     raft_terms=True,
                                     model_serialization=False,
                                     topology="committee", committees=2,
                                     stat_sampler="exact"),
        # a crash schedule over it (FaultConfig.crashes: the fault phase at
        # the head of the tick, the per-crash records at its end)
        "raft_crash": SimConfig(protocol="raft", n=8, sim_ms=200,
                                raft_terms=True, model_serialization=False,
                                stat_sampler="exact",
                                faults=_crash_schedule()),
        "raft_crash_comm": SimConfig(protocol="raft", n=10, sim_ms=200,
                                     raft_terms=True,
                                     model_serialization=False,
                                     topology="committee", committees=2,
                                     stat_sampler="exact",
                                     faults=_crash_schedule()),
        # link classes (SimConfig.link_classes): per-edge PBFT whose channels
        # read sender-side delay lines, three classes and an asymmetric
        # matrix, so that the traced reads are real class-by-class reads
        "pbft_geo": SimConfig(protocol="pbft", n=8, sim_ms=200,
                              quorum_rule="2f1", stat_sampler="exact",
                              link_classes=(4, 3, 1),
                              link_class_delay_ms=((3, 12, 30), (10, 4, 25),
                                                   (30, 20, 5))),
        # fast paths, explicitly scheduled (eligibility asserted in tests)
        "pbft_round": SimConfig(protocol="pbft", n=8, sim_ms=200,
                                delivery="stat", schedule="round",
                                model_serialization=False,
                                stat_sampler="exact"),
        "raft_hb": SimConfig(protocol="raft", n=8, sim_ms=400,
                             delivery="stat", schedule="round",
                             stat_sampler="exact"),
        "mixed_fast": SimConfig(protocol="mixed", n=8, mixed_shards=2,
                                sim_ms=400, delivery="stat",
                                schedule="round", stat_sampler="exact"),
    }


def _crash_schedule():
    from blockchain_simulator_tpu.utils.config import FaultConfig

    return FaultConfig(crashes=2, first_ms=40, period_ms=80, downtime_ms=30)


def _audit_mesh():
    """A 2-device nodes mesh for the shard_map wrappers (the degenerate
    sweep axis matches parallel/mesh.make_mesh's layout)."""
    from blockchain_simulator_tpu.parallel.mesh import make_mesh

    return make_mesh(n_node_shards=2, n_sweep=1)


# ---------------------------------------------------------------- catalog

def build_catalog() -> list[ProgramSpec]:
    """Every audited program.  Lazy throughout: importing this module (or
    calling this function) touches no backend — each spec's ``build`` does,
    on first trace."""
    cfgs = audit_configs()
    specs: list[ProgramSpec] = []

    # --- runner.make_sim_fn ("sim"): every engine arm -------------------
    def sim_spec(arm):
        def build():
            from blockchain_simulator_tpu import runner

            return _raw(runner.make_sim_fn)(cfgs[arm]), (_key_sds(),)

        return ProgramSpec(f"sim.{arm}", "sim", build)

    for arm in ("pbft_tick", "pbft_round", "raft_tick", "raft_hb",
                "paxos_tick", "mixed_tick", "mixed_fast",
                # the topology axis: every gather-overlay arm (edge + stat
                # per protocol) and the committee lax.map body — the new
                # programs must come in budgeted, and their gather bodies
                # scatter-free beyond the dense engines' baselined [W]-fold
                # accumulators (tests/test_zztopo.py counts them)
                "pbft_kreg", "pbft_kreg_stat", "raft_kreg",
                "raft_kreg_stat", "paxos_kreg", "pbft_comm", "raft_terms",
                "raft_terms_comm", "raft_crash", "raft_crash_comm",
                "pbft_geo"):
        specs.append(sim_spec(arm))

    # --- runner.make_segment_fn ("segment") -----------------------------
    def build_segment():
        import jax

        from blockchain_simulator_tpu import runner
        from blockchain_simulator_tpu.models.base import get_protocol

        cfg = cfgs["pbft_tick"]
        proto = get_protocol(cfg.protocol)
        state, bufs = jax.eval_shape(
            lambda k: proto.init(cfg, jax.random.fold_in(k, 0x1217)),
            _key_sds(),
        )
        seg = _raw(runner.make_segment_fn)(cfg, 50)
        return seg, (_key_sds(), state, bufs, _i32_sds())

    specs.append(ProgramSpec("segment.pbft_tick", "segment", build_segment))

    # --- parallel/sweep._batched_fn ("sweep-batched") -------------------
    def build_batched():
        from blockchain_simulator_tpu.parallel import sweep

        return _raw(sweep._batched_fn)(cfgs["pbft_tick"], None), (_keys_sds(2),)

    specs.append(ProgramSpec(
        "sweep_batched.pbft_tick", "sweep-batched", build_batched
    ))

    # --- parallel/sweep.dyn_batched_fn ("sweep-batched-dynf") -----------
    # Divergence twins: fault configs that differ only in COUNTS must trace
    # to ONE jaxpr after canonicalization — otherwise run_fault_sweep's
    # same-structure grouping silently recompiles per point (the leak the
    # registry-key-divergence rule exists to catch).
    def dynf_spec(name, base_arm, fc_kw, group, budget):
        def build():
            import dataclasses as _dc

            import jax

            from blockchain_simulator_tpu import runner
            from blockchain_simulator_tpu.models.base import lane_vmap

            cfg = cfgs[base_arm]
            cfg = cfg.with_(faults=_dc.replace(cfg.faults, **fc_kw))
            # make_dyn_sim_fn canonicalizes internally — the twins' traces
            # must come out identical, which is exactly what the
            # registry-key-divergence rule asserts.  Per-call jit is fine:
            # audit builds trace once and never execute.
            fn = jax.jit(lane_vmap(runner.make_dyn_sim_fn(cfg)))  # jaxlint: disable=static-arg-recompile-hazard
            return fn, (_keys_sds(2), _i32_sds((2,)), _i32_sds((2,)))

        return ProgramSpec(name, "sweep-batched-dynf", build,
                           divergence_group=group, budget=budget)

    specs.append(dynf_spec("sweep_dynf.pbft", "pbft_tick",
                           {"n_byzantine": 1}, "dynf:pbft_tick", True))
    specs.append(dynf_spec("sweep_dynf.pbft_b2", "pbft_tick",
                           {"n_byzantine": 2}, "dynf:pbft_tick", False))
    specs.append(dynf_spec("sweep_dynf.raft", "raft_tick",
                           {"n_crashed": 1}, "dynf:raft_tick", True))
    specs.append(dynf_spec("sweep_dynf.raft_c2", "raft_tick",
                           {"n_crashed": 2}, "dynf:raft_tick", False))
    # topology-axis twins: ONE executable per (protocol, topology, fault
    # structure) — fault counts over one kregular overlay / committee
    # hierarchy must trace to one fingerprint, or topology sweeps silently
    # recompile per fault level (the ISSUE 15 registry pin)
    specs.append(dynf_spec("sweep_dynf.pbft_kreg", "pbft_kreg",
                           {"n_crashed": 1}, "dynf:pbft_kreg", True))
    specs.append(dynf_spec("sweep_dynf.pbft_kreg_c2", "pbft_kreg",
                           {"n_crashed": 2}, "dynf:pbft_kreg", False))
    specs.append(dynf_spec("sweep_dynf.pbft_comm", "pbft_comm",
                           {"n_crashed": 1}, "dynf:pbft_comm", True))
    specs.append(dynf_spec("sweep_dynf.pbft_comm_c2", "pbft_comm",
                           {"n_crashed": 2}, "dynf:pbft_comm", False))

    # --- parallel/sweep.mesh_dyn_batched_fn ("partition-dyn-sweep") -----
    # The mesh-partitioned sweep executable (parallel/partition.py layer):
    # shard_map over the batch axis, per-device lax.map of the unvmapped
    # dyn sim.  Divergence twins mirror the dynf pair — fault configs
    # differing only in counts must trace to ONE fingerprint per mesh, or
    # a mesh sweep silently recompiles per point.  The nodes arm traces
    # the explicit-sharding pjit path (node axis sharded for large n).
    def partition_dynf_spec(name, fc_kw, sweep_n, node_n, group, budget):
        def build():
            import dataclasses as _dc

            from blockchain_simulator_tpu.parallel import sweep
            from blockchain_simulator_tpu.parallel.mesh import make_mesh

            cfg = cfgs["pbft_tick"]
            cfg = cfg.with_(faults=_dc.replace(cfg.faults, **fc_kw))
            mesh = make_mesh(n_node_shards=node_n, n_sweep=sweep_n)
            fn = _raw(sweep.mesh_dyn_batched_fn)(cfg, mesh)
            b = max(sweep_n, 2)
            return fn, (_keys_sds(b), _i32_sds((b,)), _i32_sds((b,)))

        return ProgramSpec(name, "partition-dyn-sweep", build,
                           divergence_group=group, budget=budget)

    specs.append(partition_dynf_spec(
        "partition_dynf.pbft", {"n_byzantine": 1}, 2, 1,
        "partition-dynf:pbft_tick", True))
    specs.append(partition_dynf_spec(
        "partition_dynf.pbft_b2", {"n_byzantine": 2}, 2, 1,
        "partition-dynf:pbft_tick", False))
    specs.append(partition_dynf_spec(
        "partition_dynf.pbft_nodes", {"n_byzantine": 1}, 1, 2,
        None, True))

    # --- parallel/sweep.multi_seed_fn ("multi-seed-tick") ---------------
    # The single-device multi-seed Monte Carlo executable: lax.map over the
    # UNVMAPPED dyn sim (ISSUE 13).  Its whole reason to exist is the
    # scatter-free body (#0i), so its budget entry carries NO baselined
    # scatter findings — any scatter lowering in this program is a NEW
    # slow-lowering-confirmed finding and fails the gate.  Divergence
    # twins: fault-count (and seed — canonical_fault_cfg normalizes it)
    # changes at one seed count must share ONE fingerprint, so a sweep
    # tile's level never mints a second executable.
    def multi_seed_spec(name, arm, fc_kw, seed, group, budget):
        def build():
            import dataclasses as _dc

            from blockchain_simulator_tpu.parallel import sweep

            cfg = cfgs[arm].with_(seed=seed)
            cfg = cfg.with_(faults=_dc.replace(cfg.faults, **fc_kw))
            from blockchain_simulator_tpu.models.base import canonical_fault_cfg

            fn = _raw(sweep.multi_seed_fn)(canonical_fault_cfg(cfg), 2)
            return fn, (_keys_sds(2), _i32_sds((2,)), _i32_sds((2,)))

        return ProgramSpec(name, "multi-seed-tick", build,
                           divergence_group=group, budget=budget)

    specs.append(multi_seed_spec("multi_seed.pbft", "pbft_tick",
                                 {"n_byzantine": 1}, 0,
                                 "multi-seed:pbft_tick", True))
    specs.append(multi_seed_spec("multi_seed.pbft_b2_s7", "pbft_tick",
                                 {"n_byzantine": 2}, 7,
                                 "multi-seed:pbft_tick", False))
    specs.append(multi_seed_spec("multi_seed.raft", "raft_tick",
                                 {"n_crashed": 1}, 0, None, True))

    # --- serve/dispatch._solo_fn ("serve-solo") -------------------------
    # The scenario server's un-vmapped degrade/solo path.  Divergence
    # twins mirror the dynf pair: requests differing only in fault counts
    # (or seed — canonical_fault_cfg normalizes both) must trace to ONE
    # fingerprint, or the server silently recompiles per request.
    def serve_solo_spec(name, fc_kw, seed, budget):
        def build():
            import dataclasses as _dc

            from blockchain_simulator_tpu.serve import dispatch

            cfg = cfgs["pbft_tick"].with_(seed=seed)
            cfg = cfg.with_(faults=_dc.replace(cfg.faults, **fc_kw))
            fn = _raw(dispatch._solo_fn)(cfg)
            return fn, (_key_sds(), _i32_sds(), _i32_sds())

        return ProgramSpec(name, "serve-solo", build,
                           divergence_group="serve-solo:pbft_tick",
                           budget=budget)

    specs.append(serve_solo_spec("serve_solo.pbft", {"n_byzantine": 1}, 0,
                                 True))
    specs.append(serve_solo_spec("serve_solo.pbft_b2_s7", {"n_byzantine": 2},
                                 7, False))

    # --- parallel/shard.py factories ------------------------------------
    def shard_spec(program, factory, fget, arm):
        def build():
            fn = fget()(cfgs[arm], _audit_mesh())
            return fn, (_key_sds(),)

        return ProgramSpec(program, factory, build)

    def _shard_mod():
        from blockchain_simulator_tpu.parallel import shard

        return shard

    specs.append(shard_spec(
        "shard.sim_tick", "shard-sim",
        lambda: _raw(_shard_mod().make_sharded_sim_fn), "pbft_tick"))
    specs.append(shard_spec(
        "shard.pbft_round", "shard-round",
        lambda: _raw(_shard_mod()._make_sharded_round_fn), "pbft_round"))
    specs.append(shard_spec(
        "shard.raft_hb", "shard-raft-hb",
        lambda: _raw(_shard_mod()._make_sharded_raft_hb_fn), "raft_hb"))
    specs.append(shard_spec(
        "shard.mixed_fast", "shard-mixed",
        lambda: _raw(_shard_mod()._make_sharded_mixed_fast_fn), "mixed_fast"))

    # --- parallel/sweep.sharded_topo_sim_fn ("shard-topo-sim") ----------
    # The node-dim-sharded overlay programs (ISSUE 16).  The kregular arm
    # is audited through ``sim.partitioned`` + ``sim.table_avals`` — the
    # pjit callable with the [N, K+1] overlay tables as OPERANDS — so the
    # traced jaxpr proves the tables stopped being baked constants
    # (large-jaxpr-constant stays clean by construction, not by waiver).
    # Divergence twins: fault counts over one kregular overlay must trace
    # to ONE fingerprint per mesh (the one-executable-per-(protocol,
    # topology, fault structure, mesh) registry pin).
    def shard_topo_spec(name, arm, fc_kw, group, budget):
        def build():
            import dataclasses as _dc

            from blockchain_simulator_tpu.models.base import canonical_fault_cfg
            from blockchain_simulator_tpu.parallel import sweep

            cfg = cfgs[arm]
            if fc_kw:
                cfg = cfg.with_(faults=_dc.replace(cfg.faults, **fc_kw))
            sim = _raw(sweep.sharded_topo_sim_fn)(
                canonical_fault_cfg(cfg), _audit_mesh()
            )
            args = (_key_sds(), _i32_sds(), _i32_sds())
            if hasattr(sim, "partitioned"):
                return sim.partitioned, args + tuple(sim.table_avals)
            return sim, args

        return ProgramSpec(name, "shard-topo-sim", build,
                           divergence_group=group, budget=budget)

    specs.append(shard_topo_spec("shard_topo.pbft_kreg", "pbft_kreg",
                                 {"n_crashed": 1}, "shard-topo:pbft_kreg",
                                 True))
    specs.append(shard_topo_spec("shard_topo.pbft_kreg_c2", "pbft_kreg",
                                 {"n_crashed": 2}, "shard-topo:pbft_kreg",
                                 False))
    specs.append(shard_topo_spec("shard_topo.raft_kreg", "raft_kreg",
                                 {}, None, True))
    specs.append(shard_topo_spec("shard_topo.pbft_comm", "pbft_comm",
                                 {"n_crashed": 1}, None, True))

    # --- utils/trace.py factories ---------------------------------------
    def build_trace_tick():
        from blockchain_simulator_tpu.utils import trace

        return _raw(trace._tick_traced_fn)(cfgs["pbft_tick"]), (_key_sds(),)

    specs.append(ProgramSpec("trace.tick", "trace-tick", build_trace_tick))

    def build_trace_round():
        from blockchain_simulator_tpu.utils import trace

        return (_raw(trace._pbft_round_traced_fn)(cfgs["pbft_round"]),
                (_key_sds(),))

    specs.append(ProgramSpec(
        "trace.pbft_round", "trace-pbft-round", build_trace_round
    ))

    # The raft_hb / mixed trace factories return several programs (the host
    # drives the phase split); every one of them is an executable the
    # registry serves, so every one is audited.  Downstream example args
    # come from eval_shape chains — still nothing executes.
    def _raft_hb_fns():
        from blockchain_simulator_tpu.utils import trace

        return _raw(trace._raft_hb_traced_fns)(cfgs["raft_hb"])

    def build_hb_prefix():
        return _raft_hb_fns()[0], (_key_sds(),)

    def build_hb_steady():
        import jax

        prefix, steady, _ = _raft_hb_fns()
        carry, _ys, _ok, h = jax.eval_shape(prefix, _key_sds())
        return steady, (carry[0], h, _key_sds())

    def build_hb_cont():
        import jax

        prefix, _, cont = _raft_hb_fns()
        carry, _ys, _ok, _h = jax.eval_shape(prefix, _key_sds())
        return cont, (carry, _key_sds())

    specs.append(ProgramSpec(
        "trace.raft_hb_prefix", "trace-raft-hb", build_hb_prefix))
    specs.append(ProgramSpec(
        "trace.raft_hb_steady", "trace-raft-hb", build_hb_steady))
    specs.append(ProgramSpec(
        "trace.raft_hb_cont", "trace-raft-hb", build_hb_cont))

    def _mixed_fns():
        from blockchain_simulator_tpu.utils import trace

        return _raw(trace._mixed_traced_fns)(cfgs["mixed_fast"])

    def build_mx_prefix():
        return _mixed_fns()[0], (_key_sds(),)

    def build_mx_finish():
        import jax

        prefix, finish, _, _ = _mixed_fns()
        carry, _ok, h_s = jax.eval_shape(prefix, _key_sds())
        return finish, (carry, h_s, _key_sds())

    def build_mx_prefix_probed():
        return _mixed_fns()[2], (_key_sds(),)

    def build_mx_cont():
        import jax

        _, _, prefix_probed, cont = _mixed_fns()
        carry, _ys = jax.eval_shape(prefix_probed, _key_sds())
        return cont, (carry, _key_sds())

    specs.append(ProgramSpec(
        "trace.mixed_prefix", "trace-mixed", build_mx_prefix))
    specs.append(ProgramSpec(
        "trace.mixed_finish", "trace-mixed", build_mx_finish))
    specs.append(ProgramSpec(
        "trace.mixed_prefix_probed", "trace-mixed", build_mx_prefix_probed))
    specs.append(ProgramSpec(
        "trace.mixed_cont", "trace-mixed", build_mx_cont))

    # --- utils/trace._committee_traced_fn ("trace-committee") -----------
    # The committee --trace arm (ISSUE 17 satellite: the old typed refusal
    # became a stacked [C, T] probe program).  Taps ride inside the jit, so
    # the host-callback rule audits it like every consensus program.
    def build_trace_committee():
        from blockchain_simulator_tpu.utils import trace

        return (_raw(trace._committee_traced_fn)(cfgs["pbft_comm"]),
                (_key_sds(),))

    specs.append(ProgramSpec(
        "trace.committee", "trace-committee", build_trace_committee))

    # --- obsim/build.py factories ("consobs-*") -------------------------
    # The armed twins of the dyn-fault programs (ISSUE 17): probe taps +
    # monitors as extra scan outputs.  Audited for the same contracts as
    # their disarmed twins — no host callback in the HLO (the taps are
    # traced data, the telemetry hook is host-side in obsim/host.py), no
    # scatter in the batched bodies — plus divergence twins pinning ONE
    # executable per (fault structure, probe config): arming probes must
    # not reintroduce the per-fault-level recompile leak.
    def _pcfg():
        from blockchain_simulator_tpu.obsim import schema as obsim_schema

        return obsim_schema.ProbeConfig()

    def consobs_solo_spec(name, arm, fc_kw, group, budget):
        def build():
            import dataclasses as _dc

            from blockchain_simulator_tpu.obsim import build as obsim_build

            cfg = cfgs[arm]
            if fc_kw:
                cfg = cfg.with_(faults=_dc.replace(cfg.faults, **fc_kw))
            fn = _raw(obsim_build.probed_solo_fn)(cfg, _pcfg())
            return fn, (_key_sds(), _i32_sds(), _i32_sds())

        return ProgramSpec(name, "consobs-solo", build,
                           divergence_group=group, budget=budget)

    specs.append(consobs_solo_spec("consobs.solo_pbft", "pbft_tick",
                                   {"n_byzantine": 1},
                                   "consobs-solo:pbft_tick", True))
    specs.append(consobs_solo_spec("consobs.solo_pbft_b2", "pbft_tick",
                                   {"n_byzantine": 2},
                                   "consobs-solo:pbft_tick", False))
    specs.append(consobs_solo_spec("consobs.solo_comm", "pbft_comm",
                                   {}, None, True))
    specs.append(consobs_solo_spec("consobs.solo_raft_hb", "raft_hb",
                                   {}, None, True))
    specs.append(consobs_solo_spec("consobs.solo_pbft_round", "pbft_round",
                                   {}, None, True))

    def consobs_batched_spec(name, fc_kw, multi_seed, group, budget):
        def build():
            import dataclasses as _dc

            from blockchain_simulator_tpu.obsim import build as obsim_build

            cfg = cfgs["pbft_tick"]
            cfg = cfg.with_(faults=_dc.replace(cfg.faults, **fc_kw))
            fn = _raw(obsim_build.probed_batched_fn)(
                cfg, _pcfg(), multi_seed=multi_seed
            )
            return fn, (_keys_sds(2), _i32_sds((2,)), _i32_sds((2,)))

        return ProgramSpec(name, "consobs-batched", build,
                           divergence_group=group, budget=budget)

    specs.append(consobs_batched_spec(
        "consobs.batched_pbft", {"n_byzantine": 1}, False,
        "consobs-batched:pbft_tick", True))
    specs.append(consobs_batched_spec(
        "consobs.batched_pbft_b2", {"n_byzantine": 2}, False,
        "consobs-batched:pbft_tick", False))
    # the multi-seed lax.map arm inherits the scatter-free-body contract
    # of multi-seed-tick (#0i): probes must not smuggle a scatter in
    specs.append(consobs_batched_spec(
        "consobs.batched_multi_seed", {"n_byzantine": 1}, True,
        None, True))

    def consobs_mesh_spec(name, sweep_n, node_n, budget):
        def build():
            from blockchain_simulator_tpu.obsim import build as obsim_build
            from blockchain_simulator_tpu.parallel.mesh import make_mesh

            mesh = make_mesh(n_node_shards=node_n, n_sweep=sweep_n)
            fn = _raw(obsim_build.probed_mesh_fn)(
                cfgs["pbft_tick"], _pcfg(), mesh
            )
            b = max(sweep_n, 2)
            return fn, (_keys_sds(b), _i32_sds((b,)), _i32_sds((b,)))

        return ProgramSpec(name, "consobs-mesh", build, budget=budget)

    specs.append(consobs_mesh_spec("consobs.mesh_sweep", 2, 1, True))
    specs.append(consobs_mesh_spec("consobs.mesh_nodes", 1, 2, True))

    for s in specs:
        if s.program in MEMORY_PINNED:
            s.memory = True
    return specs


# The memory-pinned subset: one program per RSS story the ROADMAP tracks
# (dense tick/round engines, the gather-overlay arms behind the 1M/4M-node
# RSS numbers, the batched sweep, the sharded overlay, the serving solo
# path).  Compiling is the expensive step — ~8 compiles keeps the gate
# under a minute where pinning all ~34 budgeted programs costs 10+.
MEMORY_PINNED = frozenset({
    "sim.pbft_tick",
    "sim.pbft_round",
    "sim.raft_tick",
    "sim.pbft_kreg",
    "sim.pbft_comm",
    "sweep_dynf.pbft",
    "shard_topo.pbft_kreg",
    "serve_solo.pbft",
})
