"""ARTIFACT_gather_locality.json generator: shard-local exchange locality.

The acceptance measurement of ISSUE 20 (kill the prologue table/state
all-gather in the sharded overlay programs): the SAME kregular program
compiled under both data-movement layouts of
``parallel/sweep.sharded_topo_sim_fn`` —

- ``layout="regather"``: the pre-exchange behavior, GSPMD rematerializes
  the P("nodes")-sharded tables (and neighbor state rows) with
  all-gathers whose output scales with GLOBAL N;
- ``layout="exchange"`` (the default): owner-bucketed shard-local
  exchange — cross-shard reads move through fixed-capacity ``all-to-all``
  islands, nothing on any device scales with global N.

Measured per layout, straight off the post-SPMD HLO (the shardlint
parser, ``lint/comms/hlo.py``):

- **prologue bytes/device**: summed output bytes of every all-gather
  OUTSIDE the tick loop — the table-regather cost the exchange retires.
  The acceptance gate: reduced by >= (D-1)/D on the 4M-node rung (with
  zero all-gathers left it is a 100% reduction);
- **per-tick exchange bytes/device**: loop-body collective bytes split by
  opcode (the all-to-all rows are the new exchange, bounded by the plan
  capacity x D — not by N);
- **peak-live bytes/device**: XLA's ``memory_analysis`` of the compiled
  executable (argument + temp + output), plus ``cost_analysis`` bytes
  accessed — the [K, N] operand-footprint claim as data;
- **ticks/s ratio** exchange-over-regather at a small executed rung, and
  the trace-only 10M aval math (global table bytes vs the 1/D per-device
  slice the exchange layout actually binds).

1-core caveat (KNOWN_ISSUES #0n): the 8 virtual CPU devices time-slice
ONE core, so wall-clock ratios measure mechanism overhead, not
real-hardware capacity — the BYTES and collective PLACEMENT are the
contract here, the timing leg is a sanity row.

Usage:
    python tools/gather_locality_bench.py            # full artifact
    python tools/gather_locality_bench.py --quick    # lint.sh smoke
    ... [--rung-n 4000000] [--ratio-n 100000] [--ratio-ticks 60]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
ARTIFACT = os.path.join(REPO, "ARTIFACT_gather_locality.json")

N_MESH = 8  # virtual CPU devices (XLA_FLAGS)

LAYOUTS = ("regather", "exchange")


def _force_cpu_mesh() -> None:
    """CPU backend with 8 virtual devices BEFORE any backend init (the
    shard_topo_bench contract: env for the host-device-count flag, config
    in case jax was imported before the env var was set)."""
    if "jax" not in sys.modules:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={N_MESH}"
        ).strip()
    import jax

    jax.config.update("jax_platforms", "cpu")


def _kreg_cfg(n: int, ticks: int, degree: int = 8):
    """The ladder config shape shared with tools/shard_topo_bench.py so
    the rungs line up with the committed topo_scale artifacts."""
    from blockchain_simulator_tpu.utils.config import SimConfig

    return SimConfig(
        protocol="pbft", n=n, sim_ms=ticks, fidelity="clean",
        topology="kregular", degree=degree, delivery="edge",
        edge_sampler="rbg", stat_sampler="exact", schedule="tick",
        model_serialization=False, link_delay_ms=1,
        pbft_delay_lo=1, pbft_delay_hi=3, pbft_window=8,
    )


def _lowered(cfg, mesh, layout: str):
    """The partitioned program of ``cfg`` under ``layout``, lowered at
    aval level (compilation only, nothing executes)."""
    import jax
    import jax.numpy as jnp

    from blockchain_simulator_tpu.models.base import canonical_fault_cfg
    from blockchain_simulator_tpu.parallel.sweep import sharded_topo_sim_fn

    sim = sharded_topo_sim_fn(canonical_fault_cfg(cfg), mesh, layout=layout)
    key_sds = jax.eval_shape(lambda: jax.random.key(0))
    cnt = jax.ShapeDtypeStruct((), jnp.int32)
    return sim.partitioned.lower(key_sds, cnt, cnt, *sim.table_avals)


def _memory_row(compiled) -> dict:
    """Per-device argument/temp/output bytes from XLA's memory analysis
    (None fields where the backend does not report them)."""
    row = {}
    try:
        m = compiled.memory_analysis()
    except Exception:
        m = None
    for key, attr in (
        ("argument_bytes", "argument_size_in_bytes"),
        ("output_bytes", "output_size_in_bytes"),
        ("temp_bytes", "temp_size_in_bytes"),
        ("generated_code_bytes", "generated_code_size_in_bytes"),
    ):
        row[key] = getattr(m, attr, None) if m is not None else None
    live = [row[k] for k in ("argument_bytes", "output_bytes", "temp_bytes")]
    row["peak_live_bytes"] = sum(v for v in live if v) if any(live) else None
    try:
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        row["cost_bytes_accessed"] = float(
            cost.get("bytes accessed", 0.0)
        ) or None
    except Exception:
        row["cost_bytes_accessed"] = None
    return row


def hlo_row(cfg, mesh, layout: str, with_memory: bool = True) -> dict:
    """Compile one layout and read its communication structure off the
    post-SPMD HLO: prologue all-gather bytes, loop bytes by opcode."""
    from blockchain_simulator_tpu.lint.comms import hlo

    t0 = time.monotonic()
    lowered = _lowered(cfg, mesh, layout)
    compiled = lowered.compile()
    colls = hlo.collectives(hlo.parse_module(compiled.as_text()))
    loop_by_op: dict[str, float] = {}
    for c in colls:
        if c.in_loop:
            loop_by_op[c.opcode] = loop_by_op.get(c.opcode, 0.0) + c.bytes
    row = {
        "layout": layout,
        "compile_s": round(time.monotonic() - t0, 2),
        "prologue_allgather_bytes_per_device": float(sum(
            c.bytes for c in colls
            if c.opcode == "all-gather" and not c.in_loop
        )),
        "allgather_count": sum(1 for c in colls if c.opcode == "all-gather"),
        "alltoall_count": sum(1 for c in colls if c.opcode == "all-to-all"),
        "loop_bytes_per_device_by_opcode": {
            k: float(v) for k, v in sorted(loop_by_op.items())
        },
        "loop_bytes_per_device": float(sum(loop_by_op.values())),
    }
    if with_memory:
        row["memory"] = _memory_row(compiled)
    return row


def locality_block(mesh, n: int, degree: int = 8, ticks: int = 60) -> dict:
    """Both layouts of one kregular rung, compiled and compared: the
    prologue-reduction acceptance row."""
    cfg = _kreg_cfg(n, ticks, degree)
    rows = {lay: hlo_row(cfg, mesh, lay) for lay in LAYOUTS}
    old = rows["regather"]["prologue_allgather_bytes_per_device"]
    new = rows["exchange"]["prologue_allgather_bytes_per_device"]
    d = N_MESH
    reduction = (1.0 - new / old) if old else None
    return {
        "n": n, "degree": degree, "n_devices": d,
        "regather": rows["regather"],
        "exchange": rows["exchange"],
        "prologue_reduction": round(reduction, 4)
        if reduction is not None else None,
        "required_reduction": round((d - 1) / d, 4),
        "acceptance": bool(
            reduction is not None and reduction >= (d - 1) / d
        ) and rows["exchange"]["allgather_count"] == 0,
    }


def ratio_block(mesh, n: int, ticks: int) -> dict:
    """Executed ticks/s of both layouts (the 1-core-caveat sanity row)."""
    import jax
    import jax.numpy as jnp

    from blockchain_simulator_tpu.models.base import canonical_fault_cfg
    from blockchain_simulator_tpu.parallel.sweep import sharded_topo_sim_fn
    from blockchain_simulator_tpu.utils import obs

    cfg = _kreg_cfg(n, ticks)
    canon = canonical_fault_cfg(cfg)
    nc = jnp.int32(cfg.faults.resolved_n_crashed(cfg.n))
    nb = jnp.int32(cfg.faults.n_byzantine)
    out = {"n": n, "ticks": ticks, "n_devices": N_MESH}
    for lay in LAYOUTS:
        sim = sharded_topo_sim_fn(canon, mesh, layout=lay)
        _f, compile_s, exec_s = obs.timed_run(
            lambda key, sim=sim: sim(key, nc, nb), jax.random.key(cfg.seed)
        )
        out[lay] = {
            "compile_s": round(compile_s, 2),
            "exec_s": round(exec_s, 3),
            "ticks_per_s": round(ticks / exec_s, 2) if exec_s > 0 else None,
        }
    r, x = out["regather"], out["exchange"]
    if r["ticks_per_s"] and x["ticks_per_s"]:
        out["exchange_over_regather"] = round(
            x["ticks_per_s"] / r["ticks_per_s"], 2
        )
    return out


def analytical_block(n: int, degree: int = 8) -> dict:
    """Trace-only aval math at the 10M rung: what each device must HOLD
    for the table operands under each layout (nothing allocated)."""
    k1 = degree + 1
    table_bytes = n * k1 * 4
    n_tables = 2
    return {
        "n": n, "degree": degree, "n_devices": N_MESH,
        "table_operand_mb_global": round(n_tables * table_bytes / 2**20, 1),
        # regather: the prologue all-gather puts the FULL global tables
        # back on every device before the loop starts
        "per_device_mb_regather": round(n_tables * table_bytes / 2**20, 1),
        # exchange: each device binds its 1/D slice of tables AND plans
        # (pos is table-shaped, send is [D, D, C] with C <= min(n/D, K*n/D)
        # — N/D-bounded, never global)
        "per_device_mb_exchange": round(
            2 * n_tables * table_bytes / N_MESH / 2**20, 1
        ),
        "footprint_ratio": round(1.0 / N_MESH, 4),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="gather_locality_bench")
    p.add_argument("--quick", action="store_true",
                   help="lint.sh smoke: both layouts compiled at n=4096, "
                        "prologue-reduction asserted; no artifact write")
    p.add_argument("--rung-n", type=int, default=4_000_000,
                   help="acceptance rung node count (>= 4M)")
    p.add_argument("--ratio-n", type=int, default=100_000)
    p.add_argument("--ratio-ticks", type=int, default=60)
    args = p.parse_args(argv)

    _force_cpu_mesh()
    import jax

    from blockchain_simulator_tpu.parallel.mesh import make_mesh
    from blockchain_simulator_tpu.utils import obs

    if len(jax.devices()) < N_MESH:
        print(f"gather_locality_bench: need {N_MESH} devices, have "
              f"{len(jax.devices())}", file=sys.stderr)
        return 2

    mesh8 = make_mesh(n_node_shards=N_MESH, n_sweep=1)

    if args.quick:
        loc = locality_block(mesh8, 4096, ticks=120)
        rec = {"quick": True, "locality_4096": loc}
        obs.finalize({"metric": "gather_prologue_reduction",
                      "value": loc["prologue_reduction"], "unit": "frac"})
        print(json.dumps(obs.finalize(rec, None, append=False)))
        if not loc["acceptance"]:
            print("gather_locality_bench: PROLOGUE PIN FAILED")
            return 1
        return 0

    loc_small = locality_block(mesh8, 4096, ticks=120)
    ratio = ratio_block(mesh8, args.ratio_n, args.ratio_ticks)
    obs.finalize({"metric": f"gather_locality_ratio_{args.ratio_n}",
                  "value": ratio.get("exchange_over_regather"), "unit": "x"})
    rung = locality_block(mesh8, args.rung_n, ticks=60)
    obs.finalize({"metric": f"gather_prologue_bytes_{args.rung_n}",
                  "value": rung["exchange"][
                      "prologue_allgather_bytes_per_device"],
                  "unit": "bytes"})
    analytical = analytical_block(10_000_000)

    rec = {
        "metric": "gather_prologue_reduction",
        "value": rung["prologue_reduction"],
        "unit": "frac",
        "locality_4096": loc_small,
        "ratio": ratio,
        "rung": rung,
        "analytical_10m": analytical,
        "note": (
            "virtual CPU devices time-slice ONE core on this box: the "
            "ticks/s ratio measures mechanism overhead only — the "
            "contract here is the BYTES and collective PLACEMENT read "
            "off the post-SPMD HLO.  regather = pre-ISSUE-20 layout "
            "(GSPMD all-gathers the P(\"nodes\") tables/state), exchange "
            "= owner-bucketed all_to_all (parallel/partition."
            "NeighborExchange over topo/spec.owner_bucket_plan); the "
            "10M block is aval math, nothing allocated."
        ),
    }
    with open(ARTIFACT, "w") as f:
        json.dump(rec, f, indent=1)
        f.write("\n")
    print(json.dumps(obs.finalize(dict(rec), None, append=False)))
    accept = (
        loc_small["acceptance"]
        and rung["acceptance"]
        and rung["n"] >= 4_000_000
        and ratio.get("exchange_over_regather") is not None
    )
    if not accept:
        print("gather_locality_bench: ACCEPTANCE NOT MET")
    return 0 if accept else 1


if __name__ == "__main__":
    raise SystemExit(main())
