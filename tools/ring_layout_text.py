"""The rings of a multi-Raft stack in the text XLA:TPU compiles, read without a
chip: the stack is compiled for a *described* v5e
(``jax.experimental.topologies.get_topology_desc``, libtpu alone) and its
optimised HLO is searched for what a lane-batched ring laid out with the slot
axis second-minor costs on every tick (PERF.md section 6, PR 50): a ``copy``
of a ring-shaped value (the pop's relayout of the whole ring), a ring update
outside every fusion (a bare, sublane-strided ``dynamic-update-slice``) and a
ring whose physical order is not "slot major-most, lanes minor-most"
(``ops/ring.node_minor``'s lane rule).

    python tools/ring_layout_text.py [--groups 256] [--size 5] [--crashes 0]
                                     [--hlo out.txt]

prints one JSON line (``rings``: count by shape and layout, ``ring_copies``,
``bare_ring_updates``, ``not_slot_major``, ``ok``) and exits 0 when nothing
was found, 1 when something was, 3 where no v5e can be described (no libtpu).
256 groups of 5 compile in ~8 s; the text is ``raft-groups-20kx5``'s own
but for the lane count (``--groups 20000``: ~20 s, 170 MB of temporaries;
``--crashes 3`` gives ``raft-leadercrash-20kx5``'s stack).  A reading of
compiled text, never a timing.  ``tests/test_lane_ring.py`` runs it.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # libtpu logs nowhere
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# a v5e's memory, about: the tile rule cuts a stack by it (one tile here)
V5E_BYTES = 16 * 2**30

_COMPUTATION = re.compile(r"(?:ENTRY )?%?([\w.-]+) \(.*\{\s*$")
_VALUE = re.compile(
    r"\s*(?:ROOT )?%?([\w.-]+) = \w+\[([\d,]*)\]\{([\d,]*)[^}]*\} ([\w-]+)\(")


def read(text: str, lanes: int, depth: int) -> dict:
    """What :mod:`tools.ring_layout_text` reports, from a compiled module's
    text: a value is ring-shaped when its two leading dimensions are the
    lanes and the ring depth."""
    rings: dict = {}
    copies, bare, crooked = [], [], set()
    fused = False
    for line in text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            fused = "fused" in head.group(1)
            continue
        m = _VALUE.match(line)
        if not m:
            continue
        name, dims, layout, op = m.groups()
        dims = dims.split(",")
        if len(dims) < 3 or dims[:2] != [str(lanes), str(depth)]:
            continue
        shape = f"[{','.join(dims)}]{{{layout}}}"
        rings[shape] = rings.get(shape, 0) + 1
        # minor to major: lanes (axis 0) first, the slot (axis 1) last
        if not (layout.startswith("0,") and layout.endswith(",1")):
            crooked.add(shape)
        if op == "copy":
            copies.append(f"{name} {shape}")
        elif op == "dynamic-update-slice" and not fused:
            bare.append(f"{name} {shape}")
    return {"rings": rings, "ring_copies": copies, "bare_ring_updates": bare,
            "not_slot_major": sorted(crooked),
            "ok": bool(rings) and not (copies or bare or crooked)}


def compiled_text(groups: int, size: int, crashes: int) -> tuple:
    """``(text, ring depth)`` of the lone stack of ``groups`` Raft groups of
    ``size`` with terms, compiled for one chip of a described v5e."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from blockchain_simulator_tpu.models.base import canonical_fault_cfg
    from blockchain_simulator_tpu.parallel import sweep
    from blockchain_simulator_tpu.topo import committee
    from blockchain_simulator_tpu.utils.config import FaultConfig, SimConfig

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or one that knows no v5e
        print(f"no v5e can be described here: {e!r}"[:300], file=sys.stderr)
        sys.exit(3)
    one = SingleDeviceSharding(topo.devices[0])
    fields = dict(protocol="raft", raft_terms=True, n=groups * size,
                  topology="committee", committees=groups,
                  model_serialization=False, sim_ms=4500)
    if crashes:  # raft-leadercrash-20kx5's schedule and timing
        fields.update(
            raft_heartbeat_ms=75, link_delay_ms=7, sim_ms=4700,
            faults=FaultConfig(crashes=crashes, first_ms=1000,
                               period_ms=1000, downtime_ms=500))
    canon = canonical_fault_cfg(SimConfig(**fields))
    sweep._device_bytes = lambda: V5E_BYTES  # XLA:CPU reports no memory
    key = jax.eval_shape(lambda: jax.random.key(0))
    k = jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=one)
    c = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)
    # compiled once a process, for its text
    fn = jax.jit(functools.partial(committee.run_stacked, canon))  # jaxlint: disable=static-arg-recompile-hazard
    comp = fn.lower(k, c, c).compile()
    assert committee.ran_as(canon) == {"lanes": groups, "tiles": 1}
    return comp.as_text(), committee.inner_cfg(canon).ring_depth


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--groups", type=int, default=256)
    ap.add_argument("--size", type=int, default=5)
    ap.add_argument("--crashes", type=int, default=0)
    ap.add_argument("--hlo", help="write the compiled text here too")
    args = ap.parse_args()
    text, depth = compiled_text(args.groups, args.size, args.crashes)
    if args.hlo:
        with open(args.hlo, "w") as f:
            f.write(text)
    report = {"groups": args.groups, "size": args.size,
              "crashes": args.crashes, "ring_depth": depth,
              **read(text, args.groups, depth)}
    print(json.dumps(report))
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
