"""Sparse & hierarchical topologies — the runtime topology axis.

The reference builds exactly one topology (a full N x (N-1)/2 mesh,
blockchain-simulator.cc:34-51) and every tensorized model historically
materialized it as dense N x N edge tensors — quadratic memory, ~100k
nodes practical ceiling (ROADMAP item 3).  This package makes topology a
runtime axis orthogonal to the protocol, the way fault structure already
is:

- :mod:`~blockchain_simulator_tpu.topo.spec` — the representation type
  (``TopoSpec``) and the seeded, deterministic circulant overlay builders
  behind ``topology="kregular"`` (fixed-degree neighbor-index tables the
  models consume through the gather-based delivery primitives in
  ``ops/gatherdeliv.py``: O(N*k) per tick instead of O(N^2), bit-equal to
  the dense program at degree k = N-1);
- :mod:`~blockchain_simulator_tpu.topo.committee` — two-level committee
  consensus behind ``topology="committee"``: inner-quorum consensus per
  committee (the stacked committee axis run as tiles of lanes)
  plus an outer aggregate step over committee representatives; with one
  committee it IS the flat protocol.

Import-clean by the jaxlint ``module-scope-backend-touch`` contract: no
module in this package touches a backend (or jax at all, for spec.py) at
import time.
"""

from blockchain_simulator_tpu.topo.spec import TopoSpec  # noqa: F401
