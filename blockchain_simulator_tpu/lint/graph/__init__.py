"""jaxgraph: IR-level audit of every registered executable factory.

``jaxlint`` (the sibling AST layer, ``lint/engine.py``) polices the Python
that *produces* programs; this package audits the programs themselves.  The
north star lifts consensus state machines into batched XLA executables, so
the artifact that must stay correct and fast is the compiled graph — and the
switch-consensus line this repo tracks ("Paxos Made Switch-y", 1511.04985;
"Network Hardware-Accelerated Consensus", 1605.05619) wins precisely by
knowing statically what the dataplane will execute.  Here that means: trace
every ``aotcache.cached_factory`` program (round + tick engines, raft_hb,
mixed, sweep batched fns, shard wrappers, traced probes) to its jaxpr and
check IR-level contracts AST rules can only approximate:

- no host callbacks / infeed / debug prints inside sim programs
  (``host-callback-in-program``);
- no 64-bit dtypes and no weak-type drift across program boundaries
  (``f64-in-program``, ``weak-type-boundary``);
- no large constants baked into the jaxpr — they bloat the executable
  and defeat the one-executable-per-fault-structure contract
  (``large-jaxpr-constant``);
- confirmed-slow CPU lowerings found post-trace, replacing the AST
  ``slow-cpu-lowering`` allowlist guesswork with ground truth
  (``slow-lowering-confirmed``);
- registry-key divergence: one registry key producing multiple distinct
  jaxprs across a sweep is a silent recompile leak
  (``registry-key-divergence``);
- every ``cached_factory`` name discovered in source has at least one audit
  program covering it (``unaudited-factory``).

On the same traces, per-program ``cost_analysis()`` FLOP/byte budgets are
pinned in ``GRAPH_BASELINE.json`` and gated like ``LINT_BASELINE.json``
gates findings (``budget-missing`` / ``budget-regression``): a static
perf-regression gate that fires in CI without running a bench.  The
``*_gflops`` / ``*_bytes`` trajectories are charted — never hard-gated — by
``tools/bench_compare.py``.

Run ``python -m blockchain_simulator_tpu.lint.graph`` (text/JSON output,
baseline mechanics mirroring jaxlint's); ``tools/lint.sh`` chains it after
the AST gate.
"""

from __future__ import annotations
