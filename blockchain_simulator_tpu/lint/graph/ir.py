"""jaxpr-walking primitives for the graph auditor.

Everything here is aval-level: programs are *traced* (``jit(f).trace`` /
``jax.eval_shape``), never executed, and cost comes from
``Lowered.cost_analysis()`` — XLA's analytical model on the lowered module —
so a whole-repo audit touches no simulation data and stays deterministic
(the bit-stability the budget gate relies on; pinned in tests).

The walkers duck-type jaxprs (``.eqns`` / ``.jaxpr`` attributes) instead of
importing ``jax._src`` internals, so they keep working across the jax
versions this repo straddles (0.4.x container, current releases on TPU).
"""

from __future__ import annotations

import hashlib
from collections import Counter

# Primitives that hand control back to the host mid-program.  Any of these
# inside a sim program breaks the "compiled graph is the artifact" contract:
# serialized executables stop being self-contained, vmap/shard_map sweeps
# serialize on the callback.  debug prints/callbacks count: they are host
# round-trips with the same composition hazards.
HOST_CALLBACK_PRIMS = frozenset({
    "pure_callback",
    "io_callback",
    "debug_callback",
    "debug_print",
    "callback",
    "infeed",
    "outfeed",
    "host_local_array_to_global_array",
    "global_array_to_host_local_array",
})

# Confirmed-slow XLA:CPU lowerings (KNOWN_ISSUES.md #0b: scatter-add runs as
# a serialized per-index loop on CPU; sort and the cum* family lower to
# O(n log n)/sequential loops).  The AST `slow-cpu-lowering` rule guesses at
# these from `.at[].add`/`jnp.cumsum` spellings behind an allowlist; here
# the primitive either IS in the trace or is not.
SLOW_PRIMS = frozenset({
    "scatter",
    "scatter-add",
    "scatter-mul",
    "scatter-min",
    "scatter-max",
    "cumsum",
    "cumprod",
    "cummax",
    "cummin",
    "cumlogsumexp",
    "sort",
})

# 64-bit dtypes: the repo runs everything in 32-bit (jax_enable_x64 off);
# a 64-bit aval in a trace means a numpy float64/int64 leaked in as a
# constant or an x64 flag flipped somewhere — either way the program
# silently doubles its memory traffic on TPU or fails to lower.
_WIDE_DTYPES = frozenset({"float64", "int64", "uint64", "complex128"})


def _inner_jaxprs(value):
    """Yield jaxpr objects hiding in one eqn param value (Jaxpr,
    ClosedJaxpr, or tuples/lists of them — lax.cond branches)."""
    vals = value if isinstance(value, (tuple, list)) else (value,)
    for v in vals:
        if hasattr(v, "eqns"):  # Jaxpr
            yield v
        elif hasattr(v, "jaxpr") and hasattr(v.jaxpr, "eqns"):  # ClosedJaxpr
            yield v.jaxpr


def iter_eqns(jaxpr):
    """Every eqn in ``jaxpr`` and all nested sub-jaxprs (scan/cond/while
    bodies, pjit calls), depth-first."""
    stack = [jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr]
    while stack:
        j = stack.pop()
        for eqn in j.eqns:
            yield eqn
            for v in eqn.params.values():
                stack.extend(_inner_jaxprs(v))


def primitive_counts(closed) -> Counter:
    """{primitive name: occurrence count} over the whole (nested) jaxpr."""
    counts: Counter = Counter()
    for eqn in iter_eqns(closed):
        counts[eqn.primitive.name] += 1
    return counts


def _aval_of(var):
    """aval of a Var or Literal (both carry .aval), else None."""
    return getattr(var, "aval", None)


def iter_avals(closed):
    """Every aval mentioned by the (nested) jaxpr: eqn in/outvars plus the
    top-level consts.  Yields avals (possibly repeated)."""
    top = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    for v in list(top.invars) + list(top.outvars) + list(top.constvars):
        a = _aval_of(v)
        if a is not None:
            yield a
    for eqn in iter_eqns(closed):
        for v in list(eqn.invars) + list(eqn.outvars):
            a = _aval_of(v)
            if a is not None:
                yield a


def wide_dtypes(closed) -> Counter:
    """{64-bit dtype name: aval count} found anywhere in the trace."""
    counts: Counter = Counter()
    for a in iter_avals(closed):
        name = str(getattr(a, "dtype", ""))
        if name in _WIDE_DTYPES:
            counts[name] += 1
    return counts


def boundary_weak_types(closed) -> list[str]:
    """Descriptions of weak-typed program inputs/outputs.  A weak-typed
    boundary aval re-specializes on the caller's literal dtype context —
    the same registry key can then produce distinct executables (a silent
    recompile leak at engine boundaries)."""
    top = closed.jaxpr if hasattr(closed, "jaxpr") else closed
    out = []
    for kind, vs in (("in", top.invars), ("out", top.outvars)):
        for i, v in enumerate(vs):
            a = _aval_of(v)
            if a is not None and getattr(a, "weak_type", False):
                out.append(f"{kind}[{i}]:{getattr(a, 'dtype', '?')}")
    return out


def const_leaves(closed) -> list[tuple[str, str, int]]:
    """(shape, dtype, nbytes) of every top-level constant baked into the
    closed jaxpr."""
    out = []
    for c in getattr(closed, "consts", ()):
        nbytes = getattr(c, "nbytes", None)
        if nbytes is None:
            size = getattr(c, "size", 1)
            itemsize = getattr(getattr(c, "dtype", None), "itemsize", 8)
            nbytes = int(size) * int(itemsize)
        out.append((
            str(getattr(c, "shape", ())),
            str(getattr(c, "dtype", type(c).__name__)),
            int(nbytes),
        ))
    return out


def fingerprint(closed) -> str:
    """Stable identity of a traced program: sha256 of the pretty-printed
    jaxpr.  Two traces that print identically lower identically (trace-time
    var names are assigned deterministically), so sweeps whose points share
    a fingerprint share one executable — the registry-key-divergence rule's
    ground truth."""
    return hashlib.sha256(str(closed).encode()).hexdigest()[:24]


def cost_summary(lowered) -> dict | None:
    """{"flops", "bytes"} from a Lowered's analytical cost model, or None
    when the backend provides none.  Delegates to
    ``utils/aotcache.cost_of`` — the budget gate and the AOT compile path
    must read the same normalized record."""
    from blockchain_simulator_tpu.utils import aotcache

    return aotcache.cost_of(lowered)


def memory_summary(lowered) -> dict | None:
    """{"temp_bytes", "argument_bytes"} from the COMPILED executable's
    ``memory_analysis()`` — peak XLA temp allocation and total argument
    bytes per device — or None when the backend provides none.  This is
    the one audit step that pays a real compile (still nothing executes);
    the budget gate pins it next to flops/bytes so the RSS stories
    (7.4 GB @1M, 12.4 GB @4M nodes — ROADMAP item 3) regress loudly."""
    try:
        stats = lowered.compile().memory_analysis()
        return {
            "temp_bytes": float(stats.temp_size_in_bytes),
            "argument_bytes": float(stats.argument_size_in_bytes),
        }
    except Exception:
        return None


def trace_program(fn, example_args: tuple):
    """Trace ``fn`` (jitted or plain) on aval-level ``example_args``;
    returns ``(closed_jaxpr, lowered)``.  Nothing executes: plain callables
    are wrapped in a fresh ``jax.jit`` first, and args may be
    ``ShapeDtypeStruct`` pytrees (``jax.eval_shape`` products)."""
    import jax

    # per-call jit is the point here: an audit traces each program exactly
    # once and executes nothing, so there is no recompile to hazard
    jitted = fn if hasattr(fn, "trace") else jax.jit(fn)  # jaxlint: disable=static-arg-recompile-hazard
    traced = jitted.trace(*example_args)
    return traced.jaxpr, traced.lower()
