"""slow-cpu-lowering: scatter-add and cumsum are measured XLA:CPU traps.

KNOWN_ISSUES #0b (measured end-to-end on the 2-core driver box): a
scatter-add commit-wave variant ran 2.6x SLOWER than padded shifted adds,
and a ``jnp.cumsum`` crossing loop cost +2.5 ms/round vs an unrolled running
sum.  Every test and rehearsal runs on XLA:CPU, so hot-path code in
``models/`` and ``ops/`` must not reach for ``.at[...].add`` or ``cumsum``
casually.

The rule is allowlist-aware: sites measured acceptable (cold paths, small
static axes, ``mode="drop"`` windowed accumulators whose vectorized
alternative was worse) are listed in :data:`ALLOWLIST` as
``"<basename>::<function>"`` — add an entry ONLY with a measurement, or
grandfather via LINT_BASELINE.json with a justification.
"""

from __future__ import annotations

import ast

from blockchain_simulator_tpu.lint import common

RULE_ID = "slow-cpu-lowering"
SUMMARY = (".at[].add / cumsum in models/ and ops/ hot paths "
           "(KNOWN_ISSUES #0b: 2.6x slower scatter, +2.5 ms/round cumsum "
           "on XLA:CPU); allowlist-aware")

SCOPES = ("/models/", "/ops/")

CUMSUM_CALLS = frozenset({
    "jax.numpy.cumsum", "jax.lax.cumsum", "jax.lax.associative_scan",
})

# "<basename>::<enclosing function>" sites measured acceptable.  Every entry
# needs a measurement or a structural argument in the comment.
ALLOWLIST = frozenset({
    # windowed vote-table accumulators: O(N*W) drop-mode scatters over the
    # small static window axis, measured as part of the tick engine (the
    # round fast path that owns the perf target has no vote table at all)
    "pbft.py::_scatter_window_events",
    # one cumsum over a shard's n_loc * p (row, lane) pairs, inside a taken
    # flood arm of a SHARDED relay only: 58 us at the tests' 192 pairs and
    # 0.2 ms at 7,500 on XLA:CPU (this box, PR 32), in an arm whose dense
    # form scatters 120,000 updates; on four v5e chips the flood's work
    # reads 74 us a tick where the dense arms read 1,244 (PERF.md section 6,
    # PR 32)
    "delivery.py::_flood_exchange",
})


def _enclosing_fn_name(node: ast.AST) -> str | None:
    for parent in common.parent_chain(node):
        if isinstance(parent, (ast.FunctionDef, ast.AsyncFunctionDef)):
            return parent.name
    return None


def _is_scatter_add(call: ast.Call) -> bool:
    f = call.func
    return (
        isinstance(f, ast.Attribute) and f.attr == "add"
        and isinstance(f.value, ast.Subscript)
        and isinstance(f.value.value, ast.Attribute)
        and f.value.value.attr == "at"
    )


def _is_cumsum(call: ast.Call, aliases: dict[str, str]) -> bool:
    r = common.resolve(call.func, aliases)
    if r in CUMSUM_CALLS:
        return True
    return isinstance(call.func, ast.Attribute) and call.func.attr == "cumsum"


def check(ctx: common.RuleContext) -> list[common.Finding]:
    if not any(scope in f"/{ctx.path}" for scope in SCOPES):
        return []
    findings: list[common.Finding] = []
    basename = ctx.path.rsplit("/", 1)[-1]
    for call in ast.walk(ctx.tree):
        if not isinstance(call, ast.Call):
            continue
        if _is_scatter_add(call):
            what = ".at[...].add scatter-add"
            hint = ("lowers to a serialized generic scatter on XLA:CPU "
                    "(measured 2.6x slower than padded shifted adds end-to-"
                    "end, KNOWN_ISSUES #0b)")
        elif _is_cumsum(call, ctx.aliases):
            what = "cumsum"
            hint = ("lowers pathologically on XLA:CPU (+2.5 ms/round vs an "
                    "unrolled running-sum chain, KNOWN_ISSUES #0b; see "
                    "models/pbft_round.py's crossing latch)")
        else:
            continue
        fn = _enclosing_fn_name(call)
        if fn and f"{basename}::{fn}" in ALLOWLIST:
            continue
        remedy = (
            f"vectorize differently, or add \"{basename}::{fn}\" to the "
            "rule allowlist WITH a measurement"
            if fn else
            # module-scope sites have no allowlist key: only an inline
            # suppression or a baseline entry can exempt them
            "vectorize differently, or suppress inline / baseline with a "
            "justification"
        )
        findings.append(common.Finding(
            rule=RULE_ID, path=ctx.path, line=call.lineno,
            col=call.col_offset,
            message=f"`{what}` in a models/ops hot path {hint} — {remedy}",
            end_line=getattr(call, "end_lineno", None),
            function=fn,
        ))
    return findings
