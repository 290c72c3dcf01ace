"""module-scope-backend-touch: importing must never initialize a backend.

A chip belongs to one process at a time: a process that touches jax's
backend holds the chip, and a child that needs it then fails or hangs.  So
a parent that touches the backend at import cannot launch chip children
(chip_smoke.py, the fleet launcher, the supervised health probe), and a
process that merely imports the package for its config types must not claim
the chip.  The defense has two layers, both enforced here:

- NOWHERE in the tree may module scope (import time) execute a
  ``jnp.*`` / ``jax.random.*`` call or a backend introspection call
  (``jax.devices`` / ``jax.default_backend`` / ...): importing a module for
  its config types must stay free of device work;
- the GUARDED modules — ``utils/obs.py`` and ``utils/health.py``, which by
  contract run inside jax-free parents (the "manifest never triggers
  backend init" guard) — may not make backend-touching calls *anywhere*,
  not just at module scope.  The deliberate exceptions (obs.py's
  ``_backends``-guarded reads and its timing of a caller's own sim,
  health.py's probe whose JOB is the init) carry inline
  ``# jaxlint: disable=`` suppressions with their justification.
"""

from __future__ import annotations

import ast

from blockchain_simulator_tpu.lint import common

RULE_ID = "module-scope-backend-touch"
SUMMARY = ("jnp/jax.random/jax.devices at import time anywhere; any "
           "backend-touching call inside utils/obs.py + utils/health.py "
           "(one process per chip: a parent that touches jax at import "
           "cannot launch chip children)")

# introspection / placement calls that force a backend init
BACKEND_CALLS = frozenset({
    "jax.devices", "jax.local_devices", "jax.device_count",
    "jax.local_device_count", "jax.default_backend", "jax.process_index",
    "jax.process_count", "jax.device_put", "jax.device_get",
    "jax.live_arrays", "jax.block_until_ready",
})

GUARDED_SUFFIXES = (
    "blockchain_simulator_tpu/utils/obs.py",
    "blockchain_simulator_tpu/utils/health.py",
)


# jnp calls that only read dtype METADATA — no device array is created and
# no backend is initialized (verified: jnp.iinfo leaves xla_bridge._backends
# empty); exempting them keeps the rule from forcing churn on harmless code
METADATA_CALLS = frozenset({
    "jax.numpy.iinfo", "jax.numpy.finfo", "jax.numpy.dtype",
    "jax.numpy.issubdtype", "jax.numpy.promote_types",
    "jax.numpy.result_type",
})


def _touch(callee: ast.AST, aliases: dict[str, str]) -> str | None:
    """Canonical name of a backend-touching callable, or None."""
    r = common.resolve(callee, aliases)
    if not r:
        return None
    if r in BACKEND_CALLS:
        return r
    if r in METADATA_CALLS:
        return None
    if r.startswith("jax.numpy.") or r.startswith("jax.random."):
        return r
    return None


def _module_scope_calls(tree: ast.Module):
    """(node, callee_expr) pairs executed at import time: module body,
    descending through If/Try/For/While/With and CLASS bodies (executed at
    import).  Function BODIES are skipped, but their decorators and
    default-argument values DO run at def time, so those subtrees stay in
    scope — and a bare ``@jax.device_put``-style decorator is itself a call
    at def time even though the AST has no Call node for it."""
    stack: list[ast.AST] = list(tree.body)
    while stack:
        node = stack.pop()
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.Lambda)):
            for dec in getattr(node, "decorator_list", []):
                if isinstance(dec, (ast.Name, ast.Attribute)):
                    yield dec, dec  # decorator application IS a call
                else:
                    stack.append(dec)
            a = node.args
            stack.extend(a.defaults)
            stack.extend(d for d in a.kw_defaults if d is not None)
            continue
        if isinstance(node, ast.Call):
            yield node, node.func
        stack.extend(ast.iter_child_nodes(node))


def check(ctx: common.RuleContext) -> list[common.Finding]:
    findings: list[common.Finding] = []
    seen: set[tuple[int, int]] = set()

    def add(node: ast.AST, what: str, why: str) -> None:
        loc = (node.lineno, node.col_offset)
        if loc in seen:
            return
        seen.add(loc)
        findings.append(common.Finding(
            rule=RULE_ID, path=ctx.path, line=node.lineno,
            col=node.col_offset, message=f"`{what}` {why}",
            end_line=getattr(node, "end_lineno", None),
        ))

    for node, callee in _module_scope_calls(ctx.tree):
        what = _touch(callee, ctx.aliases)
        if what:
            add(node, what,
                "runs at import time: importing this module would claim "
                "the chip, and a parent that holds it cannot launch chip "
                "children; move it inside the function that needs it")

    if ctx.path.endswith(GUARDED_SUFFIXES):
        for call in ast.walk(ctx.tree):
            if isinstance(call, ast.Call):
                what = _touch(call.func, ctx.aliases)
                if what:
                    add(call, what,
                        "inside a guarded module (utils/obs.py / "
                        "utils/health.py run inside jax-free parents — the "
                        "'manifest never triggers backend init' contract); "
                        "guard it or justify with an inline suppression")
    return findings
