"""jaxlint rule registry: one module per rule.

Each rule module exposes ``RULE_ID`` (the kebab-case id used in findings,
``# jaxlint: disable=<id>`` comments and the baseline file), ``SUMMARY``
(one line, with the KNOWN_ISSUES / PR reference that motivated the rule)
and ``check(ctx: common.RuleContext) -> list[common.Finding]``.
"""

from __future__ import annotations

from blockchain_simulator_tpu.lint.rules import (  # noqa: F401
    hardcoded_mesh_axis,
    host_sync_in_traced,
    module_scope_backend_touch,
    prng_key_reuse,
    slow_cpu_lowering,
    static_arg_recompile_hazard,
    unused_import,
)

ALL_RULES = [
    host_sync_in_traced,
    prng_key_reuse,
    module_scope_backend_touch,
    slow_cpu_lowering,
    static_arg_recompile_hazard,
    unused_import,
    hardcoded_mesh_axis,
]

RULES_BY_ID = {mod.RULE_ID: mod for mod in ALL_RULES}
