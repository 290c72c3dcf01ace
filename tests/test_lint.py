"""jaxlint: per-rule fixture tests (firing / clean / suppressed), engine
mechanics (suppression spans, baseline matching), and the tier-1 whole-repo
gate — the committed tree must carry zero non-baselined findings.

Every fixture is linted with ONLY the rule under test so hygiene rules
(unused-import) cannot contaminate another rule's assertion.  All tests are
pure-AST (no compilation), so the whole file runs in well under a second.
"""

import json
import os

from blockchain_simulator_tpu.lint import engine
from blockchain_simulator_tpu.lint.rules import (
    hardcoded_mesh_axis,
    host_sync_in_traced,
    module_scope_backend_touch,
    prng_key_reuse,
    slow_cpu_lowering,
    static_arg_recompile_hazard,
    unused_import,
)


def run_rule(rule, src, path="fixture.py"):
    findings, n_sup = engine.lint_source(src, path=path, rules=[rule])
    return findings, n_sup


# ---------------------------------------------------------------------------
# host-sync-in-traced
# ---------------------------------------------------------------------------

# The PR 1 regression, as a fixture: a host readback + Python branch between
# two stages of a function that runner-style code jits via functools.partial.
PR1_DEVICE_GET_HANDOFF = """
import functools
import jax

def prefix(key):
    return key

def run(cfg, key):
    ok = prefix(key)
    if bool(jax.device_get(ok)):
        return 1
    return 0

sim = jax.jit(functools.partial(run, None))
"""


def test_host_sync_fires_on_pr1_device_get_handoff():
    findings, _ = run_rule(host_sync_in_traced, PR1_DEVICE_GET_HANDOFF)
    assert any("jax.device_get" in f.message for f in findings), findings
    assert all(f.rule == "host-sync-in-traced" for f in findings)
    # the Python-bool branch on the readback is the same hazard
    assert any("bool()" in f.message for f in findings)


def test_host_sync_fires_in_scan_body_and_decorated_jit():
    src = """
import jax
import numpy as np

@jax.jit
def sim(key):
    def body(carry, t):
        return carry + np.asarray(t), ()
    out, _ = jax.lax.scan(body, key, None, length=3)
    return out
"""
    findings, _ = run_rule(host_sync_in_traced, src)
    assert any("numpy.asarray" in f.message for f in findings), findings


def test_host_sync_clean_on_traced_cond_and_static_casts():
    src = """
import jax

@jax.jit
def run(cfg, key):
    n = int(cfg.n)  # static config read: fine under trace
    ok = key > 0
    return jax.lax.cond(ok, lambda _: n, lambda _: 0, 0)
"""
    findings, _ = run_rule(host_sync_in_traced, src)
    assert findings == []


def test_host_sync_shape_reads_are_static():
    src = """
import jax

@jax.jit
def run(x):
    n = int(x.shape[0])  # static metadata, not a device sync
    d = int(x.ndim)
    return x * (n + d)
"""
    findings, _ = run_rule(host_sync_in_traced, src)
    assert findings == []


def test_host_sync_same_name_in_other_scope_not_dragged_under_trace():
    # every scan body here is named `body`; a host-side helper sharing the
    # name must not inherit traced-ness from an unrelated scope
    src = """
import jax

@jax.jit
def sim(key):
    def body(carry, t):
        return carry, ()
    out, _ = jax.lax.scan(body, key, None, length=3)
    return out

def host_helper(x):
    def body(y):
        return float(jax.device_get(y))
    return body(x)
"""
    findings, _ = run_rule(host_sync_in_traced, src)
    assert findings == [], findings


def test_host_sync_self_attribute_cast_is_not_exempt():
    # int(self.field) on a traced state pytree is a real host sync
    src = """
import jax

@jax.jit
def step(self):
    return int(self.next_hb)
"""
    findings, _ = run_rule(host_sync_in_traced, src)
    assert len(findings) == 1


def test_host_sync_untraced_function_is_clean():
    src = """
import jax

def metrics(state):
    return float(jax.device_get(state).sum())
"""
    findings, _ = run_rule(host_sync_in_traced, src)
    assert findings == []


def test_host_sync_suppressed():
    src = PR1_DEVICE_GET_HANDOFF.replace(
        "if bool(jax.device_get(ok)):",
        "if bool(jax.device_get(ok)):  # jaxlint: disable=host-sync-in-traced",
    )
    findings, n_sup = run_rule(host_sync_in_traced, src)
    assert findings == []
    assert n_sup >= 1


# ---------------------------------------------------------------------------
# prng-key-reuse
# ---------------------------------------------------------------------------

def test_prng_reuse_fires_on_double_consumption():
    src = """
import jax

def draws(key):
    a = jax.random.normal(key, (4,))
    b = jax.random.normal(key, (4,))
    return a + b
"""
    findings, _ = run_rule(prng_key_reuse, src)
    assert len(findings) == 1
    assert "already consumed" in findings[0].message


def test_prng_reuse_clean_with_fold_in_discipline():
    src = """
import jax

def draws(key):
    a = jax.random.normal(jax.random.fold_in(key, 0), (4,))
    b = jax.random.normal(jax.random.fold_in(key, 1), (4,))
    k1, k2 = jax.random.split(key)
    return a + b + jax.random.normal(k1) + jax.random.normal(k2)
"""
    findings, _ = run_rule(prng_key_reuse, src)
    assert findings == []


def test_prng_reuse_branch_aware_and_loop_aware():
    # exclusive if/else arms may share a key; a loop body may not
    clean_branches = """
import jax

def draw(key, flag):
    if flag:
        return jax.random.normal(key, (2,))
    else:
        return jax.random.bernoulli(key)
"""
    findings, _ = run_rule(prng_key_reuse, clean_branches)
    assert findings == []

    loop_reuse = """
import jax

def draw(key):
    out = 0.0
    for i in range(3):
        out = out + jax.random.normal(key)
    return out
"""
    findings, _ = run_rule(prng_key_reuse, loop_reuse)
    assert len(findings) == 1, findings

    loop_rekey = """
import jax

def draw(key):
    out = 0.0
    for i in range(3):
        key, sub = jax.random.split(key)
        out = out + jax.random.normal(sub)
    return out
"""
    findings, _ = run_rule(prng_key_reuse, loop_rekey)
    assert findings == []


def test_prng_reuse_lambda_bodies_and_ternaries():
    # a lambda body is a scope like any other — reuse inside it reports
    lam = """
import jax

f = lambda key: jax.random.normal(key) + jax.random.bernoulli(key)
"""
    findings, _ = run_rule(prng_key_reuse, lam)
    assert len(findings) == 1, findings
    # ternary arms are exclusive paths, same as if/else
    tern = """
import jax

def draw(key, flag):
    return jax.random.normal(key) if flag else jax.random.bernoulli(key)
"""
    findings, _ = run_rule(prng_key_reuse, tern)
    assert findings == []


def test_prng_reuse_guard_clause_early_return_is_exclusive():
    src = """
import jax

def draw(key, flag):
    if flag:
        return jax.random.normal(key)
    return jax.random.bernoulli(key)
"""
    findings, _ = run_rule(prng_key_reuse, src)
    assert findings == []
    # but a fall-through arm still poisons the key
    falls = """
import jax

def draw(key, flag):
    if flag:
        a = jax.random.normal(key)
    return jax.random.bernoulli(key)
"""
    findings, _ = run_rule(prng_key_reuse, falls)
    assert len(findings) == 1


def test_prng_reuse_comprehensions_are_loops():
    src = """
import jax

def draw(key, ps):
    return [jax.random.bernoulli(key, p) for p in ps]
"""
    findings, _ = run_rule(prng_key_reuse, src)
    assert len(findings) == 1, findings
    # per-iteration rebinding stays clean
    clean = """
import jax

def draw(keys):
    return [jax.random.normal(k) for k in keys]
"""
    findings, _ = run_rule(prng_key_reuse, clean)
    assert findings == []


def test_prng_reuse_suppressed():
    src = """
import jax

def draws(key):
    a = jax.random.normal(key, (4,))
    b = jax.random.normal(key, (4,))  # jaxlint: disable=prng-key-reuse
    return a + b
"""
    findings, n_sup = run_rule(prng_key_reuse, src)
    assert findings == []
    assert n_sup == 1


# ---------------------------------------------------------------------------
# module-scope-backend-touch
# ---------------------------------------------------------------------------

def test_backend_touch_fires_at_module_scope():
    src = """
import jax.numpy as jnp

SENTINEL = jnp.int32(1 << 30)
"""
    findings, _ = run_rule(module_scope_backend_touch, src)
    assert len(findings) == 1
    assert "import time" in findings[0].message


def test_backend_touch_exempts_dtype_metadata():
    # iinfo/finfo read dtype metadata without creating device arrays
    src = """
import jax.numpy as jnp

NEVER = jnp.iinfo(jnp.int32).max
EPS = jnp.finfo(jnp.float32).eps
"""
    findings, _ = run_rule(module_scope_backend_touch, src)
    assert findings == []


def test_backend_touch_clean_inside_function():
    src = """
import jax.numpy as jnp

def f():
    return jnp.zeros((4,))
"""
    findings, _ = run_rule(module_scope_backend_touch, src)
    assert findings == []


def test_backend_touch_guarded_module_flags_function_bodies():
    src = """
import jax

def manifest():
    return {"backend": jax.default_backend()}
"""
    path = "blockchain_simulator_tpu/utils/obs.py"
    findings, _ = run_rule(module_scope_backend_touch, src, path=path)
    assert len(findings) == 1
    assert "guarded module" in findings[0].message
    # the same source in a non-guarded module is fine
    findings, _ = run_rule(module_scope_backend_touch, src, path="cli.py")
    assert findings == []


def test_backend_touch_fires_in_default_args_and_decorators():
    # default-argument values and decorators run at def (= import) time
    src = """
import jax
import jax.numpy as jnp

def f(x=jnp.zeros(4)):
    return x

@jax.device_put
def g():
    pass
"""
    findings, _ = run_rule(module_scope_backend_touch, src)
    assert len(findings) == 2, findings


def test_backend_touch_suppressed():
    src = """
import jax.numpy as jnp

SENTINEL = jnp.int32(1 << 30)  # jaxlint: disable=module-scope-backend-touch
"""
    findings, n_sup = run_rule(module_scope_backend_touch, src)
    assert findings == []
    assert n_sup == 1


# ---------------------------------------------------------------------------
# slow-cpu-lowering
# ---------------------------------------------------------------------------

SCATTER_SRC = """
import jax.numpy as jnp

def step(buf, idx, v):
    acc = buf.at[idx].add(v)
    return acc + jnp.cumsum(v)
"""


def test_slow_lowering_fires_in_models_scope():
    path = "blockchain_simulator_tpu/models/fixture.py"
    findings, _ = run_rule(slow_cpu_lowering, SCATTER_SRC, path=path)
    kinds = {f.message.split("`")[1] for f in findings}
    assert len(findings) == 2
    assert any("scatter-add" in k for k in kinds)
    assert any("cumsum" in k for k in kinds)


def test_slow_lowering_out_of_scope_and_allowlist_are_clean():
    # utils/ is not a hot-path scope
    findings, _ = run_rule(
        slow_cpu_lowering, SCATTER_SRC,
        path="blockchain_simulator_tpu/utils/fixture.py",
    )
    assert findings == []
    # the allowlisted pbft windowed accumulator does not fire
    allow_src = """
def _scatter_window_events(acc_add, idx, cnt_w):
    return acc_add.at[idx].add(cnt_w, mode="drop")
"""
    findings, _ = run_rule(
        slow_cpu_lowering, allow_src,
        path="blockchain_simulator_tpu/models/pbft.py",
    )
    assert findings == []


def test_slow_lowering_suppressed():
    src = SCATTER_SRC.replace(
        "acc = buf.at[idx].add(v)",
        "acc = buf.at[idx].add(v)  # jaxlint: disable=slow-cpu-lowering",
    ).replace(
        "return acc + jnp.cumsum(v)",
        "return acc + jnp.cumsum(v)  # jaxlint: disable=slow-cpu-lowering",
    )
    findings, n_sup = run_rule(
        slow_cpu_lowering, src,
        path="blockchain_simulator_tpu/ops/fixture.py",
    )
    assert findings == []
    assert n_sup == 2


# ---------------------------------------------------------------------------
# static-arg-recompile-hazard
# ---------------------------------------------------------------------------

def test_recompile_hazard_fires_on_percall_jit_capture():
    call_form = """
import jax

def measure(sim):
    run = jax.jit(jax.vmap(sim))
    return run
"""
    findings, _ = run_rule(static_arg_recompile_hazard, call_form)
    assert len(findings) == 1
    assert "sim" in findings[0].message

    nested_def_form = """
import jax

def make(scale):
    @jax.jit
    def sim(key):
        return key * scale
    return sim
"""
    findings, _ = run_rule(static_arg_recompile_hazard, nested_def_form)
    assert len(findings) == 1
    assert "scale" in findings[0].message


def test_recompile_hazard_clean_with_lru_cache_or_no_capture():
    cached = """
import functools
import jax

@functools.lru_cache(maxsize=8)
def make(scale):
    @jax.jit
    def sim(key):
        return key * scale
    return sim
"""
    findings, _ = run_rule(static_arg_recompile_hazard, cached)
    assert findings == []

    # a no-capture lambda (utils/health.py's probe matmul) is fine, and
    # function-local imports are not per-call captures
    no_capture = """
def probe():
    import jax
    import jax.numpy as jnp
    return float(jax.jit(lambda a: (a @ a).sum())(jnp.ones((8, 8))))
"""
    findings, _ = run_rule(static_arg_recompile_hazard, no_capture)
    assert findings == []


def test_recompile_hazard_suppressed():
    src = """
import jax

def measure(sim):
    run = jax.jit(jax.vmap(sim))  # jaxlint: disable=static-arg-recompile-hazard
    return run
"""
    findings, n_sup = run_rule(static_arg_recompile_hazard, src)
    assert findings == []
    assert n_sup == 1


# ---------------------------------------------------------------------------
# unused-import
# ---------------------------------------------------------------------------

def test_unused_import_fires():
    src = """
import os
import sys

print(sys.argv)
"""
    findings, _ = run_rule(unused_import, src)
    assert len(findings) == 1
    assert "`os`" in findings[0].message


def test_unused_import_clean_cases():
    used = """
import os

print(os.sep)
"""
    findings, _ = run_rule(unused_import, used)
    assert findings == []
    # noqa is honored, __init__.py is exempt wholesale, __all__ counts
    noqa = "import os  # noqa: F401\n"
    findings, _ = run_rule(unused_import, noqa)
    assert findings == []
    findings, _ = run_rule(
        unused_import, "import os\n", path="pkg/__init__.py"
    )
    assert findings == []
    dunder_all = "from os import sep\n__all__ = [\"sep\"]\n"
    findings, _ = run_rule(unused_import, dunder_all)
    assert findings == []
    # quoted (forward-reference) annotations still use the import
    quoted = 'from typing import List\ndef g(x: "List[int]"):\n    return x\n'
    findings, _ = run_rule(unused_import, quoted)
    assert findings == []
    # noqa on a continuation line of a parenthesized import is honored
    multiline = (
        "import os\n"
        "from os import (\n"
        "    sep,  # noqa: F401\n"
        ")\n"
        "print(os.sep)\n"
    )
    findings, _ = run_rule(unused_import, multiline)
    assert findings == []


def test_overlapping_path_args_do_not_double_count(tmp_path, capsys):
    d = tmp_path / "pkg"
    d.mkdir()
    f = d / "mod.py"
    f.write_text("import os\nimport sys\nprint(sys.argv)\n")
    findings, files, _, _ = engine.lint_paths([str(d), str(f)])
    assert len(files) == 1
    assert len(findings) == 1  # one finding, not two


def test_unused_import_suppressed():
    src = "import os  # jaxlint: disable=unused-import\n"
    findings, n_sup = run_rule(unused_import, src)
    assert findings == []
    assert n_sup == 1


# ---------------------------------------------------------------------------
# engine mechanics
# ---------------------------------------------------------------------------

def test_multiline_node_suppression_spans_all_lines():
    # the disable comment may sit on any line the offending call spans
    src = """
import jax

def draws(key):
    a = jax.random.normal(key, (4,))
    b = jax.random.normal(
        key,
        (4,),
    )  # jaxlint: disable=prng-key-reuse
    return a + b
"""
    findings, n_sup = run_rule(prng_key_reuse, src)
    assert findings == []
    assert n_sup == 1


def test_suppression_inside_string_literal_is_content_not_directive():
    src = 'import os\nmsg = "# jaxlint: disable=all"\n'
    findings, n_sup = run_rule(unused_import, src)
    assert len(findings) == 1  # the unused import still reports
    assert n_sup == 0


def test_baseline_split_counts_and_staleness():
    from blockchain_simulator_tpu.lint.common import Finding

    f = lambda line: Finding(rule="r", path="p.py", line=line, col=0,
                             message="m")
    line_text = lambda _f: "the line"
    baseline = {("r", "p.py", "the line"): {"count": 2, "justification": ""}}
    # two findings fit the baseline; a third is new
    new, n_base, stale = engine.split_by_baseline(
        [f(1), f(2), f(3)], baseline, line_text
    )
    assert len(new) == 1 and n_base == 2 and stale == []
    # one finding leaves the baseline partially stale
    new, n_base, stale = engine.split_by_baseline([f(1)], baseline, line_text)
    assert new == [] and n_base == 1 and len(stale) == 1


def test_cli_json_output_and_exit_codes(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import os\nimport sys\nprint(sys.argv)\n")
    rc = engine.main([str(bad), "--format", "json", "--no-baseline"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert out["jaxlint_schema"] == 1
    assert [f["rule"] for f in out["new_findings"]] == ["unused-import"]

    good = tmp_path / "good.py"
    good.write_text("import os\nprint(os.sep)\n")
    rc = engine.main([str(good), "--format", "json", "--no-baseline"])
    capsys.readouterr()
    assert rc == 0

    broken = tmp_path / "broken.py"
    broken.write_text("def f(:\n")
    rc = engine.main([str(broken), "--no-baseline"])
    capsys.readouterr()
    assert rc == 2

    # an explicit non-.py file arg is a misconfigured gate, not a clean run
    notpy = tmp_path / "gate.sh"
    notpy.write_text("echo hi\n")
    rc = engine.main([str(notpy), "--no-baseline"])
    capsys.readouterr()
    assert rc == 2


def test_write_baseline_roundtrip(tmp_path, capsys):
    bad = tmp_path / "bad.py"
    bad.write_text("import os\nimport sys\nprint(sys.argv)\n")
    bl = tmp_path / "bl.json"
    rc = engine.main([str(bad), "--baseline", str(bl), "--write-baseline"])
    capsys.readouterr()
    assert rc == 0 and bl.exists()
    # against its own baseline the file is clean
    rc = engine.main([str(bad), "--baseline", str(bl)])
    capsys.readouterr()
    assert rc == 0
    # justifications survive a regeneration
    doc = json.loads(bl.read_text())
    doc["entries"][0]["justification"] = "kept on purpose"
    bl.write_text(json.dumps(doc))
    rc = engine.main([str(bad), "--baseline", str(bl), "--write-baseline"])
    capsys.readouterr()
    assert rc == 0
    doc2 = json.loads(bl.read_text())
    assert doc2["entries"][0]["justification"] == "kept on purpose"


def test_write_baseline_subset_preserves_out_of_scope_entries(
    tmp_path, capsys
):
    # re-baselining ONE file must not drop other files' grandfathered
    # entries (or their hand-written justifications)
    a = tmp_path / "a.py"
    a.write_text("import os\nimport sys\nprint(sys.argv)\n")
    b = tmp_path / "b.py"
    b.write_text("import os\nimport sys\nprint(sys.argv)\n")
    bl = tmp_path / "bl.json"
    rc = engine.main([str(a), str(b), "--baseline", str(bl),
                      "--write-baseline"])
    capsys.readouterr()
    assert rc == 0
    doc = json.loads(bl.read_text())
    assert len(doc["entries"]) == 2
    for e in doc["entries"]:
        e["justification"] = "hand-written"
    bl.write_text(json.dumps(doc))
    # regenerate from a that now became clean: a's entry goes, b's stays
    a.write_text("import sys\nprint(sys.argv)\n")
    rc = engine.main([str(a), "--baseline", str(bl), "--write-baseline"])
    capsys.readouterr()
    assert rc == 0
    doc = json.loads(bl.read_text())
    assert len(doc["entries"]) == 1
    assert doc["entries"][0]["path"] == engine.rel_path(str(b))
    assert doc["entries"][0]["justification"] == "hand-written"


# ---------------------------------------------------------------------------
# the tier-1 gate: the committed tree is clean vs the committed baseline
# ---------------------------------------------------------------------------

def test_whole_repo_zero_non_baselined_findings():
    paths = [os.path.join(engine.REPO_ROOT, "blockchain_simulator_tpu"),
             os.path.join(engine.REPO_ROOT, "tools"),
             os.path.join(engine.REPO_ROOT, "bench.py"),
             os.path.join(engine.REPO_ROOT, "chip_smoke.py")]
    findings, files, _, errors = engine.lint_paths(paths)
    assert errors == []
    assert len(files) > 50  # the walker actually saw the tree
    baseline = engine.load_baseline(
        os.path.join(engine.REPO_ROOT, engine.BASELINE_NAME)
    )
    new, _, _ = engine.split_by_baseline(
        findings, baseline, engine._line_text_reader()
    )
    assert new == [], "\n".join(
        f"{f.path}:{f.line}: {f.rule}: {f.message}" for f in new
    )


# ---------------------------------------------------------------------------
# round-8 host-sync gap closures (np.as* family, keyword casts, callable refs)
# ---------------------------------------------------------------------------

def test_host_sync_fires_on_asanyarray_family():
    src = """
import jax
import numpy as np

@jax.jit
def sim(x):
    return np.asanyarray(x) + np.ascontiguousarray(x)
"""
    findings, _ = run_rule(host_sync_in_traced, src)
    msgs = " ".join(f.message for f in findings)
    assert "numpy.asanyarray" in msgs and "numpy.ascontiguousarray" in msgs




def test_host_sync_fires_on_callable_reference():
    # np.asarray handed INTO a traced call syncs exactly like calling it
    src = """
import jax
import numpy as np

@jax.jit
def sim(x):
    return jax.tree.map(np.asarray, x)
"""
    findings, _ = run_rule(host_sync_in_traced, src)
    assert any("passed as callable" in f.message for f in findings), findings


def test_host_sync_jnp_callable_reference_stays_clean():
    src = """
import jax
import jax.numpy as jnp

@jax.jit
def sim(x):
    return jax.tree.map(jnp.asarray, x)
"""
    findings, _ = run_rule(host_sync_in_traced, src)
    assert findings == []


# ---------------------------------------------------------------------------
# baseline hygiene: stale suppressions + --prune-baseline
# ---------------------------------------------------------------------------

def test_stale_suppression_detected_on_full_rule_runs():
    src = "import os  # jaxlint: disable=prng-key-reuse\nprint(os.sep)\n"
    stale = []
    findings, _ = engine.lint_source(src, path="f.py", stale_sup_out=stale)
    assert findings == []
    assert stale == [("f.py", 1, "prng-key-reuse")]


def test_live_suppression_is_not_stale():
    src = "import os  # jaxlint: disable=unused-import\n"
    stale = []
    findings, n_sup = engine.lint_source(src, path="f.py",
                                         stale_sup_out=stale)
    assert findings == [] and n_sup == 1
    assert stale == []


def test_stale_suppression_not_claimed_on_rule_subset_runs():
    # a subset run cannot decide a directive for an un-run rule is dead
    src = "import os  # jaxlint: disable=prng-key-reuse\nprint(os.sep)\n"
    stale = []
    engine.lint_source(src, path="f.py", rules=[unused_import],
                       stale_sup_out=stale)
    assert stale == []


def test_prune_baseline_drops_fixed_and_keeps_firing_entries(
    tmp_path, capsys
):
    a = tmp_path / "a.py"
    a.write_text("import os\nimport sys\nprint(sys.argv)\n")
    bl = tmp_path / "bl.json"
    rc = engine.main([str(a), "--baseline", str(bl), "--write-baseline"])
    capsys.readouterr()
    assert rc == 0
    doc = json.loads(bl.read_text())
    assert len(doc["entries"]) == 1
    doc["entries"][0]["justification"] = "hand-written"
    # a second, already-fixed entry that prune must drop
    doc["entries"].append({
        "rule": "unused-import", "path": engine.rel_path(str(a)),
        "text": "import gone", "count": 1, "justification": "obsolete",
    })
    bl.write_text(json.dumps(doc))

    rc = engine.main([str(a), "--baseline", str(bl), "--prune-baseline"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "pruned 1 entr(ies)" in out
    doc = json.loads(bl.read_text())
    assert len(doc["entries"]) == 1
    assert doc["entries"][0]["text"] == "import os"
    assert doc["entries"][0]["justification"] == "hand-written"


def test_prune_baseline_shrinks_overcounted_entries(tmp_path, capsys):
    a = tmp_path / "a.py"
    a.write_text("import os\nimport sys\nprint(sys.argv)\n")
    bl = tmp_path / "bl.json"
    rc = engine.main([str(a), "--baseline", str(bl), "--write-baseline"])
    capsys.readouterr()
    assert rc == 0
    doc = json.loads(bl.read_text())
    doc["entries"][0]["count"] = 5  # overcounted: only 1 still fires
    bl.write_text(json.dumps(doc))
    rc = engine.main([str(a), "--baseline", str(bl), "--prune-baseline"])
    out = capsys.readouterr().out
    assert rc == 0 and "reduced 1" in out
    doc = json.loads(bl.read_text())
    assert doc["entries"][0]["count"] == 1


def test_prune_baseline_preserves_out_of_scope_entries(tmp_path, capsys):
    a = tmp_path / "a.py"
    a.write_text("import os\nimport sys\nprint(sys.argv)\n")
    # b exists on disk but is NOT linted this run: not decidable, preserved
    b = tmp_path / "b.py"
    b.write_text("import os\nprint(os.sep)\n")
    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps({
        "jaxlint_baseline": 1,
        "entries": [
            {"rule": "unused-import", "path": str(b),
             "text": "import x", "count": 2, "justification": "elsewhere"},
        ],
    }))
    rc = engine.main([str(a), "--baseline", str(bl), "--prune-baseline"])
    capsys.readouterr()
    assert rc == 0
    doc = json.loads(bl.read_text())
    # the out-of-scope entry survives untouched; a's finding is NOT added
    # (prune only removes/shrinks — growing the baseline is --write-baseline)
    assert len(doc["entries"]) == 1
    assert doc["entries"][0]["path"] == str(b)
    assert doc["entries"][0]["count"] == 2


def test_prune_baseline_reports_stale_suppressions(tmp_path, capsys):
    a = tmp_path / "a.py"
    a.write_text(
        "import sys  # jaxlint: disable=prng-key-reuse\nprint(sys.argv)\n"
    )
    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps({"jaxlint_baseline": 1, "entries": []}))
    rc = engine.main([str(a), "--baseline", str(bl), "--prune-baseline"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "stale suppression" in out and "prng-key-reuse" in out


def test_cli_json_reports_stale_suppressions(tmp_path, capsys):
    a = tmp_path / "a.py"
    a.write_text(
        "import sys  # jaxlint: disable=prng-key-reuse\nprint(sys.argv)\n"
    )
    rc = engine.main([str(a), "--format", "json", "--no-baseline"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0
    assert out["stale_suppressions"] == [
        {"path": engine.rel_path(str(a)), "line": 1,
         "rule": "prng-key-reuse"},
    ]


def test_whole_repo_has_no_stale_suppressions():
    """Every inline `# jaxlint: disable=` in the committed tree still
    suppresses a live finding (the --prune-baseline hygiene contract)."""
    paths = [os.path.join(engine.REPO_ROOT, "blockchain_simulator_tpu"),
             os.path.join(engine.REPO_ROOT, "tools"),
             os.path.join(engine.REPO_ROOT, "bench.py"),
             os.path.join(engine.REPO_ROOT, "chip_smoke.py")]
    stale = []
    _, _, _, errors = engine.lint_paths(paths, stale_sup_out=stale)
    assert errors == []
    assert stale == [], stale


def test_prune_baseline_drops_entries_for_deleted_files(tmp_path, capsys):
    a = tmp_path / "a.py"
    a.write_text("import os\nimport sys\nprint(sys.argv)\n")
    bl = tmp_path / "bl.json"
    bl.write_text(json.dumps({
        "jaxlint_baseline": 1,
        "entries": [
            {"rule": "unused-import", "path": str(tmp_path / "gone.py"),
             "text": "import x", "count": 1, "justification": "dead"},
        ],
    }))
    rc = engine.main([str(a), "--baseline", str(bl), "--prune-baseline"])
    out = capsys.readouterr().out
    assert rc == 0 and "pruned 1 entr(ies)" in out
    assert json.loads(bl.read_text())["entries"] == []


def test_prune_baseline_corrupt_baseline_exits_2(tmp_path, capsys):
    a = tmp_path / "a.py"
    a.write_text("import sys\nprint(sys.argv)\n")
    bl = tmp_path / "bl.json"
    bl.write_text("{not json")
    rc = engine.main([str(a), "--baseline", str(bl), "--prune-baseline"])
    err = capsys.readouterr().err
    assert rc == 2 and "bad baseline" in err


# ---------------------------------------------------------------------------
# hardcoded-mesh-axis
# ---------------------------------------------------------------------------

def test_mesh_axis_fires_on_inline_partition_spec():
    src = """
from jax.sharding import PartitionSpec as P

SPEC = P("nodes", None)
"""
    findings, _ = run_rule(hardcoded_mesh_axis, src,
                           path="blockchain_simulator_tpu/models/pbft.py")
    assert findings, "inline PartitionSpec must fire outside partition.py"
    assert all(f.rule == "hardcoded-mesh-axis" for f in findings)
    assert any("inline PartitionSpec" in f.message for f in findings)


def test_mesh_axis_fires_on_axis_literal_at_sharding_calls():
    src = """
import jax

def f(x, mesh):
    return jax.lax.psum(x, axis_name="nodes")

def g(fn, mesh):
    return jax.vmap(fn, spmd_axis_name="sweep")
"""
    findings, _ = run_rule(hardcoded_mesh_axis, src,
                           path="blockchain_simulator_tpu/serve/batch.py")
    lits = {f.message.split("'")[1] for f in findings}
    assert lits == {"nodes", "sweep"}, findings


def test_mesh_axis_clean_in_partition_layer_and_on_constants():
    spec_src = """
from jax.sharding import PartitionSpec as P

RULES = [(r"state", P("nodes"))]
"""
    # the partition layer itself defines the vocabulary: never flagged
    for allowed in ("blockchain_simulator_tpu/parallel/partition.py",
                    "blockchain_simulator_tpu/parallel/mesh.py"):
        findings, _ = run_rule(hardcoded_mesh_axis, spec_src, path=allowed)
        assert findings == [], allowed

    # importing the constants (the remedy) is clean anywhere
    clean = """
import jax

from blockchain_simulator_tpu.parallel.mesh import NODES_AXIS

def f(x, mesh):
    return jax.lax.psum(x, axis_name=NODES_AXIS)
"""
    findings, _ = run_rule(hardcoded_mesh_axis, clean,
                           path="blockchain_simulator_tpu/serve/batch.py")
    assert findings == []

    # unrelated strings at unrelated calls: "nodes" as a dict key or a
    # print argument is content, not sharding vocabulary
    unrelated = """
def report(stats):
    print("nodes", stats["nodes"])
"""
    findings, _ = run_rule(hardcoded_mesh_axis, unrelated,
                           path="blockchain_simulator_tpu/utils/obs.py")
    assert findings == []


def test_mesh_axis_suppressed_inline():
    src = """
import jax

def f(x, mesh):
    return jax.lax.psum(x, axis_name="nodes")  # jaxlint: disable=hardcoded-mesh-axis
"""
    findings, n_sup = run_rule(hardcoded_mesh_axis, src,
                               path="blockchain_simulator_tpu/m.py")
    assert findings == [] and n_sup == 1


def test_mesh_axis_grandfathered_sites_are_baselined():
    """The committed LINT_BASELINE.json carries the partition-adjacent
    grandfathers (shard.py/sweep.py/obsim) WITH justifications."""
    baseline = engine.load_baseline(
        os.path.join(engine.REPO_ROOT, "LINT_BASELINE.json")
    )
    mesh_entries = {k: v for k, v in baseline.items()
                    if k[0] == "hardcoded-mesh-axis"}
    grandfathered_files = {k[1].rsplit("/", 1)[-1] for k in mesh_entries}
    assert {"shard.py", "sweep.py", "build.py"} <= grandfathered_files
    for key, entry in mesh_entries.items():
        assert entry["justification"], key
        assert not entry["justification"].startswith("TODO"), key
