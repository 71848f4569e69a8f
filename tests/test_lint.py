"""tests for tools/static_check.py and its tools/lint/ passes.

Each pass gets a positive fixture (clean at HEAD) and a negative fixture
(an injected copy of the original bug shape fails). Negative fixtures
copy the package into a tmp root and mutate one file, so the checks run
against a real tree, not toy snippets.

Regression notes (jit-purity fixture set):
- ``test_jit_purity_flags_module_jnp_constant`` is the PR-5 eval.py bug:
  a module-level ``jnp.*`` constant captured as a tracer when its module
  is first imported inside a traced fused body. The shipped instance at
  HEAD was ``exprs/cast_strings._DIG0 = jnp.uint8(ord("0"))`` (fixed to
  ``np.uint8`` in this PR; any regression re-flags here).
- ``test_jit_purity_flags_import_under_trace`` is the trigger half of
  the same bug: an import, under trace, of a module the constant check
  found impure.
"""

import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.lint import (  # noqa: E402
    cache_keys, conf_keys, doc_drift, gauge_catalog, jit_purity,
    pallas_fallback, span_catalog, type_support,
)
from tools.lint import core  # noqa: E402


@pytest.fixture()
def repo_copy(tmp_path):
    """A mutable copy of the checked tree (package + docs)."""
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(REPO, "spark_rapids_tpu"),
                    root / "spark_rapids_tpu",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(os.path.join(REPO, "docs"), root / "docs")
    return str(root)


def _append(root, rel, text):
    with open(os.path.join(root, rel), "a") as f:
        f.write(text)


def _replace(root, rel, old, new):
    path = os.path.join(root, rel)
    with open(path, "r") as f:
        src = f.read()
    assert old in src, f"fixture out of date: {old!r} not in {rel}"
    with open(path, "w") as f:
        f.write(src.replace(old, new))


# -- driver ------------------------------------------------------------------


def test_driver_clean_at_head():
    """The wired-in tier-1 run: every pass clean against the repo."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "static_check.py")],
        capture_output=True, text=True, env=env, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "all clean" in proc.stdout
    # per-pass timing lines, one per registered pass
    assert proc.stdout.count("[OK  ]") == len(core.PASSES)


def test_driver_list_and_only():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "static_check.py"),
         "--list"], capture_output=True, text=True, env=env).stdout
    for p in core.PASSES:
        assert p.name in out
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "static_check.py"),
         "--only", "conf-keys"], capture_output=True, text=True, env=env)
    assert proc.returncode == 0
    assert "conf-keys" in proc.stdout and "gauge-catalog" not in proc.stdout
    assert subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "static_check.py"),
         "--only", "not-a-pass"], capture_output=True, env=env,
    ).returncode == 2


def test_driver_fails_on_injected_violation(repo_copy):
    """One exit code across passes: any violation makes the driver fail."""
    _append(repo_copy, "spark_rapids_tpu/obs/__init__.py",
            '\n_X = {"fixture_bogus_total": 0}\n')
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "static_check.py"),
         "--root", repo_copy, "--only", "gauge-catalog"],
        capture_output=True, text=True, env=env)
    assert proc.returncode == 1
    assert "fixture_bogus_total" in proc.stderr


# -- type-support pass --------------------------------------------------------


def test_type_support_clean_at_head():
    assert type_support.run_pass(REPO) == []


def test_type_support_flags_undeclared_device_placement(repo_copy):
    """Injected undeclared (op,dtype) placement: RLike stays in
    _DEVICE_EXPRS but loses its declaration."""
    _replace(repo_copy, "spark_rapids_tpu/exprs/expr.py",
             'RLike.type_support = ts(STRINGY, out="boolean")', "")
    v = type_support.run_pass(repo_copy)
    assert any("RLike" in x and "_DEVICE_EXPRS" in x for x in v)


def test_type_support_flags_unknown_vocabulary(repo_copy):
    _replace(repo_copy, "spark_rapids_tpu/exprs/expr.py",
             'And.type_support = ts("boolean")',
             'And.type_support = ts("bool")')
    v = type_support.run_pass(repo_copy)
    assert any("unknown type class" in x and "'bool'" in x for x in v)


def test_type_support_flags_allowlist_gate_mismatch(repo_copy):
    """_WIDE_OK entry whose declaration has no decimal128: the allowlist
    permits what the central gate rejects."""
    _replace(repo_copy, "spark_rapids_tpu/exprs/expr.py",
             "Abs.type_support = ts(NUMERIC, DECIMAL)",
             "Abs.type_support = ts(NUMERIC)")
    v = type_support.run_pass(repo_copy)
    assert any("Abs" in x and "_WIDE_OK" in x for x in v)


def test_type_support_flags_undeclared_exec_placement(repo_copy):
    _replace(repo_copy, "spark_rapids_tpu/exec/sort.py",
             "SortExec.type_support = ts(", "_fixture_unassigned = ts(")
    v = type_support.run_pass(repo_copy)
    assert any("SortExec" in x and "type_support" in x for x in v)


def test_type_support_flags_unwired_gate(repo_copy):
    _replace(repo_copy, "spark_rapids_tpu/plan/overrides.py",
             "decl = type(bound).type_support",
             "decl = getattr(type(bound), '_ts_' + 'gone', None)")
    v = type_support.run_pass(repo_copy)
    assert any("check_expr" in x and "gate" in x for x in v)


def test_type_support_flags_output_outside_declaration(repo_copy):
    """An op whose dtype property constructs a type its declaration does
    not cover."""
    _replace(repo_copy, "spark_rapids_tpu/exprs/expr.py",
             'Length.type_support = ts(STRINGY, out=INTEGRAL)',
             'Length.type_support = ts(STRINGY, out="boolean")')
    v = type_support.run_pass(repo_copy)
    assert any("Length" in x and "outside its declaration" in x for x in v)


def test_runtime_gate_enforces_declaration():
    """The plan-time side of the contract: check_expr rejects an
    (op,dtype) pair outside the declaration."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.exprs import expr as E
    from spark_rapids_tpu.plan.overrides import check_expr

    schema = T.Schema([T.Field("b", T.BOOLEAN), T.Field("s", T.STRING)])
    # And over booleans: declared, no reasons
    assert check_expr(E.And(E.col("b"), E.col("b")), schema) == []
    # And over strings: outside ts("boolean")
    reasons = check_expr(E.And(E.col("s"), E.col("s")), schema)
    assert any("does not support string inputs" in r for r in reasons)


# -- jit-purity pass ----------------------------------------------------------


def test_jit_purity_clean_at_head():
    assert jit_purity.run_pass(REPO) == []


def test_jit_purity_flags_module_jnp_constant(repo_copy):
    """Regression: PR-5 shipped exprs/eval.py constants captured as
    tracers; HEAD's last instance was cast_strings._DIG0 (now np.uint8).
    Reinjecting the original shape must fail."""
    _replace(repo_copy, "spark_rapids_tpu/exprs/cast_strings.py",
             '_DIG0 = np.uint8(ord("0"))',
             '_DIG0 = jnp.uint8(ord("0"))')
    v = jit_purity.run_pass(repo_copy)
    assert any("cast_strings" in x and "module-level jnp" in x for x in v)


def test_jit_purity_flags_import_under_trace(repo_copy):
    """The composite PR-5 trigger: a traced function lazily imports a
    module that materializes jnp constants at import."""
    with open(os.path.join(repo_copy,
                           "spark_rapids_tpu/_fixture_const.py"), "w") as f:
        f.write("import jax.numpy as jnp\n_K = jnp.float32(1.0)\n")
    with open(os.path.join(repo_copy,
                           "spark_rapids_tpu/_fixture_jit.py"), "w") as f:
        f.write("import jax\n\n"
                "@jax.jit\n"
                "def traced(x):\n"
                "    from spark_rapids_tpu import _fixture_const\n"
                "    return x\n")
    v = jit_purity.run_pass(repo_copy)
    assert any("_fixture_const" in x and "module-level jnp" in x
               for x in v)
    assert any("_fixture_jit" in x and "under trace" in x for x in v)


def test_jit_purity_flags_nondeterminism_under_trace(repo_copy):
    with open(os.path.join(repo_copy,
                           "spark_rapids_tpu/_fixture_rand.py"), "w") as f:
        f.write("import time\nimport jax\n\n"
                "@jax.jit\n"
                "def traced(x):\n"
                "    return x * time.time()\n")
    v = jit_purity.run_pass(repo_copy)
    assert any("_fixture_rand" in x and "time.time" in x for x in v)


def test_jit_purity_suppress_comment(repo_copy):
    with open(os.path.join(repo_copy,
                           "spark_rapids_tpu/_fixture_ok.py"), "w") as f:
        f.write("import jax.numpy as jnp\n"
                "_K = jnp.float32(1.0)  # jit-purity: ok\n")
    assert jit_purity.run_pass(repo_copy) == []


def test_jit_purity_skips_lambda_tables():
    """eval.py's _TRIG-style dispatch dicts (lambdas over jnp) do not
    materialize at import and must not be flagged — they are why the
    check skips nested lambda/def bodies."""
    v = jit_purity.run_pass(REPO)
    assert not any("eval.py" in x for x in v)


# -- conf-keys pass -----------------------------------------------------------


def test_conf_keys_clean_at_head():
    assert conf_keys.run_pass(REPO) == []


def test_conf_keys_flags_undeclared_read(repo_copy):
    _append(repo_copy, "spark_rapids_tpu/exec/misc.py",
            '\n_FIXTURE_KEY = "spark.rapids.tpu.fixture.notDeclared"\n')
    v = conf_keys.run_pass(repo_copy)
    assert any("spark.rapids.tpu.fixture.notDeclared" in x
               and "not declared" in x for x in v)


def test_conf_keys_flags_undocumented_declaration(repo_copy):
    _replace(repo_copy, "docs/configs.md",
             "spark.rapids.tpu.sql.join.hashTable.enabled", "removed.key")
    v = conf_keys.run_pass(repo_copy)
    assert any("spark.rapids.tpu.sql.join.hashTable.enabled" in x
               and "not documented" in x for x in v)
    assert any("removed.key" not in x or "no longer declared" in x
               for x in v)


def test_conf_keys_flags_unread_declaration(repo_copy):
    """A declared key nothing reads is a knob that does nothing: flagged
    until the package (not conf.py itself) names it."""
    _append(repo_copy, "spark_rapids_tpu/config/conf.py",
            '\nFIXTURE_DEAD = conf("spark.rapids.tpu.fixture.deadKnob", '
            'default=1, doc="never read")\n'
            '_SELF_READ = FIXTURE_DEAD.key\n')

    def unread(v):
        return [x for x in v if "read by nothing" in x]

    v = unread(conf_keys.run_pass(repo_copy))
    assert len(v) == 1 and "spark.rapids.tpu.fixture.deadKnob" in v[0]
    _append(repo_copy, "spark_rapids_tpu/exec/misc.py",
            "\ndef _fixture(conf, C):\n"
            "    return C.FIXTURE_DEAD.get(conf)\n")
    assert unread(conf_keys.run_pass(repo_copy)) == []


def test_conf_keys_ignores_prose_fragments():
    """Doc strings saying 'spark.rapids.tpu.sql.enabled is false' must not
    count as key reads (the matcher requires a full key, nothing more)."""
    assert conf_keys._KEY_RE.match(
        "spark.rapids.tpu.sql.enabled is false") is None
    assert conf_keys._KEY_RE.match("spark.rapids.tpu.sql.enabled")


# -- doc-drift pass -----------------------------------------------------------


def test_doc_drift_clean_at_head():
    assert doc_drift.run_pass(REPO) == []


def test_doc_drift_flags_stale_supported_ops(repo_copy):
    _append(repo_copy, "docs/supported_ops.md", "\nstale line\n")
    v = doc_drift.run_pass(repo_copy)
    assert any("supported_ops.md" in x and "drifted" in x for x in v)


def test_doc_drift_flags_stale_configs(repo_copy):
    _replace(repo_copy, "docs/configs.md", "spark.rapids.tpu", "spark.x")
    v = doc_drift.run_pass(repo_copy)
    assert any("configs.md" in x for x in v)


# -- migrated guards keep catching their original bug shapes ------------------


def test_gauge_catalog_clean_at_head():
    assert gauge_catalog.run_pass(REPO) == []


def test_gauge_catalog_flags_undeclared_counter(repo_copy):
    """Original bug shape: a subsystem increments a *_total counter that
    obs/gauges.CATALOG never declares."""
    _append(repo_copy, "spark_rapids_tpu/exec/misc.py",
            '\n_C = {}\n\n\ndef _fixture_bump():\n'
            '    _C["fixture_lost_total"] = _C.get('
            '"fixture_lost_total", 0) + 1\n')
    v = gauge_catalog.run_pass(repo_copy)
    assert any("fixture_lost_total" in x for x in v)


def test_span_catalog_clean_at_head():
    assert span_catalog.run_pass(REPO) == []


def test_span_catalog_flags_undeclared_span(repo_copy):
    """A span name opened in code but missing from obs/span.CATALOG
    raises KeyError at runtime and fragments trace reassembly — the
    pass catches it statically."""
    _append(repo_copy, "spark_rapids_tpu/exec/misc.py",
            "\n\ndef _fixture_traced():\n"
            "    from spark_rapids_tpu.obs import span as _sp\n"
            '    with _sp.span("fixture:bogus-phase"):\n'
            "        pass\n")
    v = span_catalog.run_pass(repo_copy)
    assert any("fixture:bogus-phase" in x and "obs/span.CATALOG" in x
               for x in v)


def test_span_catalog_flags_fstring_span_name(repo_copy):
    """Dynamic detail belongs in attrs, never interpolated into the span
    name — an f-string name is flagged outright."""
    _append(repo_copy, "spark_rapids_tpu/exec/misc.py",
            "\n\ndef _fixture_traced(q):\n"
            "    from spark_rapids_tpu.obs import span as _sp\n"
            '    _sp.record_span(f"query:{q}", 0, 1)\n')
    v = span_catalog.run_pass(repo_copy)
    assert any("f-string" in x for x in v)


def test_span_catalog_flags_bare_device_get_in_exec(repo_copy):
    """A device->host read under exec/ that goes round
    utils/sync.host_get is a sync no exec:host-sync span or counter
    sees; the same read through the door is clean."""
    _append(repo_copy, "spark_rapids_tpu/exec/misc.py",
            "\n\ndef _fixture_sync(x):\n"
            "    import jax\n"
            "    return jax.device_get(x)\n")
    _append(repo_copy, "spark_rapids_tpu/exec/expand.py",
            "\n\ndef _fixture_sync(x):\n"
            "    from spark_rapids_tpu.utils.sync import host_get\n"
            '    return host_get(x, "fixture")\n')
    v = [x for x in span_catalog.run_pass(repo_copy) if "device_get" in x]
    assert len(v) == 1 and "exec/misc.py" in v[0] and "host_get" in v[0]


def test_cache_keys_clean_at_head():
    assert cache_keys.run_pass(REPO) == []


def test_cache_keys_flags_autotune_salt_drop(repo_copy):
    """The autotune timing store must key on the full environment salt:
    dropping the CPU-feature fingerprint would let ns/row measured on one
    host steer dispatch on a different microarchitecture."""
    _replace(repo_copy, "spark_rapids_tpu/plan/autotune.py",
             "jax.default_backend(),\n                     "
             "cpu_feature_fingerprint()",
             'jax.default_backend(),\n                     "static"')
    v = cache_keys.run_pass(repo_copy)
    assert any("autotune" in x and "cpu_feature_fingerprint" in x
               for x in v), v


def test_cache_keys_flags_autotune_digest_without_salt(repo_copy):
    _replace(repo_copy, "spark_rapids_tpu/plan/autotune.py",
             '(_environment_salt() + "||" + repr(key))',
             'repr(key)')
    v = cache_keys.run_pass(repo_copy)
    assert any("_store_digest" in x and "_environment_salt" in x
               for x in v), v


def test_cache_keys_flags_params_dropping_key(repo_copy):
    """Original bug shape (VERDICT r5): a parameterized expression whose
    custom cache_key drops _params, silently sharing one compiled kernel
    across different parameter values."""
    _append(repo_copy, "spark_rapids_tpu/exprs/window.py",
            "\n\nclass _FixtureParamExpr(E.Expression):\n"
            "    def __init__(self, pat):\n"
            "        self._params = (pat,)\n"
            "    def cache_key(self):\n"
            "        return (type(self).__name__,)\n")
    v = cache_keys.run_pass(repo_copy)
    assert any("_FixtureParamExpr" in x and "_params" in x for x in v)


# -- declarations/runtime consistency -----------------------------------------


def test_declarations_match_runtime_attributes():
    """The statically-resolved declarations equal the live class
    attributes — the AST resolver (inheritance included) mirrors what
    check_expr enforces at plan time."""
    from spark_rapids_tpu.plan import overrides as O

    groups_violations = []
    vocab, groups = type_support._support_constants(REPO,
                                                    groups_violations)
    assert groups_violations == []
    bases, decls, _ = type_support._collect_classes(REPO, groups, [])
    for cls in set(O._DEVICE_EXPRS):
        static = type_support._resolve_decl(cls.__name__, bases, decls)
        live = cls.type_support
        assert static is not None and live is not None, cls
        assert static.inputs == set(live.inputs), cls
        assert static.outputs == set(live.outputs), cls
