"""cache-keys pass: the jit-cache key contract (VERDICT r5 bug class).

Two programs whose expressions differ only in a non-child parameter (a LIKE pattern, a
round scale, a trunc format...) MUST produce different ``cache_key()``
tuples, or they silently share one compiled kernel and return wrong
results. The convention: such parameters are recorded in ``self._params``,
and the base ``Expression.cache_key`` folds ``_params`` in through
``_KEY_PRIVATE_ATTRS`` (exprs/expr.py).

This pass fails when either side of that contract breaks, and also guards
the persistent-program cache key site (exec/jit_persist.py environment
salt), the autotune timing-store digest (plan/autotune.py — same salt
contract), and the hash-table kernel static-arg contract
(exec/kernels.py). Pure AST, no imports of the checked code.
"""

from __future__ import annotations

import ast
import os

from tools.lint import core
from tools.lint.core import register


def _assigns_self_attr(node: ast.AST, attr: str) -> bool:
    for sub in ast.walk(node):
        if isinstance(sub, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
            targets = (sub.targets if isinstance(sub, ast.Assign)
                       else [sub.target])
            for t in targets:
                if (isinstance(t, ast.Attribute) and t.attr == attr
                        and isinstance(t.value, ast.Name)
                        and t.value.id == "self"):
                    return True
    return False


def _mentions_params(fn: ast.AST) -> bool:
    """cache_key is compliant if it touches _params itself or defers to the
    base implementation (which folds _params in)."""
    for sub in ast.walk(fn):
        if isinstance(sub, ast.Attribute) and sub.attr in (
                "_params", "cache_key"):
            if sub.attr == "cache_key" and isinstance(sub.value, ast.Call) \
                    and isinstance(sub.value.func, ast.Name) \
                    and sub.value.func.id == "super":
                return True
            if sub.attr == "_params":
                return True
        if isinstance(sub, ast.Constant) and sub.value == "_params":
            return True
    return False


def check_file(path: str, violations: list, root: str = "") -> None:
    with open(path, "r") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        violations.append(f"{path}: not parseable: {e}")
        return
    for node in ast.walk(tree):
        if not isinstance(node, ast.ClassDef):
            continue
        methods = {m.name: m for m in node.body
                   if isinstance(m, (ast.FunctionDef, ast.AsyncFunctionDef))}
        if "cache_key" not in methods:
            continue  # inherits the base key, which includes _params
        if not _assigns_self_attr(node, "_params"):
            continue
        if not _mentions_params(methods["cache_key"]):
            rel = os.path.relpath(path, root) if root else path
            violations.append(
                f"{rel}:{node.lineno}: class {node.name} assigns "
                f"self._params but its cache_key() neither includes "
                f"_params nor calls super().cache_key() — parameterized "
                f"programs would share one compiled kernel (VERDICT r5)")


def _check_key_private_attrs(violations: list, root: str) -> None:
    path = os.path.join(core.pkg_dir(root), "exprs", "expr.py")
    tree = core.parse(path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and t.id == "_KEY_PRIVATE_ATTRS":
                    try:
                        vals = ast.literal_eval(node.value)
                    except ValueError:
                        vals = ()
                    if "_params" in vals:
                        return
                    violations.append(
                        "spark_rapids_tpu/exprs/expr.py: _KEY_PRIVATE_ATTRS "
                        "no longer contains '_params' — every _params "
                        "parameter would vanish from cache keys")
                    return
    violations.append(
        "spark_rapids_tpu/exprs/expr.py: _KEY_PRIVATE_ATTRS not found "
        "(cache_key contract changed? update tools/lint/cache_keys.py)")


def _fn_mentions(fn: ast.AST, needles) -> set:
    """Which of ``needles`` appear in ``fn`` as an attribute access, a bare
    name, or a call target."""
    seen = set()
    for sub in ast.walk(fn):
        if isinstance(sub, ast.Attribute) and sub.attr in needles:
            seen.add(sub.attr)
        elif isinstance(sub, ast.Name) and sub.id in needles:
            seen.add(sub.id)
    return seen


def _check_persist_key(violations: list, root: str) -> None:
    """exec/jit_persist.py digest contract: the on-disk entry key covers
    the full environment (jax version + backend + CPU features)."""
    path = os.path.join(core.pkg_dir(root), "exec", "jit_persist.py")
    rel = os.path.relpath(path, root)
    if not os.path.exists(path):
        violations.append(f"{rel}: missing (persistent-program cache "
                          "removed? update tools/lint/cache_keys.py)")
        return
    tree = core.parse(path)
    fns = {n.name: n for n in ast.walk(tree)
           if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
    salt = fns.get("_environment_salt")
    if salt is None:
        violations.append(
            f"{rel}: _environment_salt() not found — the on-disk program "
            "digest no longer has a declared environment key site")
    else:
        needed = {"__version__", "default_backend",
                  "cpu_feature_fingerprint"}
        missing = needed - _fn_mentions(salt, needed)
        if missing:
            violations.append(
                f"{rel}:{salt.lineno}: _environment_salt() no longer "
                f"covers {sorted(missing)} — a persisted program could "
                "replay in an environment where it is invalid")
    dig = fns.get("_digest")
    if dig is None or "_environment_salt" not in _fn_mentions(
            dig, {"_environment_salt"}):
        violations.append(
            f"{rel}: _digest() must fold _environment_salt() into every "
            "on-disk entry key")


def _check_autotune_key(violations: list, root: str) -> None:
    """plan/autotune.py store-digest contract: the persistent timing
    store's file name must fold the same environment salt as jit_persist
    (jax version + backend + CPU features) — measured ns/row must never
    steer dispatch on a different backend or host."""
    path = os.path.join(core.pkg_dir(root), "plan", "autotune.py")
    rel = os.path.relpath(path, root)
    if not os.path.exists(path):
        violations.append(f"{rel}: missing (autotune store removed? "
                          "update tools/lint/cache_keys.py)")
        return
    tree = core.parse(path)
    fns = {n.name: n for n in ast.walk(tree)
           if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
    salt = fns.get("_environment_salt")
    if salt is None:
        violations.append(
            f"{rel}: _environment_salt() not found — the timing-store "
            "digest no longer has a declared environment key site")
    else:
        needed = {"__version__", "default_backend",
                  "cpu_feature_fingerprint"}
        missing = needed - _fn_mentions(salt, needed)
        if missing:
            violations.append(
                f"{rel}:{salt.lineno}: _environment_salt() no longer "
                f"covers {sorted(missing)} — persisted timings could "
                "steer dispatch in an environment they never measured")
    dig = fns.get("_store_digest")
    if dig is None or "_environment_salt" not in _fn_mentions(
            dig, {"_environment_salt"}):
        violations.append(
            f"{rel}: _store_digest() must fold _environment_salt() into "
            "the timing-store file name")


def _check_kernel_static_keys(violations: list, root: str) -> None:
    """exec/kernels.py hash-table jit key contract: table-layout parameters
    (capacity, seed, max_probes) must be STATIC jit args — they shape the
    compiled program (probe-loop bounds, buffer extents, rehash mixing), so
    a traced-value key would silently reuse a kernel compiled for a
    different table layout. Also: SortSpec carries the per-key string width
    (str_words), so widened sort keys fork compiles per width bucket."""
    path = os.path.join(core.pkg_dir(root), "exec", "kernels.py")
    rel = os.path.relpath(path, root)
    tree = core.parse(path)
    layout_params = ("capacity", "seed", "max_probes")
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef) and node.name in (
                "build_hash_table", "probe_hash_table"):
            found.add(node.name)
            args = [a.arg for a in node.args.args]
            static_pos = set()
            for dec in node.decorator_list:
                if not isinstance(dec, ast.Call):
                    continue
                for kw in dec.keywords:
                    if kw.arg not in ("static_argnums", "static_argnames"):
                        continue
                    try:
                        v = ast.literal_eval(kw.value)
                    except ValueError:
                        continue
                    for s in (v if isinstance(v, (tuple, list)) else (v,)):
                        static_pos.add(args.index(s)
                                       if isinstance(s, str) and s in args
                                       else s)
            bad = [p for p in layout_params
                   if p not in args or args.index(p) not in static_pos]
            if bad:
                violations.append(
                    f"{rel}:{node.lineno}: {node.name}() must take the "
                    f"table-layout parameters {list(layout_params)} as "
                    f"static jit args (non-static or missing: {bad}) — a "
                    "layout change must fork the compiled kernel, not "
                    "reuse one traced for another capacity/seed")
    for name in ("build_hash_table", "probe_hash_table"):
        if name not in found:
            violations.append(
                f"{rel}: {name}() not found (hash-table kernels moved? "
                "update tools/lint/cache_keys.py)")
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == "SortSpec":
            fields = {s.target.id for s in node.body
                      if isinstance(s, ast.AnnAssign)
                      and isinstance(s.target, ast.Name)}
            if "str_words" not in fields:
                violations.append(
                    f"{rel}:{node.lineno}: SortSpec lost its str_words "
                    "field — widened string sort keys would share one "
                    "compiled kernel across key widths")
            break
    else:
        violations.append(
            f"{rel}: SortSpec not found (sort key specs moved? update "
            "tools/lint/cache_keys.py)")


@register("cache-keys",
          "_params/cache_key contract, persist/autotune digest salts, "
          "kernel static jit args")
def run_pass(root: str) -> list:
    violations: list = []
    for path in core.iter_py_files(root):
        check_file(path, violations, root)
    _check_key_private_attrs(violations, root)
    _check_persist_key(violations, root)
    _check_autotune_key(violations, root)
    _check_kernel_static_keys(violations, root)
    return violations
