"""conf-keys pass: every spark.rapids.tpu.* key is declared, documented
and read.

The config registry (config/conf.py ``conf(key, ...)`` calls) is the
single source of truth for configuration: a key read anywhere in the
package but never declared silently reads a raw default with no
validation, no docs entry, and no discoverability; a declared non-internal
key missing from docs/configs.md is invisible to users; a declared key
that nothing reads stands in docs/configs.md as a working knob and does
nothing when set. Pure AST over the package plus a text scan of the
committed docs — the doc-drift pass additionally re-renders configs.md and
diffs it byte-for-byte.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Set, Tuple

from tools.lint import core
from tools.lint.core import register

#: a full conf key, nothing more: rejects prose fragments like
#: "spark.rapids.tpu.sql.enabled is false" inside doc strings
_KEY_RE = re.compile(r"^spark\.rapids\.tpu\.[A-Za-z0-9][A-Za-z0-9.]*$")


#: keys whose whole effect is their own ``check=`` in config/conf.py (a
#: guard that raises when the RapidsConf is made): enforced, so not dead,
#: though no code outside conf.py reads them
_ENFORCED_BY_CHECK = frozenset({"spark.rapids.tpu.requires"})

#: readers besides the package: the benchmark and the chip smoke set and
#: read keys through the registry's names too. Tests alone do not count.
_OTHER_READERS = ("benchmark", "chip_smoke.py")


def _conf_path(root: str) -> str:
    return os.path.join(core.pkg_dir(root), "config", "conf.py")


def _is_conf_call(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and isinstance(node.func, ast.Name)
            and node.func.id == "conf" and bool(node.args)
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str))


def declared_keys(root: str) -> Tuple[Set[str], Set[str]]:
    """(all declared keys, internal keys) from config/conf.py conf(...)
    calls."""
    declared: Set[str] = set()
    internal: Set[str] = set()
    for node in ast.walk(core.parse(_conf_path(root))):
        if not _is_conf_call(node):
            continue
        key = node.args[0].value
        declared.add(key)
        for kw in node.keywords:
            if kw.arg == "internal" and isinstance(kw.value, ast.Constant) \
                    and kw.value.value:
                internal.add(key)
    return declared, internal


def declared_names(root: str) -> Dict[str, str]:
    """``NAME = conf("key", ...)`` at config/conf.py's top level, as
    {NAME: key}: the name the package reads the key by."""
    out: Dict[str, str] = {}
    for node in core.parse(_conf_path(root)).body:
        if isinstance(node, ast.Assign) and _is_conf_call(node.value):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    out[t.id] = node.value.args[0].value
    return out


def _reader_files(root: str) -> List[str]:
    conf_path = _conf_path(root)
    files = [p for p in core.iter_py_files(root)
             if not os.path.samefile(p, conf_path)]
    for other in _OTHER_READERS:
        path = os.path.join(root, other)
        if os.path.isdir(path):
            files.extend(core.iter_py_files(root, other))
        elif os.path.isfile(path):
            files.append(path)
    return files


def unread_keys(root: str) -> List[str]:
    """Declared keys that no reader names, by registry name (``C.NAME``,
    ``from ...conf import NAME``) or by the key's own string."""
    names = declared_names(root)
    seen: Set[str] = set()
    for path in _reader_files(root):
        for node in ast.walk(core.parse(path)):
            if isinstance(node, ast.Attribute):
                seen.add(node.attr)
            elif isinstance(node, ast.Name):
                seen.add(node.id)
            elif isinstance(node, ast.alias):
                seen.add(node.name)
            elif isinstance(node, ast.Constant) and isinstance(
                    node.value, str):
                seen.add(node.value)
    read = {key for name, key in names.items()
            if name in seen or key in seen}
    declared, _ = declared_keys(root)
    return sorted(declared - read - _ENFORCED_BY_CHECK)


def documented_keys(root: str) -> Set[str]:
    path = os.path.join(root, "docs", "configs.md")
    if not os.path.exists(path):
        return set()
    with open(path, "r") as f:
        text = f.read()
    return set(re.findall(r"spark\.rapids\.tpu\.[A-Za-z0-9.]+", text))


def used_keys(root: str) -> List[Tuple[str, int, str]]:
    """(relpath, lineno, key) for every full-key string constant in the
    package outside config/conf.py."""
    out = []
    conf_path = _conf_path(root)
    for path in core.iter_py_files(root):
        if os.path.samefile(path, conf_path):
            continue
        rel = os.path.relpath(path, root)
        for node in ast.walk(core.parse(path)):
            if isinstance(node, ast.Constant) and isinstance(
                    node.value, str) and _KEY_RE.match(node.value):
                out.append((rel, node.lineno, node.value))
    return out


@register("conf-keys",
          "spark.rapids.tpu.* keys are declared in config/conf.py, "
          "documented, and read by something")
def run_pass(root: str) -> List[str]:
    violations: List[str] = []
    declared, internal = declared_keys(root)
    if not declared:
        violations.append("config/conf.py: no conf(...) declarations found "
                          "(registry moved? update tools/lint)")
        return violations
    documented = documented_keys(root)
    for rel, lineno, key in used_keys(root):
        if key not in declared:
            violations.append(
                f"{rel}:{lineno}: conf key '{key}' is read but not "
                f"declared in config/conf.py — it has no type, default, "
                f"validation, or docs entry")
    for key in sorted(declared - internal - documented):
        violations.append(
            f"docs/configs.md: declared key '{key}' is not documented — "
            f"regenerate with spark_rapids_tpu.plan.docs.write_docs('docs')")
    for key in sorted(documented - declared):
        violations.append(
            f"docs/configs.md: documents '{key}' which is no longer "
            f"declared in config/conf.py — regenerate the docs")
    for key in unread_keys(root):
        violations.append(
            f"config/conf.py: declared key '{key}' is read by nothing in "
            f"the package, benchmark/ or chip_smoke.py — setting it does "
            f"nothing; delete the declaration")
    return violations
