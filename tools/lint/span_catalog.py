"""span-catalog pass: every span name opened in code must be declared.

Contract (mirroring the gauge-catalog guard): ``obs/span.CATALOG`` is
the closed set of span names — ``Span(...)``/``span(...)``/
``task_span(...)``/``record_span(...)`` raise at runtime on an
undeclared name, and an undeclared name would also fragment trace
reassembly (``assemble_traces`` groups by name for phase rollups). This
pass flags any string constant passed as the first argument (or
``name=`` keyword) of those calls that the CATALOG does not declare, so
the default lane catches the mistake without executing the span site.
Dynamic detail belongs in ``attrs``, never interpolated into the name —
an f-string first argument is flagged outright.

The same pass keeps the ``exec:host-sync`` span and its counter pair
whole: a bare ``jax.device_get(...)`` under ``spark_rapids_tpu/exec/``
goes round the one door (``utils/sync.host_get``) that records them, and
is flagged unless its file is allowlisted as off a query's path.
Pure AST, no imports.
"""

from __future__ import annotations

import ast
import os

from tools.lint import core
from tools.lint.core import register

#: the call names whose first argument is a span name
_SPAN_FUNCS = ("Span", "span", "task_span", "record_span")

#: where a device->host read has to go through utils/sync.host_get, and
#: the files under it whose reads are off every query's path (none today)
_SYNC_DIR = os.path.join("spark_rapids_tpu", "exec")
_SYNC_ALLOW: tuple = ()


def catalog_names(root: str) -> set:
    """CATALOG span names, parsed statically from obs/span.py."""
    path = os.path.join(core.pkg_dir(root), "obs", "span.py")
    entries = core.module_literal(path, "CATALOG")
    if entries is None:
        raise SystemExit("obs/span.py: CATALOG assignment not found "
                         "(update tools/lint/span_catalog.py)")
    return {name for name, _ in entries}


def _span_name_arg(node: ast.Call):
    """The expression supplying the span name, or None."""
    if node.args:
        return node.args[0]
    for kw in node.keywords:
        if kw.arg == "name":
            return kw.value
    return None


def check_file(path: str, declared: set, violations: list,
               root: str = "") -> None:
    try:
        tree = core.parse(path)
    except SyntaxError as e:
        violations.append(f"{path}: not parseable: {e}")
        return
    rel = os.path.relpath(path, root) if root else path
    guard_syncs = (rel.startswith(_SYNC_DIR + os.sep)
                   and rel not in _SYNC_ALLOW)

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        if (guard_syncs and isinstance(node.func, ast.Attribute)
                and node.func.attr == "device_get"
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "jax"):
            violations.append(
                f"{rel}:{node.lineno}: bare jax.device_get(...) on the "
                f"query path — read through utils/sync.host_get(x, site) "
                f"so the sync is an exec:host-sync span and is counted in "
                f"exec_host_sync_total")
            continue
        fname = (node.func.id if isinstance(node.func, ast.Name)
                 else node.func.attr if isinstance(node.func, ast.Attribute)
                 else None)
        if fname not in _SPAN_FUNCS:
            continue
        arg = _span_name_arg(node)
        if arg is None:
            continue
        if isinstance(arg, ast.JoinedStr):
            violations.append(
                f"{rel}:{arg.lineno}: span name passed to {fname}(...) is "
                f"an f-string — span names are a closed catalog "
                f"(obs/span.CATALOG); put the dynamic part in attrs")
        elif (isinstance(arg, ast.Constant) and isinstance(arg.value, str)
                and arg.value not in declared):
            violations.append(
                f"{rel}:{arg.lineno}: span name '{arg.value}' is passed to "
                f"{fname}(...) but is not declared in obs/span.CATALOG — "
                f"it raises KeyError at runtime and would be invisible to "
                f"trace reassembly")


@register("span-catalog",
          "every span name opened via span()/record_span() is declared")
def run_pass(root: str) -> list:
    declared = catalog_names(root)
    violations: list = []
    for path in core.iter_py_files(root):
        check_file(path, declared, violations, root)
    return violations
