"""gauge-catalog pass: metric/histogram names must be declared.

``obs/gauges.CATALOG`` is the single source of truth for every metric the
process exposes — a counter a subsystem increments but never declares is
invisible to snapshot()/Prometheus/QueryProfile diffs. Counter names end
in ``_total``; this pass flags any ``*_total`` string constant used as a
metric name (dict-literal key, subscript key, or first arg of ``note``)
that CATALOG does not declare, plus the memtrack per-site gauges and any
``*_ns`` histogram name passed to ``record``/``get`` that
``obs/histo.CATALOG`` does not declare. Pure AST, no imports.
"""

from __future__ import annotations

import ast
import os

from tools.lint import core
from tools.lint.core import register


def catalog_names(root: str) -> set:
    """CATALOG metric names, parsed statically from obs/gauges.py."""
    path = os.path.join(core.pkg_dir(root), "obs", "gauges.py")
    entries = core.module_literal(path, "CATALOG")
    if entries is None:
        raise SystemExit("obs/gauges.py: CATALOG assignment not found "
                         "(update tools/lint/gauge_catalog.py)")
    return {name for name, _, _ in entries}


def histo_names(root: str) -> set:
    """obs/histo.py CATALOG names (2-tuples of name, help)."""
    path = os.path.join(core.pkg_dir(root), "obs", "histo.py")
    entries = core.module_literal(path, "CATALOG")
    if entries is None:
        raise SystemExit("obs/histo.py: CATALOG assignment not found "
                         "(update tools/lint/gauge_catalog.py)")
    return {name for name, _ in entries}


def check_memtrack_site_gauges(declared: set, violations: list,
                               root: str) -> None:
    """Every memtrack site must have its derived peak gauge declared, and
    the fixed tracked-bytes gauges must be declared too."""
    path = os.path.join(core.pkg_dir(root), "obs", "memtrack.py")
    sites = core.module_literal(path, "SITES")
    if sites is None:
        violations.append("obs/memtrack.py: SITES tuple not found "
                          "(update tools/lint/gauge_catalog.py)")
        return
    expected = {"mem_site_" + s.replace("-", "_") + "_peak_bytes"
                for s in sites}
    expected |= {"mem_tracked_live_bytes", "mem_tracked_peak_bytes"}
    for name in sorted(expected - declared):
        violations.append(
            f"spark_rapids_tpu/obs/memtrack.py: memory gauge '{name}' is "
            f"emitted by memtrack.counters() but not declared in "
            f"obs/gauges.CATALOG — it would be invisible to "
            f"snapshot()/Prometheus")


def _is_metric_name(node: ast.AST) -> bool:
    return (isinstance(node, ast.Constant) and isinstance(node.value, str)
            and node.value.endswith("_total"))


def _is_histo_name(node: ast.AST) -> bool:
    return (isinstance(node, ast.Constant) and isinstance(node.value, str)
            and node.value.endswith("_ns"))


def check_file(path: str, declared: set, violations: list,
               histos: set = frozenset(), root: str = "") -> None:
    with open(path, "r") as f:
        src = f.read()
    try:
        tree = ast.parse(src, filename=path)
    except SyntaxError as e:
        violations.append(f"{path}: not parseable: {e}")
        return
    rel = os.path.relpath(path, root) if root else path

    def flag(const: ast.Constant, how: str) -> None:
        if const.value not in declared:
            violations.append(
                f"{rel}:{const.lineno}: counter '{const.value}' {how} but is "
                f"not declared in obs/gauges.CATALOG — it would be invisible "
                f"to snapshot()/Prometheus/QueryProfile diffs")

    for node in ast.walk(tree):
        if isinstance(node, ast.Dict):
            for k in node.keys:
                if k is not None and _is_metric_name(k):
                    flag(k, "is a dict-literal metric key")
        elif isinstance(node, ast.Subscript):
            sl = node.slice
            if _is_metric_name(sl):
                flag(sl, "is used as a subscript metric key")
        elif isinstance(node, ast.Call):
            fname = (node.func.id if isinstance(node.func, ast.Name)
                     else node.func.attr if isinstance(node.func,
                                                       ast.Attribute)
                     else None)
            if fname == "note" and node.args and _is_metric_name(
                    node.args[0]):
                flag(node.args[0], "is passed to note(...)")
            # histogram-catalog guard: record()/get() with a *_ns name
            # constant must reference a declared obs/histo.CATALOG entry
            if (fname in ("record", "get") and node.args
                    and _is_histo_name(node.args[0])
                    and node.args[0].value not in histos):
                violations.append(
                    f"{rel}:{node.args[0].lineno}: histogram "
                    f"'{node.args[0].value}' is passed to {fname}(...) but "
                    f"is not declared in obs/histo.CATALOG — record() "
                    f"raises on undeclared names at runtime")


@register("gauge-catalog",
          "every *_total metric / *_ns histogram name is declared")
def run_pass(root: str) -> list:
    declared = catalog_names(root)
    histos = histo_names(root)
    violations: list = []
    check_memtrack_site_gauges(declared, violations, root)
    for path in core.iter_py_files(root):
        check_file(path, declared, violations, histos, root)
    return violations
