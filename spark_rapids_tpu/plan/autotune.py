"""Measurement-driven dispatch: persistent per-(op, shape-class) timings.

PR 11's join/agg kernels pick among dense / bucketed / general-ht /
sorted-hash paths with hardcoded thresholds, and ``plan/cbo.py`` costs
placement with made-up constants. This module closes the loop from *measured* timings back into
those decisions, mirroring the reference's ``CostBasedOptimizer``
bandwidth-flavored model:

* ``observe()`` buffers (op-kind, shape-class, path, ns, rows) samples;
  ``feedback()`` harvests them from a finished exec tree out of the
  existing QueryProfile operator timings (``obs/profile.py`` calls it
  from ``QueryProfile.finish``) and ``flush()`` merges + persists.
* The on-disk store is one JSON file per environment, named by
  ``_store_digest()`` — sha256 over ``_environment_salt()`` (jax
  version, active backend, host CPU-feature fingerprint — the exact
  ``jit_persist`` contract, guarded by tools/lint/cache_keys.py) so
  timings never migrate across backends or hosts. The salt is *also*
  recorded inside the file and re-verified on load; corrupt, truncated,
  or salt-drifted stores are unlinked and dispatch degrades to the
  static defaults.
* ``choose()`` is the Dispatcher facade the hot paths consult: with no
  sample for the static path it returns the static choice
  (``source="default"`` — measurement is never a correctness
  dependency); once the static path is measured it deterministically
  explores any unmeasured order-equivalent candidate, then ranks all
  candidates by median ns/row (``source="measured"``).

Shape-class = log2-bucketed rows x key-width x dtype-family
(``shape_class()``); batch capacities are already power-of-two buckets
so ``ColumnarBatch.capacity`` is used as the rows proxy — no device
sync on the hot path. Callers restrict candidate sets to paths proven
to produce bit-identical output in identical order (dense<->unique for
every join type; ht<->sorted only for semi/anti; lex<->radix and
resort<->merge for ``op="sort"``/``"sort:ooc"``; scan<->rmq for
``op="window:minmax"`` — comparisons only, no float reassociation), so
measurements only ever *re-rank* paths, never change results.

Counters export as ``srtpu_autotune_{hit,miss,store,override}_total``
(obs/gauges.py CATALOG). Config: ``spark.rapids.tpu.autotune.*``; the
``SRTPU_AUTOTUNE_DIR`` env var overrides the default store directory
(tests pin it to a fresh tmpdir for hermetic runs).
"""

from __future__ import annotations

import hashlib
import json
import math
import os
import statistics
import tempfile
import threading
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import jax

from spark_rapids_tpu.exec.jit_persist import cpu_feature_fingerprint

_LOCK = threading.RLock()

#: bump when the on-disk schema changes; folded into the store digest
_SCHEMA_VERSION = 1
#: per-(op, shape, path) sample cap — bounds file size and keeps the
#: median responsive to drift (oldest samples age out)
_MAX_SAMPLES = 32
#: per-node pending decision cap (profile disabled -> never harvested)
MAX_PENDING_DECISIONS = 64

_CONFIGURED = False
_ENABLED = True
_DIR: Optional[str] = None
_MIN_SAMPLES = 2
_LOADED = False
#: {"op|shape": {"path": [ns_per_row, ...]}}
_ENTRIES: Dict[str, Dict[str, List[float]]] = {}
#: buffered (op, shape, path, ns, rows) awaiting flush()
_PENDING: List[Tuple[str, str, str, float, float]] = []

_HITS = 0
_MISSES = 0
_STORES = 0
_OVERRIDES = 0


# -- environment salt / store digest ------------------------------------
def _environment_salt() -> str:
    """Everything outside the semantic key that changes what a timing
    means: jax version (jax.__version__), the target platform
    (jax.default_backend()), and the host instruction set
    (cpu_feature_fingerprint()). Same contract as jit_persist._digest;
    guarded by tools/lint/cache_keys.py."""
    return "|".join((jax.__version__, jax.default_backend(),
                     cpu_feature_fingerprint()))


def _store_digest() -> str:
    key = ("srtpu-autotune", _SCHEMA_VERSION)
    return hashlib.sha256(
        (_environment_salt() + "||" + repr(key)).encode()).hexdigest()[:32]


def store_path() -> Optional[str]:
    """Absolute path of the store file for this environment, or None
    when persistence is disabled."""
    with _LOCK:
        _ensure_configured_locked()
        if not _ENABLED or not _DIR:
            return None
        return os.path.join(_DIR, _store_digest() + ".json")


# -- configuration ------------------------------------------------------
def configure(conf) -> None:
    """Adopt a RapidsConf (plan/overrides.py calls this per query)."""
    from spark_rapids_tpu.config import conf as C
    try:
        enabled = bool(conf[C.AUTOTUNE_ENABLED])
        directory = str(conf[C.AUTOTUNE_DIR] or "").strip()
        min_samples = max(1, int(conf[C.AUTOTUNE_MIN_SAMPLES]))
    except Exception:
        enabled, directory, min_samples = False, "", 2
    if not directory:
        directory = os.environ.get("SRTPU_AUTOTUNE_DIR", "").strip()
    if not directory:
        directory = os.path.join(
            tempfile.gettempdir(),
            f"srtpu_autotune_{cpu_feature_fingerprint()}")
    global _CONFIGURED, _ENABLED, _DIR, _MIN_SAMPLES, _LOADED, _ENTRIES
    with _LOCK:
        if directory != _DIR or enabled != _ENABLED:
            _LOADED = False
            _ENTRIES = {}
        _ENABLED, _DIR, _MIN_SAMPLES = enabled, directory, min_samples
        _CONFIGURED = True


def _ensure_configured_locked() -> None:
    global _ENABLED, _CONFIGURED
    if _CONFIGURED:
        return
    try:
        from spark_rapids_tpu.config import conf as C
        configure(C.get_active())
    except Exception:
        _ENABLED, _CONFIGURED = False, True


# -- store load / persist ----------------------------------------------
def _load_locked() -> None:
    """Read the store file once; unlink anything that fails validation
    (corrupt JSON, truncated writes, salt drift) and start empty."""
    global _LOADED, _ENTRIES
    if _LOADED:
        return
    _LOADED = True
    _ENTRIES = {}
    if not _ENABLED or not _DIR:
        return
    path = os.path.join(_DIR, _store_digest() + ".json")
    if not os.path.exists(path):
        return
    try:
        with open(path, "r", encoding="utf-8") as f:
            data = json.load(f)
        if not isinstance(data, dict):
            raise ValueError("store root is not an object")
        if data.get("salt") != _environment_salt():
            raise ValueError("environment salt drift")
        raw = data.get("entries")
        if not isinstance(raw, dict):
            raise ValueError("missing entries")
        clean: Dict[str, Dict[str, List[float]]] = {}
        for key, paths in raw.items():
            if not (isinstance(key, str) and isinstance(paths, dict)):
                raise ValueError("malformed entry")
            out: Dict[str, List[float]] = {}
            for p, samples in paths.items():
                if not (isinstance(p, str) and isinstance(samples, list)):
                    raise ValueError("malformed samples")
                vals = []
                for s in samples:
                    v = float(s)
                    if not math.isfinite(v) or v < 0:
                        raise ValueError("non-finite sample")
                    vals.append(v)
                out[p] = vals[-_MAX_SAMPLES:]
            clean[key] = out
        _ENTRIES = clean
    except Exception:
        _ENTRIES = {}
        try:
            os.unlink(path)
        except OSError:
            pass


def _persist_locked() -> None:
    if not _ENABLED or not _DIR:
        return
    tmp = None
    try:
        os.makedirs(_DIR, exist_ok=True)
        payload = json.dumps(
            {"version": _SCHEMA_VERSION, "salt": _environment_salt(),
             "entries": _ENTRIES},
            sort_keys=True)
        fd, tmp = tempfile.mkstemp(dir=_DIR, prefix=".autotune-")
        with os.fdopen(fd, "w", encoding="utf-8") as f:
            f.write(payload)
        os.replace(tmp, os.path.join(_DIR, _store_digest() + ".json"))
        tmp = None
    except OSError:
        if tmp is not None:
            try:
                os.unlink(tmp)
            except OSError:
                pass


# -- shape classes / fingerprints --------------------------------------
def shape_class(rows: int, key_width: int = 0, family: str = "na") -> str:
    """log2-bucketed rows x key-width x dtype-family."""
    bucket = max(int(rows), 1).bit_length() - 1
    return f"r{bucket}/w{int(key_width)}/{family}"


def family_of(type_names: Iterable[str]) -> str:
    """Collapse spark type names into a coarse dtype family label."""
    fams = set()
    for n in type_names:
        n = str(n).lower()
        if "string" in n or "char" in n:
            fams.add("str")
        elif "float" in n or "double" in n:
            fams.add("flt")
        elif "decimal" in n:
            fams.add("dec")
        else:
            fams.add("int")
    return "+".join(sorted(fams)) or "na"


def plan_fingerprint(obj) -> str:
    """Stable fingerprint of a plan fragment (expression reprs are
    deterministic across processes; selectivity ratios key on this)."""
    return hashlib.sha256(repr(obj).encode()).hexdigest()[:16]


# -- observation --------------------------------------------------------
def observe(op: str, shape: str, path: str, ns: float, rows: float) -> None:
    """Buffer one timing sample (ns over rows); flushed at query finish.

    The (ns, rows) pair is stored as ns/row, which doubles as a plain
    ratio channel: selectivity feedback passes (output_rows, input_rows)
    and reads the stored "ns/row" back as the observed ratio."""
    try:
        ns_f, rows_f = float(ns), float(rows)
    except (TypeError, ValueError):
        return
    if rows_f <= 0 or ns_f < 0 or not math.isfinite(ns_f / rows_f):
        return
    with _LOCK:
        _ensure_configured_locked()
        if not _ENABLED:
            return
        _PENDING.append((str(op), str(shape), str(path), ns_f, rows_f))


def observe_ratio(kind: str, fingerprint: str,
                  out_rows: float, in_rows: float) -> None:
    observe(f"sel:{kind}", fingerprint, "ratio", out_rows, in_rows)


def flush() -> int:
    """Merge buffered samples into the store and persist. Returns the
    number of samples merged."""
    global _STORES
    with _LOCK:
        _ensure_configured_locked()
        if not _ENABLED:
            _PENDING.clear()
            return 0
        if not _PENDING:
            return 0
        _load_locked()
        merged = 0
        for op, shape, path, ns, rows in _PENDING:
            samples = _ENTRIES.setdefault(f"{op}|{shape}", {}).setdefault(
                path, [])
            samples.append(ns / rows)
            del samples[:-_MAX_SAMPLES]
            merged += 1
        _PENDING.clear()
        if merged:
            _STORES += merged
            _persist_locked()
        return merged


# -- dispatch -----------------------------------------------------------
def choose(op: str, shape: str, static_path: str,
           candidates: Sequence[str]) -> Tuple[str, str]:
    """Pick a path for (op, shape) among order-equivalent candidates.

    Precedence: (1) static path unmeasured -> static, "default" (miss);
    (2) some candidate unmeasured -> explore it, "measured" (hit +
    override — deterministic, so a warm store converges); (3) all
    measured -> lowest median ns/row, "measured" (hit, + override when
    it differs from the static choice)."""
    global _HITS, _MISSES, _OVERRIDES
    with _LOCK:
        _ensure_configured_locked()
        if not _ENABLED:
            return static_path, "default"
        _load_locked()
        paths = _ENTRIES.get(f"{op}|{shape}", {})
        meds = {}
        for p in candidates:
            samples = paths.get(p)
            if samples and len(samples) >= _MIN_SAMPLES:
                meds[p] = statistics.median(samples)
        if static_path not in meds:
            _MISSES += 1
            return static_path, "default"
        unexplored = [p for p in candidates if p not in meds]
        if unexplored:
            _HITS += 1
            _OVERRIDES += 1
            return unexplored[0], "measured"
        order = list(candidates)
        best = min(meds, key=lambda p: (meds[p], order.index(p)))
        _HITS += 1
        if best != static_path:
            _OVERRIDES += 1
        return best, "measured"


def medians(op: str, shape: str,
            paths: Sequence[str]) -> Dict[str, float]:
    """Median ns/row per path, only paths with >= minSamples samples."""
    with _LOCK:
        _ensure_configured_locked()
        if not _ENABLED:
            return {}
        _load_locked()
        stored = _ENTRIES.get(f"{op}|{shape}", {})
        out = {}
        for p in paths:
            samples = stored.get(p)
            if samples and len(samples) >= _MIN_SAMPLES:
                out[p] = statistics.median(samples)
        return out


def ratio(kind: str, fingerprint: str) -> Optional[float]:
    """Observed output/input ratio for a plan fragment, clamped to
    [0, 1]; None when unmeasured (caller keeps its static constant)."""
    global _HITS, _MISSES
    with _LOCK:
        _ensure_configured_locked()
        if not _ENABLED:
            return None
        _load_locked()
        samples = _ENTRIES.get(f"sel:{kind}|{fingerprint}", {}).get("ratio")
        if not samples or len(samples) < _MIN_SAMPLES:
            _MISSES += 1
            return None
        _HITS += 1
        return min(max(statistics.median(samples), 0.0), 1.0)


def record_decision(node, op: str, path: str, source: str,
                    shape: str, ns: Optional[float] = None,
                    rows: Optional[float] = None) -> None:
    """Attach a dispatch decision to an exec node. obs/profile.py
    copies it into node stats (explain_analyze renders
    ``path=<p> source=measured|default``) and ``feedback()`` turns
    timed entries into store samples at query finish."""
    entry = {"op": op, "path": path, "source": source, "shape": shape}
    if ns is not None:
        entry["ns"] = float(ns)
    if rows is not None:
        entry["rows"] = float(rows)
    pend = getattr(node, "_dispatch", None)
    if pend is None:
        pend = []
        node._dispatch = pend
    pend.append(entry)
    del pend[:-MAX_PENDING_DECISIONS]


# -- query-finish feedback ---------------------------------------------
def feedback(root) -> None:
    """Harvest a finished exec tree: timed dispatch decisions, filter /
    agg selectivity ratios, and device/cpu ns-per-row totals for the
    CBO. Called from QueryProfile.finish; never raises."""
    with _LOCK:
        _ensure_configured_locked()
        enabled = _ENABLED
    if not enabled:
        with _LOCK:
            _PENDING.clear()
        return
    try:
        if root is not None:
            _harvest(root)
    except Exception:
        pass
    try:
        flush()
    except Exception:
        pass


def _harvest(root) -> None:
    dev_ns = dev_rows = cpu_ns = cpu_rows = 0
    seen = set()
    stack = [root]
    while stack:
        node = stack.pop()
        if id(node) in seen:
            continue
        seen.add(id(node))
        stack.extend(getattr(node, "children", ()) or ())
        stack.extend(getattr(node, "fused_ops", ()) or ())
        pend = getattr(node, "_dispatch", None)
        if pend:
            drained = list(pend)
            del pend[:len(drained)]
            for d in drained:
                if d.get("ns") is not None and d.get("rows"):
                    observe(d["op"], d["shape"], d["path"],
                            d["ns"], d["rows"])
        try:
            snap = node.metrics_snapshot()
        except Exception:
            continue
        name = type(node).__name__
        op_ns = int(snap.get("opTime", 0) or 0)
        rows = int(snap.get("numOutputRows", 0) or 0)
        if name.startswith("Cpu"):
            cpu_ns += op_ns
            cpu_rows += rows
        else:
            dev_ns += op_ns
            dev_rows += rows
        if name == "FilterExec" and rows >= 0:
            cond = getattr(node, "condition", None)
            kids = getattr(node, "children", None)
            if cond is not None and kids:
                try:
                    in_rows = int(
                        kids[0].metrics_snapshot().get("numOutputRows", 0))
                except Exception:
                    in_rows = 0
                if in_rows > 0:
                    observe_ratio("filter", plan_fingerprint(cond),
                                  rows, in_rows)
        elif name == "HashAggregateExec":
            groups = getattr(node, "group_exprs", None)
            kids = getattr(node, "children", None)
            if groups is not None and kids:
                try:
                    in_rows = int(
                        kids[0].metrics_snapshot().get("numOutputRows", 0))
                except Exception:
                    in_rows = 0
                if in_rows > 0 and rows > 0:
                    observe_ratio("agg", plan_fingerprint(tuple(groups)),
                                  rows, in_rows)
    if dev_ns > 0 and dev_rows > 0:
        observe("cbo", "global", "dev", dev_ns, dev_rows)
    if cpu_ns > 0 and cpu_rows > 0:
        observe("cbo", "global", "cpu", cpu_ns, cpu_rows)


# -- counters -----------------------------------------------------------
def counters() -> Dict[str, int]:
    with _LOCK:
        return {
            "autotune_hit_total": _HITS,
            "autotune_miss_total": _MISSES,
            "autotune_store_total": _STORES,
            "autotune_override_total": _OVERRIDES,
        }


def reset_stats() -> None:
    global _HITS, _MISSES, _STORES, _OVERRIDES
    with _LOCK:
        _HITS = _MISSES = _STORES = _OVERRIDES = 0


def reset_for_tests() -> None:
    """Drop all in-memory state (store file untouched)."""
    global _CONFIGURED, _LOADED, _ENTRIES, _PENDING
    with _LOCK:
        _CONFIGURED = False
        _LOADED = False
        _ENTRIES = {}
        _PENDING = []
        reset_stats()
