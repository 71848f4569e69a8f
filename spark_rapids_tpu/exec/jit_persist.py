"""Cross-process persistence for shared_jit programs.

``shared_jit`` dedupes traced programs within one process, but a fresh
process still pays trace + compile for every distinct program before
its first query returns. This module extends the dedupe across
process restarts: on a shared_jit miss the traced program is serialized
with ``jax.export`` to an on-disk entry, and the next process that asks
for the same semantic key deserializes the executable instead of
re-tracing it.

Entry digest = sha256 over the semantic shared_jit key (already
``Expression.cache_key()``/stage-fingerprint based, so rename-equal plans
share and literal changes split) plus ``_environment_salt()``: the jax
version, the active backend, and the host CPU-feature fingerprint
(_xla_cpu_cache.py). Any of those changing lands in a fresh entry —
serialized StableHLO is versioned by jax, and host-compiled code must
never migrate across CPU feature sets (the r5/r6 SIGSEGV lesson).

Failure policy: this cache is an accelerator, never a correctness
dependency. A missing, corrupt, or signature-mismatched entry is
discarded and the program recompiled; any exception in load or store
falls back to the plain ``jax.jit`` path. Counters are exported as
``srtpu_jit_persist_*`` gauges (obs/gauges.py).
"""

from __future__ import annotations

import hashlib
import os
import tempfile
import threading
import time
from typing import Callable, Dict, Optional

import jax

try:
    from _xla_cpu_cache import cpu_feature_fingerprint, program_cache_dir
except ImportError:  # installed without the repo-root helper module
    import platform

    def cpu_feature_fingerprint() -> str:
        bits = [platform.machine()]
        model = ""
        flags: set = set()
        try:
            with open("/proc/cpuinfo") as f:
                for line in f:
                    if line.startswith(("flags", "Features")):
                        flags.update(line.split(":", 1)[1].split())
                    elif line.startswith("model name") and not model:
                        model = line.split(":", 1)[1].strip()
        except OSError:
            model = platform.processor() or "unknown"
        bits.append(model)
        bits.append(" ".join(sorted(flags)))
        return hashlib.sha256("|".join(bits).encode()).hexdigest()[:16]

    def program_cache_dir() -> str:
        return os.path.join(tempfile.gettempdir(),
                            f"srtpu_jit_persist_{cpu_feature_fingerprint()}")

_LOCK = threading.Lock()
_HITS = 0
_MISSES = 0
_STORES = 0
_STORE_BYTES = 0
_ERRORS = 0
_LOAD_NS = 0


def _count(name: str, delta: int = 1) -> None:
    global _HITS, _MISSES, _STORES, _STORE_BYTES, _ERRORS, _LOAD_NS
    with _LOCK:
        if name == "hit":
            _HITS += delta
        elif name == "miss":
            _MISSES += delta
        elif name == "store":
            _STORES += delta
        elif name == "store_bytes":
            _STORE_BYTES += delta
        elif name == "error":
            _ERRORS += delta
        elif name == "load_ns":
            _LOAD_NS += delta


def _environment_salt() -> str:
    """Everything outside the semantic key that changes what a serialized
    program means: jax serialization format (jax.__version__), the target
    platform (jax.default_backend()), and the host instruction set
    (cpu_feature_fingerprint()). Guarded by tools/lint/cache_keys.py."""
    return "|".join((jax.__version__, jax.default_backend(),
                     cpu_feature_fingerprint()))


def _digest(key: tuple) -> str:
    return hashlib.sha256(
        (_environment_salt() + "||" + repr(key)).encode()).hexdigest()[:32]


def _enabled_dir() -> Optional[str]:
    """Cache directory when persistence is enabled, else None."""
    try:
        from spark_rapids_tpu.config import conf as C
        active = C.get_active()
        if not active[C.JIT_PERSIST_ENABLED]:
            return None
        return active[C.JIT_PERSIST_DIR] or program_cache_dir()
    except Exception:
        return None


def _entry_path(dir_: str, digest: str) -> str:
    return os.path.join(dir_, digest + ".jexp")


_registered = False


def _ensure_registrations() -> None:
    """jax.export serializes the in/out pytree structure of a program, and
    custom pytree nodes (ColumnarBatch, DeviceColumn) need an explicit
    auxdata codec. Auxdata is pickled: the cache directory carries the
    same local trust as the XLA compile cache itself (both replay code
    artifacts written by this user)."""
    global _registered
    if _registered:
        return
    import pickle

    from jax import export as jexport

    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.column import ColVal, DeviceColumn

    for cls, name in ((DeviceColumn,
                       "spark_rapids_tpu.columnar.DeviceColumn"),
                      (ColumnarBatch,
                       "spark_rapids_tpu.columnar.ColumnarBatch")):
        jexport.register_pytree_node_serialization(
            cls, serialized_name=name,
            serialize_auxdata=pickle.dumps,
            deserialize_auxdata=pickle.loads)
    jexport.register_namedtuple_serialization(
        ColVal, serialized_name="spark_rapids_tpu.columnar.ColVal")
    _registered = True


def _load(dir_: str, digest: str):
    """Deserialize an entry into an Exported, or None (counting the miss,
    discarding anything unreadable)."""
    from jax import export as jexport
    _ensure_registrations()
    path = _entry_path(dir_, digest)
    try:
        with open(path, "rb") as f:
            blob = f.read()
    except OSError:
        _count("miss")
        return None
    try:
        t0 = time.perf_counter_ns()
        exported = jexport.deserialize(blob)
        _count("load_ns", time.perf_counter_ns() - t0)
        return exported
    except Exception:
        # Corrupt / truncated / version-incompatible entry: drop it so the
        # recompile below rewrites a good one.
        _count("error")
        _count("miss")
        try:
            os.unlink(path)
        except OSError:
            pass
        return None


def _export(jfn: Callable, args, kwargs) -> Optional[bytes]:
    """The traced program for the given call signature as a serialized
    ``jax.export`` blob, or None (counted) where it cannot be exported."""
    from jax import export as jexport
    try:
        _ensure_registrations()
        return bytes(jexport.export(jfn)(*args, **kwargs).serialize())
    except Exception:
        # Not every program is exportable (callbacks, unusual pytrees);
        # the in-process jit keeps working.
        _count("error")
        return None


def _store(dir_: str, digest: str, blob: bytes) -> None:
    """Write an entry atomically (tmp + rename: concurrent processes race
    benignly to the same content)."""
    try:
        os.makedirs(dir_, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=dir_, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(blob)
            os.replace(tmp, _entry_path(dir_, digest))
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        _count("store")
        _count("store_bytes", len(blob))
    except Exception:
        # not every dir is writable; the in-process program keeps working
        _count("error")


class _PersistentProgram:
    """Callable wrapper around one shared_jit entry.

    First call resolves against the on-disk cache: a hit binds
    ``jax.jit(exported.call)`` (no re-trace of the original function); a
    miss traces via ``make()``, exports the program, and binds the export
    it has just written, read back: the process that traces a program
    compiles exactly the module every later process will load, so XLA's
    persistent cache holds it after ONE compile (binding the traced
    function itself would compile a module that no later process asks
    for, and leave the loaded export to compile again in the next one).
    A bound export whose call signature drifts from what was exported
    (different avals/pytree) permanently falls back to a fresh trace —
    jax raises before running anything wrong.
    """

    __slots__ = ("_key", "_make", "_fn", "_from_export")

    def __init__(self, key: tuple, make: Callable[[], Callable]):
        self._key = key
        self._make = make
        self._fn: Optional[Callable] = None
        self._from_export = False

    def _fresh(self) -> Callable:
        self._from_export = False
        self._fn = jax.jit(self._make())
        return self._fn

    def _bind(self, exported) -> Callable:
        self._from_export = True
        self._fn = jax.jit(exported.call)
        return self._fn

    def _first_call(self, args, kwargs):
        from jax import export as jexport
        dir_ = _enabled_dir()
        if not dir_:
            return self._fresh()(*args, **kwargs)
        digest = _digest(self._key)
        exported = _load(dir_, digest)
        if exported is not None:
            try:
                out = self._bind(exported)(*args, **kwargs)
                _count("hit")
                return out
            except Exception:
                # Signature drift (aval/pytree mismatch vs. what was
                # exported): recompile, and refresh the entry.
                _count("error")
                _count("miss")
        blob = _export(jax.jit(self._make()), args, kwargs)
        if blob is not None:
            try:
                out = self._bind(jexport.deserialize(blob))(*args, **kwargs)
            except Exception:
                _count("error")
            else:
                _store(dir_, digest, blob)
                return out
        return self._fresh()(*args, **kwargs)

    def __call__(self, *args, **kwargs):
        fn = self._fn
        if fn is None:
            return self._first_call(args, kwargs)
        if self._from_export:
            try:
                return fn(*args, **kwargs)
            except Exception:
                # The exported program only accepts its recorded
                # signature; later calls with new shapes re-trace fresh.
                return self._fresh()(*args, **kwargs)
        return fn(*args, **kwargs)


def bind(key: tuple, make: Callable[[], Callable]) -> Callable:
    """shared_jit's construction hook: a persist-aware program when the
    cache is enabled, the plain jit otherwise."""
    if _enabled_dir() is None:
        return jax.jit(make())
    return _PersistentProgram(key, make)


def counters() -> Dict[str, int]:
    return {"jit_persist_hit_total": _HITS,
            "jit_persist_miss_total": _MISSES,
            "jit_persist_store_total": _STORES,
            "jit_persist_bytes_total": _STORE_BYTES,
            "jit_persist_error_total": _ERRORS,
            "jit_persist_load_ns_total": _LOAD_NS}


def reset_stats() -> None:
    global _HITS, _MISSES, _STORES, _STORE_BYTES, _ERRORS, _LOAD_NS
    with _LOCK:
        _HITS = _MISSES = _STORES = _STORE_BYTES = _ERRORS = _LOAD_NS = 0
