"""spark_rapids_tpu: a TPU-native SQL acceleration framework.

A from-scratch re-design of the capability set of NVIDIA's RAPIDS Accelerator
for Apache Spark (reference: /root/reference, spark-rapids v25.02), built
TPU-first on JAX/XLA/Pallas:

- TPU-resident Arrow-compatible columnar batches (columnar/)
- Spark-exact expression engine compiled to fused XLA (exprs/)
- physical operators: scan/project/filter/hash-agg/sort/join/... (exec/)
- plan rewrite with per-operator CPU fallback (plan/, cpu/)
- HBM accounting pool, device->host->disk spill, OOM retry/split (mem/)
- columnar shuffle: kudo-style host serialization + ICI all_to_all (shuffle/)
- device-mesh parallelism helpers (parallel/)

Reference architecture map: SURVEY.md sections 1-2.
"""

import os as _os

import jax as _jax

# Spark semantics are 64-bit (LongType, DoubleType, TimestampType micros).
# The whole framework assumes x64 is on; see docs/design.md.
_jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache: operator jits are created per exec
# instance, and bench/driver runs are separate processes - without it every
# identical pipeline pays full compile again. Where JAX_COMPILATION_CACHE_DIR
# is set JAX reads it itself and no directory is set in code. Otherwise the
# cache lives at a fixed path derived from the package's own location,
# <checkout>/.jax_cache (git-ignored): the path is part of the cache key, so
# a directory that moves never hits. The two thresholds are not part of the
# key; zeroed so every operator kernel is kept (the working set is bounded
# per capacity bucket).
if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update(
        "jax_compilation_cache_dir",
        _os.path.join(_os.path.dirname(_os.path.dirname(
            _os.path.abspath(__file__))), ".jax_cache"))
_jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
_jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

__version__ = "0.1.0"

from spark_rapids_tpu import types  # noqa: E402,F401
from spark_rapids_tpu.config.conf import RapidsConf  # noqa: E402,F401
