"""Core device kernels: gather, sortable keys, hashing, segmented aggregation,
join gather-maps.

This module is the TPU-native replacement for the reference's cudf Table
primitives (reference: ai.rapids.cudf.Table gather/orderBy/groupBy/join used
throughout sql-plugin; SURVEY.md section 2.11 item 1). Instead of a C++ kernel
per operation, every primitive here is a traced JAX function over statically
shaped buffers, so XLA fuses chains of them into a few TPU kernels.

Key design decisions (TPU-first):
- All row movement is expressed as a *gather map* (an int32 index vector) plus
  one `gather_batch` call — the same decomposition cudf uses (GatherMap), but
  here the map computation and the gather both live in one XLA computation.
- Ordering uses order-preserving bijections into uint64 ("sortable keys") +
  `lexsort`, instead of comparator-based sorts: Spark null ordering and NaN
  semantics become pure bit tricks (see `sortable_key`).
- Grouping/joining use 64-bit mixed hashes with *exact verification*: hash
  gives candidate equality classes, a verification pass compares the real key
  columns so results never depend on hash quality (join verification is exact;
  see `hash_keys`).
- Variable-width (string) columns ride along as offsets+bytes; gathers
  recompute offsets with a cumsum and move bytes with one flat gather.
- The gather, sort, aggregate, join and concat entry points carry a
  ``jax.named_scope`` (gather, sort, agg.group, agg.reduce, join.build,
  join.probe, concat): HLO metadata only, so no op changes and the
  persistent compilation cache's key is untouched; a device op profile
  then ranks by kernel and not by ``concatenate.18``
  (docs/observability.md).
"""

from __future__ import annotations

import threading
from functools import lru_cache, partial
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, bucket_capacity
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.utils.sync import host_get



# ---------------------------------------------------------------------------
# Gather
# ---------------------------------------------------------------------------


def _string_row_ids(offsets: jax.Array, nbytes: int) -> jax.Array:
    """Row id owning each byte position: the last row whose start <= pos.

    Scatter-count + cumsum instead of a per-byte binary search — one
    bandwidth pass over the byte space beats nbytes*log(cap) gathers on
    TPU (searchsorted lowers to serialized dependent gathers)."""
    starts = jnp.clip(offsets[:-1], 0, nbytes)
    marks = jnp.zeros(nbytes + 1, jnp.int32).at[starts].add(
        1, mode="drop")
    return jnp.cumsum(marks[:nbytes]) - 1


@jax.named_scope("gather")
def gather_column(
    col: DeviceColumn,
    indices: jax.Array,
    row_valid: jax.Array,
    out_byte_capacity: Optional[int] = None,
) -> DeviceColumn:
    """Gather rows of one column. ``indices`` has the output capacity;
    ``row_valid`` marks LIVE output rows (False rows produce null/zero).

    Out-of-range or negative indices must be pre-clipped by the caller except
    where ``row_valid`` is False (those gather row 0 and are masked).
    """
    safe_idx = jnp.where(row_valid, indices, 0).astype(jnp.int32)
    validity = jnp.where(row_valid, col.validity[safe_idx], False)
    if col.is_struct:
        # struct-of-columns: move every child by the same map (recursive)
        kids = tuple(gather_column(c, indices, row_valid & validity)
                     for c in col.children)
        return DeviceColumn(col.dtype, jnp.zeros(0, jnp.int32), validity,
                            children=kids)
    if col.is_map:
        # entry-space gather (string byte gather generalized to entries)
        lens = col.offsets[1:] - col.offsets[:-1]
        out_lens = jnp.where(row_valid & validity, lens[safe_idx], 0)
        out_offsets = jnp.concatenate(
            [jnp.zeros(1, jnp.int32),
             jnp.cumsum(out_lens).astype(jnp.int32)])
        ecap = out_byte_capacity or col.children[0].capacity
        rows = _string_row_ids(out_offsets, ecap)
        rows = jnp.clip(rows, 0, indices.shape[0] - 1)
        rel = jnp.arange(ecap, dtype=jnp.int32) - out_offsets[rows]
        src = col.offsets[safe_idx[rows]] + rel
        src = jnp.clip(src, 0, col.children[0].capacity - 1)
        in_range = jnp.arange(ecap, dtype=jnp.int32) < out_offsets[-1]
        kids = tuple(gather_column(c, src, in_range) for c in col.children)
        return DeviceColumn(col.dtype, jnp.zeros(0, jnp.int32), validity,
                            out_offsets, children=kids)
    if col.offsets is None:
        data = col.data[safe_idx]
        data = jnp.where(row_valid & validity, data, jnp.zeros_like(data))
        data2 = None
        if col.data2 is not None:
            data2 = col.data2[safe_idx]
            data2 = jnp.where(row_valid & validity, data2,
                              jnp.zeros_like(data2))
        return DeviceColumn(col.dtype, data, validity, None, col.dictionary,
                            col.dict_size, col.dict_max_len, data2)
    lens = col.offsets[1:] - col.offsets[:-1]
    out_lens = jnp.where(row_valid, lens[safe_idx], 0)
    out_offsets = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(out_lens).astype(jnp.int32)]
    )
    out_bytes = out_byte_capacity or col.data.shape[0]
    rows = _string_row_ids(out_offsets, out_bytes)
    rows = jnp.clip(rows, 0, indices.shape[0] - 1)
    rel = jnp.arange(out_bytes, dtype=jnp.int32) - out_offsets[rows]
    src = col.offsets[safe_idx[rows]] + rel
    src = jnp.clip(src, 0, col.data.shape[0] - 1)
    in_range = jnp.arange(out_bytes, dtype=jnp.int32) < out_offsets[-1]
    data = jnp.where(in_range, col.data[src], jnp.zeros((), col.data.dtype))
    return DeviceColumn(col.dtype, data, validity, out_offsets)


def decode_dictionary(col: DeviceColumn) -> DeviceColumn:
    """Dict-encoded column -> plain string/binary column (traced).

    One byte-space gather of the dictionary by code; the output byte capacity
    is the static worst case capacity * dict_max_len."""
    assert col.is_dict
    worst = col.capacity * max(col.dict_max_len, 1)
    assert worst < (1 << 31), (
        "decoded worst case overflows int32 offsets; ingest must not "
        "dict-encode such columns (_dict_bytes_encodable)")
    out_bytes = bucket_capacity(max(worst, 8), 8)
    # null rows gather with row_valid=False -> length 0, validity False
    return gather_column(col.dictionary, col.data, col.validity, out_bytes)


def ensure_plain_column(col: DeviceColumn) -> DeviceColumn:
    return decode_dictionary(col) if col.is_dict else col


def ensure_plain_batch(batch: ColumnarBatch) -> ColumnarBatch:
    """Decode any dict-encoded columns (for operators/serializers that work
    on raw bytes, and for joins where the two sides' dictionaries differ)."""
    if not any(c.is_dict for c in batch.columns):
        return batch
    return ColumnarBatch([ensure_plain_column(c) for c in batch.columns],
                         batch.num_rows)


def _arr_to_words(a: jax.Array) -> List[jax.Array]:
    """Fixed-width data lane -> uint32 words (bijective encodings).

    All per-batch row movement packs every fixed-width lane into uint32
    words, gathers the (W, N) matrix once, and unpacks: one gather op, not
    one per column. No cell of the benchmark reaches the gather yet, so the
    form has no number on the ledger (ROADMAP S5).
    """
    dt = a.dtype
    if dt == jnp.bool_:
        return [a.astype(jnp.uint32)]
    if dt.itemsize <= 4 and jnp.issubdtype(dt, jnp.integer):
        return [jax.lax.bitcast_convert_type(a.astype(jnp.int32), jnp.uint32)]
    if dt == jnp.float32:
        return [jax.lax.bitcast_convert_type(a, jnp.uint32)]
    if dt.itemsize == 8 and jnp.issubdtype(dt, jnp.integer):
        w = jax.lax.bitcast_convert_type(a, jnp.uint32)  # (..., 2) [lo, hi]
        return [w[..., 0], w[..., 1]]
    # NOTE: float64 is deliberately NOT word-packable. The real-TPU backend
    # stores f64 as a f32 double-double with flush-to-zero arithmetic: any
    # float decomposition (astype, subtract) silently flushes subnormal
    # lo/hi parts, and 64-bit bitcasts don't lower. f64 columns instead ride
    # a separate same-dtype matrix in gather_columns — pure data movement,
    # exact on every backend.
    raise NotImplementedError(f"pack dtype {dt}")


def _words_to_arr(words: List[jax.Array], dt) -> jax.Array:
    dt = jnp.dtype(dt)
    if dt == jnp.bool_:
        return words[0].astype(jnp.bool_)
    if dt.itemsize <= 4 and jnp.issubdtype(dt, jnp.integer):
        return jax.lax.bitcast_convert_type(words[0], jnp.int32).astype(dt)
    if dt == jnp.float32:
        return jax.lax.bitcast_convert_type(words[0], jnp.float32)
    if dt.itemsize == 8 and jnp.issubdtype(dt, jnp.integer):
        u = (words[1].astype(jnp.uint64) << jnp.uint64(32)) | words[0].astype(
            jnp.uint64)
        return u.astype(dt)
    raise NotImplementedError(f"unpack dtype {dt}")


def gather_lanes(lanes: Sequence[jax.Array], idx: jax.Array) -> List[jax.Array]:
    """Gather many same-capacity 1-D arrays by one index vector with one
    packed take (+ one more for f64 lanes) — the gather_columns trick for
    raw arrays (one XLA gather op ~0.25s at 16M rows regardless of width)."""
    f64_pos = [k for k, a in enumerate(lanes) if a.dtype == jnp.float64]
    out: List[Optional[jax.Array]] = [None] * len(lanes)
    if f64_pos:
        gf = jnp.take(jnp.stack([lanes[k] for k in f64_pos], axis=0), idx,
                      axis=1, mode="clip")
        for j, k in enumerate(f64_pos):
            out[k] = gf[j]
    rest = [k for k in range(len(lanes)) if out[k] is None]
    if rest:
        words: List[jax.Array] = []
        slots = []
        for k in rest:
            ws = _arr_to_words(lanes[k])
            slots.append((len(words), len(ws)))
            words.extend(ws)
        g = jnp.take(jnp.stack(words, axis=0), idx, axis=1, mode="clip")
        for k, (start, n) in zip(rest, slots):
            out[k] = _words_to_arr([g[start + j] for j in range(n)],
                                   lanes[k].dtype)
    return out  # type: ignore[return-value]


@jax.named_scope("gather")
def gather_columns(
    cols: Sequence[DeviceColumn],
    indices: jax.Array,
    row_valid: jax.Array,
    out_byte_capacities: Optional[Sequence[Optional[int]]] = None,
) -> List[DeviceColumn]:
    """Gather many columns by ONE index vector with ONE fused gather op.

    Fixed-width lanes (data, data2, dict codes) pack into a (W, cap) uint32
    matrix + validity bits pack 32-per-word; a single `take` moves
    everything. Var-width (string/binary) columns keep the byte-space path
    (`gather_column`) — their offsets/data shapes differ per column.

    Semantics identical to mapping `gather_column` over `cols`.
    """
    from spark_rapids_tpu.config import conf as _C
    if not _C.GATHER_FUSION_ENABLED.get(_C.get_active()):
        return [gather_column(c, indices, row_valid,
                              out_byte_capacities[i]
                              if out_byte_capacities else None)
                for i, c in enumerate(cols)]
    if not cols:
        return []
    safe_idx = jnp.where(row_valid, indices, 0).astype(jnp.int32)
    fixed = [i for i, c in enumerate(cols)
             if c.offsets is None and c.children is None]
    out: List[Optional[DeviceColumn]] = [None] * len(cols)
    for i, c in enumerate(cols):
        if c.offsets is not None or c.children is not None:
            bc = out_byte_capacities[i] if out_byte_capacities else None
            out[i] = gather_column(c, indices, row_valid, bc)
    if not fixed:
        return out  # type: ignore[return-value]

    lanes: List[jax.Array] = []
    lane_slot: dict = {}  # (col index, "data"/"data2") -> lane index
    for i in fixed:
        c = cols[i]
        for which, arr in (("data", c.data), ("data2", c.data2)):
            if arr is not None:
                lane_slot[(i, which)] = len(lanes)
                lanes.append(arr)
    # validity bits, 32 per uint32 word (cheaper than one bool lane each)
    n_vwords = (len(fixed) + 31) // 32
    for base in range(0, len(fixed), 32):
        vbits = jnp.zeros(cols[fixed[0]].validity.shape[0], jnp.uint32)
        for bit, i in enumerate(fixed[base:base + 32]):
            vbits = vbits | (cols[i].validity.astype(jnp.uint32)
                             << jnp.uint32(bit))
        lanes.append(vbits)
    g = gather_lanes(lanes, safe_idx)
    vwords = g[len(lanes) - n_vwords:]

    for j, i in enumerate(fixed):
        c = cols[i]
        vbit = (vwords[j // 32] >> jnp.uint32(j % 32)) & jnp.uint32(1)
        validity = row_valid & vbit.astype(jnp.bool_)
        data = g[lane_slot[(i, "data")]]
        data = jnp.where(validity, data, jnp.zeros_like(data))
        data2 = None
        if c.data2 is not None:
            data2 = g[lane_slot[(i, "data2")]]
            data2 = jnp.where(validity, data2, jnp.zeros_like(data2))
        out[i] = DeviceColumn(c.dtype, data, validity, None, c.dictionary,
                              c.dict_size, c.dict_max_len, data2)
    return out  # type: ignore[return-value]


@jax.named_scope("gather")
def gather_batch(
    batch: ColumnarBatch,
    indices: jax.Array,
    num_rows: jax.Array,
    out_byte_capacity: Optional[int] = None,
) -> ColumnarBatch:
    """Gather a whole batch into a new batch of capacity len(indices)."""
    out_cap = indices.shape[0]
    row_valid = jnp.arange(out_cap, dtype=jnp.int32) < num_rows
    caps = [out_byte_capacity] * len(batch.columns)
    cols = gather_columns(batch.columns, indices, row_valid, caps)
    return ColumnarBatch(cols, num_rows.astype(jnp.int32))


# ---------------------------------------------------------------------------
# Sortable keys (order-preserving uint64 encodings)
# ---------------------------------------------------------------------------

_SIGN64 = np.uint64(1) << np.uint64(63)


def _u64_from_words(x: jax.Array) -> jax.Array:
    """Assemble uint64 from a 64-bit-typed array via two u32 words.

    Written for a platform that no longer exists, which could not rewrite
    64-bit bitcast_convert HLOs; N-bit -> 32-bit-word bitcasts plus shifts
    lower everywhere. Whether the v5e compiler accepts the direct 64-bit
    bitcast is not measured on the current machine; this form compiles for
    it (tests/test_tpu_compile.py), and chip_smoke.py holds what it
    computes on the chip to the CPU engine's answers."""
    w = jax.lax.bitcast_convert_type(x, jnp.uint32)  # (..., 2), [lo, hi]
    return (w[..., 1].astype(jnp.uint64) << jnp.uint64(32)) | w[..., 0].astype(
        jnp.uint64)


def _float_canonical(data: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(canonical value, is_nan): all NaNs collapse to 0.0 + flag, -0.0 ->
    +0.0. Spark float ordering/equality treats all NaNs as one value greater
    than everything and -0.0 == 0.0.

    Every float kernel works on canonical VALUES (+ a NaN flag), never on
    IEEE bit encodings: the TPU has no native float64, so f64 bit patterns
    and range are the compiler's emulation, not IEEE's. How the v5e
    compiler represents float64 (and where it saturates) is not measured on
    the current machine; chip_smoke.py holds float64 sums and averages over
    6M rows to 1e-6 relative against the CPU engine."""
    d = data.astype(jnp.float64)
    is_nan = jnp.isnan(d)
    d = jnp.where(is_nan, jnp.float64(0.0), d)
    d = jnp.where(d == 0.0, jnp.float64(0.0), d)  # -0.0 -> +0.0
    return d, is_nan


def _float_hash_key(data: jax.Array) -> jax.Array:
    """Deterministic uint64 hash key for a float column: the two float32
    words of the device double-double (exact: hi = round-to-f32, lo =
    residual), bitcast through the supported 32-bit path. Equal canonical
    values always produce equal keys; hash collisions are resolved by the
    exact verification pass."""
    d, is_nan = _float_canonical(data)
    hi = d.astype(jnp.float32)
    lo = (d - hi.astype(jnp.float64)).astype(jnp.float32)
    uhi = jax.lax.bitcast_convert_type(hi, jnp.uint32).astype(jnp.uint64)
    ulo = jax.lax.bitcast_convert_type(lo, jnp.uint32).astype(jnp.uint64)
    u = (uhi << jnp.uint64(32)) | ulo
    return jnp.where(is_nan, jnp.uint64(0x7FF8DEAD7F4A7C15), u)


def _int_sortable(data: jax.Array) -> jax.Array:
    x = data.astype(jnp.int64)
    return _u64_from_words(x) ^ jnp.uint64(_SIGN64)


def string_full_keys(col: DeviceColumn, words: int) -> List[jax.Array]:
    """``words`` uint64 keys from the first ``8 * words`` bytes, big-endian so
    integer order == byte-lexicographic order, most-significant word first.
    Shorter rows zero-pad, so a proper prefix sorts before its extensions.
    ``words`` is static: callers size it from the observed max row length
    (bucketed to a power of two) so the jit key carries the key width."""
    lens = col.offsets[1:] - col.offsets[:-1]
    nbytes = col.data.shape[0]
    keys = []
    for word in range(words):
        acc = jnp.zeros(col.capacity, jnp.uint64)
        for b in range(8):
            k = word * 8 + b
            pos = jnp.clip(col.offsets[:-1] + k, 0, max(nbytes - 1, 0))
            byte = jnp.where(
                (k < lens) & (nbytes > 0),
                col.data[pos] if nbytes > 0 else jnp.zeros(col.capacity, jnp.uint8),
                jnp.uint8(0),
            ).astype(jnp.uint64)
            acc = (acc << jnp.uint64(8)) | byte
        keys.append(acc)
    return keys


def string_prefix_keys(col: DeviceColumn) -> List[jax.Array]:
    """Two uint64 keys from the first 16 bytes (see ``string_full_keys``).
    Exact for strings that differ in the first 16 bytes; longer shared
    prefixes tie. Sorts widen past this via SortSpec.str_words
    (exec/sort.py measures the max row length per batch); grouping/joins
    use exact hashes + byte verification instead."""
    return string_full_keys(col, 2)


def sortable_keys(
    col: DeviceColumn, ascending: bool = True,
    nulls_first: Optional[bool] = None, str_words: int = 2
) -> List[jax.Array]:
    """Per-column lexsort keys, least-significant first within the column.

    Key stacks by type (null ordering FOLDS into a data word wherever the
    word has spare values, minimizing sort passes): dict/bool -> [folded
    key]; float -> [value, exception_word] (null/NaN ordering in the
    exception word); 32-bit ints -> [u32_key, null_key]; 64-bit ints /
    decimals / strings -> [lo, hi, null_key]. Spark default null ordering:
    NULLS FIRST for ascending, NULLS LAST for descending."""
    if nulls_first is None:
        nulls_first = ascending
    dt = col.dtype
    if col.is_dict:
        # sorted dictionary: int32 code order IS byte-lexicographic order.
        # Codes are a small non-negative range, so null ordering folds into
        # the SAME word (INT32_MIN/MAX are unreachable as +-codes) — one
        # sort pass per dict key, no separate null key.
        k = col.data.astype(jnp.int32)
        if not ascending:
            k = -k
        null_v = jnp.int32(-2**31) if nulls_first else jnp.int32(2**31 - 1)
        return [jnp.where(col.validity, k, null_v)]
    if dt == T.BOOLEAN:
        k = col.data.astype(jnp.int32)
        if not ascending:
            k = 1 - k
        null_v = jnp.int32(-1) if nulls_first else jnp.int32(2)
        return [jnp.where(col.validity, k, null_v)]
    if dt in T.FRACTIONAL_TYPES:
        # float order rides the VALUE itself — no f64 bit encoding exists on
        # the real-TPU backend (f64 there is a f32 double-double). The
        # "exception" orderings (NaN greater than all non-null; null per
        # spec) fold into ONE more-significant word: null < normal < NaN
        # for asc/nulls-first, flipped as the spec requires.
        d, is_nan = _float_canonical(col.data)
        ex = jnp.where(is_nan, jnp.int32(2), jnp.int32(1))
        if not ascending:
            d = -d
            ex = 3 - ex  # nan below normals when descending
        ex = jnp.where(col.validity, ex,
                       jnp.int32(0) if nulls_first else jnp.int32(3))
        d = jnp.where(col.validity & ~is_nan, d, jnp.zeros_like(d))
        return [d, ex]
    if dt in (T.STRING, T.BINARY):
        # str_words static words of big-endian bytes (most significant
        # first); emit least-significant first for the lexsort contract.
        # str_words=2 is the legacy 16-byte prefix; exec/sort.py widens it
        # to cover the longest row so string ORDER BY is full-width exact.
        pk = string_full_keys(col, max(int(str_words), 1))
        data_keys = list(reversed(pk))
        if not ascending:
            data_keys = [~k for k in data_keys]
    elif col.is_wide_decimal:
        from spark_rapids_tpu.exec import int128 as I128

        kh, kl = I128.sortable_keys(col.data2, col.data)
        data_keys = [kl, kh]  # least-significant first
        if not ascending:
            data_keys = [~k for k in data_keys]
    elif dt in (T.INT, T.DATE, T.SHORT, T.BYTE):
        # 32-bit-storable ints sort on ONE u32 word (not a u64 pair)
        k32 = jax.lax.bitcast_convert_type(
            col.data.astype(jnp.int32), jnp.uint32) ^ jnp.uint32(1 << 31)
        data_keys = [~k32 if not ascending else k32]
    else:
        k = _int_sortable(col.data)
        data_keys = [~k if not ascending else k]
    # neutralize data keys for nulls so ties are broken deterministically
    data_keys = [jnp.where(col.validity, k, jnp.zeros_like(k))
                 for k in data_keys]
    null_key = jnp.where(col.validity, jnp.int32(1), jnp.int32(0))
    if not nulls_first:
        null_key = 1 - null_key
    return data_keys + [null_key]


# Max key operands for the single variadic sort. Compile time grows
# superlinearly with operand count (~12s/28s/64s/128s for 2/3/5/7) but is
# one-time per (shape, operand set) under the persistent compile cache,
# while RUNTIME is one fused pass (~0.17s at 16M for 3 operands on v5e) vs
# ~0.4-0.6s per chained pass (gather + sort). Above the cap the chained
# fallback bounds compile cost at O(n) fixed-size compiles.
# (spark.rapids.tpu.sql.sort.variadicMaxOperands is the live value.)
def _lexsort_variadic_max() -> int:
    from spark_rapids_tpu.config import conf as _C
    return _C.LEXSORT_VARIADIC_MAX.get(_C.get_active())


def _sort_words(keys: Sequence[jax.Array]) -> List[jax.Array]:
    """``keys`` (least-significant first) as native sort words, in the same
    order: 64-bit integer keys are word-pair-emulated on the VPU (~18x the
    cost of native u32), so each splits into (lo32, hi32), which give the
    same total order under a stable LSD composition."""
    flat: List[jax.Array] = []
    for k in keys:
        if k.dtype == jnp.int64:
            k = k.astype(jnp.uint64) ^ jnp.uint64(_SIGN64)
        if k.dtype == jnp.uint64:
            flat.append((k & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32))
            flat.append((k >> jnp.uint64(32)).astype(jnp.uint32))
        else:
            flat.append(k)
    return flat


@jax.named_scope("sort.lexsort")
def lexsort_chain(keys: Sequence[jax.Array]) -> jax.Array:
    """Stable lexicographic argsort. Semantics match ``jnp.lexsort(keys)``
    (last key primary).

    Primary path: ONE variadic ``lax.sort`` over all key words carrying the
    row-id permutation as a payload operand — no per-pass gathers at all.
    Fallback (many keys): LSD chain of single-key stable sorts, each
    carrying the permutation as payload (stability preserves prior order
    within ties).
    """
    assert keys, "lexsort_chain needs at least one key"
    flat = _sort_words(keys)
    n = flat[0].shape[0]
    row_ids = jnp.arange(n, dtype=jnp.int32)
    if len(flat) <= _lexsort_variadic_max():
        operands = tuple(reversed(flat)) + (row_ids,)
        out = jax.lax.sort(operands, num_keys=len(flat), is_stable=True)
        return out[-1]
    return lsd_order(flat)


def lsd_order(words: Sequence[jax.Array]) -> jax.Array:
    """Stable argsort by ``words`` (least-significant first) as a chain of
    single-key stable sorts, each carrying the permutation as its payload:
    one gather a pass, and ONE sort signature (key, int32 payload) however
    many words there are.

    The TPU compiler's bill for a sort is per distinct signature (operand
    count, types, length), grows steeply with the operand count and hardly
    with the length (sandbox compile for a described v5e, PR 35: one u32
    key and a payload 18 s at 2^17 rows and 24 s at 2^20; three u32 keys
    and a payload 88 s, 108 s, and 132 s at 2^23; two u64 keys and a
    payload, the join build's former ``jnp.lexsort``, 214 s at 2^21; four
    identical sorts in one program cost what one does). So whatever sorts
    at a batch's capacity inside a fused stage or a join build goes
    through here."""
    perm = jnp.arange(words[0].shape[0], dtype=jnp.int32)
    for i, k in enumerate(words):
        kg = k if i == 0 else k[perm]
        _, perm = jax.lax.sort((kg, perm), num_keys=1, is_stable=True)
    return perm


class SortSpec(NamedTuple):
    column: int
    ascending: bool = True
    nulls_first: Optional[bool] = None
    # static string key width in uint64 words (8 bytes each). 2 = the legacy
    # 16-byte prefix; exec/sort.py buckets the observed max row length to a
    # power of two so long string keys order full-width. Part of the jit key
    # (specs are static), so two widths never share a compiled sort.
    str_words: int = 2


@jax.named_scope("sort")
def sort_indices(
    batch: ColumnarBatch, specs: Sequence[SortSpec], path: str = "lex"
) -> jax.Array:
    """Stable lexicographic argsort of the live rows; padding rows sort last.

    Replaces cudf ``Table.orderBy`` (reference GpuSortExec.scala:144 /
    SortUtils.scala) with a single fused lexsort on bit-encoded keys.

    ``path="radix"`` sorts on the packed key-normalized words instead
    (``packed_sort_keys``): the same total order in fewer sort operands.
    Both paths are stable over identical preorders, so their outputs are
    bit-identical — the dispatch (exec/sort.py + plan/autotune.py) may
    pick either freely. Falls back to lexsort when a key column is
    radix-ineligible."""
    active = batch.active_mask()
    if path == "radix":
        packed = packed_sort_keys(batch, specs)
        if packed is not None:
            return lexsort_chain(packed).astype(jnp.int32)
    keys = _spec_keys(batch, specs)
    keys.append(jnp.where(active, jnp.uint32(0), jnp.uint32(1)))  # padding last
    return lexsort_chain(keys).astype(jnp.int32)


def _spec_keys(batch: ColumnarBatch, specs: Sequence[SortSpec]
               ) -> List[jax.Array]:
    """The lexsort keys of ``specs``: LAST key is primary, so the
    least-significant spec comes first."""
    keys: List[jax.Array] = []
    for spec in reversed(list(specs)):
        keys.extend(sortable_keys(batch.columns[spec.column], spec.ascending,
                                  spec.nulls_first,
                                  getattr(spec, "str_words", 2)))
    return keys


def topn_select_max_k(capacity: int) -> int:
    """The largest k for which ``topn_indices`` is taken over a full sort
    of ``capacity`` rows: a selection round reads every key word once or
    twice, as one compare-exchange stage of a sorting network over the same
    words does, and a network of 2^c rows has c(c+1)/2 stages; past that
    many rounds the sort moves less (55 at 2^10 rows, 153 at 2^17, 210 at
    2^20)."""
    c = max(int(capacity) - 1, 1).bit_length()
    return c * (c + 1) // 2


@jax.named_scope("sort.topn")
def topn_indices(batch: ColumnarBatch, specs: Sequence[SortSpec],
                 k: jax.Array, out_cap: int) -> Tuple[jax.Array, jax.Array]:
    """The first ``k`` entries of ``sort_indices(batch, specs)`` without the
    sort: ``k`` rounds of selection. A round narrows the rows not yet taken
    to those that hold the least value of the most significant key word,
    then of the next word among those, and so on; of the rows equal on
    every word it takes the one with the lowest index, which is where a
    stable sort puts it. Exact on ties, nulls and NaNs because the words
    are the sort's own (``sortable_keys``). Elementwise passes and
    reductions only, under one ``while`` loop: the program is the same for
    every ``k`` (traced; at most ``out_cap``) and its compile cost does not
    grow with the batch, as a variadic sort's does (PERF.md). Returns
    (indices padded to ``out_cap``, rows taken = min(k, live rows))."""
    cap = batch.capacity
    live = batch.active_mask()
    words = _sort_words(_spec_keys(batch, specs))[::-1]  # primary first
    rows = jnp.arange(cap, dtype=jnp.int32)

    def ceiling(w):
        if jnp.issubdtype(w.dtype, jnp.floating):
            return jnp.array(jnp.inf, w.dtype)
        return jnp.array(jnp.iinfo(w.dtype).max, w.dtype)

    def pick(i, state):
        taken, out = state
        cand = live & ~taken
        for w in words:
            least = jnp.min(jnp.where(cand, w, ceiling(w)))
            cand = cand & (w == least)
        j = jnp.min(jnp.where(cand, rows, jnp.int32(cap)))  # cap: none left
        return taken | (rows == j), out.at[i].set(
            jnp.where(j < cap, j, 0), mode="drop")

    n = jnp.minimum(k.astype(jnp.int32), batch.num_rows.astype(jnp.int32))
    _, out = jax.lax.fori_loop(
        0, n, pick, (jnp.zeros(cap, jnp.bool_),
                     jnp.zeros(out_cap, jnp.int32)))
    return out, n


def str_key_words(batch: ColumnarBatch, specs: Sequence[SortSpec],
                  max_words: int = 16) -> Tuple[SortSpec, ...]:
    """Widen each plain-string sort spec to cover its column's longest row.

    HOST-side helper (syncs one scalar per plain-string key column): rounds
    ceil(max_len / 8) up to a power of two so compile count stays bounded,
    capped at ``max_words`` (rows longer than 8 * max_words bytes tie past
    that width — the documented residual ORDER BY truncation). Dict-encoded
    strings already order full-width through their sorted dictionary."""
    out = []
    for spec in specs:
        c = batch.columns[spec.column]
        w = 2
        if c.offsets is not None and not c.is_dict and c.data.shape[0] > 0:
            ml = int(host_get(jnp.max(c.offsets[1:] - c.offsets[:-1]),
                              "sort.str_key_words"))
            need = (ml + 7) // 8
            while w < need:
                w *= 2
            w = min(w, max_words)
        out.append(spec._replace(str_words=w))
    return tuple(out)


# ---------------------------------------------------------------------------
# Hashing (splitmix64 mixing; polynomial rolling hash for strings)
# ---------------------------------------------------------------------------


def _splitmix64(x: jax.Array) -> jax.Array:
    x = x + jnp.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> jnp.uint64(31))


# per-variant constants: variant 1 is an INDEPENDENT second hash of the raw
# bytes (not derived from variant 0), so the pair behaves as a 128-bit id
_STR_P = (0x100000001B3, 0x9E3779B97F4A7C15)  # FNV prime / odd golden ratio
_LEN_MIX = (0x9E3779B97F4A7C15, 0xC2B2AE3D27D4EB4F)
_INT_SALT = (0, 0xA5A5A5A5A5A5A5A5)
_COMBINE_MULT = (31, 0x100000001B3)


def _string_hash(col: DeviceColumn, variant: int = 0) -> jax.Array:
    """Order-dependent polynomial hash of each row's bytes (mod 2^64).

    hash(row) = sum_k byte[k] * P^(len-1-rel_k); computed as a segment sum of
    byte * P^(-rel) * P^(len-1) using modular inverse powers — instead we use
    forward powers with a per-row normalization: sum byte*P^rel, then no
    normalization needed since rows are compared whole (same rel ordering)."""
    nbytes = col.data.shape[0]
    cap = col.capacity
    if nbytes == 0:
        return jnp.zeros(cap, jnp.uint64)
    rows = _string_row_ids(col.offsets, nbytes)
    rows_c = jnp.clip(rows, 0, cap - 1)
    rel = jnp.arange(nbytes, dtype=jnp.int32) - col.offsets[rows_c]
    powers = _pow_table(_STR_P[variant], nbytes)
    contrib = (col.data.astype(jnp.uint64) + jnp.uint64(1)) * powers[
        jnp.clip(rel, 0, nbytes - 1)
    ]
    in_range = jnp.arange(nbytes, dtype=jnp.int32) < col.offsets[-1]
    contrib = jnp.where(in_range, contrib, jnp.uint64(0))
    h = jax.ops.segment_sum(contrib, rows_c, num_segments=cap,
                            indices_are_sorted=True)
    lens = (col.offsets[1:] - col.offsets[:-1]).astype(jnp.uint64)
    return _splitmix64(h ^ (lens * jnp.uint64(_LEN_MIX[variant])))


def _pow_table(p: int, n: int) -> jax.Array:
    """powers[k] = p^k mod 2^64, by log-depth doubling (n is static).

    Computed host-side: expressed in jnp the doubling chain is a pure
    constant, and XLA's single-threaded constant folder spends seconds
    evaluating the multi-million-element multiplies at every compile.
    Only the numpy table is cached — the jnp handle would be a staged
    tracer inside a jit trace and must not outlive it."""
    return jnp.asarray(_pow_table_np(p, n))


@lru_cache(maxsize=32)
def _pow_table_np(p: int, n: int) -> np.ndarray:
    vals = np.ones(1, np.uint64)
    stride = p & 0xFFFFFFFFFFFFFFFF
    while vals.shape[0] < n:
        vals = np.concatenate([vals, vals * np.uint64(stride)])
        stride = (stride * stride) & 0xFFFFFFFFFFFFFFFF
    return vals[:n]


def hash_keys(batch: ColumnarBatch, key_cols: Sequence[int],
              variant: int = 0) -> jax.Array:
    """64-bit combined hash of the key columns per row. Used for grouping and
    join candidate generation; exactness comes from the verification pass
    (`keys_equal`), not from this hash. ``variant=1`` computes an independent
    second hash of the same raw bytes (grouping sorts by the pair as a
    128-bit key)."""
    salt = jnp.uint64(_INT_SALT[variant])
    h = jnp.zeros(batch.capacity, jnp.uint64)
    for i in key_cols:
        col = batch.columns[i]
        if col.is_dict:
            # hash the dictionary entries (tiny byte pass), gather by code:
            # identical VALUE hash as the plain string path, so partitioning,
            # bloom filters and join candidates agree across encodings
            dh = _string_hash(col.dictionary, variant)
            ch = dh[jnp.clip(col.data, 0, col.dictionary.capacity - 1)]
        elif col.offsets is not None:
            ch = _string_hash(col, variant)
        elif col.dtype in T.FRACTIONAL_TYPES:
            # hash the canonical value words so NaN==NaN, -0.0==0.0
            ch = _splitmix64(_float_hash_key(col.data) ^ salt)
        else:
            ch = _splitmix64(_int_sortable(col.data) ^ salt)
        ch = jnp.where(col.validity, ch, jnp.uint64(0xDEADBEEFCAFEBABE))
        h = _splitmix64(h * jnp.uint64(_COMBINE_MULT[variant]) + ch)
    return h


def keys_equal(
    a: ColumnarBatch, a_idx: jax.Array, a_cols: Sequence[int],
    b: ColumnarBatch, b_idx: jax.Array, b_cols: Sequence[int],
) -> jax.Array:
    """Exact null-safe equality of key tuples at gathered positions.

    SQL equi-join semantics: NULL keys never match (callers pre-filter null
    keys); here nulls compare equal only if both null (callers decide)."""
    eq = jnp.ones(a_idx.shape[0], jnp.bool_)
    for ai, bi in zip(a_cols, b_cols):
        ca, cb = a.columns[ai], b.columns[bi]
        va = ca.validity[a_idx]
        vb = cb.validity[b_idx]
        if ca.is_dict and cb.is_dict and ca.dictionary is cb.dictionary:
            # shared dictionary: codes compare exactly
            ceq = ca.data[a_idx] == cb.data[b_idx]
        elif ca.is_wide_decimal or cb.is_wide_decimal:
            def limbs(c, idx):
                lo = c.data.astype(jnp.int64)[idx]
                if c.data2 is not None:
                    return c.data2[idx], lo
                return jnp.where(lo < 0, jnp.int64(-1), jnp.int64(0)), lo
            ha, la = limbs(ca, a_idx)
            hb, lb = limbs(cb, b_idx)
            ceq = (ha == hb) & (la == lb)
        elif (ca.offsets is not None or ca.is_dict
              or cb.offsets is not None or cb.is_dict):
            ceq = _string_eq_at(ca, a_idx, cb, b_idx)
        elif ca.dtype in T.FRACTIONAL_TYPES:
            da, na = _float_canonical(ca.data)
            db, nb = _float_canonical(cb.data)
            ceq = ((da[a_idx] == db[b_idx]) & ~na[a_idx] & ~nb[b_idx]) | (
                na[a_idx] & nb[b_idx])
        else:
            da = ca.data[a_idx]
            db = cb.data[b_idx]
            ceq = da.astype(jnp.int64) == db.astype(jnp.int64)
        eq = eq & ((ceq & va & vb) | (~va & ~vb))
    return eq


def _string_sig_at(c: DeviceColumn, idx: jax.Array):
    """(hash, length, prefix_hi, prefix_lo) of string rows at ``idx``.

    Dict-aware: for dict-encoded columns the signatures are computed over the
    tiny dictionary and gathered by code, giving the identical values the
    plain layout produces — so mixed-encoding comparisons are consistent."""
    if c.is_dict:
        codes = jnp.clip(c.data, 0, c.dictionary.capacity - 1)[idx]
        d = c.dictionary
        h = _string_hash(d)[codes]
        lens = (d.offsets[1:] - d.offsets[:-1])[codes]
        pk = string_prefix_keys(d)
        return h, lens, pk[0][codes], pk[1][codes]
    h = _string_hash(c)[idx]
    lens = (c.offsets[1:] - c.offsets[:-1])[idx]
    pk = string_prefix_keys(c)
    return h, lens, pk[0][idx], pk[1][idx]


def _string_rows_at(c: DeviceColumn, idx: jax.Array):
    """(byte buffer, row start, row length) for string rows at ``idx``,
    dict-aware (dict rows resolve into the dictionary's byte space)."""
    if c.is_dict:
        d = c.dictionary
        codes = jnp.clip(c.data, 0, d.capacity - 1)[idx]
        return d.data, d.offsets[:-1][codes], (d.offsets[1:]
                                               - d.offsets[:-1])[codes]
    return c.data, c.offsets[:-1][idx], (c.offsets[1:] - c.offsets[:-1])[idx]


def _bytes_word_at(data: jax.Array, start: jax.Array, lens: jax.Array,
                   off: jax.Array) -> jax.Array:
    """uint64 of bytes [off, off+8) of each row (zero past the row length)."""
    nbytes = data.shape[0]
    acc = jnp.zeros(start.shape[0], jnp.uint64)
    for b in range(8):
        k = off + b
        pos = jnp.clip(start + k, 0, max(nbytes - 1, 0))
        byte = jnp.where(
            (k < lens) & (nbytes > 0),
            data[pos] if nbytes > 0 else jnp.zeros(start.shape[0], jnp.uint8),
            jnp.uint8(0)).astype(jnp.uint64)
        acc = (acc << jnp.uint64(8)) | byte
    return acc


def _string_eq_at(
    ca: DeviceColumn, a_idx: jax.Array, cb: DeviceColumn, b_idx: jax.Array
) -> jax.Array:
    """Exact full-width string equality at row pairs.

    Fast screen first — 64-bit polynomial hash, length, and both 16-byte
    prefix words must agree — then a byte-payload verification walks the
    remaining payload in 8-byte windows (``lax.while_loop``: the trip count
    is the longest surviving candidate, so short keys pay nothing). Equality
    therefore never depends on hash quality; a collision only costs the
    discarded verification pass."""
    ha, la, pa0, pa1 = _string_sig_at(ca, a_idx)
    hb, lb, pb0, pb1 = _string_sig_at(cb, b_idx)
    eq = (ha == hb) & (la == lb) & (pa0 == pb0) & (pa1 == pb1)
    da, sa, lla = _string_rows_at(ca, a_idx)
    db, sb, llb = _string_rows_at(cb, b_idx)

    def cond(st):
        off, e = st
        return jnp.any(e & (lla > off))

    def body(st):
        off, e = st
        wa = _bytes_word_at(da, sa, lla, off)
        wb = _bytes_word_at(db, sb, llb, off)
        return off + 8, e & (wa == wb)

    # lengths already agreed (la == lb folded into eq); bytes past the row
    # length read as 0 on both sides, so whole-word compares are safe
    _, eq = jax.lax.while_loop(cond, body, (jnp.int32(16), eq))
    return eq


# ---------------------------------------------------------------------------
# Filter compaction
# ---------------------------------------------------------------------------


def filter_indices(keep: jax.Array, active: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Order-preserving compaction map: indices of kept rows moved to front.

    Returns (indices, n_kept). O(n) cumsum + scatter — the XLA-friendly
    equivalent of cudf's stream compaction (Table.filter in the reference's
    GpuFilterExec). Slots past n_kept point at row 0; callers mask them with
    the returned count (gather_batch row_valid)."""
    k = keep & active
    cap = k.shape[0]
    dst = jnp.cumsum(k.astype(jnp.int32)) - 1
    out = jnp.zeros(cap, jnp.int32)
    out = out.at[jnp.where(k, dst, cap)].set(
        jnp.arange(cap, dtype=jnp.int32), mode="drop"
    )
    return out, jnp.sum(k).astype(jnp.int32)


@jax.named_scope("filter.compact")
def compact_indices(keep: jax.Array, out_cap: int
                    ) -> Tuple[jax.Array, jax.Array]:
    """``filter_indices`` by ONE stable sort of (dropped?, row id) instead
    of a cumsum and a scatter, cut to the first ``out_cap`` slots: (indices,
    rows kept). On the v5e a sort of a u32 key with an int32 payload over
    2^20 rows takes 1.3 ms and a scatter of as many int32 4.9 ms (my chip
    run, PR 35; PERF.md); the sort's signature is ``lsd_order``'s, so a
    program that groups pays nothing more to compile it. ``keep`` already
    holds what is live. Slots past the count hold dropped rows' ids: valid
    indices, masked by the count as ``filter_indices``' are. A count above
    ``out_cap`` means rows were cut: the caller's to flag."""
    perm = lsd_order([jnp.where(keep, jnp.uint32(0), jnp.uint32(1))])
    return fit_indices(perm, out_cap), jnp.sum(keep).astype(jnp.int32)


def fit_indices(idx: jax.Array, out_cap: int) -> jax.Array:
    """An index vector cut, or padded with row 0, to ``out_cap`` slots."""
    if idx.shape[0] >= out_cap:
        return idx[:out_cap]
    return jnp.concatenate([idx, jnp.zeros(out_cap - idx.shape[0], idx.dtype)])


# ---------------------------------------------------------------------------
# Group-by: sort-based segmented aggregation
# ---------------------------------------------------------------------------


class GroupInfo(NamedTuple):
    """Result of grouping rows: a permutation placing rows in group order,
    per-row segment ids (in permuted order), and the group count."""

    perm: jax.Array  # (cap,) int32 — gather map into the input
    segment_ids: jax.Array  # (cap,) int32 — group id per permuted row
    num_groups: jax.Array  # int32 scalar
    group_starts: jax.Array  # (cap,) int32 — permuted index of each group head


@jax.named_scope("agg.group")
def group_rows(batch: ColumnarBatch, key_cols: Sequence[int],
               active: Optional[jax.Array] = None) -> GroupInfo:
    """Cluster live rows by key equality.

    TPU-first replacement for cudf hash-groupby: sort by hash then split
    segments wherever the *exact* keys differ between neighbors — so hash
    collisions create adjacent-but-separate groups, never merged ones.

    Sort-key budget: TPU XLA sort compile time grows superlinearly with the
    operand count (measured ~23s/64s/128s for 2/4/6 u64 operands at 2^19 on
    v5e), so clustering NEVER sorts by per-key prefix operands.

    Exactness bar: non-string keys get exact neighbor verification
    (keys_equal), so a 64-bit hash collision only ever SPLITS a group.
    String keys group on an independent 128-bit hash pair with NO byte
    verification — two distinct keys colliding on both words (p ~ 2^-86
    over 2^21 rows) WOULD merge; this is the same treat-as-exact bar as
    _string_eq_at and the documented engine-wide string-equality contract.
    """
    cap = batch.capacity
    if active is None:
        active = batch.active_mask()
    if any(batch.columns[i].offsets is not None for i in key_cols):
        # plain string keys: cluster on an independent 128-bit hash pair —
        # through the open-addressing table when enabled (one int32 slot
        # sort), else by lexsort + a cheap neighbor check (length + 16-byte
        # prefix) that can only SPLIT a double-collided group. Either way
        # the bar is the documented engine-wide 128-bit treat-as-exact
        # string-equality contract.
        h1 = hash_keys(batch, key_cols)
        h2 = hash_keys(batch, key_cols, variant=1)
        if _agg_hashtbl_enabled():
            return group_rows_table(h1, h2, active)
        keys = [h2, h1, jnp.where(active, jnp.uint32(0), jnp.uint32(1))]
        perm = lexsort_chain(keys).astype(jnp.int32)
        neq = _neighbor_key_neq(batch, key_cols, perm, extra=(h1, h2))
        return _group_from_boundaries(perm, neq, active, cap)
    perm = _hash_order(hash_keys(batch, key_cols), active)
    neq = _neighbor_key_neq(batch, key_cols, perm)
    return _group_from_boundaries(perm, neq, active, cap)


def _hash_order(h: jax.Array, active: jax.Array) -> jax.Array:
    """Rows in the order of 63 bits of their hash, inactive rows last: two
    passes of ``lsd_order``, low word then high word (the padding flag
    rides in the high word's top bit). The variadic (hash lo, hash hi,
    flag, row) sort it replaces made every program that groups pay the
    four-operand price once per capacity it groups at."""
    lo = (h & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = jnp.where(active, (h >> jnp.uint64(33)).astype(jnp.uint32),
                   jnp.uint32(0xFFFFFFFF))
    return lsd_order([lo, hi])




def _neighbor_key_neq(batch: ColumnarBatch, key_cols: Sequence[int],
                      perm: jax.Array, extra: Sequence[jax.Array] = ()
                      ) -> jax.Array:
    """Per-position "differs from previous row" over key columns in permuted
    order, with keys_equal semantics (null==null, Spark float canonical
    equality) — but ONE fused gather instead of 4 per key column: every
    comparable signature lane is computed elementwise first, gathered by
    ``perm`` in one packed take, then compared against its shift-by-one."""
    lanes: List[jax.Array] = list(extra)
    for i in key_cols:
        c = batch.columns[i]
        lanes.append(c.validity)
        # every data-derived lane is masked by validity: null keys must
        # compare equal regardless of residual data under the null (some
        # producers, e.g. projected expressions, do not zero it)
        v = c.validity

        def m(lane, v=v):
            return jnp.where(v, lane, jnp.zeros_like(lane))

        if c.offsets is not None:
            lanes.append(m(c.offsets[1:] - c.offsets[:-1]))
            lanes.extend(m(w) for w in string_prefix_keys(c))
        elif c.is_wide_decimal:
            lanes.append(m(c.data))
            lanes.append(m(c.data2))
        elif c.dtype in T.FRACTIONAL_TYPES:
            d, is_nan = _float_canonical(c.data)
            lanes.append(m(d))
            lanes.append(m(is_nan))
        else:
            lanes.append(m(c.data))
    g = gather_lanes(lanes, perm)
    neq = jnp.zeros(perm.shape[0], jnp.bool_)
    for lane in g:
        prev = jnp.concatenate([lane[:1], lane[:-1]])
        neq = neq | (lane != prev)
    return neq


def _agg_hashtbl_enabled() -> bool:
    from spark_rapids_tpu.config import conf as _C
    return _C.AGG_HASHTBL_ENABLED.get(_C.get_active())


@jax.named_scope("agg.group")
def group_rows_prehashed(h1: jax.Array, h2: jax.Array,
                         active: jax.Array) -> GroupInfo:
    """Cluster rows whose 128-bit (h1, h2) hash pair matches. Used for
    string group keys and for merge passes that carry the pair as columns
    (hash-once aggregation: bytes are hashed exactly once per query).

    Round 12: routes through the open-addressing table
    (``group_rows_table`` — one stable int32 slot sort instead of the
    128-bit lexsort), with the sort-based clustering as both the conf-off
    path and the in-trace overflow fallback. Same treat-as-exact bar: rows
    group iff their 128-bit pair matches."""
    if _agg_hashtbl_enabled():
        return group_rows_table(h1, h2, active)
    return _group_rows_prehashed_sort(h1, h2, active)


def _group_from_boundaries(perm: jax.Array, neq: jax.Array,
                           active: jax.Array, cap: int) -> GroupInfo:
    idx = jnp.arange(cap, dtype=jnp.int32)
    perm_active = active[perm]
    boundary = perm_active & ((idx == 0) | neq)
    seg = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    seg = jnp.clip(seg, 0, cap - 1)
    num_groups = jnp.sum(boundary.astype(jnp.int32))
    # head position of each group (for gathering key values)
    group_starts = jax.ops.segment_min(
        jnp.where(boundary, idx, cap - 1), seg, num_segments=cap
    ).astype(jnp.int32)
    return GroupInfo(perm, seg, num_groups, group_starts)


def segment_ends(group_starts: jax.Array, num_groups: jax.Array,
                 cap: int) -> jax.Array:
    """Per-segment last-row index (permuted order) for SORTED segment ids.

    Derived from GroupInfo.group_starts: segment s ends where s+1 starts;
    the last real segment absorbs the trailing padding rows (they carry
    identity values), so it ends at cap-1."""
    nxt = jnp.concatenate([group_starts[1:],
                           jnp.full((1,), cap, group_starts.dtype)])
    sidx = jnp.arange(cap, dtype=jnp.int32)
    ends = jnp.where(sidx >= num_groups - 1, cap - 1, nxt - 1)
    return jnp.clip(ends, 0, cap - 1)


def _sorted_segment_reducers(seg: jax.Array, starts: jax.Array,
                             ends: jax.Array):
    """(sum, min, max) reducers over SORTED segment ids. Runs at HBM
    bandwidth where TPU scatters (jax.ops.segment_*) serialize.

    integer sum/count: one native cumsum + boundary gathers (seg total =
    cs[end] - cs[start] + v[start]) — exact (int adds commute with the
    subtraction, wraparound included).
    float sum: scatter segment_sum — the cumsum trick is NOT float-safe:
    small groups downstream of a large-magnitude group lose their values to
    prefix absorption (cs accumulates 1e17, later 0.456 adds vanish into
    its ulp), a cross-group contamination plain per-segment summation never
    has. The scatter is exact per segment.
    min/max: scatter-based jax.ops.segment_min/max. (An associative_scan
    formulation was measured at ~8s for 2^21 rows on the real chip — the
    unrolled log-depth scan HLO is pathological there — while the scatter
    runs in the same ~150-300ms band as every other memory pass.)"""
    n = seg.shape[0]
    starts_c = jnp.clip(starts, 0, n - 1)
    ends_c = jnp.clip(ends, 0, n - 1)

    def seg_sum(v: jax.Array) -> jax.Array:
        if jnp.issubdtype(v.dtype, jnp.floating):
            return jax.ops.segment_sum(v, seg, num_segments=n,
                                       indices_are_sorted=True)
        cs = jnp.cumsum(v)
        return cs[ends_c] - cs[starts_c] + v[starts_c]

    def seg_min(v: jax.Array) -> jax.Array:
        return jax.ops.segment_min(v, seg, num_segments=n,
                                   indices_are_sorted=True)

    def seg_max(v: jax.Array) -> jax.Array:
        return jax.ops.segment_max(v, seg, num_segments=n,
                                   indices_are_sorted=True)

    return (seg_sum, seg_min, seg_max)


@jax.named_scope("agg.reduce")
def segment_agg(
    values: jax.Array,
    validity: jax.Array,
    contributing: jax.Array,
    seg: jax.Array,
    num_segments: int,
    op: str,
    ends: Optional[jax.Array] = None,
    starts: Optional[jax.Array] = None,
):
    """One segmented aggregation. ``contributing`` masks rows that count.

    Returns (agg_values, agg_validity). op in sum/count/min/max/first/last/
    count_all/sum_sq (sum of squares, for variance).

    ``starts``/``ends`` (per-segment first/last row index; GroupInfo
    group_starts and ``segment_ends``) assert the ids are SORTED and switch
    the reducers from scatter-based ``jax.ops.segment_*`` to cumsum/scan +
    boundary gathers. TPU scatters serialize (~90ms per op at 2^20 on v5e)
    while cumsums run at bandwidth — the grouped-aggregation hot path
    always passes them."""
    live = contributing & validity
    if ends is not None:
        assert starts is not None
        seg_sum, seg_min, seg_max = _sorted_segment_reducers(
            seg, starts, ends)
        def any_valid_of(flags):
            return seg_sum(flags.astype(jnp.int32)) > 0
    else:
        def any_valid_of(flags):
            return jax.ops.segment_max(flags.astype(jnp.int32), seg,
                                       num_segments=num_segments) > 0
        def seg_sum(v):
            return jax.ops.segment_sum(v, seg, num_segments=num_segments)

        def seg_min(v):
            return jax.ops.segment_min(v, seg, num_segments=num_segments)

        def seg_max(v):
            return jax.ops.segment_max(v, seg, num_segments=num_segments)
    if op == "count_all":
        data = seg_sum(contributing.astype(jnp.int64))
        return data, jnp.ones_like(data, jnp.bool_)
    if op == "count":
        data = seg_sum(live.astype(jnp.int64))
        return data, jnp.ones_like(data, jnp.bool_)
    any_valid = any_valid_of(live)
    if op in ("sum", "sum_sq"):
        v = values.astype(
            jnp.float64 if jnp.issubdtype(values.dtype, jnp.floating) else jnp.int64
        )
        if op == "sum_sq":
            v = v * v
        v = jnp.where(live, v, jnp.zeros_like(v))
        return seg_sum(v), any_valid
    if op in ("min", "max"):
        if jnp.issubdtype(values.dtype, jnp.floating):
            # NaN-aware on VALUES (Spark: NaN greater than everything): clean
            # reduce with +/-inf identity, then splice NaN segments back in
            d, is_nan = _float_canonical(values)
            live_clean = live & ~is_nan
            ident = jnp.float64(-np.inf if op == "max" else np.inf)
            v = jnp.where(live_clean, d, ident)
            red = (seg_max if op == "max" else seg_min)(v)
            nan_any = any_valid_of(live & is_nan)
            clean_any = any_valid_of(live_clean)
            if op == "max":
                dec = jnp.where(nan_any, jnp.float64(np.nan), red)
            else:
                dec = jnp.where(clean_any, red, jnp.float64(np.nan))
            return dec.astype(values.dtype), any_valid
        ii = jnp.iinfo(values.dtype if values.dtype != jnp.bool_ else jnp.int8)
        if values.dtype == jnp.bool_:
            v = values.astype(jnp.int8)
        else:
            v = values
        ident = ii.min if op == "max" else ii.max
        v = jnp.where(live, v, jnp.full_like(v, ident))
        red = (seg_max if op == "max" else seg_min)(v)
        if values.dtype == jnp.bool_:
            red = red.astype(jnp.bool_)
        return red, any_valid
    if op in ("first", "last"):
        idx = jnp.arange(values.shape[0], dtype=jnp.int32)
        pick = jnp.where(live, idx, values.shape[0] if op == "first" else -1)
        sel = (seg_min if op == "first" else seg_max)(pick)
        sel_c = jnp.clip(sel, 0, values.shape[0] - 1)
        return values[sel_c], any_valid
    raise NotImplementedError(op)


# ---------------------------------------------------------------------------
# Dense-id aggregation (MXU path for small group-key domains)
# ---------------------------------------------------------------------------


@jax.named_scope("agg.reduce")
def dense_segment_sums(rows: jax.Array, ids: jax.Array, num_ids: int
                       ) -> jax.Array:
    """Sum each of R value rows per dense id: (R, n) f64 -> (R, num_ids) f64.

    Exact f64 sums (max rel err ~1e-14 vs numpy oracle). Masking (nulls,
    filters) is the caller's job: masked rows must carry 0 in ``rows`` (for
    sums) and their id may be anything in [0, num_ids).
    """
    n = ids.shape[0]
    nrows = rows.shape[0]
    ids = jnp.clip(ids, 0, num_ids - 1)

    assert num_ids <= 64, (
        "dense_segment_sums is for small id domains; larger group-key "
        "domains take the sort-based aggregation path")
    del nrows, n
    # per-group masked full reductions: XLA fuses all num_ids x nrows
    # reductions into one streaming pass over the rows (measured ~8ms
    # marginal for (11, 4M) -> (11, 16) in f64 — faster than ANY dot
    # formulation here: f64 dots lower to a multi-pass bf16 decomposition
    # with dozens of materialized (rows, n) intermediates, and f32 dots
    # cannot accumulate exactly enough)
    outs = []
    for g in range(num_ids):
        m = ids == g
        outs.append(jnp.sum(jnp.where(m[None, :], rows, 0.0), axis=1))
    return jnp.stack(outs, axis=1)


# One int8 contraction may span this many rows: a biased byte is at most 128
# in magnitude, so 2^23 rows keep every per-id sum inside int32.
_LIMB_BLOCK_ROWS = 1 << 23
_FLAGS_PER_WORD = 7  # bytes 0..6 of a flag word; byte 7 of word 0 counts rows


@jax.named_scope("agg.reduce")
def dense_segment_reduce(rows: Sequence[jax.Array],
                         flags: Sequence[jax.Array], ids: jax.Array,
                         num_ids: int, block_rows: int = _LIMB_BLOCK_ROWS):
    """Exact per-id sums of int64 rows and counts of bool flags by int8
    contractions against ONE one-hot id matrix (native int8 MXU path).

    ``rows``: R x (n,) int64; ``flags``: k x (n,) bool. Returns
    ``(hi, lo, counts, n_rows)``: the exact signed 128-bit sums as (R,
    num_ids) int64 limb pairs (``lo`` alone is the int64 sum mod 2^64, Java's
    long wrap), the (k, num_ids) flag counts and the (num_ids,) rows per id,
    both int64. A row whose id is outside [0, num_ids) is in no sum and no
    count: that is how callers mask rows.

    The limbs are the value's own bytes: a lane is bitcast to (n, 8) int8
    (little-endian) and contracted over n as it is, so the compiler fuses the
    bitcast into the contraction's operand and no limb matrix is ever stored.
    Bytes 0..6 are unsigned and are biased by ``^ 0x80`` into int8 (u - 128);
    byte 7 is the two's-complement sign byte and enters unchanged, which
    makes the recombined sum the true signed one. Flags ride seven to an
    int64 word, a lane like any other; byte 7 of the first word holds 1, so
    its sum is the rows per id, and 128 times that undoes the bias. int32
    accumulation holds for ``block_rows`` <= 2^23 rows (|limb| <= 128); a
    longer batch is contracted in blocks whose sums are added in int64.
    docs/fusion.md has the argument.
    """
    from spark_rapids_tpu.exec import int128 as I128

    assert block_rows <= _LIMB_BLOCK_ROWS
    n = ids.shape[0]
    R = len(rows)
    words = [jnp.full((n,), 1 << 56, jnp.int64)]
    for j, f in enumerate(flags):
        w, b = divmod(j, _FLAGS_PER_WORD)
        if w == len(words):
            words.append(jnp.zeros((n,), jnp.int64))
        words[w] = words[w] | (f.astype(jnp.int64) << (8 * b))
    nb = -(-n // block_rows)

    def blocks(a, fill):  # (n, ...) -> (nb, rows of a block, ...)
        if nb == 1:
            return a[None]
        pad = [(0, nb * block_rows - n)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, pad, constant_values=fill).reshape(
            (nb, block_rows) + a.shape[1:])

    oh = (blocks(ids, -1)[..., None]
          == jnp.arange(num_ids, dtype=jnp.int32)).astype(jnp.int8)
    bias = np.array([-128] * 7 + [0], np.int8)

    def byte_sums(x):  # (n,) int64 -> (8, num_ids) sums of its biased bytes
        limbs = jax.lax.bitcast_convert_type(x.astype(jnp.int64), jnp.int8)
        s = jax.lax.dot_general(
            blocks(limbs ^ bias, 0), oh, (((1,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.int32)
        return s.astype(jnp.int64).sum(axis=0)

    s = jnp.stack([byte_sums(x) for x in list(rows) + words])
    n_rows = s[R, 7]
    s = s.at[:, :7].add(128 * n_rows)  # undo the bias: true byte sums
    counts = s[R:, :7].reshape(-1, num_ids)[:len(flags)]
    hi = jnp.zeros((R, num_ids), jnp.int64)
    lo = jnp.zeros((R, num_ids), jnp.int64)
    for k in range(8):  # value = sum_k bytes[k] << 8k, in 128 bits
        b = s[:R, k]
        hi, lo = I128.add(hi, lo, b >> (64 - 8 * k) if k else b >> 63,
                          b << (8 * k))
    return hi, lo, counts, n_rows


@jax.named_scope("agg.reduce")
def sorted_segment_sum_int128(hi: jax.Array, lo: jax.Array, live: jax.Array,
                              starts: jax.Array, ends: jax.Array):
    """Exact 128-bit sums of (hi, lo) rows over SORTED segments, and the
    live rows of each: (hi, lo, count) at the segments' ids. ``starts`` and
    ``ends`` are each segment's first and last row (``GroupInfo``,
    ``segment_ends``); rows that do not contribute come in as zeros.

    A segment's total is the running sum at its end less the running sum
    before its start: four running sums (the low limb in 32-bit halves so
    no carry is lost: n < 2^31) and two packed gathers; three scatter-adds
    of int64 (93 ms each for 2^20 rows on the v5e) did it before."""
    n = hi.shape[0]
    lo_u = lo.astype(jnp.uint64)
    lanes = [(lo_u & jnp.uint64(0xFFFFFFFF)).astype(jnp.int64),
             (lo_u >> jnp.uint64(32)).astype(jnp.int64), hi,
             live.astype(jnp.int64)]
    run = [jnp.cumsum(v) for v in lanes]
    at_end = gather_lanes(run, jnp.clip(ends, 0, n - 1))
    before = gather_lanes([r - v for r, v in zip(run, lanes)],
                          jnp.clip(starts, 0, n - 1))
    s_lo0, s_lo1, s_hi, count = (e - b for e, b in zip(at_end, before))
    from spark_rapids_tpu.exec import int128 as I128

    # total_lo_u = s_lo0 + s_lo1 * 2^32 as 128 bits, + s_hi * 2^64 (mod
    # 2^128: only the hi limb)
    h = (s_lo1.astype(jnp.uint64) >> jnp.uint64(32)).astype(jnp.int64)
    l = (s_lo1.astype(jnp.uint64) << jnp.uint64(32)).astype(jnp.int64)
    h2, l2 = I128.add(h, l, jnp.zeros_like(s_lo0), s_lo0)
    return h2 + s_hi, l2, count


# ---------------------------------------------------------------------------
# Device concatenation (GpuCoalesceBatches concat, on device)
# ---------------------------------------------------------------------------


@jax.named_scope("concat")
def concat_device(
    batches: Sequence[ColumnarBatch],
    out_capacity: int,
    out_byte_capacities: Sequence[int],
) -> ColumnarBatch:
    """Concatenate batches entirely on device (no host round trip).

    The reference concatenates on device via cudf Table.concatenate
    (GpuCoalesceBatches.scala:160); here each input's live rows are placed
    at a running offset. Capacities are static; live row counts are traced.
    Rows past ``out_capacity`` (a caller that packs into less than the
    capacities' sum and guessed too low) are dropped: ``num_rows`` stays
    the true total, which is how the caller sees it.
    """
    ncols = len(batches[0].columns)
    total_rows = jnp.int32(0)
    starts = []
    for b in batches:
        starts.append(total_rows)
        total_rows = total_rows + b.num_rows
    room = max(b.capacity for b in batches)

    def place(lanes):
        """One fixed-width lane of every input, live rows only, end to
        end: each input is written whole (its padding zeroed) at its
        offset, in order, so a later input's live rows overwrite only an
        earlier one's padding. Contiguous copies at a dynamic offset, not
        scatters (which serialize on the TPU); the buffer's tail of
        ``room`` slots takes whatever starts past ``out_capacity``."""
        buf = jnp.zeros(out_capacity + room, lanes[0].dtype)
        for b, st, lane in zip(batches, starts, lanes):
            live = jnp.arange(lane.shape[0], dtype=jnp.int32) < b.num_rows
            buf = jax.lax.dynamic_update_slice(
                buf, jnp.where(live, lane, jnp.zeros_like(lane)),
                (jnp.minimum(st, out_capacity),))
        return buf[:out_capacity]

    out_cols: List[DeviceColumn] = []
    for ci in range(ncols):
        dtype = batches[0].columns[ci].dtype
        is_string = batches[0].columns[ci].offsets is not None
        if not is_string:
            data = place([b.columns[ci].data for b in batches])
            validity = place([b.columns[ci].validity for b in batches])
            wide = batches[0].columns[ci].data2 is not None
            data2 = (place([b.columns[ci].data2 for b in batches])
                     if wide else None)
            # dict codes concat only when every input shares one dictionary
            # (the concat_jit host wrapper decodes mismatched dicts first)
            first = batches[0].columns[ci]
            out_cols.append(DeviceColumn(dtype, data, validity, None,
                                         first.dictionary, first.dict_size,
                                         first.dict_max_len, data2))
            continue
        out_bytes = out_byte_capacities[ci]
        lens_out = jnp.zeros(out_capacity, jnp.int32)
        validity = jnp.zeros(out_capacity, jnp.bool_)
        for b, st in zip(batches, starts):
            c = b.columns[ci]
            j = jnp.arange(c.capacity, dtype=jnp.int32)
            live = j < b.num_rows
            pos = jnp.where(live, st + j, out_capacity)
            lens = c.offsets[1:] - c.offsets[:-1]
            lens_out = lens_out.at[pos].set(lens, mode="drop")
            validity = validity.at[pos].set(c.validity, mode="drop")
        offsets = jnp.concatenate(
            [jnp.zeros(1, jnp.int32), jnp.cumsum(lens_out).astype(jnp.int32)]
        )
        data = jnp.zeros(out_bytes, jnp.uint8)
        for b, st in zip(batches, starts):
            c = b.columns[ci]
            nbytes_in = c.data.shape[0]
            if nbytes_in == 0:
                continue
            k = jnp.arange(nbytes_in, dtype=jnp.int32)
            rows = _string_row_ids(c.offsets, nbytes_in)
            rows_c = jnp.clip(rows, 0, c.capacity - 1)
            live_byte = (rows_c < b.num_rows) & (k < c.offsets[-1]) & (rows >= 0)
            dst_row = st + rows_c
            dst = offsets[jnp.clip(dst_row, 0, out_capacity - 1)] + (
                k - c.offsets[rows_c]
            )
            dst = jnp.where(live_byte, dst, out_bytes)
            data = data.at[dst].set(c.data, mode="drop")
        out_cols.append(DeviceColumn(dtype, data, validity, offsets))
    return ColumnarBatch(out_cols, total_rows)


# ---------------------------------------------------------------------------
# Join gather maps (sorted-hash merge + exact verification)
# ---------------------------------------------------------------------------


class JoinHashes(NamedTuple):
    """Build-side preprocessed state: hashes sorted with an order map."""

    sorted_hash: jax.Array  # (cap_b,) uint64, invalid rows at the end
    order: jax.Array  # (cap_b,) int32, original row of each sorted slot
    valid: jax.Array  # (cap_b,) bool in sorted order


@jax.named_scope("join.hash")
def prepare_join_side(batch: ColumnarBatch, key_cols: Sequence[int]) -> JoinHashes:
    h = hash_keys(batch, key_cols)
    valid = batch.active_mask()
    for i in key_cols:
        valid = valid & batch.columns[i].validity  # SQL: null keys never match
    # push invalid rows past every real hash, keeping the array globally
    # sorted so searchsorted stays valid; candidates landing in the invalid
    # tail are cut by the n_valid clamp in join_candidate_counts
    hh = jnp.where(valid, h, jnp.uint64(0xFFFFFFFFFFFFFFFF))
    order = jnp.lexsort((hh, ~valid)).astype(jnp.int32)
    return JoinHashes(hh[order], order, valid[order])


@jax.named_scope("join.probe")
def join_candidate_counts(
    probe: ColumnarBatch, probe_keys: Sequence[int], build: JoinHashes
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Per-probe-row candidate ranges in the sorted build hashes.

    Returns (lo, cnt, probe_valid); total candidates = sum(cnt)."""
    ph = hash_keys(probe, probe_keys)
    pvalid = probe.active_mask()
    for i in probe_keys:
        pvalid = pvalid & probe.columns[i].validity
    n_build_valid = jnp.sum(build.valid.astype(jnp.int32))
    lo = jnp.searchsorted(build.sorted_hash, ph, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(build.sorted_hash, ph, side="right").astype(jnp.int32)
    hi = jnp.minimum(hi, n_build_valid)
    lo = jnp.minimum(lo, hi)
    cnt = jnp.where(pvalid, hi - lo, 0)
    return lo, cnt, pvalid


@jax.named_scope("join.probe")
def expand_candidates(
    lo: jax.Array, cnt: jax.Array, out_capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Expand per-row candidate ranges into flat (probe_row, build_slot) pairs.

    Returns (probe_idx, build_slot, pair_valid) of length out_capacity.
    The reference's analog is the gather-map pair produced by cudf joins
    (GpuHashJoin.scala:332 JoinGatherer)."""
    ends = jnp.cumsum(cnt).astype(jnp.int32)
    total = ends[-1] if cnt.shape[0] else jnp.int32(0)
    j = jnp.arange(out_capacity, dtype=jnp.int32)
    probe_idx = jnp.searchsorted(ends, j, side="right").astype(jnp.int32)
    probe_c = jnp.clip(probe_idx, 0, cnt.shape[0] - 1)
    start = ends[probe_c] - cnt[probe_c]
    build_slot = lo[probe_c] + (j - start)
    pair_valid = j < total
    return probe_c, build_slot, pair_valid


# ---------------------------------------------------------------------------
# Bucketed join hash table (round-4 general-join rebuild)
# ---------------------------------------------------------------------------
#
# The sorted-hash join above sizes its output from a per-batch candidate
# total (a host sync per probe batch) and compiles a fresh expansion program
# per output-capacity bucket. This table makes the COMMON case — build keys
# unique (dimension tables, de-duplicated subqueries) — fully traced with
# STATIC shapes: probe output capacity = probe capacity, no host syncs, one
# compile. Reference role: cuDF's hash join build/probe under
# GpuHashJoin.scala:332; the design here is TPU-first (sort-once build,
# vectorized S-slot bucket scan on the probe — no device pointers, no
# dynamic parallelism).


class JoinTable(NamedTuple):
    """Build side as a bucket-contiguous sorted layout.

    Rows sort by (h1, h2); a bucket is the TOP ``lg_b`` bits of h1, so the
    sorted layout is bucket-contiguous and ``starts`` (B+1 int32) gives each
    bucket's slot range. Invalid rows (null keys / masked) sort past every
    real row and are also marked in ``valid``."""

    order: jax.Array   # (cap,) int32 original build row per sorted slot
    h1s: jax.Array     # (cap,) uint64 sorted primary hash
    h2s: jax.Array     # (cap,) uint64 secondary hash in sorted order
    valid: jax.Array   # (cap,) bool in sorted order
    starts: jax.Array  # (B+1,) int32 bucket start slots
    lg_b: int          # static: log2(bucket count)


def _join_lg_b(capacity: int) -> int:
    lg = max(int(capacity - 1).bit_length(), 4)
    # ~2x load headroom; cap the starts table at 2^24+1 int32 (64MB) — a
    # build bigger than ~8M rows gets >1 row/bucket on average and the
    # unique-slot bound rejects it long before correctness is at risk
    return min(lg + 1, 24)


@partial(jax.jit, static_argnums=(1,))
@jax.named_scope("join.build")
def build_join_table(batch: ColumnarBatch, key_cols: Tuple[int, ...]):
    """Build the table + per-build stats in ONE traced program.

    Returns (JoinTable, dup_any, max_bucket): ``dup_any`` = some two valid
    build rows carry equal keys (exact, not hash-based); ``max_bucket`` =
    largest bucket population. The caller reads these two scalars once per
    build side to choose the probe strategy — the only host sync in the
    whole join."""
    cap = batch.capacity
    lg_b = _join_lg_b(cap)
    h1 = hash_keys(batch, list(key_cols))
    h2 = hash_keys(batch, list(key_cols), variant=1)
    valid = batch.active_mask()
    for i in key_cols:
        valid = valid & batch.columns[i].validity
    h1m = jnp.where(valid, h1, jnp.uint64(0xFFFFFFFFFFFFFFFF))
    # (h1m, h2) order by four single-key passes, not one variadic sort of
    # two u64 keys (lsd_order: the compiler's bill)
    order = lsd_order(_sort_words([h2, h1m]))
    sh1 = h1m[order]
    sh2 = h2[order]
    sv = valid[order]
    bucket = (sh1 >> jnp.uint64(64 - lg_b)).astype(jnp.int32)
    B = 1 << lg_b
    # a bucket starts where the rows of the buckets before it end: one
    # histogram and its running sum (a searchsorted of B + 1 needles is a
    # loop of gathers: 158 ms for 2^20 in 2^20 on the v5e, PERF.md PR 35)
    sizes = jnp.zeros(B, jnp.int32).at[bucket].add(1)
    starts = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(sizes).astype(jnp.int32)])
    # exact duplicate-key detection: equal adjacent (h1,h2) pairs verified
    # by full key equality (adjacency is sufficient — equal keys hash equal
    # and the sort groups equal (h1,h2))
    adj_hash = sv[1:] & sv[:-1] & (sh1[1:] == sh1[:-1]) & (sh2[1:] == sh2[:-1])
    adj_keys = keys_equal(batch, order[1:], list(key_cols),
                          batch, order[:-1], list(key_cols))
    dup_any = jnp.any(adj_hash & adj_keys)
    n_valid = jnp.sum(sv.astype(jnp.int32))
    # the invalid tail inflates the last bucket; cap sizes at valid slots
    ends_v = jnp.minimum(starts[1:], n_valid)
    starts_v = jnp.minimum(starts[:-1], n_valid)
    max_bucket = jnp.max(ends_v - starts_v)
    return JoinTable(order, sh1, sh2, sv, starts, lg_b), dup_any, max_bucket


@partial(jax.jit, static_argnums=(2, 4, 5, 6))
@jax.named_scope("join.probe")
def probe_join_table_unique(probe: ColumnarBatch, tbl: JoinTable,
                            probe_keys: Tuple[int, ...],
                            build: ColumnarBatch,
                            build_keys: Tuple[int, ...], slots: int,
                            lg_b: int):
    """Probe a unique-key table: per probe row, scan its bucket's first
    ``slots`` slots (static; callers size it at the measured max bucket),
    hash-match then exact-verify. Returns (bi, hit): build row per probe row
    (-1 on miss). Fully traced — no candidate-count sync, output shapes are
    the probe's."""
    cap_p = probe.capacity
    cap_b = tbl.order.shape[0]
    ph1 = hash_keys(probe, list(probe_keys))
    ph2 = hash_keys(probe, list(probe_keys), variant=1)
    pvalid = probe.active_mask()
    for i in probe_keys:
        pvalid = pvalid & probe.columns[i].validity
    b = (ph1 >> jnp.uint64(64 - lg_b)).astype(jnp.int32)
    lo = tbl.starts[b]
    hi = tbl.starts[b + 1]
    slot = lo[:, None] + jnp.arange(slots, dtype=jnp.int32)[None, :]
    in_rng = slot < hi[:, None]
    slot_c = jnp.clip(slot, 0, cap_b - 1)
    cand_ok = (in_rng & tbl.valid[slot_c]
               & (tbl.h1s[slot_c] == ph1[:, None])
               & (tbl.h2s[slot_c] == ph2[:, None])
               & pvalid[:, None])
    rows = tbl.order[slot_c]
    flat_p = jnp.repeat(jnp.arange(cap_p, dtype=jnp.int32), slots)
    eq = keys_equal(probe, flat_p, list(probe_keys),
                    build, rows.reshape(-1), list(build_keys))
    ok = cand_ok & eq.reshape(cap_p, slots)
    hit = jnp.any(ok, axis=1)
    first = jnp.argmax(ok, axis=1)
    bi = jnp.where(hit, rows[jnp.arange(cap_p), first], -1)
    return bi.astype(jnp.int32), hit


ROW_WORDS = 5  # an entry of the row table: h1 lo, h1 hi, h2 lo, h2 hi, row


def join_rows_lg_b(capacity: int) -> int:
    """log2 of the row table's bucket count: half the build's capacity, so
    a full build puts two rows in a bucket on average and its largest
    bucket stays under ``join.uniqueTable.maxSlots`` (16). A bucket is a
    row of ``slots * ROW_WORDS`` words, which the TPU's layout pads to 128:
    more buckets than this buy shorter rows that cost the same memory."""
    return max(int(capacity - 1).bit_length() - 1, 4)


@partial(jax.jit, static_argnums=(1,))
@jax.named_scope("join.build")
def join_row_slots(batch: ColumnarBatch, key_cols: Tuple[int, ...]):
    """First half of the row table's build: each build row's slot.

    Returns ((h1, h2, valid, bucket, rank), largest bucket): rows with a
    valid key fall into the bucket the top ``join_rows_lg_b`` bits of h1
    name; ``rank`` is a row's position among its bucket's rows. ONE sort of
    (bucket, row id) puts a bucket's rows side by side, a running maximum
    of the runs' first positions ranks them, and a second sort, of (row
    id, rank), brings the ranks back into row order: no gather, no scatter
    (``lsd_order``'s signature both times). The caller reads the largest
    bucket, sizes the table's rows by it, and calls ``join_rows_table``."""
    h1 = hash_keys(batch, list(key_cols))
    h2 = hash_keys(batch, list(key_cols), variant=1)
    valid = batch.active_mask()
    for i in key_cols:
        valid = valid & batch.columns[i].validity
    return _place_rows(h1, h2, valid)


def _place_rows(h1: jax.Array, h2: jax.Array, valid: jax.Array):
    """``join_row_slots`` over hashes already made: the rows of ``valid``
    placed in their buckets (a row's id is its index here)."""
    cap = h1.shape[0]
    lg_b = join_rows_lg_b(cap)
    B = 1 << lg_b
    bucket = jnp.where(valid, (h1 >> jnp.uint64(64 - lg_b)).astype(jnp.int32),
                       B)  # rows without a key sort past every bucket
    i = jnp.arange(cap, dtype=jnp.int32)
    sb, perm = jax.lax.sort((bucket.astype(jnp.uint32), i), num_keys=1,
                            is_stable=True)
    first = jnp.concatenate([jnp.ones(1, jnp.bool_), sb[1:] != sb[:-1]])
    run_start = jax.lax.cummax(jnp.where(first, i, 0))
    _, rank = jax.lax.sort((perm.astype(jnp.uint32), i - run_start),
                           num_keys=1, is_stable=True)
    largest = jnp.max(jnp.where(valid, rank, -1)) + 1
    return (h1, h2, valid, bucket, rank), largest


@partial(jax.jit, static_argnums=(1, 2))
@jax.named_scope("join.build")
def join_rows_table(placed, slots: int, lg_b: int):
    """Second half: the buckets as fixed rows, for a probe that reads a
    bucket with ONE gather: (2^lg_b, ROW_WORDS * slots) uint32, a bucket's
    row holding its ``slots`` entries word by word (every h1 lo, then
    every h1 hi, h2 lo, h2 hi, then the build rows' ids, -1 where the
    bucket is shorter). Also whether two build rows share a 128-bit hash
    pair: such twins share h1's top bits and so a bucket, whose slots are
    compared with each other (``slots`` covers ``join_row_slots``'s
    largest bucket: every keyed row is in the table). Two rows with one
    key always share the pair, so a table without twins holds unique keys,
    and one with them is refused as duplicate-keyed (rows of different keys
    and one hash pair: the engine-wide treat-as-exact bar of ``group_rows``).

    On the v5e a gather costs about 9 ns an index whatever it fetches and
    a sort of 2^20 (key, row id) pairs 1.3 ms (my chip runs, PR 35;
    PERF.md): the bucket-contiguous layout's build (``build_join_table``)
    sorts by both hashes and gathers every lane into that order, and its
    probe (``probe_join_table_unique``) makes two gathers a probe row for
    the bucket's range and five a slot. Every array here is long in its
    last axis: the TPU pads a last axis of 5 to 128."""
    rows = _fill_rows(placed, slots, lg_b)
    B = 1 << lg_b
    # word-major a slot's word is a lane over the buckets: slots s and s + d
    # of every bucket compare elementwise (probing for it: 260 ms, PERF.md)
    ent = jnp.transpose(rows).reshape(ROW_WORDS, slots, B)
    held = ent[4] != jnp.uint32(0xFFFFFFFF)
    twin = jnp.zeros(B, jnp.bool_)
    for d in range(1, slots):
        same = held[d:] & held[:-d]
        for k in range(4):
            same = same & (ent[k, d:] == ent[k, :-d])
        twin = twin | jnp.any(same, axis=0)
    return rows, jnp.any(twin)


def _fill_rows(placed, slots: int, lg_b: int) -> jax.Array:
    """The table of ``join_rows_table``: every placed row's entry in its
    bucket's row, the row word its index in ``placed``."""
    h1, h2, valid, bucket, rank = placed
    B = 1 << lg_b
    n = h1.shape[0]
    width = ROW_WORDS * slots
    at = jnp.where(valid & (rank < slots), bucket * width + rank, B * width)
    words = _sort_words([h1, h2]) + [jnp.arange(n, dtype=jnp.uint32)]
    # ONE flat table, scattered into word by word: a (B, slots) array a
    # word would be padded to 128 lanes each (2.5 GB at 2^20 x 16 slots)
    flat = jnp.tile(jnp.concatenate([
        jnp.zeros(4 * slots, jnp.uint32),
        jnp.full(slots, 0xFFFFFFFF, jnp.uint32)]), B)
    for k, w in enumerate(words):
        flat = flat.at[jnp.where(at < B * width, at + k * slots,
                                 B * width)].set(w, mode="drop")
    return flat.reshape(B, width)


# -- runs: the build of a join whose keys repeat ------------------------------
#
# The fused probe's third rung (exec/join.py, after the dense and the unique
# tables have refused). The build's rows sorted by their 128-bit hash pair
# lie in runs, one a key; a run's first row (its head) stands for the run in
# a row table of heads (``join_rows_table``'s layout, so one gather a probe
# row finds it), and the probe expands each row that found its run into as
# many (probe row, build row) pairs as the run is long (``expand_runs``).
# Over one integer key whose range was read the heads need no table: see
# ``join_runs_ranged`` below.


@partial(jax.jit, static_argnums=(1,))
@jax.named_scope("join.build")
def join_runs(batch: ColumnarBatch, key_cols: Tuple[int, ...]):
    """First program of the runs build's table form (the merge form over
    one ranged integer key is ``join_runs_ranged``): the rows sorted so
    that each key's rows lie side by side (two passes of ``lsd_order``'s
    one signature), each run's head and length, and the heads placed in
    the buckets of a row table (``_place_rows``, a head's id its sorted
    position). On a TPU v5e this program and ``join_runs_table`` take
    106.8 ms together over 2^20 rows of one int64 key, where the ranged
    form's two programs take 4.0 ms.

    One integer key (Q12's ``l_orderkey``) is sorted by its own value:
    runs are exact, and the heads' hashes are made from the sorted keys,
    so the sort's order is the one gather of a 64-bit lane (a gather costs
    about 9 ns an index on the v5e, PERF.md). Other keys are sorted by h1:
    where two rows of one h1 hold different keys (or hash pairs) the rung
    is refused (``mixed``), as the unique table refuses its twins: their
    rows could interleave. Returns (runs, placed, stats): runs = (order,
    count), the build row at each sorted position and at a head its run's
    length (0 elsewhere); stats = (largest bucket of heads, longest run,
    keyed rows, mixed), read by the caller in the build's one sync. Plain
    tuples: a program's arguments are exported (exec/jit_persist.py)."""
    cap = batch.capacity
    valid = batch.active_mask()
    for i in key_cols:
        valid = valid & batch.columns[i].validity
    c = batch.columns[key_cols[0]]
    if (len(key_cols) == 1 and c.offsets is None and not c.is_dict
            and not c.is_wide_decimal and c.dtype not in T.FRACTIONAL_TYPES):
        sk = jnp.where(valid, _int_sortable(c.data),
                       jnp.uint64(0xFFFFFFFFFFFFFFFF))
        order = lsd_order(_sort_words([sk]))
        sv = valid[order]
        ranked = ColumnarBatch([DeviceColumn(c.dtype, c.data[order], sv)],
                               batch.num_rows)
        sh1 = hash_keys(ranked, [0])
        sh2 = hash_keys(ranked, [0], variant=1)
        same = sv[1:] & sv[:-1] & (ranked.columns[0].data[1:]
                                   == ranked.columns[0].data[:-1])
        mixed = jnp.bool_(False)
    else:
        h1m = jnp.where(valid, hash_keys(batch, list(key_cols)),
                        jnp.uint64(0xFFFFFFFFFFFFFFFF))
        order = lsd_order(_sort_words([h1m]))
        sh1 = h1m[order]
        sh2 = hash_keys(batch, list(key_cols), variant=1)[order]
        sv = valid[order]
        one_h1 = sv[1:] & sv[:-1] & (sh1[1:] == sh1[:-1])
        same = (one_h1 & (sh2[1:] == sh2[:-1])
                & keys_equal(batch, order[1:], list(key_cols),
                             batch, order[:-1], list(key_cols)))
        mixed = jnp.any(one_h1 & ~same)
    head = sv & jnp.concatenate([jnp.ones(1, jnp.bool_), ~same])
    n_valid = jnp.sum(sv.astype(jnp.int32))
    i = jnp.arange(cap, dtype=jnp.int32)
    # a run's last row (the next is a head, or has no key) writes its
    # length at its head, which a running maximum carried forward: a
    # reversed running minimum from the next head costs the TPU compiler
    # 50 s more (sandbox compile for a described v5e, 2^16 rows)
    last = jax.lax.cummax(jnp.where(head, i, 0))
    end = sv & jnp.concatenate([head[1:] | ~sv[1:], jnp.ones(1, jnp.bool_)])
    count = jnp.zeros(cap, jnp.int32).at[jnp.where(end, last, cap)].set(
        i - last + 1, mode="drop")
    placed, largest = _place_rows(sh1, sh2, head)
    return ((order, count), placed,
            (largest, jnp.max(count), n_valid, mixed))


@partial(jax.jit, static_argnums=(1, 2))
@jax.named_scope("join.build")
def join_runs_table(placed, slots: int, lg_b: int) -> jax.Array:
    """Second program of the runs build: the heads' row table, sized by
    the largest bucket the caller read. Heads are distinct hash pairs by
    construction: no twin check."""
    return _fill_rows(placed, slots, lg_b)


# -- runs over one ranged integer key: u32 offsets, no hashes, no row table --
#
# Where the key is one integer whose range was read and is under 2^28 wide,
# a key is its offset from the least one, a u32. The build sorts the offsets
# once and compacts its runs' heads (``join_runs_ranged``,
# ``join_run_heads``); a probe batch finds its rows' heads by sorting its
# offsets together with the heads' (``merge_run_heads``). Every sort is
# ``lsd_order``'s (u32 key, int32 payload) signature.

NO_OFFSET = 0xFFFFFFFF  # a row with no key; sorts after every offset


@partial(jax.jit, static_argnums=(1,))
@jax.named_scope("join.build")
def join_runs_ranged(batch: ColumnarBatch, key: int, first):
    """First program of the ranged runs build: the rows sorted by their
    offset ``key - first`` as a u32 (``NO_OFFSET`` for a row with no key,
    so those sort last) in ONE stable sort, which hands back the sorted
    offsets themselves: no gather of the key, of a high word or of the
    validity, and no hashes: ``join_runs`` gathers the high word, the
    int64 key and the validity by its order, and the key's two 32-bit
    halves alone took 28 ms each a TPC-H Q12 request at SF10 on a TPU v5e,
    where this program's sort and the heads' compaction take 4.0 ms
    together at 2^20 rows. The caller has read the key's range: every
    offset is under 2^28.
    Returns ((offsets, order): the sorted offsets and the build row at
    each sorted position, (heads, longest run, keyed rows)), the stats for
    the build's one sync."""
    c = batch.columns[key]
    valid = c.validity & batch.active_mask()
    off = jnp.where(valid, c.data.astype(jnp.int64) - first,
                    NO_OFFSET).astype(jnp.uint32)
    i = jnp.arange(batch.capacity, dtype=jnp.int32)
    offsets, order = jax.lax.sort((off, i), num_keys=1, is_stable=True)
    keyed = offsets != jnp.uint32(NO_OFFSET)
    head = keyed & jnp.concatenate([jnp.ones(1, jnp.bool_),
                                    offsets[1:] != offsets[:-1]])
    # a keyed row's place in its run: from the last head at or before it
    last = jax.lax.cummax(jnp.where(head, i, 0))
    longest = jnp.max(jnp.where(keyed, i - last + 1, 0))
    return (offsets, order), (jnp.sum(head.astype(jnp.int32)), longest,
                              jnp.sum(keyed.astype(jnp.int32)))


@partial(jax.jit, static_argnums=(1,))
@jax.named_scope("join.build")
def join_run_heads(offsets: jax.Array, hcap: int):
    """Second program of the ranged runs build: the runs' heads compacted
    into ``hcap`` slots (at least the heads, at most the build's capacity)
    by ONE stable sort of (a head's offset, or ``NO_OFFSET``; its sorted
    position), which leaves them in ascending offset: (offset, sorted
    position of the run's first row, run length) a head, ``NO_OFFSET``
    and length 0 past the heads. A run ends where the next begins, the
    last one at the keyed rows' end: no scatter, no gather."""
    i = jnp.arange(offsets.shape[0], dtype=jnp.int32)
    keyed = offsets != jnp.uint32(NO_OFFSET)
    head = keyed & jnp.concatenate([jnp.ones(1, jnp.bool_),
                                    offsets[1:] != offsets[:-1]])
    hoff, hpos = jax.lax.sort(
        (jnp.where(head, offsets, jnp.uint32(NO_OFFSET)), i), num_keys=1,
        is_stable=True)
    hoff, hpos = hoff[:hcap], hpos[:hcap]
    heads = jnp.sum(head.astype(jnp.int32))
    j = jnp.arange(hcap, dtype=jnp.int32)
    nxt = jnp.where(j + 1 < heads,
                    jnp.concatenate([hpos[1:], jnp.zeros(1, jnp.int32)]),
                    jnp.sum(keyed.astype(jnp.int32)))
    return hoff, hpos, jnp.where(j < heads, nxt - hpos, 0)


@jax.named_scope("join.merge")
def merge_run_heads(off: jax.Array, hoff: jax.Array, hcap: int
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The rows of a probe batch that have a run, with their runs' heads,
    by merging the batch's offsets (u32 under 2^28, ``NO_OFFSET`` for a
    row with no key, a filter's or the range's) with the heads' ascending
    offsets (``join_run_heads``): a head sorts as ``2 * offset``, a probe
    row as ``2 * offset + 1``, so ONE sort of the two puts each probe row
    right after its key's head, and two running maxima carry the nearest
    head's offset and index forward to it. A second sort of the same
    signature, keyed by the probe row (``NO_OFFSET`` for rows with no
    head), compacts them in row order. Returns (probe rows, their heads'
    indexes, rows found): ``hcap`` slots, 0 past the count; a count above
    ``hcap`` means rows were cut, the caller's to flag. It replaces a
    presence table's byte a row (a gather over the batch), that gather's
    compaction, and the heads' row table's hashes, bucket gather and key
    check. On a TPU v5e, 2^20 offsets against 2^19 heads cut to 2^16
    slots take 5.9 ms; the presence gather and its compaction 10.6 ms."""
    n, nh = off.shape[0], hoff.shape[0]
    none = jnp.uint32(NO_OFFSET)
    keys = jnp.concatenate([
        jnp.where(off != none, off * jnp.uint32(2) + jnp.uint32(1), none),
        jnp.where(hoff != none, hoff * jnp.uint32(2), none)])
    at = jnp.arange(n + nh, dtype=jnp.int32)
    skey, sat = jax.lax.sort((keys, at), num_keys=1, is_stable=True)
    is_head = (sat >= n) & (skey != none)
    # keys are under 2^29 + 1: int32 holds them, and the running maxima
    # carry the heads, which ascend in both offset and index
    k32 = skey.astype(jnp.int32)
    near = jax.lax.cummax(jnp.where(is_head, k32 + 1, 0))
    head = jax.lax.cummax(jnp.where(is_head, sat - n, 0))
    found = (sat < n) & (skey != none) & (near == k32)
    row, head = jax.lax.sort(
        (jnp.where(found, sat.astype(jnp.uint32), none), head), num_keys=1,
        is_stable=True)
    count = jnp.sum(found.astype(jnp.int32))
    live = jnp.arange(hcap, dtype=jnp.int32) < count
    return (jnp.where(live, row[:hcap].astype(jnp.int32), 0),
            jnp.where(live, head[:hcap], 0), count)


@jax.named_scope("join.expand")
def expand_runs(start: jax.Array, cnt: jax.Array, out_cap: int
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Each row's run as pairs: row ``r`` with ``cnt[r]`` build rows from
    sorted position ``start[r]`` fills ``cnt[r]`` consecutive slots of an
    output of ``out_cap``. Returns (row, position, pairs) per slot: the row
    that owns it (the last row whose first slot is at or before it: one
    scatter of the rows that have a run to their first slots and a running
    maximum, no search), the build's sorted position, and how many pairs
    there are in all (int64; more than ``out_cap`` means some were cut:
    the caller's to flag). Slots past the pairs repeat the last one."""
    n = cnt.shape[0]
    ends = jnp.cumsum(cnt)
    first = ends - cnt  # wraps only past 2^31 pairs, where ``pairs`` flags
    total = jnp.sum(cnt.astype(jnp.int64))
    owns = (cnt > 0) & (first >= 0) & (first < out_cap)
    owner = jnp.zeros(out_cap, jnp.int32).at[
        jnp.where(owns, first, out_cap)].set(
            jnp.arange(n, dtype=jnp.int32), mode="drop")
    row = jax.lax.cummax(owner)
    j = jnp.arange(out_cap, dtype=jnp.int32)
    pos = start[row] + jnp.minimum(j - first[row], jnp.maximum(cnt[row] - 1,
                                                               0))
    return row, pos, total


@jax.named_scope("join.probe")
def probe_join_rows(rows: jax.Array, lg_b: int, ph1: jax.Array,
                    ph2: jax.Array, pvalid: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """(build row or -1, candidate?) per probe row against
    ``join_rows_table``'s table: one gather of the probe row's bucket, then
    a compare of the 128-bit hash pair over its slots in registers; the
    first slot that matches wins. A candidate still has to pass the exact
    key comparison (the caller's, over the few rows that are left once the
    candidates are compacted)."""
    slots = rows.shape[1] // ROW_WORDS
    b = (ph1 >> jnp.uint64(64 - lg_b)).astype(jnp.int32)
    # (ROW_WORDS * slots, n): a word of a slot is then a contiguous lane; a
    # column of the fetched (n, words) array is a pass over all of it
    # (2.3 ms each for 2^20 rows: 92 ms a batch at 8 slots on the v5e)
    ent = jnp.transpose(jnp.take(rows, b, axis=0, mode="clip"))
    words = _sort_words([ph1, ph2])
    bi = jnp.full(ph1.shape[0], -1, jnp.int32)
    hit = jnp.zeros(ph1.shape[0], jnp.bool_)
    for s in range(slots):
        row = jax.lax.bitcast_convert_type(ent[4 * slots + s], jnp.int32)
        ok = (row >= 0) & pvalid
        for k, w in enumerate(words):
            ok = ok & (ent[k * slots + s] == w)
        bi = jnp.where(ok & ~hit, row, bi)
        hit = hit | ok
    return bi, hit


# ---------------------------------------------------------------------------
# Open-addressing device hash table (round-12; shared by join and aggregate)
# ---------------------------------------------------------------------------
#
# The general duplicate-key layer both the join and the aggregate were
# missing (reference: cuDF's open-addressing hash tables under
# GpuHashJoin/GpuAggregateExec; SURVEY §2.4). Design is TPU-first:
#
# - linear probing over a power-of-two slot array; each build round is a
#   data-parallel claim pass (scatter-min of row ids into contested empty
#   slots) instead of per-thread CAS loops — all rows advance in lockstep,
#   so the build is a bounded ``lax.while_loop`` of pure gathers/scatters
#   and jits on every backend (the pure-XLA fallback IS the kernel; a
#   Pallas build of the same loop body is dispatched when the backend
#   supports it, see docs/kernels.md);
# - the table stores the 128-bit hash pair per slot; duplicate rows attach
#   to their key's slot, and a count+offset layout (rows stably sorted by
#   slot id) turns each slot into a candidate range — the row-chain analog
#   of cuDF's multimap, but readable with two searchsorted gathers;
# - overflow (a probe cluster outrunning the static probe bound) reports a
#   device flag; the HOST retries with the next seed (seeded rehash), and
#   the seed is a static jit argument so two seeds never share a program.
#
# Static jit keys carry (capacity, seed, max_probes): the table layout
# parameters can never collide in the jit/persist caches
# (tools/lint/cache_keys.py guards this structurally).

HASHTBL_MAX_PROBES = 16  # default static probe bound per seed
HASHTBL_MAX_REHASH = 4   # host-side seeded rehash attempts before fallback

_hashtbl_lock = threading.Lock()
_hashtbl_counters = {
    "hashtbl_build_total": 0,   # tables built (host-visible builds)
    "hashtbl_probe_total": 0,   # probe passes over a table
    "hashtbl_rehash_total": 0,  # seeded rebuilds after overflow
    "hashtbl_chunk_total": 0,   # bounded output chunks emitted by joins
    "hashtbl_pallas_fallback_total": 0,  # lowering failures -> sticky XLA
}


def _note_hashtbl(name: str, n: int = 1) -> None:
    with _hashtbl_lock:
        _hashtbl_counters[name] += n


def counters() -> dict:
    """Kernel counters (hash-table + sort/window) for the gauge catalog."""
    with _hashtbl_lock:
        out = dict(_hashtbl_counters)
    out.update(sortwin_counters())
    return out


def hashtbl_capacity(n_rows: int) -> int:
    """Static slot count for an n-row build: next power of two >= 2 * rows
    (load factor <= 0.5 keeps linear-probe clusters short)."""
    cap = 16
    while cap < 2 * max(n_rows, 1):
        cap *= 2
    return cap


def _hashtbl_base(h1: jax.Array, capacity: int, seed: int) -> jax.Array:
    """Home slot per row: the seed re-mixes the hash so a rehash relocates
    every cluster, not just the overflowing one."""
    mix = jnp.uint64((seed * 0x9E3779B97F4A7C15 + 0xC2B2AE3D27D4EB4F)
                     & 0xFFFFFFFFFFFFFFFF)
    return (_splitmix64(h1 ^ mix)
            & jnp.uint64(capacity - 1)).astype(jnp.int32)


class HashTable(NamedTuple):
    """Open-addressing table over the 128-bit hash pair, plus the
    count+offset duplicate layout (``order``/``sorted_slots``).

    ``slot_h1``/``slot_h2`` hold the occupying key's hash pair (undefined
    while ``slot_used`` is False). ``row_slot`` maps each build row to its
    slot (-1: invalid key / unplaced). ``order`` lists build rows stably
    sorted by slot id — a slot's rows are the contiguous run
    ``order[searchsorted(sorted_slots, s, left):searchsorted(..., right)]``.
    """

    slot_h1: jax.Array      # (capacity,) uint64
    slot_h2: jax.Array      # (capacity,) uint64
    slot_used: jax.Array    # (capacity,) bool
    row_slot: jax.Array     # (n,) int32
    order: jax.Array        # (n,) int32 rows sorted by slot id
    sorted_slots: jax.Array  # (n,) int32 row_slot[order]; invalid -> capacity


def _hashtbl_insert_rounds(h1, h2, valid, capacity: int, seed: int,
                           max_probes: int):
    """Shared build loop: returns (slot_h1, slot_h2, slot_used, row_slot).

    Round p: every unplaced row looks at base+p. Empty slots are claimed by
    scatter-min of row ids; after claims land, every unplaced row re-checks
    the slot — matching (h1, h2) attaches (winners match their own write,
    duplicate keys attach to their winner the same round, so equal keys can
    never split across slots)."""
    n = h1.shape[0]
    row_ids = jnp.arange(n, dtype=jnp.int32)
    base = _hashtbl_base(h1, capacity, seed)

    def cond(st):
        p, _, _, _, row_slot = st
        return (p < max_probes) & jnp.any(valid & (row_slot < 0))

    def body(st):
        p, slot_h1, slot_h2, slot_used, row_slot = st
        pos = ((base + p) & (capacity - 1)).astype(jnp.int32)
        unplaced = valid & (row_slot < 0)
        want = unplaced & ~slot_used[pos]
        tgt = jnp.where(want, pos, capacity)
        claim = jnp.full(capacity, n, jnp.int32).at[tgt].min(
            row_ids, mode="drop")
        won = want & (claim[pos] == row_ids)
        wpos = jnp.where(won, pos, capacity)
        slot_h1 = slot_h1.at[wpos].set(h1, mode="drop")
        slot_h2 = slot_h2.at[wpos].set(h2, mode="drop")
        slot_used = slot_used.at[wpos].set(True, mode="drop")
        match = (unplaced & slot_used[pos]
                 & (slot_h1[pos] == h1) & (slot_h2[pos] == h2))
        row_slot = jnp.where(match, pos, row_slot)
        return p + 1, slot_h1, slot_h2, slot_used, row_slot

    st = (jnp.int32(0),
          jnp.zeros(capacity, jnp.uint64), jnp.zeros(capacity, jnp.uint64),
          jnp.zeros(capacity, jnp.bool_), jnp.full(n, -1, jnp.int32))
    _, slot_h1, slot_h2, slot_used, row_slot = jax.lax.while_loop(
        cond, body, st)
    return slot_h1, slot_h2, slot_used, row_slot


@partial(jax.jit, static_argnums=(3, 4, 5))
@jax.named_scope("join.build")
def build_hash_table(h1: jax.Array, h2: jax.Array, valid: jax.Array,
                     capacity: int, seed: int, max_probes: int):
    """Build the table + duplicate layout in one traced program.

    Returns (HashTable, overflow). ``overflow`` is the ONLY host read: True
    means some valid row ran out of probe window under this seed — the
    caller rebuilds with seed+1 (``build_batch_hash_table``)."""
    slot_h1, slot_h2, slot_used, row_slot = _hashtbl_insert_rounds(
        h1, h2, valid, capacity, seed, max_probes)
    overflow = jnp.any(valid & (row_slot < 0))
    srt = jnp.where(valid & (row_slot >= 0), row_slot, capacity)
    n = h1.shape[0]
    _, order = jax.lax.sort(
        (srt, jnp.arange(n, dtype=jnp.int32)), num_keys=1, is_stable=True)
    return HashTable(slot_h1, slot_h2, slot_used, row_slot,
                     order.astype(jnp.int32), srt[order]), overflow


def build_batch_hash_table(batch: ColumnarBatch, key_cols: Tuple[int, ...]):
    """HOST wrapper: hash the key columns, build with seeded rehash.

    Returns (HashTable, capacity, seed) or None when every seed overflowed
    (callers fall back to the sorted-hash join). One device->host scalar
    read per attempt; almost always exactly one."""
    h1 = hash_keys(batch, list(key_cols))
    h2 = hash_keys(batch, list(key_cols), variant=1)
    valid = batch.active_mask()
    for i in key_cols:
        valid = valid & batch.columns[i].validity
    capacity = hashtbl_capacity(batch.capacity)
    for seed in range(HASHTBL_MAX_REHASH):
        tbl, overflow = build_hash_table(h1, h2, valid, capacity, seed,
                                         HASHTBL_MAX_PROBES)
        if not bool(host_get(overflow, "hashtbl.build_overflow")):
            _note_hashtbl("hashtbl_build_total")
            return tbl, capacity, seed
        _note_hashtbl("hashtbl_rehash_total")
        capacity *= 2  # grow + reseed: clusters can't reform in place
    return None


@partial(jax.jit, static_argnums=(3, 4, 5))
@jax.named_scope("join.probe")
def probe_hash_table(tbl: HashTable, h1: jax.Array, h2: jax.Array,
                     capacity: int, seed: int, max_probes: int):
    """Find each probe key's slot: bounded linear scan of pure gathers.

    Returns (slot, hit); a probe row stops at its match or at the first
    empty slot (linear probing guarantees the key is absent past one).
    No scatters, no host sync — safe inside any traced program."""
    base = _hashtbl_base(h1, capacity, seed)
    n = h1.shape[0]

    def cond(st):
        p, _, done = st
        return (p < max_probes) & jnp.any(~done)

    def body(st):
        p, slot, done = st
        pos = ((base + p) & (capacity - 1)).astype(jnp.int32)
        occ = tbl.slot_used[pos]
        match = occ & (tbl.slot_h1[pos] == h1) & (tbl.slot_h2[pos] == h2)
        slot = jnp.where(~done & match, pos, slot)
        done = done | match | ~occ
        return p + 1, slot, done

    _, slot, _ = jax.lax.while_loop(
        cond, body, (jnp.int32(0), jnp.full(n, -1, jnp.int32),
                     jnp.zeros(n, jnp.bool_)))
    return slot, slot >= 0


def _split_u64(a: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """(lo32, hi32) uint32 words of a uint64 array (Pallas TPU kernels have
    no 64-bit integer lanes; the probe compares word pairs instead)."""
    return ((a & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32),
            (a >> jnp.uint64(32)).astype(jnp.uint32))


def _pallas_probe_kernel(capacity: int, max_probes: int):
    """Kernel body factory for the Pallas probe (whole-array blocks)."""

    def kernel(used_ref, t1l_ref, t1h_ref, t2l_ref, t2h_ref, base_ref,
               p1l_ref, p1h_ref, p2l_ref, p2h_ref, slot_ref):
        used = used_ref[...]
        t1l, t1h = t1l_ref[...], t1h_ref[...]
        t2l, t2h = t2l_ref[...], t2h_ref[...]
        base = base_ref[...]
        p1l, p1h = p1l_ref[...], p1h_ref[...]
        p2l, p2h = p2l_ref[...], p2h_ref[...]

        def body(p, st):
            slot, done = st
            pos = ((base + p) & (capacity - 1)).astype(jnp.int32)
            occ = used[pos]
            match = (occ & (t1l[pos] == p1l) & (t1h[pos] == p1h)
                     & (t2l[pos] == p2l) & (t2h[pos] == p2h))
            slot = jnp.where(~done & match, pos, slot)
            done = done | match | ~occ
            return slot, done

        slot0 = jnp.full(base.shape, -1, jnp.int32)
        done0 = jnp.zeros(base.shape, jnp.bool_)
        slot, _ = jax.lax.fori_loop(0, max_probes, body, (slot0, done0))
        slot_ref[...] = slot

    return kernel


_pallas_broken = False  # sticky: first lowering failure disables the path
_pallas_mode_last = None  # last-seen conf mode, to detect off/auto -> "on"


def reset_pallas_fallback() -> None:
    """Clear the sticky Pallas lowering-failure latch so the next probe
    re-attempts the kernel (e.g. after a driver/library fix)."""
    global _pallas_broken
    _pallas_broken = False


def _note_pallas_fallback(err: Exception) -> None:
    _note_hashtbl("hashtbl_pallas_fallback_total")
    try:
        from spark_rapids_tpu.obs import events as _events
        _events.emit("pallas-fallback",
                     backend=jax.default_backend(),
                     error=f"{type(err).__name__}: {err}"[:200])
    except Exception:
        pass


def probe_hash_table_pallas(tbl: HashTable, h1: jax.Array, h2: jax.Array,
                            capacity: int, seed: int, max_probes: int,
                            interpret: bool = False):
    """Pallas variant of ``probe_hash_table`` — identical contract.

    The hash pair is pre-split into uint32 word lanes (no 64-bit lanes on
    TPU Pallas); the bounded linear scan runs as one kernel over the whole
    probe block. ``interpret=True`` runs the same kernel through the Pallas
    interpreter (how the CPU test lane covers it)."""
    from jax.experimental import pallas as pl

    base = _hashtbl_base(h1, capacity, seed)
    t1l, t1h = _split_u64(tbl.slot_h1)
    t2l, t2h = _split_u64(tbl.slot_h2)
    p1l, p1h = _split_u64(h1)
    p2l, p2h = _split_u64(h2)
    slot = pl.pallas_call(
        _pallas_probe_kernel(capacity, max_probes),
        out_shape=jax.ShapeDtypeStruct(h1.shape, jnp.int32),
        interpret=interpret,
    )(tbl.slot_used, t1l, t1h, t2l, t2h, base, p1l, p1h, p2l, p2h)
    return slot, slot >= 0


def probe_hash_table_dispatch(tbl: HashTable, h1: jax.Array, h2: jax.Array,
                              capacity: int, seed: int, max_probes: int):
    """Dispatch between the pure-XLA ``probe_hash_table`` and the Pallas
    kernel. Rule: ``auto`` selects a Pallas kernel only on a backend whose
    compiler is shown to accept it by a compile test in
    tests/test_tpu_compile.py. The TPU v5e compiler refuses this one (a
    data-dependent gather over whole-array VMEM blocks; RecursionError
    while lowering), so ``auto`` runs the XLA formulation on every backend
    and the kernel is reached only through ``on`` (interpret-mode tests,
    and the sticky fallback that catches an ``on`` lowering failure)."""
    global _pallas_broken, _pallas_mode_last
    from spark_rapids_tpu.config import conf as _C
    mode = _C.HASHTBL_PALLAS_MODE.get(_C.get_active())
    if mode == "on" and _pallas_mode_last not in (None, "on"):
        # conf changed to an explicit "on": the operator asked for a
        # re-attempt, so the sticky latch from the previous mode resets
        reset_pallas_fallback()
    _pallas_mode_last = mode
    if mode == "on" and not _pallas_broken:
        try:
            return probe_hash_table_pallas(tbl, h1, h2, capacity, seed,
                                           max_probes)
        except Exception as e:  # unsupported lowering: never fail the query
            _pallas_broken = True
            _note_pallas_fallback(e)
    return probe_hash_table(tbl, h1, h2, capacity, seed, max_probes)


def hashtbl_candidate_ranges(tbl: HashTable, slot: jax.Array,
                             hit: jax.Array):
    """(lo, cnt) candidate ranges in ``tbl.order`` for probed slots —
    the count+offset read of the duplicate layout."""
    lo = jnp.searchsorted(tbl.sorted_slots, slot, side="left").astype(
        jnp.int32)
    hi = jnp.searchsorted(tbl.sorted_slots, slot, side="right").astype(
        jnp.int32)
    cnt = jnp.where(hit, hi - lo, 0)
    lo = jnp.minimum(lo, hi)
    return lo, cnt


# -- aggregate grouping on the same table -----------------------------------


def _group_rows_prehashed_sort(h1: jax.Array, h2: jax.Array,
                               active: jax.Array) -> GroupInfo:
    """The pre-round-12 sort-based clustering (also the in-trace fallback
    branch when the table build overflows its probe bound)."""
    cap = h1.shape[0]
    keys = [h2, h1, jnp.where(active, jnp.uint32(0), jnp.uint32(1))]
    perm = lexsort_chain(keys).astype(jnp.int32)
    g1, g2 = gather_lanes([h1, h2], perm)
    p1 = jnp.concatenate([g1[:1], g1[:-1]])
    p2 = jnp.concatenate([g2[:1], g2[:-1]])
    neq = (g1 != p1) | (g2 != p2)
    return _group_from_boundaries(perm, neq, active, cap)


@jax.named_scope("agg.group")
def group_rows_table(h1: jax.Array, h2: jax.Array,
                     active: jax.Array) -> GroupInfo:
    """Cluster rows by 128-bit hash pair via the open-addressing table.

    In-trace (usable under shared_jit): builds the table with the default
    seed, then sorts rows by their SLOT id — one stable int32 sort pass
    instead of the four u32 passes of the 128-bit lexsort. Equal keys share
    a slot (the build attaches duplicates in their claim round), so slot
    order is group order. Overflow takes a ``lax.cond`` to the sort-based
    clustering — identical GroupInfo shapes, so the traced program covers
    both and only the taken branch runs."""
    cap = h1.shape[0]
    capacity = hashtbl_capacity(cap)
    slot_h1, slot_h2, slot_used, row_slot = _hashtbl_insert_rounds(
        h1, h2, active, capacity, 0, HASHTBL_MAX_PROBES)
    overflow = jnp.any(active & (row_slot < 0))

    def via_table(_):
        srt = jnp.where(active & (row_slot >= 0), row_slot, capacity)
        _, perm = jax.lax.sort(
            (srt, jnp.arange(cap, dtype=jnp.int32)), num_keys=1,
            is_stable=True)
        perm = perm.astype(jnp.int32)
        ss = srt[perm]
        neq = ss != jnp.concatenate([ss[:1], ss[:-1]])
        return _group_from_boundaries(perm, neq, active, cap)

    def via_sort(_):
        return _group_rows_prehashed_sort(h1, h2, active)

    return jax.lax.cond(overflow, via_sort, via_table, operand=None)


# ---------------------------------------------------------------------------
# Ordered-computation kernels (round 13): segmented prefix scans, the
# merge-path out-of-core merge, and packed ("radix") sort keys. Reference:
# the GpuWindowExec/segmented-scan layer and the out-of-core merge of
# GpuSortExec.scala — here each is a gather/scan formulation over the same
# statically-shaped buffers the rest of the module uses. docs/kernels.md
# "Sort & window kernels".
# ---------------------------------------------------------------------------


_sortwin_lock = threading.Lock()
_sortwin_counters = {
    "sort_runs_total": 0,    # sorted runs created by the out-of-core sort
    "sort_merge_total": 0,   # merge-path device merges (vs concat+re-sort)
    "sort_radix_total": 0,   # packed-key single-pass sorts taken
    "window_scan_total": 0,  # window functions served by scan/prefix paths
    "window_loop_total": 0,  # window functions served by gather/RMQ paths
    "sortwin_pallas_fallback_total": 0,  # segscan lowering failures -> XLA
}


def _note_sortwin(name: str, n: int = 1) -> None:
    with _sortwin_lock:
        _sortwin_counters[name] += n


def sortwin_counters() -> dict:
    with _sortwin_lock:
        return dict(_sortwin_counters)


_SEGSCAN_OPS = {
    "add": jnp.add,
    "min": jnp.minimum,
    "max": jnp.maximum,
}


def _segscan_identity(op_name: str, dtype):
    if op_name == "add":
        return jnp.zeros((), dtype)
    big = (jnp.array(jnp.inf, dtype) if jnp.issubdtype(dtype, jnp.floating)
           else jnp.array(jnp.iinfo(dtype).max, dtype))
    small = (jnp.array(-jnp.inf, dtype)
             if jnp.issubdtype(dtype, jnp.floating)
             else jnp.array(jnp.iinfo(dtype).min, dtype))
    return big if op_name == "min" else small


def _segscan_combine(op):
    def combine(a, b):
        fa, va = a
        fb, vb = b
        return (fa | fb, jnp.where(fb, vb, op(va, vb)))

    return combine


def segmented_scan_xla(values: jax.Array, is_start: jax.Array,
                       op_name: str = "add") -> jax.Array:
    """Inclusive segmented scan (resets at segment heads), pure XLA.

    The associative-scan carry pair (seen-a-head, running value) is the
    canonical two-prefix formulation: window running aggregates and
    rank/row_number are differences of these prefixes."""
    op = _SEGSCAN_OPS[op_name]
    _, out = jax.lax.associative_scan(
        _segscan_combine(op), (is_start, values))
    return out


_SEGSCAN_LANES = 128       # last-dim tile width (VPU lanes)
_SEGSCAN_SUBLANES = 8      # f32/i32 min sublane count
_SEGSCAN_TILE_ROWS = 256   # rows per grid step: 32K elements, 128 KiB/array


def _pallas_segscan_kernel(op_name: str, rows: int):
    """Kernel body factory for the blocked segmented scan over one
    (rows, 128) tile of a sequential grid.

    Both levels are log-step (Hillis-Steele) scans built from lane/sublane
    rotates and selects, which is what the TPU's compiler lowers (an
    in-kernel ``associative_scan`` slices to zero-width vectors and is
    refused): an inclusive scan within each 128-lane row, then a scan of
    the row summaries down the tile. The running value crosses tiles in a
    VMEM scratch tile. Flags are int32 and every
    rotate amount and index is an explicit numpy int32: with x64 on, a
    Python int would reach Mosaic as an i64 it does not take, and a jnp
    scalar would be a captured constant under the eager probe."""
    op = _SEGSCAN_OPS[op_name]

    def scan(v, f, axis, n):
        from jax.experimental.pallas import tpu as pltpu
        idx = jax.lax.broadcasted_iota(jnp.int32, v.shape, axis)
        d = 1
        while d < n:
            v_sh = pltpu.roll(v, np.int32(d), axis)
            f_sh = pltpu.roll(f, np.int32(d), axis)
            ok = idx >= d
            v = jnp.where(ok & (f == 0), op(v_sh, v), v)
            f = jnp.where(ok, f | f_sh, f)
            d *= 2
        return v, f

    def kernel(vals_ref, seg_ref, out_ref, carry_ref):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        @pl.when(pl.program_id(0) == 0)
        def _():
            carry_ref[...] = jnp.full(
                carry_ref.shape, _segscan_identity(op_name, vals_ref.dtype))

        # level 1: segmented scan within each 128-lane row
        v, f = scan(vals_ref[...], seg_ref[...], 1, _SEGSCAN_LANES)
        # level 2: row summaries (last lane, spread over the lanes) scanned
        # down the rows of the tile
        vs, fs = scan(jnp.broadcast_to(v[:, _SEGSCAN_LANES - 1:], v.shape),
                      jnp.broadcast_to(f[:, _SEGSCAN_LANES - 1:], f.shape),
                      0, rows)
        # a row's carry-in: the previous row's inclusive summary folded
        # onto the carry from earlier tiles; it applies to the row's prefix
        # before its first head
        cv = jnp.broadcast_to(carry_ref[0:1, :], v.shape)
        row = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
        pv = pltpu.roll(vs, np.int32(1), 0)
        pf = pltpu.roll(fs, np.int32(1), 0)
        ev = jnp.where(row == 0, cv, jnp.where(pf != 0, pv, op(cv, pv)))
        out_ref[...] = jnp.where(f == 0, op(ev, v), v)
        # carry out: the tile's last inclusive summary folded onto the carry
        lv = jnp.broadcast_to(vs[rows - 1:, :], carry_ref.shape)
        lf = jnp.broadcast_to(fs[rows - 1:, :], carry_ref.shape)
        carry_ref[...] = jnp.where(lf != 0, lv, op(carry_ref[...], lv))

    return kernel


def segmented_scan_pallas(values: jax.Array, is_start: jax.Array,
                          op_name: str = "add",
                          interpret: bool = False) -> jax.Array:
    """Pallas variant of ``segmented_scan_xla`` — identical contract.

    Pads to whole (rows x 128) tiles (padding rows are their own one-row
    segments, so they never contaminate the carry) and runs the two-level
    scan tile by tile over a sequential grid. ``interpret=True`` runs the
    same kernel through the Pallas interpreter (the CPU test lane)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = values.shape[0]
    blk = _SEGSCAN_LANES * _SEGSCAN_SUBLANES
    rows = (_SEGSCAN_TILE_ROWS if n > _SEGSCAN_TILE_ROWS * _SEGSCAN_LANES
            else _SEGSCAN_SUBLANES * ((max(n, 1) + blk - 1) // blk))
    tile = rows * _SEGSCAN_LANES
    npad = ((max(n, 1) + tile - 1) // tile) * tile
    ident = jnp.full((npad - n,), _segscan_identity(op_name, values.dtype))
    v = jnp.concatenate([values, ident]) if npad > n else values
    s = is_start.astype(jnp.int32)
    if npad > n:
        s = jnp.concatenate([s, jnp.ones(npad - n, jnp.int32)])
    v2 = v.reshape(npad // _SEGSCAN_LANES, _SEGSCAN_LANES)
    s2 = s.reshape(npad // _SEGSCAN_LANES, _SEGSCAN_LANES)
    spec = pl.BlockSpec((rows, _SEGSCAN_LANES),
                        lambda i: (i, np.int32(0)))
    out = pl.pallas_call(
        _pallas_segscan_kernel(op_name, rows),
        out_shape=jax.ShapeDtypeStruct(v2.shape, v2.dtype),
        grid=(npad // tile,),
        in_specs=[spec, spec],
        out_specs=spec,
        scratch_shapes=[
            pltpu.VMEM((_SEGSCAN_SUBLANES, _SEGSCAN_LANES), v2.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(v2, s2)
    return out.reshape(-1)[:n]


_sortwin_pallas_broken = False  # sticky: first lowering failure -> XLA
_sortwin_mode_last = None       # last-seen conf mode (off/auto -> "on" reset)
_sortwin_probed = False         # one-time eager lowering probe ran


def reset_sortwin_pallas_fallback() -> None:
    """Clear the sticky segscan Pallas latch (and its lowering probe) so
    the next scan re-attempts the kernel."""
    global _sortwin_pallas_broken, _sortwin_probed
    _sortwin_pallas_broken = False
    _sortwin_probed = False


def _note_sortwin_pallas_fallback(err: Exception) -> None:
    _note_sortwin("sortwin_pallas_fallback_total")
    try:
        from spark_rapids_tpu.obs import events as _events
        _events.emit("pallas-fallback",
                     backend=jax.default_backend(), site="segscan",
                     error=f"{type(err).__name__}: {err}"[:200])
    except Exception:
        pass


def _segscan_pallas_ok() -> bool:
    """One-time EAGER probe: the segmented scan is embedded in traced
    window programs, where a lowering failure would surface at compile
    time and fail the query. A plain call from inside an outer trace would
    be STAGED into that trace (burying the failure in the caller's compile
    — and injecting the dead kernel into its program), so the probe runs
    on a thread of its own: trace state is thread-local, and there the
    kernel compiles and runs now, where the except can latch the sticky
    fallback. (``ensure_compile_time_eval`` would also escape the trace,
    but its constant folding evaluates the kernel's input-less primitives
    — ``program_id``, iotas — while the kernel itself is traced.) The probe
    also holds the kernel to the XLA scan's answer on an input whose
    segments cross rows and a tile boundary: interpret mode cannot show
    what the hardware's rotates do, and a kernel that disagrees must not
    be used."""
    global _sortwin_probed, _sortwin_pallas_broken
    if not _sortwin_probed:
        _sortwin_probed = True
        errors = []

        def probe():
            try:
                n = (_SEGSCAN_TILE_ROWS + _SEGSCAN_SUBLANES) * _SEGSCAN_LANES
                v = jnp.arange(n, dtype=jnp.int32) % 7 - 3
                s = (jnp.arange(n, dtype=jnp.int32) % 5003) == 0
                if not bool(jnp.array_equal(
                        segmented_scan_pallas(v, s, "add"),
                        segmented_scan_xla(v, s, "add"))):
                    raise AssertionError(
                        "Pallas segmented scan disagrees with the XLA scan")
            except Exception as e:  # any failure: never fail the query
                errors.append(e)

        t = threading.Thread(target=probe, name="srtpu-segscan-probe")
        t.start()
        t.join()
        if errors:
            _sortwin_pallas_broken = True
            _note_sortwin_pallas_fallback(errors[0])
    return not _sortwin_pallas_broken


# Pallas TPU kernels have no 64-bit lanes: the dispatch only routes 32-bit
# scans to the kernel; 64-bit running sums (window f64/int64 lanes) keep
# the XLA formulation. uint32 too: the v5e compiler refuses unsigned
# vector min/max (arith.minui/maxui).
_SEGSCAN_PALLAS_DTYPES = (jnp.float32, jnp.int32)


def segmented_scan(values: jax.Array, is_start: jax.Array,
                   op_name: str = "add") -> jax.Array:
    """Dispatch between ``segmented_scan_xla`` and the Pallas kernel. Same
    rule as the hash-table probe: ``auto`` selects a Pallas kernel only on
    a backend whose compiler is shown to accept it by a compile test in
    tests/test_tpu_compile.py. The TPU v5e compiler accepts this one
    (float32/int32, add/min/max, at 2^20 rows), so ``auto`` means Pallas on
    a TPU and the XLA formulation elsewhere. The kernel is probed eagerly
    before any traced program commits to it, with a sticky XLA fallback on
    any failure and the latch reset on a transition to 'on'."""
    global _sortwin_mode_last, _sortwin_pallas_broken
    from spark_rapids_tpu.config import conf as _C
    mode = _C.SORTWIN_PALLAS_MODE.get(_C.get_active())
    if mode == "on" and _sortwin_mode_last not in (None, "on"):
        reset_sortwin_pallas_fallback()
    _sortwin_mode_last = mode
    use = (mode == "on"
           or (mode == "auto" and jax.default_backend() == "tpu"))
    if (use and values.ndim == 1
            and any(values.dtype == d for d in _SEGSCAN_PALLAS_DTYPES)
            and _segscan_pallas_ok()):
        try:
            return segmented_scan_pallas(values, is_start, op_name)
        except Exception as e:  # eager-path failure: never fail the query
            _sortwin_pallas_broken = True
            _note_sortwin_pallas_fallback(e)
    return segmented_scan_xla(values, is_start, op_name)


# -- packed ("radix") sort keys ---------------------------------------------
#
# sortable_keys() emits one word per ordering concern (data, null flag,
# NaN class, padding), so a single-column ORDER BY already costs 2-3 sort
# operands and multi-column sorts overflow the variadic-sort budget into
# the chained LSD fallback. But most words are nearly empty: null flags
# are 1 bit, NaN classes 2 bits, SHORT/BYTE keys 16/8 bits. The radix
# plan normalizes every key word to an unsigned field of known bit width
# and greedily packs adjacent (in significance order) fields into u32
# words — the same total order in strictly fewer sort passes. Packing is
# order-preserving by construction, so the packed sort is bit-identical
# to the lexsort path (autotune may flip between them freely).


def _radix_widths(dtype, str_words: int = 2) -> Optional[List[int]]:
    """Field bit widths (least-significant first, null field included) for
    one sort column, or None when the dtype's keys cannot be bounded
    (DOUBLE sorts on f64 values — no device bit encoding exists)."""
    if dtype == T.BOOLEAN:
        return [2]                      # null folds into the data field
    if dtype == T.BYTE:
        return [8, 1]
    if dtype == T.SHORT:
        return [16, 1]
    if dtype in (T.INT, T.DATE):
        return [32, 1]
    if dtype in (T.LONG, T.TIMESTAMP):
        return [32, 32, 1]
    if dtype == T.FLOAT:
        return [32, 2]                  # value bits + NaN/null class
    if isinstance(dtype, T.DecimalType):
        if dtype.precision <= T.DecimalType.MAX_LONG_DIGITS:
            return [32, 32, 1]
        return [32, 32, 32, 32, 1]
    return None  # DOUBLE (f64 values), STRING/BINARY (dict-dynamic), nested


def radix_plan(dtypes: Sequence, specs) -> Optional[Tuple[int, int]]:
    """(flat_words, packed_words) the two sort paths would use for these
    key columns (padding word included), or None when any key column is
    radix-ineligible. Host-side and static: dtypes only."""
    fields: List[int] = []
    for spec in reversed(list(specs)):
        w = _radix_widths(dtypes[spec.column],
                          getattr(spec, "str_words", 2))
        if w is None:
            return None
        fields.extend(w)
    fields.append(1)  # the padding-last word sort_indices appends
    # one lexsort operand per field: sortable_keys emits exactly one word
    # per ordering concern for every radix-eligible dtype
    flat = len(fields)
    packed = 0
    used = 33
    for w in fields:
        if used + w > 32:
            packed += 1
            used = w
        else:
            used += w
    return flat, packed


def _radix_fields(col: DeviceColumn, ascending: bool,
                  nulls_first: Optional[bool]
                  ) -> List[Tuple[jax.Array, int]]:
    """(unsigned u32 field, bit width) list, least-significant first,
    matching ``_radix_widths`` and ordering EXACTLY like the
    ``sortable_keys`` words for the same column (ties included)."""
    if nulls_first is None:
        nulls_first = ascending
    dt = col.dtype
    valid = col.validity

    def null_field():
        nk = jnp.where(valid, jnp.uint32(1), jnp.uint32(0))
        return (jnp.uint32(1) - nk if not nulls_first else nk, 1)

    if dt == T.BOOLEAN:
        k = col.data.astype(jnp.int32)
        if not ascending:
            k = 1 - k
        null_v = jnp.int32(-1) if nulls_first else jnp.int32(2)
        k = jnp.where(valid, k, null_v)
        return [((k + 1).astype(jnp.uint32), 2)]
    if dt in (T.BYTE, T.SHORT):
        bias = 1 << (7 if dt == T.BYTE else 15)
        d = col.data.astype(jnp.int32)
        k = (d + bias) if ascending else (bias - 1 - d)
        k = jnp.where(valid, k, 0).astype(jnp.uint32)
        return [(k, 16 if dt == T.SHORT else 8), null_field()]
    if dt in (T.INT, T.DATE):
        k32 = jax.lax.bitcast_convert_type(
            col.data.astype(jnp.int32), jnp.uint32) ^ jnp.uint32(1 << 31)
        if not ascending:
            k32 = ~k32
        k32 = jnp.where(valid, k32, jnp.uint32(0))
        return [(k32, 32), null_field()]
    if dt == T.FLOAT:
        d, is_nan = _float_canonical(col.data)
        d32 = d.astype(jnp.float32)
        bits = jax.lax.bitcast_convert_type(d32, jnp.uint32)
        neg = (bits >> 31) != 0
        ordered = bits ^ jnp.where(neg, jnp.uint32(0xFFFFFFFF),
                                   jnp.uint32(1 << 31))
        ex = jnp.where(is_nan, jnp.int32(2), jnp.int32(1))
        if not ascending:
            ordered = ~ordered
            ex = 3 - ex
        ex = jnp.where(valid, ex,
                       jnp.int32(0) if nulls_first else jnp.int32(3))
        ordered = jnp.where(valid & ~is_nan, ordered, jnp.uint32(0))
        return [(ordered, 32), (ex.astype(jnp.uint32), 2)]
    if col.is_wide_decimal:
        from spark_rapids_tpu.exec import int128 as I128
        kh, kl = I128.sortable_keys(col.data2, col.data)
        words = [kl, kh]
        if not ascending:
            words = [~w for w in words]
        words = [jnp.where(valid, w, jnp.zeros_like(w)) for w in words]
        out: List[Tuple[jax.Array, int]] = []
        for w in words:
            lo, hi = _split_u64(w)
            out.extend([(lo, 32), (hi, 32)])
        out.append(null_field())
        return out
    # LONG / TIMESTAMP / DECIMAL64: the u64 bijection, split to u32 lanes
    k = _int_sortable(col.data)
    if not ascending:
        k = ~k
    k = jnp.where(valid, k, jnp.zeros_like(k))
    lo, hi = _split_u64(k)
    return [(lo, 32), (hi, 32), null_field()]


def packed_sort_keys(batch: ColumnarBatch,
                     specs) -> Optional[List[jax.Array]]:
    """u32 sort operands for the packed radix path (padding field
    included), least-significant first — ``lexsort_chain`` input. None
    when any key column is radix-ineligible (callers keep the lexsort
    path; ``radix_plan`` pre-checks this statically)."""
    fields: List[Tuple[jax.Array, int]] = []
    for spec in reversed(list(specs)):
        col = batch.columns[spec.column]
        if _radix_widths(col.dtype, getattr(spec, "str_words", 2)) is None:
            return None
        fields.extend(_radix_fields(col, spec.ascending, spec.nulls_first))
    pad = jnp.where(batch.active_mask(), jnp.uint32(0), jnp.uint32(1))
    fields.append((pad, 1))
    words: List[jax.Array] = []
    cur = None
    used = 0
    for w, bits in fields:
        w = w.astype(jnp.uint32)
        if cur is None or used + bits > 32:
            if cur is not None:
                words.append(cur)
            cur, used = w, bits
        else:
            cur = cur | (w << jnp.uint32(used))
            used += bits
    words.append(cur)
    return words


# -- merge-path out-of-core merge --------------------------------------------


_MERGE_PAD = np.uint64(0xFFFFFFFFFFFFFFFF)


def merge_key_bits(dtype) -> Optional[int]:
    """Total key bits when this dtype's full sort key (null ordering
    included) packs into ONE u64 word — the merge-path eligibility test.
    The padding sentinel (all-ones) must stay unreachable, so 64-bit
    data keys (LONG/TIMESTAMP/decimal) are excluded."""
    widths = _radix_widths(dtype)
    if widths is None:
        return None
    bits = sum(widths)
    return bits if bits < 64 else None


def merge_key_u64(col: DeviceColumn, ascending: bool,
                  nulls_first: Optional[bool],
                  active: jax.Array) -> jax.Array:
    """One u64 key per row whose ascending order IS the column's full
    sort order (``sortable_keys`` ties included); padding rows get the
    unreachable all-ones sentinel so they sort past every live row."""
    fields = _radix_fields(col, ascending, nulls_first)
    key = jnp.zeros(col.validity.shape[0], jnp.uint64)
    shift = 0
    for w, bits in fields:
        key = key | (w.astype(jnp.uint64) << jnp.uint64(shift))
        shift += bits
    assert shift < 64, "merge key overflows one word; caller gates on " \
                       "merge_key_bits"
    return jnp.where(active, key, _MERGE_PAD)


def merge_piece_positions(keys: Sequence[jax.Array]) -> List[jax.Array]:
    """Merged-order position of every row of every presorted piece.

    The merge-path formulation: a row's global rank is its local index
    plus, per other piece, a binary-search count of that piece's rows
    ordered before it — ``side`` breaks cross-piece ties by piece index,
    matching what a stable sort of the concatenation would do, so the
    merge is bit-identical to the re-sort it replaces. O(k^2 log n)
    searchsorted lanes, no data movement until the final gather."""
    out: List[jax.Array] = []
    for p, kp in enumerate(keys):
        pos = jnp.arange(kp.shape[0], dtype=jnp.int32)
        for q, kq in enumerate(keys):
            if q == p:
                continue
            side = "right" if q < p else "left"
            pos = pos + jnp.searchsorted(kq, kp, side=side).astype(jnp.int32)
        out.append(pos)
    return out
