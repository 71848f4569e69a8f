"""TPU-resident columnar batches + Arrow interop.

The batch is the unit of work flowing between operators, replacing the
reference's ``ColumnarBatch`` of ``GpuColumnVector`` (reference:
GpuColumnVector.java:40; transitions in GpuRowToColumnarExec.scala /
HostColumnarToGpu.scala). TPU-first differences:

- batches are pytrees of statically-shaped jnp arrays; ``num_rows`` is a
  traced int32 scalar so one compiled kernel serves every batch in the same
  capacity bucket;
- host<->device moves are whole-buffer ``jax.device_put`` / ``np.asarray``
  against Arrow buffers (zero copy on host side where possible).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.column import (
    DeviceColumn,
    make_fixed_column,
    make_string_column,
)


def bucket_capacity(n: int, min_bucket: int = 1024) -> int:
    """Round a row count up to the next power-of-two bucket (compile-cache
    friendly: capacity is a static shape)."""
    cap = max(int(min_bucket), 1)
    while cap < n:
        cap <<= 1
    return cap


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class ColumnarBatch:
    """A TPU-resident batch: columns + live row count.

    ``num_rows`` is a jnp int32 scalar (traced); ``capacity`` is static.
    """

    columns: List[DeviceColumn]
    num_rows: jax.Array  # int32 scalar

    def tree_flatten(self):
        return (self.columns, self.num_rows), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        columns, num_rows = children
        return cls(list(columns), num_rows)

    @property
    def capacity(self) -> int:
        if not self.columns:
            return 0
        return self.columns[0].capacity

    @property
    def num_cols(self) -> int:
        return len(self.columns)

    def dtypes(self) -> List[T.DataType]:
        return [c.dtype for c in self.columns]

    def row_count(self) -> int:
        """Host-side row count (blocks on device value)."""
        from spark_rapids_tpu.utils.sync import host_get
        return int(host_get(self.num_rows, "row_count"))

    def active_mask(self) -> jax.Array:
        """Boolean mask of live rows (True for i < num_rows)."""
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.num_rows

    def nbytes(self) -> int:
        return sum(c.nbytes() for c in self.columns)

    def column(self, i: int) -> DeviceColumn:
        return self.columns[i]


def empty_batch(dtypes: Sequence[T.DataType], capacity: int = 1024) -> ColumnarBatch:
    cols = []
    for dt in dtypes:
        if (isinstance(dt, T.DecimalType)
                and dt.precision > T.DecimalType.MAX_LONG_DIGITS):
            z = jnp.zeros(capacity, jnp.int64)
            cols.append(DeviceColumn(dt, z, jnp.zeros(capacity, jnp.bool_),
                                     data2=z))
        elif dt.fixed_width:
            cols.append(
                make_fixed_column(dt, np.zeros(0, T.numpy_dtype(dt)), None, capacity)
            )
        else:
            cols.append(
                make_string_column(
                    np.zeros(0, np.uint8), np.zeros(1, np.int32), None, capacity, 8, dt
                )
            )
    return ColumnarBatch(cols, jnp.int32(0))


def _wide_decimal_from_arrow(arr: pa.Array, dt: T.DecimalType, cap: int,
                             n: int) -> DeviceColumn:
    """arrow decimal128 -> two-limb (hi, lo) int64 device column
    (exec/int128.py representation)."""
    arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    valid = (None if arr.null_count == 0
             else np.asarray(arr.is_valid(), dtype=np.bool_))
    limbs = np.frombuffer(arr.buffers()[1], dtype=np.int64,
                          count=2 * len(arr), offset=arr.offset * 16)
    lo = np.zeros(cap, np.int64)
    hi = np.zeros(cap, np.int64)
    lo[:n] = limbs[0::2]
    hi[:n] = limbs[1::2]
    validity = np.zeros(cap, np.bool_)
    validity[:n] = True if valid is None else valid
    lo[~validity] = 0
    hi[~validity] = 0
    return DeviceColumn(dt, jnp.asarray(lo), jnp.asarray(validity),
                        data2=jnp.asarray(hi))


def _arrow_fixed_to_numpy(arr: pa.Array, dt: T.DataType):
    """Extract (values, valid) numpy arrays from a fixed-width arrow array."""
    np_dtype = T.numpy_dtype(dt)
    arr = arr.combine_chunks() if isinstance(arr, pa.ChunkedArray) else arr
    valid = (
        None
        if arr.null_count == 0
        else np.asarray(arr.is_valid(), dtype=np.bool_)
    )
    if isinstance(dt, T.DecimalType):
        # arrow decimal128 -> int64 unscaled: the 16-byte little-endian value's
        # low limb is the full value for p<=18 (|v| < 2^63).
        limbs = np.frombuffer(
            arr.buffers()[1], dtype=np.int64, count=2 * len(arr),
            offset=arr.offset * 16,
        )
        values = limbs[0::2].copy()
    elif dt == T.TIMESTAMP:
        # normalize any timestamp unit (s/ms/us/ns) to microseconds first
        if arr.type.unit != "us":
            arr = arr.cast(pa.timestamp("us", tz=arr.type.tz))
        values = np.asarray(arr.fill_null(0).cast(pa.int64()))
    elif dt == T.DATE:
        values = np.asarray(arr.fill_null(0).cast(pa.int32()))
    elif dt == T.BOOLEAN:
        values = np.asarray(arr.fill_null(False).cast(pa.int8())).astype(np.bool_)
    else:
        values = np.asarray(arr.fill_null(0)).astype(np_dtype, copy=False)
    if valid is not None:
        values = np.where(valid, values, np.zeros((), np_dtype))
    return values, valid


def _sort_remap_dictionary(enc: pa.DictionaryArray) -> pa.DictionaryArray:
    """Sort a DictionaryArray's dictionary bytewise and remap its codes.

    Device kernels require code order == byte-lexicographic order; this is
    the single implementation both the table-level encoder and the direct
    ingest path use (a no-op when already sorted)."""
    import pyarrow.compute as pc

    dvals = enc.dictionary
    order = pc.sort_indices(dvals)  # bytewise (UTF-8) ascending
    rank = np.empty(len(dvals), np.int32)
    rank[np.asarray(order)] = np.arange(len(dvals), dtype=np.int32)
    codes = np.asarray(enc.indices.fill_null(0)).astype(np.int32)
    new_codes = pa.array(rank[codes], pa.int32(),
                         mask=~np.asarray(enc.is_valid()))
    return pa.DictionaryArray.from_arrays(new_codes, dvals.take(order))


def _dict_bytes_encodable(dvals, n_rows: int) -> bool:
    """Worst-case decode (n_rows * longest entry) must fit int32 offsets."""
    if len(dvals) == 0:
        return False
    lens = np.diff(np.frombuffer(dvals.buffers()[1], np.int32,
                                 count=len(dvals) + 1,
                                 offset=dvals.offset * 4))
    dmax = int(lens.max()) if len(lens) else 0
    return max(n_rows, 1024) * max(dmax, 1) < (1 << 31)


def dictionary_encode_table(table: pa.Table, columns: Optional[Sequence[str]] = None,
                            max_size: int = 1 << 16) -> pa.Table:
    """Dictionary-encode eligible string/binary columns with a SORTED dict.

    TPU-first ingest step: encoding happens once on the host; every device
    batch sliced from the returned table shares one dictionary, so codes are
    comparable across batches and code order == byte-lexicographic order
    (the engine sorts/groups strings on int32 codes). Columns whose distinct
    count exceeds ``max_size`` (or half the rows) stay plain.
    """
    out = table
    for i, name in enumerate(table.column_names):
        if columns is not None and name not in columns:
            continue
        col = table.column(i).combine_chunks()
        if isinstance(col, pa.ChunkedArray):
            col = col.combine_chunks()
        if pa.types.is_dictionary(col.type):
            if not (pa.types.is_string(col.type.value_type)
                    or pa.types.is_binary(col.type.value_type)):
                continue  # non-string dictionaries decode at batch build
            enc = col  # re-sort a user-provided dictionary below
        elif pa.types.is_string(col.type) or pa.types.is_binary(col.type):
            enc = col.dictionary_encode()
            if isinstance(enc, pa.ChunkedArray):
                enc = enc.combine_chunks()
        else:
            continue
        dvals = enc.dictionary.cast(
            pa.string() if pa.types.is_string(enc.type.value_type)
            else pa.binary())
        if len(dvals) == 0:
            continue  # all-null column: keep plain (no dictionary to sort)
        if not _dict_bytes_encodable(dvals, len(col)):
            continue
        if not pa.types.is_dictionary(col.type) and (
                len(dvals) > max_size or len(dvals) > max(16, len(col) // 2)):
            continue
        out = out.set_column(i, name, _sort_remap_dictionary(enc))
    return out


def _dict_col_from_arrow(arr: pa.DictionaryArray, dt: T.DataType, cap: int,
                         n: int, dict_cache: Optional[dict]) -> DeviceColumn:
    """Device dict column from an arrow DictionaryArray with a sorted dict.

    ``dict_cache`` (optional, caller-held) maps the arrow dictionary's buffer
    address to an uploaded device dictionary so batches sliced from one table
    share one device dictionary (object identity is what concat/merge check).
    """
    dvals = arr.dictionary
    dvals = dvals.cast(pa.string()) if dt == T.STRING else dvals.cast(pa.binary())
    if len(dvals) == 0:
        # all-null dictionary column: no dictionary to sort — plain layout
        n_ = len(arr)
        return make_string_column(np.zeros(0, np.uint8),
                                  np.zeros(n_ + 1, np.int32),
                                  np.zeros(n_, np.bool_), cap, 8, dt)
    import pyarrow.compute as pc

    order = np.asarray(pc.sort_indices(dvals))
    if not np.array_equal(order, np.arange(len(dvals))):
        # keep the original array identity when already sorted: the device
        # dictionary cache below is keyed by the dict buffer address, and
        # batches sliced from one table must share one device dictionary
        arr = _sort_remap_dictionary(
            pa.DictionaryArray.from_arrays(arr.indices, dvals))
        dvals = arr.dictionary
    key = dvals.buffers()[2].address if dvals.buffers()[2] is not None else 0
    dict_col = dict_cache.get(key) if dict_cache is not None else None
    if dict_col is None:
        dsize = len(dvals)
        raw_off = np.frombuffer(dvals.buffers()[1], np.int32,
                                count=dsize + 1, offset=dvals.offset * 4)
        offsets = (raw_off - raw_off[0]).astype(np.int32)
        lens = np.diff(offsets)
        dmax = int(lens.max()) if len(lens) else 0
        dcap = bucket_capacity(max(dsize, 1), 16)
        nbytes = int(offsets[-1])
        buf = dvals.buffers()[2]
        data = (np.frombuffer(buf, np.uint8, count=nbytes,
                              offset=int(raw_off[0])).copy()
                if buf is not None and nbytes else np.zeros(0, np.uint8))
        plain = make_string_column(data, offsets, None, dcap,
                                   bucket_capacity(max(nbytes, 8), 8), dt)
        dict_col = (plain, dsize, dmax)
        if dict_cache is not None:
            dict_cache[key] = dict_col
    plain, dsize, dmax = dict_col
    valid = (None if arr.null_count == 0
             else np.asarray(arr.is_valid(), dtype=np.bool_))
    codes = np.zeros(cap, np.int32)
    codes[:n] = np.asarray(arr.indices.fill_null(0)).astype(np.int32)
    validity = np.zeros(cap, np.bool_)
    validity[:n] = True if valid is None else valid
    codes[~validity] = 0
    return DeviceColumn(dt, jnp.asarray(codes), jnp.asarray(validity),
                        None, plain, dsize, dmax)


def _column_from_arrow(arr: pa.Array, dt: T.DataType, cap: int, n: int,
                       dict_cache: Optional[dict]) -> DeviceColumn:
    """One arrow array -> device column (recursive for struct/map)."""
    if isinstance(dt, T.StructType):
        valid = (None if arr.null_count == 0
                 else np.asarray(arr.is_valid(), dtype=np.bool_))
        validity = np.zeros(cap, np.bool_)
        validity[:n] = True if valid is None else valid
        kids = []
        for i, f in enumerate(dt.fields):
            child = arr.field(i)
            if isinstance(child, pa.ChunkedArray):
                child = child.combine_chunks()
            kids.append(_column_from_arrow(child, f.dtype, cap, n,
                                           dict_cache))
        return DeviceColumn(dt, jnp.zeros(0, jnp.int32),
                            jnp.asarray(validity), children=tuple(kids))
    if isinstance(dt, T.MapType):
        valid = (None if arr.null_count == 0
                 else np.asarray(arr.is_valid(), dtype=np.bool_))
        raw_off = np.asarray(arr.offsets, dtype=np.int32)
        offsets = raw_off - raw_off[0]
        n_entries = int(offsets[-1]) if n else 0
        keys = arr.keys.slice(int(raw_off[0]), n_entries)
        items = arr.items.slice(int(raw_off[0]), n_entries)
        ecap = bucket_capacity(max(n_entries, 8), 8)
        kcol = _column_from_arrow(keys, dt.key, ecap, n_entries, dict_cache)
        vcol = _column_from_arrow(items, dt.value, ecap, n_entries,
                                  dict_cache)
        off = np.full(cap + 1, offsets[-1] if n else 0, dtype=np.int32)
        off[: n + 1] = offsets
        validity = np.zeros(cap, np.bool_)
        validity[:n] = True if valid is None else valid
        return DeviceColumn(dt, jnp.zeros(0, jnp.int32),
                            jnp.asarray(validity), jnp.asarray(off),
                            children=(kcol, vcol))
    # scalar types: reuse the table-level paths via a one-column table
    tmp = pa.table({"c": arr})
    b = batch_from_arrow(tmp, capacity=cap, dict_cache=dict_cache)
    return b.columns[0]


def batch_from_arrow(
    table, min_bucket: int = 1024, capacity: Optional[int] = None,
    dict_cache: Optional[dict] = None,
) -> ColumnarBatch:
    """Host Arrow table/record-batch -> padded device batch.

    Dictionary-typed columns (see ``dictionary_encode_table``) become
    dict-encoded device columns; pass one ``dict_cache`` across calls so
    slices of the same table share one device dictionary.
    """
    if isinstance(table, pa.RecordBatch):
        table = pa.table(table)
    n = table.num_rows
    cap = capacity if capacity is not None else bucket_capacity(n, min_bucket)
    cols: List[DeviceColumn] = []
    for name in table.column_names:
        arr = table.column(name).combine_chunks()
        dt = T.from_arrow_type(arr.type)
        if isinstance(arr.type, pa.DictionaryType):
            vt = arr.type.value_type
            is_str = pa.types.is_string(vt) or pa.types.is_binary(vt)
            ok = is_str and (
                len(arr.dictionary) == 0  # all-null: plain fallback inside
                or _dict_bytes_encodable(
                    arr.dictionary.cast(
                        pa.string() if pa.types.is_string(vt)
                        else pa.binary()), cap))
            if ok:
                cols.append(_dict_col_from_arrow(arr, dt, cap, n, dict_cache))
                continue
            # non-string dictionary values (or entries so long the decoded
            # worst case would overflow int32 offsets): plain layout
            arr = arr.cast(vt)
        if isinstance(dt, (T.StructType, T.MapType)):
            cols.append(_column_from_arrow(arr, dt, cap, n, dict_cache))
        elif (isinstance(dt, T.DecimalType)
                and dt.precision > T.DecimalType.MAX_LONG_DIGITS):
            cols.append(_wide_decimal_from_arrow(arr, dt, cap, n))
        elif dt.fixed_width:
            values, valid = _arrow_fixed_to_numpy(arr, dt)
            cols.append(make_fixed_column(dt, values, valid, cap))
        elif isinstance(dt, T.ArrayType):
            valid = (None if arr.null_count == 0
                     else np.asarray(arr.is_valid(), dtype=np.bool_))
            raw_off = np.asarray(arr.offsets, dtype=np.int32)
            offsets = raw_off - raw_off[0]
            # arr.values (not flatten()): keeps elements spanned by null
            # slots, so offsets and the element buffer stay aligned even for
            # non-canonical Arrow producers
            flat = arr.values.slice(int(raw_off[0]),
                                    int(raw_off[-1] - raw_off[0]))
            assert flat.null_count == 0, (
                "element nulls in arrays not device-supported (CPU fallback)")
            evalues, _ = _arrow_fixed_to_numpy(flat, dt.element)
            ecap = bucket_capacity(max(len(evalues), 8), 8)
            edata = np.zeros(ecap, dtype=T.numpy_dtype(dt.element))
            edata[: len(evalues)] = evalues
            off = np.full(cap + 1, offsets[-1], dtype=np.int32)
            off[: n + 1] = offsets
            validity = np.zeros(cap, dtype=np.bool_)
            validity[:n] = True if valid is None else valid
            cols.append(DeviceColumn(dt, jnp.asarray(edata),
                                     jnp.asarray(validity), jnp.asarray(off)))
        else:
            sarr = arr.cast(pa.string()) if dt == T.STRING else arr.cast(pa.binary())
            valid = (
                None
                if sarr.null_count == 0
                else np.asarray(sarr.is_valid(), dtype=np.bool_)
            )
            # arrow string arrays: buffers()[1] = offsets, [2] = data
            offsets = np.frombuffer(sarr.buffers()[1], dtype=np.int32,
                                    count=n + 1, offset=sarr.offset * 4).copy()
            offsets -= offsets[0]
            databuf = sarr.buffers()[2]
            nbytes = int(offsets[-1])
            if databuf is None:
                data = np.zeros(0, np.uint8)
            else:
                start = np.frombuffer(sarr.buffers()[1], dtype=np.int32,
                                      count=1, offset=sarr.offset * 4)[0]
                data = np.frombuffer(databuf, dtype=np.uint8,
                                     count=nbytes, offset=int(start)).copy()
            byte_cap = bucket_capacity(max(nbytes, 8), 8)
            cols.append(
                make_string_column(data, offsets, valid, cap, byte_cap, dt)
            )
    return ColumnarBatch(cols, jnp.int32(n))


from functools import partial as _partial


def _shrink_col(c: DeviceColumn, newcap: int, bc: int) -> DeviceColumn:
    if c.children is not None:
        # struct/map: slice the ROW-space arrays only; children keep their
        # element/byte buffers (offsets still index into them correctly)
        kids = tuple(ck if ck.capacity <= newcap
                     else _shrink_col(ck, newcap, 0)
                     for ck in c.children) if c.offsets is None else c.children
        return DeviceColumn(
            c.dtype, c.data, c.validity[:newcap],
            c.offsets[: newcap + 1] if c.offsets is not None else None,
            children=kids)
    if c.offsets is not None:
        return DeviceColumn(c.dtype, c.data[:bc] if bc else c.data,
                            c.validity[:newcap], c.offsets[: newcap + 1])
    d2 = c.data2[:newcap] if c.data2 is not None else None
    return DeviceColumn(c.dtype, c.data[:newcap], c.validity[:newcap], None,
                        c.dictionary, c.dict_size, c.dict_max_len, d2)


@_partial(jax.jit, static_argnums=(1, 2))
def _shrink_slice(batch: ColumnarBatch, newcap: int, byte_caps):
    cols = [_shrink_col(c, newcap, bc)
            for c, bc in zip(batch.columns, byte_caps)]
    return ColumnarBatch(cols, batch.num_rows)


def shrink_to_live(batch: ColumnarBatch, min_capacity: int = 1 << 20
                   ) -> ColumnarBatch:
    """Re-bucket a front-packed batch DOWN to the live row count's bucket.

    Static shapes mean every downstream kernel pays for the full capacity:
    a filter/join/agg output holding 1M live rows in a 16M-capacity batch
    makes every later gather/sort/scan 16x more expensive than needed
    (device cost scales with capacity). The shrink is
    ONE host sync of (row count + string byte counts) and contiguous
    slices; only applied when at least half the capacity would be saved
    and the batch is big enough for the sync to pay for itself.

    Reference analog: GpuCoalesceBatches' goal-driven re-batching
    (GpuCoalesceBatches.scala:160) — sizing batches to what the data
    needs, not what the worst case allowed.
    """
    cap = batch.capacity
    if cap < min_capacity or not batch.columns:
        return batch
    scalars = [batch.num_rows]
    for c in batch.columns:
        if c.offsets is not None:
            scalars.append(c.offsets[jnp.clip(batch.num_rows, 0, cap)])
    from spark_rapids_tpu.utils.sync import host_get
    vals = host_get(scalars, "shrink_to_live")
    n = int(vals[0])
    newcap = bucket_capacity(max(n, 1024))
    if newcap * 2 > cap:
        return batch
    byte_caps = []
    k = 1
    for c in batch.columns:
        if c.offsets is not None:
            byte_caps.append(bucket_capacity(max(int(vals[k]), 8), 8))
            k += 1
        else:
            byte_caps.append(0)
    return _shrink_slice(batch, newcap, tuple(byte_caps))


def batch_to_arrow(batch: ColumnarBatch, schema: T.Schema) -> pa.Table:
    """Device batch -> host Arrow table (slices away padding)."""
    # pull the row count and every device buffer in ONE batched transfer:
    # per-array readbacks pay a round trip each (utils/sync.py; cost not
    # measured on the current machine)
    from spark_rapids_tpu.utils.sync import host_get
    n, host = host_get((batch.num_rows, batch.columns), "batch_to_arrow")
    n = int(n)
    arrays = [_host_column_to_arrow(col, field.dtype, n)
              for col, field in zip(host, schema)]
    return pa.table(arrays, schema=schema.to_arrow())


def _host_column_to_arrow(col, dt: T.DataType, n: int) -> pa.Array:
    """One host-leaf device column -> arrow array (recursive for nested)."""
    valid_np = np.asarray(col.validity)[:n]
    mask = None if valid_np.all() else ~valid_np
    if isinstance(dt, T.StructType):
        kids = [_host_column_to_arrow(c, f.dtype, n)
                for c, f in zip(col.children, dt.fields)]
        arr = pa.StructArray.from_arrays(
            kids, fields=[pa.field(f.name, f.dtype.arrow_type(),
                                   f.nullable) for f in dt.fields],
            mask=(pa.array(mask) if mask is not None else None))
        return arr
    if isinstance(dt, T.MapType):
        offsets = np.asarray(col.offsets)[: n + 1].astype(np.int32)
        ne = int(offsets[-1]) if n else 0
        keys = _host_column_to_arrow(col.children[0], dt.key, ne)
        items = _host_column_to_arrow(col.children[1], dt.value, ne)
        off_arr = pa.array(offsets, pa.int32(), mask=(
            np.concatenate([mask, [False]]) if mask is not None
            else None))
        return pa.MapArray.from_arrays(off_arr, keys, items)
    if col.is_dict:
        codes = np.asarray(col.data)[:n].astype(np.int32)
        d = col.dictionary
        doff = np.asarray(d.offsets)[: col.dict_size + 1].astype(np.int32)
        dbytes = np.asarray(d.data)[: int(doff[-1]) if col.dict_size else 0]
        dvals = pa.Array.from_buffers(
            pa.string() if dt == T.STRING else pa.binary(),
            col.dict_size,
            [None, pa.py_buffer(doff.tobytes()),
             pa.py_buffer(dbytes.tobytes())],
        )
        codes_arr = pa.array(codes, pa.int32(), mask=mask)
        return pa.DictionaryArray.from_arrays(codes_arr, dvals).cast(
            pa.string() if dt == T.STRING else pa.binary())
    if col.is_wide_decimal:
        from spark_rapids_tpu.exec import int128 as I128
        import decimal as _d

        lo = np.asarray(col.data)[:n]
        hi = np.asarray(col.data2)[:n]
        ints = I128.to_py_ints(hi, lo)  # already signed (hi is signed)
        with _d.localcontext() as _c:
            _c.prec = 50
            pyvals = [
                None if (mask is not None and mask[i]) else
                _d.Decimal(v).scaleb(-dt.scale)
                for i, v in enumerate(ints)
            ]
        return pa.array(pyvals, type=dt.arrow_type())
    if dt.fixed_width:
        values = np.asarray(col.data)[:n]
        if isinstance(dt, T.DecimalType):
            import decimal as _d

            with _d.localcontext() as _c:
                _c.prec = 50
                pyvals = [
                    None if (mask is not None and mask[i]) else
                    _d.Decimal(int(values[i])).scaleb(-dt.scale)
                    for i in range(n)
                ]
            arr = pa.array(pyvals, type=dt.arrow_type())
        elif dt == T.DATE:
            arr = pa.array(values.astype(np.int32), type=pa.int32(), mask=mask)
            arr = arr.cast(pa.date32())
        elif dt == T.TIMESTAMP:
            arr = pa.array(values.astype(np.int64), type=pa.int64(), mask=mask)
            arr = arr.cast(pa.timestamp("us", tz="UTC"))
        else:
            arr = pa.array(values, type=dt.arrow_type(), mask=mask)
    elif isinstance(dt, T.ArrayType):
        offsets = np.asarray(col.offsets)[: n + 1].astype(np.int32)
        flat = np.asarray(col.data)[: int(offsets[-1]) if n else 0]
        values = pa.array(flat, type=dt.element.arrow_type())
        arr = pa.ListArray.from_arrays(
            pa.array(offsets, pa.int32()), values)
        if mask is not None:
            # from_arrays has no mask param: rebuild with a validity buffer
            arr = pa.Array.from_buffers(
                dt.arrow_type(), n,
                [_validity_buffer(valid_np),
                 pa.py_buffer(offsets.tobytes())],
                children=[values])
    else:
        offsets = np.asarray(col.offsets)[: n + 1]
        data = np.asarray(col.data)[: int(offsets[-1]) if n else 0]
        arr = pa.Array.from_buffers(
            pa.string() if dt == T.STRING else pa.binary(),
            n,
            [
                _validity_buffer(valid_np) if mask is not None else None,
                pa.py_buffer(offsets.astype(np.int32).tobytes()),
                pa.py_buffer(data.tobytes()),
            ],
        )
    return arr


def _validity_buffer(valid: np.ndarray) -> pa.Buffer:
    return pa.py_buffer(np.packbits(valid, bitorder="little").tobytes())


def concat_batches(
    batches: Sequence[ColumnarBatch], schema: T.Schema, min_bucket: int = 1024
) -> ColumnarBatch:
    """Concatenate device batches (host-coordinated; used by coalesce).

    Mirrors the reference's GpuCoalesceBatches concat (GpuCoalesceBatches.scala:160)
    but implemented as an Arrow-level host concat + single upload when sizes
    are heterogeneous, matching the GpuShuffleCoalesceExec pattern of one
    upload per coalesced output (GpuShuffleCoalesceExec.scala:49).
    """
    if len(batches) == 1:
        return batches[0]
    tables = [batch_to_arrow(b, schema) for b in batches]
    return batch_from_arrow(pa.concat_tables(tables), min_bucket)
