"""Broadcast joins, nested-loop/cartesian joins, and sub-partition joins.

Reference surface being rebuilt (SURVEY.md §2.4):
- GpuBroadcastHashJoinExecBase — build side broadcast once, probed per
  partition (GpuBroadcastHashJoinExecBase / GpuBroadcastExchangeExec.scala:354).
- GpuBroadcastNestedLoopJoinExecBase + GpuCartesianProductExec — all-pairs
  joins with an optional residual condition; the reference compiles the
  condition through cudf AST (GpuExpressions.scala:197), here it is the same
  fused XLA expression engine used by the hash join.
- GpuSubPartitionHashJoin — oversized-key sub-partitioning: both sides are
  hash-partitioned into disjoint buckets and joined bucket-by-bucket so the
  build side of each sub-join fits in HBM.

TPU-first notes: the pair space of a nested-loop join is enumerated in
static-shaped (probe x build-chunk) tiles so every step is one fused XLA
computation; candidate counts are pulled to host only to choose a bucketed
output capacity, exactly like the hash join.
"""

from __future__ import annotations

import threading
from functools import partial
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import (
    ColumnarBatch, bucket_capacity, empty_batch,
)
from spark_rapids_tpu.exec.base import BatchSourceExec, BinaryExec, TpuExec
from spark_rapids_tpu.exec import kernels as K
from spark_rapids_tpu.exec.aggregate import concat_jit
from spark_rapids_tpu.exec.join import (
    HashJoinExec, _null_column, _pad_idx, build_span)
from spark_rapids_tpu.exprs import expr as E
from spark_rapids_tpu.exprs import eval as EV
from spark_rapids_tpu.utils.sync import host_get


class BroadcastHashJoinExec(HashJoinExec):
    """Hash join whose build (right) side is broadcast: executed once across
    ALL its partitions and reused by every probe partition.

    Mirrors GpuBroadcastHashJoinExecBase: the reference broadcasts
    host-serialized build batches and uploads once per task
    (GpuBroadcastExchangeExec.scala:354,469); in-process the equivalent is
    building the join hashes once and sharing the device-resident build.
    Join types follow the reference's broadcast restrictions (no right/full
    with a broadcast build side).
    """

    mem_site = "broadcast"

    BROADCAST_TYPES = ("inner", "left", "left_semi", "left_anti")

    def __init__(self, left_keys, right_keys, join_type, left, right,
                 condition=None):
        assert join_type in self.BROADCAST_TYPES, (
            f"broadcast build side does not support {join_type}")
        super().__init__(left_keys, right_keys, join_type, left, right,
                         condition)
        self._broadcast = None
        self._bcast_lock = threading.Lock()
        # set by plan/reuse.py when another join shares this build side: a
        # SharedBroadcast holder publishing one prepared (build, jh) pair
        self._shared_broadcast = None
        # (path, source, shape) picked when the broadcast was built —
        # consulted by do_execute when recording dispatch decisions
        self._bcast_decision = None
        self._register_metric("broadcastTimeNs")

    def num_partitions(self) -> int:
        return self.left.num_partitions()

    def _build_broadcast(self, probe_cap: int = 16):
        # locked: probe partitions run concurrently under parallel shuffle
        # writes / prefetch workers, and the build must execute exactly once
        self._prepare()
        with self._bcast_lock:
            if self._broadcast is None:
                from spark_rapids_tpu.plan import autotune as AT
                ls = self.left.output_schema
                shape = AT.shape_class(
                    probe_cap, len(self._lkeys),
                    AT.family_of(str(ls[i].dtype) for i in self._lkeys))
                # ht<->sorted re-ranking is order-safe only for the
                # semi/anti filters (probe-order output); plain inner/left
                # output order depends on the structure, so they stay on
                # the static precedence (see exec/join.py _choose_path)
                path, source = (("ht", "default") if self._hashtbl_enabled
                                else ("sorted", "default"))
                if path == "ht" and self.join_type in ("left_semi",
                                                       "left_anti"):
                    path, source = AT.choose(f"join:{self.join_type}",
                                             shape, "ht", ("ht", "sorted"))
                holder = self._shared_broadcast
                if holder is not None:
                    shared = holder.get()
                    if shared is not None:
                        # another join with the identical build side (same
                        # fingerprint + key ordinals) already concatenated
                        # and hashed it — adopt instead of rebuilding
                        from spark_rapids_tpu.exec import reuse as _reuse
                        _reuse.note("reuse_bytes_saved_total",
                                    int(shared[0].nbytes()))
                        self._broadcast = shared
                        self._bcast_decision = (
                            "ht" if shared[2] is not None else "sorted",
                            "default", shape)
                        return self._broadcast
                with build_span(self) as battrs, \
                        self.timer("broadcastTimeNs"):
                    batches = list(self.right.execute_all())
                    if batches:
                        build = (batches[0] if len(batches) == 1
                                 else concat_jit(batches))
                    else:
                        build = empty_batch(
                            self.right.output_schema.types(), 16)
                    # round 12: the broadcast build probes the device hash
                    # table; sorted hashes remain the conf-off / overflow
                    # fallback
                    ht = jh = None
                    if path == "ht":
                        ht = K.build_batch_hash_table(build,
                                                      tuple(self._rkeys))
                        if ht is None:
                            path, source = "sorted", "default"
                    if ht is None:
                        jh = jax.jit(K.prepare_join_side, static_argnums=1)(
                            build, tuple(self._rkeys))
                    battrs.update(path=path, capacity=build.capacity)
                self._broadcast = (build, jh, ht)
                self._bcast_decision = (path, source, shape)
                if holder is not None:
                    holder.put(self._broadcast)
            return self._broadcast

    def do_execute(self, partition: int) -> Iterator[ColumnarBatch]:
        self._prepare()
        # peek one probe batch so a cold broadcast build decides its probe
        # structure at the probe's shape-class (capacity is static: no sync)
        probe_iter = self.left.execute(partition)
        first = next(probe_iter, None)
        probe_cap = first.capacity if first is not None else 16
        build, jh, ht = self._build_broadcast(probe_cap)
        decision = self._bcast_decision or (
            "ht" if ht is not None else "sorted", "default", None)
        build_matched = jnp.zeros(build.capacity, jnp.bool_)
        join_ns0 = self.metrics["joinTimeNs"].value
        probe_rows = 0

        def _probes():
            if first is not None:
                yield first
                yield from probe_iter

        for probe in _probes():
            probe_rows += probe.capacity
            if ht is not None:
                with self.timer("joinTimeNs"):
                    handles, build_matched = self._join_batch_ht(
                        probe, build, ht, build_matched, partition)
                for hd in handles:
                    try:
                        yield hd.get()
                    finally:
                        hd.unpin()
                        hd.close()
                continue
            with self.timer("joinTimeNs"):
                out, build_matched = self._join_batch(probe, build, jh,
                                                      build_matched)
            if out is not None:
                yield out

        from spark_rapids_tpu.plan import autotune as AT
        path, source, shape = decision
        if shape is None:
            ls = self.left.output_schema
            shape = AT.shape_class(
                probe_cap, len(self._lkeys),
                AT.family_of(str(ls[i].dtype) for i in self._lkeys))
        AT.record_decision(
            self, f"join:{self.join_type}", path, source, shape,
            ns=self.metrics["joinTimeNs"].value - join_ns0,
            rows=probe_rows)

    def _fused_build_side(self, partition):
        # the broadcast build spans ALL build-side partitions — the
        # inherited partition-local materialization would silently drop
        # every match whose build row lives in another partition's slice
        build, _jh, _ht = self._build_broadcast()
        if not bool(host_get(build.num_rows > 0, "join.build_empty")):
            return None
        return build

    def fused_probe(self, partition: int):
        # build prep (dense table / bucketed table + the byte-bound syncs)
        # is partition-independent for a broadcast build: do it once
        seg = getattr(self, "_fused_seg", None)
        if seg is None:
            seg = self._fused_seg = (super().fused_probe(partition), )
        return seg[0]

    def node_description(self) -> str:
        return (f"TpuBroadcastHashJoin {self.join_type} "
                f"keys={list(zip(self.left_keys, self.right_keys))}")


NLJ_TYPES = ("inner", "cross", "left", "left_semi", "left_anti")


class BroadcastNestedLoopJoinExec(BinaryExec):
    """All-pairs join with an optional condition; build side = right,
    broadcast across probe partitions.

    Reference: GpuBroadcastNestedLoopJoinExecBase — the build side is
    materialized once; each probe batch is joined against the whole build
    side. Here the (probe x build) pair space is walked in static-shaped
    build chunks so each step is one compiled XLA computation; `cross` is
    `inner` with no condition (GpuCartesianProductExec shares this path).
    """

    mem_site = "broadcast"

    def __init__(self, join_type: str, left: TpuExec, right: TpuExec,
                 condition: Optional[E.Expression] = None,
                 build_chunk_rows: int = 4096):
        super().__init__(left, right)
        assert join_type in NLJ_TYPES, join_type
        if join_type in ("inner", "cross") and condition is None:
            join_type = "cross"
        self.join_type = join_type
        self.condition = condition
        self.build_chunk_rows = build_chunk_rows
        self._broadcast = None
        self._bcast_lock = threading.Lock()
        self._prepared = False
        self._register_metric("joinTimeNs")

    def _prepare(self):
        if self._prepared:
            return
        ls, rs = self.left.output_schema, self.right.output_schema
        if self.join_type in ("left_semi", "left_anti"):
            self._schema = T.Schema(list(ls))
        else:
            lf = list(ls)
            rf = [T.Field(f.name, f.dtype, f.nullable or self.join_type == "left")
                  for f in rs]
            self._schema = T.Schema(lf + rf)
        if self.condition is not None:
            self._cond_bound = E.resolve(self.condition,
                                         T.Schema(list(ls) + list(rs)))
        else:
            self._cond_bound = None
        self._prepared = True

    @property
    def output_schema(self) -> T.Schema:
        self._prepare()
        return self._schema

    def num_partitions(self) -> int:
        return self.left.num_partitions()

    def node_description(self) -> str:
        return (f"TpuBroadcastNestedLoopJoin {self.join_type}"
                + (f" cond={self.condition!r}" if self.condition is not None
                   else ""))

    def _build_side(self) -> ColumnarBatch:
        with self._bcast_lock:
            if self._broadcast is None:
                batches = list(self.right.execute_all())
                if batches:
                    self._broadcast = (batches[0] if len(batches) == 1
                                       else concat_jit(batches))
                else:
                    self._broadcast = empty_batch(
                        self.right.output_schema.types(), 16)
            return self._broadcast

    def do_execute(self, partition: int) -> Iterator[ColumnarBatch]:
        self._prepare()
        build = self._build_side()
        chunk = min(self.build_chunk_rows, build.capacity)
        for probe in self.left.execute(partition):
            with self.timer("joinTimeNs"):
                yield from self._join_probe(probe, build, chunk)

    def _join_probe(self, probe: ColumnarBatch, build: ColumnarBatch,
                    chunk: int) -> Iterator[ColumnarBatch]:
        jt = self.join_type
        pmatch = jnp.zeros(probe.capacity, jnp.bool_)
        # pair batches stream chunk by chunk (only the final unmatched-rows
        # batch of a left join waits for the full build loop)
        for start in range(0, build.capacity, chunk):
            ver, n_dev, pbytes, bbytes = _nlj_verify(probe, build, start,
                                                     chunk, self._cond_bound)
            if jt in ("left_semi", "left_anti", "left"):
                pmatch = pmatch | jnp.any(
                    ver.reshape(probe.capacity, chunk), axis=1)
            if jt not in ("left_semi", "left_anti"):
                n = int(n_dev)
                if n == 0:
                    continue
                out_cap = bucket_capacity(n, 16)
                pcaps = tuple(sorted(
                    (i, bucket_capacity(max(int(v), 8), 8))
                    for i, v in pbytes.items()))
                bcaps = tuple(sorted(
                    (i, bucket_capacity(max(int(v), 8), 8))
                    for i, v in bbytes.items()))
                yield _nlj_gather(probe, build, ver, start, chunk, out_cap,
                                  pcaps, bcaps)
        if jt in ("left_semi", "left_anti"):
            want = pmatch if jt == "left_semi" else (~pmatch
                                                     & probe.active_mask())
            idx, n = K.filter_indices(want, probe.active_mask())
            yield K.gather_batch(probe, idx, n)
            return
        if jt == "left":
            unmatched = ~pmatch & probe.active_mask()
            n = int(host_get(jnp.sum(unmatched), "join.unmatched_probe"))
            if n:
                idx, nn = K.filter_indices(unmatched, probe.active_mask())
                left_out = K.gather_batch(probe, idx, nn)
                cols = list(left_out.columns)
                for f in self.right.output_schema:
                    cols.append(_null_column(f.dtype, left_out.capacity))
                yield ColumnarBatch(cols, left_out.num_rows)



class CartesianProductExec(BroadcastNestedLoopJoinExec):
    """Cross join (GpuCartesianProductExec): inner all-pairs, optional
    residual condition."""

    def __init__(self, left: TpuExec, right: TpuExec,
                 condition: Optional[E.Expression] = None, **kw):
        super().__init__("inner" if condition is not None else "cross",
                         left, right, condition, **kw)

    def node_description(self) -> str:
        return ("TpuCartesianProduct"
                + (f" cond={self.condition!r}" if self.condition is not None
                   else ""))


@partial(jax.jit, static_argnums=(2, 3, 4))
def _nlj_verify(probe: ColumnarBatch, build: ColumnarBatch, start: int,
                chunk: int, cond_bound):
    """Pair-validity mask for the (probe x build[start:start+chunk]) tile,
    plus verified-pair count and exact per-string-column output byte needs
    (so downstream gathers can size static byte capacities tightly)."""
    P = probe.capacity
    k = jnp.arange(P * chunk, dtype=jnp.int32)
    pi = k // chunk
    bi = start + (k % chunk)
    bi_c = jnp.clip(bi, 0, build.capacity - 1)
    active = (probe.active_mask()[pi]
              & (bi < build.capacity)
              & build.active_mask()[bi_c])
    if cond_bound is not None:
        # condition eval over the expanded tile: only columns the condition
        # actually reads are gathered (unreferenced ones — often wide string
        # payloads — become cheap null placeholders); the tile repeats probe
        # bytes `chunk` times and build-chunk bytes P times, so input byte
        # capacity scaled by the fanout is an exact upper bound
        refs = set(E.referenced_columns(cond_bound))
        nl = len(probe.columns)
        pref = [i for i in range(nl) if i in refs]
        bref = [i for i in range(len(build.columns)) if nl + i in refs]
        pg = K.gather_columns(
            [probe.columns[i] for i in pref], pi, active,
            [probe.columns[i].data.shape[0] * chunk
             if probe.columns[i].offsets is not None else None for i in pref])
        bg = K.gather_columns(
            [build.columns[i] for i in bref], bi_c, active,
            [build.columns[i].data.shape[0] * P
             if build.columns[i].offsets is not None else None for i in bref])
        pmap = dict(zip(pref, pg))
        bmap = dict(zip(bref, bg))
        cols = [pmap[i] if i in pmap else _null_column(c.dtype, P * chunk)
                for i, c in enumerate(probe.columns)]
        cols += [bmap[i] if i in bmap else _null_column(c.dtype, P * chunk)
                 for i, c in enumerate(build.columns)]
        pair = ColumnarBatch(cols, jnp.int32(P * chunk))
        res = EV.eval_expr(cond_bound, EV.EvalContext(pair))
        active = active & res.data & res.validity
    pbytes = {}
    for i, c in enumerate(probe.columns):
        if c.offsets is not None:
            lens = (c.offsets[1:] - c.offsets[:-1]).astype(jnp.int64)
            pbytes[i] = jnp.sum(jnp.where(active, lens[pi], 0))
    bbytes = {}
    for i, c in enumerate(build.columns):
        if c.offsets is not None:
            lens = (c.offsets[1:] - c.offsets[:-1]).astype(jnp.int64)
            bbytes[i] = jnp.sum(jnp.where(active, lens[bi_c], 0))
    return active, jnp.sum(active.astype(jnp.int64)), pbytes, bbytes


@partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
def _nlj_gather(probe: ColumnarBatch, build: ColumnarBatch, ver: jax.Array,
                start: int, chunk: int, out_cap: int, pcap_items, bcap_items):
    pcaps, bcaps = dict(pcap_items), dict(bcap_items)
    idx, n = K.filter_indices(ver, jnp.ones_like(ver))
    idx = _pad_idx(idx, out_cap)
    pi = idx // chunk
    bi = jnp.clip(start + (idx % chunk), 0, build.capacity - 1)
    row_valid = jnp.arange(out_cap, dtype=jnp.int32) < n
    cols = list(K.gather_columns(
        probe.columns, pi, row_valid,
        [pcaps.get(i) for i in range(len(probe.columns))]))
    cols += list(K.gather_columns(
        build.columns, bi, row_valid,
        [bcaps.get(i) for i in range(len(build.columns))]))
    return ColumnarBatch(cols, n.astype(jnp.int32))


class SubPartitionHashJoinExec(BinaryExec):
    """Hash join for oversized inputs: both sides are hash-partitioned on the
    join keys into disjoint buckets; each bucket pair is joined independently.

    Reference: GpuSubPartitionHashJoin.scala — when the build side exceeds
    the target batch budget, the join recursively re-partitions so each
    sub-join's build side fits. Bucket disjointness makes per-bucket outer
    bookkeeping exact. Null-keyed rows land in some bucket and simply never
    match, which is the equi-join semantic.
    """

    def __init__(self, left_keys: Sequence[E.Expression],
                 right_keys: Sequence[E.Expression], join_type: str,
                 left: TpuExec, right: TpuExec,
                 condition: Optional[E.Expression] = None,
                 num_sub_partitions: int = 4):
        super().__init__(left, right)
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.join_type = join_type
        self.condition = condition
        self.num_sub_partitions = num_sub_partitions
        self._register_metric("numSubJoins")
        self._template = HashJoinExec(left_keys, right_keys, join_type,
                                      left, right, condition)

    @property
    def output_schema(self) -> T.Schema:
        return self._template.output_schema

    def num_partitions(self) -> int:
        return self.left.num_partitions()

    def node_description(self) -> str:
        return (f"TpuSubPartitionHashJoin {self.join_type} "
                f"k={self.num_sub_partitions}")

    def _bucketize(self, batches: List[ColumnarBatch],
                   key_idx: Tuple[int, ...]) -> List[List[ColumnarBatch]]:
        k = self.num_sub_partitions
        out: List[List[ColumnarBatch]] = [[] for _ in range(k)]
        for b in batches:
            # one device pass computes bucket ids + per-bucket row/byte
            # counts; each bucket is then gathered into a batch sized to its
            # own rows/bytes — this is what makes sub-partitioning actually
            # shrink the per-join working set
            hmod, counts, byte_counts = _bucket_stats(b, key_idx, k)
            counts_h = [int(c) for c in counts]
            bytes_h = [[int(x) for x in row] for row in byte_counts]
            str_cols = tuple(i for i, c in enumerate(b.columns)
                             if c.offsets is not None)
            for p in range(k):
                cap = bucket_capacity(max(counts_h[p], 1), 16)
                bcaps = tuple(
                    (i, bucket_capacity(max(bytes_h[p][j], 8), 8))
                    for j, i in enumerate(str_cols))
                out[p].append(_bucket_gather(b, hmod, p, cap, bcaps))
        return out

    def do_execute(self, partition: int) -> Iterator[ColumnarBatch]:
        self._template._prepare()
        lk = tuple(self._template._lkeys)
        rk = tuple(self._template._rkeys)
        ls, rs = self.left.output_schema, self.right.output_schema
        lbuckets = self._bucketize(list(self.left.execute(partition)), lk)
        rbuckets = self._bucketize(list(self.right.execute(partition)), rk)
        for p in range(self.num_sub_partitions):
            sub = HashJoinExec(
                self.left_keys, self.right_keys, self.join_type,
                BatchSourceExec([lbuckets[p]], ls),
                BatchSourceExec([rbuckets[p]], rs),
                self.condition)
            self.metrics["numSubJoins"].add(1)
            yield from sub.execute(0)


@partial(jax.jit, static_argnums=(1, 2))
def _bucket_stats(batch: ColumnarBatch, key_idx: Tuple[int, ...], k: int):
    """Bucket id per row plus per-bucket row counts and string byte counts."""
    h = K.hash_keys(batch, list(key_idx))
    hmod = (h % jnp.uint64(k)).astype(jnp.int32)
    hmod = jnp.where(batch.active_mask(), hmod, k)  # padding rows -> no bucket
    counts = jax.ops.segment_sum(jnp.ones(batch.capacity, jnp.int32), hmod,
                                 num_segments=k + 1)[:k]
    byte_rows = []
    for c in batch.columns:
        if c.offsets is not None:
            lens = (c.offsets[1:] - c.offsets[:-1]).astype(jnp.int64)
            byte_rows.append(jax.ops.segment_sum(
                lens, hmod, num_segments=k + 1)[:k])
    bytes_mat = (jnp.stack(byte_rows, axis=1) if byte_rows
                 else jnp.zeros((k, 0), jnp.int64))
    return hmod, counts, bytes_mat


@partial(jax.jit, static_argnums=(2, 3, 4))
def _bucket_gather(batch: ColumnarBatch, hmod: jax.Array, p: int, cap: int,
                   bcap_items) -> ColumnarBatch:
    bcaps = dict(bcap_items)
    want = hmod == p
    idx, n = K.filter_indices(want, batch.active_mask())
    idx = _pad_idx(idx, cap)
    row_valid = jnp.arange(cap, dtype=jnp.int32) < n
    cols = K.gather_columns(batch.columns, idx, row_valid,
                            [bcaps.get(i) for i in range(len(batch.columns))])
    return ColumnarBatch(cols, n.astype(jnp.int32))


# type_support declarations (spark_rapids_tpu.support);
# BroadcastHashJoinExec inherits from HashJoinExec.
from spark_rapids_tpu.support import ALL_SCALAR, ts  # noqa: E402

BroadcastNestedLoopJoinExec.type_support = ts(
    ALL_SCALAR, note="join condition typed by check_expr over the pair "
    "tile; CartesianProductExec inherits")
SubPartitionHashJoinExec.type_support = ts(
    ALL_SCALAR, note="same key typing as HashJoinExec; sub-partitions by "
    "rehashing keys")
