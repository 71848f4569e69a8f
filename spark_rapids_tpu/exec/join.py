"""Hash equi-joins (inner/left/right/full/semi/anti) with optional residual
condition.

Reference: GpuHashJoin.scala (gather-map joins, 1212 LoC), GpuShuffledHashJoin
/ GpuBroadcastHashJoinExecBase; conditional joins via cudf AST
(GpuExpressions.scala:197). TPU-first re-design:

- the build side is concatenated once (RequireSingleBatch, like the
  reference's build side) and preprocessed into sorted 64-bit hashes;
- each probe batch computes candidate ranges by binary search in the sorted
  hashes (the XLA analog of a hash-table probe), expands them into flat
  (probe,build) pairs, then *exactly verifies* real key equality — hash
  collisions only cost a discarded candidate, never a wrong result;
- residual (non-equi) conditions are evaluated by the fused expression engine
  over the candidate pairs — the analog of the reference's AST-compiled
  conditional join;
- outer sides are completed with matched-flag bookkeeping: a device bool
  vector per build row (right/full) and per-probe-row match counts
  (left/semi/anti).

Output sizing is data-dependent: candidate totals are pulled to host to pick
a static output capacity bucket, mirroring how the reference sizes gather
output from join row counts.
"""

from __future__ import annotations

import contextlib
import threading
from functools import partial
from typing import Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, bucket_capacity
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.exec.base import BinaryExec, TpuExec
from spark_rapids_tpu.exec import kernels as K
from spark_rapids_tpu.exec.aggregate import concat_jit
from spark_rapids_tpu.exprs import expr as E
from spark_rapids_tpu.exprs import eval as EV
from spark_rapids_tpu.obs import span as _span
from spark_rapids_tpu.utils.sync import host_get

JOIN_TYPES = ("inner", "left", "right", "full", "left_semi", "left_anti")

# -- the build side's span and counters --------------------------------------

BUILD_PATHS = ("dense", "unique", "ht", "sorted")
_build_tls = threading.local()
_build_lock = threading.Lock()
_build_paths = dict.fromkeys(BUILD_PATHS, 0)


def counters() -> dict:
    """For obs/gauges.snapshot(): build sides by the probe structure they
    ended in."""
    with _build_lock:
        out = {f"join_build_path_{p}_total": n
               for p, n in _build_paths.items()}
    out["join_build_path_total"] = sum(out.values())
    return out


@contextlib.contextmanager
def build_span(join):
    """One ``exec:join-build`` span around ``join``'s build side: executing
    it and constructing the probe structure, the construction's host syncs
    as its children. Yields the dict the span's attrs are taken from
    (``path``, ``rows``, ``capacity``). The same join's build inside its
    own build (a broadcast build re-prepared for a fused probe) fills the
    outer one's, so a build side is one span and one count of
    ``join_build_path_total``, under the path it is probed by; another
    join's build below it (a join in the build subtree) is a span of its
    own, a child of this one."""
    outer = getattr(_build_tls, "open", None)
    if outer is not None and outer[0] is join:
        yield outer[1]
        return
    attrs = {}
    _build_tls.open = (join, attrs)
    try:
        with _span.task_span("exec:join-build") as sp:
            try:
                yield attrs
            finally:
                if sp is not None:
                    sp.attrs.update(attrs)
    finally:
        _build_tls.open = outer
        if attrs.get("path") in _build_paths:
            with _build_lock:
                _build_paths[attrs["path"]] += 1


class HashJoinExec(BinaryExec):
    shrink_output = True

    def __init__(self, left_keys: Sequence[E.Expression],
                 right_keys: Sequence[E.Expression],
                 join_type: str, left: TpuExec, right: TpuExec,
                 condition: Optional[E.Expression] = None,
                 max_candidate_rows: Optional[int] = None):
        super().__init__(left, right)
        assert join_type in JOIN_TYPES, join_type
        from spark_rapids_tpu.config import conf as _C
        self.max_candidate_rows = (max_candidate_rows
                                   if max_candidate_rows is not None
                                   else _C.JOIN_MAX_OUTPUT_ROWS.default)
        self.join_type = join_type
        self.left_keys = list(left_keys)
        self.right_keys = list(right_keys)
        self.condition = condition
        self._prepared = False
        self._prepare_lock = threading.Lock()
        self._register_metric("buildTimeNs")
        self._register_metric("joinTimeNs")
        self._register_metric("numCandidatePairs")

    # -- schema ------------------------------------------------------------
    def _prepare(self):
        if self._prepared:
            return
        with self._prepare_lock:
            if self._prepared:
                return
            self._prepare_locked()

    def _prepare_locked(self):
        ls, rs = self.left.output_schema, self.right.output_schema
        self._lkeys = [self._key_index(k, ls) for k in self.left_keys]
        self._rkeys = [self._key_index(k, rs) for k in self.right_keys]
        if self.join_type in ("left_semi", "left_anti"):
            self._schema = T.Schema(list(ls))
        else:
            lf = [T.Field(f.name, f.dtype,
                          f.nullable or self.join_type in ("right", "full"))
                  for f in ls]
            rf = [T.Field(f.name, f.dtype,
                          f.nullable or self.join_type in ("left", "full"))
                  for f in rs]
            self._schema = T.Schema(lf + rf)
        if self.condition is not None:
            pair_schema = T.Schema(list(ls) + list(rs))
            self._cond_bound = E.resolve(self.condition, pair_schema)
        else:
            self._cond_bound = None
        self._prepared = True

    @staticmethod
    def _key_index(k: E.Expression, schema: T.Schema) -> int:
        b = E.resolve(k, schema)
        assert isinstance(b, E.ColumnRef), "join keys must be column refs"
        return b.index

    @property
    def output_schema(self) -> T.Schema:
        self._prepare()
        return self._schema

    def node_description(self) -> str:
        return (f"TpuHashJoin {self.join_type} "
                f"keys={list(zip(self.left_keys, self.right_keys))}"
                + (f" cond={self.condition!r}" if self.condition is not None else ""))

    # -- execution ---------------------------------------------------------

    def do_execute(self, partition: int) -> Iterator[ColumnarBatch]:
        self._prepare()
        with build_span(self) as battrs:
            with self.timer("buildTimeNs"):
                build = self._collect_build(partition)
            # Peek one probe batch so the path decision happens at the
            # probe's shape-class (plan/autotune.py): capacity is the log2
            # rows bucket and is static, so this costs no device sync.
            probe_iter = self.left.execute(partition)
            first = next(probe_iter, None)
            probe_cap = first.capacity if first is not None else 16
            with self.timer("buildTimeNs"):
                (dense, table, ht, jh, path,
                 source, shape) = self._choose_path(build, probe_cap)
            battrs.update(path=path, capacity=build.capacity)
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
                if dense is not None:
                    out, build_matched = self._join_batch_dense(
                        probe, build, dense, build_matched, partition)
                elif table is not None:
                    out, build_matched = self._join_batch_unique(
                        probe, build, table, build_matched, partition)
                else:
                    out, build_matched = self._join_batch(probe, build, jh,
                                                          build_matched)
            if out is not None:
                yield out

        if self.join_type in ("right", "full"):
            out = self._unmatched_build(build, build_matched)
            if out is not None:
                yield out

        from spark_rapids_tpu.plan import autotune as AT
        AT.record_decision(
            self, f"join:{self.join_type}", path, source, shape,
            ns=self.metrics["joinTimeNs"].value - join_ns0,
            rows=probe_rows)

    def _collect_build(self, partition: int) -> ColumnarBatch:
        """The build side as one batch. Its batches are collected as
        spillable handles: while later ones are still being produced,
        earlier ones can shed to host/disk under pool pressure (same door
        as agg buckets and out-of-core sort runs), then re-materialize for
        the concat."""
        from spark_rapids_tpu.mem.spill import SpillableBatch, get_framework

        fw = get_framework()
        handles = [SpillableBatch(b, fw)
                   for b in self.right.execute(partition)]
        try:
            if not handles:
                from spark_rapids_tpu.columnar.batch import empty_batch
                return empty_batch(self.right.output_schema.types(), 16)
            build_batches = [h.get() for h in handles]
            try:
                return (build_batches[0] if len(build_batches) == 1
                        else concat_jit(build_batches))
            finally:
                for h in handles:
                    h.unpin()
        finally:
            for h in handles:
                h.close()

    def _choose_path(self, build: ColumnarBatch, probe_cap: int):
        """Pick the probe structure for this partition: the static
        dense -> bucketed-unique -> ht -> sorted-hash precedence, with
        the autotune Dispatcher re-ranking only between paths proven to
        emit identical rows in identical order (dense<->unique for every
        join type; ht<->sorted only for the semi/anti filters). Returns
        (dense, table, ht, jh, path, source, shape)."""
        from spark_rapids_tpu.plan import autotune as AT
        ls = self.left.output_schema
        fam = AT.family_of(str(ls[i].dtype) for i in self._lkeys)
        shape = AT.shape_class(probe_cap, len(self._lkeys), fam)
        op = f"join:{self.join_type}"
        dense = self._prepare_dense(build)
        if dense is not None:
            path, source = AT.choose(op, shape, "dense",
                                     ("dense", "unique"))
            if path == "unique":
                prep = self._prepare_table(build)
                if prep is not None and not isinstance(prep, K.JoinHashes):
                    return None, prep, None, None, "unique", source, shape
                # table refused (slot budget): back to the static path
                path, source = "dense", "default"
            return dense, None, None, None, "dense", source, shape
        prep = self._prepare_table(build)
        if prep is not None and not isinstance(prep, K.JoinHashes):
            return None, prep, None, None, "unique", "default", shape
        # duplicate keys (JoinHashes view) or build too large: the general
        # path. Round 12: open-addressing hash table with chunked gather;
        # the sorted-hash join is the conf-off / rehash-exhausted fallback.
        path, source = (("ht", "default") if self._hashtbl_enabled
                        else ("sorted", "default"))
        if path == "ht" and self.join_type in ("left_semi", "left_anti"):
            path, source = AT.choose(op, shape, "ht", ("ht", "sorted"))
        if path == "ht":
            ht = K.build_batch_hash_table(build, tuple(self._rkeys))
            if ht is not None:
                return None, None, ht, None, "ht", source, shape
            path, source = "sorted", "default"
        jh = (prep if isinstance(prep, K.JoinHashes)
              else _prepare_build(build, tuple(self._rkeys)))
        return None, None, None, jh, "sorted", source, shape

    # -- bucketed unique-key table path ------------------------------------
    # Round-4 general-join rebuild (VERDICT r3 item 3): when the build keys
    # are UNIQUE — dimension tables, distinct subqueries — but the dense
    # direct-address path can't apply (string/multi/wide-domain keys), the
    # bucketed table (kernels.build_join_table) gives a fully traced probe
    # with STATIC output shapes: out_cap = probe capacity, no per-batch
    # candidate-count host sync, one compile per probe bucket. The ONLY
    # sync is the (dup_any, max_bucket) pair read once per build side.
    #
    # The rung has two layouts, by who probes. This operator, the mesh
    # executor's broadcast join and the general path's sort keep the
    # bucket-contiguous one (_prepare_table): five lanes of the build's
    # capacity, 29 B a row up to 2^27 rows, a view of which IS the
    # general path's JoinHashes when the keys repeat. The fused probe
    # (_prepare_rows) reads fixed rows of a bucket's slots with one gather
    # a probe row where this one makes two and five a slot: 256 B a row of
    # the build's capacity (half as many buckets, each padded to 128
    # words), so 4 GB at 2^24 rows against 0.5 GB, and no view to hand on.

    @property
    def _max_unique_slots(self) -> int:
        from spark_rapids_tpu.config import conf as _C
        return _C.JOIN_UNIQUE_MAX_SLOTS.get(_C.get_active())

    @property
    def _dense_max_domain(self) -> int:
        from spark_rapids_tpu.config import conf as _C
        return _C.JOIN_DENSE_MAX_DOMAIN.get(_C.get_active())

    def _prepare_table(self, build: ColumnarBatch):
        """Build the bucketed table; returns (tbl, slots) for the unique
        probe, or a ``JoinHashes`` view of the SAME sorted layout when keys
        are duplicated (the general path reuses the sort — the speculative
        build is never thrown away)."""
        if build.capacity > (1 << 27):
            return None  # table sort beyond the slot budget: general path
        tbl, dup_any, max_bucket = K.build_join_table(
            build, tuple(self._rkeys))
        dup, mb = host_get((dup_any, max_bucket), "join.table_stats")
        slots = 1
        while slots < max(int(mb), 1):
            slots *= 2
        if bool(dup) or slots > self._max_unique_slots:
            # the (h1,h2)-sorted layout IS a valid JoinHashes (sorted by
            # hash, invalid rows pushed to the end)
            return K.JoinHashes(tbl.h1s, tbl.order, tbl.valid)
        # lg_b comes back as a device scalar from the jitted build; the probe
        # needs it static — it is a pure function of the build capacity
        tbl = tbl._replace(lg_b=K._join_lg_b(build.capacity))
        return tbl, slots

    def _prepare_rows(self, build: ColumnarBatch,
                      told: Optional[dict] = None):
        """The unique table as the fused probe reads it (``K.join_row_slots``
        + ``K.join_rows_table``): (rows, slots, lg_b), or None where the
        keys repeat or a bucket outgrows ``join.uniqueTable.maxSlots``
        (the general path's case). Two syncs a build side: the largest
        bucket sizes the table's rows, then whether two rows share a key
        (two slots of one bucket of the finished table hold one hash
        pair: the table's own compare, not a probe of it by the build)."""
        if build.capacity > (1 << 24):
            return None  # 256 B a row of capacity: 4 GB of table here
        placed, largest = K.join_row_slots(build, tuple(self._rkeys))
        mb, rows = host_get((largest, build.num_rows), "join.table_stats")
        if told is not None:
            told["rows"] = int(rows)
        slots = 1
        while slots < max(int(mb), 1):
            slots *= 2
        lg_b = K.join_rows_lg_b(build.capacity)
        if (slots > self._max_unique_slots
                or (K.ROW_WORDS * slots) << lg_b >= 1 << 31):
            return None  # (the table's word offsets are int32)
        tbl, twin = K.join_rows_table(placed, slots, lg_b)
        if bool(host_get(twin, "join.table_dup")):
            return None
        return tbl, slots, lg_b

    def _join_batch_unique(self, probe: ColumnarBatch, build: ColumnarBatch,
                           table, build_matched, partition: int):
        tbl, slots = table
        jt = self.join_type
        out_cap = probe.capacity
        pcaps = {i: c.byte_capacity
                 for i, c in enumerate(probe.columns) if c.offsets is not None}
        cache = getattr(self, "_dense_bcache", None)
        if cache is None:
            cache = self._dense_bcache = {}
        ckey = ("tbl", partition, out_cap)
        if ckey not in cache:
            caps = {}
            for i, c in enumerate(build.columns):
                if c.offsets is not None:
                    ml = _max_row_bytes(c)
                    caps[i] = bucket_capacity(max(out_cap * max(ml, 1), 8), 8)
            cache[ckey] = caps
        self._pcaps, self._bcaps = pcaps, cache[ckey]
        bi, hit, new_matched = _unique_probe(
            probe, build, tbl, build_matched, tuple(self._lkeys),
            tuple(self._rkeys), slots, tbl.lg_b, self._cond_bound, jt,
            tuple(sorted(cache[ckey].items())))
        if jt == "left_semi":
            idx, n = K.filter_indices(hit, probe.active_mask())
            return K.gather_batch(probe, idx, n), new_matched
        if jt == "left_anti":
            want = ~hit & probe.active_mask()
            idx, n = K.filter_indices(want, probe.active_mask())
            return K.gather_batch(probe, idx, n), new_matched
        if jt in ("left", "full"):
            pi = jnp.arange(out_cap, dtype=jnp.int32)
            out = self._gather_pairs(probe, build, pi,
                                     jnp.where(hit, bi, 0), hit,
                                     probe.num_rows, out_cap)
            return out, new_matched
        # inner: compact hit rows
        idx, n = K.filter_indices(hit, probe.active_mask())
        bi_c = jnp.where(idx < out_cap, bi[jnp.clip(idx, 0, out_cap - 1)], 0)
        out = self._gather_pairs(probe, build, idx, jnp.clip(bi_c, 0, None),
                                 jnp.arange(out_cap, dtype=jnp.int32) < n,
                                 n, out_cap)
        return out, new_matched

    # -- dense surrogate-key fast path -------------------------------------
    # TPC-style schemas join facts to dimensions on DENSE INT SURROGATE KEYS
    # (unique on the build side). On TPU that makes the whole hash table
    # machinery unnecessary: scatter build row ids into a direct-address
    # table once, then every probe batch is ONE gather — no sort, no hash,
    # no per-batch candidate-count host sync, and the output capacity is
    # statically bounded by the probe capacity (max one match per row).
    # cuDF has no analog (it cannot assume key density); the sorted-hash
    # path remains the general fallback.
    def _prepare_dense(self, build: ColumnarBatch,
                       told: Optional[dict] = None):
        if len(self._rkeys) != 1:
            return None
        assert self.join_type in JOIN_TYPES  # all types have a dense impl
        kcol = build.columns[self._rkeys[0]]
        pdt = self.left.output_schema[self._lkeys[0]].dtype
        if (kcol.offsets is not None or kcol.is_dict or kcol.is_wide_decimal
                or kcol.dtype not in (T.INT, T.LONG)
                or pdt not in (T.INT, T.LONG)):
            return None
        stats = host_get(_dense_key_stats(build, self._rkeys[0]),
                         "join.dense_key_stats")
        kmin, kmax, n_valid = (int(stats[0]), int(stats[1]), int(stats[2]))
        if told is not None:
            told.update(rows=n_valid, kmin=kmin, kmax=kmax)
        if n_valid == 0 or kmin < 0 or kmax >= self._dense_max_domain:
            return None
        size = bucket_capacity(kmax + 1, 16)
        tbl, dup_any = _dense_build_table(build, self._rkeys[0], size)
        if bool(host_get(dup_any, "join.dense_dup")):
            return None  # non-unique build keys: general path
        return tbl

    def _join_batch_dense(self, probe: ColumnarBatch, build: ColumnarBatch,
                          tbl, build_matched, partition: int):
        jt = self.join_type
        out_cap = probe.capacity
        pcaps = {i: c.byte_capacity
                 for i, c in enumerate(probe.columns) if c.offsets is not None}
        # static byte bound for gathered build strings: one match per probe
        # row at the longest build row length; keyed per (partition,
        # out_cap) — each partition rebuilds its build side, and a larger
        # probe bucket needs a larger bound
        cache = getattr(self, "_dense_bcache", None)
        if cache is None:
            cache = self._dense_bcache = {}
        ckey = (partition, out_cap)
        if ckey not in cache:
            caps = {}
            for i, c in enumerate(build.columns):
                if c.offsets is not None:
                    ml = _max_row_bytes(c)
                    caps[i] = bucket_capacity(max(out_cap * max(ml, 1), 8), 8)
            cache[ckey] = caps
        self._pcaps, self._bcaps = pcaps, cache[ckey]
        pi, bi, hit, n_out, new_matched = _dense_probe(
            probe, build, tbl, self._lkeys[0], self._cond_bound, jt,
            build_matched, tuple(sorted(cache[ckey].items())))
        if jt == "left_semi":
            idx, n = K.filter_indices(hit, probe.active_mask())
            return K.gather_batch(probe, idx, n), new_matched
        if jt == "left_anti":
            want = ~hit & probe.active_mask()
            idx, n = K.filter_indices(want, probe.active_mask())
            return K.gather_batch(probe, idx, n), new_matched
        bi_valid = bi >= 0
        out = self._gather_pairs(probe, build, pi,
                                 jnp.where(bi_valid, bi, 0), bi_valid,
                                 n_out, out_cap)
        return out, new_matched

    def _join_batch(self, probe: ColumnarBatch, build: ColumnarBatch,
                    jh: K.JoinHashes, build_matched):
        lkeys, rkeys = tuple(self._lkeys), tuple(self._rkeys)
        pstr = tuple(i for i, c in enumerate(probe.columns) if c.offsets is not None)
        bstr = tuple(i for i, c in enumerate(build.columns) if c.offsets is not None)
        lo, cnt, total_dev, pbytes, bbytes = _probe_stats(
            probe, build, jh, lkeys, pstr, bstr)
        total = int(total_dev)
        self.metrics["numCandidatePairs"].add(total)
        cap_rows = self.max_candidate_rows
        if total > cap_rows:
            # explosion guard (JoinGatherer chunking analog; round-2 q72
            # hang): degrade loudly instead of hanging/OOMing
            raise RuntimeError(
                f"join candidate explosion: one probe batch produced "
                f"{total} candidate pairs (> "
                f"spark.rapids.tpu.sql.join.maxCandidateRowsPerBatch="
                f"{cap_rows}); check the join keys "
                f"({self.node_description()})")
        # left/full append unmatched probe rows after the pairs; only they
        # need the extra probe-capacity headroom
        extra = probe.capacity if self.join_type in ("left", "full") else 0
        out_cap = bucket_capacity(max(total + extra, 1), 16)
        # exact byte-capacity upper bounds: candidate bytes (+ once-per-probe
        # input bytes for rows appended by left/full outer)
        pcaps = {
            i: bucket_capacity(max(int(b) + probe.columns[i].byte_capacity, 8), 8)
            for i, b in zip(pstr, pbytes)
        }
        bcaps = {i: bucket_capacity(max(int(b), 8), 8) for i, b in zip(bstr, bbytes)}
        pi, bi, nver, pmatch = _verified_pairs(
            probe, build, jh.order, lo, cnt, jnp.int32(0),
            jnp.int32(cnt.shape[0]), lkeys, rkeys, self._cond_bound, out_cap,
            tuple(sorted(pcaps.items())), tuple(sorted(bcaps.items())))
        self._pcaps, self._bcaps = pcaps, bcaps

        jt = self.join_type
        if jt in ("right", "full"):
            new_matched = build_matched.at[
                jnp.where(jnp.arange(out_cap, dtype=jnp.int32) < nver, bi,
                          build.capacity)
            ].set(True, mode="drop")
        else:
            new_matched = build_matched

        if jt in ("left_semi", "left_anti"):
            want = pmatch if jt == "left_semi" else (
                ~pmatch & probe.active_mask())
            idx, n = K.filter_indices(want, probe.active_mask())
            out = K.gather_batch(probe, idx, n)
            return out, new_matched
        if jt in ("left", "full"):
            # append unmatched probe rows after the verified pairs
            unmatched = ~pmatch & probe.active_mask()
            uidx, un = K.filter_indices(unmatched, probe.active_mask())
            pi = _append_rows(pi, nver, uidx, un, out_cap)
            bi_valid = jnp.arange(out_cap, dtype=jnp.int32) < nver
            n_out = nver + un
        else:
            bi_valid = jnp.arange(out_cap, dtype=jnp.int32) < nver
            n_out = nver
        out = self._gather_pairs(probe, build, pi, bi, bi_valid, n_out, out_cap)
        return out, new_matched

    # -- general hash-table path with chunked gather -----------------------
    # Round-12 tentpole: duplicate-key / wide-domain builds probe an
    # open-addressing device table (kernels.build_batch_hash_table) instead
    # of re-sorting hashes per build. Oversized probe outputs are emitted in
    # bounded row-range CHUNKS (GpuSubPartitionHashJoin's JoinGatherer
    # analog): the candidate prefix sum is cut into ranges of at most
    # gatherChunkTargetRows candidates, each gathered into its own batch and
    # wrapped spillable, so a skewed probe batch never materializes its full
    # output at once — and never trips the candidate-explosion guard.

    @property
    def _hashtbl_enabled(self) -> bool:
        from spark_rapids_tpu.config import conf as _C
        return _C.JOIN_HASHTBL_ENABLED.get(_C.get_active())

    @property
    def _chunk_target_rows(self) -> int:
        from spark_rapids_tpu.config import conf as _C
        return _C.JOIN_CHUNK_TARGET_ROWS.get(_C.get_active())

    def _join_batch_ht(self, probe: ColumnarBatch, build: ColumnarBatch,
                       ht, build_matched, partition: int):
        import numpy as np
        from spark_rapids_tpu.mem.spill import SpillableBatch, get_framework

        tbl, capacity, seed = ht
        jt = self.join_type
        lkeys, rkeys = tuple(self._lkeys), tuple(self._rkeys)
        pstr = tuple(i for i, c in enumerate(probe.columns)
                     if c.offsets is not None)
        K._note_hashtbl("hashtbl_probe_total")
        ph1, ph2, pvalid = _ht_probe_hashes(probe, lkeys)
        slot, hit = K.probe_hash_table_dispatch(tbl, ph1, ph2, capacity,
                                                seed, K.HASHTBL_MAX_PROBES)
        lo, cnt, total_dev, ends, pml_dev = _ht_candidate_stats(
            tbl, slot, hit & pvalid, probe, pstr)
        got = host_get((total_dev,) + tuple(pml_dev),
                       "join.candidate_stats")
        total = int(got[0])
        pml = {i: int(m) for i, m in zip(pstr, got[1:])}
        self.metrics["numCandidatePairs"].add(total)
        if total > self.max_candidate_rows:
            # chunking bounds what materializes at once, but a probe batch
            # whose TOTAL candidate count blows the budget is still a
            # semi-cartesian key explosion: degrade loudly (q72 guard)
            raise RuntimeError(
                f"join candidate explosion: one probe batch produced "
                f"{total} candidate pairs (> "
                f"spark.rapids.tpu.sql.join.maxCandidateRowsPerBatch="
                f"{self.max_candidate_rows}); check the join keys "
                f"({self.node_description()})")
        # longest build row per string column, read once per partition
        cache = getattr(self, "_dense_bcache", None)
        if cache is None:
            cache = self._dense_bcache = {}
        ckey = ("ht", partition)
        if ckey not in cache:
            cache[ckey] = {
                i: _max_row_bytes(c)
                for i, c in enumerate(build.columns)
                if c.offsets is not None}
        bml = cache[ckey]

        # cut the candidate prefix sum into bounded row ranges
        chunk_target = self._chunk_target_rows
        cap_rows = probe.capacity
        if total <= chunk_target:
            ranges = [(0, cap_rows, total)]
        else:
            ends_h = np.asarray(host_get(ends, "join.candidate_ends"))
            ranges = []
            r0, done = 0, 0
            while r0 < cap_rows and done < total:
                # largest r1 with candidates(rows[r0:r1]) <= chunk_target;
                # a single row past the target gets its own chunk
                r1 = int(np.searchsorted(ends_h, done + chunk_target,
                                         side="right"))
                r1 = min(max(r1, r0 + 1), cap_rows)
                ctot = int(ends_h[r1 - 1]) - done
                ranges.append((r0, r1, ctot))
                done += ctot
                r0 = r1
            K._note_hashtbl("hashtbl_chunk_total", len(ranges))

        fw = get_framework()
        handles = []
        pmatch_acc = jnp.zeros(probe.capacity, jnp.bool_)
        pairs_out = jt in ("inner", "left", "right", "full")
        for (r0, r1, ctot) in ranges:
            out_cap = bucket_capacity(max(ctot, 1), 16)
            pcaps = {i: bucket_capacity(max(ctot * max(pml[i], 1), 8), 8)
                     for i in pstr}
            bcaps = {i: bucket_capacity(max(ctot * max(m, 1), 8), 8)
                     for i, m in bml.items()}
            pi, bi, nver, pmatch = _verified_pairs(
                probe, build, tbl.order, lo, cnt, jnp.int32(r0),
                jnp.int32(r1), lkeys, rkeys, self._cond_bound, out_cap,
                tuple(sorted(pcaps.items())), tuple(sorted(bcaps.items())))
            pmatch_acc = pmatch_acc | pmatch
            if jt in ("right", "full"):
                build_matched = build_matched.at[
                    jnp.where(jnp.arange(out_cap, dtype=jnp.int32) < nver,
                              bi, build.capacity)
                ].set(True, mode="drop")
            if pairs_out:
                self._pcaps, self._bcaps = pcaps, bcaps
                out = self._gather_pairs(
                    probe, build, pi, bi,
                    jnp.arange(out_cap, dtype=jnp.int32) < nver, nver,
                    out_cap)
                handles.append(SpillableBatch(out, fw))
        if jt in ("left", "full"):
            # unmatched probe rows ride as their own (final) chunk
            unmatched = ~pmatch_acc & probe.active_mask()
            n = int(host_get(jnp.sum(unmatched), "join.unmatched_probe"))
            if n > 0:
                out_cap = bucket_capacity(n, 16)
                uidx, un = K.filter_indices(unmatched, probe.active_mask())
                row_valid = jnp.arange(out_cap, dtype=jnp.int32) < un
                sidx = (uidx[:out_cap] if uidx.shape[0] >= out_cap
                        else _pad_idx(uidx, out_cap))
                cols = list(K.gather_columns(probe.columns, sidx, row_valid))
                for f in self.right.output_schema:
                    cols.append(_null_column(f.dtype, out_cap))
                handles.append(SpillableBatch(
                    ColumnarBatch(cols, un.astype(jnp.int32)), fw))
        elif jt in ("left_semi", "left_anti"):
            want = (pmatch_acc if jt == "left_semi"
                    else ~pmatch_acc & probe.active_mask())
            idx, n = K.filter_indices(want, probe.active_mask())
            handles.append(SpillableBatch(K.gather_batch(probe, idx, n), fw))
        return handles, build_matched

    # -- whole-stage fusion hook (exec/fused.py) ---------------------------
    def fused_probe(self, partition: int):
        """Build this join's build side now and return a stage segment whose
        per-batch probe is PURE and traceable, or None when the runtime
        path can't be traced (non-inner joins need build/probe matched-flag
        bookkeeping across batches; the general sorted-hash path sizes its
        output from a per-batch host sync of the candidate total).

        The returned segment's fn takes ``(probe_batch, (build, tbl))`` —
        the build arrays ride as jit ARGUMENTS, so the traced program (and
        its shared_jit key) depends only on shapes and the static probe
        parameters, never on build data.
        """
        if self.join_type != "inner":
            return None
        self._prepare()
        with build_span(self) as battrs:
            build = self._fused_build_side(partition)
            if build is None:
                return None  # classic path has the empty-build semantics
            battrs["capacity"] = build.capacity
            with self.timer("buildTimeNs"):
                dense = self._prepare_dense(build, battrs)
                slots = lg_b = None
                if dense is not None:
                    kind, tbl = "dense", dense
                else:
                    prep = self._prepare_rows(build, battrs)
                    if prep is None:
                        # duplicate keys: per-batch host sync path; the
                        # unfused operator builds again and counts there
                        return None
                    kind, (tbl, slots, lg_b) = "unique", prep
                    tbl = (tbl,) + self._presence(build, battrs)
            battrs["path"] = kind
            battrs.pop("kmin", None)
            battrs.pop("kmax", None)
        # longest build row per string column, read ONCE per build; byte
        # bounds for any probe capacity are then pure host arithmetic
        mls = {i: _max_row_bytes(c)
               for i, c in enumerate(build.columns) if c.offsets is not None}
        # fused probes have no per-operator timing to feed the store, but
        # the decision is still surfaced in explain_analyze/dispatch_paths
        from spark_rapids_tpu.plan import autotune as AT
        ls = self.left.output_schema
        AT.record_decision(
            self, f"join:{self.join_type}", kind, "default",
            AT.shape_class(build.capacity, len(self._lkeys),
                           AT.family_of(str(ls[i].dtype)
                                        for i in self._lkeys)))
        return _FusedJoinProbe(self, kind, build, tbl, slots, lg_b, mls)

    PRESENCE_MAX_DOMAIN = 1 << 28  # one byte a possible key

    def _presence(self, build: ColumnarBatch, told: dict) -> tuple:
        """(present, first key): which keys of the build's range exist, one
        byte a possible key, where the unique table's key is one integer
        whose range was read (``_prepare_dense`` read it and found it past
        the dense table's bound) and is at most ``PRESENCE_MAX_DOMAIN``
        wide; else ``(None, None)``. The fused probe asks it first: one
        gather of a byte a probe row says exactly which rows have a match,
        and the table of rows is then probed by those alone."""
        if "kmin" not in told or not (
                0 <= told["kmax"] - told["kmin"] < self.PRESENCE_MAX_DOMAIN):
            return None, None
        size = bucket_capacity(told["kmax"] - told["kmin"] + 1, 16)
        first = jnp.int64(told["kmin"])
        return _presence_table(build, self._rkeys[0], first, size), first

    def _fused_build_side(self, partition: int) -> Optional[ColumnarBatch]:
        """Materialize the build side exactly as do_execute would see it.
        Subclasses with a different build scope (broadcast: ALL partitions)
        must override to match — fusing a partition-local slice of a
        broadcast build silently drops matches. None = empty build, let the
        classic path supply its semantics."""
        with self.timer("buildTimeNs"):
            build_batches = list(self.right.execute(partition))
        if not build_batches:
            return None
        return (build_batches[0] if len(build_batches) == 1
                else concat_jit(build_batches))

    def _gather_pairs(self, probe, build, pi, bi, bi_valid, n_out, out_cap):
        row_valid = jnp.arange(out_cap, dtype=jnp.int32) < n_out
        pcols = K.gather_columns(
            probe.columns, pi, row_valid,
            [self._pcaps.get(i) for i in range(len(probe.columns))])
        bcols = K.gather_columns(
            build.columns, bi, row_valid & bi_valid,
            [self._bcaps.get(i) for i in range(len(build.columns))])
        return ColumnarBatch(list(pcols) + list(bcols),
                             n_out.astype(jnp.int32))

    def _unmatched_build(self, build: ColumnarBatch, matched) -> Optional[ColumnarBatch]:
        want = ~matched & build.active_mask()
        n = int(host_get(jnp.sum(want), "join.unmatched_build"))
        if n == 0:
            return None
        out_cap = bucket_capacity(n, 16)
        idx, nn = K.filter_indices(want, build.active_mask())
        row_valid = jnp.arange(out_cap, dtype=jnp.int32) < nn
        cols: List[DeviceColumn] = []
        ls = self.left.output_schema
        for f in ls:
            cols.append(_null_column(f.dtype, out_cap))
        # subset gather (each build row at most once): input byte capacity
        # is already an upper bound
        sidx = idx[:out_cap] if idx.shape[0] >= out_cap else _pad_idx(
            idx, out_cap)
        cols.extend(K.gather_columns(build.columns, sidx, row_valid))
        return ColumnarBatch(cols, nn.astype(jnp.int32))


class _FusedJoinProbe:
    """Stage segment for an absorbed inner join (HashJoinExec.fused_probe).

    Holds the materialized build side + probe table for one partition and
    hands the fusion driver (exec/fused.py) a pure ``fn(batch, live,
    (build, tbl)) -> (batch, live, ran short?)`` per probe capacity (the
    chain's protocol: ``_make_body``), plus the static key fragment that
    makes the composed stage program shareable across identical plans.

    The probe is written for a chip on which a gather costs about 9 ns an
    index whatever it fetches and a sort of 2^20 (key, row id) pairs 1.3 ms
    (v5e, PERF.md PR 35). At the probe batch's capacity it makes ONE gather
    (the dense table's slot, or the unique table's whole bucket:
    ``K.join_rows_table``) and one sort (``K.compact_indices`` of the
    hits); everything after that, the pairs' gathers and the exact key
    comparison, runs over ``shrink_to`` rows where the stage has learned
    that the hits fit there (``out_cap``), and says so when they do not.
    The unique table over one integer key is asked only about the rows a
    presence table (``HashJoinExec._presence``: a byte a possible key, one
    gather a row) says have a match, already compacted to ``shrink_to``.
    ``live`` is the mask a filter below left instead of compacting."""

    def __init__(self, join: HashJoinExec, kind: str, build: ColumnarBatch,
                 tbl, slots, lg_b, mls):
        self.op = join
        self.kind = kind
        self.build = build
        self.tbl = tbl
        self.slots = slots
        self.lg_b = lg_b
        self._mls = mls  # string col -> longest build row in bytes
        self._bcaps = {}
        self.shrink_to: Optional[int] = None  # the stage's (exec/fused.py)

    @property
    def consts(self):
        return (self.build, self.tbl)

    def out_cap(self, in_cap: int) -> int:
        # dense/unique probes emit at most one row per row
        if self.shrink_to is not None and self.shrink_to < in_cap:
            return self.shrink_to
        return in_cap

    def _bcaps_t(self, out_cap: int) -> tuple:
        t = self._bcaps.get(out_cap)
        if t is None:
            t = tuple(sorted(
                (i, bucket_capacity(max(out_cap * max(ml, 1), 8), 8))
                for i, ml in self._mls.items()))
            self._bcaps[out_cap] = t
        return t

    def key_part(self, in_cap: int) -> tuple:
        j = self.op
        out_cap = self.out_cap(in_cap)
        return ("join", self.kind, tuple(j._lkeys), tuple(j._rkeys),
                j._cond_bound.cache_key() if j._cond_bound is not None
                else None,
                self.slots, self.lg_b, in_cap, out_cap,
                self._bcaps_t(out_cap),
                # the unique kind's presence table, or that it has none
                tuple(None if a is None else a.shape
                      for a in self.tbl[1:2]) if self.kind == "unique"
                else None)

    def probe_fn(self, in_cap: int):
        join, kind = self.op, self.kind
        out_cap = self.out_cap(in_cap)
        bt = self._bcaps_t(out_cap)
        lkeys, rkeys = tuple(join._lkeys), tuple(join._rkeys)
        cond = join._cond_bound
        lg_b = self.lg_b
        # the bucket's candidates matched on 128 hash bits; the exact key
        # comparison (and a residual condition) runs on the pairs, before
        # they are compacted where that leaves the batch's capacity and
        # after (a second, small compaction) where it shrinks
        check_pairs = kind == "unique" or cond is not None

        def pairs_ok(pair, n_probe_cols):
            ok = jnp.ones(pair.capacity, jnp.bool_)
            if kind == "unique":
                rows = jnp.arange(pair.capacity, dtype=jnp.int32)
                ok = K.keys_equal(pair, rows, list(lkeys), pair, rows,
                                  [n_probe_cols + k for k in rkeys])
            if cond is not None:
                cv = EV.eval_expr(cond, EV.EvalContext(pair))
                ok = ok & cv.data & cv.validity
            return ok

        def run(probe, live, consts):
            build, tbl = consts
            cap = probe.capacity
            join._pcaps = {i: c.byte_capacity
                           for i, c in enumerate(probe.columns)
                           if c.offsets is not None}
            join._bcaps = dict(bt)
            pvalid = probe.active_mask() if live is None else live
            for i in lkeys:
                pvalid = pvalid & probe.columns[i].validity
            short = jnp.bool_(False)
            if kind == "unique":
                tbl, present, first = tbl
                if present is not None:
                    # exactly the rows that have a match, by one gather of
                    # a byte a row; the table is probed by those alone
                    off = probe.columns[lkeys[0]].data.astype(
                        jnp.int64) - first
                    inb = pvalid & (off >= 0) & (off < present.shape[0])
                    found = inb & present[
                        jnp.where(inb, off, 0).astype(jnp.int32)]
                    idx, n = K.compact_indices(found, out_cap)
                    short = n > out_cap
                    probe = K.gather_batch(probe, idx,
                                           jnp.minimum(n, out_cap))
                    pvalid, cap = probe.active_mask(), out_cap
            if kind == "dense":
                k64 = probe.columns[lkeys[0]].data.astype(jnp.int64)
                inb = (k64 >= 0) & (k64 < tbl.shape[0])
                bi = tbl[jnp.where(pvalid & inb, k64, 0).astype(jnp.int32)]
                hit = pvalid & inb & (bi >= 0)
            else:
                bi, hit = K.probe_join_rows(
                    tbl, lg_b, K.hash_keys(probe, list(lkeys)),
                    K.hash_keys(probe, list(lkeys), variant=1), pvalid)
            if check_pairs and out_cap * 4 > cap:
                bcols = K.gather_columns(
                    build.columns, jnp.where(hit, bi, 0), hit,
                    [dict(self._bcaps_t(cap)).get(i)
                     for i in range(len(build.columns))])
                pair = ColumnarBatch(list(probe.columns) + list(bcols),
                                     probe.num_rows)
                hit = hit & pairs_ok(pair, len(probe.columns))
            idx, n = K.compact_indices(hit, out_cap)
            short = short | (n > out_cap)
            n = jnp.minimum(n, out_cap)
            row_live = jnp.arange(out_cap, dtype=jnp.int32) < n
            bi_c = jnp.where(row_live, bi[idx], 0)  # masked by row_live
            out = join._gather_pairs(probe, build, idx, bi_c, row_live, n,
                                     out_cap)
            if check_pairs and out_cap * 4 <= cap:
                ok = row_live & pairs_ok(out, len(probe.columns))
                idx2, n2 = K.compact_indices(ok, out_cap)
                out = K.gather_batch(out, idx2, n2)
            return out, None, short  # front-packed: no mask to hand on
        return run


def _max_row_bytes(c: DeviceColumn) -> int:
    """Longest row of a string column, in bytes (one host sync)."""
    return int(host_get(jnp.max(c.offsets[1:] - c.offsets[:-1]),
                        "join.max_row_bytes"))


def _pad_idx(idx: jax.Array, out_cap: int) -> jax.Array:
    """Pad or truncate a compaction index vector to a static capacity."""
    if idx.shape[0] >= out_cap:
        return idx[:out_cap]
    pad = jnp.zeros(out_cap - idx.shape[0], jnp.int32)
    return jnp.concatenate([idx, pad])


@partial(jax.jit, static_argnums=(1, 3))
def _presence_table(build: ColumnarBatch, key: int, first, size: int):
    c = build.columns[key]
    live = c.validity & build.active_mask()
    at = jnp.where(live, c.data.astype(jnp.int64) - first, size)
    return jnp.zeros(size, jnp.bool_).at[at.astype(jnp.int32)].set(
        True, mode="drop")


@partial(jax.jit, static_argnums=(1,))
def _dense_key_stats(build: ColumnarBatch, key: int):
    c = build.columns[key]
    live = c.validity & build.active_mask()
    k = c.data.astype(jnp.int64)
    kmin = jnp.min(jnp.where(live, k, jnp.int64(2**62)))
    kmax = jnp.max(jnp.where(live, k, jnp.int64(-1)))
    return jnp.stack([kmin, kmax, jnp.sum(live.astype(jnp.int64))])


@partial(jax.jit, static_argnums=(1, 2))
def _dense_build_table(build: ColumnarBatch, key: int, size: int):
    c = build.columns[key]
    live = c.validity & build.active_mask()
    k = jnp.where(live, c.data.astype(jnp.int32), size)
    rows = jnp.arange(build.capacity, dtype=jnp.int32)
    tbl = jnp.full(size, -1, jnp.int32)
    tbl = tbl.at[k].set(rows, mode="drop")
    counts = jnp.zeros(size, jnp.int32).at[k].add(1, mode="drop")
    return tbl, jnp.any(counts > 1)


@partial(jax.jit, static_argnums=(3, 4, 5, 7))
def _dense_probe(probe: ColumnarBatch, build: ColumnarBatch, tbl,
                 lkey: int, cond, jt: str, build_matched, bcaps_t=()):
    size = tbl.shape[0]
    cap = probe.capacity
    kc = probe.columns[lkey]
    k64 = kc.data.astype(jnp.int64)
    kvalid = kc.validity & probe.active_mask()
    inb = (k64 >= 0) & (k64 < size)
    safe = jnp.where(kvalid & inb, k64, 0).astype(jnp.int32)
    cand = tbl[safe]
    hit = kvalid & inb & (cand >= 0)
    if cond is not None:
        from spark_rapids_tpu.exprs import eval as EV

        bsafe = jnp.where(hit, cand, 0)
        bcaps = dict(bcaps_t)
        pair_cols = list(probe.columns)
        pair_cols.extend(K.gather_columns(
            build.columns, bsafe, hit,
            [bcaps.get(ci) for ci in range(len(build.columns))]))
        pair = ColumnarBatch(pair_cols, probe.num_rows)
        cv = EV.eval_expr(cond, EV.EvalContext(pair))
        hit = hit & cv.data & cv.validity
    if jt in ("left", "full"):
        pi = jnp.arange(cap, dtype=jnp.int32)
        bi = jnp.where(hit, cand, -1)
        n_out = probe.num_rows
    else:
        pi, n_out = K.filter_indices(hit, probe.active_mask())
        row_live = jnp.arange(cap, dtype=jnp.int32) < n_out
        bi = jnp.where(row_live, cand[jnp.where(row_live, pi, 0)], -1)
    if jt in ("right", "full"):
        new_matched = build_matched.at[
            jnp.where(hit, cand, build.capacity)].set(True, mode="drop")
    else:
        new_matched = build_matched
    return pi, bi, hit, n_out, new_matched


def _null_column(dtype: T.DataType, capacity: int) -> DeviceColumn:
    if (isinstance(dtype, T.DecimalType)
            and dtype.precision > T.DecimalType.MAX_LONG_DIGITS):
        z = jnp.zeros(capacity, jnp.int64)
        return DeviceColumn(dtype, z, jnp.zeros(capacity, jnp.bool_),
                            data2=z)
    if dtype.fixed_width:
        return DeviceColumn(
            dtype, jnp.zeros(capacity, T.numpy_dtype(dtype)),
            jnp.zeros(capacity, jnp.bool_))
    return DeviceColumn(
        dtype, jnp.zeros(8, jnp.uint8),
        jnp.zeros(capacity, jnp.bool_),
        jnp.zeros(capacity + 1, jnp.int32))


def _append_rows(pi, nver, uidx, un, out_cap):
    """Place uidx[0:un] at positions [nver, nver+un) of pi."""
    j = jnp.arange(uidx.shape[0], dtype=jnp.int32)
    pos = jnp.where(j < un, nver + j, out_cap)  # OOB writes drop
    return pi.at[pos].set(uidx, mode="drop")


# ---------------------------------------------------------------------------
# jitted helpers (module-level for cross-instance compile cache reuse)
# ---------------------------------------------------------------------------


_prepare_build = jax.jit(K.prepare_join_side, static_argnums=1)


@partial(jax.jit, static_argnums=(3, 4, 5))
def _probe_stats(probe, build, jh, lkeys, pstr, bstr):
    """One fused pass: candidate ranges + total + exact string byte needs.

    Byte needs make the later gathers' static byte capacities tight upper
    bounds even under skewed fanout (each candidate pair contributes its real
    row length; build-side sums use a prefix sum over hash-sorted lengths)."""
    lo, cnt, pvalid = K.join_candidate_counts(probe, list(lkeys), jh)
    total = jnp.sum(cnt.astype(jnp.int64))
    pbytes = []
    for i in pstr:
        c = probe.columns[i]
        lens = (c.offsets[1:] - c.offsets[:-1]).astype(jnp.int64)
        pbytes.append(jnp.sum(lens * cnt.astype(jnp.int64)))
    bbytes = []
    for i in bstr:
        c = build.columns[i]
        lens = (c.offsets[1:] - c.offsets[:-1]).astype(jnp.int64)
        pre = jnp.concatenate(
            [jnp.zeros(1, jnp.int64), jnp.cumsum(lens[jh.order])]
        )
        hi = lo + cnt
        bbytes.append(jnp.sum(pre[hi] - pre[lo]))
    return lo, cnt, total, pbytes, bbytes


@partial(jax.jit, static_argnums=(1,))
def _ht_probe_hashes(probe, lkeys):
    """Probe-side 128-bit hash pair + null-key mask for the table probe."""
    ph1 = K.hash_keys(probe, list(lkeys))
    ph2 = K.hash_keys(probe, list(lkeys), variant=1)
    pvalid = probe.active_mask()
    for i in lkeys:
        pvalid = pvalid & probe.columns[i].validity
    return ph1, ph2, pvalid


@partial(jax.jit, static_argnums=(4,))
def _ht_candidate_stats(tbl, slot, ok, probe, pstr):
    """Candidate ranges + totals for the hash-table probe in one pass.

    Returns (lo, cnt, total, ends, probe_max_lens): ``ends`` is the
    candidate prefix sum the chunker cuts into row ranges; the probe string
    max lengths ride along so the host reads everything in one sync."""
    lo, cnt = K.hashtbl_candidate_ranges(tbl, slot, ok)
    c64 = cnt.astype(jnp.int64)
    total = jnp.sum(c64)
    ends = jnp.cumsum(c64)
    pml = [jnp.max(probe.columns[i].offsets[1:]
                   - probe.columns[i].offsets[:-1]) for i in pstr]
    return lo, cnt, total, ends, pml


@partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9, 10))
def _unique_probe(probe, build, tbl, build_matched, lkeys, rkeys, slots,
                  lg_b, cond_bound, jt, bcap_items):
    """Unique-build probe: <=1 match per probe row, static shapes, no host
    sync (kernels.probe_join_table_unique + fused residual condition)."""
    bi, hit = K.probe_join_table_unique(probe, tbl, lkeys, build, rkeys,
                                        slots, lg_b)
    hit = hit & probe.active_mask()
    if cond_bound is not None:
        bcaps = dict(bcap_items)
        bcols = K.gather_columns(
            build.columns, jnp.where(hit, bi, 0), hit,
            [bcaps.get(i) for i in range(len(build.columns))])
        pair = ColumnarBatch(list(probe.columns) + list(bcols),
                             probe.num_rows)
        cres = EV.eval_expr(cond_bound, EV.EvalContext(pair))
        hit = hit & cres.data & cres.validity
    if jt in ("right", "full"):
        new_matched = build_matched.at[
            jnp.where(hit, bi, build.capacity)].set(True, mode="drop")
    else:
        new_matched = build_matched
    return bi, hit, new_matched


@partial(jax.jit, static_argnums=(7, 8, 9, 10, 11, 12))
def _verified_pairs(probe, build, order, lo, cnt, r0, r1, lkeys, rkeys,
                    cond_bound, out_cap, pcap_items, bcap_items):
    """Expand candidates, verify exact key equality (+ residual condition).

    ``order`` maps candidate positions to build rows (JoinHashes.order or
    HashTable.order — both are the same count+offset duplicate layout).
    Only probe rows in [r0, r1) contribute: the chunked gather runs this
    once per row range with the same traced program (r0/r1 ride as traced
    scalars, so chunk boundaries never force a recompile).

    Returns (probe_idx, build_row, n_verified, probe_matched)."""
    pcaps, bcaps = dict(pcap_items), dict(bcap_items)
    rows = jnp.arange(cnt.shape[0], dtype=jnp.int32)
    cnt = jnp.where((rows >= r0) & (rows < r1), cnt, 0)
    probe_c, slot, pair_valid = K.expand_candidates(lo, cnt, out_cap)
    slot_c = jnp.clip(slot, 0, order.shape[0] - 1)
    build_row = order[slot_c]
    ver = pair_valid & K.keys_equal(probe, probe_c, list(lkeys),
                                    build, build_row, list(rkeys))
    if cond_bound is not None:
        pair_cols = list(K.gather_columns(
            probe.columns, probe_c, ver,
            [pcaps.get(i) for i in range(len(probe.columns))]))
        pair_cols += list(K.gather_columns(
            build.columns, build_row, ver,
            [bcaps.get(i) for i in range(len(build.columns))]))
        pair_batch = ColumnarBatch(pair_cols, jnp.int32(out_cap))
        ctx = EV.EvalContext(pair_batch)
        cres = EV.eval_expr(cond_bound, ctx)
        ver = ver & cres.data & cres.validity
    # compact verified pairs to the front
    idx, nver = K.filter_indices(ver, jnp.ones_like(ver))
    pi = probe_c[idx]
    bi = build_row[idx]
    # per-probe-row matched flag
    pmatch_scatter = jnp.zeros(probe.capacity + 1, jnp.bool_)
    pmatch_scatter = pmatch_scatter.at[
        jnp.where(ver, probe_c, probe.capacity)
    ].set(True, mode="drop")
    return pi, bi, nver, pmatch_scatter[: probe.capacity]


# type_support declarations (spark_rapids_tpu.support)
from spark_rapids_tpu.support import ALL_SCALAR, ts  # noqa: E402

HashJoinExec.type_support = ts(
    ALL_SCALAR, note="equi-join keys hashed full-width (incl. strings); "
    "payload columns may be any representable type")
