"""Sort operator.

Reference: GpuSortExec (GpuSortExec.scala:144) with full/each-batch modes and
an out-of-core path (:281). TPU-first: sort keys are order-preserving uint64
encodings and the sort is one fused lexsort + gather (kernels.sort_indices);
Spark null ordering and NaN totality are bit tricks, not comparators.

The out-of-core path (sort chunks, split on boundaries, spill pending) plugs
in at the mem/ layer; within-HBM sorts here handle one concatenated partition.
"""

from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import Iterator, List, Optional, Sequence

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, bucket_capacity
from spark_rapids_tpu.exec.base import TpuExec, UnaryExec
from spark_rapids_tpu.exec import kernels as K
from spark_rapids_tpu.exec.aggregate import concat_jit
from spark_rapids_tpu.exprs import expr as E
from spark_rapids_tpu.utils.sync import host_get


@partial(jax.jit, static_argnums=(1, 2))
def _sort_run(batch: ColumnarBatch, specs, path: str = "lex"):
    idx = K.sort_indices(batch, specs, path)
    return K.gather_batch(batch, idx, batch.num_rows)


@partial(jax.jit, static_argnums=(1, 3))
def _topn_run(batch: ColumnarBatch, specs, k, out_cap: int):
    """The ``k`` first rows of the sorted batch, in order, by selection."""
    idx, n = K.topn_indices(batch, specs, k, out_cap)
    return K.gather_batch(batch, idx, n)


@partial(jax.jit, static_argnums=(1, 3))
def _sorted_head(batch: ColumnarBatch, specs, k, out_cap: int):
    """The same rows by a full sort: for a ``k`` past the selection's
    bound (``K.topn_select_max_k``), where the sort moves less."""
    idx = K.sort_indices(batch, specs)
    n = jnp.minimum(k.astype(jnp.int32), batch.num_rows.astype(jnp.int32))
    return K.gather_batch(batch, K.fit_indices(idx, out_cap), n)


def topn_batch(batch: ColumnarBatch, specs, limit: int,
               out_cap: int) -> ColumnarBatch:
    """The ``limit`` first rows of the sorted batch in a batch of
    ``out_cap`` rows: by selection while ``limit`` is within the bound the
    batch's capacity gives, by a full sort past it."""
    run = (_topn_run if limit <= K.topn_select_max_k(batch.capacity)
           else _sorted_head)
    return run(batch, specs, jnp.int32(limit), out_cap)


@partial(jax.jit, static_argnums=(2, 3, 4))
def _merge_gather(merged: ColumnarBatch, pieces, col: int, ascending: bool,
                  nulls_first):
    """Merge-path device merge of presorted pieces: rank every row against
    every other piece with searchsorted on the one-word merge key, scatter
    the ranks into a gather map over the device concat, gather once. No
    re-sort; bit-identical to a stable lexsort of the concatenation
    (kernels.merge_piece_positions ties by piece index then local order,
    exactly the stable-sort outcome). ``merged`` must be the concat of
    ``pieces`` in order (concat_device packs row j of piece p at
    sum(num_rows[:p]) + j)."""
    keys = [K.merge_key_u64(p.columns[col], ascending, nulls_first,
                            p.active_mask()) for p in pieces]
    positions = K.merge_piece_positions(keys)
    src = jnp.zeros(merged.capacity, jnp.int32)
    start = jnp.int32(0)
    total = jnp.int32(0)
    for p, pos in zip(pieces, positions):
        local = jnp.arange(p.capacity, dtype=jnp.int32)
        # padding rows rank past every live row (all-ones sentinel key), so
        # they only touch map slots >= total, which gather_batch masks out
        src = src.at[pos].set(start + local, mode="drop")
        start = start + p.num_rows
        total = total + p.num_rows
    return K.gather_batch(merged, src, total)


def _str_max_words() -> int:
    from spark_rapids_tpu.config import conf as _C
    return _C.STRING_SORT_MAX_WORDS.get(_C.get_active())


@dataclasses.dataclass(frozen=True)
class SortOrder:
    child: E.Expression
    ascending: bool = True
    nulls_first: Optional[bool] = None  # None = Spark default for direction

    def __repr__(self):
        d = "ASC" if self.ascending else "DESC"
        return f"{self.child!r} {d}"


class SortExec(UnaryExec):
    """Sorts each partition (total order per partition).

    A global sort is a range-shuffle (shuffle/) followed by this.
    ``out_of_core=True`` switches to the chunked external sort
    (GpuOutOfCoreSortIterator analog): each input batch is sorted as a run,
    runs are held spillable, and output batches are produced by boundary
    splitting + merge so no step needs the whole partition in HBM."""

    mem_site = "sort-spill"

    def __init__(self, orders: Sequence[SortOrder], child: TpuExec,
                 each_batch: bool = False, out_of_core: bool = False,
                 target_rows: int = 1 << 17, spill_framework=None):
        super().__init__(child)
        self.orders = list(orders)
        self.each_batch = each_batch
        self.out_of_core = out_of_core
        self.target_rows = target_rows
        self.spill_framework = spill_framework
        self._prepared = False
        self._register_metric("sortTimeNs")

    def _prepare(self):
        if self._prepared:
            return
        from spark_rapids_tpu.config import conf as _C
        from spark_rapids_tpu.plan import autotune as AT
        cf = _C.get_active()
        schema = self.child.output_schema
        self._specs = []
        for o in self.orders:
            bound = E.resolve(o.child, schema)
            assert isinstance(bound, E.ColumnRef), (
                "sort keys must be column refs; plan layer pre-projects"
            )
            self._specs.append(
                K.SortSpec(bound.index, o.ascending, o.nulls_first)
            )
        specs = tuple(self._specs)
        # module-level jit + hashable static specs: same-shaped sorts share
        # one compiled kernel across operator instances. String keys widen
        # per batch to the observed max row length (full-width ORDER BY,
        # round 12) — the widened widths are part of the static specs, so
        # width buckets share compiles too.
        self._spec_tuple = specs
        self._has_str = any(schema[s.column].dtype == T.STRING
                            for s in specs)
        key_dtypes = tuple(schema[s.column].dtype for s in specs)
        # radix path: only when the packed encoding actually saves sort
        # operands (packed < flat); both paths are bit-identical, so the
        # autotune dispatcher is free to pick from measured ns/row.
        # radix_plan indexes dtypes by the specs' schema column positions
        all_dtypes = tuple(f.dtype for f in schema)
        plan = K.radix_plan(all_dtypes, specs)
        self._radix_ok = (plan is not None and plan[1] < plan[0]
                          and _C.SORT_RADIX_ENABLED.get(cf))
        # merge-path OOC merge: single key whose full sort key (nulls
        # included) packs into ONE u64 word — the all-ones padding
        # sentinel must stay unreachable
        self._merge_ok = (len(specs) == 1
                          and K.merge_key_bits(key_dtypes[0]) is not None
                          and _C.SORT_MERGE_PATH_ENABLED.get(cf))
        self._family = AT.family_of(str(d) for d in key_dtypes)
        self._prepared = True

    def _batch_specs(self, batch: ColumnarBatch):
        if self._has_str:
            return K.str_key_words(batch, self._spec_tuple, _str_max_words())
        return self._spec_tuple

    def _choose_sort_path(self, cap: int):
        """lex vs radix at this capacity's shape-class (capacity is the
        log2 rows bucket — no device sync). Order-equivalent paths only."""
        from spark_rapids_tpu.plan import autotune as AT
        shape = AT.shape_class(cap, len(self._spec_tuple), self._family)
        if not self._radix_ok:
            return "lex", "default", shape
        return AT.choose("sort", shape, "lex", ("lex", "radix")) + (shape,)

    def _sorted(self, batch: ColumnarBatch, path: str) -> ColumnarBatch:
        if path == "radix":
            K._note_sortwin("sort_radix_total")
        return _sort_run(batch, self._batch_specs(batch), path)

    def node_description(self) -> str:
        return f"TpuSort [{', '.join(map(repr, self.orders))}]"

    def do_execute(self, partition: int) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.plan import autotune as AT
        self._prepare()
        if self.each_batch:
            # peek one batch so the path decision happens at its
            # shape-class (join.py idiom: capacity is static, no sync)
            it = self.child.execute(partition)
            first = next(it, None)
            if first is None:
                return
            path, source, shape = self._choose_sort_path(first.capacity)
            ns0 = self.metrics["sortTimeNs"].value
            rows = 0

            def _batches():
                yield first
                yield from it

            for b in _batches():
                rows += b.capacity
                with self.timer("sortTimeNs"):
                    out = self._sorted(b, path)
                yield out
            AT.record_decision(
                self, "sort", path, source, shape,
                ns=self.metrics["sortTimeNs"].value - ns0, rows=rows)
            return
        if self.out_of_core:
            fw = self.spill_framework
            if fw is None:
                # same-door default: runs shed through the process spill
                # framework under pool pressure like agg buckets and join
                # build state, instead of pinning every run in HBM
                from spark_rapids_tpu.mem.spill import get_framework
                fw = get_framework()
            yield from OutOfCoreSortIterator(
                self.child.execute(partition), tuple(self._specs),
                self.target_rows, fw, node=self)
            return
        batches = list(self.child.execute(partition))
        if not batches:
            return
        ns0 = self.metrics["sortTimeNs"].value
        with self.timer("sortTimeNs"):
            whole = batches[0] if len(batches) == 1 else concat_jit(batches)
            path, source, shape = self._choose_sort_path(whole.capacity)
            out = self._sorted(whole, path)
        yield out
        AT.record_decision(
            self, "sort", path, source, shape,
            ns=self.metrics["sortTimeNs"].value - ns0, rows=whole.capacity)


class TopNExec(SortExec):
    """ORDER BY ... LIMIT k (GpuTakeOrderedAndProjectExec / GpuTopN analog):
    the k first rows of each partition in sort order, never a sort of the
    partition.

    Per input batch one dispatch keeps the batch's k best rows (a row
    outside its batch's k best is outside the partition's); the partial
    results, each sorted and at most k rows in a bucket of k's capacity,
    are concatenated in arrival order and reduced the same way once. Ties
    go to the earlier batch and the lower row, which is what the stable
    sort of the concatenated partition gives, so the rows and their order
    equal ``SortExec`` + limit on every input. A dispatch selects
    (``K.topn_indices``) while k is within ``K.topn_select_max_k`` of the
    batch's capacity and sorts the batch past it: the bound is derived
    there, not configured."""

    def __init__(self, orders: Sequence[SortOrder], limit: int,
                 child: TpuExec, partial: bool = False):
        super().__init__(orders, child)
        self.limit = max(int(limit), 0)
        # partial: a TopNExec above a gather reduces this one's results
        # again, so any split of the rows may run it (the mesh executor
        # lowers it per device)
        self.partial = partial
        self._register_metric("numTopNDispatches")

    def node_description(self) -> str:
        return (f"TpuTopN {self.limit} "
                f"[{', '.join(map(repr, self.orders))}]")

    def _best(self, batch: ColumnarBatch) -> ColumnarBatch:
        from spark_rapids_tpu.obs import span as _span
        out_cap = bucket_capacity(max(self.limit, 1))
        with _span.task_span("exec:topn", attrs={
                "k": self.limit, "rows": batch.capacity,
                "capacity": out_cap}), self.timer("sortTimeNs"):
            out = topn_batch(batch, self._batch_specs(batch), self.limit,
                             out_cap)
        self.metrics["numTopNDispatches"].add(1)
        return out

    def do_execute(self, partition: int) -> Iterator[ColumnarBatch]:
        self._prepare()
        partials = [self._best(b) for b in self.child.execute(partition)]
        if not partials:
            return
        if len(partials) == 1:
            yield partials[0]
            return
        # each partial holds at most k live rows in k's bucket: pack them
        yield self._best(concat_jit(partials, out_capacity=bucket_capacity(
            len(partials) * max(self.limit, 1))))


# ---------------------------------------------------------------------------
# Out-of-core sort (GpuSortExec.scala:281-411, GpuOutOfCoreSortIterator)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnums=1)
def _run_boundary_keys(batch: ColumnarBatch, spec):
    """Coarse primary-order key triple for the FIRST sort spec,
    most-significant first. Any most-significant prefix of the lexsort key
    sequence is a valid coarsening of the total order, so splitting the
    stream at such a boundary preserves global order across emitted batches;
    full order within a batch comes from the final lexsort. Keys are native
    dtypes (float value keys, int32 flags, uint64 string prefixes)."""
    keys = K.sortable_keys(batch.columns[spec.column], spec.ascending,
                           spec.nulls_first)
    rev = list(reversed(keys))  # most significant first
    while len(rev) < 3:
        rev.append(jnp.zeros(batch.capacity, jnp.int32))
    return tuple(rev[:3])


class _SortRun:
    """One sorted run: device batch (optionally spillable) + consumed offset."""

    def __init__(self, batch: ColumnarBatch, keys, framework):
        self.offset = 0
        self.n = int(host_get(batch.num_rows, "sort.run_rows"))
        self.keys = keys  # boundary key triple, most significant first
        if framework is not None:
            from spark_rapids_tpu.mem.spill import SpillableBatch
            self.handle = SpillableBatch(batch, framework)
            self.batch = None
        else:
            self.handle = None
            self.batch = batch

    def get(self) -> ColumnarBatch:
        return self.handle.get() if self.handle is not None else self.batch

    def unpin(self):
        if self.handle is not None:
            self.handle.unpin()

    def close(self):
        if self.handle is not None:
            self.handle.close()


class OutOfCoreSortIterator:
    """Chunked external sort: sort each input batch into a run, then emit
    globally-ordered output batches by picking a boundary key = min over runs
    of each run's t-th remaining key, taking every remaining row <= boundary
    from every run, and merging that bounded merge set — merge-path device
    merge when the key packs into one u64 word, stable re-sort otherwise
    (bit-identical either way; plan/autotune.py picks from measured ns/row).
    The merge set is capped at sort.outOfCore.maxMergeRuns runs: overflow
    runs are pre-merged into combined runs that shed through the spill
    framework instead of growing the per-emit concat."""

    def __init__(self, source, specs, target_rows: int, framework,
                 node=None):
        self.source = source
        self.specs = specs
        self.target_rows = max(int(target_rows), 1)
        self.framework = framework
        self.node = node  # SortExec, for autotune decisions + timers

    def _merge_eligible(self, batch: ColumnarBatch) -> bool:
        from spark_rapids_tpu.config import conf as _C
        if len(self.specs) != 1:
            return False  # full order needs every spec in the merge key
        if not _C.SORT_MERGE_PATH_ENABLED.get(_C.get_active()):
            return False
        dtype = batch.columns[self.specs[0].column].dtype
        return K.merge_key_bits(dtype) is not None

    def _combine(self, pieces: List[ColumnarBatch]):
        """One sorted batch from >= 2 presorted pieces; returns
        (batch, path, source, shape). Paths are order-equivalent."""
        from spark_rapids_tpu.plan import autotune as AT
        merged = pieces[0] if len(pieces) == 1 else concat_jit(pieces)
        fam = AT.family_of(
            str(merged.columns[s.column].dtype) for s in self.specs)
        shape = AT.shape_class(merged.capacity, len(self.specs), fam)
        path, source = "resort", "default"
        if len(pieces) > 1 and self._merge_eligible(merged):
            path, source = AT.choose("sort:ooc", shape, "resort",
                                     ("resort", "merge"))
        if path == "merge":
            s = self.specs[0]
            K._note_sortwin("sort_merge_total")
            return (_merge_gather(merged, pieces, s.column, s.ascending,
                                  s.nulls_first), path, source, shape)
        if len(pieces) == 1:
            return merged, path, source, shape  # a slice of a sorted run
        return (_sort_run(merged, K.str_key_words(merged, self.specs,
                                                  _str_max_words())),
                path, source, shape)

    def __iter__(self) -> Iterator[ColumnarBatch]:
        from spark_rapids_tpu.config import conf as _C
        node = self.node

        def _timed():
            return (node.timer("sortTimeNs") if node is not None
                    else contextlib.nullcontext())

        runs: List[_SortRun] = []
        for b in self.source:
            with _timed():
                sb = _sort_run(b, K.str_key_words(b, self.specs,
                                                  _str_max_words()))
                keys = _run_boundary_keys(sb, self.specs[0])
            K._note_sortwin("sort_runs_total")
            runs.append(_SortRun(sb, keys, self.framework))
        runs = [r for r in runs if r.n > 0]
        if not runs:
            return
        # merge-set cap: pre-merge overflow runs into combined spillable
        # runs so the per-emit merge set stays bounded (satellite: shed
        # through the spill framework instead of growing the concat)
        max_runs = _C.SORT_OOC_MAX_MERGE_RUNS.get(_C.get_active())
        while len(runs) > max_runs:
            group, runs = runs[:max_runs], runs[max_runs:]
            with _timed():
                comb, path, source, shape = self._combine(
                    [r.get() for r in group])
                keys = _run_boundary_keys(comb, self.specs[0])
            for r in group:
                r.unpin()
                r.close()
            if node is not None:
                from spark_rapids_tpu.plan import autotune as AT
                AT.record_decision(node, "sort:ooc", path, source, shape,
                                   rows=comb.capacity)
            runs.insert(0, _SortRun(comb, keys, self.framework))
        t = max(self.target_rows // len(runs), 1)
        dec = None  # last merge decision + accumulated ns/rows
        while runs:
            # boundary = min over runs of the t-th remaining key triple; the
            # host compare only SELECTS the boundary run — the boundary
            # scalars stay on device so comparisons are exact even where the
            # device float representation (double-double on real TPU) does
            # not round-trip through host float64
            picks = [(r, min(r.offset + t - 1, r.n - 1)) for r in runs]
            # one sync for every run's boundary keys, one for the counts
            keys_h = host_get([[k[j] for k in r.keys] for r, j in picks],
                              "sort.boundary_keys")
            bounds = [(tuple(v.item() for v in ks), r, j)
                      for ks, (r, j) in zip(keys_h, picks)]
            _, rb, jb = min(bounds, key=lambda x: x[0])
            bvals = tuple(k[jb] for k in rb.keys)
            counts = host_get([_count_le(r.keys, r.offset, r.n, bvals)
                               for r in runs], "sort.boundary_counts")
            pieces = []
            for r, c in zip(runs, counts):
                c = int(c)
                if c > 0:
                    batch = r.get()
                    # exact byte needs per string column keep emitted pieces
                    # truly bounded (no full-run byte buffers riding along)
                    spans = [col.offsets[r.offset + c] - col.offsets[r.offset]
                             for col in batch.columns
                             if col.offsets is not None]
                    nbytes = iter(host_get(spans, "sort.piece_bytes")
                                  if spans else ())
                    bcaps = tuple(
                        bucket_capacity(max(int(next(nbytes)), 8), 8)
                        if col.offsets is not None else 0
                        for col in batch.columns)
                    pieces.append(_slice_rows(batch, jnp.int32(r.offset),
                                              jnp.int32(c), _cap(c), bcaps))
                    r.unpin()
                    r.offset += c
            runs_left = []
            for r in runs:
                if r.offset >= r.n:
                    r.close()
                else:
                    runs_left.append(r)
            runs = runs_left
            if not pieces:
                continue  # cannot happen (boundary includes >= t rows)
            ns0 = (node.metrics["sortTimeNs"].value if node is not None
                   else 0)
            with _timed():
                out, path, source, shape = self._combine(pieces)
            if node is not None:
                ns = node.metrics["sortTimeNs"].value - ns0
                if dec is None or (path, shape) != dec[:2]:
                    if dec is not None:
                        from spark_rapids_tpu.plan import autotune as AT
                        AT.record_decision(node, "sort:ooc", dec[0],
                                           dec[3], dec[1],
                                           ns=dec[2], rows=dec[4])
                    dec = (path, shape, ns, source, out.capacity)
                else:
                    dec = (path, shape, dec[2] + ns, source,
                           dec[4] + out.capacity)
            yield out
        if dec is not None:
            from spark_rapids_tpu.plan import autotune as AT
            AT.record_decision(node, "sort:ooc", dec[0], dec[3], dec[1],
                               ns=dec[2], rows=dec[4])


def _cap(n: int) -> int:
    return bucket_capacity(n, 16)


@jax.jit
def _count_le(keys, offset, n, bounds):
    """Rows in [offset, n) whose key triple is lexicographically <= bounds."""
    (k0, k1, k2), (b0, b1, b2) = keys, bounds
    i = jnp.arange(k0.shape[0])
    live = (i >= offset) & (i < n)
    le = ((k0 < b0)
          | ((k0 == b0) & (k1 < b1))
          | ((k0 == b0) & (k1 == b1) & (k2 <= b2)))
    return jnp.sum((live & le).astype(jnp.int32))


@partial(jax.jit, static_argnums=(3, 4))
def _slice_rows(batch: ColumnarBatch, start, count, cap: int, byte_caps):
    """Slice rows [start, start+count) into a cap-capacity batch. Only the
    capacity buckets are static — start/count are traced, so all slices of a
    capacity bucket share one compiled kernel."""
    idx = jnp.arange(cap, dtype=jnp.int32) + start
    idx = jnp.clip(idx, 0, batch.capacity - 1)
    row_valid = jnp.arange(cap, dtype=jnp.int32) < count
    cols = K.gather_columns(batch.columns, idx, row_valid,
                            [bc or None for bc in byte_caps])
    return ColumnarBatch(cols, count.astype(jnp.int32))


# type_support declarations (spark_rapids_tpu.support)
from spark_rapids_tpu.support import ORDERABLE, ts  # noqa: E402

TopNExec.type_support = SortExec.type_support = ts(
    ORDERABLE, "string",
    note="string keys widened to str_words words (conf "
    "spark.rapids.tpu.sql.sort.stringKeyMaxWords); payload columns may be "
    "any representable type. Keys other than double/string are additionally "
    "radix-packable (kernels.radix_plan) and, when a single key fits one "
    "u64 word, out-of-core-mergeable (kernels.merge_key_bits) — both "
    "bit-identical to the lexsort path, so they never change typing")
