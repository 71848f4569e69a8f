"""Whole-stage jitted pipeline fusion: one TPU dispatch per pipeline stage.

Every per-operator jit call pays a dispatch floor (not measured on the
current machine; large on the platform this pass was designed against):
a chain of K narrow operators costs K floors *per batch* even when each
body is microseconds of device work.
The reference escapes the analogous launch overhead with codegen'd
whole-stage pipelines (Spark WholeStageCodegenExec) and cuDF's fused AST
kernels; the XLA-native analog is simpler — operators already ARE traced
programs, so a stage is just their composition under ONE ``jax.jit``.

Plan-time pass (``fuse_exec``, called from plan/overrides.py behind
``spark.rapids.tpu.sql.fusion.enabled``) collapses maximal chains of:

- narrow per-batch operators — anything implementing the ``batch_fn()``
  protocol (exec/base.py): project, filter, expand;
- inner hash joins along their PROBE side (the build subtree executes
  normally at stage setup; only the per-batch probe is absorbed, and only
  for the dense / unique-table runtime paths whose probes are pure —
  the general sorted-hash path needs a per-batch host sync and bails to
  the unfused fallback, see HashJoinExec.fused_probe);
- a terminal partial/complete hash aggregate, absorbed in STREAMING form:
  per window of batches one dispatch runs chain -> first_pass per batch
  -> concat(carry, firsts) -> merge_pass -> truncate-to-carry-capacity,
  which also deletes the end-of-partition concat/merge cascade the classic
  operator pays. A window holds batches of one capacity, so a step
  program is keyed by (capacity, window length) (``_StepRunner``): a
  partition of equal batches with a short last one binds at most three.

into a single ``TpuFusedStageExec`` whose per-batch body is one shared_jit
program. Operators that don't implement the protocol are fusion BARRIERS
and keep their per-operator execution (including CPU fallback semantics).

Correctness safety valves — every data-dependent assumption is checked and
degrades to the ORIGINAL operator chain (constituents keep their children
links, so the unfused plan is always re-executable):

- join build turns out duplicate-keyed / oversized -> fallback before any
  output is produced;
- the streaming aggregate's carry overflows its capacity (more groups, or
  more group-key bytes, than the first batch's bucket) -> overflow flags
  are computed ON DEVICE inside the fused body and read back once at
  partition end; on overflow the partition is re-run unfused;
- empty partitions -> fallback (classic empty-input semantics).

``shrink_to_live`` moves from per-operator to the fused-stage boundary:
intermediates never materialize at operator granularity, so only the
stage output is re-bucketed (base.execute applies it when
``shrink_output`` is set, which the stage derives from its constituents).

Metrics: constituents are not structural children but still get per-batch
``numOutputRows``/``numOutputBatches`` attribution — the fused body
returns every intermediate live-row count as auxiliary traced scalars (no
extra dispatch, resolved lazily like base.execute's _pending_rows).
obs/profile.py renders them as ``fused=#<stage>`` rows under the stage.
"""

from __future__ import annotations

import itertools
import threading
from typing import Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, bucket_capacity
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.exec.base import TpuExec, UnaryExec
from spark_rapids_tpu.exec.jit_cache import shared_jit
from spark_rapids_tpu.obs import span as _span
from spark_rapids_tpu.utils.sync import host_get


# ---------------------------------------------------------------------------
# traced helpers
# ---------------------------------------------------------------------------


def _fit(a: jax.Array, n: int) -> jax.Array:
    """Slice or zero-pad a 1-D array to exactly ``n`` elements (static)."""
    if a.shape[0] == n:
        return a
    if a.shape[0] > n:
        return a[:n]
    return jnp.concatenate([a, jnp.zeros(n - a.shape[0], a.dtype)])


def _truncate_buffers(merged: ColumnarBatch, newcap: int,
                      bc_targets: Tuple[int, ...]):
    """Slice a merged aggregation buffer back down to the carry capacity.

    Returns ``(carry, overflow)``: overflow is a traced bool that is True
    when the merged groups no longer fit the carry's static row or string
    byte capacity — the stage then discards the fused result and re-runs
    the partition through the unfused fallback chain, so truncated
    garbage never escapes.
    """
    over = merged.num_rows > newcap
    nkeep = jnp.clip(merged.num_rows, 0, newcap).astype(jnp.int32)
    cols: List[DeviceColumn] = []
    for c, bc in zip(merged.columns, bc_targets):
        if c.offsets is not None:
            over = over | (c.offsets[nkeep] > bc)
            cols.append(DeviceColumn(c.dtype, _fit(c.data, bc),
                                     c.validity[:newcap],
                                     _fit(c.offsets, newcap + 1)))
        else:
            d2 = c.data2[:newcap] if c.data2 is not None else None
            cols.append(DeviceColumn(c.dtype, c.data[:newcap],
                                     c.validity[:newcap], None,
                                     c.dictionary, c.dict_size,
                                     c.dict_max_len, d2))
    return ColumnarBatch(cols, nkeep), over


def _carry_byte_targets(first: ColumnarBatch) -> Tuple[int, ...]:
    """Static per-column byte capacities the streaming carry truncates to.

    Plain string buffer columns get 2x the first batch's byte bucket
    (headroom for later batches with longer group keys); dict-encoded
    columns get the exact worst case after decode (rows * longest entry)
    — concat under trace always decodes, tracer identity can't prove a
    shared dictionary. The overflow flag guards both estimates.
    """
    t = []
    for c in first.columns:
        if c.offsets is not None:
            t.append(bucket_capacity(max(2 * c.byte_capacity, 8), 8))
        elif c.is_dict:
            t.append(bucket_capacity(
                max(first.capacity * max(c.dict_max_len, 1), 8), 8))
        else:
            t.append(0)
    return tuple(t)


def _make_body(fns):
    """Compose segment fns into one traced chain. A fn takes and returns
    ``(batch, live)``: ``live`` is None for a front-packed batch, or the
    mask of the rows a filter kept where it left them in place for the
    segment that consumes them (a join probe or the aggregate's first
    pass: ``_OpSeg``). Returns the last batch, its mask, every
    intermediate live-row count (per-constituent metric attribution), and
    whether a probe that shrinks its output ran out of room."""
    def body(batch, consts):
        live, counts, short = None, [], jnp.bool_(False)
        for fn, cst in zip(fns, consts):
            batch, live, cut = fn(batch, live, cst)
            counts.append(batch.num_rows if live is None
                          else jnp.sum(live).astype(jnp.int32))
            if cut is not None:
                short = short | cut
        return batch, live, tuple(counts), short
    return body


def _packed(batch: ColumnarBatch, live) -> ColumnarBatch:
    """The batch front-packed: the rows of ``live``, in order."""
    if live is None:
        return batch
    from spark_rapids_tpu.exec import kernels as K
    idx, n = K.compact_indices(live, batch.capacity)
    return K.gather_batch(batch, idx, n)


def _make_plain(fns):
    body = _make_body(fns)

    def plain(batch, consts):
        out, live, counts, short = body(batch, consts)
        return _packed(out, live), counts, short
    return plain


def _make_sizing(fns):
    """The chain for its row counts alone: what the compiler keeps of it
    is what the counts depend on (masks, table lookups), not the gathers."""
    body = _make_body(fns)

    def sizing(batch, consts):
        return body(batch, consts)[2]
    return sizing


def _as_carry(first: ColumnarBatch) -> ColumnarBatch:
    """A first-pass result in the form every step returns its carry in:
    dictionary-coded keys decoded (the step's concat decodes them) and
    string buffers fitted to the carry's byte capacities. The seed hands
    the steps this form so that a step program sees ONE carry signature:
    given the raw first pass, the first window's step is a second program
    of the same bodies, compiled and loaded beside the one every later
    window runs."""
    from spark_rapids_tpu.exec import kernels as K
    plain = K.ensure_plain_batch(first)
    # a batch's own groups always fit its own capacities: no overflow here
    return _truncate_buffers(plain, first.capacity,
                             _carry_byte_targets(first))[0]


def _carry_shape(carry: ColumnarBatch) -> Tuple[int, Tuple[int, ...]]:
    """(row capacity, per-column byte capacities) of the seed's carry:
    every step truncates its merged buffers back to exactly this."""
    return carry.capacity, tuple(
        c.byte_capacity if c.offsets is not None else 0
        for c in carry.columns)


def _make_seed(fns, agg):
    body = _make_body(fns)

    def seed(batch, consts):
        out, live, counts, _ = body(batch, consts)
        return _as_carry(agg._first_pass(out, live)), counts
    return seed


def _make_step(fns, agg, carry_cap: int, bc_targets: Tuple[int, ...]):
    """Streaming-aggregate step over a WINDOW of equal-capacity batches:
    one dispatch runs chain -> first_pass for every batch in the window
    (unrolled: one body per batch), then a single (carry + firsts)
    concat/merge — the fused analog of the classic operator's 8-way merge
    cascade, without the per-batch first-pass dispatches or the
    end-of-partition cascade."""
    from spark_rapids_tpu.exec.aggregate import concat_jit
    body = _make_body(fns)

    def step(carry, batches, consts):
        firsts = []
        counts_all = []
        for batch in batches:
            out, live, counts, _ = body(batch, consts)
            firsts.append(agg._first_pass(out, live))
            counts_all.append(counts)
        # named scopes are HLO metadata only (docs/observability.md): the
        # window's concatenate-and-reduce reads as aggwin/* in an op profile
        with jax.named_scope("aggwin.concat"):
            cat = concat_jit([carry] + firsts)
        with jax.named_scope("aggwin.merge"):
            merged = agg._merge_pass(cat)
            carry2, over = _truncate_buffers(merged, carry_cap, bc_targets)
        return carry2, over, tuple(counts_all)
    return step


def _make_partial(fns, agg):
    """A window of the DEFERRED streaming aggregate: chain -> first pass
    for every batch of the window, their buffers packed end to end, and no
    merge. Taken where a probe shrank the chain's output so far that a
    window's first passes fit one source batch (``_execute_agg``): the
    first passes then hold a few thousand
    groups each, a partition's worth fits one buffer, and ONE merge at the
    end (sized by the rows the windows really hold, read with the overflow
    flags) does what a merge into a carry after every window would do nine
    times at the carry's capacity. Returns (buffers, a probe ran short,
    counts)."""
    from spark_rapids_tpu.exec import kernels as K
    from spark_rapids_tpu.exec.aggregate import concat_jit
    body = _make_body(fns)

    def partial(batches, consts):
        firsts, counts_all, short = [], [], jnp.bool_(False)
        for batch in batches:
            out, live, counts, cut = body(batch, consts)
            firsts.append(agg._first_pass(out, live))
            counts_all.append(counts)
            short = short | cut
        with jax.named_scope("aggwin.concat"):
            # room for every first pass: nothing to guess, nothing dropped
            cat = (K.ensure_plain_batch(firsts[0]) if len(firsts) == 1
                   else concat_jit(firsts))
        return cat, short, tuple(counts_all)
    return partial


_STEP_KEYS: set = set()  # streaming-step program keys bound in this process

#: why a partition left its fused stage for the unfused operator chain
FALLBACK_CAUSES = ("join-refused", "carry-overflow", "empty")
_fallback_lock = threading.Lock()
_fallbacks = dict.fromkeys(FALLBACK_CAUSES, 0)


def counters() -> dict:
    """For obs/gauges.snapshot()."""
    with _fallback_lock:
        out = {f"fused_fallback_{c.replace('-', '_')}_total": n
               for c, n in _fallbacks.items()}
    out["fused_fallback_total"] = sum(out.values())
    out["fused_step_programs_total"] = len(_STEP_KEYS)
    return out


# ---------------------------------------------------------------------------
# segments
# ---------------------------------------------------------------------------


class _OpSeg:
    """A narrow batch_fn operator inside a stage (shape-independent).

    A filter that can say which rows it keeps (``mask_fn``) does not
    compact them: it hands the mask on, ANDed with the one it was given,
    to the next segment that consumes one (a join probe, the aggregate's
    first pass, the stage's end), through operators that compute a row
    from that row alone (``row_preserving``: a projection outside ANSI
    mode, which may not raise for a row a filter dropped). Any other
    operator is given its input front-packed."""

    __slots__ = ("op", "_fn", "_key")

    def __init__(self, op: TpuExec):
        self.op = op
        keep = op.mask_fn() if hasattr(op, "mask_fn") else None
        if keep is not None:
            def fn(batch, live, _cst):
                was = batch.active_mask() if live is None else live
                return batch, was & keep(batch), None
        elif getattr(op, "row_preserving", False):
            apply = op.batch_fn()

            def fn(batch, live, _cst):
                return apply(batch), live, None
        else:
            apply = op.batch_fn()

            def fn(batch, live, _cst):
                return apply(_packed(batch, live)), None, None
        self._fn = fn
        self._key = op.batch_fn_key()

    def key_part(self, in_cap: int) -> tuple:
        return self._key

    def out_cap(self, in_cap: int) -> int:
        return self.op.fused_out_cap(in_cap)

    def probe_fn(self, in_cap: int):
        return self._fn

    @property
    def consts(self):
        return ()


class TpuFusedStageExec(UnaryExec):
    """One jitted program per pipeline stage (see module docstring).

    ``segments`` are the absorbed operators in DATA-FLOW order (closest to
    the source first); ``agg`` is an optional terminal partial/complete
    HashAggregateExec absorbed in streaming form. ``fallback`` is the
    original top of the chain — constituents keep their children links, so
    executing it re-runs the exact unfused plan.
    """

    def __init__(self, segments: List[TpuExec], child: TpuExec,
                 agg=None, fallback: Optional[TpuExec] = None,
                 agg_window: int = 7):
        super().__init__(child)
        self.segments = list(segments)
        self.agg = agg
        self.agg_window = max(1, int(agg_window))
        self._fallback = fallback if fallback is not None else (
            agg if agg is not None else segments[-1])
        self.fused_ops = self.segments + ([agg] if agg is not None else [])
        self.shrink_output = (agg is not None or any(
            op.shrink_output for op in self.segments))
        self._register_metric("numFallbacks")
        self._register_metric("numFusedBatches")
        # partition -> the capacity its first join's hits were found to fit
        # (_size), None where they did not; kept with the plan, so a
        # repeated query sizes once
        self._learned: dict = {}

    # -- plan surface ------------------------------------------------------
    @property
    def output_schema(self) -> T.Schema:
        top = self.agg if self.agg is not None else self.segments[-1]
        return top.output_schema

    def node_description(self) -> str:
        names = [type(op).__name__ for op in self.fused_ops]
        return f"TpuFusedStage [{' -> '.join(names)}]"

    def explain(self, indent: int = 0) -> str:
        pad = "  " * indent
        lines = [f"{pad}{'+- ' if indent else ''}{self.node_description()}"]
        for op in reversed(self.fused_ops):
            lines.append("  " * (indent + 1) + f"*  {op.node_description()}")
            # absorbed joins: show the build subtree (it executes for real)
            if len(op.children) == 2:
                lines.append(op.children[1].explain(indent + 2))
        lines.append(self.child.explain(indent + 1))
        return "\n".join(lines)

    # -- execution ---------------------------------------------------------
    def _runtime_segments(self, partition: int):
        """Resolve segments for one partition; joins build their build side
        here and may refuse (general path) -> None means fall back."""
        segs = []
        for op in self.segments:
            if len(op.children) == 2:  # absorbed hash join
                seg = op.fused_probe(partition)
                if seg is None:
                    return None
                segs.append(seg)
            else:
                segs.append(_OpSeg(op))
        return segs

    def _fall_back(self, partition: int,
                   cause: str) -> Iterator[ColumnarBatch]:
        """Re-runs the partition through the unfused operator chain, under
        an ``exec:fused-fallback`` span that lasts until the chain is
        drained, so a request that left its fused stage shows it in its
        own span tree. The span is not installed as the thread's context:
        it stays open across this generator's yields, where the consumer
        runs."""
        self.metrics["numFallbacks"].add(1)
        with _fallback_lock:
            _fallbacks[cause] += 1
        ctx = _span.current()
        sp = (_span.Span("exec:fused-fallback", ctx=ctx,
                         attrs={"cause": cause})
              if _span.enabled() and ctx is not None else None)
        try:
            yield from self._fallback.execute(partition)
        finally:
            if sp is not None:
                sp.finish()

    def _stage_key(self, segs, in_cap: int) -> tuple:
        parts = []
        cap = in_cap
        for seg in segs:
            parts.append(seg.key_part(cap))
            cap = seg.out_cap(cap)
        # "live": the chain's (batch, live) protocol; a program a store
        # kept from before it has another signature under the old key
        return ("fused_stage", "live") + tuple(parts)

    def _chain_fns(self, segs, in_cap: int):
        fns = []
        cap = in_cap
        for seg in segs:
            fns.append(seg.probe_fn(cap))
            cap = seg.out_cap(cap)
        return fns

    def _attribute(self, segs, counts) -> None:
        for seg, n in zip(segs, counts):
            op = seg.op
            op.metrics["numOutputBatches"].add(1)
            op._pending_rows.append(n)
            if len(op._pending_rows) >= 64:
                op.metrics["numOutputRows"].add(
                    sum(int(x) for x in host_get(list(op._pending_rows),
                                                 "metrics.rows")))
                op._pending_rows.clear()

    # -- learned capacities -------------------------------------------------
    # a later batch may hold twice the first batch's hits (and up to the
    # bucket's end) before the partition has to run again unshrunk
    SHRINK_HEADROOM = 2

    def _size(self, partition: int, segs, first: ColumnarBatch,
              consts) -> None:
        """Sets the capacity the first absorbed join shrinks its output to
        (``_FusedJoinProbe.shrink_to``). A selective join leaves a few
        thousand rows in a batch of 2^20, and every gather, sort and
        segment sum after it costs by capacity, not by rows. What it
        leaves is learned, not configured: the first time a partition
        runs, the chain is run over its first batch for its row counts
        alone (one dispatch, one host sync: ``fused.sizing``), and the
        join's hits there, with ``SHRINK_HEADROOM``, name a capacity
        bucket. A later batch that does not fit says so (the programs'
        ``short`` flag): what was cut is run again unshrunk and the stage
        stops shrinking that partition. The learned capacity stays with
        the plan (``_learned``), which a repeated query reuses."""
        at = next((i for i, seg in enumerate(segs)
                   if hasattr(seg, "shrink_to")), None)
        if at is None:
            return
        if partition not in self._learned:
            cap = first.capacity
            fns = self._chain_fns(segs, cap)
            run = shared_jit(self._stage_key(segs, cap) + ("sizing",),
                             lambda: _make_sizing(fns))
            hits = int(host_get(run(first, consts)[at], "fused.sizing"))
            in_cap = cap
            for seg in segs[:at]:
                in_cap = seg.out_cap(in_cap)
            want = bucket_capacity(max(self.SHRINK_HEADROOM * hits, 1024))
            self._learned[partition] = want if want * 2 <= in_cap else None
        segs[at].shrink_to = self._learned[partition]

    def _unlearn(self, partition: int, segs) -> None:
        self._learned[partition] = None
        for seg in segs:
            if hasattr(seg, "shrink_to"):
                seg.shrink_to = None

    def do_execute(self, partition: int) -> Iterator[ColumnarBatch]:
        segs = self._runtime_segments(partition)
        if segs is None:
            yield from self._fall_back(partition, "join-refused")
            return
        if self.agg is not None:
            yield from self._execute_agg(partition, segs)
        else:
            yield from self._execute_plain(partition, segs)

    def _windows(self, batches) -> Iterator[List[ColumnarBatch]]:
        """Runs of up to ``agg_window`` batches of ONE capacity: what one
        dispatch of the streaming aggregate takes."""
        window: List[ColumnarBatch] = []
        for batch in batches:
            if window and batch.capacity != window[0].capacity:
                yield window
                window = []
            window.append(batch)
            if len(window) == self.agg_window:
                yield window
                window = []
        if window:
            yield window

    def _execute_plain(self, partition: int, segs):
        consts = tuple(seg.consts for seg in segs)
        runs = {}

        def program(cap: int):
            run = runs.get(cap)
            if run is None:
                fns = self._chain_fns(segs, cap)
                run = runs[cap] = shared_jit(self._stage_key(segs, cap),
                                             lambda: _make_plain(fns))
            return run

        def run_all(batches):
            for batch in batches:
                out, counts, short = program(batch.capacity)(batch, consts)
                self.metrics["numFusedBatches"].add(1)
                self._attribute(segs, counts)
                yield out, short

        it = self.child.execute(partition)
        first = next(it, None)
        if first is None:
            return
        self._size(partition, segs, first, consts)
        batches = itertools.chain([first], it)
        if self._learned.get(partition) is None:
            for out, _ in run_all(batches):
                yield out
            return
        # shrunk outputs (half a source batch or less each) wait for the
        # partition's end: ONE host sync reads every probe's ``short`` flag,
        # as the deferred aggregate does. Where a batch's hits did not fit
        # what was learned, the partition runs again at the probes' own
        # capacity, and the stage stops shrinking it
        held = list(run_all(batches))
        if any(bool(v) for v in host_get([short for _, short in held],
                                         "fused.short")):
            self._unlearn(partition, segs)
            runs.clear()
            held = run_all(self.child.execute(partition))
        for out, _ in held:
            yield out

    def _execute_agg(self, partition: int, segs):
        agg = self.agg
        agg._prepare()
        consts = tuple(seg.consts for seg in segs)
        it = self.child.execute(partition)
        first = next(it, None)
        if first is None:
            yield from self._fall_back(partition, "empty")
            return
        self._size(partition, segs, first, consts)
        out_cap = first.capacity
        for seg in segs:
            out_cap = seg.out_cap(out_cap)
        # deferred where a window's first passes and the carry's share fit
        # one source batch together: the buffers kept are then no more
        # than the batches read
        if out_cap * (self.agg_window + 1) <= first.capacity:
            yield from self._execute_deferred(partition, segs, consts,
                                              first, it)
        else:
            yield from self._execute_carried(partition, segs, consts,
                                             first, it)

    def _execute_deferred(self, partition: int, segs, consts, first, it):
        """The streaming aggregate behind a probe that shrank: a window's
        first passes are packed and kept (``_make_partial``), and one merge
        at the end, at the bucket of the rows they hold, gives the groups.
        One host sync a partition, as the carried form has: the probes'
        ``short`` flags and the windows' row counts together."""
        from spark_rapids_tpu.exec.aggregate import concat_jit
        agg = self.agg
        akey = ("streaming",) + agg._base_key
        runs = {}
        partials, shorts = [], []

        def dispatch(window: List[ColumnarBatch]):
            cap, n = window[0].capacity, len(window)
            prog = runs.get((cap, n))
            if prog is None:
                fns = self._chain_fns(segs, cap)
                key = akey + ("partial", cap, n) + self._stage_key(segs, cap)
                prog = runs[(cap, n)] = shared_jit(
                    key, lambda: _make_partial(fns, agg))
                _STEP_KEYS.add(key)
            with _span.task_span("exec:agg-step",
                                 attrs={"batches": n, "rows": n * cap}):
                part, short, counts_all = prog(tuple(window), consts)
            partials.append(part)
            shorts.append(short)
            self.metrics["numFusedBatches"].add(n)
            agg.metrics["numAggBatches"].add(n)
            for counts in counts_all:
                self._attribute(segs, counts)

        for window in self._windows(itertools.chain([first], it)):
            dispatch(window)
        cut, rows = host_get((shorts, [p.num_rows for p in partials]),
                             "fused.overflow_flags")
        if any(bool(v) for v in cut):
            # a batch's hits outgrew what the first batch's had promised:
            # the partition again, carried, at the probe's own capacity
            self._unlearn(partition, segs)
            yield from self._execute_agg(partition, segs)
            return
        total = sum(int(n) for n in rows)
        with _span.task_span("exec:agg-step",
                             attrs={"batches": 0, "rows": total}):
            merged = agg._merge_pass_fn(concat_jit(
                partials, out_capacity=bucket_capacity(max(total, 1))))
            out = (merged if agg.mode == "partial"
                   else agg._final_project_fn(merged))
        agg.metrics["numOutputBatches"].add(1)
        agg._pending_rows.append(out.num_rows)
        yield out

    def _execute_carried(self, partition: int, segs, consts, first, it):
        agg = self.agg
        akey = ("streaming",) + agg._base_key
        # seed: the first batch's first-pass output defines the carry's
        # static capacity (its bucket bounds the groups a partition may
        # hold fused — more groups trip the overflow flag -> fallback)
        cap = first.capacity
        key = self._stage_key(segs, cap) + akey + ("seed",)
        fns = self._chain_fns(segs, cap)
        run = shared_jit(key, lambda: _make_seed(fns, agg))
        with _span.task_span("exec:agg-step",
                             attrs={"batches": 1, "rows": cap}):
            carry, counts = run(first, consts)
        self.metrics["numFusedBatches"].add(1)
        agg.metrics["numAggBatches"].add(1)
        self._attribute(segs, counts)
        # steps: windows of up to agg_window batches of ONE capacity, one
        # dispatch each — chain+first_pass per batch then a single
        # (carry+firsts) concat/merge (the classic operator pays a dispatch
        # per batch plus an end-of-partition 8-way cascade). The window is
        # the conf's and not tuned at run time: every length merges to the
        # same buffers, and each length is a program of that many unrolled
        # bodies to compile
        step = _StepRunner(self, segs, agg, consts, akey, carry)
        for window in self._windows(it):
            step.run(window)
        # ONE host sync per partition resolves every overflow flag; on
        # overflow the carry holds truncated garbage -> re-run unfused
        flags = (host_get(step.flags, "fused.overflow_flags")
                 if step.flags else ())
        if any(bool(v) for v in flags):
            yield from self._fall_back(partition, "carry-overflow")
            return
        carry = step.carry
        out = carry if agg.mode == "partial" else agg._final_project_fn(carry)
        agg.metrics["numOutputBatches"].add(1)
        agg._pending_rows.append(out.num_rows)
        yield out


class _StepRunner:
    """The streaming aggregate's window dispatches over one partition: the
    carry, the overflow flags, and the step program of each (batch
    capacity, window length) met.

    A partition of N equal batches with a short last one binds at most
    three step programs whatever N: the full window's, one for a tail of
    full batches, one for the short batch if it falls into a smaller
    capacity bucket. Capacities that interleave cost a dispatch per run
    of equal capacity, and still one program per (capacity, length)."""

    def __init__(self, stage, segs, agg, consts, akey, carry):
        self.stage, self.segs, self.agg = stage, segs, agg
        self.consts, self.akey = consts, akey
        self.carry = carry
        self.carry_cap, self.bc_targets = _carry_shape(carry)
        self.flags: list = []  # per step: the carry overflowed
        self._runs: dict = {}  # (capacity, window length) -> program

    def _program(self, cap: int, length: int):
        run = self._runs.get((cap, length))
        if run is None:
            stage, segs, agg = self.stage, self.segs, self.agg
            # join-probe byte bounds are capacity-dependent: the chain
            # closures are those of the window's batch capacity
            fns = stage._chain_fns(segs, cap)
            key = (self.akey + ("step", self.carry_cap, self.bc_targets,
                                cap, length)
                   + stage._stage_key(segs, cap))
            carry_cap, bc_targets = self.carry_cap, self.bc_targets
            run = shared_jit(key, lambda: _make_step(
                fns, agg, carry_cap, bc_targets))
            _STEP_KEYS.add(key)
            self._runs[(cap, length)] = run
        return run

    def run(self, window: List[ColumnarBatch]) -> None:
        stage, agg = self.stage, self.agg
        cap, n = window[0].capacity, len(window)
        with _span.task_span("exec:agg-step",
                             attrs={"batches": n, "rows": n * cap}):
            self.carry, over, counts_all = self._program(cap, n)(
                self.carry, tuple(window), self.consts)
        self.flags.append(over)
        stage.metrics["numFusedBatches"].add(n)
        agg.metrics["numAggBatches"].add(n)
        for counts in counts_all:
            stage._attribute(self.segs, counts)


# ---------------------------------------------------------------------------
# plan-time fusion pass
# ---------------------------------------------------------------------------


def _agg_absorbable(op) -> bool:
    from spark_rapids_tpu.exec.aggregate import HashAggregateExec
    if not isinstance(op, HashAggregateExec):
        return False
    if op.mode not in ("partial", "complete"):
        return False  # "final" consumes pre-aggregated buffers
    op._prepare()
    # nested buffer columns would hit concat_jit's host-arrow path, which
    # can't run under trace
    return all(not isinstance(f.dtype, (T.StructType, T.MapType))
               for f in op._buffer_schema())


def _join_absorbable(op) -> bool:
    from spark_rapids_tpu.exec.join import HashJoinExec
    return isinstance(op, HashJoinExec) and op.join_type == "inner"


def fuse_exec(root: TpuExec, min_ops: int = 2,
              agg_window: int = 7) -> TpuExec:
    """Rewrite an exec tree, collapsing maximal fusable chains into
    TpuFusedStageExec nodes. ``min_ops`` is the minimum number of absorbed
    per-batch dispatch sites for a stage to be worth one more compiled
    program (spark.rapids.tpu.sql.fusion.minOperators). An absorbed
    terminal aggregate counts as TWO sites: windowed streaming absorption
    alone replaces ``agg_window`` per-batch first-pass dispatches (plus the
    merge cascade) with one, so even a lone aggregate clears the bar."""

    def try_stage(node: TpuExec):
        agg = None
        cur = node
        if _agg_absorbable(cur):
            agg = cur
            cur = cur.children[0]
        path = []  # top-down
        while True:
            if _join_absorbable(cur):
                path.append(cur)
                cur = cur.children[0]  # descend the probe side
            elif cur.children and len(cur.children) == 1 \
                    and cur.batch_fn() is not None:
                path.append(cur)
                cur = cur.children[0]
            else:
                break
        n_sites = len(path) + (2 if agg is not None else 0)
        if n_sites < min_ops:
            return None
        top = agg if agg is not None else path[0]
        return TpuFusedStageExec(list(reversed(path)), cur,
                                 agg=agg, fallback=top,
                                 agg_window=agg_window)

    def rewrite(node: TpuExec) -> TpuExec:
        stage = try_stage(node)
        if stage is not None:
            stage.children[0] = rewrite(stage.children[0])
            for op in stage.segments:
                if len(op.children) == 2:
                    op.children[1] = rewrite(op.children[1])
            return stage
        node.children[:] = [rewrite(c) for c in node.children]
        return node

    return rewrite(root)


# type_support declarations (spark_rapids_tpu.support)
from spark_rapids_tpu.support import ALL, ts  # noqa: E402

TpuFusedStageExec.type_support = ts(
    ALL, note="fuses already-placed stages; member typing was enforced "
    "when each member was placed")
