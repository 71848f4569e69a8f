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
    """Compose segment fns into one traced chain returning every
    intermediate live-row count (per-constituent metric attribution)."""
    def body(batch, consts):
        counts = []
        for fn, cst in zip(fns, consts):
            batch = fn(batch, cst)
            counts.append(batch.num_rows)
        return batch, tuple(counts)
    return body


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
        out, counts = body(batch, consts)
        return _as_carry(agg._first_pass(out)), counts
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
            out, counts = body(batch, consts)
            firsts.append(agg._first_pass(out))
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


_STEP_KEYS: set = set()  # streaming-step program keys bound in this process


def counters() -> dict:
    """For obs/gauges.snapshot()."""
    return {"fused_step_programs_total": len(_STEP_KEYS)}


# ---------------------------------------------------------------------------
# segments
# ---------------------------------------------------------------------------


class _OpSeg:
    """A narrow batch_fn operator inside a stage (shape-independent)."""

    __slots__ = ("op", "_fn", "_key")

    def __init__(self, op: TpuExec):
        self.op = op
        fn = op.batch_fn()
        self._fn = lambda batch, _cst, f=fn: f(batch)
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

    def _fall_back(self, partition: int) -> Iterator[ColumnarBatch]:
        self.metrics["numFallbacks"].add(1)
        return self._fallback.execute(partition)

    def _stage_key(self, segs, in_cap: int) -> tuple:
        parts = []
        cap = in_cap
        for seg in segs:
            parts.append(seg.key_part(cap))
            cap = seg.out_cap(cap)
        return ("fused_stage",) + tuple(parts)

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

    def do_execute(self, partition: int) -> Iterator[ColumnarBatch]:
        segs = self._runtime_segments(partition)
        if segs is None:
            yield from self._fall_back(partition)
            return
        if self.agg is not None:
            yield from self._execute_agg(partition, segs)
        else:
            yield from self._execute_plain(partition, segs)

    def _execute_plain(self, partition: int, segs):
        consts = tuple(seg.consts for seg in segs)
        runs = {}
        for batch in self.child.execute(partition):
            cap = batch.capacity
            run = runs.get(cap)
            if run is None:
                fns = self._chain_fns(segs, cap)
                run = shared_jit(self._stage_key(segs, cap),
                                 lambda: _make_body(fns))
                runs[cap] = run
            out, counts = run(batch, consts)
            self.metrics["numFusedBatches"].add(1)
            self._attribute(segs, counts)
            yield out

    def _execute_agg(self, partition: int, segs):
        agg = self.agg
        agg._prepare()
        consts = tuple(seg.consts for seg in segs)
        akey = ("streaming",) + agg._base_key
        carry = None
        it = self.child.execute(partition)
        # seed: the first batch's first-pass output defines the carry's
        # static capacity (its bucket bounds the groups a partition may
        # hold fused — more groups trip the overflow flag -> fallback)
        for batch in it:
            cap = batch.capacity
            key = self._stage_key(segs, cap) + akey + ("seed",)
            fns = self._chain_fns(segs, cap)
            run = shared_jit(key, lambda: _make_seed(fns, agg))
            with _span.task_span("exec:agg-step",
                                 attrs={"batches": 1, "rows": cap}):
                carry, counts = run(batch, consts)
            self.metrics["numFusedBatches"].add(1)
            agg.metrics["numAggBatches"].add(1)
            self._attribute(segs, counts)
            break
        if carry is None:
            yield from self._fall_back(partition)
            return
        # steps: windows of up to agg_window batches of ONE capacity, one
        # dispatch each — chain+first_pass per batch then a single
        # (carry+firsts) concat/merge (the classic operator pays a dispatch
        # per batch plus an end-of-partition 8-way cascade). The window is
        # the conf's and not tuned at run time: every length merges to the
        # same buffers, and each length is a program of that many unrolled
        # bodies to compile
        step = _StepRunner(self, segs, agg, consts, akey, carry)
        window: List[ColumnarBatch] = []
        for batch in it:
            if window and batch.capacity != window[0].capacity:
                step.run(window)
                window = []
            window.append(batch)
            if len(window) == self.agg_window:
                step.run(window)
                window = []
        if window:
            step.run(window)
        # ONE host sync per partition resolves every overflow flag; on
        # overflow the carry holds truncated garbage -> re-run unfused
        if step.flags and any(bool(v) for v in
                              host_get(step.flags, "fused.overflow_flags")):
            yield from self._fall_back(partition)
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
        self.flags: list = []
        self._runs: dict = {}  # (capacity, window length) -> program

    def _program(self, cap: int, length: int):
        run = self._runs.get((cap, length))
        if run is None:
            stage, segs, agg = self.stage, self.segs, self.agg
            # join-probe byte bounds are capacity-dependent: the chain
            # closures are those of the window's batch capacity
            fns = stage._chain_fns(segs, cap)
            key = (self.akey + ("step", self.carry_cap, self.bc_targets,
                                cap, length) + stage._stage_key(segs, cap))
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
