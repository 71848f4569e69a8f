"""Compile guard: the smoke path's kernels, AOT-compiled for a described v5e.

The TPU's compiler is installed in the CPU-only sandbox and compiles for a
chip that is described, not attached (`on-chip-measurement` guide, section
2). These tests hand it the kernels `chip_smoke.py` runs, at the column
types the smoke uses (int64 keys, float64 money, date32, dict-coded
strings), so that a kernel the chip's compiler refuses is found here at no
chip time. A compile that passes is not a chip run and says nothing about
results or times.

The topology is described inside a module-scoped fixture of this file
only (never at import, in a skipif, a parametrize argument or
conftest.py): only one process may hold libtpu, and every xdist worker
imports every test file.

Sizes: the scan-shaped kernels compile at 2^16-2^17 rows (2^20 is the slow
size, ROADMAP S2). The sort-bearing ones (grouping, join build, ORDER BY)
compile at 2^12: for the v5e a variadic sort's compile cost is set by its
operand count and jumps with size (q3's ORDER BY measured here: 2.6 s at
2^12 rows, 55 s at 2^14, 294 s at 2^16; one u32 key: 19 s / 22 s / 28 s at
2^16 / 2^18 / 2^20), so at a size this suite can afford the guard holds
their column types and operand sets, not their capacity. A Pallas kernel
is here if and only if ``auto`` selects it on a TPU (exec/kernels.py).
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

import spark_rapids_tpu  # noqa: F401  (x64 on)
from spark_rapids_tpu.columnar.batch import batch_from_arrow
from spark_rapids_tpu.exec import kernels as K

CAP = 1 << 16       # scan-shaped kernels
SORT_CAP = 1 << 12  # sort-bearing kernels (see the module docstring)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure to describe = skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()


def _lineitem(sf, capacity):
    """(batch, column index by name) of a TPC-H lineitem batch as the scan
    makes it: int64 keys, float64 money, date32, dict-coded string flags."""
    from spark_rapids_tpu.bench import tpch
    table = tpch.gen_lineitem(sf, seed=7)
    batch = batch_from_arrow(table)
    assert batch.capacity == capacity
    return batch, {n: i for i, n in enumerate(table.schema.names)}


@pytest.fixture(scope="module")
def lineitem():
    return _lineitem(0.01, CAP)


@pytest.fixture(scope="module")
def lineitem_small():
    return _lineitem(0.0005, SORT_CAP)


def _lower(fn, one_chip, *args):
    """``fn`` traced and lowered over the shapes of ``args`` for the
    described chip."""
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        args)
    return jax.jit(fn).lower(*shapes)


def _compile(fn, one_chip, *args):
    """Lower ``fn`` over the shapes of ``args`` for the described chip and
    compile it: raises what the chip's compiler would raise."""
    return _lower(fn, one_chip, *args).compile()


def test_graft_entry_fused_step(one_chip):
    """filter -> compact -> gather -> group -> segment_agg over int64 keys
    and float64 values: the engine's core XLA step."""
    import __graft_entry__ as g
    step, (batch,) = g.entry()
    assert _compile(step, one_chip, batch) is not None


def test_filter_indices(one_chip):
    keep = jnp.zeros(1 << 17, jnp.bool_)
    assert _compile(K.filter_indices, one_chip, keep, keep) is not None


def test_packed_gather(one_chip, lineitem):
    batch, _ = lineitem
    idx = jnp.zeros(CAP, jnp.int32)
    assert _compile(K.gather_batch, one_chip, batch, idx,
                    jnp.int32(0)) is not None


def test_group_rows_segment_agg(one_chip, lineitem_small):
    """q1's shape: group on two dict-coded strings, sum float64 money."""
    batch, col = lineitem_small
    keys = [col["l_returnflag"], col["l_linestatus"]]
    price = col["l_extendedprice"]

    def step(b):
        gi = K.group_rows(b, keys)
        c = b.columns[price]
        contributing = b.active_mask()[gi.perm]
        ends = K.segment_ends(gi.group_starts, gi.num_groups, b.capacity)
        return K.segment_agg(c.data[gi.perm], c.validity[gi.perm],
                             contributing, gi.segment_ids, b.capacity, "sum",
                             ends=ends, starts=gi.group_starts)

    assert _compile(step, one_chip, batch) is not None


def test_hash_table_build_probe_xla(one_chip):
    h = jnp.zeros(CAP, jnp.uint64)
    valid = jnp.zeros(CAP, jnp.bool_)
    cap = K.hashtbl_capacity(CAP)

    def step(h1, h2, v):
        tbl, overflow = K.build_hash_table(h1, h2, v, cap, 0,
                                           K.HASHTBL_MAX_PROBES)
        slot, hit = K.probe_hash_table(tbl, h1, h2, cap, 0,
                                       K.HASHTBL_MAX_PROBES)
        return slot, hit, overflow

    assert _compile(step, one_chip, h, h, valid) is not None


def test_dense_join_build_probe(one_chip, lineitem_small):
    """q3's join: a direct-address table on an int64 key, probed by the
    fact side."""
    batch, col = lineitem_small
    key = (col["l_orderkey"],)

    def step(b):
        tbl, dup_any, max_bucket = K.build_join_table(b, key)
        bi, hit = K.probe_join_table_unique(b, tbl, key, b, key, 4,
                                            K._join_lg_b(b.capacity))
        return bi, hit, dup_any, max_bucket

    assert _compile(step, one_chip, batch) is not None


def test_variadic_sort(one_chip, lineitem_small):
    """q3's top-N order: float64 descending, date32 ascending."""
    batch, col = lineitem_small
    specs = (K.SortSpec(col["l_extendedprice"], ascending=False),
             K.SortSpec(col["l_shipdate"]))
    assert _compile(lambda b: K.sort_indices(b, specs), one_chip,
                    batch) is not None


@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float64])
def test_segmented_scan_xla(one_chip, dtype):
    v = jnp.zeros(CAP, dtype)
    s = jnp.zeros(CAP, jnp.bool_)
    assert _compile(lambda a, b: K.segmented_scan_xla(a, b, "add"),
                    one_chip, v, s) is not None


@pytest.mark.parametrize("op", ["add", "min", "max"])
@pytest.mark.parametrize("dtype", [jnp.int32, jnp.float32])
def test_segmented_scan_pallas(one_chip, op, dtype):
    """The Pallas kernel ``auto`` selects on a TPU, at the smoke's batch
    size (32 sequential tiles): every op and lane type the dispatch routes
    to it."""
    v = jnp.zeros(1 << 20, dtype)
    s = jnp.zeros(1 << 20, jnp.bool_)
    compiled = _compile(lambda a, b: K.segmented_scan_pallas(a, b, op),
                        one_chip, v, s)
    assert "tpu_custom_call" in compiled.as_text()


def _q1_stage(sf, capacity):
    """(fused stage, its first batch) of the benchmark's Q1 over DECIMAL
    money at ``capacity`` rows a batch."""
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import datagen
    import harness
    from spark_rapids_tpu.exec import fused as F
    from spark_rapids_tpu.plan import from_arrow
    table = datagen.arrow(datagen.make(["lineitem"], sf, 7)["lineitem"])
    df = from_arrow(table, batch_rows=capacity, partitions=1)
    plan = harness.load_by_path("queries", "q1").build(
        {"lineitem": df}).physical_plan()
    stage = plan.children[0]
    assert isinstance(stage, F.TpuFusedStageExec)
    batch = next(iter(stage.child.execute(0)))
    assert batch.capacity == capacity
    return stage, batch


def test_fused_agg_step_q1(one_chip):
    """The benchmark's Q1 (DECIMAL money, 128-bit sums) as the fused stage's
    streaming step (exec/fused.py ``_make_step``): a window of two batches,
    one unrolled body each, one carry merge. The program of cell
    ``sf10_q1_agg1`` at a capacity this suite can afford (its merge pass
    sorts)."""
    from spark_rapids_tpu.exec import fused as F
    stage, batch = _q1_stage(0.0005, SORT_CAP)
    agg = stage.agg
    carry = jax.eval_shape(F._make_seed([], agg), batch, ())[0]
    carry = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), carry)
    step = F._make_step([], agg, *F._carry_shape(carry))
    compiled = _compile(step, one_chip, carry, (batch,) * 2, ())
    assert "fusion" in compiled.as_text()


@pytest.fixture(scope="module")
def q1_seed(one_chip):
    """(traced StableHLO, [(name, shape, op, line)] of the compiled entry
    computation) of Q1's seed program (filter -> dense first pass, which
    does not sort, so 2^16 rows): the program of both cells' first batch."""
    import re
    from spark_rapids_tpu.exec import fused as F
    stage, batch = _q1_stage(0.01, CAP)
    lowered = _lower(F._make_seed([], stage.agg), one_chip, batch, ())
    text = lowered.compile().as_text()
    entry = re.search(r"^ENTRY .*?\{\n(.*?)^\}", text, re.S | re.M).group(1)
    ops = []
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?(\S+) = (.+?) ([\w\-]+)\(", line)
        if m is not None and m.group(3) not in (
                "parameter", "get-tuple-element", "bitcast"):
            ops.append(m.groups() + (line,))
    return lowered.as_text(), ops


def test_dense_first_pass_q1_stores_no_limb_matrix(q1_seed):
    """Q1's first pass over its DECIMAL lanes: the compiled entry
    computation holds no byte-typed array of batch width with a limb axis
    (the int8 limb matrix the MXU contraction once read from HBM, with a
    per-limb relayout and a concatenate in front of it: 3.0 ms a 2^20-row
    batch on the chip against 0.8 ms, PERF.md PR 32), and the reduce leaves
    few batch-width results of any kind: the bytes are made inside the
    contractions."""
    import re
    limb_matrices, reduce_wide = [], []
    for name, shape, _, line in q1_seed[1]:
        dims = re.match(r"(?:s8|u8|pred)\[([\d,]+)\]", shape)
        if dims and str(CAP) in dims.group(1).split(",") \
                and "," in dims.group(1):
            limb_matrices.append(f"{name} {shape}")
        if "agg.reduce" in line and re.search(rf"\b{CAP}\b", shape):
            reduce_wide.append(f"{name} {shape}")
    assert not limb_matrices, limb_matrices
    assert len(reduce_wide) <= 12, reduce_wide
    assert "concatenate" not in " ".join(reduce_wide)


def test_q1_charge_multiplies_wide_by_narrow_without_limbs(q1_seed):
    """Q1's ``charge`` is DECIMAL(32,4) x DECIMAL(16,2), one wide operand:
    the seed program is traced with the 128x64 multiply
    (exec/int128.mul_128x64) and not with ``mul_128_exact``'s 16-bit limb
    engine. As traced it holds no batch-width int64 array with a trailing
    limb axis (the engine's stacks of 8 and 16); as compiled for the v5e its
    entry has three fusions that write batch-width arrays in front of the
    contractions (the id select and the two lane fusions) where the limb
    engine had six, among them a first stage that wrote the limbs to HBM
    for the lane fusion to read: 0.46 ms of a 2^20-row batch's 0.81 on the
    chip against 0.12 of 0.43 (PERF.md PR 34). This is the proof that the
    narrow form engages in the cells' program: the choice is made when the
    program is traced, so no counter could count it."""
    import re
    traced, ops = q1_seed
    assert f"tensor<{CAP}x" in traced
    limb_axis = re.findall(rf"tensor<{CAP}x(?:8|16)xi64>", traced)
    assert not limb_axis, sorted(set(limb_axis))
    wide_fusions = [f"{name} {shape.count(str(CAP))} arrays"
                    for name, shape, op, _ in ops
                    if op == "fusion" and re.search(rf"\[{CAP}\]", shape)]
    assert 1 <= len(wide_fusions) <= 3, wide_fusions


def _sort_operands(stablehlo: str) -> list:
    """Operand count of every ``stablehlo.sort`` of a lowered program."""
    import re
    return [len(m.group(1).split(","))
            for m in re.finditer(r'"stablehlo\.sort"\(([^)]*)\)', stablehlo)]


TOPN_CAP = 1 << 17       # the bucket of Q3's 116k groups at SF10
TOPN_COMPILE_S = 30.0    # measured here: 0.4 s; the sort it replaced: PERF.md


def test_topn_program_compiles_in_seconds_and_does_not_sort(one_chip):
    """ORDER BY revenue desc, o_orderdate, l_orderkey LIMIT 10 over the
    aggregate's output in a 2^17-row bucket (cell ``sf10_q3_join1``): a
    DECIMAL(38,4) key of two 64-bit limbs, a date, an int64. The top-N by
    selection (exec/kernels.py ``topn_indices``) has no sort at all, and
    compiles for the described v5e in well under ``TOPN_COMPILE_S``; the
    full sort the planner used before PR 35 grows with the batch (module
    docstring: 294 s at 2^16 rows with five operands)."""
    import time
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.columnar.batch import ColumnarBatch
    from spark_rapids_tpu.columnar.column import DeviceColumn
    from spark_rapids_tpu.exec import sort as S
    n = TOPN_CAP
    ok = jnp.ones(n, jnp.bool_)
    batch = ColumnarBatch([
        DeviceColumn(T.LONG, jnp.zeros(n, jnp.int64), ok),
        DeviceColumn(T.DATE, jnp.zeros(n, jnp.int32), ok),
        DeviceColumn(T.INT, jnp.zeros(n, jnp.int32), ok),
        DeviceColumn(T.DecimalType(38, 4), jnp.zeros(n, jnp.int64), ok,
                     data2=jnp.zeros(n, jnp.int64))], jnp.int32(n))
    specs = (K.SortSpec(3, ascending=False), K.SortSpec(1), K.SortSpec(0))

    def topn(b, k):
        return S._topn_run.__wrapped__(b, specs, k, 1024)
    lowered = _lower(topn, one_chip, batch, jnp.int32(10))
    assert _sort_operands(lowered.as_text()) == []
    t = time.perf_counter()
    assert lowered.compile() is not None
    assert time.perf_counter() - t < TOPN_COMPILE_S
    # what it replaced, as traced: eleven key words, past the variadic
    # sort's operand cap, so a chain of eleven sorts and ten gathers of the
    # whole batch (float64 money: one variadic sort of five operands)
    old = _lower(lambda b: K.sort_indices(b, specs), one_chip, batch)
    assert len(_sort_operands(old.as_text())) == 11


def test_grouping_and_join_build_sort_two_operands_at_a_time(
        one_chip, lineitem_small):
    """The sorts a fused Q3 stage runs at a batch's capacity (grouping by
    hash in the first pass and the merge, the unique join table's build)
    are chains of ONE two-operand signature (``K.lsd_order``): the TPU
    compiler bills a sort per distinct signature and steeply per operand
    (three u32 keys and a payload 108 s at 2^20 rows against 24 s for one
    key, two u64 keys 214 s at 2^21; PERF.md PR 35), and a cold start of
    ``sf10_q3_join1`` has 1,000 s for everything."""
    batch, col = lineitem_small
    keys = (col["l_orderkey"],)
    group = _lower(lambda b: K.group_rows(b, [col["l_orderkey"],
                                              col["l_shipdate"]]),
                   one_chip, batch)
    assert set(_sort_operands(group.as_text())) == {2}
    build = _lower(lambda b: K.build_join_table.__wrapped__(b, keys),
                   one_chip, batch)
    assert set(_sort_operands(build.as_text())) == {2}
    assert group.compile() is not None and build.compile() is not None


def _no_loop_no_gather(table):
    """The row table finds two rows of one hash pair by comparing the
    slots of a bucket with each other, elementwise: no probe of the table
    by its own build (a ``while`` over pieces of it, a gather each)."""
    text = table.as_text()
    assert "stablehlo.while" not in text
    assert "stablehlo.gather" not in text and "dynamic_gather" not in text


def test_fused_q3_stage_programs(one_chip):
    """The benchmark's Q3 (cell ``sf10_q3_join1``) as its fused `lineitem`
    stage runs it, at a capacity this suite can afford: the sizing program
    (the chain for its row counts), a deferred window of two batches
    (filter mask -> projection -> one-gather probe of the unique table ->
    compaction by sort -> sort-based first pass at the learned capacity,
    packed, no merge), and the unique table's two build programs. The
    order keys are shifted past ``join.denseKey.maxDomain`` as SF10's are.
    Every sort in them has two operands (``K.lsd_order``'s signature)."""
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import datagen
    import harness
    from spark_rapids_tpu.exec import fused as F
    from spark_rapids_tpu.plan import from_arrow
    raw = datagen.make(["lineitem", "orders", "customer"], 0.01, 7)
    for t, c in (("lineitem", "l_orderkey"), ("orders", "o_orderkey")):
        raw[t][c] = raw[t][c] + (1 << 26)
    d = {k: from_arrow(datagen.arrow(v), batch_rows=8192, partitions=1)
         for k, v in raw.items()}
    plan = harness.load_by_path("queries", "q3").build(d).physical_plan()
    stage = plan.children[0]
    assert isinstance(stage, F.TpuFusedStageExec) and stage.agg is not None
    batch = next(iter(stage.child.execute(0)))
    segs = stage._runtime_segments(0)
    consts = tuple(seg.consts for seg in segs)
    assert [getattr(s, "kind", None) for s in segs] == [None, None, "unique"]
    stage._size(0, segs, batch, consts)
    assert stage._learned == {0: 1024}
    fns = stage._chain_fns(segs, batch.capacity)
    sizing = _lower(F._make_sizing(fns), one_chip, batch, consts)
    window = _lower(F._make_partial(fns, stage.agg), one_chip,
                    (batch, batch), consts)
    for lowered in (sizing, window):
        assert set(_sort_operands(lowered.as_text())) <= {2}
        assert lowered.compile() is not None
    build = segs[2].build
    slots = _lower(lambda b: K.join_row_slots.__wrapped__(b, (0,)),
                   one_chip, build)
    assert set(_sort_operands(slots.as_text())) == {2}
    assert slots.compile() is not None
    placed = jax.eval_shape(
        lambda b: K.join_row_slots.__wrapped__(b, (0,)), build)[0]
    placed = jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), placed)
    table = _lower(lambda p: K.join_rows_table.__wrapped__(
        p, 4, K.join_rows_lg_b(build.capacity)), one_chip, placed)
    _no_loop_no_gather(table)
    # no array with a short last axis: the TPU pads a last axis to 128
    assert table.compile().memory_analysis().temp_size_in_bytes < 64 << 20


CELL_BUILD_CAP = 1 << 21  # Q3's `orders` build at SF10 (1.46M rows)
CELL_BUILD_SLOTS = 16     # its widest table (seed 2147483659; 8 on most)
# temporaries of the program this one replaced (the build probing its own
# table in sixteen pieces), compiled here at this shape: 570.9 MiB; this
# one's: 562.6, and 560.4 with no twin check at all (PERF.md, PR 36)
CELL_BUILD_TEMP_BYTES = 571 << 20


def test_unique_table_at_the_cells_shape(one_chip):
    """``K.join_rows_table`` at the shape ``sf10_q3_join1`` runs it
    (capacity 2^21, 16 slots: a table of 2^20 buckets of 80 words): on the
    chip it runs beside 10.75 GB of resident tables, and a check that
    wanted 6 GB more once ran the cell out of memory (PERF.md, PR 35)."""
    placed = tuple(jax.ShapeDtypeStruct((CELL_BUILD_CAP,), dt) for dt in (
        jnp.uint64, jnp.uint64, jnp.bool_, jnp.int32, jnp.int32))
    table = _lower(lambda p: K.join_rows_table.__wrapped__(
        p, CELL_BUILD_SLOTS, K.join_rows_lg_b(CELL_BUILD_CAP)),
        one_chip, placed)
    _no_loop_no_gather(table)
    compiled = table.compile()
    assert compiled.memory_analysis().temp_size_in_bytes < CELL_BUILD_TEMP_BYTES
    hlo = compiled.as_text()
    assert " while(" not in hlo and " gather(" not in hlo
