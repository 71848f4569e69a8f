"""ICI all-to-all exchange + distributed aggregation step.

The TPU-native shuffle for co-scheduled stages: instead of serializing
batches to host shuffle files (the MULTITHREADED path in shuffle/), a stage
that fits one mesh runs as a single SPMD program where repartitioning is
``jax.lax.all_to_all`` over ICI — the role UCX plays in the reference
(shuffle-plugin/.../UCXShuffleTransport; SURVEY.md §2.8 "TPU-native
equivalent").

Round-3 scope: fixed-width + dict-encoded string columns (codes shard over
ICI, dictionaries replicate); the aggregation exchange is WINDOWED — rows
stream in count-prefixed windows of W rows per peer and every received
window is merged into the running aggregation state immediately, so receive
buffering is n_dev*W = 2x local capacity instead of n_dev x local_cap.
This mirrors the reference's bounce-buffer windowing (BufferSendState /
WindowedBlockIterator, shuffle/RapidsShuffleServer.scala) in SPMD form.
"""

from __future__ import annotations

from functools import partial
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.columnar.column import DeviceColumn
from spark_rapids_tpu.exec import kernels as K


def _route_by_hash(key_hash, num_rows, local_cap: int, n_dev: int):
    """Per-target compaction maps: row indices + counts per destination."""
    live = jnp.arange(local_cap, dtype=jnp.int32) < num_rows
    target = (key_hash % jnp.uint64(n_dev)).astype(jnp.int32)
    idx_rows, counts = [], []
    for t in range(n_dev):
        idx_t, cnt_t = K.filter_indices(target == t, live)
        idx_rows.append(idx_t)
        counts.append(cnt_t)
    return jnp.stack(idx_rows), jnp.stack(counts)


def windowed_exchange_merge(part: ColumnarBatch, key_hash, n_keys: int,
                            merge_ops, axis: str, n_dev: int,
                            window: int = 0):
    """Stream partial-agg rows to their hash-owner devices in W-row windows,
    merging each received window into the running aggregation state.

    Receive buffering is (n_dev, W) = 2x local rows (W = 2*local/n_dev).
    The merge scratch holds state_cap + n_dev*W rows so a window can never
    be dropped before merging; if MERGED distinct groups ever exceed the
    scratch (pathological skew beyond 2x local + one window), an overflow
    flag is returned so the caller can raise instead of mis-aggregating.
    One lax.fori_loop round processes one window: the compiled program is
    O(1) in round count.
    """
    local_cap = part.capacity
    W = window or max(2 * local_cap // n_dev, 8)
    rounds = -(-local_cap // W)
    scratch_cap = 2 * local_cap + n_dev * W

    idx, cnt = _route_by_hash(key_hash, part.num_rows, local_cap, n_dev)
    idx_pad = jnp.pad(idx, ((0, 0), (0, rounds * W - idx.shape[1]))) \
        if idx.shape[1] < rounds * W else idx
    ncols = len(part.columns)
    # dtype-stable carry: a dry merge of an empty scratch yields the exact
    # post-merge column dtypes (e.g. count buffers promote to int64)
    dry = _local_partial_agg(
        ColumnarBatch(
            [DeviceColumn(c.dtype, jnp.zeros(scratch_cap, c.data.dtype),
                          jnp.zeros(scratch_cap, jnp.bool_), None,
                          c.dictionary, c.dict_size, c.dict_max_len)
             for c in part.columns], jnp.int32(0)),
        n_keys, merge_ops)
    state_d = tuple(jnp.zeros_like(c.data) for c in dry.columns)
    state_v = tuple(jnp.zeros(scratch_cap, jnp.bool_)
                    for _ in part.columns)

    def round_body(r, carry):
        state_d, state_v, state_n, ovf = carry
        sl = jax.lax.dynamic_slice_in_dim(idx_pad, r * W, W, axis=1)
        cnt_r = jnp.clip(cnt - r * W, 0, W)
        slot_live = jnp.arange(W, dtype=jnp.int32)[None, :] < cnt_r[:, None]
        recv_cnt = jax.lax.all_to_all(cnt_r, axis, 0, 0, tiled=True)
        flat_live = (jnp.arange(W, dtype=jnp.int32)[None, :]
                     < recv_cnt[:, None]).reshape(-1)
        crank = jnp.cumsum(flat_live.astype(jnp.int32)) - 1
        n_recv = jnp.sum(recv_cnt).astype(jnp.int32)
        dst = jnp.where(flat_live, state_n + crank, scratch_cap)
        ovf = ovf | (state_n + n_recv > scratch_cap)
        new_d, new_v = [], []
        for ci in range(ncols):
            c = part.columns[ci]
            send = jnp.where(slot_live, c.data[sl],
                             jnp.zeros_like(c.data)[:1])
            send_v = jnp.where(slot_live, c.validity[sl], False)
            recv = jax.lax.all_to_all(send, axis, 0, 0).reshape(-1)
            recv_v = jax.lax.all_to_all(send_v, axis, 0, 0).reshape(-1)
            new_d.append(state_d[ci].at[dst].set(
                recv.astype(state_d[ci].dtype), mode="drop"))
            new_v.append(state_v[ci].at[dst].set(recv_v, mode="drop"))
        state_n = jnp.minimum(state_n + n_recv, scratch_cap)
        # merge duplicates so the state stays front-packed and small
        sbatch = ColumnarBatch(
            [DeviceColumn(c.dtype, d, v, None, c.dictionary, c.dict_size,
                          c.dict_max_len)
             for c, d, v in zip(part.columns, new_d, new_v)], state_n)
        merged = _local_partial_agg(sbatch, n_keys, merge_ops)
        return (tuple(c.data for c in merged.columns),
                tuple(c.validity for c in merged.columns),
                merged.num_rows, ovf)

    state_d, state_v, state_n, ovf = jax.lax.fori_loop(
        0, rounds, round_body,
        (state_d, state_v, dry.num_rows * 0, jnp.bool_(False)))
    return ColumnarBatch(
        [DeviceColumn(c.dtype, d, v, None, c.dictionary, c.dict_size,
                      c.dict_max_len)
         for c, d, v in zip(part.columns, state_d, state_v)], state_n), ovf


_SEG_OPS = {"sum", "count", "count_all", "min", "max"}


def _local_partial_agg(batch: ColumnarBatch, n_keys: int,
                       ops: Sequence[Tuple[int, str]]) -> ColumnarBatch:
    """Group local rows, produce keys + one buffer column per op."""
    cap = batch.capacity
    if n_keys == 0:
        gi = K.GroupInfo(jnp.arange(cap, dtype=jnp.int32),
                         jnp.zeros(cap, jnp.int32), jnp.int32(1),
                         jnp.zeros(cap, jnp.int32))
    else:
        gi = K.group_rows(batch, list(range(n_keys)))
    active = batch.active_mask()
    contributing = active[gi.perm]
    out_valid = jnp.arange(cap, dtype=jnp.int32) < gi.num_groups
    head_rows = jnp.where(out_valid,
                          gi.perm[jnp.clip(gi.group_starts, 0, cap - 1)], 0)
    out_cols: List[DeviceColumn] = list(K.gather_columns(
        batch.columns[:n_keys], head_rows, out_valid))
    seg_ends = K.segment_ends(gi.group_starts, gi.num_groups, cap)
    for col_i, op in ops:
        assert op in _SEG_OPS, op
        src = batch.columns[col_i]
        data, avalid = K.segment_agg(src.data[gi.perm], src.validity[gi.perm],
                                     contributing, gi.segment_ids, cap, op,
                                     ends=seg_ends, starts=gi.group_starts)
        out_cols.append(DeviceColumn(
            T.LONG if op in ("count", "count_all") else src.dtype,
            jnp.where(out_valid & avalid, data, jnp.zeros_like(data)),
            avalid & out_valid))
    return ColumnarBatch(out_cols, gi.num_groups)


_MERGE = {"sum": "sum", "count": "sum", "count_all": "sum", "min": "min",
          "max": "max"}


def distributed_agg_step(mesh: Mesh, batch: ColumnarBatch, n_keys: int,
                         ops: Sequence[Tuple[int, str]], axis: str = "dp"):
    """One SPMD group-by step: local partial agg -> all-to-all by key hash ->
    local merge. The compiled program contains the whole pipeline; XLA
    schedules the ICI collective against compute.

    ``batch`` must be row-sharded over ``mesh`` (parallel.mesh.shard_batch).
    Returns a row-sharded batch of merged (keys + buffers); each group lives
    on exactly one device (hash-routed), so concatenating partitions yields
    the global result without further merging.
    """
    n_dev = mesh.devices.size
    from spark_rapids_tpu import faults
    faults.check("parallel.exchange", n_dev=n_dev)
    ops = list(ops)
    n_bufs = len(ops)
    merge_ops = [(n_keys + i, _MERGE[op]) for i, (_, op) in enumerate(ops)]

    def step(col_datas, col_valids, num_rows):
        local_cols = [
            DeviceColumn(c.dtype, d, v, None, c.dictionary, c.dict_size,
                         c.dict_max_len)
            for c, d, v in zip(batch.columns, col_datas, col_valids)
        ]
        local = ColumnarBatch(local_cols, num_rows[0])
        part = _local_partial_agg(local, n_keys, ops)
        if n_keys == 0:
            # global agg: tree-reduce buffers with psum/pmin/pmax
            outs, valids = [], []
            for (_, op), c in zip(ops, part.columns):
                red = {"sum": jax.lax.psum, "count": jax.lax.psum,
                       "count_all": jax.lax.psum,
                       "min": jax.lax.pmin, "max": jax.lax.pmax}[op]
                outs.append(red(jnp.where(c.validity, c.data,
                                          _identity(op, c.data)), axis))
                valids.append(jax.lax.pmax(
                    c.validity[: 1].astype(jnp.int32), axis) > 0)
            # one live row on device 0 only
            dev = jax.lax.axis_index(axis)
            n_out = jnp.where(dev == 0, 1, 0).astype(jnp.int32)
            return (tuple(o for o in outs),
                    tuple(jnp.broadcast_to(v, o.shape) for v, o in
                          zip(valids, outs)),
                    n_out[None], jnp.zeros(1, jnp.bool_))
        kh = K.hash_keys(part, list(range(n_keys)))
        merged, ovf = windowed_exchange_merge(part, kh, n_keys, merge_ops,
                                              axis, n_dev)
        return (tuple(c.data for c in merged.columns),
                tuple(c.validity for c in merged.columns),
                merged.num_rows[None], ovf[None])

    spec_cols = tuple(P(axis) for _ in batch.columns)
    fn = shard_map(
        step, mesh=mesh,
        in_specs=(spec_cols, spec_cols, P(axis)),
        out_specs=(tuple(P(axis) for _ in range(n_keys + n_bufs)),
                   tuple(P(axis) for _ in range(n_keys + n_bufs)),
                   P(axis), P(axis)),
        check_vma=False,
    )
    datas = tuple(c.data for c in batch.columns)
    valids = tuple(c.validity for c in batch.columns)
    out_d, out_v, out_n, ovf = jax.jit(fn)(datas, valids, batch.num_rows)
    if bool(np.any(np.asarray(ovf))):
        raise RuntimeError(
            "distributed agg state overflow (skew beyond 2x local groups "
            "per owner) — raise shuffle partitions / use the host shuffle")
    dtypes = ([batch.columns[i].dtype for i in range(n_keys)]
              + [T.LONG if op in ("count", "count_all")
                 else batch.columns[ci].dtype for ci, op in ops])
    cols = []
    for i, (dt, d, v) in enumerate(zip(dtypes, out_d, out_v)):
        src = batch.columns[i] if i < n_keys else None
        if src is not None and src.is_dict:
            # key codes came back; reattach the (replicated) dictionary
            cols.append(DeviceColumn(dt, d, v, None, src.dictionary,
                                     src.dict_size, src.dict_max_len))
        else:
            cols.append(DeviceColumn(dt, d, v))
    return ColumnarBatch(cols, out_n)


def _identity(op: str, data: jax.Array):
    if op in ("sum", "count", "count_all"):
        return jnp.zeros_like(data)
    if op == "min":
        if jnp.issubdtype(data.dtype, jnp.floating):
            return jnp.full_like(data, jnp.inf)
        return jnp.full_like(data, jnp.iinfo(data.dtype).max)
    if jnp.issubdtype(data.dtype, jnp.floating):
        return jnp.full_like(data, -jnp.inf)
    return jnp.full_like(data, jnp.iinfo(data.dtype).min)
