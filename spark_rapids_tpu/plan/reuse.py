"""Plan-time computation reuse: canonical subtree fingerprints + rewrite.

Reference: Spark's ReuseExchangeAndSubquery (physical rule collapsing
semantically-equal exchange/subquery subtrees into ReusedExchangeExec /
ReusedSubqueryExec) which the plugin relies on to replay one materialized
GpuBroadcastExchangeExec / shuffle stage per plan (SURVEY §2.3/§2.8). This
repo owns its planner, so the rule is rebuilt here and runs in
``Overrides.apply`` right after logical->physical conversion — BEFORE
fusion and prefetch insertion, so fused stages and pipeline lanes see the
rewritten plan.

Fingerprints are *semantic*: expressions are resolved positionally against
the child schema and then scrubbed of attribute names (ColumnRef keeps its
ordinal, Alias output names are cosmetic), so two subtrees equal up to
renaming hash equal — while anything that changes the computed values
(literals, ``_params`` rebuild tuples, partitioner key ordinals, dynamic
pruning filters on a scan) stays in the key. A node whose key cannot be
extracted safely degrades to an identity-opaque key, which can never merge
with anything — unknown operators cost a missed reuse, never a wrong one.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

from spark_rapids_tpu import types as T
from spark_rapids_tpu.exprs import expr as E


# ---------------------------------------------------------------------------
# expression keys
# ---------------------------------------------------------------------------


def _scrub(key):
    """Drop name-carrying scalar entries from a resolved expression
    cache_key: a bound ColumnRef is identified by its ordinal, and an Alias
    only renames. Everything else in the key (literals, ``_params``, dtypes)
    stays — the VERDICT-r5 contract that two programs differing only in a
    non-child parameter must never collide."""
    if not (isinstance(key, tuple) and len(key) == 3):
        return key
    tname, scalars, children = key
    if tname in ("ColumnRef", "Alias"):
        scalars = tuple(p for p in scalars if p[0] != "name")
    return (tname, scalars, tuple(_scrub(c) for c in children))


def _expr_key(expr: E.Expression, schema: T.Schema):
    return _scrub(E.resolve(expr, schema).cache_key())


def _exprs_key(exprs, schema: T.Schema) -> tuple:
    return tuple(_expr_key(e, schema) for e in exprs)


def _partitioner_key(p) -> tuple:
    from spark_rapids_tpu.shuffle.partition import (
        HashPartitioner, RangePartitioner, RoundRobinPartitioner,
        SinglePartitioner)

    if isinstance(p, HashPartitioner):
        return ("hash", p.key_cols, p.num_partitions)
    if isinstance(p, RoundRobinPartitioner):
        return ("rr", p.num_partitions, p.start)
    if isinstance(p, SinglePartitioner):
        return ("single",)
    if isinstance(p, RangePartitioner):
        return ("range", p.key_col, p.ascending, p.nulls_first,
                p.bounds.tobytes())
    raise NotImplementedError(type(p).__name__)


# ---------------------------------------------------------------------------
# plan fingerprints
# ---------------------------------------------------------------------------


def plan_fingerprint(node, memo: Optional[Dict[int, tuple]] = None) -> tuple:
    """Semantic hashable key of a physical subtree; equal keys mean the
    subtrees compute identical data (positionally) from identical sources.
    ``memo`` is keyed by object id so a plan walk is linear."""
    if memo is None:
        memo = {}
    fp = memo.get(id(node))
    if fp is None:
        kids = tuple(plan_fingerprint(c, memo) for c in node.children)
        try:
            local = _local_key(node)
            fp = (type(node).__name__, local, kids)
        except Exception:
            # unknown/unextractable node: identity key — unique, so it can
            # never merge with another subtree (missed reuse, never wrong)
            fp = ("opaque", id(node))
        memo[id(node)] = fp
    return fp


def _local_key(node) -> tuple:
    from spark_rapids_tpu.exec.aggregate import HashAggregateExec
    from spark_rapids_tpu.exec.base import BatchSourceExec
    from spark_rapids_tpu.exec.dpp import ReplayExec
    from spark_rapids_tpu.exec.expand import ExpandExec
    from spark_rapids_tpu.exec.join import HashJoinExec
    from spark_rapids_tpu.exec.misc import (
        CoalesceBatchesExec, GlobalLimitExec, LocalLimitExec, UnionExec)
    from spark_rapids_tpu.exec.project import FilterExec, ProjectExec
    from spark_rapids_tpu.exec.scan import ParquetScanExec
    from spark_rapids_tpu.exec.sort import SortExec
    from spark_rapids_tpu.plan.cache import CachedRelation
    from spark_rapids_tpu.shuffle.aqe import AQEShuffleReadExec
    from spark_rapids_tpu.shuffle.exchange_exec import ShuffleExchangeExec

    if isinstance(node, BatchSourceExec):
        # overrides._device_source_parts memoizes per (table, slicing), so
        # two scans of one in-memory table share the cached BATCH objects
        # (the node copies the outer lists, so compare element identity)
        return ("src", tuple(tuple(id(b) for b in p) for p in node._parts))
    if isinstance(node, CachedRelation):
        return ("cached", id(node._blobs))
    if isinstance(node, ParquetScanExec):
        # dynamic filters change what the scan emits: their build
        # fingerprints are part of the scan's identity
        dyn = tuple(
            (plan_fingerprint(f.build, {}), f.key_index, f.column,
             f.max_values)
            for f in node.dynamic_filters)
        pred = (None if node.predicate is None
                else node.predicate.cache_key())  # file-column names canon
        return ("parquet", tuple(node.paths),
                None if node.columns is None else tuple(node.columns),
                pred, node.n_partitions, dyn)
    if isinstance(node, ProjectExec):
        return ("project", _exprs_key(node.exprs, node.child.output_schema),
                node._ansi)
    if isinstance(node, FilterExec):
        return ("filter", _expr_key(node.condition,
                                    node.child.output_schema), node._ansi)
    if isinstance(node, ExpandExec):
        cs = node.child.output_schema
        return ("expand", tuple(_exprs_key(p, cs) for p in node.projections))
    if isinstance(node, HashAggregateExec):
        cs = node.child.output_schema
        pre = (None if node.pre_filter is None
               else _expr_key(node.pre_filter, cs))
        return ("agg", node.mode, _exprs_key(node.group_exprs, cs),
                _exprs_key(node.agg_exprs, cs), pre)
    if isinstance(node, SortExec):  # covers TopNExec
        cs = node.child.output_schema
        orders = tuple((_expr_key(o.child, cs), o.ascending, o.nulls_first)
                       for o in node.orders)
        return ("sort", orders, node.each_batch, node.out_of_core,
                node.target_rows, getattr(node, "limit", None))
    if isinstance(node, LocalLimitExec):
        return ("llimit", node.limit)
    if isinstance(node, GlobalLimitExec):
        return ("glimit", node.limit, node.offset)
    if isinstance(node, CoalesceBatchesExec):
        return ("coalesce", node.target_rows, node.require_single)
    if isinstance(node, UnionExec):
        return ("union",)
    if isinstance(node, HashJoinExec):  # covers BroadcastHashJoinExec
        ls = node.left.output_schema
        rs = node.right.output_schema
        cond = (None if node.condition is None
                else _expr_key(node.condition,
                               T.Schema(list(ls) + list(rs))))
        return ("join", node.join_type,
                _exprs_key(node.left_keys, ls),
                _exprs_key(node.right_keys, rs),
                cond, node.max_candidate_rows)
    if isinstance(node, ShuffleExchangeExec):
        return ("exchange", _partitioner_key(node.partitioner),
                node.target_batch_rows, id(node.manager))
    if isinstance(node, AQEShuffleReadExec):  # covers SkewAware
        return ("aqeread", node.target_batch_rows)
    if isinstance(node, ReplayExec):
        return ("replay",)
    raise NotImplementedError(type(node).__name__)


# ---------------------------------------------------------------------------
# duplicate discovery
# ---------------------------------------------------------------------------


def _walk_slots(root) -> List[Tuple[object, int, object]]:
    """(parent, child_index, node) triples in DFS pre-order; the root has
    (None, -1)."""
    out: List[Tuple[object, int, object]] = []

    def walk(node, parent, idx):
        out.append((parent, idx, node))
        for i, c in enumerate(node.children):
            walk(c, node, i)

    walk(root, None, -1)
    return out


def _reusable_roots(root, memo) -> Dict[tuple, List[Tuple[object, int, object]]]:
    """Fingerprint groups of reuse-eligible subtree roots: shuffle
    exchanges and materialized broadcast builds (ReplayExec)."""
    from spark_rapids_tpu.exec.dpp import ReplayExec
    from spark_rapids_tpu.shuffle.exchange_exec import ShuffleExchangeExec

    groups: Dict[tuple, List[Tuple[object, int, object]]] = {}
    for parent, idx, node in _walk_slots(root):
        if parent is None:
            continue
        if isinstance(node, (ShuffleExchangeExec, ReplayExec)):
            fp = plan_fingerprint(node, memo)
            groups.setdefault(fp, []).append((parent, idx, node))
    return groups


def _subtree_size(node) -> int:
    return 1 + sum(_subtree_size(c) for c in node.children)


# ---------------------------------------------------------------------------
# the rewrite pass
# ---------------------------------------------------------------------------

_next_reuse_id = [0]


def apply_reuse(root, conf=None):
    """Collapse repeated exchange/broadcast/DPP-subquery subtrees of a
    converted physical plan. Runs before fusion (Overrides.apply). Returns
    the (mutated in place) root."""
    from spark_rapids_tpu.config import conf as C

    if conf is not None and not C.REUSE_ENABLED.get(conf):
        return root

    from spark_rapids_tpu.exec import reuse as R
    from spark_rapids_tpu.exec.dpp import ReplayExec
    from spark_rapids_tpu.shuffle.exchange_exec import ShuffleExchangeExec

    memo: Dict[int, tuple] = {}
    groups = _reusable_roots(root, memo)

    dead: set = set()

    def mark_dead(node):
        dead.add(id(node))
        for c in node.children:
            mark_dead(c)
        # plan-time sampling (range-exchange bounds) may have materialized
        # exchanges inside a replaced subtree: nothing reaches them after
        # the swap (the cleanup walk only sees the live tree), so free
        # their registrations now
        if isinstance(node, ShuffleExchangeExec) and node._reg is not None:
            node.cleanup()

    survivors: Dict[tuple, object] = {}

    # largest subtrees first: deduping an outer repeat subsumes its inner
    # repeats, and the dead-set keeps inner groups from resurrecting them
    ordered = sorted(groups.items(),
                     key=lambda kv: -_subtree_size(kv[1][0][2]))
    for fp, occs in ordered:
        seen_ids: set = set()
        live = []
        for parent, idx, node in occs:
            if id(node) in dead or id(node) in seen_ids:
                continue  # same-object DAG shares are already reused
            seen_ids.add(id(node))
            live.append((parent, idx, node))
        if len(live) < 2:
            continue
        survivor = live[0][2]
        _next_reuse_id[0] += 1
        rid = _next_reuse_id[0]
        survivors[fp] = survivor
        survivor.reuse_id = rid
        if isinstance(survivor, ShuffleExchangeExec):
            entry = R.SharedExchangeEntry()
            entry.retain(len(live))
            survivor._shared = entry
            for parent, idx, node in live[1:]:
                reused = R.ReusedExchangeExec(
                    survivor, node.output_schema, rid, entry)
                parent.children[idx] = reused
                R.note("reuse_exchanges_total")
                # a duplicate already materialized by plan-time sampling:
                # its consumer now reads the survivor instead — credit the
                # avoided write before mark_dead frees the registration
                if node._written:
                    try:
                        sizes = node.manager.partition_sizes(node._reg)
                        R.note("reuse_bytes_saved_total", int(sum(sizes)))
                        reused._counted_write_skip = True
                    except Exception:
                        pass
                mark_dead(node)
        else:  # ReplayExec (broadcast build)
            for parent, idx, node in live[1:]:
                parent.children[idx] = R.ReusedBroadcastExec(
                    survivor, node.output_schema, rid)
                mark_dead(node)
                R.note("reuse_broadcasts_total")

    _dedupe_subqueries(root, memo, dead, survivors)
    _attach_shared_broadcasts(root, memo)
    return root


def _dedupe_subqueries(root, memo, dead, survivors) -> None:
    """DPP filters are subqueries hanging off scans: repoint builds that
    were replaced in the tree at the surviving materialization, and collapse
    filters with identical (build, key, column) to one object so the key
    set is collected once for every consumer scan."""
    from spark_rapids_tpu.exec.scan import ParquetScanExec

    canon: Dict[tuple, object] = {}
    for _, _, node in _walk_slots(root):
        if not isinstance(node, ParquetScanExec) or not node.dynamic_filters:
            continue
        for j, f in enumerate(list(node.dynamic_filters)):
            bfp = plan_fingerprint(f.build, memo)
            key = (bfp, f.key_index, f.column, f.max_values)
            prior = canon.get(key)
            if prior is not None:
                if prior is not f:
                    node.dynamic_filters[j] = prior
                    from spark_rapids_tpu.exec import reuse as R
                    R.note("reuse_subqueries_total")
                continue
            if id(f.build) in dead:
                surv = survivors.get(bfp)
                if surv is not None:
                    f.build = surv
                    from spark_rapids_tpu.exec import reuse as R
                    R.note("reuse_subqueries_total")
            canon[key] = f


def _attach_shared_broadcasts(root, memo) -> None:
    """Broadcast joins whose (build fingerprint, build-key ordinals) match
    share one prepared (build batch, join hashes) pair via a SharedBroadcast
    holder — exec/join_bcast.py adopts it under its build lock, and the
    fused path composes because _fused_build_side goes through the same
    _build_broadcast."""
    from spark_rapids_tpu.exec import reuse as R
    from spark_rapids_tpu.exec.join_bcast import BroadcastHashJoinExec

    by_key: Dict[tuple, List[object]] = {}
    for _, _, node in _walk_slots(root):
        if not isinstance(node, BroadcastHashJoinExec):
            continue
        build = node.right
        target = build.target if isinstance(build, R.ReusedBroadcastExec) \
            else build
        try:
            bfp = plan_fingerprint(target, memo)
            rs = build.output_schema
            idxs = []
            for k in node.right_keys:
                b = E.resolve(k, rs)
                if not isinstance(b, E.ColumnRef):
                    raise NotImplementedError
                idxs.append(b.index)
        except Exception:
            continue
        by_key.setdefault((bfp, tuple(idxs)), []).append(node)
    for joins in by_key.values():
        if len(joins) < 2:
            continue
        holder = R.SharedBroadcast()
        for j in joins:
            j._shared_broadcast = holder
