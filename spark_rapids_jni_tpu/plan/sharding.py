"""Sharding the fused plan across the mesh: Column pytrees as GSPMD leaves.

The whole-plan compiler (plan/compile.py) lowers one query into ONE jitted
XLA program; this module extends that program across the process-wide mesh
(parallel/cluster.get_mesh — the single mesh every subsystem shares):

* **Sharding is a property of the Column pytree, not of operators.** A
  fixed-width column flattens to (data[, validity]) leaves annotated
  ``P(axis)`` — the row axis splits into one contiguous block per device.
  A DICT32 column shards its int32 ``codes`` the same way while the shared
  dictionary (values/ranks children) REPLICATES: every device decodes
  against the same entries, and the dictionary never moves again.
* **Rows pad to a device multiple** (the exchange layer's pattern): pads
  carry ``live = global_row < n`` liveness that conjoins with every filter
  mask and groupby pushdown, so padded rows are arithmetic no-ops.
* **Per-shard cores + XLA-inserted collectives.** Filter/Project evaluate
  locally (embarrassingly row-parallel). GroupBy runs the UNCHANGED
  ``groupby_core`` per shard over decomposed partial aggregates
  (mean -> sum+count; every agg rides a count partial for null semantics),
  ``all_gather``s the G_s partial slots from all D shards, and re-groups
  the D*G_s partial rows with the same stable-lexsort segmented core —
  merging each partial by its exact operator. After that merge the state
  is REPLICATED on every device, and downstream Sort/Limit/Filter run the
  solo lowering verbatim (identical replicated inputs -> identical
  replicated outputs).
* **Bit-identity is a gate, not a hope.** Integer sums/means merge
  exactly (int64 partial sums commute; the final f64 division replicates
  the solo expression bit-for-bit), count/min/max are order-independent,
  and group representatives resolve to the same global first row (shards
  hold contiguous row blocks and both lexsorts are stable). Float
  sum/mean/min/max accumulate in data order, so
  ``sharding_unsupported_reason`` routes those plans to the SOLO fused
  program — never a silently different answer.

``named_sharding`` below is the only sanctioned ``NamedSharding``
constructor in the package (lint rule SRJT014): annotation decisions live
here, next to the pytree layout they describe.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

from ..columnar import dtype as dt
from ..columnar.column import Column, Table
from ..ops.float_bits import f64_bits_from_value
from ..ops.groupby import groupby_core
from ..ops.sort import gather, sort_lanes, stable_lexsort
from ..parallel import cluster
from ..utils.shapes import bucket_size
from . import expr as ex
from .compile import HEAD_SCOPE, node_scope
from .nodes import (Filter, GroupBy, Limit, PlanError, PlanNode, Project,
                    Sort, is_dag, linearize)

_FLOAT_IDS = (dt.TypeId.FLOAT32, dt.TypeId.FLOAT64)


# ---------------------------------------------------------------------------
# mesh plumbing
# ---------------------------------------------------------------------------

def plan_mesh(num_devices: int = 0):
    """The plan layer's mesh — always the process-wide cached instance
    (cluster.get_mesh), so plan, exchange and serving agree on device
    order and axis name by construction."""
    return cluster.get_mesh(num_devices)


def mesh_axis(mesh) -> str:
    return mesh.axis_names[0]


def named_sharding(mesh, spec):
    """THE sanctioned NamedSharding constructor (SRJT014): every sharding
    annotation in the package is minted here so the Column-pytree layout
    rules above stay in one reviewable place."""
    return NamedSharding(mesh, spec)


def row_spec(mesh):
    """Row-axis partition spec for top-level column leaves."""
    return P(mesh_axis(mesh))


def replicated_spec():
    """Replication spec (dictionary children, merged groupby state)."""
    return P()


def stage_leaves(leaves, specs, mesh):
    """Commit flat column leaves to their mesh shardings (device_put is
    idempotent for already-conforming arrays, so retries re-stage free)."""
    return tuple(jax.device_put(a, named_sharding(mesh, s))
                 for a, s in zip(leaves, specs))


def stage_batched(stacked_cols, mesh, rows: int):
    """Row-shard a serving micro-batch: stacked leaves [k, rows] split
    along the ROW axis (axis 1) while everything else — dictionary
    children, scalar-ish leaves, rows not divisible by the mesh —
    replicates. ``jit(vmap(plan))`` then partitions under GSPMD with
    XLA-inserted collectives; per-member semantics are untouched."""
    axis = mesh_axis(mesh)
    nd = int(mesh.devices.size)

    def put(leaf):
        shard = (getattr(leaf, "ndim", 0) >= 2 and leaf.shape[1] == rows
                 and rows % nd == 0)
        spec = P(None, axis) if shard else P()
        return jax.device_put(leaf, named_sharding(mesh, spec))

    return jax.tree_util.tree_map(put, stacked_cols)


# ---------------------------------------------------------------------------
# Column pytree <-> flat sharded leaves
# ---------------------------------------------------------------------------

def _pad_rows(cols: List[Column], n_pad: int) -> List[Column]:
    out = []
    for c in cols:
        if c.size == n_pad:
            out.append(c)
            continue
        k = n_pad - c.size
        data = jnp.concatenate([c.data, jnp.zeros((k,), c.data.dtype)])
        validity = None
        if c.validity is not None:
            validity = jnp.concatenate(
                [c.validity, jnp.zeros((k,), c.validity.dtype)])
        out.append(Column(c.dtype, n_pad, data=data, validity=validity,
                          children=c.children))
    return out


def _flatten_col(col: Column, shard_rows: bool, mesh,
                 leaves: List[Any], specs: Optional[List[Any]]) -> Dict:
    """Append ``col``'s leaves (and their partition specs) and return the
    static rebuild metadata. Top-level data/validity shard by rows;
    children (the DICT32 dictionary) always replicate."""
    row = row_spec(mesh) if shard_rows else replicated_spec()
    meta: Dict[str, Any] = {
        "dtype": col.dtype, "size": col.size,
        "data": col.data is not None,
        "validity": col.validity is not None,
        "offsets": col.offsets is not None,
        "children": [],
    }
    if col.data is not None:
        leaves.append(col.data)
        if specs is not None:
            specs.append(row)
    if col.validity is not None:
        leaves.append(col.validity)
        if specs is not None:
            specs.append(row)
    if col.offsets is not None:
        leaves.append(col.offsets)
        if specs is not None:
            specs.append(replicated_spec())
    for ch in col.children:
        meta["children"].append(_flatten_col(ch, False, mesh, leaves, specs))
    return meta


def _rebuild_col(meta: Dict, it, size: int) -> Column:
    data = next(it) if meta["data"] else None
    validity = next(it) if meta["validity"] else None
    offsets = next(it) if meta["offsets"] else None
    children = tuple(_rebuild_col(m, it, m["size"])
                     for m in meta["children"])
    return Column(meta["dtype"], size, data=data, validity=validity,
                  offsets=offsets, children=children)


def table_layout(table: Table, mesh):
    """(leaves, in_specs, meta, n, per): the table as row-padded flat
    leaves plus the specs and static metadata to rebuild local Columns
    inside the shard body. Deterministic — compile-time and dispatch-time
    calls agree by construction."""
    nd = int(mesh.devices.size)
    n = table.num_rows
    per = -(-max(n, 1) // nd)
    cols = _pad_rows(list(table.columns), per * nd)
    leaves: List[Any] = []
    specs: List[Any] = []
    meta = [_flatten_col(c, True, mesh, leaves, specs) for c in cols]
    return leaves, specs, meta, n, per


def rebuild_outputs(replicated: bool, out_cols, leaves,
                    table: Table) -> List[Column]:
    """Global output Columns from the sharded program's flat leaves.
    Replicated (post-GroupBy) outputs carry every leaf, children
    included; row-sharded outputs carry data/validity only and reattach
    the UNTOUCHED dictionary children from the input table."""
    it = iter(leaves)
    cols: List[Column] = []
    if replicated:
        for m in out_cols:
            cols.append(_rebuild_col(m, it, m["size"]))
        return cols
    for m in out_cols:
        data = next(it)
        validity = next(it) if m["validity"] else None
        children: Tuple[Column, ...] = ()
        if m["children_from"] is not None:
            children = table.columns[m["children_from"]].children
        cols.append(Column(m["dtype"], int(data.shape[0]), data=data,
                           validity=validity, children=children))
    return cols


# ---------------------------------------------------------------------------
# bit-identity gate
# ---------------------------------------------------------------------------

def sharding_unsupported_reason(plan: PlanNode,
                                table: Table) -> Optional[str]:
    """Why this plan can't run SHARDED bit-identically — None when it
    can. Plans gated here still run fused, just on the solo program:
    conservatism costs scale-out, never correctness. (The solo
    ``unsupported_reason`` gate applies before this one.)

    * Float sum/mean accumulate in row order; float min/max resolve
      NaN/-0.0 ties by order. Partial-aggregate merges would reorder
      both, so any non-count aggregation over a float value column stays
      solo. Plan expressions are integer-only (plan/expr.py), so floats
      reach aggs only as raw input columns — tracked through Projects.
    * Sort/Limit before the first GroupBy would need a global row sort
      over sharded state; after a GroupBy the state is replicated and
      the solo lowering runs verbatim.
    * DAG plans (Join nodes) stay solo: a sharded join build would need
      either a replicated build side or a key-partitioned exchange, and
      neither preserves the solo program's probe-row order guarantees
      yet. The solo DAG path still fuses the whole query.
    """
    if is_dag(plan):
        return ("plan is a DAG (Join) — cross-shard join builds are "
                "not partitionable bit-identically; runs solo-fused")
    for i, c in enumerate(table.columns):
        if c.dtype.id in (dt.TypeId.RLE, dt.TypeId.FOR32, dt.TypeId.FOR64):
            # run boundaries and packed bit lanes don't split on row-block
            # boundaries — sharding them means a repack/expand per shard
            # that the sharded lowering doesn't model; runs solo-fused
            return (f"column {i} is {c.dtype.id.value}-encoded — run/"
                    f"packed buffers don't shard on row blocks")
    nodes = linearize(plan)
    is_float = [c.dtype.id in _FLOAT_IDS for c in table.columns]
    for node in nodes[1:]:
        if isinstance(node, Project):
            is_float = [isinstance(e, ex.Col) and is_float[e.index]
                        for e in node.exprs]
        elif isinstance(node, GroupBy):
            for i, op in node.aggs:
                if op != "count" and is_float[i]:
                    return (f"{op} over a float value column is "
                            f"accumulation-order-sensitive across shards")
            return None  # state replicated from here on: solo semantics
        elif isinstance(node, Sort):
            return ("Sort precedes the first GroupBy — a global row sort "
                    "over sharded state")
        elif isinstance(node, Limit):
            return "Limit precedes the first GroupBy"
    return None


# ---------------------------------------------------------------------------
# sharded lowering
# ---------------------------------------------------------------------------

def _slice_col(c: Column, k: int) -> Column:
    v = c.validity[:k] if c.validity is not None else None
    return Column(c.dtype, k, data=c.data[:k], validity=v,
                  children=c.children)


def _sharded_groupby(node: GroupBy, cols: List[Column], row_mask,
                     axis: str, nd: int, per: int, n: int,
                     max_groups: int):
    """Per-shard partial aggregation + all_gather + replicated exact
    merge. Returns (out_cols, live_groups, overflow) with the solo
    contract: G-slot padded replicated columns, live/overflow device
    scalars."""
    G = bucket_size(min(max_groups, n))      # the SOLO slot count
    Gs = bucket_size(min(max_groups, per))   # per-shard slot count
    keys = [cols[i] for i in node.keys]

    # decompose each agg into mergeable partials; every value column
    # rides ONE count partial (global null semantics), mean shares the
    # sum partial with an explicit sum over the same column
    porder: List[Tuple[int, str]] = []
    pindex: Dict[Tuple[int, str], int] = {}

    def need(i: int, op: str) -> int:
        if (i, op) not in pindex:
            pindex[(i, op)] = len(porder)
            porder.append((i, op))
        return pindex[(i, op)]

    for i, op in node.aggs:
        need(i, "count")
        if op in ("sum", "mean"):
            need(i, "sum")
        elif op in ("min", "max"):
            need(i, op)
        elif op != "count":
            raise PlanError(f"unknown aggregation {op}")

    paggs = [(cols[i], op) for i, op in porder]
    pouts, plive, pov = groupby_core(keys, paggs, row_mask, Gs)

    def ag(x):
        g = lax.all_gather(x, axis)          # [nd, ...] shard-major
        return g.reshape((-1,) + g.shape[2:])

    def ag_col(c: Column) -> Column:
        validity = None if c.validity is None else ag(c.validity)
        return Column(c.dtype, nd * Gs, data=ag(c.data), validity=validity,
                      children=c.children)

    gkeys = [ag_col(c) for c in pouts[:len(keys)]]
    gparts = [ag_col(c) for c in pouts[len(keys):]]
    lives = lax.all_gather(plive, axis)      # i32[nd]
    slot_live = (jnp.arange(Gs, dtype=jnp.int32)[None, :]
                 < lives[:, None]).reshape(-1)
    overflow = jnp.any(lax.all_gather(pov, axis))

    # exact merge: the same stable-lexsort segmented core re-groups the
    # nd*Gs partial rows (dead slots mask off via slot_live), each
    # partial merged by its operator — counts merge by summing
    mops = [(c, "sum" if op == "count" else op)
            for (_, op), c in zip(porder, gparts)]
    mouts, mlive, mov = groupby_core(gkeys, mops, slot_live, G)
    overflow = overflow | mov

    def merged(i: int, op: str) -> Column:
        return mouts[len(keys) + pindex[(i, op)]]

    out: List[Column] = list(mouts[:len(keys)])
    for i, op in node.aggs:
        if op == "count":
            # solo count columns carry no validity (0 for all-null groups)
            out.append(Column(dt.INT64, G, data=merged(i, "count").data))
        elif op == "mean":
            # exact replica of _segment_agg_fixed's division: global int64
            # sum / global int64 count, identical expression -> identical
            # f64 bits
            s = merged(i, "sum").data
            cnt = merged(i, "count").data
            m = s / jnp.maximum(cnt, 1).astype(s.dtype)
            out.append(Column(dt.FLOAT64, G, data=f64_bits_from_value(m),
                              validity=cnt > 0))
        else:
            out.append(merged(i, op))
    return out, mlive, overflow


def make_sharded_fn(plan: PlanNode, max_groups: int, mesh,
                    meta, n: int, per: int, out_info: Dict[str, Any]):
    """Build the shard-local whole-plan body for ``shard_map``. Static
    output facts (rebuild metadata, prefix-ness, padded length) drop into
    ``out_info`` during tracing — read them after ``.lower()``."""
    nodes = linearize(plan)
    axis = mesh_axis(mesh)
    nd = int(mesh.devices.size)

    def plan_sharded(*leaves):
        with jax.named_scope(node_scope(0, nodes[0])):
            it = iter(leaves)
            cols = [_rebuild_col(m, it, per) for m in meta]
            gid = (lax.axis_index(axis).astype(jnp.int32) * per
                   + jnp.arange(per, dtype=jnp.int32))
            live_local = gid < n                 # pad-row liveness
        # DICT32 passthrough tracking: a Project of col(i) keeps the
        # input's children tuple by reference, so identity recovers which
        # dictionary to reattach on the host side
        child_src = {id(c.children): i for i, c in enumerate(cols)
                     if c.children}
        mask = None
        live = None
        replicated = False
        prefix = True
        overflow = jnp.asarray(False)
        ncur = per
        for pos, node in enumerate(nodes[1:], 1):
            with jax.named_scope(node_scope(pos, node)):
                if isinstance(node, Filter):
                    keep = ex.predicate_mask(
                        ex.eval_expr(node.predicate, cols))
                    mask = keep if mask is None else mask & keep
                    if replicated:
                        live = jnp.sum(mask, dtype=jnp.int32)
                    prefix = False
                elif isinstance(node, Project):
                    cols = [ex.project_column(e, cols, ncur)
                            for e in node.exprs]
                elif isinstance(node, GroupBy):
                    if not replicated:
                        row_mask = (live_local if mask is None
                                    else (mask & live_local))
                        cols, live, ov = _sharded_groupby(
                            node, cols, row_mask, axis, nd, per, n,
                            max_groups)
                        overflow = overflow | ov
                        replicated = True
                        ncur = bucket_size(min(max_groups, n))
                    else:
                        G = bucket_size(min(max_groups, ncur))
                        keys = [cols[i] for i in node.keys]
                        aggs = [(cols[i], op) for i, op in node.aggs]
                        cols, live, ov = groupby_core(keys, aggs, mask, G)
                        overflow = overflow | ov
                        ncur = G
                    mask = jnp.arange(ncur, dtype=jnp.int32) < live
                    prefix = True
                elif isinstance(node, Sort):
                    if not replicated:
                        raise PlanError(
                            "sharded Sort before GroupBy (gate this plan "
                            "via sharding_unsupported_reason)")
                    keys = [cols[i] for i in node.keys]
                    lanes = sort_lanes(keys, node.ascending,
                                       node.nulls_first)
                    if mask is not None:
                        # dead lane LAST == most significant: live first
                        lanes.append((~mask).astype(jnp.uint8))
                    order = stable_lexsort(lanes)
                    cols = [gather(c, order) for c in cols]
                    if mask is not None:
                        mask = jnp.take(mask, order)
                    prefix = True
                elif isinstance(node, Limit):
                    if not replicated:
                        raise PlanError(
                            "sharded Limit before GroupBy (gate this plan "
                            "via sharding_unsupported_reason)")
                    k = min(node.count, ncur)
                    cols = [_slice_col(c, k) for c in cols]
                    if mask is not None:
                        mask = mask[:k]
                        live = jnp.minimum(live, jnp.int32(k))
                    ncur = k
                else:
                    raise PlanError(
                        f"unknown plan node {type(node).__name__}")

        out_leaves: List[Any] = []
        out_cols_meta: List[Dict] = []
        with jax.named_scope(HEAD_SCOPE):
            if replicated:
                for c in cols:
                    out_cols_meta.append(
                        _flatten_col(c, False, mesh, out_leaves, None))
                mask_out = mask      # never None after a GroupBy
                live_out = live.astype(jnp.int32)
                out_info["prefix"] = prefix
                out_info["n_out"] = ncur
            else:
                # row-sharded outputs: data/validity only; children
                # reattach from the input table on the host side
                for c in cols:
                    out_cols_meta.append({
                        "dtype": c.dtype,
                        "validity": c.validity is not None,
                        "children_from": (child_src.get(id(c.children))
                                          if c.children else None),
                    })
                    out_leaves.append(c.data)
                    if c.validity is not None:
                        out_leaves.append(c.validity)
                mask_out = (live_local if mask is None
                            else (mask & live_local))
                live_out = lax.psum(jnp.sum(mask_out, dtype=jnp.int32),
                                    axis)
                out_info["prefix"] = mask is None  # pads trail unfiltered
                out_info["n_out"] = per * nd
            out_info["replicated"] = replicated
            out_info["has_mask"] = True
            out_info["out_cols"] = out_cols_meta
            head = jnp.stack([live_out, overflow.astype(jnp.int32)])
        return tuple(out_leaves), mask_out, head

    return plan_sharded


def lower_sharded(plan: PlanNode, table: Table, mesh, max_groups: int):
    """jit(shard_map(whole-plan body)) plus its staged example leaves.
    Returns (jitted, staged_leaves, in_specs, out_info, n); ``out_info``
    fills during the caller's ``.lower()`` (tracing is synchronous)."""
    leaves, in_specs, meta, n, per = table_layout(table, mesh)
    out_info: Dict[str, Any] = {}
    fn = make_sharded_fn(plan, max_groups, mesh, meta, n, per, out_info)
    replicated_out = any(isinstance(nd, GroupBy) for nd in linearize(plan))
    spec_cols = replicated_spec() if replicated_out else row_spec(mesh)
    mapped = shard_map(fn, mesh=mesh, in_specs=tuple(in_specs),
                       out_specs=(spec_cols, spec_cols, replicated_spec()),
                       check_vma=False)
    jitted = jax.jit(mapped)
    staged = stage_leaves(leaves, in_specs, mesh)
    return jitted, staged, in_specs, out_info, n
