"""Lowering and compilation: one logical plan -> one jitted XLA program.

The lowering walks the linearized plan and traces the existing pure op
cores (plan/expr.py, ops/groupby.groupby_core, ops/sort.sort_lanes +
gather) into a single function of the input column pytree. Inside the
fused program there is no host sync, no guard, and no data-dependent
shape:

* Filter carries a keep-mask instead of compacting (state stays the
  input's static shape);
* GroupBy pads its group axis to ``bucket_size(min(plan.max_groups, n))``
  and reports (live_groups, overflow) as device scalars;
* Sort appends a dead-row lane so masked rows sink to the tail, making
  the live rows a prefix;
* Limit is a static slice (valid only on prefix-compacted state).

The program returns ``(columns, mask, head)`` where ``head =
stack([live, overflow])`` — the executor reads ``head`` with ONE host
sync and trims on the host side. Everything else stays on device.

Caching is two-level: a process-local ``ProgramCache`` keyed on
(plan fingerprint, input shape signature, donation, group budget) holds
the AOT-compiled executable (shape-locked — jax AOT executables reject
other shapes, which is exactly the key), and underneath it jax's
persistent compile cache (``jax_compilation_cache_dir``, set in the package
__init__) makes the miss path a disk hit across process restarts.
"""

from __future__ import annotations

import collections
import dataclasses
import re
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..columnar import dtype as dt
from ..columnar.column import Column, Table
from ..ops.groupby import (groupby_core, groupby_direct_small_core,
                           groupby_direct_wide_core)
from ..ops.join import (join_build_sorted_core, join_probe_direct_core,
                        join_probe_sorted_core)
from ..ops.sort import (gather, select_topk_core, sort_lanes,
                        stable_lexsort)
from ..utils import config
from ..utils.shapes import bucket_size
from ..utils.tracing import timed_range
from . import expr as ex
from .nodes import (Filter, GroupBy, Join, Limit, PlanError, PlanNode,
                    Project, Scan, Sort, fingerprint, linearize, walk)


class PlanMetrics:
    """Compile/execute counters for the whole-plan layer, surfaced in
    bench rows and asserted by the recompile-guard tests. Named ``inc``
    (not ``bump``) on purpose: SRJT008 reserves ``.bump`` for the fault
    domain's fixed counter set."""

    _COUNTERS = ("plan_compiles", "plan_cache_hits", "plan_cache_misses",
                 "plan_executes", "plan_fallbacks", "plan_join_fallbacks",
                 "plan_overflows", "plan_oom_retries", "plan_oom_splits",
                 "plan_oom_pieces", "plan_oom_spill_bytes",
                 "plan_block_sums", "plan_scatter_sums")
    _TIMES = ("compile_s", "execute_s", "prepare_s", "dispatch_s",
              "stage_s", "trim_s")
    RECENT = 512  # additions kept per time counter, for ``recent``

    def __init__(self):
        self._lock = threading.Lock()
        self.reset()

    def reset(self) -> None:
        with self._lock:
            self._c = {k: 0 for k in self._COUNTERS}
            self._t = {k: 0.0 for k in self._TIMES}
            self._recent = {k: collections.deque(maxlen=self.RECENT)
                            for k in self._TIMES}
            self._reasons: Dict[str, int] = {}

    def inc(self, name: str, by: int = 1) -> None:
        with self._lock:
            self._c[name] += by

    def inc_fallback_reason(self, reason: str) -> None:
        """Per-reason fallback label (overflow vs unsupported-node vs
        planner gate ...) so serving metrics can tell fallback causes
        apart; the reason string is a short stable slug, not free text."""
        with self._lock:
            self._reasons[reason] = self._reasons.get(reason, 0) + 1

    def add_time(self, name: str, seconds: float) -> None:
        with self._lock:
            self._t[name] += seconds
            self._recent[name].append(seconds)

    def recent(self, name: str, n: int) -> List[float]:
        """The last ``n`` additions to a time counter, oldest first (at
        most ``RECENT``). The plan-path phases add once per query, so
        these are the latest queries' own times, with no earlier warm-up
        or compile averaged in."""
        with self._lock:
            kept = list(self._recent[name])
        return kept[-n:] if n > 0 else []

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            out: Dict[str, Any] = dict(self._c)
            out.update({k: round(v, 6) for k, v in self._t.items()})
            out["plan_fallback_reasons"] = dict(self._reasons)
            return out


plan_metrics = PlanMetrics()


@dataclasses.dataclass
class CompiledPlan:
    """AOT-compiled fused program plus the static facts the executor
    needs to interpret its output."""

    compiled: Any              # jax.stages.Compiled
    fingerprint: str
    has_mask: bool             # program returns a keep-mask
    prefix: bool               # live rows are a prefix (slice-trim ok)
    n_out: int                 # static (padded) output row count


@dataclasses.dataclass
class CompiledShardedPlan:
    """AOT-compiled GSPMD program (plan/sharding.py lowering) plus the
    facts the sharded executor needs: input leaf specs to re-stage fresh
    tables, and whether outputs are replicated (post-GroupBy) or still
    row-sharded."""

    compiled: Any              # jax.stages.Compiled over flat leaves
    fingerprint: str
    prefix: bool
    n_out: int
    replicated: bool           # outputs replicated vs row-sharded
    out_cols: Any              # static rebuild metadata per output column
    in_specs: Tuple            # PartitionSpec per input leaf
    mesh: Any
    n_rows: int                # global row count the program is locked to


def _shape_key(table: Table) -> Tuple:
    """Input signature component of the cache key: per-column dtype,
    static size, and validity presence — everything that changes the
    traced program. Data values are deliberately absent; encoded columns
    append their ``encoding_cache_key`` component (columnar/encodings.py):
    DICT32 contributes its dictionary fingerprint (the dictionary enters
    the program as a constant-like traced operand, never donated, and the
    fingerprint keeps programs from aliasing across dictionaries), RLE its
    static run structure (run count / value dtype / run-validity — run
    CONTENT is per-batch traced data and stays out of the key), FOR a bare
    encoding tag (width rides dtype.scale, already in the base entry)."""
    from ..columnar.encodings import encoding_cache_key
    key = []
    for c in table.columns:
        ent: Tuple = (c.dtype.id.value, getattr(c.dtype, "scale", 0) or 0,
                      c.size, c.validity is not None)
        key.append(ent + encoding_cache_key(c))
    return tuple(key)


def _slice_col(c: Column, k: int) -> Column:
    if c.dtype.id in (dt.TypeId.RLE, dt.TypeId.FOR32, dt.TypeId.FOR64):
        # static prefix slices don't land on run/byte boundaries; Limit is
        # an output trim, so decode at this declared boundary (SRJT016)
        from ..columnar.encodings import decoded_rows
        return _slice_col(decoded_rows(c), k)
    v = c.validity[:k] if c.validity is not None else None
    return Column(c.dtype, k, data=c.data[:k], validity=v,
                  children=c.children)


def _lower_groupby(node: GroupBy, cols, mask, n: int, dec,
                   max_groups: int):
    """One GroupBy under its planner decision (None = generic), shared by
    the linear and the DAG lowering. Returns ``(cols, live_mask, G,
    prefix, overflow, slot_keys)``. ``slot_keys`` is set only for
    direct_wide, whose output keeps slots in key order (live mask not a
    prefix) and holds None at FD-dropped key positions for the DAG
    lowering to re-probe."""
    strat = dec.strategy if dec is not None else "generic"
    if strat == "generic":
        G = bucket_size(min(max_groups, n))
        keys = [cols[i] for i in node.keys]
        aggs = [(cols[i], op) for i, op in node.aggs]
        out, live, ov = groupby_core(keys, aggs, mask, G)
        return (list(out), jnp.arange(G, dtype=jnp.int32) < live, G, True,
                ov, None)
    if strat == "direct_small":
        kcol = cols[node.keys[0]]
        vi, _ = node.aggs[0]
        value = cols[vi].data.astype(jnp.int64)
        slot_keys, sums, live, bad = groupby_direct_small_core(
            kcol.data.astype(jnp.int64), value, mask,
            dec.lo, dec.span, dec.num_slots, dec.chunk)
        G = dec.num_slots
        m = jnp.arange(G, dtype=jnp.int32) < live
        # a live slot holds >= 1 valid row (the planner picks direct
        # strategies for non-null values only): validity as the generic
        # core reports it
        out = [Column(kcol.dtype, G,
                      data=slot_keys.astype(kcol.dtype.jnp_dtype)),
               Column(dt.INT64, G, data=sums, validity=m)]
        return out, m, G, True, bad, None
    dropped = {e[0] for e in dec.fd_drop}
    kept_pos = next(p for p in range(len(node.keys)) if p not in dropped)
    kcol = cols[node.keys[kept_pos]]
    aggs_in = []
    for i, op in node.aggs:
        v = None if op == "count" else cols[i].data.astype(jnp.int64)
        aggs_in.append((v, op))
    slot_keys, outs, live_mask, _live, bad = groupby_direct_wide_core(
        kcol.data.astype(jnp.int64), tuple(aggs_in), mask,
        dec.lo, dec.span, dec.num_slots, dec.live_agg)
    G = dec.num_slots
    nk = len(node.keys)
    out: list = [None] * (nk + len(node.aggs))
    out[kept_pos] = Column(kcol.dtype, G,
                           data=slot_keys.astype(kcol.dtype.jnp_dtype))
    for j, (_i, op) in enumerate(node.aggs):
        out[nk + j] = Column(dt.INT64, G, data=outs[j],
                             validity=None if op == "count" else live_mask)
    return out, live_mask, G, False, bad, slot_keys


HEAD_SCOPE = "plan.head"


def node_scope(pos: int, node: PlanNode,
               strategy: Optional[str] = None) -> str:
    """The name scope of one plan node's operations in a fused program:
    ``plan.<pos>.<Kind>[.<strategy>]``, ``pos`` the node's place in
    ``walk(plan)`` and ``strategy`` the lowering the planner chose."""
    name = f"plan.{pos}.{type(node).__name__}"
    return name if strategy is None else f"{name}.{strategy}"


def _make_fn(plan: PlanNode, max_groups: int, out_info: Dict[str, Any],
             decisions=None):
    """Build the traceable whole-plan function. Static facts about the
    output (mask presence, prefix-ness, padded length) are discovered
    during tracing and dropped into ``out_info`` — tracing happens
    synchronously inside ``.lower()`` so the caller reads them right
    after. ``decisions`` (planner.PlanDecisions for this plan object)
    picks each GroupBy's strategy; None keeps the generic sort-based
    one."""
    nodes = linearize(plan)
    after = {id(a): b for a, b in zip(nodes, nodes[1:])}

    def plan_fused(cols: Tuple[Column, ...]):
        scan = nodes[0]
        assert isinstance(scan, Scan)
        if len(cols) != scan.ncols:
            raise PlanError(f"plan expects {scan.ncols} columns, "
                            f"got {len(cols)}")
        cols = list(cols)
        n = cols[0].size
        mask: Optional[jnp.ndarray] = None
        live = None                     # device i32; None while mask is None
        prefix = True                   # trivially true with no mask
        overflow = jnp.asarray(False)
        for pos, node in enumerate(nodes[1:], 1):
            dec = None
            if isinstance(node, GroupBy):
                dec = decisions.of(node) if decisions is not None else None
                if dec is not None and dec.strategy == "direct_wide" \
                        and isinstance(after.get(id(node)), Limit):
                    dec = None  # Limit needs the generic prefix layout
            strategy = ((dec.strategy if dec is not None else "generic")
                        if isinstance(node, GroupBy) else None)
            with jax.named_scope(node_scope(pos, node, strategy)):
                if isinstance(node, Filter):
                    keep = ex.predicate_mask(
                        ex.eval_expr(node.predicate, cols))
                    mask = keep if mask is None else mask & keep
                    live = jnp.sum(mask, dtype=jnp.int32)
                    prefix = False
                elif isinstance(node, Project):
                    cols = [ex.project_column(e, cols, n)
                            for e in node.exprs]
                elif isinstance(node, GroupBy):
                    cols, mask, n, prefix, ov, _ = _lower_groupby(
                        node, cols, mask, n, dec, max_groups)
                    overflow = overflow | ov
                    live = jnp.sum(mask, dtype=jnp.int32)
                elif isinstance(node, Sort):
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
                    if mask is not None and not prefix:
                        raise PlanError(
                            "Limit needs prefix-compacted rows — place it "
                            "after a Sort or GroupBy, not directly on a "
                            "Filter")
                    k = min(node.count, n)
                    cols = [_slice_col(c, k) for c in cols]
                    if mask is not None:
                        mask = mask[:k]
                        live = jnp.minimum(live, jnp.int32(k))
                    n = k
                else:
                    raise PlanError(
                        f"unknown plan node {type(node).__name__}")
        out_info["has_mask"] = mask is not None
        out_info["prefix"] = prefix
        out_info["n_out"] = n
        with jax.named_scope(HEAD_SCOPE):
            live_out = (jnp.int32(n) if live is None
                        else live.astype(jnp.int32))
            head = jnp.stack([live_out, overflow.astype(jnp.int32)])
        return tuple(cols), mask, head

    return plan_fused


@dataclasses.dataclass
class _DagState:
    """Per-subtree lowering state: traced columns, carried keep-mask
    (None = all rows live), static lane count, and whether live rows are
    a prefix of the lanes."""

    cols: list
    mask: Optional[jnp.ndarray]
    n: int
    prefix: bool


def _key_values(col: Column) -> jnp.ndarray:
    """int64 join-key lane for an integer or DICT32 (code) column."""
    return col.data.astype(jnp.int64)


def _gather_probe(rc: Column, r_idx: jnp.ndarray, found: jnp.ndarray,
                  how: str) -> Column:
    """Build-side payload gather at probe positions. ``r_idx`` is
    clipped-in-range even for misses, so the gather itself is always
    safe; miss lanes hold garbage that the row mask (inner) or the
    validity bits (left) hide. DICT32 children (dictionary values/ranks)
    stay shared by reference — only codes are row-indexed."""
    if rc.offsets is not None:
        # executor input gating keeps offset columns out of DAG plans
        raise PlanError("offset-based column on a join build side")
    data = (jnp.take(rc.data, r_idx, axis=0)
            if rc.data is not None else None)
    validity = (jnp.take(rc.validity, r_idx)
                if rc.validity is not None else None)
    if how == "left":
        # LEFT OUTER: miss lanes keep the probe row and null the payload.
        # Miss lanes survive into the output (validity-nulled, never
        # mask-dropped), so their data is pinned to dtype-zero — the
        # canonical value the eager interpreter also writes — keeping
        # left-join results bit-identical under the nulls.
        if data is not None:
            f = found.reshape(found.shape + (1,) * (data.ndim - 1))
            data = jnp.where(f, data, jnp.zeros((), data.dtype))
        validity = found if validity is None else (validity & found)
    return Column(rc.dtype, int(r_idx.shape[0]), data=data,
                  validity=validity, children=rc.children)


def _make_dag_fn(plan: PlanNode, decisions, max_groups: int,
                 out_info: Dict[str, Any]):
    """Build the traceable whole-DAG function: multiple input tables,
    Join nodes lowered to build/probe cores, GroupBy/Sort+Limit lowered
    to the planner-picked strategies. Same contract as ``_make_fn`` —
    one function of the input pytree, zero host syncs inside, returns
    ``(columns, mask, head)`` with every advisory-stats claim re-checked
    on device and folded into the overflow flag.

    ``decisions`` is a planner.PlanDecisions for THIS plan object (the
    by_node map keys on node identity); ``aux`` carries one int32
    code-remap array per cross-dictionary join, in ``dict_joins``
    iteration order."""
    aux_pos = {jid: i for i, jid in enumerate(decisions.dict_joins)}
    walk_pos = {id(n): i for i, n in enumerate(walk(plan))}

    def scope(node) -> str:
        dec = decisions.of(node)
        if isinstance(node, GroupBy):
            strategy = dec.strategy if dec is not None else "generic"
        elif isinstance(node, (Join, Sort, Limit)) and dec is not None:
            strategy = dec.strategy
        else:
            strategy = None
        return node_scope(walk_pos[id(node)], node, strategy)

    def plan_dag(tables: Tuple[Tuple[Column, ...], ...],
                 aux: Tuple[jnp.ndarray, ...]):
        overflow = [jnp.asarray(False)]
        # per-join build context for FD reprobe at GroupBy lowering
        join_env: Dict[int, Dict[str, Any]] = {}

        def lower_join(node: Join, ls: _DagState,
                       rs: _DagState) -> _DagState:
            dec = decisions.of(node)
            lkey = ls.cols[node.left_on[0]]
            rkey = rs.cols[node.right_on[0]]
            pk = _key_values(lkey)
            blive = rs.mask
            if rkey.validity is not None:
                blive = (rkey.validity if blive is None
                         else blive & rkey.validity)
            if dec.dict_remap:
                remap = aux[aux_pos[id(node)]]
                nd = int(remap.shape[0])
                if nd:
                    bk = jnp.take(remap, jnp.clip(rkey.data, 0, nd - 1)
                                  ).astype(jnp.int64)
                else:  # empty right dictionary: nothing can match
                    bk = jnp.full(rkey.data.shape, -1, dtype=jnp.int64)
                alive = bk >= 0
                blive = alive if blive is None else blive & alive
            else:
                bk = _key_values(rkey)
            if dec.strategy == "direct":
                r_idx, found, bad = join_probe_direct_core(
                    bk, blive, dec.lo, pk)
                overflow[0] = overflow[0] | bad
            else:
                order, sk, sl, dup = join_build_sorted_core(bk, blive)
                overflow[0] = overflow[0] | dup
                r_idx, found = join_probe_sorted_core(order, sk, sl, pk)
            if lkey.validity is not None:
                found = found & lkey.validity
            join_env[id(node)] = {"dec": dec, "bk": bk, "blive": blive,
                                  "rcols": rs.cols}
            if node.how == "semi":
                m = found if ls.mask is None else ls.mask & found
                return _DagState(list(ls.cols), m, ls.n, False)
            if node.how == "anti":
                # NOT EXISTS: null probe keys never match -> kept
                m = ~found if ls.mask is None else ls.mask & ~found
                return _DagState(list(ls.cols), m, ls.n, False)
            out = list(ls.cols)
            for rc in rs.cols:
                out.append(_gather_probe(rc, r_idx, found, node.how))
            if node.how == "inner":
                m = found if ls.mask is None else ls.mask & found
                return _DagState(out, m, ls.n, False)
            return _DagState(out, ls.mask, ls.n, ls.prefix)  # left

        def fd_reprobe(jid: int, slot_keys: jnp.ndarray):
            """Re-probe a direct join's build at the groupby slot keys —
            the FD-reduction gather that restores a dropped key column."""
            env = join_env[jid]
            bk, lo = env["bk"], env["dec"].lo
            rn = bk.shape[0]
            # inner + not-null payload: every LIVE slot key matched a live
            # in-range build row, so no found-mask is needed here — dead
            # slots gather garbage the live mask already hides
            idx = slot_keys - lo
            return jnp.clip(idx, 0, rn - 1).astype(jnp.int32)

        def lower_groupby(node: GroupBy, st: _DagState) -> _DagState:
            dec = decisions.of(node)
            cols, m, G, prefix, bad, slot_keys = _lower_groupby(
                node, st.cols, st.mask, st.n, dec, max_groups)
            overflow[0] = overflow[0] | bad
            for pos, jid, rloc in (dec.fd_drop if slot_keys is not None
                                   else ()):
                rc = join_env[jid]["rcols"][rloc]
                r_idx = fd_reprobe(jid, slot_keys)
                cols[pos] = Column(rc.dtype, G,
                                   data=jnp.take(rc.data, r_idx, axis=0))
            return _DagState(cols, m, G, prefix)

        def rec(node) -> _DagState:
            """Lower the inputs of ``node``, then the node itself under
            its own name scope (scopes never nest)."""
            if isinstance(node, Scan):
                cols = list(tables[node.input_index])
                if len(cols) != node.ncols:
                    raise PlanError(f"plan expects {node.ncols} columns "
                                    f"for input {node.input_index}, got "
                                    f"{len(cols)}")
                return _DagState(cols, None, cols[0].size, True)
            dec = decisions.of(node)
            if isinstance(node, Sort) and dec is not None \
                    and dec.strategy == "skip":
                return rec(node.child)  # folded into Limit topk
            topk = (isinstance(node, Limit) and dec is not None
                    and dec.strategy == "topk")
            if isinstance(node, Join):
                inputs = (rec(node.left), rec(node.right))
            elif topk:
                inputs = (rec(node.child.child),)
            else:
                inputs = (rec(node.child),)
            with jax.named_scope(scope(node)):
                return lower(node, dec, topk, *inputs)

        def lower(node, dec, topk: bool, st: _DagState,
                  rs: Optional[_DagState] = None) -> _DagState:
            if isinstance(node, Filter):
                keep = ex.predicate_mask(
                    ex.eval_expr(node.predicate, st.cols))
                m = keep if st.mask is None else st.mask & keep
                return _DagState(st.cols, m, st.n, False)
            if isinstance(node, Project):
                cols = [ex.project_column(e, st.cols, st.n)
                        for e in node.exprs]
                return _DagState(cols, st.mask, st.n, st.prefix)
            if isinstance(node, Join):
                return lower_join(node, st, rs)
            if isinstance(node, GroupBy):
                return lower_groupby(node, st)
            if isinstance(node, Sort):
                keys = [st.cols[i] for i in node.keys]
                lanes = sort_lanes(keys, node.ascending, node.nulls_first)
                if st.mask is not None:
                    lanes.append((~st.mask).astype(jnp.uint8))
                order = stable_lexsort(lanes)
                cols = [gather(c, order) for c in st.cols]
                m = (jnp.take(st.mask, order)
                     if st.mask is not None else None)
                return _DagState(cols, m, st.n, True)
            if isinstance(node, Limit):
                if topk:
                    sort_node = node.child
                    keys = [st.cols[i] for i in sort_node.keys]
                    lanes = sort_lanes(keys, sort_node.ascending,
                                       sort_node.nulls_first)
                    livem = (st.mask if st.mask is not None
                             else jnp.ones((st.n,), dtype=bool))
                    k = min(dec.k, st.n)
                    idx = select_topk_core(lanes, livem, k)
                    cols = [gather(c, idx) for c in st.cols]
                    nlive = jnp.minimum(
                        jnp.sum(livem, dtype=jnp.int32), jnp.int32(k))
                    m = jnp.arange(k, dtype=jnp.int32) < nlive
                    return _DagState(cols, m, k, True)
                if st.mask is not None and not st.prefix:
                    raise PlanError(
                        "Limit needs prefix-compacted rows — place it "
                        "after a Sort or GroupBy, not directly on a "
                        "Filter or Join")
                k = min(node.count, st.n)
                cols = [_slice_col(c, k) for c in st.cols]
                m = st.mask[:k] if st.mask is not None else None
                return _DagState(cols, m, k, st.prefix)
            raise PlanError(f"unknown plan node {type(node).__name__}")

        st = rec(plan)
        out_info["has_mask"] = st.mask is not None
        out_info["prefix"] = st.prefix
        out_info["n_out"] = st.n
        with jax.named_scope(HEAD_SCOPE):
            live_out = (jnp.int32(st.n) if st.mask is None
                        else jnp.sum(st.mask, dtype=jnp.int32))
            head = jnp.stack([live_out, overflow[0].astype(jnp.int32)])
        return tuple(st.cols), st.mask, head

    return plan_dag


_MODULE = re.compile(r"HloModule ([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(ROOT )?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLEE = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")


def _scope_of(op_name: str) -> Optional[str]:
    return next((p for p in op_name.split("/") if p.startswith("plan.")),
                None)


def hlo_scopes(text: str) -> Tuple[Dict[str, str],
                                   Dict[str, Tuple[str, ...]]]:
    """({instruction: node scope}, {fusion: scopes of its computation})
    from a compiled module's HLO text; see ``ProgramCache.op_scopes``.
    An instruction with no ``plan.`` scope of its own takes that of the
    root of the computation it calls (``calls=``, ``to_apply=``),
    followed down until one has a scope."""
    own: Dict[str, Optional[str]] = {}
    callees: Dict[str, list] = {}
    root: Dict[str, str] = {}           # computation -> ROOT instruction
    members: Dict[str, list] = {}       # computation -> its instructions
    comp = None
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                comp = c.group(1)
                members[comp] = []
            continue
        name = m.group(2)
        op = _OP_NAME.search(line)
        own[name] = _scope_of(op.group(1)) if op else None
        callees[name] = _CALLEE.findall(line)
        if comp is not None:
            members[comp].append(name)
            if m.group(1):
                root[comp] = name

    def resolve(name: str, seen: frozenset) -> Optional[str]:
        if own.get(name) is not None or name in seen:
            return own.get(name)
        for callee in callees.get(name, ()):
            if callee in root:
                s = resolve(root[callee], seen | {name})
                if s is not None:
                    return s
        return None

    def drawn(comp: str, seen: set) -> set:
        out: set = set()
        if comp in seen:
            return out
        seen.add(comp)
        for n in members.get(comp, ()):
            if own[n] is not None:
                out.add(own[n])
            for callee in callees[n]:
                out |= drawn(callee, seen)
        return out

    scopes = {}
    for name in own:
        s = resolve(name, frozenset())
        if s is not None:
            scopes[name] = s
    fused = {name: tuple(sorted(set().union(
                 *(drawn(c, set()) for c in cs))))
             for name, cs in callees.items() if cs}
    return scopes, fused


class ProgramCache:
    """Compile-once-per-(plan, shape) cache of AOT executables. The
    fingerprint is structural (nodes.py), the shape key is the input
    signature, so ``_NVARIANTS``-style dataset cycling reuses one
    program. Thread-safe; a process restart starts empty but the
    underlying jax persistent cache turns the recompile into a disk
    hit."""

    def __init__(self):
        self._lock = threading.Lock()
        self._programs: Dict[Tuple, CompiledPlan] = {}

    def get_or_compile(self, plan: PlanNode, table: Table,
                       donate: bool = False) -> CompiledPlan:
        max_groups = int(config.get("plan.max_groups"))
        # the planner picks each GroupBy's strategy from the input's
        # stats: a constant or small-span key aggregates into slots with
        # no sort over the input rows (on the chip a sort over millions
        # of rows compiles for minutes, PR 21)
        from .planner import plan_decisions
        decisions = plan_decisions(plan, (table,))
        key = (fingerprint(plan), _shape_key(table), bool(donate),
               max_groups, decisions.cache_suffix)
        with self._lock:
            prog = self._programs.get(key)
        if prog is not None:
            plan_metrics.inc("plan_cache_hits")
            return prog
        plan_metrics.inc("plan_cache_misses")
        with timed_range("plan.compile", plan_metrics, "compile_s"):
            out_info: Dict[str, Any] = {}
            fn = _make_fn(plan, max_groups, out_info, decisions)
            jitted = jax.jit(fn, donate_argnums=(0,) if donate else ())
            compiled = jitted.lower(tuple(table.columns)).compile()
        plan_metrics.inc("plan_compiles")
        prog = CompiledPlan(compiled=compiled, fingerprint=key[0],
                            has_mask=out_info["has_mask"],
                            prefix=out_info["prefix"],
                            n_out=out_info["n_out"])
        with self._lock:
            # lost race: keep the first compile, drop ours
            prog = self._programs.setdefault(key, prog)
        return prog

    def get_or_compile_dag(self, plan: PlanNode,
                           tables: Tuple[Table, ...], decisions,
                           aux: Tuple) -> CompiledPlan:
        """DAG (multi-input, Join-bearing) variant. The key extends the
        solo key with every input's shape signature, the planner's
        ``cache_suffix`` (canonical strategy tuples — a stats-driven
        strategy flip compiles a distinct program instead of aliasing),
        and the aux remap-array lengths; the "dag" sentinel keeps the
        namespace disjoint from solo/sharded/vmap entries. Dictionary
        content is covered by the DICT32 fingerprints inside each
        ``_shape_key`` — both sides' fingerprints pin the remap arrays'
        CONTENT, their lengths pin the traced shapes. Never donates:
        inputs must survive for the eager overflow replay."""
        max_groups = int(config.get("plan.max_groups"))
        key = (fingerprint(plan),
               tuple(_shape_key(t) for t in tables), "dag",
               max_groups, decisions.cache_suffix,
               tuple(int(a.shape[0]) for a in aux))
        with self._lock:
            prog = self._programs.get(key)
        if prog is not None:
            plan_metrics.inc("plan_cache_hits")
            return prog
        plan_metrics.inc("plan_cache_misses")
        with timed_range("plan.compile", plan_metrics, "compile_s"):
            out_info: Dict[str, Any] = {}
            fn = _make_dag_fn(plan, decisions, max_groups, out_info)
            jitted = jax.jit(fn)
            compiled = jitted.lower(
                tuple(tuple(t.columns) for t in tables),
                tuple(aux)).compile()
        plan_metrics.inc("plan_compiles")
        prog = CompiledPlan(compiled=compiled, fingerprint=key[0],
                            has_mask=out_info["has_mask"],
                            prefix=out_info["prefix"],
                            n_out=out_info["n_out"])
        with self._lock:
            prog = self._programs.setdefault(key, prog)
        return prog

    def get_or_compile_sharded(self, plan: PlanNode,
                               table: Table, mesh) -> CompiledShardedPlan:
        """GSPMD variant: ONE jitted shard_map program spanning ``mesh``
        (plan/sharding.py lowering). The key extends the solo key with
        the mesh shape and axis name — "sharded" is a string sentinel, so
        solo entries (bool donate in that slot) and sharded entries can
        never collide, and each device count compiles separately (the
        degradation ladder walks distinct cache entries). Never donates:
        inputs must survive for degraded replay."""
        max_groups = int(config.get("plan.max_groups"))
        nd = int(mesh.devices.size)
        key = (fingerprint(plan), _shape_key(table), "sharded", nd,
               mesh.axis_names[0], max_groups)
        with self._lock:
            prog = self._programs.get(key)
        if prog is not None:
            plan_metrics.inc("plan_cache_hits")
            return prog
        plan_metrics.inc("plan_cache_misses")
        from . import sharding  # lazy: sharding imports this module
        with timed_range("plan.compile", plan_metrics, "compile_s"):
            jitted, staged, in_specs, out_info, n = sharding.lower_sharded(
                plan, table, mesh, max_groups)
            compiled = jitted.lower(*staged).compile()
        plan_metrics.inc("plan_compiles")
        prog = CompiledShardedPlan(
            compiled=compiled, fingerprint=key[0],
            prefix=out_info["prefix"], n_out=out_info["n_out"],
            replicated=out_info["replicated"],
            out_cols=out_info["out_cols"], in_specs=tuple(in_specs),
            mesh=mesh, n_rows=n)
        with self._lock:
            prog = self._programs.setdefault(key, prog)
        return prog

    def get_or_compile_batched(self, plan: PlanNode, template: Table,
                               stacked_cols: Tuple[Column, ...],
                               k: int, mesh=None) -> CompiledPlan:
        """Batched variant for the serving micro-batcher: ``jax.vmap`` of
        the same traced plan function over a leading batch axis of ``k``
        stacked same-shape inputs. One dispatch then executes ``k``
        queries; per-example semantics are untouched (vmap maps every op
        core over axis 0), so each slice of the output is bit-identical
        to the solo program's. Never donates: the stacked operand is a
        serving-owned copy and member tables stay live for solo replay.

        With ``mesh`` the caller has staged ``stacked_cols`` across it
        (sharding.stage_batched) and the jitted program partitions under
        GSPMD; the key grows (mesh shape, axis) so sharded-batch entries
        never serve an unsharded dispatch or vice versa."""
        max_groups = int(config.get("plan.max_groups"))
        key = (fingerprint(plan), _shape_key(template), "vmap", k,
               max_groups)
        if mesh is not None:
            key = key + ("sharded", int(mesh.devices.size),
                         mesh.axis_names[0])
        with self._lock:
            prog = self._programs.get(key)
        if prog is not None:
            plan_metrics.inc("plan_cache_hits")
            return prog
        plan_metrics.inc("plan_cache_misses")
        with timed_range("plan.compile", plan_metrics, "compile_s"):
            out_info: Dict[str, Any] = {}
            batched = jax.vmap(_make_fn(plan, max_groups, out_info))

            def plan_vmap(cols):
                return batched(cols)
            compiled = jax.jit(plan_vmap).lower(stacked_cols).compile()
        plan_metrics.inc("plan_compiles")
        prog = CompiledPlan(compiled=compiled, fingerprint=key[0],
                            has_mask=out_info["has_mask"],
                            prefix=out_info["prefix"],
                            n_out=out_info["n_out"])
        with self._lock:
            prog = self._programs.setdefault(key, prog)
        return prog

    def op_scopes(self) -> Dict[str, Dict[str, str]]:
        """{program: {instruction name: node scope}} over the cached
        programs, from each one's compiled HLO text (an executable loaded
        from the persistent cache carries the same text). ``program`` is
        the compiled module's name and the executable's fingerprint in
        hex, ``jit_plan_fused(<fingerprint>)``: the module name is what a
        profiler's ``XLA Modules`` event shares with it (the number there
        is not this fingerprint). An instruction's scope is the first
        ``plan.`` component of its ``op_name``; a fusion without one of
        its own takes its root's. Instructions with neither are left
        out. Parses on every call: never on the query path."""
        return {k: hlo_scopes(t)[0] for k, t in self._texts().items()}

    def fusion_scopes(self) -> Dict[str, Dict[str, Tuple[str, ...]]]:
        """{program: {fusion name: the node scopes its fused computation
        draws on}}: which nodes' operations XLA fused into one kernel."""
        return {k: hlo_scopes(t)[1] for k, t in self._texts().items()}

    def _texts(self) -> Dict[str, str]:
        with self._lock:
            progs = list(self._programs.values())
        out = {}
        for p in progs:
            text = p.compiled.as_text()
            m = _MODULE.match(text)
            fp = p.compiled.runtime_executable().fingerprint
            if isinstance(fp, bytes):
                fp = fp.hex()
            out[f"{m.group(1) if m else '?'}({fp})"] = text
        return out

    def clear(self) -> None:
        with self._lock:
            self._programs.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._programs)
