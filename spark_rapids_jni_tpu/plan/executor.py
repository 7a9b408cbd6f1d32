"""Plan execution: one guarded dispatch around one fused XLA program.

This is where the guard/fault-domain/deadline machinery that used to
wrap every individual op now lives for planned queries — a single
``guarded_dispatch("plan_execute", ...)`` brackets the whole fused
program (reservation, injection point, fault classification, retry,
watchdog). The op cores inside the program are pure by contract
(plan/registry.py), so a retry after a TRANSIENT fault re-runs the
program from the same immutable inputs and lands on bit-identical
results.

Host traffic per query is exactly one sync: the 2-element ``head``
vector (live row count, overflow flag). Trimming to the live rows
happens after that sync — a static prefix slice when the fused state is
prefix-compacted (post GroupBy/Sort), else a nonzero-gather.

Fallbacks go through ``run_eager`` (plan/interpreter.py), which bumps
``plan_fallbacks`` plus a per-reason label: unsupported input column
types, empty input, a planner gate (DAG plans the strategy selector
can't fuse), and group-budget / join-shape overflow detected on device
(``plan_overflows``).

DAG plans (Join nodes, multiple input tables) take the same shape of
path: the cost-shaped planner (plan/planner.py) rewrites and annotates
the plan, ``ProgramCache.get_or_compile_dag`` lowers the whole DAG into
ONE fused program, and the identical single guarded dispatch + single
head sync protocol applies. Join-order and strategy decisions are the
planner's alone (SRJT015); this module only routes them.
"""

from __future__ import annotations

import dataclasses
import time
from typing import List, Optional, Sequence, Tuple, Union

import jax.numpy as jnp
import numpy as np

from ..columnar import dtype as dt
from ..columnar.column import Column, Table
from ..columnar.table_ops import gather_table, mask_indices_core
from ..faultinj.guard import guarded_dispatch
from ..memory.exceptions import TpuSplitAndRetryOOM
from ..memory.reservation import device_reservation, release_barrier
from ..memory.retry import with_retry
from ..utils import config
from ..utils.tracing import timed_range
from . import expr as ex
from . import planner as _planner
from . import split as _split
from .compile import CompiledPlan, ProgramCache, plan_metrics
from .interpreter import run_eager
from .nodes import (Filter, GroupBy, Join, PlanError, PlanNode, Project,
                    Scan, is_dag, linearize, num_inputs, walk)

_default_cache = ProgramCache()


class _OverflowSignal(Exception):
    """Internal: the fused program's device re-check tripped (group
    budget, join shape) — the output is garbage; recompute eagerly."""


def _eager_fallback(plan: PlanNode, table, reason: str) -> Table:
    """Declared engine fallback -> eager interpreter, under the SAME
    guarded ``plan_execute`` surface as the fused dispatch. Interior op
    entry points (ops/sort.sort_order, hashing, row conversion) are
    fault-injector-instrumented, so an UNGUARDED eager walk leaks their
    injected/transient faults raw instead of classifying them into the
    retry / typed-failure protocol — the fuzz storm lane caught exactly
    that (a type-2 API-error substitution on sort_order escaping untyped
    through the unsupported-input fallback). The interpreter is pure
    over immutable tables, so the guard's retry re-run is safe. SRJT021
    enforces the literal catalog reason at every call site of this
    forwarder, exactly as it does for a direct run_eager fallback."""
    with timed_range("plan.eager"):
        return guarded_dispatch(
            "plan_execute",
            lambda: run_eager(plan, table, fallback_reason=reason))  # srjt: noqa[SRJT021] — the forwarder itself; SRJT021 checks its callers' literals instead


def _pool_cap_check(want_bytes: int) -> None:
    """injectionType 6 "shrink" mode (faultinj/injector.py): a standing
    injected pool cap at the plan_execute surface ONLY — a reservation
    envelope that doesn't fit demands a split, so storms can force the
    ladder's split rung deterministically while the eager fallback (which
    never takes this surface) still completes."""
    from ..faultinj import injector as _inj
    cap = _inj.oom_pool_cap("plan_execute")
    if cap is not None and want_bytes > cap:
        raise TpuSplitAndRetryOOM(
            f"injected shrinking pool: reservation envelope {want_bytes} "
            f"bytes exceeds the {cap}-byte cap")


def _rollback_spill() -> None:
    """The ladder's spill-rollback rung: release every SpillStore-
    registered table, then account the retry (plan_oom_retries) and the
    freed bytes (plan_oom_spill_bytes)."""
    from ..memory import transport
    freed = transport.rollback_all_stores()
    plan_metrics.inc("plan_oom_retries")
    plan_metrics.inc("plan_oom_spill_bytes", freed)


def _released(out, took: bool):
    """``release_barrier`` under ``plan.wait`` when it blocks (a
    reservation was taken)."""
    if not took:
        return out
    with timed_range("plan.wait"):
        return release_barrier(out, took)


def _head_sync(head) -> np.ndarray:
    """THE host sync for the query: the head's live count and overflow
    flag, under ``plan.wait``."""
    with timed_range("plan.wait"):
        return np.asarray(head)


def _oom_budget() -> int:
    return int(config.get("plan.oom_retry_budget"))


def default_cache() -> ProgramCache:
    return _default_cache


def _table_unsupported_reason(table: Table) -> Optional[str]:
    """Why one input table can't feed a fused program — None when it
    can. Conservative by design: anything not provably supported falls
    back to the eager path rather than risking wrong fused results."""
    if table.num_rows == 0:
        return "empty input"
    for i, c in enumerate(table.columns):
        if not c.dtype.is_fixed_width:
            return f"column {i} is {c.dtype.id.value} (not fixed-width)"
        if c.dtype.is_decimal:
            return f"column {i} is decimal (eager-only aggregation path)"
    return None


def unsupported_reason(plan: PlanNode, table: Table) -> Optional[str]:
    """Why this (plan, table) can't run fused — None when it can."""
    return _table_unsupported_reason(table)


def _trim_prefix(cols, live: int) -> Table:
    out = []
    for c in cols:
        if c.dtype.id in (dt.TypeId.RLE, dt.TypeId.FOR32, dt.TypeId.FOR64):
            # encoded passthrough output under a prefix trim: row-slicing
            # run/packed buffers isn't a plain data[:live] — decode at this
            # declared boundary (rare: prefix states come from GroupBy/Sort,
            # which already decode in-program)
            from ..columnar.encodings import decoded_rows
            c = decoded_rows(c)
        v = c.validity[:live] if c.validity is not None else None
        out.append(Column(c.dtype, live, data=c.data[:live], validity=v,
                          children=c.children))
    return Table(tuple(out))


# ---------------------------------------------------------------------------
# dictionary literal resolution
# ---------------------------------------------------------------------------

def _has_str_lit(e: ex.Expr) -> bool:
    if isinstance(e, ex.Lit):
        return isinstance(e.value, str)
    if isinstance(e, ex.BinOp):
        return _has_str_lit(e.left) or _has_str_lit(e.right)
    if isinstance(e, (ex.Not, ex.Cast64)):
        return _has_str_lit(e.operand)
    return False


def _resolve_pair(left: ex.Expr, right: ex.Expr, desc):
    from ..columnar.dictionary import lookup_code

    def code_lit(lit_e, col_e):
        if not (isinstance(col_e, ex.Col)
                and col_e.index < len(desc)
                and desc[col_e.index] is not None):
            raise TypeError(
                "a string literal in a plan expression can only be "
                "compared (eq/ne) against a dictionary-encoded column")
        return ex.Lit(int(lookup_code(desc[col_e.index], lit_e.value)))

    if isinstance(left, ex.Lit) and isinstance(left.value, str):
        left = code_lit(left, right)
    if isinstance(right, ex.Lit) and isinstance(right.value, str):
        right = code_lit(right, left)
    return left, right


def _resolve_expr(e: ex.Expr, desc) -> ex.Expr:
    if isinstance(e, ex.Lit) and isinstance(e.value, str):
        raise TypeError(
            "string literal outside an eq/ne comparison with a "
            "dictionary-encoded column")
    if isinstance(e, ex.BinOp):
        left, right = e.left, e.right
        if e.op in ("eq", "ne"):
            left, right = _resolve_pair(left, right, desc)
        return ex.BinOp(e.op, _resolve_expr(left, desc),
                        _resolve_expr(right, desc))
    if isinstance(e, ex.Not):
        return ex.Not(_resolve_expr(e.operand, desc))
    if isinstance(e, ex.Cast64):
        return ex.Cast64(_resolve_expr(e.operand, desc))
    return e


def resolve_dict_literals(plan: PlanNode, table: Table) -> PlanNode:
    """Rewrite string literals compared against DICT32 columns into their
    int32 dictionary codes (absent entry -> -1, which no code equals — the
    encoded always-false). A pure, deterministic pre-trace pass: the
    rewritten plan's fingerprint keys the program cache, so queries whose
    literals resolve to different codes compile/cached separately and the
    fused program contains only integer compares. Plans without string
    literals return UNCHANGED (same object, same fingerprint)."""
    nodes = linearize(plan)
    needs = any(
        (isinstance(n, Filter) and _has_str_lit(n.predicate))
        or (isinstance(n, Project) and any(_has_str_lit(e) for e in n.exprs))
        for n in nodes[1:])
    if not needs:
        return plan
    desc: List[Optional[Column]] = [
        c if c.dtype.id is dt.TypeId.DICT32 else None for c in table.columns]
    new_plan: PlanNode = nodes[0]
    for node in nodes[1:]:
        if isinstance(node, Filter):
            node = Filter(new_plan, _resolve_expr(node.predicate, desc))
        elif isinstance(node, Project):
            exprs = tuple(_resolve_expr(e, desc) for e in node.exprs)
            desc = [desc[e.index] if isinstance(e, ex.Col) else None
                    for e in exprs]
            node = Project(new_plan, exprs)
        else:
            if isinstance(node, GroupBy):
                desc = ([desc[i] for i in node.keys]
                        + [None] * len(node.aggs))
            node = dataclasses.replace(node, child=new_plan)
        new_plan = node
    return new_plan


def _resolve_dag_literals(plan: PlanNode, tables: Tuple[Table, ...]
                          ) -> PlanNode:
    """``resolve_dict_literals`` for DAG plans: the dictionary-column
    descriptor is tracked per branch and concatenated across Join
    outputs. Plans without string literals return UNCHANGED (same
    object, same fingerprint, same decision identity map)."""
    needs = False
    for n in walk(plan):
        if isinstance(n, Filter) and _has_str_lit(n.predicate):
            needs = True
        if isinstance(n, Project) and any(_has_str_lit(e)
                                          for e in n.exprs):
            needs = True
    if not needs:
        return plan

    def rec(node):
        if isinstance(node, Scan):
            t = tables[node.input_index]
            return node, [c if c.dtype.id is dt.TypeId.DICT32 else None
                          for c in t.columns]
        if isinstance(node, Join):
            left, ldesc = rec(node.left)
            right, rdesc = rec(node.right)
            desc = (ldesc if node.how in ("semi", "anti")
                    else ldesc + rdesc)
            return Join(left, right, node.left_on, node.right_on,
                        node.how), desc
        child, desc = rec(node.child)
        if isinstance(node, Filter):
            return Filter(child, _resolve_expr(node.predicate,
                                               desc)), desc
        if isinstance(node, Project):
            exprs = tuple(_resolve_expr(e, desc) for e in node.exprs)
            desc = [desc[e.index] if isinstance(e, ex.Col) else None
                    for e in exprs]
            return Project(child, exprs), desc
        if isinstance(node, GroupBy):
            desc = ([desc[i] for i in node.keys]
                    + [None] * len(node.aggs))
            return GroupBy(child, node.keys, node.aggs), desc
        return dataclasses.replace(node, child=child), desc

    new_plan, _ = rec(plan)
    return new_plan


def _trim(prog, cols, mask, live: int) -> Table:
    """The program's padded output cut to its ``live`` rows on the host
    side: as is, a prefix slice, or a compaction of the masked rows."""
    if mask is None:
        return Table(tuple(cols))
    if prog.prefix:
        return _trim_prefix(cols, live)
    idx = mask_indices_core(mask, live)
    return gather_table(Table(tuple(cols)), idx)


def _execute_dag(plan: PlanNode, tables: Tuple[Table, ...],
                 cache: ProgramCache) -> Table:
    """DAG (Join-bearing / multi-input) execution: planner passes, one
    fused program, one guarded dispatch, one head sync. Fallbacks run
    the eager interpreter on the PRE-optimization plan — the reference
    semantics do not depend on the rewrite passes being loaded."""
    k = num_inputs(plan)
    if len(tables) < k:
        raise PlanError(f"plan reads {k} inputs, got {len(tables)}")
    tables = tuple(tables[:k])
    with timed_range("plan.prepare", plan_metrics, "prepare_s"):
        plan = _resolve_dag_literals(plan, tables)
        for t in tables:
            if _table_unsupported_reason(t) is not None:
                return _eager_fallback(plan, tables, "unsupported-input")
            if any(c.dtype.id in (dt.TypeId.RLE, dt.TypeId.FOR32,
                                  dt.TypeId.FOR64) for c in t.columns):
                # join lowering reads key lanes straight from column data
                # (_key_values) — run/packed layouts need a decode the
                # DAG fuser doesn't model yet; the eager interpreter
                # decodes at its join boundary instead
                return _eager_fallback(plan, tables, "unsupported-input")

        opt = _planner.optimize(plan, tables)
        decisions = _planner.plan_decisions(opt, tables)
        if decisions.eager_reason is not None:
            return _eager_fallback(plan, tables, "planner-unsupported")

        aux: List[jnp.ndarray] = []
        for jid, (lsrc, rsrc) in decisions.dict_joins.items():
            from ..columnar.dictionary import code_remap_table, dict_values
            lcol = tables[lsrc[0]].columns[lsrc[1]]
            rcol = tables[rsrc[0]].columns[rsrc[1]]
            remap = code_remap_table(lcol, rcol)
            if remap is None:  # co-dictionary after all: identity remap
                remap = np.arange(dict_values(rcol).size, dtype=np.int32)
            aux.append(jnp.asarray(remap))

        prog: CompiledPlan = cache.get_or_compile_dag(
            opt, tables, decisions, tuple(aux))

    nbytes = sum(t.device_nbytes() for t in tables)

    def run():
        _pool_cap_check(2 * nbytes)
        with device_reservation(2 * nbytes) as took:
            out = prog.compiled(tuple(tuple(t.columns) for t in tables),
                                tuple(aux))
            return _released(out, took)

    def attempt(_arg):
        t0 = time.perf_counter()
        with timed_range("plan.dispatch", plan_metrics, "dispatch_s"):
            cols, mask, head = guarded_dispatch("plan_execute", run)
        head_h = _head_sync(head)
        plan_metrics.add_time("execute_s", time.perf_counter() - t0)
        plan_metrics.inc("plan_executes")
        live, overflow = int(head_h[0]), bool(head_h[1])
        if overflow:
            raise _OverflowSignal()
        with timed_range("plan.trim", plan_metrics, "trim_s"):
            return _trim(prog, cols, mask, live)

    try:
        # retry/rollback re-dispatch the SAME compiled DAG program; a
        # split demand gates to eager — the probe side's row order spans
        # the build side, so join pieces can't merge bit-identically
        return with_retry(attempt, None, rollback=_rollback_spill,
                          max_retries=_oom_budget())[0]
    except TpuSplitAndRetryOOM:
        return _eager_fallback(plan, tables, "oom-split-unmergeable")
    except _OverflowSignal:
        # a device re-check failed (group budget, non-dense build key,
        # duplicate-key build, packing range): fused output is garbage —
        # recompute eagerly. Inputs were never donated on this path.
        plan_metrics.inc("plan_overflows")
        return _eager_fallback(plan, tables, "overflow")


def execute_plan(plan: PlanNode,
                 table: Union[Table, Sequence[Table]],
                 donate_input: bool = False,
                 cache: Optional[ProgramCache] = None) -> Table:
    """Run ``plan`` over ``table`` as one fused XLA program (eager
    fallback when unsupported). DAG plans (Join nodes) take a sequence
    of tables indexed by ``Scan.input_index``; they never donate (the
    eager overflow replay needs the inputs alive).

    ``donate_input=True`` lets XLA reuse the input buffers for
    intermediates — only safe when the caller is done with the table
    AND is willing to lose in-flight retry (a fault mid-program after
    donation cannot re-run; the guard surfaces it)."""
    cache = cache if cache is not None else _default_cache
    if is_dag(plan) or not isinstance(table, Table):
        tables = (table,) if isinstance(table, Table) else tuple(table)
        return _execute_dag(plan, tables, cache)
    with timed_range("plan.prepare", plan_metrics, "prepare_s"):
        plan = resolve_dict_literals(plan, table)
        if donate_input and any(
                c.dtype.id in (dt.TypeId.DICT32, dt.TypeId.RLE,
                               dt.TypeId.FOR32, dt.TypeId.FOR64)
                for c in table.columns):
            # encoded children (dictionary values/ranks, RLE run buffers,
            # FOR reference headers) are SHARED by reference across
            # batches — donating them would let XLA scribble over buffers
            # other queries still reference
            donate_input = False
        reason = unsupported_reason(plan, table)
        if reason is not None:
            return _eager_fallback(plan, table, "unsupported-input")
        prog: CompiledPlan = cache.get_or_compile(plan, table,
                                                  donate=donate_input)

    def _fused_once(pr: CompiledPlan, t: Table) -> Table:
        def run():
            # peak ≈ input + intermediates the fuser keeps live; 2x input
            # is the same envelope the eager sort/join brackets use
            _pool_cap_check(2 * t.device_nbytes())
            with device_reservation(2 * t.device_nbytes()) as took:
                out = pr.compiled(tuple(t.columns))
                return _released(out, took)

        t0 = time.perf_counter()
        with timed_range("plan.dispatch", plan_metrics, "dispatch_s"):
            cols, mask, head = guarded_dispatch("plan_execute", run)
        head_h = _head_sync(head)
        plan_metrics.add_time("execute_s", time.perf_counter() - t0)
        plan_metrics.inc("plan_executes")
        live, overflow = int(head_h[0]), bool(head_h[1])
        if overflow:
            raise _OverflowSignal()
        with timed_range("plan.trim", plan_metrics, "trim_s"):
            return _trim(pr, cols, mask, live)

    if donate_input:
        # donation consumes the input mid-program: a rollback or split
        # replay could not re-run it, so the donated path stays OUTSIDE
        # the retry protocol — the guard still classifies, and the OOM
        # propagates typed to a caller that owns replayable state
        try:
            return _fused_once(prog, table)
        except _OverflowSignal:
            plan_metrics.inc("plan_overflows")
            raise RuntimeError(
                "plan group-budget overflow after input donation: the "
                "input was consumed by the fused program and the eager "
                "fallback cannot run. Raise plan.max_groups or disable "
                "donation for this query.")

    # the degradation ladder: retry (same program) → spill-rollback →
    # split (halved pieces through the shape-bucketed ProgramCache) →
    # eager (named gate) → typed shed (the OOM propagates)
    unmergeable = _split.split_unmergeable_reason(plan, table)
    state = {"spec": None}

    def attempt(item):
        tag, t = item
        if tag == "whole":
            return _fused_once(prog, t)
        pr = cache.get_or_compile(state["spec"].piece_plan, t,
                                  donate=False)
        return _fused_once(pr, t)

    def do_split(item):
        if state["spec"] is None:
            state["spec"] = _split.prepare(plan)
        _tag, t = item
        pieces = _split.split_table(t)
        if len(pieces) >= 2:
            plan_metrics.inc("plan_oom_splits")
        return [("piece", p) for p in pieces]

    try:
        results = with_retry(
            attempt, ("whole", table),
            split=None if unmergeable is not None else do_split,
            rollback=_rollback_spill, max_retries=_oom_budget())
    except TpuSplitAndRetryOOM:
        if unmergeable is None:
            raise  # split depth/retry budget exhausted: typed shed
        # named gate: this plan's pieces can't merge bit-identically
        return _eager_fallback(plan, table, "oom-split-unmergeable")
    except _OverflowSignal:
        # true group count exceeded the static budget: fused output is
        # truncated garbage — recompute eagerly (data-dependent shapes)
        plan_metrics.inc("plan_overflows")
        return _eager_fallback(plan, table, "overflow")

    if state["spec"] is None:
        return results[0]
    plan_metrics.inc("plan_oom_pieces", len(results))
    try:
        return _split.merge_pieces(state["spec"], results, table.num_rows,
                                   int(config.get("plan.max_groups")))
    except _split.SplitMergeOverflow:
        plan_metrics.inc("plan_overflows")
        return _eager_fallback(plan, table, "overflow")
    except _split.SplitMergeError:
        return _eager_fallback(plan, table, "oom-split-degenerate")
