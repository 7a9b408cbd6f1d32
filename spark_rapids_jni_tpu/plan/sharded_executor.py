"""Sharded plan execution: one guarded dispatch, one mesh, one sync.

The solo executor's whole protocol carries over unchanged — resolve
dictionary literals, gate unsupported inputs to eager, compile-or-hit the
ProgramCache, ONE ``guarded_dispatch("plan_execute")`` around ONE fused
program, ONE host sync on the 2-element head, trim on the host — with two
sharded-specific layers on top:

* **The bit-identity gate** (sharding.sharding_unsupported_reason): plans
  whose sharded merge could differ from solo by even one bit (float
  accumulations, pre-GroupBy global sorts) run the SOLO fused program
  instead. Falling back to solo-fused, not eager: the answer is the same
  either way, only the device count changes.
* **The fault-domain ladder**: a storm or poisoning at the dispatch
  boundary degrades the mesh 8 -> 4 -> 2 and replays the query on the
  smaller mesh (a fresh cache entry — mesh shape is in the key — over the
  same immutable inputs, so the replay is bit-identical). At 1 device the
  replay IS the solo program, run under ``guard.degraded`` exactly like
  the exchange layer's last rung: injection suppressed, because a query
  that burned the whole ladder has already paid its fault budget.
"""

from __future__ import annotations

import time
from typing import Optional

from ..columnar.column import Table
from ..faultinj import guard
from ..faultinj.guard import (FaultStormError, ProgramPoisonedError,
                              guarded_dispatch)
from ..memory.reservation import device_reservation
from ..parallel import cluster
from ..utils.tracing import timed_range
from . import sharding
from .compile import CompiledShardedPlan, ProgramCache, plan_metrics
from .executor import (_default_cache, _eager_fallback, _head_sync,
                       _released, _trim, execute_plan,
                       resolve_dict_literals, unsupported_reason)
from .nodes import PlanNode, is_dag


def _execute_on_mesh(plan: PlanNode, table: Table,
                     prog: CompiledShardedPlan) -> Table:
    mesh = prog.mesh

    def run():
        # stage inside the guard: device_put re-commits leaves to their
        # shardings (free when already conformant, and a degraded replay
        # restages onto the smaller mesh from the same host/solo buffers)
        with timed_range("plan.stage", plan_metrics, "stage_s"):
            leaves, specs, _meta, _n, _per = sharding.table_layout(table,
                                                                   mesh)
            staged = sharding.stage_leaves(leaves, specs, mesh)
        with device_reservation(2 * table.device_nbytes()) as took:
            out = prog.compiled(*staged)
            return _released(out, took)

    t0 = time.perf_counter()
    with timed_range("plan.dispatch", plan_metrics, "dispatch_s"):
        out_leaves, mask, head = guarded_dispatch("plan_execute", run)
    head_h = _head_sync(head)
    plan_metrics.add_time("execute_s", time.perf_counter() - t0)
    plan_metrics.inc("plan_executes")
    live, overflow = int(head_h[0]), bool(head_h[1])

    if overflow:
        plan_metrics.inc("plan_overflows")
        return _eager_fallback(plan, table, "overflow")

    with timed_range("plan.trim", plan_metrics, "trim_s"):
        cols = sharding.rebuild_outputs(prog.replicated, prog.out_cols,
                                        out_leaves, table)
        return _trim(prog, cols, mask, live)


def execute_plan_sharded(plan: PlanNode, table: Table,
                         devices: int = 0, mesh=None,
                         cache: Optional[ProgramCache] = None) -> Table:
    """Run ``plan`` over ``table`` as ONE GSPMD program across the mesh,
    bit-identical to ``execute_plan``. ``devices`` picks a sub-mesh
    (0 = all); faults degrade the mesh by halves and replay."""
    cache = cache if cache is not None else _default_cache
    if is_dag(plan) or not isinstance(table, Table):
        # DAG plans are gated solo by sharding_unsupported_reason; route
        # straight to the (DAG-aware) solo executor without linearizing
        return execute_plan(plan, table, cache=cache)
    if mesh is None:
        mesh = cluster.get_mesh(devices)
    if (int(mesh.devices.size) == 1
            or sharding.sharding_unsupported_reason(plan, table)
            is not None):
        # same bits either way — run the solo fused program, whose own
        # plan.prepare is then the query's only one
        return execute_plan(plan, table, cache=cache)
    with timed_range("plan.prepare", plan_metrics, "prepare_s"):
        plan = resolve_dict_literals(plan, table)
        reason = unsupported_reason(plan, table)
        if reason is not None:
            return _eager_fallback(plan, table, "unsupported-input")
        prog = cache.get_or_compile_sharded(plan, table, mesh)

    axis = sharding.mesh_axis(mesh)
    while True:
        try:
            return _execute_on_mesh(plan, table, prog)
        except (FaultStormError, ProgramPoisonedError):
            nd = int(mesh.devices.size) // 2
            if nd < 1:
                raise
            guard.metrics.bump("degradations")
            if nd == 1:
                # last rung: the solo program under degraded semantics
                # (injection suppressed — the budget is already spent)
                with guard.degraded():
                    return execute_plan(plan, table, cache=cache)
            mesh = cluster.get_mesh(nd, axis)
            # a replay's compile counts in compile_s; prepare_s stays one
            # addition per query
            prog = cache.get_or_compile_sharded(plan, table, mesh)
