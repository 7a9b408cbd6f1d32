"""Spans, phase counters, program names and node scopes on the plan path.

Every query through ``execute_plan`` / ``execute_plan_sharded`` passes
``plan.prepare`` -> ``plan.dispatch`` -> ``plan.wait`` -> ``plan.trim``
(utils/tracing.py lists them). The spans are profiler annotations, only
built while ``trace.enabled`` is on; each phase's self time is a
``plan_metrics`` counter, kept either way. Inside the fused program each
node's operations carry a ``plan.<i>.<Kind>[.<strategy>]`` name scope,
which ``ProgramCache.op_scopes()`` reads back from the compiled HLO.

Run at small sizes on the CPU, with the q1, q5 and q6 plans and tables
of the benchmark's TPC-H queries (perfbench/configs/tpch/).
"""

import re

import jax
import numpy as np
import pytest

from perfbench import run as bench_run
from perfbench.configs.tpch import q1, q5, q6
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.columnar.column import Column, Table
from spark_rapids_jni_tpu.plan.compile import (ProgramCache, hlo_scopes,
                                               plan_metrics)
from spark_rapids_jni_tpu.plan.executor import execute_plan
from spark_rapids_jni_tpu.plan.nodes import GroupBy, Scan, Sort
from spark_rapids_jni_tpu.plan.sharded_executor import execute_plan_sharded
from spark_rapids_jni_tpu.utils import config
from spark_rapids_jni_tpu.utils.tracing import timed_range

ROWS = {"lineitem": 6000, "orders": 1500, "customer": 150,
        "supplier": 10, "nation": 25}
QUERIES = {
    "q1": (q1, {"cutoff": 2400}),
    "q6": (q6, {"date_lo": 365, "date_hi": 730, "disc_lo": 5,
                "disc_hi": 7, "qty_max": 24}),
    "q5": (q5, {"region_code": 2, "date_lo": 700, "date_hi": 1065}),
}
PHASES = ["plan.prepare", "plan.dispatch", "plan.wait", "plan.trim"]


def _query(name, sharded=False, mesh=8):
    """(run one query, check its answer) over fresh resident tables;
    ``sharded`` runs it through ``execute_plan_sharded`` on ``mesh``
    devices."""
    mod, lit = QUERIES[name]
    host = mod.generate(ROWS, 7)
    devices = jax.devices()[:mesh] if sharded else jax.devices()[:1]
    tables = bench_run.upload(host, mod.WITH_STATS,
                              "row_sharded" if len(devices) > 1
                              else "device", devices)
    plan = mod.plan(**lit)
    cache = ProgramCache()
    arg = tables[0] if len(tables) == 1 else tables

    def once():
        if sharded:
            return execute_plan_sharded(plan, arg, devices=mesh,
                                        cache=cache)
        return execute_plan(plan, arg, cache=cache)

    def check(out):
        got = mod.answer([np.asarray(c.data) for c in out.columns])
        assert mod.compare(got, mod.reference(host, **lit)) == {
            k: 0 for k in mod.LIMITS}
    return once, check, cache


def _plan_spans(log_dir):
    """(start, end, name) of the ``plan.*`` annotations in a capture,
    from the host thread that ran the queries, in start order."""
    from perfbench import trace
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(trace.find_xplane(str(log_dir)))
    best = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            spans = [(ev.start_ns, ev.end_ns, ev.name) for ev in line.events
                     if ev.name.startswith("plan.")]
            if len(spans) > len(best):
                best = spans
    return sorted(best)


def _outermost(spans):
    """The spans no other plan span encloses, in order."""
    return [s for s in spans
            if not any(o is not s and o[0] <= s[0] and s[1] <= o[1]
                       for o in spans)]


def _inside(spans, name, outer):
    return [s for s in spans if s[2] == name
            and any(o[2] == outer and o[0] <= s[0] and s[1] <= o[1]
                    for o in spans)]


# -- spans --------------------------------------------------------------------


@pytest.mark.parametrize("name,sharded", [("q1", False), ("q6", False),
                                          ("q5", False), ("q1", True)])
def test_each_query_records_its_phases_in_order(tmp_path, name, sharded):
    once, check, _cache = _query(name, sharded)
    with config.override("trace.enabled", True):
        jax.profiler.start_trace(str(tmp_path))
        try:
            first, second = once(), once()
        finally:
            jax.profiler.stop_trace()
    check(first)
    check(second)
    spans = _plan_spans(tmp_path)
    assert [s[2] for s in _outermost(spans)] == PHASES + PHASES
    # plan.compile nests in the first query's plan.prepare only (the
    # cache miss); the second query hits
    prepares = [s for s in spans if s[2] == "plan.prepare"]
    compiles = [s for s in spans if s[2] == "plan.compile"]
    assert len(compiles) == 1
    assert prepares[0][0] <= compiles[0][0] <= compiles[0][1] \
        <= prepares[0][1]
    stages = _inside(spans, "plan.stage", "plan.dispatch")
    assert len(stages) == (2 if sharded else 0)
    assert len([s for s in spans if s[2] == "plan.stage"]) == len(stages)


def test_tracing_off_builds_no_annotation(monkeypatch):
    def refuse(*_a, **_kw):
        raise AssertionError("TraceAnnotation built with tracing off")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    for name, sharded in (("q1", False), ("q5", False), ("q1", True)):
        once, check, _cache = _query(name, sharded)
        with config.override("trace.enabled", False):
            check(once())  # a cache miss, then a hit
            check(once())


# -- counters -----------------------------------------------------------------


def _warm_query_counters(monkeypatch, once, check):
    """(counter, seconds) of each ``add_time`` in one warm query."""
    check(once())                       # the compile is out of the way
    calls = []
    real = plan_metrics.add_time

    def record(counter, seconds):
        calls.append((counter, seconds))
        real(counter, seconds)
    monkeypatch.setattr(plan_metrics, "add_time", record)
    check(once())
    return calls


@pytest.mark.parametrize("name,sharded", [("q1", False), ("q6", False),
                                          ("q5", False), ("q1", True)])
def test_each_query_adds_each_phase_counter_once(monkeypatch, name,
                                                 sharded):
    once, check, _cache = _query(name, sharded)
    calls = _warm_query_counters(monkeypatch, once, check)
    names = sorted(c for c, _ in calls)
    want = ["dispatch_s", "execute_s", "prepare_s", "trim_s"]
    assert names == sorted(want + (["stage_s"] if sharded else []))
    got = dict(calls)
    assert all(v >= 0.0 for v in got.values())
    # execute_s keeps its boundaries: dispatch (staging included) plus
    # the waits, so it holds the self times of both
    assert got["execute_s"] >= got["dispatch_s"] + got.get("stage_s", 0.0)


def test_sharded_call_on_the_solo_program_adds_prepare_once(monkeypatch):
    """A sharded call that runs the solo program (a one-device mesh)
    counts the solo executor's ``plan.prepare`` and no other."""
    once, check, _cache = _query("q1", sharded=True, mesh=1)
    calls = _warm_query_counters(monkeypatch, once, check)
    assert sorted(c for c, _ in calls) == ["dispatch_s", "execute_s",
                                           "prepare_s", "trim_s"]


def test_recent_holds_each_query_phase_time():
    """``recent`` gives the latest queries' own phase times, oldest first,
    so a reader can leave a warm-up's compile out of its mean."""
    once, check, _cache = _query("q6")
    check(once())                               # the miss: compiles
    for _ in range(2):
        before = plan_metrics.snapshot()
        check(once())
        after = plan_metrics.snapshot()
        for k in ("prepare_s", "dispatch_s", "trim_s"):
            (last,) = plan_metrics.recent(k, 1)
            assert last == pytest.approx(after[k] - before[k], abs=2e-6)
    assert len(plan_metrics.recent("prepare_s", 2)) == 2
    assert plan_metrics.recent("trim_s", 0) == []
    from spark_rapids_jni_tpu.plan.compile import PlanMetrics
    pm = PlanMetrics()
    for i in range(PlanMetrics.RECENT + 5):
        pm.add_time("trim_s", float(i))
    kept = pm.recent("trim_s", 10 ** 6)
    assert kept == [float(i) for i in range(5, PlanMetrics.RECENT + 5)]


def test_timed_range_counts_self_time():
    class Sink:
        def __init__(self):
            self.t = {}

        def add_time(self, k, v):
            self.t[k] = self.t.get(k, 0.0) + v

    sink = Sink()
    import time
    with timed_range("outer", sink, "outer_s"):
        time.sleep(0.02)
        with timed_range("inner", sink, "inner_s"):
            time.sleep(0.03)
        with timed_range("uncounted"):
            time.sleep(0.03)
    assert 0.02 <= sink.t["outer_s"] < 0.05
    assert sink.t["inner_s"] >= 0.03
    with pytest.raises(ValueError):
        with timed_range("raises", sink, "raised_s"):
            raise ValueError("still counted")
    assert sink.t["raised_s"] >= 0.0


# -- programs and scopes ------------------------------------------------------


@pytest.mark.parametrize("name,sharded,module",
                         [("q1", False, "jit_plan_fused"),
                          ("q6", False, "jit_plan_fused"),
                          ("q5", False, "jit_plan_dag"),
                          ("q1", True, "jit_plan_sharded")])
def test_programs_are_named(name, sharded, module):
    once, check, cache = _query(name, sharded)
    check(once())
    (program,) = cache.op_scopes()
    assert re.fullmatch(rf"{module}\(.+\)", program), program
    assert cache.op_scopes().keys() == cache.fusion_scopes().keys()


def test_batched_program_is_named():
    li = Table(tuple(Column.from_numpy(np.arange(64, dtype=np.int64) % 5,
                                       dt.INT64) for _ in range(2)))
    plan = Sort(GroupBy(Scan(2), (0,), ((1, "sum"),)), (0,))
    stacked = jax.tree_util.tree_map(lambda a: jax.numpy.stack([a, a]),
                                     tuple(li.columns))
    cache = ProgramCache()
    cache.get_or_compile_batched(plan, li, stacked, 2)
    (program,) = cache.op_scopes()
    assert program.startswith("jit_plan_vmap(")


NODE_SCOPE = re.compile(
    r"plan\.head|plan\.\d+\.(Scan|Filter|Project|Join|GroupBy|Sort|Limit)"
    r"(\.(generic|direct_small|direct_wide|direct|sorted|topk|skip|slice))?")


@pytest.mark.parametrize("name,sharded,kind",
                         [("q1", False, "GroupBy"), ("q6", False, "GroupBy"),
                          ("q5", False, "Join"), ("q5", False, "GroupBy"),
                          ("q1", True, "GroupBy")])
def test_op_scopes_map_fusions_to_plan_nodes(name, sharded, kind):
    once, check, cache = _query(name, sharded)
    check(once())
    (prog,) = cache._programs.values()
    text = prog.compiled.as_text()
    (scopes,) = cache.op_scopes().values()
    assert all(NODE_SCOPE.fullmatch(s) for s in scopes.values())
    assert any(s.split(".")[2] == kind for s in scopes.values()
               if s != "plan.head")
    # every fusion whose instruction carries metadata maps to a node
    fusions = re.findall(r"^\s+(?:ROOT )?%?([\w.\-]+) = .* fusion\(.*"
                         r"op_name=\"[^\"]*plan\.", text, re.M)
    assert fusions
    assert all(f in scopes for f in fusions)
    # scopes never nest: an op name holds at most one plan component
    for op in re.findall(r'op_name="([^"]*)"', text):
        assert sum(p.startswith("plan.") for p in op.split("/")) <= 1, op


def test_hlo_scopes_follow_a_fusion_to_its_root():
    text = "\n".join([
        "HloModule jit_plan_fused, entry_computation_layout={()->s32[]}",
        "",
        "%fused_computation (p: s32[]) -> s32[] {",
        "  %p = s32[] parameter(0)",
        '  %a = s32[] add(%p, %p), metadata={op_name="jit(plan_fused)/'
        'plan.1.Filter/add"}',
        '  ROOT %m = s32[] multiply(%a, %a), metadata={op_name='
        '"jit(plan_fused)/plan.3.GroupBy.generic/mul"}',
        "}",
        "",
        "ENTRY %main (x: s32[]) -> s32[] {",
        "  %x = s32[] parameter(0)",
        "  ROOT %fusion = s32[] fusion(%x), kind=kLoop, "
        "calls=%fused_computation",
        "}",
    ])
    scopes, fused = hlo_scopes(text)
    assert scopes["fusion"] == "plan.3.GroupBy.generic"
    assert scopes["a"] == "plan.1.Filter"
    assert "x" not in scopes
    assert fused["fusion"] == ("plan.1.Filter", "plan.3.GroupBy.generic")


# -- the sharded overflow replays eagerly -------------------------------------


def test_sharded_group_budget_overflow_gets_the_eager_answer():
    rng = np.random.default_rng(3)
    n = 8000
    keys = rng.integers(0, 2000, n).astype(np.int32)
    vals = rng.integers(0, 1000, n)
    t = Table((Column.from_numpy(keys, dt.INT32),
               Column.from_numpy(vals, dt.INT64)))
    plan = Sort(GroupBy(Scan(2), (0,), ((1, "sum"),)), (0,))
    before = plan_metrics.snapshot()
    with config.override("plan.max_groups", 1024):
        out = execute_plan_sharded(plan, t, devices=8, cache=ProgramCache())
    after = plan_metrics.snapshot()
    assert after["plan_overflows"] == before["plan_overflows"] + 1
    assert after["plan_fallbacks"] == before["plan_fallbacks"] + 1
    uniq = np.unique(keys)
    sums = np.array([vals[keys == k].sum() for k in uniq])
    assert np.array_equal(np.asarray(out.columns[0].data), uniq)
    assert np.array_equal(np.asarray(out.columns[1].data), sums)
