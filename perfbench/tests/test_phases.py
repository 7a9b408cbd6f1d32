"""A CPU rehearsal of ``perfbench.phases`` and of the phase-counter
readers, at tiny sizes: the per-query phase readings, the kept trace and
scope maps, and the readers on the engine's counters."""

from __future__ import annotations

import gzip
import json

import pytest

from perfbench import phases, spec
from perfbench.tests.test_rehearsal import SEED, cpu_devices, tiny


@pytest.mark.parametrize("name", ["q5_sf1", "q1_sf10_4chip"])
def test_traced_run_reads_the_engine_phases(tmp_path, name):
    cell = tiny(name)
    res = phases.traced_run(cell, SEED, 0.5, cpu_devices(cell.chips),
                            spec.peaks("TPU v5 lite"), 1, str(tmp_path))
    assert res["correct"], res["checks"]
    m = res["metrics"]
    for k in ("plan_prepare_ms", "plan_dispatch_ms", "plan_trim_ms"):
        assert m[k] > 0, k
    assert ("plan_stage_ms" in m) == (cell.chips > 1)
    if "plan_outside_ms" in m:  # the one-chip cells
        # the phases outside execute_s are part of what plan_outside_ms
        # holds, and dispatch is part of execute_s
        assert m["plan_outside_ms"] >= m["plan_prepare_ms"] + m["plan_trim_ms"]
        assert m["plan_execute_ms"] >= m["plan_dispatch_ms"]
    program = {"q5_sf1": "jit_plan_dag", "q1_sf10_4chip": "jit_plan_sharded"}
    assert program[name] in [p.split("(")[0] for p in res["scope_programs"]]
    with open(tmp_path / f"{cell.name}.scopes.json") as f:
        maps = json.load(f)
    assert set(maps) == {"scopes", "fusions"}
    with gzip.open(tmp_path / f"{cell.name}.xplane.pb.gz") as f:
        assert f.read(1)


READERS = ("plan_prepare_ms", "plan_dispatch_ms", "plan_trim_ms",
           "plan_stage_ms")


def test_counter_readers_say_nothing_without_the_counters(monkeypatch):
    """On an engine from before the phase counters (no
    ``plan_metrics.recent``) the readers return nothing and do not
    raise."""
    from spark_rapids_jni_tpu.plan import plan_metrics
    monkeypatch.delattr(type(plan_metrics), "recent")
    run = {"measured": {"queries": 2}}
    for name in READERS:
        assert spec._reader(name)(run) is None


def test_counter_readers_average_the_untraced_queries(monkeypatch):
    """The last additions are the untraced queries; a warm-up's compile
    before them stays out of the mean."""
    import spark_rapids_jni_tpu.plan as engine
    from spark_rapids_jni_tpu.plan.compile import PlanMetrics
    pm = PlanMetrics()
    monkeypatch.setattr(engine, "plan_metrics", pm)
    for k, v in (("prepare_s", 0.3), ("dispatch_s", 0.03), ("trim_s", 2.0)):
        pm.add_time(k, v)                       # the warm-up
    for k, v in (("prepare_s", 0.002), ("dispatch_s", 0.001),
                 ("trim_s", 0.004)):
        for _ in range(3):                      # a traced, two untraced
            pm.add_time(k, v)
    got = {n: spec._reader(n)({"measured": {"queries": 2}})
           for n in READERS}
    assert got.pop("plan_stage_ms") is None     # no sharded query ran
    assert got == pytest.approx({"plan_prepare_ms": 2.0,
                                 "plan_dispatch_ms": 1.0,
                                 "plan_trim_ms": 4.0})
    assert all(spec._reader(n)({"measured": {"queries": 0}}) is None
               for n in READERS)
