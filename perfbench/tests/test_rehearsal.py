"""A CPU rehearsal of every cell at tiny sizes: set-up, window, reference
and metrics through the runner's own functions, with the look for a chip
skipped. The references are checked against the engine's eager
interpreter, the float32 controls and the planted faults must come out
as not correct."""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np
import pytest

from perfbench import run, spec

with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]
TINY = {"lineitem": 40000, "orders": 10000, "customer": 1000, "supplier": 66,
        "nation": 25}
SEED = 2**31 + 12345


def tiny(name: str) -> spec.Cell:
    cell = spec.load_cell(name)
    rows = {k: TINY[k] for k in cell.config["rows"]}
    return dataclasses.replace(cell, config={**cell.config, "rows": rows})


def sharded_tiny() -> spec.Cell:
    """Q1 over the row-sharded SF10 deployment of
    ``configs/tpch_sf10_4chip.json``, at a tiny size on four CPU devices:
    the path a four-chip cell drives."""
    with open(os.path.join(spec.HERE, "configs", "tpch_sf10_4chip.json")) as f:
        cfg = json.load(f)
    cell = tiny("q1_sf1")
    return dataclasses.replace(cell, name="q1_sf10_4chip", chips=4, config={
        **cfg, "rows": cell.config["rows"]})


def cpu_devices(chips: int):
    import jax
    return jax.devices()[:chips]


def rehearse(cell: spec.Cell, traced: bool = False):
    import time
    return run.run_cell(cell, SEED, 0.2, traced, cpu_devices(cell.chips),
                        spec.peaks("TPU v5 lite"), t0=time.perf_counter())


def tiny_or_sharded(name: str) -> spec.Cell:
    return sharded_tiny() if name == "sharded" else tiny(name)


@pytest.mark.parametrize("name", CELLS + ["sharded"])
def test_cell_rehearses_correct(name):
    res = rehearse(tiny_or_sharded(name))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    assert {"rows_per_s", "setup_s"} <= set(res["metrics"])
    assert res["device"]["platform"] == "cpu"


def _engine_input(cell, host):
    inputs = run.upload(host, cell.query.WITH_STATS, "device",
                        cpu_devices(1))
    return inputs[0] if len(inputs) == 1 else inputs


@pytest.mark.parametrize("name", ["q1_sf1", "q6_sf10", "q5_sf1"])
def test_reference_matches_eager_interpreter(name):
    """The numpy reference and the engine's eager interpreter agree."""
    from spark_rapids_jni_tpu.plan import run_eager
    cell = tiny(name)
    q, lit = cell.query, cell.traffic["literals"]
    host = q.generate(cell.config["rows"], SEED)
    out = run_eager(q.plan(**lit), _engine_input(cell, host))
    got = q.answer([np.asarray(c.data) for c in out.columns])
    assert q.compare(got, q.reference(host, **lit)) == {
        k: 0 for k in q.LIMITS}


@pytest.mark.parametrize("name", ["q1_sf1", "q6_sf10", "q5_sf1"])
def test_float32_control_is_not_correct(name):
    """The reference in float32, put in the engine's place, fails."""
    cell = tiny(name)
    q, lit = cell.query, cell.traffic["literals"]
    host = q.generate(cell.config["rows"], SEED)
    nums = q.compare(q.control(host, **lit), q.reference(host, **lit))
    assert any(not v <= q.LIMITS[k] for k, v in nums.items()), nums


def _faulty_entry(entry, fault: str):
    """``entry`` with one fault planted where the answer is produced."""
    from spark_rapids_jni_tpu.columnar.column import Column, Table
    from spark_rapids_jni_tpu.columnar.table_ops import slice_table

    def first(t, n):
        return slice_table(t, 0, n)

    def broken(plan, arg, **kw):
        tables = [arg] if isinstance(arg, Table) else list(arg)
        if fault == "unchanged":       # the step hands back its input
            return tables[0]
        if fault == "half_batch":      # half the rows left out
            tables = [first(t, max(t.num_rows // 2, 1)) if t is
                      max(tables, key=lambda x: x.num_rows) else t
                      for t in tables]
        if fault == "no_exchange":     # one chip's rows, never merged
            tables = [first(t, t.num_rows // 4) for t in tables]
        out = entry(plan, tables[0] if len(tables) == 1 else tables, **kw)
        if fault == "altered":         # one value of the answer changed
            c = out.columns[-1]
            c = Column(c.dtype, c.size, data=c.data.at[0].add(1),
                       validity=c.validity, children=c.children)
            out = Table(out.columns[:-1] + (c,))
        return out
    return broken


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "altered"])
@pytest.mark.parametrize("name", CELLS + ["sharded"])
def test_planted_fault_is_not_correct(name, fault, monkeypatch):
    from spark_rapids_jni_tpu import plan as engine
    cell = tiny_or_sharded(name)
    entry = getattr(engine, cell.config["entry"])
    monkeypatch.setattr(engine, cell.config["entry"],
                        _faulty_entry(entry, fault))
    res = rehearse(cell)
    assert not res["correct"], res["checks"]


def test_exchange_left_out_is_not_correct(monkeypatch):
    from spark_rapids_jni_tpu import plan as engine
    cell = sharded_tiny()
    monkeypatch.setattr(engine, "execute_plan_sharded", _faulty_entry(
        engine.execute_plan_sharded, "no_exchange"))
    assert not rehearse(cell)["correct"]
