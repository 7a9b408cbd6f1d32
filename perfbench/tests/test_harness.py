"""The harness without the engine: BENCHMARK.json's shape, the refusals
(no TPU, too few chips, a device kind with no peaks, a checkout without
the engine), the loop's window and the metric readers."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

from perfbench import loop, run, spec

with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["perfbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("perfbench/")
        with open(os.path.join(spec.ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == set(configs)
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 2)
    pairs = {(w["config"], w["traffic"]) for w in BENCH["workloads"]}
    assert len(pairs) == len(BENCH["workloads"])
    cells = {w["name"] for w in BENCH["workloads"]}
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e and m["source"] in SOURCES
        assert set(m["workloads"]) <= cells
    for entry in (BENCH["configs"] + BENCH["workloads"]
                  + BENCH["end_to_end"] + BENCH["per_layer"]):
        assert NAME.match(entry["name"]), entry["name"]
        for k in ("why", "layer", "source"):
            if k in entry:
                assert 1 <= len(entry[k]) <= 200 and "\n" not in entry[k]
        if "unit" in entry:
            assert UNIT.match(entry["unit"]), entry["unit"]
    for name in cells:
        cell = spec.load_cell(name)
        assert cell.end_to_end and cell.per_layer
        assert {"setup_s", "rows_per_s"} <= {m.name for m in cell.end_to_end}


class FakeDevice(SimpleNamespace):
    def memory_stats(self):
        return None


def _devices(monkeypatch, kind, n):
    import jax
    devs = [FakeDevice(platform="tpu", device_kind=kind, id=i)
            for i in range(n)]
    monkeypatch.setattr(jax, "devices", lambda *a: devs)


def test_refuses_the_cpu(capsys, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", run.CACHE_DIR)
    assert run.main(["--workload", "q1_sf1", "--seed", "1",
                     "--seconds", "1"]) == 3
    assert capsys.readouterr().out == ""


def test_refuses_too_few_chips(monkeypatch):
    _devices(monkeypatch, "TPU v5 lite", 1)
    with pytest.raises(run.NoDevice, match="4 chips"):
        run.require_devices(4)


def test_a_cell_uses_the_chips_its_placement_fills():
    """A cell may hold a whole host for steadiness and fill one chip of
    it: busy time and the roofline then count that chip alone."""
    devs = ["chip0", "chip1", "chip2", "chip3"]
    assert run.used_devices("device", devs) == ["chip0"]
    assert run.used_devices("parquet", devs) == ["chip0"]
    assert run.used_devices("row_sharded", devs) == devs
    q6 = spec.load_cell("q6_sf10")
    assert q6.chips == 4 and q6.config["placement"] == "device"


def test_refuses_a_device_kind_without_peaks(monkeypatch):
    _devices(monkeypatch, "TPU v99", 1)
    with pytest.raises(run.NoDevice, match="peaks.json"):
        run.require_devices(1)
    _devices(monkeypatch, "TPU v5 lite", 4)
    devs, peaks = run.require_devices(4)
    assert len(devs) == 4 and peaks["hbm_bytes_per_s"] == 819e9


def test_checkout_without_the_engine_fails(tmp_path):
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(spec.ROOT, "perfbench"),
                    tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    cmd = BENCH["command"] + ["--workload", "q1_sf1", "--seed", "3",
                              "--seconds", "1", "--trace", "0"]
    p = subprocess.run([sys.executable] + cmd[1:], cwd=tmp_path,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""


class _Probe:
    def __init__(self):
        self.x = 0.0
        self.faults = {"plan_fallbacks": 0}

    def __call__(self):
        self.x += 0.001
        return self.x, dict(self.faults)


def test_window_closes_at_the_first_completion_after_its_length():
    import time
    traffic = {"loop": "closed", "clients": 1,
               "trace": {"queries": 2, "seconds": 100}}
    calls = []

    class T:
        def start(self):
            calls.append("start")

        def stop(self):
            calls.append("stop")

    def serve():
        time.sleep(0.02)
        return "answer"

    done, w0 = loop.run_window(traffic, serve, _Probe(), 0.1, T())
    assert done[-1].end - w0 >= 0.1 and done[-2].end - w0 < 0.1
    assert calls == ["start", "stop"]
    assert [d.traced for d in done[:3]] == [True, True, False]
    assert all(d.answer == "answer" and not d.failed for d in done)


def test_a_fallback_or_a_raise_counts_as_failed():
    probe = _Probe()

    def fallback():
        probe.faults["plan_fallbacks"] += 1
        return "slow answer"

    d = loop.serve_once(fallback, probe, 0)
    assert d.failed and d.moved == ("plan_fallbacks",)

    def boom():
        raise RuntimeError("device lost")

    d = loop.serve_once(boom, probe, 1)
    assert d.failed and d.answer is None and "device lost" in d.error


def _record(**kw):
    rec = {"chips": 1, "peaks": {"hbm_bytes_per_s": 819e9},
           "setup_s": 20.0, "elapsed_s": 10.0, "latencies_s": [0.1] * 100,
           "completed": 100, "input_rows": 1000, "bytes_read": 819e6,
           "peak_bytes": 2e9,
           "compile": {"setup_s": 1.5, "window_n": 0},
           "measured": {"queries": 100, "seconds": 10.0, "execute_s": 8.0},
           "trace": None}
    rec.update(kw)
    return rec


def _read(name, rec):
    return spec._reader(name)(rec)


def test_metric_readers():
    rec = _record()
    assert _read("rows_per_s", rec) == 1000 * 100 / 10.0
    assert _read("query_p95_ms", rec) == pytest.approx(100.0)
    assert _read("peak_hbm_gb", rec) == 2.0
    assert _read("plan_execute_ms", rec) == pytest.approx(80.0)
    assert _read("plan_outside_ms", rec) == pytest.approx(20.0)
    assert _read("window_compiles", rec) == 0
    for name in ("fused_plan_roofline", "device_busy_ms", "device_idle_pct",
                 "collective_ms"):
        assert _read(name, rec) is None  # nothing to read: left out
    tr = {"queries": 2, "busy_s": 0.5, "window_s": 1.0, "collective_s": 0.1,
          "collective_ops": 4}
    rec = _record(trace=tr)
    assert _read("device_busy_ms", rec) == pytest.approx(250.0)
    assert _read("device_idle_pct", rec) == pytest.approx(50.0)
    assert _read("fused_plan_roofline", rec) == pytest.approx(0.4)
    assert _read("collective_ms", rec) == pytest.approx(50.0)
    rec = _record(trace=dict(tr, busy_s=0.0, collective_ops=0))
    assert _read("fused_plan_roofline", rec) is None
    assert _read("collective_ms", rec) is None
