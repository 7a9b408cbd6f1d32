"""The ``parquet`` placement on the CPU: a tiny file-placed q6 cell,
loaded through ``spec.load_cell`` from a benchmark file of its own,
rehearses ``correct`` with an entry that reads the file and runs the
plan; two planted reader faults come out not correct; the files exist
for the warm-up and the window and are gone afterwards; the written
file has the block's settings; a malformed block is refused."""

from __future__ import annotations

import copy
import json
import os
import time

import numpy as np
import pytest

from perfbench import run, sources, spec
from perfbench.configs.tpch import gen

SEED = 2**31 + 26
SPARK = {"compression": "snappy", "dictionary": True,
         "data_page_version": "1.0", "row_group_bytes": 134217728,
         "data_page_bytes": 1048576}
ROWS = 40000
# 36 bytes a generated lineitem row: 13,888 rows a group, three groups
TINY_GROUP_BYTES = 500000
ENTRY = "read_then_execute"

with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)


def tiny_config(**changes):
    cfg = {"name": "tpch_tiny_parquet", "schema": "tpch",
           "rows": {"lineitem": ROWS}, "chips": 1, "entry": ENTRY,
           "placement": "parquet",
           "parquet": dict(SPARK, row_group_bytes=TINY_GROUP_BYTES)}
    cfg.update(changes)
    return {k: v for k, v in cfg.items() if v is not None}


@pytest.fixture
def load(tmp_path, monkeypatch):
    """``spec.load_cell`` over a benchmark file that holds one cell,
    ``parquet_q6_tiny`` (``q6_loop`` on the given config), in a checkout
    at ``tmp_path``; the data directory lies there too."""
    monkeypatch.setattr(spec, "ROOT", str(tmp_path))

    def load_with(config):
        bench = copy.deepcopy(BENCH)
        bench["configs"] = [{"name": config.get("name", "tiny"),
                             "file": "tiny.json"}]
        bench["workloads"] = [{"name": "parquet_q6_tiny",
                               "config": config.get("name", "tiny"),
                               "traffic": "q6_loop", "chips": 1}]
        (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
        (tmp_path / "tiny.json").write_text(json.dumps(config))
        return spec.load_cell("parquet_q6_tiny")
    return load_with


def read_then_execute(plan, source):
    from spark_rapids_jni_tpu.parquet import read_parquet
    from spark_rapids_jni_tpu.plan import execute_plan
    return execute_plan(plan, read_parquet(source))


def first_row_group(plan, source):
    """Fault: decodes only the file's first row group."""
    from spark_rapids_jni_tpu.parquet import ParquetReader
    from spark_rapids_jni_tpu.plan import execute_plan
    with ParquetReader(source) as r:
        table = next(iter(r.iter_chunks(byte_budget=1)))
    return execute_plan(plan, table)


def shifted_columns(plan, source):
    """Fault: takes lineitem's columns by position in a shifted order,
    so that l_tax stands where l_discount should."""
    from spark_rapids_jni_tpu.columnar.column import Table
    from spark_rapids_jni_tpu.parquet import read_parquet
    from spark_rapids_jni_tpu.plan import execute_plan
    cols = read_parquet(source).columns
    return execute_plan(plan, Table(cols[:2] + cols[3:] + cols[2:3]))


def rehearse(cell, entry, monkeypatch):
    from spark_rapids_jni_tpu import plan as engine
    import jax
    monkeypatch.setattr(engine, ENTRY, entry, raising=False)
    return run.run_cell(cell, SEED, 0.2, False, jax.devices()[:1],
                        spec.peaks("TPU v5 lite"), t0=time.perf_counter())


def test_file_placed_cell_rehearses_correct(load, monkeypatch):
    res = rehearse(load(tiny_config()), read_then_execute, monkeypatch)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert list(res)[-1] == "checks"
    assert {"rows_per_s", "setup_s"} <= set(res["metrics"])
    li = res["sources"]["lineitem"]
    assert (li["rows"], li["row_groups"], li["codec"]) == (ROWS, 3,
                                                          ["SNAPPY"])
    assert list(li["encodings"]) == list(gen.LINEITEM_Q1)
    assert li["bytes"] > 0 and sum(li["rows_per_group"]) == ROWS


@pytest.mark.parametrize("fault", [first_row_group, shifted_columns],
                         ids=["first_row_group", "shifted_columns"])
def test_planted_reader_fault_is_not_correct(load, monkeypatch, fault):
    res = rehearse(load(tiny_config()), fault, monkeypatch)
    assert not res["correct"], res["checks"]
    assert res["checks"]["wrong_values"]["value"] > 0


def test_files_live_through_the_window_and_go_after(load, monkeypatch):
    cell = load(tiny_config())
    seen = []

    def watched(plan, source):
        seen.append((source, os.path.isfile(source)))
        return read_then_execute(plan, source)

    res = rehearse(cell, watched, monkeypatch)
    directory = sources.data_dir(cell.name, SEED)
    want = os.path.join(directory, "lineitem.parquet")
    assert len(seen) == res["attempted"] + 1  # the warm-up and the window
    assert all(s == (want, True) for s in seen)
    assert not os.path.exists(directory)


def test_files_go_after_a_warm_up_that_raises(load, monkeypatch):
    cell = load(tiny_config())
    seen = []

    def broken(plan, source):
        seen.append(os.path.isfile(source))
        raise RuntimeError("decode failed")

    with pytest.raises(RuntimeError, match="warm-up query failed"):
        rehearse(cell, broken, monkeypatch)
    assert seen == [True]
    assert not os.path.exists(sources.data_dir(cell.name, SEED))


def _page_type(path, offset):
    """A page header's type: its first field, a compact-thrift i32."""
    with open(path, "rb") as f:
        f.seek(offset)
        head = f.read(2)
    assert head[0] == 0x15  # field 1, i32
    return {0x00: "DATA_PAGE", 0x04: "DICTIONARY_PAGE",
            0x06: "DATA_PAGE_V2"}[head[1]]


@pytest.mark.parametrize("version,page", [("1.0", "DATA_PAGE"),
                                          ("2.0", "DATA_PAGE_V2")])
def test_written_file_has_the_block_settings(load, version, page):
    import pyarrow.parquet as pq
    cell = load(tiny_config())
    settings = dict(cell.config["parquet"], data_page_version=version)
    host = cell.query.generate(cell.config["rows"], SEED)
    per_group = TINY_GROUP_BYTES // 36
    assert sources.rows_per_group(host[0], TINY_GROUP_BYTES) == per_group
    with sources.parquet_files(cell.name, SEED, cell.query, host,
                               settings) as files:
        (path,) = files.paths
        md = pq.ParquetFile(path).metadata
        assert md.num_row_groups == -(-ROWS // per_group) == 3
        for g in range(md.num_row_groups):
            for j, a in enumerate(host[0]):
                c = md.row_group(g).column(j)
                assert c.compression == "SNAPPY"
                assert _page_type(path, c.data_page_offset) == page
                if a.dtype == np.int32:
                    assert c.has_dictionary_page
                    assert _page_type(path, c.dictionary_page_offset) == (
                        "DICTIONARY_PAGE")
        assert files.described["lineitem"]["row_groups"] == 3
    assert not os.path.exists(os.path.dirname(path))


def test_spark_settings_load_and_make_two_sf1_row_groups(load):
    cell = load(tiny_config(parquet=SPARK))
    assert cell.config["parquet"] == SPARK
    widths = [np.zeros(0, np.int64)] * 2 + [np.zeros(0, np.int32)] * 5
    per_group = sources.rows_per_group(widths, SPARK["row_group_bytes"])
    assert per_group == 134217728 // 36 == 3728270
    assert -(-6001215 // per_group) == 2


@pytest.mark.parametrize("config", [
    tiny_config(parquet=None),
    tiny_config(parquet=dict(SPARK, page_index=True)),
    tiny_config(parquet={k: v for k, v in SPARK.items()
                         if k != "data_page_bytes"}),
    tiny_config(placement="device"),
    tiny_config(parquet=dict(SPARK, data_page_version=1)),
    tiny_config(parquet=dict(SPARK, row_group_bytes=0)),
    tiny_config(placement="hbm", parquet=None),
], ids=["missing", "unknown_key", "key_left_out", "other_placement",
        "bad_version", "bad_size", "unknown_placement"])
def test_bad_parquet_block_is_refused(load, config):
    with pytest.raises(spec.SpecError):
        load(config)
