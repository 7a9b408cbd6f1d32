"""The ``parquet`` placement: a cell's tables kept as Parquet files.

Under ``"placement": "parquet"`` set-up writes each generated table to
``<checkout>/.srjt_cache/perfbench/data/<cell>-<seed>/<table>.parquet``
with pyarrow and the settings of the configuration's ``parquet`` block,
reads each file back once to check that it holds the generated arrays,
and uploads nothing. The engine's entry is then called as
``entry(plan, sources, **entry_kwargs)``: ``sources`` is the one file's
path, or the list of paths in plan input order, and the warm-up and
every query of the window get the same paths. Opening, decoding and
uploading the file is the engine's work, inside each timed query.

The files live from set-up until the window has closed and the peak has
been read; they are deleted then, and also when the warm-up or the
window raises. The harness drops no cache, so the window reads them
from the host's page cache: the deployment this stands for is an
executor whose split is already on local storage or in host memory.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import shutil
from types import ModuleType
from typing import Any, Dict, Iterator, List, Sequence

import numpy as np

from perfbench import spec


def data_dir(cell: str, seed: int) -> str:
    return os.path.join(spec.ROOT, ".srjt_cache", "perfbench", "data",
                        f"{cell}-{seed}")


def rows_per_group(arrays: Sequence[np.ndarray], row_group_bytes: int) -> int:
    """Rows a row group holds: ``row_group_bytes`` over the table's bytes
    a row as generated (the sum of its columns' item sizes), at least 1.
    parquet-mr closes a row group when its buffered, encoded bytes reach
    ``parquet.block.size``; encoded rows are no wider than generated
    ones, so this gives at least as many row groups as it would. The
    written file's row-group count is checked against it."""
    width = sum(a.dtype.itemsize for a in arrays)
    return max(1, row_group_bytes // width)


@dataclasses.dataclass(frozen=True)
class Files:
    """The written files, in plan input order, and what each holds."""
    paths: List[str]
    described: Dict[str, Dict[str, Any]]


def _describe(path: str) -> Dict[str, Any]:
    import pyarrow.parquet as pq
    md = pq.ParquetFile(path).metadata
    groups = [md.row_group(i) for i in range(md.num_row_groups)]
    encodings = {}
    for j in range(md.num_columns):
        name = md.schema.column(j).name
        encodings[name] = sorted({e for g in groups
                                  for e in g.column(j).encodings})
    return {"bytes": os.path.getsize(path), "rows": md.num_rows,
            "row_groups": md.num_row_groups,
            "rows_per_group": [g.num_rows for g in groups],
            "codec": sorted({g.column(j).compression for g in groups
                             for j in range(md.num_columns)}),
            "encodings": encodings}


def _write(path: str, columns: Sequence[str], arrays: Sequence[np.ndarray],
           settings: Dict[str, Any]) -> Dict[str, Any]:
    """One table to ``path``, read back and checked; what it holds."""
    import pyarrow as pa
    import pyarrow.parquet as pq
    if len(columns) != len(arrays):
        raise spec.SpecError(f"{path}: {len(columns)} column names for "
                             f"{len(arrays)} generated columns")
    per_group = rows_per_group(arrays, settings["row_group_bytes"])
    pq.write_table(pa.table(dict(zip(columns, arrays))), path,
                   row_group_size=per_group,
                   compression=settings["compression"],
                   use_dictionary=settings["dictionary"],
                   data_page_version=settings["data_page_version"],
                   data_page_size=settings["data_page_bytes"])
    back = pq.read_table(path)
    if back.column_names != list(columns):
        raise RuntimeError(f"{path} holds columns {back.column_names}, "
                           f"not {list(columns)}")
    for name, a in zip(columns, arrays):
        b = back.column(name).to_numpy()
        if b.dtype != a.dtype or not np.array_equal(b, a):
            raise RuntimeError(f"{path}: column {name} reads back as "
                               f"{b.dtype}, not the {a.dtype} written, "
                               f"or with other values")
    described = _describe(path)
    rows = int(arrays[0].shape[0])
    want = max(1, -(-rows // per_group))
    if described["row_groups"] != want:
        raise RuntimeError(f"{path}: {described['row_groups']} row groups, "
                           f"{rows} rows at {per_group} a group make {want}")
    return described


@contextlib.contextmanager
def parquet_files(cell: str, seed: int, query: ModuleType,
                  host: Sequence[Sequence[np.ndarray]],
                  settings: Dict[str, Any]) -> Iterator[Files]:
    """Write ``host``'s tables as ``query.TABLES`` names them; delete
    them, and their directory, when the context closes."""
    if len(query.TABLES) != len(host):
        raise spec.SpecError(f"{query.__name__}: TABLES names "
                             f"{len(query.TABLES)} tables, generate gives "
                             f"{len(host)}")
    directory = data_dir(cell, seed)
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory)
    try:
        paths, described = [], {}
        for (table, columns), arrays in zip(query.TABLES, host):
            path = os.path.join(directory, f"{table}.parquet")
            described[table] = _write(path, columns, arrays, settings)
            paths.append(path)
        yield Files(paths, described)
    finally:
        shutil.rmtree(directory, ignore_errors=True)
