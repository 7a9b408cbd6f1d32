"""The benchmark: cells of ``BENCHMARK.json`` run on the chip.

``python3 -m perfbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell. Everything that belongs to one
configuration, traffic mix or metric sits in a file of its own, found by
the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``: the deployment (scale, rows per table,
  chips, entry point, placement of the tables: resident on one chip,
  row-sharded over the mesh, or in Parquet files the engine reads inside
  the window, with the writer's settings);
- ``configs/<schema>/<query>.py``: the query's tables, its plan, its
  plain numpy reference and its lower-precision control;
- ``traffic/<traffic>.json``: the query, its literals, the loop and the
  traced stretch, read by ``loop.py``;
- ``metrics/<metric>.py``: one reader per metric, over the run record;
- ``peaks.json``: the chip's published peaks, keyed by ``device_kind``.
"""
