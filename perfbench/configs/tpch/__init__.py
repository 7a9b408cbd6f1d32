"""TPC-H queries as the benchmark drives them.

Each ``<query>.py`` module holds what one query needs, so a later query
is a new file:

- ``generate(rows, seed)``: the input tables as lists of host arrays;
- ``TABLES``: ``((table, column_names), ...)`` in ``generate``'s order,
  the TPC-H names of each table and of its generated columns; the
  ``parquet`` placement writes a file a table under these names, so
  that the engine can prune columns by name;
- ``WITH_STATS``: whether the tables carry advisory column statistics
  (as a Parquet footer would give them to the planner);
- ``plan(**literals)``: the logical plan handed to the engine;
- ``input_rows(rows)`` and ``bytes_read(rows)``: the rows the query
  scans, and the bytes of the columns it references, read once;
- ``answer(columns)``: the engine's output, as host arrays, in a plain
  form that ``compare`` reads;
- ``reference(tables, **literals)``: the plain numpy reference, exact;
- ``control(tables, **literals)``: the same reference in float32 on the
  default device, the precision a later change might be tempted by;
- ``compare(got, want)``: the numbers compared, each against ``LIMITS``.
"""
