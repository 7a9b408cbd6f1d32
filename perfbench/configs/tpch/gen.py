"""TPC-H-shaped tables generated on the host from the seed.

The draws are those of the engine's own TPC-H benchmark generators
(uniform values, dense ``arange`` primary keys, money as int64 cents,
discount and tax as integer percents, flags and segments as int32 codes,
dates as int32 days into a 2,500-day range), with the row count of every
table taken from the configuration instead of a fixed ratio. Only the
columns the query references are generated, as Spark's scan prunes them.
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

LINEITEM_Q1 = ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
               "l_returnflag", "l_linestatus", "l_shipdate")


def lineitem_q1(rows: Dict[str, int], seed: int) -> List[List[np.ndarray]]:
    """One table: lineitem's seven q1/q6 columns, in ``LINEITEM_Q1`` order."""
    n = rows["lineitem"]
    rng = np.random.default_rng(seed)
    return [[rng.integers(1, 51, n),
             rng.integers(90000, 10500000, n),
             rng.integers(0, 11, n).astype(np.int32),
             rng.integers(0, 9, n).astype(np.int32),
             rng.integers(0, 3, n).astype(np.int32),
             rng.integers(0, 2, n).astype(np.int32),
             rng.integers(0, 2500, n).astype(np.int32)]]


def q5_tables(rows: Dict[str, int], seed: int) -> List[List[np.ndarray]]:
    """Five tables, in plan input order:

    customer: (c_custkey i64, c_nationkey i32)
    orders:   (o_orderkey i64, o_custkey i64, o_orderdate-days i32)
    lineitem: (l_orderkey i64, l_suppkey i64, l_extendedprice-cents i64,
               l_discount-pct i32)
    supplier: (s_suppkey i64, s_nationkey i32)
    nation:   (n_nationkey i64, n_regionkey i32)
    """
    ncust, nord = rows["customer"], rows["orders"]
    nsupp, nnat, n = rows["supplier"], rows["nation"], rows["lineitem"]
    rng = np.random.default_rng(seed)
    cust = [np.arange(ncust, dtype=np.int64),
            rng.integers(0, nnat, ncust).astype(np.int32)]
    orders = [np.arange(nord, dtype=np.int64),
              rng.integers(0, ncust, nord),
              rng.integers(0, 2400, nord).astype(np.int32)]
    lineitem = [rng.integers(0, nord, n),
                rng.integers(0, nsupp, n),
                rng.integers(90000, 10500000, n),
                rng.integers(0, 11, n).astype(np.int32)]
    supplier = [np.arange(nsupp, dtype=np.int64),
                rng.integers(0, nnat, nsupp).astype(np.int32)]
    nation = [np.arange(nnat, dtype=np.int64),
              rng.integers(0, 5, nnat).astype(np.int32)]
    return [cust, orders, lineitem, supplier, nation]


def group_sums(keys: np.ndarray, vals: np.ndarray) -> Dict[int, int]:
    """{key: exact int64 sum} via a stable sort and ``reduceat``."""
    if keys.size == 0:
        return {}
    order = np.argsort(keys, kind="stable")
    k, v = keys[order], vals[order]
    starts = np.flatnonzero(np.r_[True, k[1:] != k[:-1]])
    return dict(zip(k[starts].tolist(), np.add.reduceat(v, starts).tolist()))
