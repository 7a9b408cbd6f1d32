"""TPC-H Q6, forecasting revenue change (Clause 2.4.6), over lineitem.

One year of l_shipdate, l_discount within 0.05-0.07, l_quantity < 24;
revenue = sum(l_extendedprice * l_discount), exact in cents x percents.
The engine runs it as a constant-key GroupBy that aggregates into slots.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from . import gen

WITH_STATS = False
TABLES = (("lineitem", gen.LINEITEM_Q1),)
LIMITS = {"wrong_values": 0}


def generate(rows: Dict[str, int], seed: int) -> List[List[np.ndarray]]:
    return gen.lineitem_q1(rows, seed)


def plan(date_lo: int, date_hi: int, disc_lo: int, disc_hi: int,
         qty_max: int):
    """filter -> project a literal key and the revenue -> one-group sum,
    as the engine's own TPC-H benchmark writes it."""
    from spark_rapids_jni_tpu.plan import (Filter, GroupBy, Project, Scan,
                                           col, i64, lit)
    return GroupBy(
        Project(Filter(Scan(7),
                       (col(6) >= lit(date_lo)) & (col(6) < lit(date_hi))
                       & (col(2) >= lit(disc_lo))
                       & (col(2) <= lit(disc_hi))
                       & (col(0) < lit(qty_max))),
                (i64(lit(0)), i64(col(1)) * i64(col(2)))),
        (0,), ((1, "sum"),))


def input_rows(rows: Dict[str, int]) -> int:
    return rows["lineitem"]


def bytes_read(rows: Dict[str, int]) -> int:
    """The four referenced columns: quantity and price (int64), discount
    and shipdate (int32)."""
    return rows["lineitem"] * (2 * 8 + 2 * 4)


def answer(cols: Sequence[np.ndarray]) -> int:
    return int(cols[1][0]) if cols[1].shape[0] else 0


def _mask(tables, date_lo, date_hi, disc_lo, disc_hi, qty_max):
    qty, _price, disc, _tax, _rf, _ls, sd = tables
    return ((sd >= date_lo) & (sd < date_hi) & (disc >= disc_lo)
            & (disc <= disc_hi) & (qty < qty_max))


def reference(tables, **lit) -> int:
    t = tables[0]
    m = _mask(t, **lit)
    return int((t[1][m] * t[2][m].astype(np.int64)).sum())


def control(tables, **lit) -> int:
    """``reference`` with the product and the sum in float32."""
    import jax.numpy as jnp
    t = [jnp.asarray(a) for a in tables[0]]
    m = _mask(t, **lit)
    rev = t[1].astype(jnp.float32) * t[2].astype(jnp.float32)
    return round(float(jnp.sum(jnp.where(m, rev, jnp.float32(0)))))


def compare(got: int, want: int) -> Dict[str, float]:
    return {"wrong_values": int(got != want)}
