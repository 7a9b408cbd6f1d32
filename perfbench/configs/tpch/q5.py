"""TPC-H Q5, local supplier volume (Clause 2.4.5), over five tables.

Orders in a one-year date window join their customer, lineitem joins
those orders and its supplier, suppliers join the nations of one region;
the customer's and the supplier's nation must agree. Revenue =
sum(l_extendedprice * (1 - l_discount)) per supplier nation, exact in
cents x percents, ordered by revenue descending. All four joins run
inside one fused program; the build keys are dense primary keys, so the
planner lowers each to a direct-addressed probe.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from . import gen

WITH_STATS = True
TABLES = (("customer", ("c_custkey", "c_nationkey")),
          ("orders", ("o_orderkey", "o_custkey", "o_orderdate")),
          ("lineitem", ("l_orderkey", "l_suppkey", "l_extendedprice",
                        "l_discount")),
          ("supplier", ("s_suppkey", "s_nationkey")),
          ("nation", ("n_nationkey", "n_regionkey")))
LIMITS = {"wrong_values": 0}


def generate(rows: Dict[str, int], seed: int) -> List[List[np.ndarray]]:
    return gen.q5_tables(rows, seed)


def plan(region_code: int, date_lo: int, date_hi: int):
    """A five-input plan DAG, as the engine's own TPC-H benchmark writes
    it. Inputs: customer 0, orders 1, lineitem 2, supplier 3, nation 4."""
    from spark_rapids_jni_tpu.plan import (Filter, GroupBy, Join, Project,
                                           Scan, Sort, col, i64, lit)
    ord_f = Filter(Scan(3, input_index=1),
                   (col(2) >= lit(date_lo)) & (col(2) < lit(date_hi)))
    oc = Join(ord_f, Scan(2, input_index=0), (1,), (0,), "inner")
    nat_f = Filter(Scan(2, input_index=4), col(1) == lit(region_code))
    sn = Join(Scan(2, input_index=3), nat_f, (1,), (0,), "inner")
    lo = Join(Scan(4, input_index=2), oc, (0,), (0,), "inner")
    ls = Join(lo, sn, (1,), (0,), "inner")
    # ls columns: l_orderkey0 l_suppkey1 l_price2 l_disc3 | o_orderkey4
    #   o_custkey5 o_orderdate6 | c_custkey7 c_nationkey8 | s_suppkey9
    #   s_nationkey10 | n_nationkey11 n_regionkey12
    conat = Filter(ls, col(8) == col(10))
    rev = i64(col(2)) * (lit(100) - i64(col(3)))
    proj = Project(conat, (col(10), rev))
    return Sort(GroupBy(proj, (0,), ((1, "sum"),)), (1,),
                ascending=(False,))


def input_rows(rows: Dict[str, int]) -> int:
    return sum(rows[t] for t, _columns in TABLES)


def bytes_read(rows: Dict[str, int]) -> int:
    """Every resident column is referenced: customer, supplier and nation
    an int64 key and an int32 code; orders two int64 and an int32;
    lineitem three int64 and an int32."""
    return (12 * (rows["customer"] + rows["supplier"] + rows["nation"])
            + 20 * rows["orders"] + 28 * rows["lineitem"])


def answer(cols: Sequence[np.ndarray]) -> list:
    """(nation, revenue) pairs in the engine's order."""
    return list(zip(cols[0].tolist(), cols[1].tolist()))


def _joined(tables, region_code, date_lo, date_hi):
    """Per lineitem row: its supplier's nation and whether it qualifies."""
    (_, c_nat), (_, o_cust, o_date), (l_okey, l_skey, _, _), \
        (_, s_nat), (_, n_reg) = tables
    o_ok = (o_date >= date_lo) & (o_date < date_hi)
    s_ok = n_reg[s_nat] == region_code
    snat = s_nat[l_skey]
    m = o_ok[l_okey] & s_ok[l_skey] & (c_nat[o_cust[l_okey]] == snat)
    return snat, m


def reference(tables, **lit) -> list:
    for t in tables[:2] + tables[3:]:
        if not np.array_equal(t[0], np.arange(t[0].size)):
            raise ValueError("the reference indexes by dense primary keys")
    snat, m = _joined(tables, **lit)
    price, disc = tables[2][2], tables[2][3]
    rev = price[m] * (100 - disc[m].astype(np.int64))
    sums = gen.group_sums(snat[m].astype(np.int64), rev)
    return sorted(sums.items(), key=lambda kv: -kv[1])


def control(tables, **lit) -> list:
    """``reference`` with the revenue and its sums in float32."""
    import jax
    import jax.numpy as jnp
    t = [[jnp.asarray(a) for a in tab] for tab in tables]
    snat, m = _joined(t, **lit)
    price, disc = t[2][2], t[2][3]
    rev = price.astype(jnp.float32) * (100 - disc).astype(jnp.float32)
    nseg = int(tables[4][0].size)
    sums = jax.ops.segment_sum(jnp.where(m, rev, jnp.float32(0)), snat,
                               num_segments=nseg)
    hits = jax.ops.segment_sum(m.astype(jnp.int32), snat, num_segments=nseg)
    out = [(k, round(float(v))) for k, (v, h) in
           enumerate(zip(np.asarray(sums), np.asarray(hits))) if h]
    return sorted(out, key=lambda kv: -kv[1])


def compare(got: list, want: list) -> Dict[str, float]:
    """wrong_values: nations whose revenue differs or that one side lacks,
    repeated nations, and places where the order is not descending."""
    g, w = dict(got), dict(want)
    wrong = sum(g.get(k) != w.get(k) for k in set(g) | set(w))
    wrong += len(got) - len(g)
    revs = [r for _, r in got]
    wrong += sum(a < b for a, b in zip(revs, revs[1:]))
    return {"wrong_values": wrong}
