"""Groupby segment sums without a scatter-add.

Integer sums and counts over sorted segments come from block totals
(``ops/groupby._block_segment_sums``), and the single-slot direct groupby
(a literal key, TPC-H q6) reduces each aggregate into slot 0. Both must
be bit-identical to ``jax.ops.segment_sum``, int64 wrap-around included,
and the fused q1 and q6 programs must hold no add-combining scatter: a
return to the scatter shows here, without a chip.
"""

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench.configs.tpch import q1, q6
from spark_rapids_jni_tpu.columnar import dtype as dt
from spark_rapids_jni_tpu.columnar.column import Column, Table
from spark_rapids_jni_tpu.ops import groupby as gb
from spark_rapids_jni_tpu.ops.scan import nonzero_indices
from spark_rapids_jni_tpu.plan import execute_plan, plan_metrics
from spark_rapids_jni_tpu.plan.compile import _make_fn
from spark_rapids_jni_tpu.plan.planner import plan_decisions
from spark_rapids_jni_tpu.utils import config

B = gb._SUM_BLOCK
I64 = np.iinfo(np.int64)


def _starts(rng, n, segments):
    """Sorted first rows of ``segments`` segments over n rows."""
    rest = rng.choice(np.arange(1, n), segments - 1, replace=False)
    return np.sort(np.concatenate([[0], rest]))


def _case(name):
    """(z, segment starts, n, G) for one named case."""
    rng = np.random.default_rng(sum(map(ord, name)))
    big = lambda n: rng.integers(I64.min, I64.max, n)  # noqa: E731
    if name == "wrap":  # every sum wraps: values near +-2^63
        n = 3 * B + 5
        z = np.where(rng.random(n) < 0.5, I64.max - rng.integers(0, 9, n),
                     I64.min + rng.integers(0, 9, n))
        return z, _starts(rng, n, 10), n, 16
    if name == "one_row":
        return np.array([I64.min + 3]), np.array([0]), 1, 4
    if name == "ragged":  # n not a multiple of the block
        n = 40 * B + 77
        return big(n), _starts(rng, n, 20), n, 32
    if name == "under_block":  # fewer rows than a block
        n = B // 3
        return rng.integers(-1000, 1000, n), _starts(rng, n, 5), n, 8
    if name == "group_a_row":  # end blocks past 2^20 values: blocks of 1
        n = 3000
        return big(n), np.arange(n), n, 1 << 14
    if name == "sparse_slots":  # G far above the live segments
        n = 5000
        return big(n), _starts(rng, n, 3), n, 4096
    if name == "long_segment":  # one segment over hundreds of blocks
        n = 100_000
        return big(n), np.concatenate([[0], 90_000 + _starts(
            rng, n - 90_000, 20)]), n, 32
    if name == "masked_dead_clamped":
        # groupby_core's shape: more segments than slots, the ones past
        # G clamped into the last slot; masked and clamped rows carry 0
        n, G = 4 * B + 7, 8
        starts = _starts(rng, n, 2 * G)
        seg = np.cumsum(np.isin(np.arange(n), starts)) - 1
        ok = (seg < G) & (rng.random(n) < 0.7)
        return np.where(ok, big(n), 0), starts, n, G
    raise KeyError(name)


CASES = ("wrap", "one_row", "ragged", "under_block", "group_a_row",
         "sparse_slots", "long_segment", "masked_dead_clamped")


@partial(jax.jit, static_argnums=(2, 3))
def _both_sums(z, starts, n, G):
    """(block sum, scatter sum, block count, scatter count) in one
    program: one compile a case instead of one per operation. The sum
    and the count go through one block sum together, as a groupby's do;
    the slots' ends come from the segment starts, as a groupby's do."""
    boundary = jnp.zeros(n, bool).at[starts].set(True)
    seg = jnp.cumsum(boundary) - 1
    valid = z != 0
    ends = gb._segment_ends(nonzero_indices(boundary, G), seg[-1] + 1, n)

    def scatter(x):
        return jax.ops.segment_sum(x, jnp.minimum(seg, G - 1),
                                   num_segments=G, indices_are_sorted=True)
    got = gb._block_segment_sums([z, valid], ends)
    return got[0], scatter(z), got[1], scatter(valid.astype(jnp.int64))


@pytest.mark.parametrize("name", CASES)
def test_block_sum_bit_identical_to_segment_sum(name):
    """Sums, and the count of the nonzero rows, as ``segment_sum``."""
    z, starts, n, G = _case(name)
    got, want, got_n, want_n = _both_sums(jnp.asarray(z, jnp.int64),
                                          jnp.asarray(starts), n, G)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got_n), np.asarray(want_n))


@pytest.mark.parametrize("n, slots, rows", [
    (6_001_215, 4096, B),      # q1 at SF1
    (16_384, 4096, B),         # the four-chip merge of 4 x 4096 partials
    (1_000_000, 1 << 20, 1),   # about a group a row: a plain prefix
])
def test_block_rows_bound_the_end_reads(n, slots, rows):
    assert gb._block_rows(n, slots) == rows


@pytest.mark.parametrize("live_agg", (None, 0))
@pytest.mark.parametrize("keep_share", (0.6, 0.0))
def test_direct_wide_single_slot_matches_eager(live_agg, keep_share):
    """span == 1: each aggregate is one masked reduction into slot 0,
    the same sums, count and liveness as the eager groupby."""
    n, lo, slots = 3000, 7, 1024
    rng = np.random.default_rng(11)
    vals = rng.integers(1, 1 << 40, n)
    mask = rng.random(n) < keep_share
    key = jnp.full((n,), lo, jnp.int64)
    slot_keys, outs, live_mask, live, bad = gb.groupby_direct_wide_core(
        key, ((jnp.asarray(vals), "sum"), (None, "count")),
        jnp.asarray(mask), lo, 1, slots, live_agg)
    eager = gb.groupby_aggregate(
        Table((Column(dt.INT64, n, data=key),
               Column(dt.INT64, n, data=jnp.asarray(vals)))),
        [0], [(1, "sum"), (1, "count")], row_mask=jnp.asarray(mask))
    assert not bool(bad) and int(slot_keys[0]) == lo
    assert int(live) == eager.num_rows == int(mask.any())
    want_live = np.zeros(slots, bool)
    want_live[0] = mask.any()
    np.testing.assert_array_equal(np.asarray(live_mask), want_live)
    for j, out in enumerate(outs):
        out = np.asarray(out)
        assert out.shape == (slots,) and not out[1:].any()
        want = (eager.columns[1 + j].to_pylist()[0] if eager.num_rows
                else 0)
        assert int(out[0]) == want


def _tpch_table(query, n, seed):
    host = query.generate({"lineitem": n}, seed)
    types = {np.dtype(np.int64): dt.INT64, np.dtype(np.int32): dt.INT32}
    return host, Table(tuple(Column(types[a.dtype], n, data=jnp.asarray(a))
                             for a in host[0]))


def _answer(query, out):
    return query.answer([np.asarray(c.data) for c in out.columns])


@pytest.mark.parametrize("seed", (3, 2**31 + 17))
def test_fused_q1_matches_reference(seed):
    host, table = _tpch_table(q1, 3000, seed)
    got = _answer(q1, execute_plan(q1.plan(2400), table))
    err = q1.compare(got, q1.reference(host, 2400))
    assert err["wrong_values"] == 0
    assert err["mean_rel_err"] <= q1.LIMITS["mean_rel_err"]


@pytest.mark.parametrize("seed", (5, 2**31 + 29))
def test_fused_q6_matches_reference(seed):
    lits = dict(date_lo=365, date_hi=730, disc_lo=5, disc_hi=7, qty_max=24)
    host, table = _tpch_table(q6, 20_000, seed)
    got = _answer(q6, execute_plan(q6.plan(**lits), table))
    assert q6.compare(got, q6.reference(host, **lits))["wrong_values"] == 0


def _add_scatters(hlo: str) -> list:
    """Scatters whose combiner computation adds (a scatter-set, as in
    ``nonzero_indices``, returns the update unchanged)."""
    bodies = dict(re.findall(r"^%?(\S+) \([^\n]*\{\n(.*?)\n\}", hlo,
                             re.M | re.S))
    found = []
    for line in hlo.splitlines():
        m = re.search(r" scatter\(.*to_apply=%?([\w.-]+)", line)
        if m and " add(" in bodies.get(m.group(1), ""):
            found.append(line.strip())
    return found


# (plan, block sums built: q1's sort groupby takes one count, its value
# columns having no nulls, and one sum for each of the 5 columns its 4
# sums and 3 means read; q6 its sum and its slot's count)
_PLANS = {"q1": (lambda: q1.plan(2400), 6),
          "q6": (lambda: q6.plan(365, 730, 5, 7, 24), 2)}


@pytest.mark.parametrize("query", sorted(_PLANS))
def test_fused_program_has_no_scatter_add(query):
    build, block_sums = _PLANS[query]
    plan = build()
    _host, table = _tpch_table(q1, 2048, 1)
    cols = tuple(Column(c.dtype, c.size, jax.ShapeDtypeStruct(
        c.data.shape, c.data.dtype)) for c in table.columns)
    fn = _make_fn(plan, int(config.get("plan.max_groups")), {},
                  plan_decisions(plan, (table,)))
    before = plan_metrics.snapshot()
    lowered = jax.jit(fn).lower(cols)
    after = plan_metrics.snapshot()
    assert after["plan_scatter_sums"] == before["plan_scatter_sums"]
    assert (after["plan_block_sums"] - before["plan_block_sums"]
            == block_sums)
    hlo = lowered.compile().as_text()
    assert " scatter(" in hlo or query == "q6"  # the parser sees scatters
    assert _add_scatters(hlo) == []


def test_add_scatter_detector_sees_segment_sum():
    """The structural check's own control: a scatter-add is found."""
    hlo = jax.jit(lambda z, s: jax.ops.segment_sum(z, s, num_segments=8)
                  ).lower(jnp.zeros(64, jnp.int64),
                          jnp.zeros(64, jnp.int32)).compile().as_text()
    assert len(_add_scatters(hlo)) == 1
