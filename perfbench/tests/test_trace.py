"""The trace reduction: on a synthetic trace whose answers are known, and
on a small trace recorded on a TPU v5e (``data/``)."""

from __future__ import annotations

import gzip
import os

import pytest

from perfbench import trace

DATA = os.path.join(os.path.dirname(__file__), "data")


def _events(evs):
    return "\n".join(
        f"events {{ metadata_id: {m} offset_ps: {s * 1000} "
        f"duration_ps: {d * 1000} }}" for m, s, d in evs)


def _plane(pid, name, line, evs, names):
    meta = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                     f'name: "{n}" }} }}' for i, n in names.items())
    return (f'planes {{ id: {pid} name: "{name}" lines {{ id: 1 '
            f'name: "{line}" timestamp_ns: 0 {_events(evs)} }} {meta} }}')


def synthetic():
    """Host: two queries over [100, 300) and [300, 500) ns, each with an
    execute span and a to_host span. Device 0: ops over 120-180 (a
    fusion), 200-220 (an all-gather), 320-400 (a fusion) and 390-420
    (overlapping). Device 1: one fusion over 150-250. Outside the
    window, 600-700 on device 0 is not counted."""
    from jax.profiler import ProfileData
    host = _plane(1, "/host:CPU", "python3", [
        (1, 100, 200), (2, 105, 120), (3, 230, 60),
        (1, 300, 200), (2, 305, 150), (3, 460, 30)],
        {1: "perfbench.query", 2: "perfbench.execute",
         3: "perfbench.to_host"})
    dev0 = _plane(2, "/device:TPU:0", "XLA Ops", [
        (1, 120, 60), (2, 200, 20), (1, 320, 80), (3, 390, 30),
        (1, 600, 100)],
        {1: "fusion.1", 2: "all-gather.3", 3: "copy.2"})
    dev1 = _plane(3, "/device:TPU:1", "XLA Ops", [(1, 150, 100)],
                  {1: "fusion.1"})
    other = _plane(4, "/device:TPU:0 SparseCore", "XLA Ops", [(1, 0, 900)],
                   {1: "ignored"})
    return ProfileData.from_text_proto("\n".join([host, dev0, dev1, other]))


def test_reduction_of_a_known_trace():
    r = trace.reduce_profile(synthetic())
    assert r["devices"] == 2 and r["queries"] == 2
    assert r["window_s"] == pytest.approx(400e-9)
    # device 0 busy 60 + 20 + 100 (320-420) = 180; device 1 busy 100
    assert r["busy_s"] == pytest.approx(140e-9)
    assert r["collective_s"] == pytest.approx(10e-9)
    assert r["collective_ops"] == 1
    ops = dict(r["device_ops"])
    assert ops["fusion.1"] == pytest.approx((60 + 80 + 100) / 2 * 1e-9)
    # device 0 idle: 100-120, 180-200, 220-320, 420-500
    gaps = r["idle_gaps"]
    assert [g[1] for g in gaps] == pytest.approx(
        [100e-9, 80e-9, 20e-9, 20e-9])
    assert gaps[0][0] == "perfbench.to_host"       # at 270 ns
    assert gaps[1][0] == "perfbench.to_host"       # at 460: execute ended
    assert gaps[2][0] == "perfbench.execute"       # at 110 ns
    assert "(no host span)" not in {g[0] for g in gaps}


def test_device_ids_pick_the_cell_s_chips():
    r = trace.reduce_profile(synthetic(), device_ids=[1])
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(100e-9)


def test_op_names_and_collectives_from_hlo_text():
    fusion = ("%fusion.72 = (u32[4096]{0:T(1024)S(1)}, u32[4096]{0}) "
              "fusion(u32[4096]{0} %all-gather.3), kind=kCustom")
    gather = "%ag.1 = s64[8]{0} all-gather-start(s64[2]{0} %x), dims={0}"
    assert trace.op_name(fusion) == "fusion.72"
    assert not trace.is_collective(fusion)     # only reads a gathered value
    assert trace.is_collective(gather)
    assert trace.is_collective("all-reduce.5")


def test_interval_helpers():
    assert trace.union([(5, 6), (1, 3), (2, 4)]) == [(1, 4), (5, 6)]
    assert trace.gaps([(1, 4), (5, 6)], 0, 8) == [(0, 1), (4, 5), (6, 8)]
    assert trace.length([(1, 4), (5, 6)]) == 4


def test_reduction_of_a_chip_trace():
    """Two q1_sf1 queries traced on one TPU v5e (gzipped xplane): busy
    within the window, operations by their short HLO names, idle gaps
    labelled by the host spans open in them."""
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(DATA, "q1_sf1_tpu_v5e.xplane.pb.gz")) as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    r = trace.reduce_profile(pd, device_ids=[0])
    assert r["devices"] == 1 and r["queries"] == 2
    assert 0.9 * r["window_s"] < r["busy_s"] <= r["window_s"]
    names = [n for n, _ in r["device_ops"]]
    assert len(names) == 10 and all(" " not in n for n in names)
    assert all(t > 0 for _, t in r["device_ops"])
    assert r["idle_gaps"] and all(g > 0 for _, g in r["idle_gaps"])
    assert all(label.startswith("perfbench.") for label, _ in r["idle_gaps"])
    assert r["collective_ops"] == 0
