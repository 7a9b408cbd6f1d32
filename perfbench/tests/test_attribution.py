"""The attribution of device time to plan nodes and of idle gaps to the
engine's phases: on a synthetic trace whose answers are known, on the q1
chip trace recorded before the engine had spans or scopes (reduced with
no scope map), and on a q5_sf1 trace of one query recorded on a TPU v5e
by ``perfbench.phases --keep`` with the engine's spans, beside the
program cache's scope maps (``data/``)."""

from __future__ import annotations

import gzip
import json
import os

import pytest

from perfbench import attribution, trace
from perfbench.tests.test_trace import DATA, _plane

PROGRAM = "jit_plan_fused(77)"
SCOPES = {PROGRAM: {"fusion.1": "plan.3.GroupBy.generic",
                    "copy.2": "plan.1.Filter"}}
FUSIONS = {PROGRAM: {"fusion.1": ("plan.2.Project",
                                  "plan.3.GroupBy.generic")}}


def _trace():
    """``test_trace.synthetic()``'s host and devices, with ``XLA Modules``
    lines and the engine's spans. Device 0 runs the plan program over
    110-230 and 310-430 and a trim program over 590-710 (past the
    window); device 1 the plan program over 140-260. In the first query
    the host holds plan.dispatch over 106-119 and plan.trim over 185-225,
    with a trim program's dispatch over 188-195."""
    from jax.profiler import ProfileData
    host = _plane(1, "/host:CPU", "python3", [
        (1, 100, 200), (2, 105, 120), (4, 106, 13), (5, 185, 40),
        (6, 188, 7), (3, 230, 60),
        (1, 300, 200), (2, 305, 150), (3, 460, 30)],
        {1: "perfbench.query", 2: "perfbench.execute",
         3: "perfbench.to_host", 4: "plan.dispatch", 5: "plan.trim",
         6: "PjitFunction(dynamic_slice)"})
    ops0 = ('lines { id: 1 name: "XLA Ops" timestamp_ns: 0 '
            + " ".join(f"events {{ metadata_id: {m} offset_ps: {s * 1000} "
                       f"duration_ps: {d * 1000} }}" for m, s, d in [
                           (1, 120, 60), (2, 200, 20), (1, 320, 80),
                           (3, 390, 30), (4, 600, 100)]) + " } "
            + 'lines { id: 2 name: "XLA Modules" timestamp_ns: 0 '
            + " ".join(f"events {{ metadata_id: {m} offset_ps: {s * 1000} "
                       f"duration_ps: {d * 1000} }}" for m, s, d in [
                           (5, 110, 120), (5, 310, 120), (6, 590, 120)])
            + " }")
    meta0 = "\n".join(f'event_metadata {{ key: {i} value {{ id: {i} '
                      f'name: "{n}" }} }}' for i, n in {
                          1: "fusion.1", 2: "all-gather.3", 3: "copy.2",
                          4: "fusion.1", 5: PROGRAM,
                          6: "jit_dynamic_slice(5)"}.items())
    dev0 = f'planes {{ id: 2 name: "/device:TPU:0" {ops0} {meta0} }}'
    ops1 = ('lines { id: 1 name: "XLA Ops" timestamp_ns: 0 '
            'events { metadata_id: 1 offset_ps: 150000 '
            'duration_ps: 100000 } } '
            'lines { id: 2 name: "XLA Modules" timestamp_ns: 0 '
            'events { metadata_id: 2 offset_ps: 140000 '
            'duration_ps: 120000 } }')
    meta1 = ('event_metadata { key: 1 value { id: 1 name: "fusion.1" } } '
             f'event_metadata {{ key: 2 value {{ id: 2 name: "{PROGRAM}" }}'
             ' }')
    dev1 = f'planes {{ id: 3 name: "/device:TPU:1" {ops1} {meta1} }}'
    return ProfileData.from_text_proto("\n".join([host, dev0, dev1]))


def test_no_scope_map_is_the_plain_reduction():
    pd = _trace()
    assert attribution.reduce_profile(pd) == trace.reduce_profile(pd)


def test_attribution_of_a_known_trace():
    pd = _trace()
    r = attribution.reduce_profile(pd, SCOPES, FUSIONS)
    base = trace.reduce_profile(pd)
    for k in ("devices", "queries", "window_s", "busy_s", "collective_s",
              "collective_ops"):
        assert r[k] == base[k]
    nodes = dict(r["plan_nodes"])
    # device 0: fusion.1 60 + 80 and device 1: 100 -> GroupBy; copy.2
    # 390-420 -> Filter, less the 10 it overlaps fusion.1; all-gather.3
    # 20 has no scope; the trim program is outside the window (600-700
    # is past 500)
    assert nodes["plan.3.GroupBy.generic"] == pytest.approx(240 / 2 * 1e-9)
    assert nodes["plan.1.Filter"] == pytest.approx(20 / 2 * 1e-9)
    assert nodes[attribution.UNSCOPED] == pytest.approx(20 / 2 * 1e-9)
    assert attribution.OUTSIDE not in nodes
    assert sum(nodes.values()) == pytest.approx(r["busy_s"])
    assert r["plan_program_s"] == pytest.approx(280 / 2 * 1e-9)
    ops = dict(r["device_ops"])
    assert ops["fusion.1 @ plan.3.GroupBy.generic"] == pytest.approx(
        120e-9)
    assert ops["all-gather.3 @ unscoped"] == pytest.approx(10e-9)
    assert r["unscoped_ops"] == [["all-gather.3", pytest.approx(10e-9)]]
    assert r["programs"] == {PROGRAM: [PROGRAM, "module name"]}
    assert r["fusion_scopes"] == [["fusion.1 @ plan.3.GroupBy.generic",
                                   ["plan.2.Project",
                                    "plan.3.GroupBy.generic"]]]
    rd = r["readings"]
    assert rd["groupby_device_ms"] == pytest.approx(1000 * 120e-9 / 2)
    assert rd["join_device_ms"] is None and rd["trim_device_ms"] is None


def test_idle_gaps_name_the_engine_phase():
    r = attribution.reduce_profile(_trace(), SCOPES, FUSIONS)
    base = trace.reduce_profile(_trace())
    assert [g for _, g in r["idle_gaps"]] == [g for _, g in
                                              base["idle_gaps"]]
    labels = [lab for lab, _ in r["idle_gaps"]]
    # device 0 idle: 220-320 (middle 270, to_host), 420-500 (460),
    # 100-120 (110, plan.dispatch in execute), 180-200 (190, a trim
    # program's dispatch in plan.trim)
    assert labels == ["perfbench.to_host", "perfbench.to_host",
                      "perfbench.execute > plan.dispatch",
                      "perfbench.execute > plan.trim > "
                      "PjitFunction(dynamic_slice)"]


def test_program_matched_by_its_module_name_alone():
    """The number in the event's name plays no part; two cached programs
    with one module name leave it unattributed."""
    other = {"jit_plan_fused(other)": SCOPES[PROGRAM]}
    r = attribution.reduce_profile(_trace(), other)
    assert r["programs"] == {PROGRAM: ["jit_plan_fused(other)",
                                       "module name"]}
    assert dict(r["plan_nodes"])["plan.1.Filter"] > 0
    two = {**other, "jit_plan_fused(third)": {}}
    r = attribution.reduce_profile(_trace(), two)
    assert r["programs"] == {PROGRAM: [None, "ambiguous"]}
    assert set(dict(r["plan_nodes"])) == {attribution.UNSCOPED}


def test_the_spanless_q1_chip_trace_reduces_as_before():
    """The q1 trace recorded with no engine spans and no scope map: the
    reduction's keys, names and values are exactly trace.py's."""
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(DATA, "q1_sf1_tpu_v5e.xplane.pb.gz")) as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    got = attribution.reduce_profile(pd, None, device_ids=[0])
    assert got == trace.reduce_profile(pd, device_ids=[0])
    assert set(got) == {"devices", "queries", "window_s", "busy_s",
                        "collective_s", "collective_ops", "device_ops",
                        "idle_gaps"}


Q5 = os.path.join(DATA, "q5_sf1_tpu_v5e_spans")


@pytest.fixture(scope="module")
def q5():
    """The trace reduced with its scope maps, and without them."""
    from jax.profiler import ProfileData
    with gzip.open(Q5 + ".xplane.pb.gz") as f:
        pd = ProfileData.from_serialized_xspace(f.read())
    with open(Q5 + ".scopes.json") as f:
        maps = json.load(f)
    return (attribution.reduce_profile(pd, maps["scopes"], maps["fusions"],
                                       device_ids=[0]),
            trace.reduce_profile(pd, device_ids=[0]))


def test_q5_chip_trace_attributes_device_time_to_plan_nodes(q5):
    r, base = q5
    for k, v in base.items():
        if k not in ("device_ops", "idle_gaps"):
            assert r[k] == v, k
    ((module, (key, how)),) = r["programs"].items()
    assert module.startswith("jit_plan_dag(") and how == "module name"
    assert key.startswith("jit_plan_dag(")
    nodes = dict(r["plan_nodes"])
    assert sum(nodes.values()) == pytest.approx(r["busy_s"], rel=1e-9)
    assert nodes[attribution.UNSCOPED] < 0.1 * r["plan_program_s"]
    join = sum(v for k, v in nodes.items() if ".Join." in k)
    assert join > 0.9 * r["busy_s"]            # the four direct probes
    for name, seconds in r["device_ops"]:
        op, scope = name.split(" @ ")
        assert scope in nodes
        assert seconds <= dict(base["device_ops"]).get(op, seconds)
    for name, kinds in r["fusion_scopes"]:
        assert name.split(" @ ")[1] in kinds


def test_q5_chip_trace_idle_gaps_name_plan_phases(q5):
    r, base = q5
    assert [g for _, g in r["idle_gaps"]] == [g for _, g in
                                              base["idle_gaps"]]
    labels = [lab for lab, _ in r["idle_gaps"]]
    # wherever the engine held the host, the gap names its phase
    assert all(" > plan." in lab for lab in labels
               if lab.startswith("perfbench.execute"))
    phases = {p for lab in labels for p in lab.split(" > ")
              if p.startswith("plan.")}
    assert {"plan.prepare", "plan.wait", "plan.trim"} <= phases
    assert "perfbench.execute > plan.trim > PjitFunction(dynamic_slice)" \
        in labels


def test_q5_chip_trace_device_readings(q5):
    r, _ = q5
    nodes = dict(r["plan_nodes"])
    q = r["queries"]

    def ms(kind):
        return 1000.0 * sum(v for k, v in nodes.items()
                            if k.split(".")[2:3] == [kind]) / q
    rd = r["readings"]
    assert rd["trim_device_ms"] == pytest.approx(
        1000.0 * nodes[attribution.OUTSIDE] / q)
    assert rd["groupby_device_ms"] == pytest.approx(ms("GroupBy"))
    assert rd["join_device_ms"] == pytest.approx(ms("Join"))
    assert rd["trim_device_ms"] < 0.01 * rd["join_device_ms"]
