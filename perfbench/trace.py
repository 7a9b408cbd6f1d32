"""Reduce a profiler trace (``.xplane.pb``) to device busy and idle time.

Read with ``jax.profiler.ProfileData``. A device is a plane named
``/device:TPU:<n>``; its operations are the events of its ``XLA Ops``
line. The window is the host-clock extent of the benchmark's
``perfbench.query`` spans. Within it, per device:

- busy: the union of the operations' intervals;
- collective: the union of the intervals of collective operations
  (all-gather, all-reduce, reduce-scatter, all-to-all, permutes), known
  by the HLO instruction's name;
- operation totals by name;
- idle gaps: the complement of busy, each labelled with the host spans
  open at its middle, outermost benchmark span first.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
QUERY_SPAN = "perfbench.query"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|all_gather|all_reduce|reduce_scatter|all_to_all|collective_permute")

OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")

Interval = Tuple[float, float]


def find_xplane(log_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a profiler log directory."""
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals: Sequence[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The complement of merged ``busy`` intervals within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def op_name(event_name: str) -> str:
    """``fusion.72`` from an XLA Ops event named by its HLO text
    (``%fusion.72 = (u32[4096]...) fusion(...)``)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def is_collective(event_name: str) -> bool:
    """By the instruction's name or its opcode (the first ``word(`` after
    the shape in the HLO text)."""
    m = OPCODE.search(event_name)
    return bool(COLLECTIVE.search(op_name(event_name))
                or (m and COLLECTIVE.search(m.group(1))))


def _host_spans(pd) -> List[Tuple[float, float, str]]:
    """Events of the host thread that ran the benchmark's queries."""
    for plane in pd.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            spans = [(ev.start_ns, ev.end_ns, ev.name) for ev in line.events]
            if any(s[2] == QUERY_SPAN for s in spans):
                return spans
    return []


def _label(spans: Sequence[Tuple[float, float, str]], t: float) -> str:
    """The outermost benchmark span and the innermost span open at t."""
    open_ = [s for s in spans if s[0] <= t < s[1]]
    if not open_:
        return "(no host span)"
    inner = min(open_, key=lambda s: s[1] - s[0])[2]
    bench = [s for s in open_ if s[2].startswith("perfbench.")
             and s[2] != QUERY_SPAN]
    outer = max(bench, key=lambda s: s[1] - s[0])[2] if bench else None
    return inner if outer in (None, inner) else f"{outer} > {inner}"


def reduce_profile(pd, top: int = 10,
                   device_ids: Optional[Sequence[int]] = None
                   ) -> Dict[str, object]:
    """Busy, collective and window seconds (busy and collective averaged
    over the devices, all of them or those of ``device_ids``), the traced
    queries, the top operations by device time and the longest idle gaps
    of the first device."""
    spans = _host_spans(pd)
    queries = [s for s in spans if s[2] == QUERY_SPAN]
    if not queries:
        raise ValueError(f"no {QUERY_SPAN} span in the trace")
    lo = min(s[0] for s in queries)
    hi = max(s[1] for s in queries)
    devices = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m is None or (device_ids is not None
                         and int(m.group(1)) not in device_ids):
            continue
        ops = [(ev.start_ns, ev.end_ns, ev.name)
               for line in plane.lines if line.name == OPS_LINE
               for ev in line.events]
        devices.append((int(m.group(1)), ops))
    devices.sort()
    busy_ns, coll_ns, totals, coll_n = [], [], {}, 0
    first_busy: List[Interval] = []
    for k, (_, ops) in enumerate(devices):
        inside = [(max(s, lo), min(e, hi), n) for s, e, n in ops
                  if e > lo and s < hi]
        busy = union([(s, e) for s, e, _ in inside])
        busy_ns.append(length(busy))
        coll = [(s, e) for s, e, n in inside if is_collective(n)]
        coll_n += len(coll)
        coll_ns.append(length(union(coll)))
        for s, e, n in inside:
            n = op_name(n)
            totals[n] = totals.get(n, 0.0) + (e - s)
        if k == 0:
            first_busy = busy
    nd = max(len(devices), 1)
    idle = sorted(gaps(first_busy, lo, hi), key=lambda g: g[0] - g[1])
    return {
        "devices": len(devices),
        "queries": len(queries),
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / nd / 1e9,
        "collective_s": sum(coll_ns) / nd / 1e9,
        "collective_ops": coll_n,
        "device_ops": [[n, t / nd / 1e9] for n, t in sorted(
            totals.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label(spans, (s + e) / 2), (e - s) / 1e9]
                      for s, e in idle[:top]],
    }


def reduce_file(path: str, top: int = 10,
                device_ids: Optional[Sequence[int]] = None
                ) -> Dict[str, object]:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), top, device_ids)
