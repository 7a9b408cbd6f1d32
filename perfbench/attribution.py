"""Attribute a profiler trace's device time to the engine's plan nodes.

Additive to ``trace.reduce_profile``: with no scope map it returns that
reduction unchanged. With the engine's scope map
(``ProgramCache.op_scopes()``, {program: {instruction: node scope}}) and
fusion map (``ProgramCache.fusion_scopes()``), per device and within the
window:

- each operation belongs to the ``XLA Modules`` interval that holds its
  start. An event is named ``<module>(<number>)``; the number identifies
  the program to the profiler but is not the executable's fingerprint
  that keys the map, so a module is matched to a cached plan program
  (``jit_plan_fused``, ``jit_plan_dag``, ``jit_plan_sharded``,
  ``jit_plan_vmap``) by its name where exactly one cached program has
  that name. Where two have it, nothing in the module is attributed
  (``unscoped``): one name cannot tell them apart;
- an operation of a matched plan program counts toward the node scope of
  its instruction (a fusion toward the node of its root), or ``unscoped``;
  every other operation is ``outside program``: the trim and compaction
  programs the engine runs after the head sync;
- ``plan_nodes``: device seconds per scope, averaged over the devices.
  Where operations overlap (an asynchronous copy or collective beside
  compute, a loop around its body), the overlap counts once, toward the
  one that started first, so the entries add up to ``busy_s``;
  ``plan_program_s`` is their part in the plan programs;
- ``device_ops`` entries are named ``<op> @ <scope>``, each with its
  whole duration, as ``trace.reduce_profile`` counts them;
- idle gaps name the innermost ``plan.*`` span open at their middle:
  ``perfbench.execute > plan.trim > PjitFunction(dynamic_slice)``;
- ``fusion_scopes``: the node scopes each of the five longest fusions
  draws on;
- ``readings``: the device metrics that read this attribution.
"""

from __future__ import annotations

import bisect
import re
from typing import Dict, List, Optional, Sequence, Tuple

from perfbench import trace

MODULES_LINE = "XLA Modules"
UNSCOPED = "unscoped"
OUTSIDE = "outside program"
EVENT = re.compile(r"^(.*)\((.*)\)$")

Scopes = Dict[str, Dict[str, str]]
Fusions = Dict[str, Dict[str, Tuple[str, ...]]]


def _base(name: str) -> str:
    m = EVENT.match(name)
    return m.group(1) if m else name


def match_programs(module_names: Sequence[str], scopes: Scopes
                   ) -> Dict[str, Tuple[Optional[str], str]]:
    """{module event name: (scope map key or None, how)} for the modules
    named like a cached program; ``how`` is ``module name`` or
    ``ambiguous``."""
    by_base: Dict[str, List[str]] = {}
    for key in scopes:
        by_base.setdefault(_base(key), []).append(key)
    out = {}
    for name in module_names:
        keys = by_base.get(_base(name))
        if keys is not None:
            out[name] = ((keys[0], "module name") if len(keys) == 1
                         else (None, "ambiguous"))
    return out


def _plan_label(spans, t: float, base: str) -> str:
    """``trace._label``'s label with the innermost ``plan.*`` span open at
    t put after the outermost benchmark span: ``perfbench.execute >
    plan.trim > PjitFunction(dynamic_slice)``."""
    open_ = [s for s in spans if s[0] <= t < s[1]]
    plan = [s for s in open_ if s[2].startswith("plan.")]
    if not plan:
        return base
    bench = [s for s in open_ if s[2].startswith("perfbench.")
             and s[2] != trace.QUERY_SPAN]
    parts = [max(bench, key=lambda s: s[1] - s[0])[2] if bench else None,
             min(plan, key=lambda s: s[1] - s[0])[2],
             min(open_, key=lambda s: s[1] - s[0])[2]]
    out: List[str] = []
    for p in parts:
        if p is not None and p not in out:
            out.append(p)
    return " > ".join(out)


def _kind(scope: str) -> Optional[str]:
    parts = scope.split(".")
    return parts[2] if len(parts) > 2 and parts[0] == "plan" else None


def reduce_profile(pd, scopes: Optional[Scopes] = None,
                   fusions: Optional[Fusions] = None, top: int = 10,
                   device_ids: Optional[Sequence[int]] = None
                   ) -> Dict[str, object]:
    """``trace.reduce_profile``, plus the attribution where a scope map
    is given."""
    base = trace.reduce_profile(pd, top, device_ids)
    if not scopes:
        return base
    spans = trace._host_spans(pd)
    queries = [s for s in spans if s[2] == trace.QUERY_SPAN]
    lo = min(s[0] for s in queries)
    hi = max(s[1] for s in queries)
    devices = []
    for plane in pd.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m is None or (device_ids is not None
                         and int(m.group(1)) not in device_ids):
            continue
        ops, mods = [], []
        for line in plane.lines:
            if line.name == trace.OPS_LINE:
                ops = [(ev.start_ns, ev.end_ns, ev.name)
                       for ev in line.events]
            elif line.name == MODULES_LINE:
                mods = sorted((ev.start_ns, ev.end_ns, ev.name)
                              for ev in line.events)
        devices.append((int(m.group(1)), ops, mods))
    devices.sort()
    nd = max(len(devices), 1)
    matched = match_programs(sorted({n for _, _, ms in devices
                                     for _, _, n in ms}), scopes)
    nodes: Dict[str, float] = {}
    named: Dict[str, float] = {}
    fused: Dict[Tuple[str, str], float] = {}  # (program, fusion) -> ns
    unscoped_ops: Dict[str, float] = {}
    plan_ns = 0.0
    for _, ops, mods in devices:
        starts = [s for s, _, _ in mods]
        frontier = lo  # busy time before it is already counted
        for s, e, n in sorted(ops):
            if not (e > lo and s < hi):
                continue
            dur = min(e, hi) - max(s, lo)
            own = max(0, min(e, hi) - max(s, lo, frontier))
            frontier = max(frontier, min(e, hi))
            op = trace.op_name(n)
            i = bisect.bisect_right(starts, s) - 1
            module = mods[i][2] if i >= 0 and s < mods[i][1] else None
            if module not in matched:
                scope = OUTSIDE
            else:
                plan_ns += own
                key = matched[module][0]
                scope = (scopes[key].get(op, UNSCOPED) if key is not None
                         else UNSCOPED)
                if scope == UNSCOPED:
                    unscoped_ops[op] = unscoped_ops.get(op, 0.0) + dur
                elif key is not None and op in (fusions or {}).get(key, {}):
                    fused[(key, op)] = fused.get((key, op), 0.0) + dur
            nodes[scope] = nodes.get(scope, 0.0) + own
            label = f"{op} @ {scope}"
            named[label] = named.get(label, 0.0) + dur
    out = dict(base)

    def per_device(d: Dict) -> List[list]:
        return [[k, v / nd / 1e9] for k, v in sorted(
            d.items(), key=lambda kv: -kv[1])]
    out["device_ops"] = per_device(named)[:top]
    idle = sorted(trace.gaps(_first_busy(devices[0][1], lo, hi), lo, hi),
                  key=lambda g: g[0] - g[1]) if devices else []
    out["idle_gaps"] = [
        [_plan_label(spans, (s + e) / 2,
                     trace._label(spans, (s + e) / 2)), (e - s) / 1e9]
        for s, e in idle[:top]]
    out["plan_nodes"] = per_device(nodes)
    out["unscoped_ops"] = per_device(unscoped_ops)[:5]
    out["plan_program_s"] = plan_ns / nd / 1e9
    out["programs"] = {k: list(v) for k, v in matched.items()}
    out["fusion_scopes"] = [
        [f"{op} @ {scopes[key].get(op, UNSCOPED)}",
         list(fusions[key][op])]
        for (key, op), _ in sorted(fused.items(), key=lambda kv: -kv[1])[:5]]
    out["readings"] = readings(out)
    return out


def _first_busy(ops, lo: float, hi: float):
    return trace.union([(max(s, lo), min(e, hi)) for s, e, _ in ops
                        if e > lo and s < hi])


def readings(red: Dict[str, object]) -> Dict[str, Optional[float]]:
    """Device milliseconds per traced query, averaged over the devices:
    outside the plan programs (``trim_device_ms``), in GroupBy and in
    Join node scopes; nothing where the attribution has none."""
    q = red["queries"]
    nodes = dict(red.get("plan_nodes") or [])

    def ms(seconds: float) -> Optional[float]:
        return 1000.0 * seconds / q if seconds else None
    by_kind: Dict[str, float] = {}
    for scope, s in nodes.items():
        k = _kind(scope)
        if k is not None:
            by_kind[k] = by_kind.get(k, 0.0) + s
    return {"trim_device_ms": ms(nodes.get(OUTSIDE, 0.0)),
            "groupby_device_ms": ms(by_kind.get("GroupBy", 0.0)),
            "join_device_ms": ms(by_kind.get("Join", 0.0))}
