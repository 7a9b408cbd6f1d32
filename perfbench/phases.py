"""The engine's own readings of a cell: phases and node attribution.

    python3 -m perfbench.phases --workload q5_sf1 --seed 11 --seconds 45
    python3 -m perfbench.phases --workload q5_sf1 --seed 11 --seconds 20 \\
        --traced-queries 1 --keep q5_trace

It runs the cell as ``perfbench.run --trace 1`` does, with the engine's
spans on (``trace.enabled``) for the whole run, and prints one JSON
line: the per-layer metrics the runner would print (the phase counters'
``plan_prepare_ms``, ``plan_dispatch_ms``, ``plan_stage_ms`` and
``plan_trim_ms`` among them), and the traced stretch reduced with the
program cache's scope map (``attribution.reduce_profile``): device time
per plan node, operations named ``<op> @ <scope>``, idle gaps named by
the engine's phase, the device readings that need the map, and how each
plan program in the trace was matched to its map. ``--traced-queries``
shortens the traced stretch; ``--keep`` also writes the gzipped trace
and the scope maps there. Like the runner, it needs the cell's TPU chips
and exits non-zero without them.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, Optional, Sequence  # noqa: E402

from perfbench import attribution, loop, run, spec, trace  # noqa: E402

TRACE_DIR = os.path.join(spec.ROOT, ".srjt_cache", "perfbench", "phases")


def _set_tracing(on: bool) -> None:
    from spark_rapids_jni_tpu.utils import config
    config.set("trace.enabled", on)


def traced_run(cell: spec.Cell, seed: int, seconds: float, devices: list,
               peaks: Dict[str, Any], traced_queries: Optional[int],
               keep: Optional[str]) -> Dict[str, Any]:
    import spark_rapids_jni_tpu  # noqa: F401  (x64, compile cache settings)
    from spark_rapids_jni_tpu import plan as engine
    from spark_rapids_jni_tpu.plan.executor import default_cache

    _set_tracing(True)
    compiles = run.CompileCounter.get()
    c0 = (compiles.n, compiles.seconds)
    q, cfg, lit = cell.query, cell.config, cell.traffic["literals"]
    host = q.generate(cfg["rows"], seed)
    with run.placed(cell, seed, host, devices) as (inputs, _files):
        serve = run.make_serve(getattr(engine, cfg["entry"]), q.plan(**lit),
                               inputs, cfg.get("entry_kwargs", {}))
        warm = loop.serve_once(serve, run.probe, -1)
        if warm.error is not None:
            raise RuntimeError(f"warm-up query failed: {warm.error}")
        c1 = (compiles.n, compiles.seconds)
        setup_s = time.perf_counter() - _T0
        traffic = dict(cell.traffic)
        if traced_queries is not None:
            traffic["trace"] = {**traffic["trace"],
                                "queries": traced_queries}
        done, w0 = loop.run_window(traffic, serve, run.probe, seconds,
                                   run.Tracer(TRACE_DIR))
        c2 = (compiles.n, compiles.seconds)
        peak = run.peak_bytes(devices)
    del inputs, serve, warm
    gc.collect()

    cache = default_cache()
    scopes, fusions = cache.op_scopes(), cache.fusion_scopes()
    path = trace.find_xplane(TRACE_DIR)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ids = [d.id for d in run.used_devices(cfg["placement"], devices)]
    tr = trace.reduce_profile(pd, device_ids=ids)
    att = attribution.reduce_profile(pd, scopes, fusions, device_ids=ids)
    if keep:
        os.makedirs(keep, exist_ok=True)
        with open(path, "rb") as f, gzip.open(
                os.path.join(keep, f"{cell.name}.xplane.pb.gz"), "wb") as g:
            shutil.copyfileobj(f, g)
        with open(os.path.join(keep, f"{cell.name}.scopes.json"), "w") as f:
            json.dump({"scopes": scopes, "fusions": fusions}, f)
    modules = sorted({ev.name for plane in pd.planes for line in plane.lines
                      if line.name == attribution.MODULES_LINE
                      for ev in line.events})
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    checks = run.check_answers(q, done, q.reference(host, **lit))

    measured = [d for d in done if not d.traced] or done
    record = {
        "chips": len(ids), "peaks": peaks, "setup_s": setup_s,
        "elapsed_s": done[-1].end - w0,
        "latencies_s": [d.seconds for d in done if d.answer is not None],
        "completed": sum(d.answer is not None for d in done),
        "input_rows": q.input_rows(cfg["rows"]),
        "bytes_read": q.bytes_read(cfg["rows"]),
        "peak_bytes": peak,
        "compile": {"setup_s": c1[1] - c0[1], "window_n": c2[0] - c1[0]},
        "measured": {"queries": len(measured),
                     "seconds": sum(d.seconds for d in measured),
                     "execute_s": sum(d.execute_s for d in measured)},
        "trace": tr,
    }
    metrics = {}
    for m in cell.per_layer:
        v = m.read(record)
        if v is not None:
            metrics[m.name] = v
    return {
        "workload": cell.name, "seed": seed,
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(done), "failed": sum(d.failed for d in done),
        "metrics": metrics,
        "attribution": {k: att.get(k) for k in (
            "readings", "plan_nodes", "plan_program_s", "busy_s",
            "queries", "device_ops", "fusion_scopes", "unscoped_ops",
            "idle_gaps", "programs")},
        "trace_modules": [m for m in modules
                          if m.startswith("jit_plan_")],
        "scope_programs": sorted(scopes),
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind, "count": len(devices),
                   "memory_peak_bytes": peak},
        "checks": checks,
    }


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--traced-queries", type=int)
    ap.add_argument("--keep")
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    run.use_checkout_cache()
    try:
        devices, peaks = run.require_devices(cell.chips)
    except run.NoDevice as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(traced_run(cell, args.seed, args.seconds, devices,
                                peaks, args.traced_queries, args.keep)),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
