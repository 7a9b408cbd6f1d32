"""Run one benchmark cell on the chips of this host; print its result.

    python3 -m perfbench.run --workload q1_sf1 --seed 7 --seconds 45 --trace 0

Set-up generates the cell's tables on the host from the seed and places
them as the configuration's ``placement`` says: ``device`` uploads them
once and keeps them resident in HBM, ``row_sharded`` the same split by
rows over the mesh, and ``parquet`` writes them to Parquet files and
uploads nothing, so that each query of the window reads and decodes the
files (``sources.py`` gives that contract). Set-up then warms the cell's
one program by running one query. The window runs the query back to
back as the traffic file says. Afterwards the peak HBM is read, the
tables are freed or the files deleted, the plain numpy reference is
computed and every answer of the window is compared with it.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, under the
``parquet`` placement ``sources`` (each file's bytes, rows, row groups,
codec and encodings per column), with ``--trace 1`` a ``breakdown``,
and last ``checks``: each number compared with its limit, which also
end standard error. Without a TPU, with fewer chips than the cell asks
for, or with a device kind missing from ``peaks.json``, it prints no
result and exits non-zero.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up is timed from the runner's first line

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import (Any, Dict, Iterator, List, Optional, Sequence,  # noqa: E402
                    Tuple)

import numpy as np  # noqa: E402

from perfbench import loop, sources, spec, trace  # noqa: E402

CACHE_DIR = os.path.join(spec.ROOT, ".srjt_cache", "xla")
TRACE_DIR = os.path.join(spec.ROOT, ".srjt_cache", "perfbench", "trace")
PROGRAM = os.path.join(spec.ROOT, "spark_rapids_jni_tpu")

PLAN_FAULTS = ("plan_fallbacks", "plan_join_fallbacks", "plan_overflows",
               "plan_oom_retries", "plan_oom_splits")
GUARD_FAULTS = ("transient_retries", "poisoned_programs", "redispatches",
                "degradations", "resource_exhausted", "oom_retries",
                "oom_splits", "stall_retries", "task_retries")


def use_checkout_cache() -> None:
    """Before JAX starts: its persistent compile cache in the checkout, at
    a fixed path and without eviction, so that only a checkout's first
    run compiles; the TPU runtime writes no log files."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


class NoDevice(RuntimeError):
    """The host lacks what the cell needs: a TPU, enough chips, peaks."""


def require_devices(chips: int) -> Tuple[list, Dict[str, Any]]:
    """The first ``chips`` TPU devices and their peaks; never the CPU."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise NoDevice(f"JAX finds no accelerator: {e}") from e
    if devs[0].platform != "tpu":
        raise NoDevice(f"JAX finds {devs[0].platform}, not a TPU")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    try:
        peaks = spec.peaks(devs[0].device_kind)
    except spec.SpecError as e:
        raise NoDevice(str(e)) from e
    return devs[:chips], peaks


class CompileCounter:
    """Backend compiles and their seconds, from JAX's monitoring event (a
    persistent-cache hit is not a backend compile). One per process."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    _installed: Optional["CompileCounter"] = None

    def __init__(self):
        self.n = 0
        self.seconds = 0.0

    def _on_event(self, event, duration_secs, **_kw):
        if event == self.EVENT:
            self.n += 1
            self.seconds += duration_secs

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._installed is None:
            import jax
            cls._installed = cls()
            jax.monitoring.register_event_duration_secs_listener(
                cls._installed._on_event)
        return cls._installed


def probe() -> Tuple[float, Dict[str, int]]:
    """The engine's dispatch seconds so far and its fallback, overflow,
    retry and degradation counters."""
    from spark_rapids_jni_tpu.faultinj import guard
    from spark_rapids_jni_tpu.plan import plan_metrics
    p, g = plan_metrics.snapshot(), guard.metrics.snapshot()
    faults = {k: p.get(k, 0) for k in PLAN_FAULTS}
    faults.update({f"guard.{k}": g.get(k, 0) for k in GUARD_FAULTS})
    return float(p.get("execute_s", 0.0)), faults


def used_devices(placement: str, devices: list) -> list:
    """The chips that hold the cell's tables and run its queries: every
    one under ``row_sharded``, else the first. A cell may ask for more
    chips than it uses, to hold a whole host and its CPU cores alone; the
    trace's busy time and the roofline count only the chips used."""
    return devices if placement == "row_sharded" else devices[:1]


def upload(host: Sequence[Sequence[np.ndarray]], with_stats: bool,
           placement: str, devices: list) -> list:
    """Host arrays -> resident device Tables, in one bulk transfer.
    ``row_sharded`` splits every column by rows over the engine's mesh of
    ``devices``, the layout its sharded executor stages to."""
    import jax
    from spark_rapids_jni_tpu.columnar import dtype as dt
    from spark_rapids_jni_tpu.columnar.column import Column, ColumnStats, Table
    if placement == "row_sharded":
        from jax.sharding import NamedSharding, PartitionSpec
        from spark_rapids_jni_tpu.parallel import cluster
        mesh = cluster.get_mesh(len(devices))
        target = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
        for t in host:
            if t[0].shape[0] % len(devices):
                raise spec.SpecError(
                    f"{t[0].shape[0]} rows do not split over "
                    f"{len(devices)} devices")
    elif placement == "device":
        target = devices[0]
    else:
        raise spec.SpecError(f"unknown placement {placement!r}")
    types = {np.dtype(np.int64): dt.INT64, np.dtype(np.int32): dt.INT32}
    flat = jax.block_until_ready(
        jax.device_put([a for t in host for a in t], target))
    it = iter(flat)
    tables = []
    for t in host:
        cols = []
        for a in t:
            c = Column(types[a.dtype], int(a.shape[0]), data=next(it))
            cols.append(c.with_stats(ColumnStats.from_numpy(a))
                        if with_stats else c)
        tables.append(Table(tuple(cols)))
    return tables


@contextlib.contextmanager
def placed(cell: spec.Cell, seed: int, host: Sequence[Sequence[np.ndarray]],
           devices: list) -> Iterator[Tuple[Any, Optional[Dict[str, Any]]]]:
    """The engine's inputs for one run and, under the ``parquet``
    placement, what each file holds: resident Tables (``upload``), or the
    paths of Parquet files that are deleted when the context closes
    (``sources.parquet_files``)."""
    cfg = cell.config
    if cfg["placement"] != "parquet":
        yield upload(host, cell.query.WITH_STATS, cfg["placement"],
                     devices), None
        return
    with sources.parquet_files(cell.name, seed, cell.query, host,
                               cfg["parquet"]) as files:
        yield files.paths, files.described


def make_serve(entry, plan, inputs, kwargs):
    """One query through the engine's entry, its answer copied to host.
    ``inputs`` are resident Tables or, under the ``parquet`` placement,
    file paths; one is handed over alone, several as a list."""
    import jax
    arg = inputs[0] if len(inputs) == 1 else inputs

    def serve():
        with jax.profiler.TraceAnnotation("perfbench.execute"):
            out = entry(plan, arg, **kwargs)
        with jax.profiler.TraceAnnotation("perfbench.to_host"):
            return ([np.asarray(c.data) for c in out.columns],
                    [None if c.validity is None else np.asarray(c.validity)
                     for c in out.columns])
    return serve


class Tracer:
    """The profiler over the traced stretch, into a fixed directory."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir

    def start(self) -> None:
        import jax
        shutil.rmtree(self.log_dir, ignore_errors=True)
        os.makedirs(self.log_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)

    def stop(self) -> None:
        import jax
        jax.profiler.stop_trace()


def check_answers(query, done: List[loop.Done], want
                  ) -> Dict[str, Dict[str, float]]:
    """Each number compared over every answer of the window (the worst
    one), beside its limit. The harness counts unanswered queries,
    answers it cannot read and null values; the query's own numbers come
    from its ``compare``."""
    worst: Dict[str, float] = {}
    nulls = unreadable = 0
    for d in done:
        if d.answer is None:
            continue
        data, validity = d.answer
        nulls += sum(int(v.size - v.sum()) for v in validity if v is not None)
        try:
            nums = query.compare(query.answer(data), want)
        except (IndexError, ValueError, TypeError) as e:
            print(f"perfbench: unreadable answer: {type(e).__name__}: {e}",
                  file=sys.stderr)
            unreadable += 1
            continue
        for k, v in nums.items():
            if k not in worst or not v <= worst[k]:  # a NaN stays
                worst[k] = v
    checks = {k: {"value": worst.get(k, float("nan")), "limit": lim}
              for k, lim in query.LIMITS.items()}
    checks["unanswered"] = {"value": sum(d.answer is None for d in done),
                            "limit": 0}
    checks["unreadable"] = {"value": unreadable, "limit": 0}
    checks["null_values"] = {"value": nulls, "limit": 0}
    return checks


def peak_bytes(devices: list) -> Optional[int]:
    """``peak_bytes_in_use`` on the fullest device, where reported."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    return max(peaks) if all(p is not None for p in peaks) else None


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool,
             devices: list, peaks: Dict[str, Any],
             t0: float = _T0) -> Dict[str, Any]:
    """Set-up, window, reference and metrics of one run."""
    import jax
    import spark_rapids_jni_tpu  # noqa: F401  (x64, compile cache settings)
    from spark_rapids_jni_tpu import plan as engine

    compiles = CompileCounter.get()
    c0 = (compiles.n, compiles.seconds)
    q, cfg, lit = cell.query, cell.config, cell.traffic["literals"]
    used = used_devices(cfg["placement"], devices)
    host = q.generate(cfg["rows"], seed)
    with placed(cell, seed, host, devices) as (inputs, files):
        serve = make_serve(getattr(engine, cfg["entry"]), q.plan(**lit),
                           inputs, cfg.get("entry_kwargs", {}))
        warm = loop.serve_once(serve, probe, -1)
        if warm.error is not None:
            raise RuntimeError(f"warm-up query failed: {warm.error}")
        c1 = (compiles.n, compiles.seconds)
        setup_s = time.perf_counter() - t0

        tracer = Tracer(TRACE_DIR) if traced else None
        done, w0 = loop.run_window(cell.traffic, serve, probe, seconds,
                                   tracer)
        c2 = (compiles.n, compiles.seconds)
        peak = peak_bytes(devices)
    del inputs, serve, warm
    gc.collect()

    tr = None
    if traced:
        path = trace.find_xplane(TRACE_DIR)
        if path is None:
            raise RuntimeError(f"the profiler wrote no trace under {TRACE_DIR}")
        tr = trace.reduce_file(path, device_ids=[d.id for d in used])
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    checks = check_answers(q, done, q.reference(host, **lit))

    measured = [d for d in done if not d.traced] or done
    record = {
        "chips": len(used), "peaks": peaks, "setup_s": setup_s,
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
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = m.read(record)
        if v is not None:
            metrics[m.name] = {"value": v, "unit": m.unit}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result: Dict[str, Any] = {
        "correct": all(c["value"] <= c["limit"] for c in checks.values()),
        "attempted": len(done),
        "failed": sum(d.failed for d in done),
        "metrics": metrics,
        "device": device,
    }
    if files is not None:
        result["sources"] = files
    if tr is not None:
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    moved = sorted({k for d in done for k in d.moved})
    if moved:
        print(f"perfbench: counters moved in the window: {moved}",
              file=sys.stderr)
    result["checks"] = checks
    return result


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    if not os.path.isdir(PROGRAM):
        print(f"perfbench: no engine at {PROGRAM}", file=sys.stderr)
        return 2
    use_checkout_cache()
    try:
        devices, peaks = require_devices(cell.chips)
    except NoDevice as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, peaks)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
