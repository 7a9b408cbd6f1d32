"""Trace annotations (reference: NVTX ranges, SURVEY.md §5.1).

The reference wraps JNI entry points in ``CUDF_FUNC_RANGE()`` NVTX ranges,
toggled by ``ai.rapids.cudf.nvtx.enabled``. TPU equivalent: XLA's profiler
(xprof) consumes ``jax.profiler.TraceAnnotation`` spans; this module provides
the same always-cheap-when-off discipline behind the
``SPARK_RAPIDS_TPU_TRACE`` env var (config key ``trace.enabled``).

Usage::

    @func_range()               # span named after the function
    def convert_to_rows(...): ...

    with trace_range("shuffle-pack"):
        ...

    with timed_range("plan.trim", plan_metrics, "trim_s"):
        ...                     # counter always, span when tracing is on

Spans land in the profiler's host plane, on the clock of the device
planes; a capture is started by whoever holds the profiler
(``jax.profiler.start_trace``).

The plan path (plan/executor.py, plan/sharded_executor.py,
plan/compile.py) opens these spans per query; the counter beside each is
a ``plan_metrics`` time holding the span's self time (its duration minus
the plan spans nested in it), kept whether tracing is on or off:

==================  ===========================================  ==========
span                what it covers                               counter
==================  ===========================================  ==========
``plan.prepare``    entry to a compiled program in hand: literal  prepare_s
                    resolution, input gates, planner passes,
                    dictionary remap arrays, the cache lookup
``plan.compile``    lowering and compile, on a cache miss         compile_s
                    (nested in ``plan.prepare``)
``plan.dispatch``   the guarded dispatch: fault injector,         dispatch_s
                    watchdog, reservation, launch
``plan.stage``      sharded only, in ``plan.dispatch``: layout    stage_s
                    and commit of the input leaves to the mesh
``plan.wait``       the host blocked on the device: the           (none)
                    reservation's release barrier and the head
                    sync
``plan.trim``       after the head sync: the live-row trim or     trim_s
                    compaction, the sharded output rebuild
``plan.eager``      the eager interpreter fallback                (none)
==================  ===========================================  ==========

``execute_s`` keeps its own boundaries: dispatch plus wait. Inside the
fused program, each plan node's operations carry a
``plan.<i>.<Kind>[.<strategy>]`` name scope (``plan.head`` for the head);
``ProgramCache.op_scopes()`` maps the compiled instructions to them.

Two ``plan_metrics`` counts say how a groupby's segment sums were built,
one per sum taken (a value column's sum, a count of valid rows), where
the sum is traced (once per compile in a fused program, once per call
in the eager op):

=====================  ================================================
counter                segment sums built
=====================  ================================================
``plan_block_sums``    without a scatter: from block totals over sorted
                       segments, or as one reduction into a single slot
``plan_scatter_sums``  as a ``jax.ops.segment_sum`` scatter-add:
                       unsorted keys, float sums, decimal limbs
=====================  ================================================
"""

from __future__ import annotations

import contextlib
import functools
import threading
import time


_cached = (-1, False)  # (config epoch, resolved flag)
_frames = threading.local()  # per thread: child seconds of each open range


def tracing_enabled() -> bool:
    """Cheap-when-off: one unlocked epoch read per call; the flag is
    re-resolved (lock + env) only after a config mutation. Env-var changes
    made after the first call are seen at the next config mutation — use
    config.set("trace.enabled", ...) to toggle at runtime."""
    global _cached
    from . import config
    e = config.epoch()
    if _cached[0] != e:
        _cached = (e, bool(config.get("trace.enabled")))
    return _cached[1]


@contextlib.contextmanager
def trace_range(name: str):
    if not tracing_enabled():
        yield
        return
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


@contextlib.contextmanager
def timed_range(name: str, sink=None, counter: str = None):
    """A span and a counter on one boundary. The block's self time (its
    ``perf_counter`` duration minus that of the timed ranges nested in it
    on this thread) goes to ``sink.add_time(counter, ...)`` whether or not
    tracing is on; ``counter=None`` counts nothing but still leaves the
    enclosing range's self time. With tracing on, the block is also a
    ``jax.profiler.TraceAnnotation`` named ``name``."""
    stack = getattr(_frames, "stack", None)
    if stack is None:
        stack = _frames.stack = []
    child = [0.0]
    stack.append(child)
    t0 = time.perf_counter()
    try:
        if tracing_enabled():
            import jax
            with jax.profiler.TraceAnnotation(name):
                yield
        else:
            yield
    finally:
        dur = time.perf_counter() - t0
        stack.pop()
        if stack:
            stack[-1][0] += dur
        if counter is not None:
            sink.add_time(counter, dur - child[0])


def func_range(name: str = None):
    """Decorator: wrap the call in a named xprof span (CUDF_FUNC_RANGE)."""
    def deco(fn):
        span = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*a, **kw):
            if not tracing_enabled():
                return fn(*a, **kw)
            import jax
            with jax.profiler.TraceAnnotation(span):
                return fn(*a, **kw)
        return wrapper
    return deco
