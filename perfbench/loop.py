"""The traffic generator: drives one query as a traffic file describes.

A traffic file (``traffic/<name>.json``) gives the query, its literals,
the loop and the stretch of the window that a traced run records::

    {"query": "q1", "literals": {"cutoff": 2400},
     "loop": "closed", "clients": 1,
     "trace": {"queries": 2, "seconds": 12}}

A closed loop with one client sends the next query when the last one's
result is on the host: a Spark executor running a stage's tasks one
after another. The window closes at the first completion after its
length, so no query is cut and the divisor is the true elapsed time.
"""

from __future__ import annotations

import dataclasses
import sys
import time
import traceback
from typing import Any, Callable, Dict, List, Optional, Tuple

# (plan execute seconds so far, {fault or fallback counter: value})
Probe = Callable[[], Tuple[float, Dict[str, int]]]


@dataclasses.dataclass
class Done:
    """One query: its host-clock interval, the engine's own dispatch time,
    the answer's host arrays (None if it raised), and the fault or
    fallback counters that moved while it ran."""
    start: float
    end: float
    execute_s: float
    answer: Optional[Any]
    error: Optional[str]
    moved: Tuple[str, ...]
    traced: bool

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def failed(self) -> bool:
        return self.error is not None or bool(self.moved)


def serve_once(serve: Callable[[], Any], probe: Probe, step: int,
               traced: bool = False) -> Done:
    """Time one query from its call to its result on the host."""
    import jax
    x0, f0 = probe()
    t0 = time.perf_counter()
    answer, error = None, None
    with jax.profiler.StepTraceAnnotation("perfbench.query", step_num=step):
        try:
            answer = serve()
        except Exception as e:  # the query never answers; the window goes on
            traceback.print_exc(file=sys.stderr)
            error = f"{type(e).__name__}: {e}"
    t1 = time.perf_counter()
    x1, f1 = probe()
    moved = tuple(k for k in f1 if f1[k] != f0.get(k, 0))
    return Done(t0, t1, x1 - x0, answer, error, moved, traced)


def run_window(traffic: Dict[str, Any], serve: Callable[[], Any],
               probe: Probe, seconds: float,
               tracer: Optional[Any] = None) -> Tuple[List[Done], float]:
    """The measured window: (queries in order, window start). A
    ``tracer`` (``start()``/``stop()``) records the traffic's traced
    stretch from the window's first query on."""
    if traffic.get("loop") != "closed" or traffic.get("clients") != 1:
        raise ValueError(f"unsupported loop {traffic.get('loop')!r} with "
                         f"{traffic.get('clients')} clients")
    stretch = traffic["trace"]
    done: List[Done] = []
    w0 = time.perf_counter()
    tracing = tracer is not None
    if tracing:
        tracer.start()
    while True:
        d = serve_once(serve, probe, len(done), traced=tracing)
        done.append(d)
        if tracing and (sum(q.traced for q in done) >= stretch["queries"]
                        or d.end - w0 >= stretch["seconds"]):
            tracer.stop()
            tracing = False
        if d.end - w0 >= seconds:
            break
    if tracing:
        tracer.stop()
    return done, w0
