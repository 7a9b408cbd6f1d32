"""The engine's ``dispatch_s``, the self time of ``plan.dispatch``:
fault injector, watchdog, reservation and launch, staging and the
release barrier excluded; in ms per untraced query. The engine adds to
the counter once a query (once an attempt or a piece where the OOM
ladder retries or splits, which no cell does) and keeps its latest
additions (``plan_metrics.recent``): the mean of the last as many as the
window has untraced queries, which come last, so the warm-up's compiles
stay out. Nothing where the engine keeps no such counter or added none."""


def read(run):
    from spark_rapids_jni_tpu.plan import plan_metrics
    recent = getattr(plan_metrics, "recent", None)
    n = run["measured"]["queries"]
    if recent is None or not n:
        return None
    vals = recent("dispatch_s", n)
    return 1000.0 * sum(vals) / len(vals) if vals else None
