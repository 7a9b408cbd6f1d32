"""The engine's own ``execute_s`` per query (plan_metrics: guarded
dispatch through the one head sync), over the untraced queries."""


def read(run):
    m = run["measured"]
    return 1000.0 * m["execute_s"] / m["queries"] if m["queries"] else None
