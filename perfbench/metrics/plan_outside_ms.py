"""Query wall time outside the engine's ``execute_s``, per query, over
the untraced queries: literal resolution, program cache lookup, output
trim and compaction, and the copy of the answer to the host."""


def read(run):
    m = run["measured"]
    if not m["queries"]:
        return None
    return 1000.0 * (m["seconds"] - m["execute_s"]) / m["queries"]
