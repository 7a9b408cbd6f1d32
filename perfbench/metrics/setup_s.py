"""Seconds from the runner's first line to the first timed query:
imports, device start, table generation and upload, program load or
compile, and one warm-up query."""


def read(run):
    return run["setup_s"]
