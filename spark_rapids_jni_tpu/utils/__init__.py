"""Cross-cutting utilities: tracing (xprof spans — the NVTX-range analog)."""

from .tracing import func_range, timed_range, trace_range, tracing_enabled

__all__ = ["func_range", "timed_range", "trace_range", "tracing_enabled"]
