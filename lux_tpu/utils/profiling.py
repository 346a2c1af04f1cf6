"""Profiling integration.

TPU-native replacement for the reference's observability hooks: Legion
Prof/Spy exist behind -lg:* flags but are unused in-repo (SURVEY.md §5);
the in-tree story is Realm::Clock timers.  Here: `jax.profiler` traces
(viewable in XProf/Perfetto/TensorBoard) wrapping any run, plus
`block_until_ready` fencing so phases attribute correctly.  The
engines name their phases (``jax.named_scope``: ``pull.gather``,
``push.dense``, ...) and the recorder's spans land on the trace's host
plane, so a captured trace attributes device time by phase.
"""
from __future__ import annotations

import contextlib
import logging

import jax

log = logging.getLogger("lux_tpu")


@contextlib.contextmanager
def trace(trace_dir: str | None):
    """Context manager: capture a jax.profiler trace when dir is given."""
    if not trace_dir:
        yield
        return
    from lux_tpu import obs

    jax.profiler.start_trace(trace_dir)
    try:
        with obs.span("profiler.trace", dir=trace_dir):
            yield
    finally:
        jax.profiler.stop_trace()
        log.info("profiler trace written to %s", trace_dir)
        print(f"profiler trace written to {trace_dir}")


def annotate(name: str):
    """Named region for trace timelines (no-op outside tracing)."""
    return jax.profiler.TraceAnnotation(name)
