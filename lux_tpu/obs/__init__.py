"""lux_tpu.obs — luxtrace, the always-on flight recorder.

Two layers, one event log per run:

* **recorder** (pure stdlib) — ``span()``/``point()`` context managers
  writing an append-only JSONL event log under a uid-checked 0o700 dir;
  nested span ids, monotonic timestamps, a ``run_id`` that bench rows
  and AUDIT/PROGRESS entries also carry.  Crash-safe by construction:
  begin events are on disk before the work they cover runs.  Once jax
  is loaded each span is also a profiler ``TraceAnnotation``, so it
  shows on a captured trace's host plane.  ``count()``/``counters()``
  keep per-name [calls, total] counters that take device scalars
  without syncing.
* **ring** (jax) — fixed-capacity on-device iteration telemetry carried
  in the hot-loop carry (static shapes, donated with the state, fetched
  once at run end); bitwise no-op on results, enforced by LUX-J1/J2/J5
  and the LUX-O checker family.

``tools/luxview.py`` renders any event log into the human report;
``tools/obs_span.py`` spans shell steps so an aborted run still leaves
a complete post-mortem artifact.  Schema + design notes:
docs/OBSERVABILITY.md.

This ``__init__`` (and recorder) stays jax-free so the tools can import
it under the same bare-package stub luxcheck uses; ``ring`` imports
lazily where needed.  Inside compiled code the engines name their
phases with ``jax.named_scope`` (``pull.*``, ``push.*``): op-name
metadata only, no runtime op.

``dtrace`` (also stdlib-only) is the distributed-tracing layer on top:
trace contexts minted at the fleet entry points, carried on every fleet
frame, recorded as span attrs each hop — ``tools/luxstitch.py`` merges
the per-process logs into one causally-ordered fleet timeline, and
``obs/slo.py`` evaluates declarative SLOs as multi-window burn rates
over the serving metrics with trace-id exemplars.
"""
from lux_tpu.obs.recorder import (  # noqa: F401
    Recorder,
    Span,
    count,
    counters,
    install,
    new_run_id,
    point,
    recorder,
    run_id,
    span,
)
from lux_tpu.obs import dtrace  # noqa: F401  (stdlib-only, like recorder)
