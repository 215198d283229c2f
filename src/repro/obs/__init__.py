"""repro.obs — zero-dependency span tracing for the coded-computation stack.

See ``stream/README.md`` ("Observability") for the span taxonomy and the
Perfetto workflow.  Core pieces:

* :class:`Tracer` / :class:`Span` — spans, instants, counters on wall and
  sim-time tracks; ``to_chrome_trace()`` / ``to_records()`` / ``summary()``.
* :func:`current_tracer` / :func:`use_tracer` — process-global registry so
  deep hot paths (kernels, stacked solves) can record without plumbing a
  tracer argument through every signature.
* :func:`device_span` — ``block_until_ready``-fenced wall timing;
  ``Tracer(jax_profiler=True)`` puts every context span on the
  ``jax.profiler`` trace as a ``TraceAnnotation``.
* ``python -m repro.obs.validate out.json`` — trace schema checker (CI).
"""
from .tracer import (DETAIL_TRACK, NULL_SPAN, STAGE_CATS, Span, Tracer,
                     current_tracer, use_tracer)
from .timing import device_fence, device_span
from .export import summary as trace_summary
from .validate import check_trace

__all__ = [
    "STAGE_CATS", "DETAIL_TRACK", "NULL_SPAN", "Span", "Tracer",
    "current_tracer", "use_tracer", "device_fence", "device_span",
    "trace_summary", "check_trace",
]
