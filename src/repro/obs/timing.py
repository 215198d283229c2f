"""Honest wall timing around jitted / device work.

An unfenced ``perf_counter`` pair around a jax call times *dispatch*, not
compute — results are futures.  :func:`device_span` fences the exit with
``jax.block_until_ready`` on whatever the body registered, so the recorded
wall span covers the device work.  The fence only happens when a tracer is
actually recording: with tracing off the async dispatch pipeline is
untouched (that's the < 2% disabled-overhead contract).

With ``Tracer(jax_profiler=True)`` the span is also a
``jax.profiler.TraceAnnotation`` (every context span of such a tracer
is), so it lines up with a concurrently captured device profile.
"""
from __future__ import annotations

import contextlib
from typing import Any, Dict, Iterator, Optional

from .tracer import Tracer, current_tracer

__all__ = ["device_fence", "device_span"]


def device_fence(x: Any) -> Any:
    """``jax.block_until_ready`` when jax is importable, else identity."""
    try:
        import jax
    except Exception:
        return x
    try:
        return jax.block_until_ready(x)
    except Exception:  # host-side objects jax refuses to traverse
        return x


class _Fence:
    """Mutable holder the ``device_span`` body loads its result into."""

    __slots__ = ("value",)

    def __init__(self) -> None:
        self.value: Any = None

    def __call__(self, x: Any) -> Any:
        self.value = x
        return x


@contextlib.contextmanager
def device_span(name: str, *, cat: str = "kernel", track: str = "wall",
                args: Optional[Dict[str, Any]] = None,
                tr: Optional[Tracer] = None) -> Iterator[_Fence]:
    """Fenced wall span.  Usage::

        with device_span("coded_shard_matmul_batch", cat="kernel") as fence:
            out = fence(jitted(...))   # blocked on at span exit

    With no active tracer the body runs untouched (no fence, no timing).
    """
    tr = tr if tr is not None else current_tracer()
    fence = _Fence()
    if tr is None:
        yield fence
        return
    with tr.span(name, cat=cat, track=track, args=args) as a:
        yield fence
        if fence.value is not None:
            device_fence(fence.value)
        a.setdefault("fenced", fence.value is not None)
