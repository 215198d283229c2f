"""The traced run's tracer rollup, for the readers of the program's own
spans and counters (``metrics/decode_*``, ``metrics/trunk_wait_*``).

``RunData`` carries only the stage rollup of the window's tracer
(``stage_wall``).  Until it carries the whole ``Tracer.summary()`` (as a
``trace_summary`` field, preferred here when present), the rollup is
taken from the tracer that ``harness.run_cell`` attached for the window,
its local ``tracer``: a reader runs inside ``run_cell``.

``summary(run)`` is None when there is no traced tracer, or when the
program's rollup has no ``per_cat_wall`` (a program that records no
detail-lane spans and none of their counters): the readers then read
nothing, rather than zero."""
import sys


def summary(run):
    s = getattr(run, "trace_summary", None)
    if s is None:
        f = sys._getframe(1)
        while f is not None and f.f_code.co_name != "run_cell":
            f = f.f_back
        tracer = f.f_locals.get("tracer") if f is not None else None
        if tracer is None:
            return None
        s = tracer.summary()
        run.trace_summary = s
    return s if "per_cat_wall" in s else None


def per_step(run, value):
    """``value``, a total over the window, per coded step."""
    return value / run.steps if run.steps else None
