"""Order of the systems the decode solves per coded step: the tracer's
``decode_system_rows`` counter (g x n for every solve call of g stacked
n x n systems)."""
import spans


def read(run):
    s = spans.summary(run)
    if s is None:
        return None
    return spans.per_step(run, s["counters"].get("decode_system_rows", 0.0))
