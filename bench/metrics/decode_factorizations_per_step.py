"""Systems LU-factorised by the decode per coded step: the tracer's
``decode_lu_factorizations`` counter (the jax engine factorises every
stacked system on every call; the numpy engine once per frozen plan)."""
import spans


def read(run):
    s = spans.summary(run)
    if s is None:
        return None
    return spans.per_step(run, s["counters"].get("decode_lu_factorizations", 0.0))
