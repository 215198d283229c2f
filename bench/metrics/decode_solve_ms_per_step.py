"""Host wall of the decode's solve calls (``decode.solve`` sub-spans of the
``decode`` stage: for the jax engine the float64 LU and solve on the CPU
device, its ``device_put`` and the copy back) per coded step, from the
tracer."""
import spans


def read(run):
    s = spans.summary(run)
    if s is None:
        return None
    return spans.per_step(run, 1e3 * s["per_cat_wall"].get("decode.solve", 0.0))
