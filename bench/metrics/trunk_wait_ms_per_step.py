"""Host wall the bridge spends in its trunk calls per coded step: the
``trunk.decode`` and ``trunk.prefill`` sub-spans (category ``trunk``),
each from the jitted call until the host holds the hidden states, so
they include the wait on the device.  Part of ``glue``."""
import spans


def read(run):
    s = spans.summary(run)
    if s is None:
        return None
    return spans.per_step(run, 1e3 * s["per_cat_wall"].get("trunk", 0.0))
