"""Host wall of the bridge's ``pack`` stage (packed shard gathers, parity
rows derived on the host) per coded step of the window, from the tracer."""


def read(run):
    if run.stage_wall is None or not run.steps:
        return None
    return 1e3 * run.stage_wall.get("pack", 0.0) / run.steps
