"""Host wall of the bridge's ``glue`` (time inside a step span that no
stage span covers: the trunk call, host conversions, bookkeeping) per
coded step, from the tracer."""


def read(run):
    if run.stage_wall is None or not run.steps:
        return None
    return 1e3 * run.stage_wall.get("glue", 0.0) / run.steps
