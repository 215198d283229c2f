"""Host wall of the bridge's ``decode`` stage (the float64 solve of the
covering prefix on the CPU device) per coded step, from the tracer."""


def read(run):
    if run.stage_wall is None or not run.steps:
        return None
    return 1e3 * run.stage_wall.get("decode", 0.0) / run.steps
