"""Host wall of the bridge's ``plan`` stage (dispatch planning, covering
prefixes) per coded step of the window, from the tracer's spans."""


def read(run):
    if run.stage_wall is None or not run.steps:
        return None
    return 1e3 * run.stage_wall.get("plan", 0.0) / run.steps
