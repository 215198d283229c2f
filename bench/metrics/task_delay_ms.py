"""The paper's objective on the probe: the mean, over its coded steps, of
the simulated completion delay of the step's slowest task (``t_done -
t_start`` of the bridge's step log, in simulated milliseconds)."""


def read(run):
    d = [s["t_done"] - s["t_start"] for s in run.probe_steps]
    return sum(d) / len(d) if d else None
