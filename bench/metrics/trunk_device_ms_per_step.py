"""Device time of the jitted trunk programs (the bridge's prefill and
decode from ``launch.serve.serving_fns``) per coded step of the window,
from the profiler trace.  Both are ``jax.jit`` of a lambda, so their
programs are the trace's ``jit__lambda_`` modules: no other jitted
lambda runs on the TPU in the served path."""

TRUNK = "jit__lambda"


def read(run):
    if run.device is None or not run.steps:
        return None
    t = sum(v for k, v in run.device["module_s"].items() if k.startswith(TRUNK))
    if t <= 0:
        return None
    return 1e3 * t / run.steps
