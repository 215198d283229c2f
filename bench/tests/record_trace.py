#!/usr/bin/env python3
"""Record the small TPU trace that ``test_devtrace.py`` reduces (run on a
chip, from the checkout's root; writes ``bench/tests/data/small.xplane.pb``
and ``small.json`` with the window's perf-counter bounds):

    python3 bench/tests/record_trace.py

Inside the ``bench:window`` annotation: a jitted lambda (as the trunk
programs are), the packed ``coded_matvec`` Pallas kernel, and a host
sleep that leaves the chip idle for 50 ms."""
import glob
import json
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "..", "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.kernels import ops  # noqa: E402


def main():
    f = jax.jit(lambda a, b: jnp.tanh(a @ b) @ b)
    a = jnp.ones((1024, 1024), jnp.bfloat16)
    tiles = jnp.ones((4, 128, 256), jnp.float32)
    x = jnp.ones((256, 8), jnp.float32)
    f(a, a).block_until_ready()
    ops.coded_shard_matmul_batch(tiles, x, interpret=False).block_until_ready()
    d = tempfile.mkdtemp()
    po = jax.profiler.ProfileOptions()
    po.python_tracer_level = 0
    po.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=po)
    with jax.profiler.TraceAnnotation("bench:window"):
        t_open = time.perf_counter()
        for _ in range(3):
            f(a, a).block_until_ready()
        time.sleep(0.05)
        ops.coded_shard_matmul_batch(tiles, x, interpret=False).block_until_ready()
        t_close = time.perf_counter()
    jax.profiler.stop_trace()
    src = glob.glob(os.path.join(d, "plugins", "profile", "*", "*.xplane.pb"))[0]
    out = os.path.join(HERE, "data")
    shutil.copy(src, os.path.join(out, "small.xplane.pb"))
    with open(os.path.join(out, "small.json"), "w") as fh:
        json.dump({"t_open": t_open, "t_close": t_close}, fh)
    print(os.path.getsize(src), "bytes")


if __name__ == "__main__":
    main()
