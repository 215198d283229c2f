#!/usr/bin/env python3
"""Benchmark entry: one run of one cell on the chip.

    python3 bench/run.py --workload glm4-9b.decode --seed 7 --seconds 30 --trace 0

Run from the root of a checkout that holds ``src/`` (the program) next to
``BENCHMARK.json`` and ``bench/``.  The run needs a TPU: with no TPU, or
fewer chips than the cell asks for, it exits non-zero and prints no
result.  ``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1``
its per-layer metrics from a profiled run; both check the served tokens
against the float32 reference.  The last line of standard output is the
result JSON; the last lines of standard error are the numbers compared,
each beside its limit.

JAX's persistent compilation cache lives in ``<checkout>/.bench_cache/jax``
(a fixed path inside the checkout), so only a cell's first run compiles.
"""
from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
CACHE = os.path.join(REPO, ".bench_cache", "jax")


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def prepare():
    """Put the program on the path, keep JAX's CPU backend beside the
    accelerator, point the persistent compile cache into the checkout;
    returns the ``jax`` module, or None when the program is missing."""
    src = os.path.join(REPO, "src")
    if not os.path.isdir(os.path.join(src, "repro", "serve_coded")):
        print(f"[bench] FAILED: the program is not in {src}", file=sys.stderr)
        return None
    sys.path.insert(0, src)
    sys.path.insert(0, HERE)
    # the coded head's exact decode solves in float64 on JAX's CPU device,
    # so a platform list must keep the CPU backend beside the accelerator
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def chips_ok(jax, cell) -> bool:
    """Print the device; False (with the reason on stderr) unless the
    default device is a TPU and there are as many chips as the cell asks."""
    try:
        devs = jax.devices()
    except RuntimeError as e:
        print(f"[bench] FAILED: no accelerator: {e}", file=sys.stderr)
        return False
    d = devs[0]
    print(f"[bench] device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}", flush=True)
    if d.platform != "tpu":
        print(f"[bench] FAILED: the default JAX device is {d.platform!r}, "
              f"not a TPU", file=sys.stderr)
        return False
    if len(devs) < int(cell["chips"]):
        print(f"[bench] FAILED: {len(devs)} chips, the cell asks for "
              f"{cell['chips']}", file=sys.stderr)
        return False
    return True


def main(argv=None) -> int:
    args = parse(argv)
    jax = prepare()
    if jax is None:
        return 2
    import harness
    bench = harness.load_benchmark()
    cell = harness.find_cell(bench, args.workload)
    if not chips_ok(jax, cell):
        return 3
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_process=T_PROCESS, bench=bench)
    except harness.BenchError as e:
        print(f"[bench] FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps(out), flush=True)
    for name, v in out["check"].items():
        print(f"[check] {name} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr, flush=True)
    print(f"[check] correct {out['correct']}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
