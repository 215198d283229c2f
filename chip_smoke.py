#!/usr/bin/env python3
"""Bring-up smoke test of the coded serving path on one TPU chip.

    python chip_smoke.py

Runs every phase in this one process (a chip belongs to one process at a
time; nothing here starts a child that touches JAX), prints one line per
phase — name, shapes, wall and compile seconds of the phase (set-up time,
not speed), and its check — and, when every phase passed, prints as its
last line exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Phases, in order:

* ``device``  — the default JAX device must be a TPU; there is no CPU
  branch (with ``JAX_PLATFORMS=cpu`` the script exits 1).
* ``kernels`` — the Pallas kernels with ``interpret=False`` at llama3.2-1b
  widths (FFN up projection: 8192 rows, K = 2048) against plain
  ``jax.numpy`` references on the chip; counter-derived parity rows must
  be bit-identical to the host derivation.
* ``plain_server`` — ``repro.launch.serve.main --arch llama3.2-1b
  --no-smoke``: the decode logits must agree with a teacher-forced full
  forward.
* ``coded_server`` — ``run_coded_smoke`` with the output head coded on the
  ``pallas`` backend, device products and virtual parity; the bridge
  asserts every decoded coded matmul against the uncoded product and its
  greedy argmax.

The persistent compilation cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, or to ``<repo>/.jax_cache`` (``repro.launch.compile_cache``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import io
import json
import os
import sys
import time
import traceback

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

#: float32 kernel tolerance on max|kernel − ref| / max|ref|.  Kernel and
#: reference both contract float32 at full precision (``F32_PRECISION``)
#: but accumulate in different orders: each contraction of length n ≤ 8192
#: rounds by about sqrt(n)·2^-24 ≈ 5e-6 relative, and the generated-parity
#: product chains two of them.  2e-5 leaves room for that and still fails
#: by two orders of magnitude if a dot fell back to one bf16 pass (~4e-3).
KERNEL_TOL = 2e-5

#: vocabulary rows of the coded server's head (all 16 layers and d_model
#: 2048 keep their published values).  Full width (128256) does not fit
#: the budget below: on a TPU v5e host the phase took 29 s at 8192 rows
#: and 450 s at 16384.  About L/2 parity rows enter a covering prefix and
#: virtual parity derives each covering block (256 × L threefry draws) in
#: host numpy: a profile on that host put 82 % of the 16384-row phase
#: there, each block costing 9.8x what it cost at 8192 for 2x the draws.
CODED_VOCAB = 8192

#: wall budget of the coded server phase (set-up plus serving).  A run at
#: 600 s passed 8192 rows; the budget was halved to a quarter of the
#: script's 20-minute limit so that the phase at ten times its measured
#: 29 s still leaves the rest for cold compiles on a slower host.  16384
#: rows (450 s) would pass under 600 s, with the cold script near 590 s.
CODED_BUDGET_S = 300.0


class PhaseFailed(Exception):
    pass


_compile = {"s": 0.0, "n": 0}


def _on_duration(event: str, duration: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compile["s"] += duration
        _compile["n"] += 1


def _phase(name: str, fn) -> None:
    """Run one phase; print its line; raise PhaseFailed on any error."""
    c0, n0 = _compile["s"], _compile["n"]
    t0 = time.perf_counter()
    try:
        shapes, check = fn()
    except Exception as e:  # reported, then the script exits non-zero
        traceback.print_exc()
        raise PhaseFailed(f"{name}: {type(e).__name__}: {e}") from e
    wall = time.perf_counter() - t0
    print(f"[phase] {name} | {shapes} | wall_s={wall:.3f} "
          f"compile_s={_compile['s'] - c0:.3f} "
          f"compiles={_compile['n'] - n0} | {check} | ok", flush=True)


def phase_device():
    import jax
    from repro.stream.backend import decode_device
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise RuntimeError(f"default JAX device is {d.platform!r} "
                           f"({d.device_kind}), not a TPU")
    return (f"platform={d.platform} kind={d.device_kind} count={len(devs)}",
            f"decode solve device={decode_device()}")


def _rel_err(got, ref) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    return float(np.abs(got - ref).max() / np.abs(ref).max())


def phase_kernels():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import mds
    from repro.kernels import ops
    from repro.kernels.matmul import F32_PRECISION

    L, K, C, n_par = 8192, 2048, 4, 2048     # FFN up: d_ff × d_model
    key = (0x1234ABCD, 0x0BADF00D)
    rng = np.random.default_rng(0)
    W = jnp.asarray(rng.normal(size=(L, K)) / np.sqrt(K), jnp.float32)
    x = jnp.asarray(rng.normal(size=(K, C)), jnp.float32)
    mm = lambda a, b: jnp.matmul(a, b, precision=F32_PRECISION)
    errs = {}

    # materialised shard tiles: W packed as (T, 128, K) row tiles
    tiles = W.reshape(L // 128, 128, K)
    y = ops.coded_shard_matmul_batch(tiles, x, mode="pallas",
                                     interpret=False)
    errs["shard_matmul"] = _rel_err(y.reshape(L, C), mm(W, x))

    # counter parity rows: edge row ids / redraw bytes, bit-identical to
    # the host derivation
    ids = np.concatenate([np.arange(n_par - 3),
                          [0, mds.PARITY_ROW_LIMIT - 1, 12345]])
    draws = np.zeros(n_par, np.int64)
    draws[-3:] = [mds.PARITY_DRAW_LIMIT - 1, 1, 7]
    ctrs = mds.parity_counters(ids, draws)
    rows_dev = np.asarray(ops.counter_parity_rows(key, L, ctrs,
                                                  interpret=False))
    rows_host = mds.counter_parity_rows(key, ctrs, L, dtype=np.float32)
    n_diff = int((rows_dev.view(np.uint32)
                  != rows_host.view(np.uint32)).sum())
    if n_diff:
        raise AssertionError(f"counter_parity_rows: {n_diff} of "
                             f"{rows_host.size} values differ in bits "
                             f"from the host derivation")

    # generated parity: zero parity lanes in the tiles, products derived
    # in-kernel from the counters against the resident W
    R = jnp.asarray(rows_host)
    tiles_g = jnp.concatenate([W, jnp.zeros((n_par, K), jnp.float32)])
    tiles_g = tiles_g.reshape((L + n_par) // 128, 128, K)
    spec = ops.GeneratedParity(lanes=L + np.arange(n_par), ctrs=ctrs,
                               key=key, w=W)
    yg = ops.coded_shard_matmul_batch(tiles_g, x, mode="pallas",
                                      parity_mode="generated", parity=[spec],
                                      interpret=False)
    ref_g = jnp.concatenate([mm(W, x), mm(R, mm(W, x))])
    errs["gen_parity"] = _rel_err(yg.reshape(L + n_par, C), ref_g)

    # systematic MDS encode: [W; R @ W]
    G = jnp.concatenate([jnp.eye(L, dtype=jnp.float32), R])
    enc = ops.mds_encode(G, W, interpret=False)
    errs["mds_encode"] = _rel_err(enc, jnp.concatenate([W, mm(R, W)]))
    jax.block_until_ready(enc)

    bad = {k: v for k, v in errs.items() if not v <= KERNEL_TOL}
    if bad:
        raise AssertionError(f"kernel error above {KERNEL_TOL:.0e}: {bad}")
    return (f"W=({L},{K}) x=({K},{C}) parity_rows={n_par}",
            "counter_parity_rows bit-identical to host; "
            + " ".join(f"{k}_rel_err={v:.3e}" for k, v in errs.items())
            + f" tol={KERNEL_TOL:.0e}")


def _captured(fn):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = fn()
    out = buf.getvalue()
    for line in out.splitlines():
        print(f"    {line}")
    return rc, out


def phase_plain_server():
    from repro.launch import serve
    # the launcher checks its decode logits against a teacher-forced full
    # forward on max|Δlogit| / (1 + max|logit|) ≤ DECODE_TOL["bfloat16"]
    # = 5e-2: the cached decode and the full forward round a bf16 residual
    # stream (ε = 2^-8) differently through 16 layers; 5e-2 is about a
    # dozen ε, far below the O(1) change a wrong cache slot or position
    # makes
    argv = ["--arch", "llama3.2-1b", "--no-smoke", "--requests", "4",
            "--prompt-len", "32", "--gen-len", "8"]
    rc, out = _captured(lambda: serve.main(argv))
    check = next((ln.split(": ", 1)[1] for ln in out.splitlines()
                  if "decode vs full forward" in ln), "no check line")
    if rc != 0:
        raise AssertionError(f"serve.main returned {rc}: {check}")
    return "llama3.2-1b full width, 4 requests, prompt 32, gen 8", check


def phase_coded_server():
    from repro.configs import get_config
    from repro.serve_coded import run_coded_smoke
    full = get_config("llama3.2-1b")
    cfg = dataclasses.replace(full, name=f"llama3.2-1b-v{CODED_VOCAB}",
                              vocab=CODED_VOCAB)
    t0 = time.perf_counter()
    rc, out = _captured(lambda: run_coded_smoke(
        arch=cfg, policies=("edf",), n_requests=4, prompt_len=16,
        gen_len=4, masters=2, coding_scope="head", backend="pallas",
        device_products=True, parity_storage="virtual"))
    wall = time.perf_counter() - t0
    if rc != 0:
        raise AssertionError(f"run_coded_smoke returned {rc}")
    if wall > CODED_BUDGET_S:
        raise AssertionError(f"coded server took {wall:.1f}s, over its "
                             f"{CODED_BUDGET_S:.0f}s budget")
    row = next((ln for ln in out.splitlines() if ln.startswith("edf")), "")
    max_err = row.split()[-1] if row else "?"
    return (f"{cfg.name} head L={CODED_VOCAB} (of {full.vocab}) "
            f"layers={cfg.n_layers} d_model={cfg.d_model}, 2 masters, "
            f"4 requests, prompt 16, gen 4, pallas, device products, "
            f"virtual parity, budget {CODED_BUDGET_S:.0f}s",
            f"decode_ok max_err={max_err}, argmax match 1.0")


def main() -> int:
    if not os.path.isdir(os.path.join(SRC, "repro", "serve_coded")):
        print(f"[chip_smoke] FAILED: the repro sources are not in {SRC}",
              file=sys.stderr)
        return 1
    sys.path.insert(0, SRC)
    # the exact decode solve is float64 LU, which the TPU compiler lacks:
    # it runs on JAX's CPU device, so a platform list must keep the CPU
    # backend next to the accelerator (listed first, it stays the default)
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    print(f"[chip_smoke] compile cache: {cache}", flush=True)
    try:
        for name, fn in (("device", phase_device),
                         ("kernels", phase_kernels),
                         ("plain_server", phase_plain_server),
                         ("coded_server", phase_coded_server)):
            _phase(name, fn)
    except PhaseFailed as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr)
        return 1
    d = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
