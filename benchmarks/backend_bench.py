"""Backend throughput record: numpy vs jax vs pallas on the shared numerics.

Three measurements, written to BENCH_backend.json (env knob
REPRO_BENCH_BACKEND_JSON) so the perf trajectory is machine-readable:

(a) Monte-Carlo completion delay (the workload behind every paper figure)
    at large trial counts: the chunked-numpy ``simulate_plan`` loop vs the
    jitted device-resident ``stream.backend.simulate_batch`` kernel
    (active-column gather, rbg float32 draws, sort-free completion rule in
    cache-sized lax.map chunks).  The acceptance bar is >= 5x throughput on
    the jax path at 1e5 trials; CPU measures ~10-15x, accelerators more.
(b) The exactly-L decode: systematic-prefix fast path (permutation scatter,
    bit-identical to the general solve) vs the forced stacked LU solve.
(c) The verification encode: the Pallas ``mds_encode`` kernel vs plain jnp
    matmul at serving-path sizes.  Off-TPU the kernel runs in interpret
    mode — correctness-scale numbers only, recorded with the flag so the
    JSON is honest about what was measured.
(d) The batched shard-execution kernel: one ``coded_shard_matmul_batch``
    pass over a serving step's packed 128-aligned shard tiles vs the
    per-tile loop (numpy einsum reference, jax vmap fallback, Pallas
    one-launch path).
(e) Virtual parity: the generated-parity kernel path (rows derived
    in-kernel from packed threefry counters) vs the materialised gather,
    plus the encoded-cache bytes each storage mode holds at redundancy 2.
    CI floors generated throughput at 0.8x materialised and ceilings the
    virtual/materialised byte ratio at 0.55.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.core import iterated_greedy, large_scale_scenario, plan_from_assignment
from repro.launch.compile_cache import enable_compile_cache
from repro.sim import simulate_plan
from repro.stream.backend import decode_batch, has_jax

from .common import emit


def _best(fn, reps: int = 3) -> float:
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return min(ts)


def run_montecarlo(trials: int, seed: int = 0) -> dict:
    sc = large_scale_scenario(seed)
    plan = plan_from_assignment(sc, iterated_greedy(sc, rng=seed))
    t_np = _best(lambda: simulate_plan(sc, plan, trials=trials, rng=seed + 1),
                 reps=2)
    rec = {
        "trials": trials,
        "numpy_seconds": round(t_np, 4),
        "numpy_trials_per_s": round(trials / t_np),
    }
    if has_jax():
        jx = lambda: simulate_plan(sc, plan, trials=trials, rng=seed + 1,
                                   backend="jax")
        jx()                                   # compile outside the timing
        t_jx = _best(jx, reps=3)
        r_np = simulate_plan(sc, plan, trials=trials, rng=seed + 1)
        r_jx = simulate_plan(sc, plan, trials=trials, rng=seed + 1,
                             backend="jax")
        rec.update({
            "jax_seconds": round(t_jx, 4),
            "jax_trials_per_s": round(trials / t_jx),
            "jax_speedup": round(t_np / t_jx, 2),
            "numpy_mean_ms": round(r_np.overall_mean, 2),
            "jax_mean_ms": round(r_jx.overall_mean, 2),
        })
        emit("backend/montecarlo", t_jx * 1e6,
             f"trials={trials};jax_speedup={rec['jax_speedup']}x;"
             f"numpy_mean={rec['numpy_mean_ms']};jax_mean={rec['jax_mean_ms']}")
    return rec


def run_decode(batch: int = 2048, L: int = 128, seed: int = 0) -> dict:
    """Systematic-prefix scatter vs forced general solve on identical input."""
    rng = np.random.default_rng(seed)
    Lt = 2 * L
    G = np.vstack([np.eye(L), rng.normal(0, 1 / np.sqrt(L), (Lt - L, L))])
    # the no-straggler serving case: every task got the systematic prefix
    rows = np.stack([rng.permutation(L) for _ in range(batch)])
    x_true = rng.normal(size=(batch, L))
    y = np.stack([x_true[i][rows[i]] for i in range(batch)])
    t_fast = _best(lambda: decode_batch(G, rows, y))
    t_solve = _best(lambda: decode_batch(G, rows, y, systematic="never"))
    out_fast = decode_batch(G, rows, y)
    out_solve = decode_batch(G, rows, y, systematic="never")
    rec = {
        "batch": batch, "L": L,
        "fast_path_seconds": round(t_fast, 5),
        "solve_seconds": round(t_solve, 5),
        "fast_path_speedup": round(t_solve / t_fast, 1),
        "bit_identical": bool((out_fast == out_solve).all()),
    }
    emit("backend/decode_fast_path", t_fast * 1e6,
         f"batch={batch};L={L};speedup={rec['fast_path_speedup']}x;"
         f"bit_identical={rec['bit_identical']}")
    return rec


def run_pallas_encode(L: int = 256, S: int = 256, seed: int = 0) -> dict:
    if not has_jax():  # pragma: no cover
        return {}
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops
    rng = np.random.default_rng(seed)
    Lt = 2 * L
    G = jnp.asarray(np.vstack([np.eye(L),
                               rng.normal(0, 1 / np.sqrt(L), (L, L))]),
                    jnp.float32)
    A = jnp.asarray(rng.normal(size=(L, S)), jnp.float32)
    interp = ops.default_interpret()
    pal = lambda: np.asarray(ops.mds_encode(G, A))
    ref = lambda: np.asarray(jnp.matmul(G, A))
    pal(), ref()                               # compile outside the timing
    t_pal, t_ref = _best(pal), _best(ref)
    err = float(np.abs(pal() - ref()).max())
    rec = {
        "shape": f"{Lt}x{L}x{S}",
        "pallas_seconds": round(t_pal, 5),
        "jnp_seconds": round(t_ref, 5),
        "interpret_mode": bool(interp),
        "max_err": err,
    }
    emit("backend/pallas_encode", t_pal * 1e6,
         f"shape={rec['shape']};interpret={interp};max_err={err:.2e}")
    return rec


def run_shard_matmul(tiles: int = 12, tile: int = 128, D: int = 128,
                     cols: int = 4, seed: int = 0) -> dict:
    """The batched serving kernel: every packed shard tile of a step in
    one pass (``kernels.ops.coded_shard_matmul_batch``) vs the per-tile
    loop it replaces — numpy einsum loop, jax vmap, Pallas one-launch
    (interpret off-TPU: correctness-scale numbers, flagged)."""
    if not has_jax():  # pragma: no cover
        return {}
    import jax.numpy as jnp
    from repro.kernels import ops
    rng = np.random.default_rng(seed)
    T = np.asarray(rng.normal(size=(tiles, tile, D)), np.float32)
    x = np.asarray(rng.normal(size=(D, cols)), np.float32)
    Td, xd = jnp.asarray(T), jnp.asarray(x)
    t_np = _best(lambda: [np.einsum("ld,dc->lc", T[i], x)
                          for i in range(tiles)])
    vm = lambda: np.asarray(ops.coded_shard_matmul_batch(Td, xd,
                                                         mode="vmap"))
    pl = lambda: np.asarray(ops.coded_shard_matmul_batch(Td, xd,
                                                         mode="pallas"))
    vm(), pl()                                 # compile outside the timing
    t_vm, t_pl = _best(vm), _best(pl)
    interp = ops.default_interpret()
    err = float(np.abs(vm() - np.stack([T[i] @ x
                                        for i in range(tiles)])).max())
    rec = {
        "tiles": tiles, "tile": tile, "D": D, "cols": cols,
        "numpy_loop_seconds": round(t_np, 5),
        "vmap_seconds": round(t_vm, 5),
        "pallas_seconds": round(t_pl, 5),
        "vmap_speedup_vs_loop": round(t_np / t_vm, 2),
        "interpret_mode": bool(interp),
        "max_err": err,
    }
    emit("backend/shard_matmul_batch", t_vm * 1e6,
         f"tiles={tiles}x{tile}x{D};vmap_speedup={rec['vmap_speedup_vs_loop']}"
         f"x;interpret={interp};max_err={err:.2e}")
    return rec


def run_generated_parity(L: int = 256, D: int = 128, cols: int = 4,
                         seed: int = 0) -> dict:
    """Virtual-parity serving cost: the generated-parity kernel path
    (parity rows derived in-kernel from packed threefry counters,
    contracted as ``R_gen @ (W @ x)``) vs the materialised path (parity
    rows gathered from the host encoded cache into the tiles).  Also
    records the encoded-cache footprint of each storage mode at
    redundancy 2 — the memory the virtual mode exists to reclaim."""
    if not has_jax():  # pragma: no cover
        return {}
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.serve_coded import CodedLinear
    rng = np.random.default_rng(seed)
    W = rng.normal(size=(L, D))
    mat = CodedLinear(W, name="bench", seed=seed, parity_chunk=64)
    virt = CodedLinear(W, name="bench", seed=seed, parity_chunk=64,
                       parity_storage="virtual")
    for lin in (mat, virt):
        lin.ensure_parity(L)                   # redundancy 2
    # one packed tile: a straggler prefix of systematic rows + parity tail
    n_par = 64
    rows = np.concatenate([np.arange(L - n_par), np.arange(L, L + n_par)])
    tiles_mat = jnp.asarray(mat.gather_encoded(rows)[None], jnp.float32)
    zeroed = virt.gather_encoded(rows).astype(np.float32)
    par_pos = np.nonzero(rows >= L)[0]
    zeroed[par_pos] = 0.0
    spec = ops.GeneratedParity(lanes=par_pos,
                               ctrs=virt.parity_ctrs(rows[par_pos] - L),
                               key=virt.pkey, w=virt.device_W())
    tiles_gen = jnp.asarray(zeroed[None])
    x = jnp.asarray(rng.normal(size=(D, cols)), jnp.float32)
    m = lambda: np.asarray(ops.coded_shard_matmul_batch(
        tiles_mat, x, mode="vmap"))
    g = lambda: np.asarray(ops.coded_shard_matmul_batch(
        tiles_gen, x, mode="vmap", parity_mode="generated", parity=[spec]))
    m(), g()                                   # compile outside the timing
    t_m, t_g = _best(m), _best(g)
    err = float(np.abs(g() - m()).max())
    b_mat, b_virt = mat.encoded_cache_bytes(), virt.encoded_cache_bytes()
    rec = {
        "L": L, "D": D, "cols": cols, "parity_rows": n_par,
        "materialized_seconds": round(t_m, 5),
        "generated_seconds": round(t_g, 5),
        "generated_vs_materialized": round(t_m / t_g, 3),
        "encoded_bytes_materialized": int(b_mat),
        "encoded_bytes_virtual": int(b_virt),
        "encoded_bytes_ratio": round(b_virt / b_mat, 3),
        "interpret_mode": bool(ops.default_interpret()),
        "max_err": err,
    }
    emit("backend/generated_parity", t_g * 1e6,
         f"L={L};D={D};gen_vs_mat={rec['generated_vs_materialized']}x;"
         f"bytes_ratio={rec['encoded_bytes_ratio']};max_err={err:.2e}")
    return rec


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--trials", type=int, default=100_000,
                   help="Monte-Carlo trials for the throughput record")
    p.add_argument("--json", default=None,
                   help="output path (default BENCH_backend.json)")
    args = p.parse_args(argv)
    enable_compile_cache()
    record = {
        "bench": "backend_throughput",
        "montecarlo": run_montecarlo(args.trials),
        "decode": run_decode(),
        "pallas_encode": run_pallas_encode(),
        "shard_matmul": run_shard_matmul(),
        "generated_parity": run_generated_parity(),
    }
    path = args.json or os.environ.get("REPRO_BENCH_BACKEND_JSON",
                                       "BENCH_backend.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    print(f"# wrote {path}")
    return record


if __name__ == "__main__":
    main()
