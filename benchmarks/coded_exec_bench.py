"""Beyond-paper benchmark: the end-to-end coded execution engine + kernels.

(a) CodedExecutor numerical round-trip at matrix scale (encode → straggle →
    k-of-n decode) with fault injection;
(b) Pallas kernel throughput (interpret mode on CPU: correctness-scale
    numbers, the real targets are TPU);
(c) coded gradient aggregation k-of-n reconstruction error;
(d) the streaming engine: a 1000-task, 3-master Poisson stream with mid-run
    churn through the batched backend vs the same tasks run sequentially
    through CodedExecutor — results land in BENCH_stream.json (env knob
    REPRO_BENCH_JSON) so the perf trajectory is machine-readable.
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.core import (Scenario, iterated_greedy, plan_from_assignment,
                        small_scale_scenario)
from repro.launch.compile_cache import enable_compile_cache
from repro.runtime import CodedExecutor
from repro.runtime.coded_grads import coded_grad_aggregate, encode_grad_shards
from repro.stream import (BackendConfig, StreamConfig, StreamingExecutor,
                          WorkerEvent, poisson_sources)

from .common import emit, timed


def run_executor(seed: int = 0):
    sc = small_scale_scenario(seed)
    plan = plan_from_assignment(sc, iterated_greedy(sc, rng=seed))
    # shrink loads to a fast matrix size while keeping proportions
    plan.l[:] = plan.l / sc.L[:, None] * 512
    sc = Scenario(a=sc.a, u=sc.u, gamma=sc.gamma, L=np.full(sc.M, 512.0))
    ex = CodedExecutor(sc, plan, rng=seed)
    rng = np.random.default_rng(seed)
    A = [rng.normal(size=(512, 128)) for _ in range(sc.M)]
    x = [rng.normal(size=128) for _ in range(sc.M)]

    def go():
        return ex.run(A, x, dead_workers=(1,))

    (res, report), t_us = timed(go)
    emit("coded_exec/roundtrip", t_us,
         f"decode_ok={bool(report.decode_ok.all())};"
         f"max_err={report.max_err.max():.2e};"
         f"completion_ms={report.overall:.1f};dead_worker_survived=True")


def run_kernels(seed: int = 0):
    import jax.numpy as jnp
    from repro.kernels import coded_matvec, mds_encode, ref
    rng = np.random.default_rng(seed)
    G = jnp.asarray(np.vstack([np.eye(256),
                               rng.normal(0, 1 / 16, size=(256, 256))]),
                    jnp.float32)
    A = jnp.asarray(rng.normal(size=(256, 512)), jnp.float32)
    (enc, t_enc) = timed(lambda: np.asarray(mds_encode(G, A)))
    err = float(np.abs(enc - np.asarray(ref.mds_encode_ref(G, A))).max())
    emit("kernels/mds_encode_interp", t_enc, f"max_err={err:.2e};shape=512x256x512")
    x = jnp.asarray(rng.normal(size=(512,)), jnp.float32)
    (y, t_mv) = timed(lambda: np.asarray(coded_matvec(jnp.asarray(enc), x)))
    err2 = float(np.abs(y - np.asarray(ref.coded_matvec_ref(jnp.asarray(enc), x))).max())
    emit("kernels/coded_matvec_interp", t_mv, f"max_err={err2:.2e}")


def run_coded_grads(seed: int = 0):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    grads = [{"w": jnp.asarray(rng.normal(size=(64, 64)), jnp.float32),
              "b": jnp.asarray(rng.normal(size=(64,)), jnp.float32)}
             for _ in range(4)]

    def go():
        coded, ctx = encode_grad_shards(grads, n_coded=6, rng=seed)
        # drop shards 0 and 2 (stragglers) — any 4 of 6 reconstruct
        return coded_grad_aggregate(coded, ctx, arrived=[1, 3, 4, 5])

    agg, t_us = timed(go)
    truth = sum(np.asarray(g["w"]) for g in grads)
    err = float(np.abs(np.asarray(agg["w"]) - truth).max() / np.abs(truth).max())
    emit("coded_grads/4of6", t_us, f"rel_err={err:.2e};stragglers_dropped=2")


def _stream_scenario(seed: int = 0, M: int = 3, N: int = 8, L: float = 256.0):
    rng = np.random.default_rng(seed)
    a = np.zeros((M, N + 1))
    a[:, 0] = 0.5
    a[:, 1:] = rng.uniform(0.2, 0.4, size=(M, N))
    return Scenario(a=a, u=1 / a, gamma=2 / a, L=np.full(M, L))


def run_stream(seed: int = 0, n_tasks: int = 1000,
               json_path: str | None = None, backend: str = "numpy"):
    """1000-task streaming simulation vs sequential CodedExecutor.run.

    Both sides simulate the same workload class (3 masters, L=256 coded
    rows, heterogeneous workers).  Two stream timings are recorded so the
    comparison is honest about what is skipped vs what is batched:

    * delay-sim (numerics='none'): arrivals + queueing + completion delays
      only — the Monte-Carlo-style use, no linear algebra;
    * verify (numerics='verify'): additionally executes every task's MDS
      encode → partial products → exactly-L decode, but *batched* per
      master (einsum + stacked solve) — like-for-like with the baseline's
      per-task numerics loop.
    """
    sc = _stream_scenario(seed)

    def stream_once(numerics):
        srcs = poisson_sources(sc, utilization=0.6, seed=seed + 1)
        churn = [WorkerEvent(2000.0, 2, "degrade", 3.0),
                 WorkerEvent(5000.0, 5, "leave"),
                 WorkerEvent(9000.0, 5, "join")]
        cfg = StreamConfig(
            policy="fractional", rng=seed,
            backend=BackendConfig(numerics=numerics, backend=backend))
        ex = StreamingExecutor(sc, srcs, config=cfg, churn=churn)
        t0 = time.perf_counter()
        ms = ex.run(max_tasks=n_tasks)
        return ms, time.perf_counter() - t0

    ms, stream_s = stream_once("none")
    ms_v, stream_verify_s = stream_once("verify")
    s = ms.summary()
    decode_rate = ms_v.summary().get("decode_ok_rate", float("nan"))

    # sequential baseline: the per-master Python-loop executor, once per task
    plan = plan_from_assignment(sc, iterated_greedy(sc, rng=seed))
    L = int(sc.L[0])
    rng = np.random.default_rng(seed)
    A = [rng.normal(size=(L, 8)) for _ in range(sc.M)]
    x = [rng.normal(size=8) for _ in range(sc.M)]
    seq_runs = max(n_tasks // sc.M, 1)       # each run executes M tasks
    cex = CodedExecutor(sc, plan, rng=seed)
    t0 = time.perf_counter()
    for _ in range(seq_runs):
        cex.run(A, x)
    seq_s = (time.perf_counter() - t0) * (n_tasks / (seq_runs * sc.M))

    speedup = seq_s / max(stream_s, 1e-12)
    speedup_verify = seq_s / max(stream_verify_s, 1e-12)
    record = {
        "bench": "stream_vs_sequential",
        "tasks": n_tasks,
        "masters": sc.M,
        "workers": sc.N,
        "L": L,
        "stream_seconds": round(stream_s, 4),
        "stream_verify_seconds": round(stream_verify_s, 4),
        "sequential_seconds": round(seq_s, 4),
        "speedup": round(speedup, 2),
        "speedup_batched_numerics": round(speedup_verify, 2),
        "decode_ok_rate": decode_rate,
        "throughput_tasks_per_s": round(n_tasks / max(stream_s, 1e-12), 1),
        "p50_sojourn_ms": round(s["sojourn_p50"], 3),
        "p99_sojourn_ms": round(s["sojourn_p99"], 3),
        "queue_wait_mean_ms": round(s["queue_wait_mean"], 3),
        "wasted_fraction": round(s["wasted_fraction"], 4),
        "replans": int(s["replans"]),
        "tasks_completed": int(s["tasks_completed"]),
    }
    path = json_path or os.environ.get("REPRO_BENCH_JSON", "BENCH_stream.json")
    # BENCH_stream.json is shared with stream_fleet_bench: carry its
    # "fleet" section over instead of clobbering it
    try:
        with open(path) as f:
            fleet = json.load(f).get("fleet")
    except (OSError, ValueError):
        fleet = None
    if fleet is not None:
        record["fleet"] = fleet
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    emit("stream/1k_tasks", stream_s * 1e6,
         f"speedup_vs_sequential={speedup:.1f}x;"
         f"speedup_batched_numerics={speedup_verify:.1f}x;"
         f"decode_ok_rate={decode_rate};"
         f"throughput={record['throughput_tasks_per_s']};"
         f"p99_sojourn_ms={record['p99_sojourn_ms']};json={path}")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--tasks", type=int, default=1000,
                   help="streaming-bench task count")
    p.add_argument("--backend", default="numpy",
                   choices=("numpy", "jax", "pallas"),
                   help="streaming verification backend")
    args = p.parse_args(argv)
    enable_compile_cache()
    run_executor()
    run_kernels()
    run_coded_grads()
    run_stream(n_tasks=args.tasks, backend=args.backend)


if __name__ == "__main__":
    main()
