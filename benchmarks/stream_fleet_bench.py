"""Fleet-scale streaming bench: ≥1e6 tasks over ≥1000 masters with churn.

Measures the two mechanisms that make ``mode="incremental"`` + the
vectorised event loop the fleet-scale configuration:

* **event throughput** — the batched drain (``BackendConfig.event_batch``)
  vs the per-event reference loop (``event_batch=1``), same scenario, same
  seeds, on a common churn-free subset of the workload (churn-forced
  planner solves cost both loops the same wall and would mask the loop
  difference).  The two loops produce identical metrics (property-tested
  in ``tests/test_stream_fleet.py``); only the wall clock differs.
* **replan latency** — incremental plan repair (O(affected rows) per churn
  event) vs the full re-solve ``mode="always"`` pays on the same churn
  schedule.  Medians over the per-event planner walls
  (``OnlinePlanner.repair_wall`` / ``solve_wall``).

Results merge into the ``"fleet"`` section of ``BENCH_stream.json`` (env
knob ``REPRO_BENCH_JSON``) next to ``coded_exec_bench``'s stream record;
CI floors the two machine-independent ratios
(``fleet.events_per_s_ratio``, ``fleet.replan_latency_ratio``) via
``check_regression.py --min``.

    PYTHONPATH=src python -m benchmarks.stream_fleet_bench \
        --tasks 1000000 --masters 1000 --workers 128
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

from repro.core.problem import Scenario
from repro.launch.compile_cache import enable_compile_cache
from repro.stream import (BackendConfig, ReplanPolicy, StreamConfig,
                          StreamingExecutor, WorkerEvent, poisson_sources)

from .common import emit


def fleet_scenario(M: int, N: int, L: float = 64.0,
                   seed: int = 0) -> Scenario:
    """M-master fleet over N shared heterogeneous workers."""
    rng = np.random.default_rng(seed)
    a = np.zeros((M, N + 1))
    a[:, 0] = 0.5
    a[:, 1:] = rng.uniform(0.2, 0.4, size=(M, N))
    return Scenario(a=a, u=1 / a, gamma=2 / a, L=np.full(M, L))


def churn_schedule(horizon: float, N: int, period: float,
                   seed: int = 0) -> list:
    """Deterministic churn: every ``period`` a perturbation fires, cycling
    degrade → restore → leave → join over a rotating worker so the pool
    always returns to health (and the schedule has both repairable events
    and the joins that force a full re-solve)."""
    rng = np.random.default_rng((seed, 0xC4))
    events, t, i = [], period, 0
    while t < horizon:
        w = 1 + (i // 4) % N
        kind = ("degrade", "restore", "leave", "join")[i % 4]
        factor = float(rng.uniform(1.5, 4.0)) if kind == "degrade" else 1.0
        events.append(WorkerEvent(t, w, kind, factor))
        t += period
        i += 1
    return events


def run_fleet(sc: Scenario, *, tasks: int, utilization: float,
              churn: list, event_batch: int, mode: str,
              seed: int) -> tuple:
    cfg = StreamConfig(
        policy="fractional",
        replan=ReplanPolicy(mode=mode),
        backend=BackendConfig(event_batch=event_batch, keep_records=False),
        rng=seed)
    srcs = poisson_sources(sc, utilization=utilization, seed=seed + 1)
    ex = StreamingExecutor(sc, srcs, config=cfg, churn=list(churn))
    t0 = time.perf_counter()
    ms = ex.run(max_tasks=tasks)
    wall = time.perf_counter() - t0
    return ex, ms, wall


def _q(xs, p):
    return float(np.quantile(np.asarray(xs), p)) if len(xs) else float("nan")


def run_bench(tasks: int = 1_000_000, masters: int = 1000,
              workers: int = 128, utilization: float = 0.15,
              churn_period: float = 20000.0, event_batch: int = 256,
              subset_tasks: int = 0, repeats: int = 3, seed: int = 0,
              json_path: str | None = None) -> dict:
    sc = fleet_scenario(masters, workers, seed=seed)
    # workload horizon estimate sizes the churn schedule; the sim stops at
    # max_tasks regardless, so an over-long schedule only leaves unused
    # events on the heap
    rates = [s.rate for s in poisson_sources(sc, utilization=utilization,
                                             seed=seed + 1)]
    horizon = 1.5 * tasks / max(sum(rates), 1e-12)
    churn = churn_schedule(horizon, workers, churn_period, seed=seed)
    subset = subset_tasks or max(min(tasks // 10, 100_000), 10_000)

    print(f"[fleet] M={masters} N={workers} tasks={tasks} "
          f"util={utilization} churn_events≈{len(churn)} "
          f"event_batch={event_batch} subset={subset}")

    # main run: batched loop + incremental repair, full task count
    ex, ms, wall = run_fleet(sc, tasks=tasks, utilization=utilization,
                             churn=churn, event_batch=event_batch,
                             mode="incremental", seed=seed)
    s = ms.summary()
    pl = ex.planner
    print(f"[fleet] main: {wall:.1f}s, "
          f"{ex.events_processed / wall:,.0f} events/s, "
          f"repairs={pl.repairs} full_solves={pl.full_solves} "
          f"fallbacks={pl.repair_fallbacks}")

    # Loop comparison on a common churn-free subset (identical runs but for
    # the batch).  Churn-free on purpose: both loops would pay the *same*
    # planner wall for every churn-forced solve, a shared constant that
    # compresses the events/s ratio toward 1 no matter how fast either loop
    # drains — planner cost is what replan_latency_ratio measures.  This
    # pair isolates the loop mechanics: heap ops, admission checks, delay
    # sampling, completion math.  Median of ``repeats`` walls.
    walls_b, walls_p = [], []
    for _ in range(max(repeats, 1)):
        exb, _, wall_b = run_fleet(sc, tasks=subset,
                                   utilization=utilization,
                                   churn=[], event_batch=event_batch,
                                   mode="incremental", seed=seed)
        walls_b.append(wall_b)
        exp, _, wall_p = run_fleet(sc, tasks=subset,
                                   utilization=utilization,
                                   churn=[], event_batch=1,
                                   mode="incremental", seed=seed)
        walls_p.append(wall_p)
    assert exb.events_processed == exp.events_processed
    evs_b = exb.events_processed / max(float(np.median(walls_b)), 1e-12)
    evs_p = exp.events_processed / max(float(np.median(walls_p)), 1e-12)

    # replan-latency comparison: full re-solve on the same churn schedule
    exa, _, _ = run_fleet(sc, tasks=subset, utilization=utilization,
                          churn=churn, event_batch=event_batch,
                          mode="always", seed=seed)
    repair_med = _q(pl.repair_wall, 0.5)
    solve_med = _q(exa.planner.solve_wall, 0.5)

    fleet = {
        "tasks": int(s["tasks_completed"]),
        "masters": masters,
        "workers": workers,
        "utilization": utilization,
        "event_batch": event_batch,
        "wall_seconds": round(wall, 2),
        "events_per_s": round(ex.events_processed / max(wall, 1e-12), 1),
        "events_per_s_batched": round(evs_b, 1),
        "events_per_s_per_event": round(evs_p, 1),
        "events_per_s_ratio": round(evs_b / max(evs_p, 1e-12), 2),
        "sojourn_p50_ms": round(s["sojourn_p50"], 3),
        "sojourn_p99_ms": round(s["sojourn_p99"], 3),
        "replan_latency_p50_ms": round(repair_med * 1e3, 3),
        "replan_latency_p99_ms": round(_q(pl.repair_wall, 0.99) * 1e3, 3),
        "full_solve_p50_ms": round(solve_med * 1e3, 3),
        "replan_latency_ratio": round(solve_med / max(repair_med, 1e-12), 2),
        "repairs": pl.repairs,
        "full_solves": pl.full_solves,
        "repair_fallbacks": pl.repair_fallbacks,
    }

    path = json_path or os.environ.get("REPRO_BENCH_JSON",
                                       "BENCH_stream.json")
    # merge: coded_exec_bench owns the top level of this JSON
    try:
        with open(path) as f:
            record = json.load(f)
    except (OSError, ValueError):
        record = {}
    record["fleet"] = fleet
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    emit("stream/fleet", wall * 1e6,
         f"events_per_s={fleet['events_per_s']};"
         f"events_per_s_ratio={fleet['events_per_s_ratio']};"
         f"replan_latency_ratio={fleet['replan_latency_ratio']};"
         f"sojourn_p99_ms={fleet['sojourn_p99_ms']};json={path}")
    return fleet


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--tasks", type=int, default=1_000_000)
    p.add_argument("--masters", type=int, default=1000)
    p.add_argument("--workers", type=int, default=128)
    p.add_argument("--utilization", type=float, default=0.15)
    p.add_argument("--churn-period", type=float, default=20000.0,
                   help="sim time between churn events")
    p.add_argument("--event-batch", type=int, default=256)
    p.add_argument("--subset-tasks", type=int, default=0,
                   help="task count of the comparison runs "
                        "(0 = tasks/10 clamped to [1e4, 1e5])")
    p.add_argument("--repeats", type=int, default=3,
                   help="loop-comparison repetitions (median wall)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--json", dest="json_path", default=None)
    args = p.parse_args(argv)
    enable_compile_cache()
    run_bench(tasks=args.tasks, masters=args.masters, workers=args.workers,
              utilization=args.utilization, churn_period=args.churn_period,
              event_batch=args.event_batch, subset_tasks=args.subset_tasks,
              repeats=args.repeats, seed=args.seed,
              json_path=args.json_path)
    return 0


if __name__ == "__main__":
    main()
