"""Coded serving benchmark: admission policies vs the FIFO baseline, and
coding scopes × shard-execution engines vs head-only.

Serves one seeded contended workload (more requests than batch slots,
mixed tight/loose deadlines, mid-run churn) through the coded serving
bridge under each admission policy and records tokens/s (simulation and
wall clock), p50/p99 request sojourn and the deadline-miss rate into
``BENCH_serve.json`` (env knob ``REPRO_BENCH_SERVE_JSON``), with the
EDF/fair numbers expressed relative to FIFO.

A second sweep serves the same workload once per ``coding_scope``
(head | ffn | trunk, default pool, EDF) × ``execution`` engine
(``serial`` shard-by-shard reference | ``batched`` packed step-barrier
passes).  Each cell reports two wall-clock numbers:

* ``tokens_per_wall_second`` — the *serving configuration* (``verify``
  off: no reference matmuls ride along; distributing the products is the
  point), best of ``--reps`` runs to damp CI-runner noise;
* a verification pass (``verify`` on, same workload) contributing
  ``decode_max_err`` / ``argmax_match_rate`` and asserting every decoded
  matmul matched the uncoded product bit-for-bit at the greedy argmax.

Headline ratios: ``trunk_wall_vs_head`` (batched trunk wall throughput
over batched head — the "Wall-clock shard execution" gap this records),
``batched_wall_speedup`` per scope (batched over serial), and the
sim-time ``trunk_throughput_vs_head``.

A final traced pass on the trunk/batched cell records the per-stage wall
breakdown (plan | pack | kernel | decode | glue), the straggler
attribution table and the disabled-tracer throughput ratio into the
JSON's ``trace`` section (``--trace out.json`` additionally writes the
Chrome/Perfetto trace itself).

A seeded chaos pass (``--faults SPEC``) then serves the same workload on
the trunk/batched cell under fault injection and records the ``faults``
section: corruption detection / localisation rates, quarantine and
readmission counts, the decode-mode histogram, the chaos token-match
rate against the clean serve (asserted 1.0 unless steps explicitly
degraded), plus the fault-free-schedule and LS-tail token-identity
checks CI floors at 1.0.

    PYTHONPATH=src python -m benchmarks.serve_bench \
        [--requests 24] [--gen-len 8] [--slots 2] [--rate 0.02] \
        [--backend numpy] [--steps-per-dispatch 1] [--reps 3] [--seed 0] \
        [--trace out.json] [--faults corrupt=0.25,kind=sign_flip,...]
"""
from __future__ import annotations

import argparse
import json
import os

from repro.launch.compile_cache import enable_compile_cache
from repro.serve_coded import (CODING_SCOPES, EXECUTION_MODES,
                               CodedServingBridge, serve_policy_sweep,
                               synthetic_requests)
from repro.stream import AdmissionConfig, StreamConfig, WorkerEvent

from .common import emit

POLICIES = ("fifo", "edf", "fair")


def _report_row(rep) -> dict:
    s = rep.summary()
    return {
        "tokens_per_sim_second": round(s["tokens_per_sim_second"], 2),
        "tokens_per_wall_second": round(s["tokens_per_wall_second"], 1),
        "p50_sojourn_ms": round(s.get("sojourn_p50", float("nan")), 1),
        "p99_sojourn_ms": round(s.get("sojourn_p99", float("nan")), 1),
        "deadline_miss_rate": round(s.get("deadline_miss_rate", 0.0), 4),
        "coded_steps": int(s["coded_steps"]),
        "solve_steps": int(s["solve_steps"]),
        "decode_max_err": rep.max_err,
        "wall_seconds": round(rep.wall_seconds, 3),
    }


def _default_churn():
    return [WorkerEvent(400.0, 2, "degrade", 4.0),
            WorkerEvent(1500.0, 5, "leave"),
            WorkerEvent(6000.0, 5, "join"),
            WorkerEvent(8000.0, 2, "restore")]


def run_serve_bench(requests: int = 24, gen_len: int = 8, masters: int = 2,
                    slots: int = 2, rate: float = 0.02, prompt_len: int = 16,
                    backend: str = "numpy", steps_per_dispatch: int = 1,
                    reps: int = 3, seed: int = 0,
                    trace: str | None = None,
                    faults: str = "corrupt=0.25,kind=sign_flip,crash=0.05,"
                                  "retries=4,seed=5",
                    json_path: str | None = None) -> dict:
    churn = _default_churn()
    per_policy = {}
    bridge = CodedServingBridge(masters=masters, backend=backend,
                                config=StreamConfig(rng=seed),
                                slots_per_master=slots,
                                steps_per_dispatch=steps_per_dispatch)
    bridge._setup_model(prompt_len + gen_len + 8)
    reqs = synthetic_requests(
        requests, masters=masters, vocab=bridge._model["cfg"].vocab,
        prompt_len=prompt_len, gen_len=gen_len, rate=rate, seed=seed)
    # discarded warmup rep: the first serve of the process pays jit
    # compilation, lazy parity encodes and allocator warmup — without it
    # the first timed cell (historically fifo) absorbed all of that and
    # the cross-policy wall ratios were skewed against it
    bridge.serve(reqs, churn=churn)
    reports = serve_policy_sweep(bridge, reqs, POLICIES, churn=churn)
    for policy, rep in reports.items():
        per_policy[policy] = _report_row(rep)

    # scope × execution sweep: same workload, same pool, EDF.  The wall
    # numbers come from the serving configuration (verify off — the
    # reference matmuls exist only for CI assertions); a separate
    # verified run per cell contributes decode_max_err and the argmax
    # assertion, so the JSON carries both honesty and throughput.
    per_scope: dict = {}
    cells = [(scope, execution) for scope in CODING_SCOPES
             for execution in EXECUTION_MODES]
    timers = {}
    for scope, execution in cells:
        vbridge = CodedServingBridge(
            masters=masters, backend=backend,
            config=StreamConfig(admission=AdmissionConfig(policy="edf"),
                                rng=seed),
            slots_per_master=slots, coding_scope=scope,
            steps_per_dispatch=steps_per_dispatch, execution=execution)
        vbridge._setup_model(prompt_len + gen_len + 8)
        vrep = vbridge.serve(reqs, churn=churn)
        assert vrep.decode_ok, (scope, execution, vrep.max_err)
        row = _report_row(vrep)
        row["verified_tokens_per_wall_second"] = \
            row.pop("tokens_per_wall_second")
        row["verified_wall_seconds"] = row.pop("wall_seconds")
        row["execution"] = execution
        row["decode_backend"] = vrep.decode_backend
        row["tasks_per_step"] = \
            int(vrep.steps[0]["n_tasks"]) if vrep.steps else 0
        per_scope.setdefault(scope, {})[execution] = row
        tbridge = CodedServingBridge(
            masters=masters, backend=backend,
            config=StreamConfig(admission=AdmissionConfig(policy="edf"),
                                rng=seed),
            slots_per_master=slots, coding_scope=scope,
            steps_per_dispatch=steps_per_dispatch, execution=execution,
            verify=False)
        tbridge._setup_model(prompt_len + gen_len + 8)
        trep = tbridge.serve(reqs, churn=churn)       # warm the engine
        assert trep.tokens == vrep.tokens    # engines + verify agree
        timers[(scope, execution)] = tbridge
        if (scope, execution) == ("trunk", "batched"):
            clean_tokens = {r: list(t) for r, t in vrep.tokens.items()}
    # serving-configuration timing, reps round-robined across the cells
    # so a noise burst on a shared CI runner degrades every cell alike —
    # the cross-scope wall ratios stay comparable even when absolute
    # throughput wobbles
    for _ in range(max(reps, 1)):
        for cell, tbridge in timers.items():
            trep = tbridge.serve(reqs, churn=churn)
            tps = trep.summary()["tokens_per_wall_second"]
            row = per_scope[cell[0]][cell[1]]
            if tps > row.get("tokens_per_wall_second", 0.0):
                row["tokens_per_wall_second"] = round(tps, 1)
                row["wall_seconds"] = round(trep.wall_seconds, 3)

    # observability: one traced pass on the trunk/batched serving cell
    # yields the per-stage wall breakdown (plan vs pack vs kernel vs
    # decode vs glue) and the straggler attribution table; paired
    # best-of-reps rounds — a *disabled* tracer attached vs no tracer,
    # interleaved so both sides see the same machine conditions — then
    # time the contract that disabled tracing serves on the identical
    # code path.  CI floors the ratio at 0.98 (< 2% disabled-mode
    # overhead); comparing against the earlier timing loop instead would
    # fold half the bench's worth of runner drift into the ratio.
    from repro.obs import Tracer
    json_out = json_path or os.environ.get("REPRO_BENCH_SERVE_JSON",
                                           "BENCH_serve.json")
    # the traced pass always runs — always write its artifact too, so the
    # JSON's trace.trace_path points at a real file instead of null
    # whenever --trace wasn't given
    if trace is None:
        trace = os.path.splitext(json_out)[0] + "_trace.json"
    tbridge = timers[("trunk", "batched")]
    tbridge.tracer = tracer = Tracer(meta={"bench": "coded_serving",
                                           "scope": "trunk",
                                           "execution": "batched"})
    traced_rep = tbridge.serve(reqs, churn=churn, trace_path=trace)
    ts = tracer.summary()
    best_disabled = off_best = 0.0
    for _ in range(max(reps, 1)):
        tbridge.tracer = Tracer(enabled=False)
        r = tbridge.serve(reqs, churn=churn)
        best_disabled = max(best_disabled,
                            r.summary()["tokens_per_wall_second"])
        tbridge.tracer = None
        r = tbridge.serve(reqs, churn=churn)
        off_best = max(off_best, r.summary()["tokens_per_wall_second"])
    cache_hits = ts["counters"].get("plan_cache_hits", 0.0)
    cache_misses = ts["counters"].get("plan_cache_misses", 0.0)
    trace_row = {
        "scope": "trunk", "execution": "batched",
        "per_stage_wall": {k: round(v, 6)
                           for k, v in ts["per_stage_wall"].items()},
        # steady-state step plans come from the StepPlanCache; misses only
        # on cold start and after churn/replan invalidations, so the rate
        # is a direct gauge of whether caching is actually engaged
        "plan_cache_hit_rate": round(
            cache_hits / max(cache_hits + cache_misses, 1.0), 4),
        "stage_coverage": None if ts["stage_coverage"] is None
        else round(ts["stage_coverage"], 4),
        "counters": {k: round(v, 1) for k, v in ts["counters"].items()},
        "stragglers": ts["stragglers"],
        "traced_tokens_per_wall_second": round(
            traced_rep.summary()["tokens_per_wall_second"], 1),
        "disabled_tracer_tokens_per_wall_second": round(best_disabled, 1),
        "tracing_off_throughput_ratio": round(
            best_disabled / max(off_best, 1e-12), 3),
        "trace_path": trace,
    }

    # chaos pass: a seeded fault schedule on the trunk/batched cell must
    # detect every applied corruption, quarantine the culprits and decode
    # back to the fault-free token stream (or explicitly degrade — never
    # silently wrong).  Three sub-checks feed the JSON's ``faults``
    # section: the chaos serve itself, the fault-free-schedule identity
    # (zero rates, detection armed) and the LS-tail decode parity.
    from repro.faults import FaultConfig, parse_fault_spec

    def _fault_bridge(**kw):
        fb = CodedServingBridge(
            masters=masters, backend=backend,
            config=StreamConfig(admission=AdmissionConfig(policy="edf"),
                                rng=seed),
            slots_per_master=slots, coding_scope="trunk",
            steps_per_dispatch=steps_per_dispatch, execution="batched",
            **kw)
        fb._setup_model(prompt_len + gen_len + 8)
        return fb

    def _tokens_match(rep) -> float:
        got = {r: list(t) for r, t in rep.tokens.items()}
        n = max(len(clean_tokens), 1)
        return sum(1 for r, t in clean_tokens.items()
                   if got.get(r) == t) / n

    frep = _fault_bridge(faults=parse_fault_spec(faults)).serve(
        reqs, churn=churn)
    fstat = frep.faults or {}
    fmodes = frep.decode_modes or {}
    degraded = int(fmodes.get("degraded", 0))
    chaos_match = _tokens_match(frep)
    # never silently wrong: every token either matches the clean serve or
    # came from a step explicitly reported as degraded
    assert chaos_match == 1.0 or degraded > 0, (chaos_match, fmodes)
    zrep = _fault_bridge(faults=FaultConfig(seed=seed)).serve(
        reqs, churn=churn)
    lrep = _fault_bridge(ls_tail=True).serve(reqs, churn=churn)
    faults_row = {
        "spec": faults,
        "scope": "trunk", "execution": "batched",
        "fault_free_token_identity": _tokens_match(zrep),
        "ls_tail_token_identity": _tokens_match(lrep),
        "token_match_rate": round(chaos_match, 4),
        "detection_rate": round(fstat.get("detection_rate", 1.0), 4),
        "localization_rate": round(fstat.get("localization_rate", 1.0), 4),
        "injected": int(fstat.get("injected", 0)),
        "corrupt_applied": int(fstat.get("corrupt_applied", 0)),
        "quarantines": int(fstat.get("quarantines", 0)),
        "readmissions": int(fstat.get("readmissions", 0)),
        "retries": int(fstat.get("retries", 0)),
        "rows_rejected": int(fstat.get("rows_rejected", 0)),
        "false_flags": int(fstat.get("false_flags", 0)),
        "degraded_steps": degraded,
        "decode_modes": fmodes,
    }

    base = per_policy["fifo"]
    head_b = per_scope["head"]["batched"]
    trunk_b = per_scope["trunk"]["batched"]
    record = {
        "bench": "coded_serving_policies",
        "requests": requests,
        "gen_len": gen_len,
        "masters": masters,
        "slots_per_master": slots,
        "backend": backend,
        "steps_per_dispatch": steps_per_dispatch,
        "timing_reps": reps,
        "baseline": "fifo",
        "policies": per_policy,
        "edf_miss_vs_fifo": round(
            per_policy["edf"]["deadline_miss_rate"]
            / max(base["deadline_miss_rate"], 1e-12), 3),
        "fair_throughput_vs_fifo": round(
            per_policy["fair"]["tokens_per_sim_second"]
            / max(base["tokens_per_sim_second"], 1e-12), 3),
        "scopes": per_scope,
        "trunk_throughput_vs_head": round(
            trunk_b["tokens_per_sim_second"]
            / max(head_b["tokens_per_sim_second"], 1e-12), 3),
        "trunk_wall_vs_head": round(
            trunk_b["tokens_per_wall_second"]
            / max(head_b["tokens_per_wall_second"], 1e-12), 3),
        "batched_wall_speedup": {
            scope: round(per_scope[scope]["batched"]
                         ["tokens_per_wall_second"]
                         / max(per_scope[scope]["serial"]
                               ["tokens_per_wall_second"], 1e-12), 3)
            for scope in CODING_SCOPES},
        "trace": trace_row,
        "faults": faults_row,
    }
    path = json_out
    with open(path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    emit("serve/coded_policies", base["wall_seconds"] * 1e6,
         f"fifo_tok_per_sim_s={base['tokens_per_sim_second']};"
         f"edf_miss_vs_fifo={record['edf_miss_vs_fifo']};"
         f"fair_throughput_vs_fifo={record['fair_throughput_vs_fifo']};"
         f"trunk_vs_head={record['trunk_throughput_vs_head']};"
         f"trunk_wall_vs_head={record['trunk_wall_vs_head']};"
         f"batched_speedup_trunk="
         f"{record['batched_wall_speedup']['trunk']};"
         f"plan_cache_hit_rate={trace_row['plan_cache_hit_rate']};"
         f"stage_coverage={trace_row['stage_coverage']};"
         f"tracing_off_ratio="
         f"{trace_row['tracing_off_throughput_ratio']};"
         f"fault_detection={faults_row['detection_rate']};"
         f"fault_token_match={faults_row['token_match_rate']};"
         f"json={path}")
    return record


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--requests", type=int, default=24)
    p.add_argument("--gen-len", type=int, default=8)
    p.add_argument("--masters", type=int, default=2)
    p.add_argument("--slots", type=int, default=2)
    p.add_argument("--rate", type=float, default=0.02)
    p.add_argument("--backend", default="numpy",
                   choices=("numpy", "jax", "pallas"))
    p.add_argument("--steps-per-dispatch", type=int, default=1)
    p.add_argument("--reps", type=int, default=3,
                   help="timing repetitions per cell (best wall wins)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--trace", default=None, metavar="OUT.json",
                   help="write the traced trunk/batched pass's "
                        "Chrome/Perfetto trace here")
    p.add_argument("--faults",
                   default="corrupt=0.25,kind=sign_flip,crash=0.05,"
                           "retries=4,seed=5",
                   metavar="SPEC",
                   help="chaos-pass fault spec (repro.faults."
                        "parse_fault_spec syntax; 'none' = zero rates "
                        "with detection armed)")
    args = p.parse_args(argv)
    enable_compile_cache()
    run_serve_bench(requests=args.requests, gen_len=args.gen_len,
                    masters=args.masters, slots=args.slots, rate=args.rate,
                    backend=args.backend,
                    steps_per_dispatch=args.steps_per_dispatch,
                    reps=args.reps, seed=args.seed, trace=args.trace,
                    faults=args.faults)


if __name__ == "__main__":
    main()
