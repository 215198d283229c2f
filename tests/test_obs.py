"""Observability: tracer core, exporters, engine/bridge integration, and
the NaN-safe metrics guard that rides along.

The tracer's contracts under test:

* span nesting/ordering is deterministic — children append before their
  parent (exit order), sequence numbers strictly increase, parents cover
  their children's intervals;
* a *disabled* tracer is indistinguishable from no tracer (normalises to
  None at every entry point) and costs < 2% on a 1k-task stream run;
* the Chrome-trace export round-trips through ``json.loads`` and every
  event carries the required ``name/ph/ts/pid/tid`` keys (``dur`` on
  complete events), with wall and sim time as separate pid groups.
"""
import gc
import json
import time

import numpy as np
import pytest

from repro.core.problem import Scenario
from repro.obs import (STAGE_CATS, Tracer, check_trace, current_tracer,
                       device_span, use_tracer)
from repro.stream import (BackendConfig, PoissonProcess, StreamConfig,
                          StreamingExecutor, WorkerEvent)
from repro.stream.metrics import StreamMetrics, TaskRecord


def _scenario(M=2, N=8, L=96.0, seed=3):
    rng = np.random.default_rng(seed)
    a = np.zeros((M, N + 1))
    a[:, 0] = 0.5
    a[:, 1:] = rng.uniform(0.2, 0.4, size=(M, N))
    return Scenario(a=a, u=1 / a, gamma=2 / a, L=np.full(M, L))


def _run_stream(tracer, max_tasks=40, churn=(), numerics="none"):
    sc = _scenario()
    srcs = [PoissonProcess(m, rate=0.05, seed=1) for m in range(sc.M)]
    cfg = StreamConfig(rng=7, backend=BackendConfig(numerics=numerics))
    ex = StreamingExecutor(sc, srcs, config=cfg, churn=churn, tracer=tracer)
    return ex.run(max_tasks=max_tasks)


# ---------------------------------------------------------------------------
# Tracer core
# ---------------------------------------------------------------------------

def test_span_nesting_and_ordering_deterministic():
    tr = Tracer()
    with tr.span("outer", cat="step"):
        with tr.span("inner_a", cat="plan"):
            pass
        with tr.span("inner_b", cat="decode") as a:
            a["note"] = 1
    assert [s.name for s in tr.spans] == ["inner_a", "inner_b", "outer"]
    seqs = [s.seq for s in tr.spans]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    outer = tr.spans[-1]
    for child in tr.spans[:-1]:
        assert outer.t0 <= child.t0 <= child.t1 <= outer.t1
    assert tr.spans[1].args == {"note": 1}
    # same structure twice -> same names/cats/ordering (timestamps differ)
    tr2 = Tracer()
    with tr2.span("outer", cat="step"):
        with tr2.span("inner_a", cat="plan"):
            pass
        with tr2.span("inner_b", cat="decode"):
            pass
    assert [(s.name, s.cat) for s in tr.spans] == \
        [(s.name, s.cat) for s in tr2.spans]


def test_parent_links_follow_the_open_span():
    tr = Tracer()
    with tr.span("outer", cat="step"):
        with tr.span("inner", cat="plan"):
            tr.add_span("measured", tr.now(), tr.now(), cat="decode",
                        track="wall")
            tr.instant("mark")
        tr.add_span("pinned", 0.0, 1.0, parent=7)
    measured, inner, pinned, outer = tr.spans
    assert outer.parent is None
    assert inner.parent == outer.seq and measured.parent == inner.seq
    assert tr.instants[0].parent == inner.seq
    assert pinned.parent == 7                    # explicit parent kept
    ev = {e["name"]: e for e in tr.to_chrome_trace()["traceEvents"]
          if e["ph"] == "X"}
    assert ev["inner"]["args"]["parent"] == outer.seq
    assert ev["outer"]["args"]["seq"] == outer.seq
    assert ev["outer"]["args"]["parent"] is None
    assert {r["name"]: r["parent"] for r in tr.to_records()}["inner"] \
        == outer.seq


def test_summary_per_cat_wall_covers_every_wall_lane():
    tr = Tracer()
    tr.add_span("a", 0.0, 1.0, cat="decode", track="wall")
    tr.add_span("b", 0.25, 0.75, cat="decode.solve", track="wall:detail")
    tr.add_span("c", 0.0, 5.0, cat="decode.solve", track="sim")
    s = tr.summary()
    assert s["per_cat_wall"] == {"decode": 1.0, "decode.solve": 0.5}
    # the detail lane's category is not a stage: the rollup is unchanged
    assert s["per_stage_wall"]["decode"] == 1.0
    assert s["stage_wall_total"] == 1.0


def test_add_span_sanitizes_endpoints():
    tr = Tracer()
    assert tr.add_span("nan", float("nan"), 1.0) is None
    assert tr.add_span("inf", 0.0, float("inf")) is None
    sp = tr.add_span("rev", 2.0, 1.0)            # reversed endpoints swap
    assert (sp.t0, sp.t1) == (1.0, 2.0) and sp.dur == 1.0
    assert len(tr.spans) == 1


def test_disabled_tracer_records_nothing_and_normalizes_to_none():
    tr = Tracer(enabled=False)
    with tr.span("s", cat="plan"):
        tr.count("c")
        tr.gauge("g", 3.0)
        tr.instant("i")
        tr.add_span("a", 0.0, 1.0)
    assert not tr.spans and not tr.instants and not tr.counters
    with use_tracer(tr) as t:
        assert t is None and current_tracer() is None
    with use_tracer(Tracer()) as t:
        assert current_tracer() is t
    assert current_tracer() is None              # restored on exit


def test_device_span_no_tracer_passthrough():
    x = object()
    with device_span("k", cat="kernel") as fence:
        assert fence(x) is x                     # untouched when off


def test_counters_and_gauges_accumulate():
    tr = Tracer()
    tr.count("hits")
    tr.count("hits", 2)
    tr.gauge("depth", 5.0, t=10.0, track="sim")
    tr.gauge("depth", 2.0, t=20.0, track="sim")
    assert tr.counters["hits"] == 3.0
    assert tr.counters["depth"] == 2.0           # gauge = last level
    assert len(tr.counter_samples) == 4


# ---------------------------------------------------------------------------
# Chrome-trace export
# ---------------------------------------------------------------------------

def test_chrome_trace_roundtrips_with_required_keys(tmp_path):
    tr = Tracer(meta={"case": "roundtrip"})
    _run_stream(tr, max_tasks=20, numerics="verify",
                churn=[WorkerEvent(50.0, 2, "degrade", 3.0)])
    path = tmp_path / "trace.json"
    tr.write(str(path))
    obj = json.loads(path.read_text())           # round-trips through JSON
    events = obj["traceEvents"]
    assert events
    for ev in events:
        for key in ("name", "ph", "ts", "pid", "tid"):
            assert key in ev, ev
        if ev["ph"] == "X":
            assert "dur" in ev and ev["dur"] >= 0.0
    # both clocks present as distinct pid groups (1 = wall, 2 = sim)
    pids = {ev["pid"] for ev in events if ev["ph"] != "M"}
    assert pids >= {1, 2}
    # per-worker sim lanes became threads with metadata names
    names = {ev["args"]["name"] for ev in events
             if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert any(n.startswith("worker") for n in names)
    ok, problems = check_trace(obj)
    assert ok, problems


def test_check_trace_flags_broken_files():
    ok, problems = check_trace({"traceEvents": []})
    assert not ok and problems
    ok, problems = check_trace(
        {"traceEvents": [{"name": "x", "ph": "X", "ts": 0.0}]})
    assert not ok                                # missing pid/tid/dur


def test_summary_rolls_stages_counters_and_stragglers():
    tr = Tracer()
    ms = _run_stream(tr, max_tasks=30, numerics="verify",
                     churn=[WorkerEvent(40.0, 1, "leave")])
    s = tr.summary(top_k=3)
    assert set(s["per_stage_wall"]) == set(STAGE_CATS)
    assert s["span_count"] == len(tr.spans)
    assert tr.counters.get("churn_retimes", 0) >= 0
    assert s["stragglers"], "delivery spans should yield a straggler table"
    top = s["stragglers"][0]
    assert {"worker", "task", "sim_duration", "critical"} <= set(top)
    durs = [row["sim_duration"] for row in s["stragglers"]]
    assert durs == sorted(durs, reverse=True)
    assert ms.summary()["tasks_completed"] == 30


# ---------------------------------------------------------------------------
# Disabled-mode overhead (the contract the whole design hangs on)
# ---------------------------------------------------------------------------

def test_disabled_tracer_overhead_under_2pct_on_1k_task_stream():
    """An attached-but-disabled tracer must serve the identical code path:
    best-of-5 wall time within 2% (plus a small absolute slack for timer
    granularity) of the no-tracer run on a 1k-task stream.  The two arms
    alternate run by run, each going first in every other pair, so a load
    change on a shared host (other test workers) falls on both arms alike
    instead of on one; a full collection before each run keeps the garbage
    of earlier tests from landing on one arm."""
    def timed(tracer):
        gc.collect()
        t0 = time.perf_counter()
        _run_stream(tracer, max_tasks=1000)
        return time.perf_counter() - t0

    timed(None)                                  # warm caches/jit once
    best = {"none": float("inf"), "disabled": float("inf")}
    for i in range(5):
        arms = ("none", "disabled") if i % 2 == 0 else ("disabled", "none")
        for arm in arms:
            t = timed(None if arm == "none" else Tracer(enabled=False))
            best[arm] = min(best[arm], t)
    t_none, t_disabled = best["none"], best["disabled"]
    assert t_disabled <= t_none * 1.02 + 0.05, (t_disabled, t_none)


# ---------------------------------------------------------------------------
# Engine integration: spans in both time domains
# ---------------------------------------------------------------------------

def test_engine_emits_sim_and_wall_spans_side_by_side():
    tr = Tracer()
    _run_stream(tr, max_tasks=25, numerics="verify",
                churn=[WorkerEvent(60.0, 2, "degrade", 2.0)])
    cats = {s.cat for s in tr.spans}
    assert {"run", "task", "delivery", "verify"} <= cats
    tracks = {s.track for s in tr.spans}
    assert "wall" in tracks
    assert any(t.startswith("sim:worker") for t in tracks)
    # every task's service span contains its per-worker delivery spans
    service = {s.args["task"]: s for s in tr.spans if s.cat == "task"}
    for d in (s for s in tr.spans if s.cat == "delivery"):
        sv = service[d.args["task"]]
        assert sv.t0 <= d.t0 and (not d.args["delivered"]
                                  or d.t1 <= sv.t1 + 1e-9)
    # a critical (prefix-closing) delivery is attributed per completed task
    # (>= 1: simultaneous finishes can tie on the closing timestamp)
    for tid in service:
        crit = [d for d in tr.spans if d.cat == "delivery"
                and d.args["task"] == tid and d.args["critical"]]
        assert len(crit) >= 1, tid


def test_flat_records_export_is_pandas_ready():
    tr = Tracer()
    _run_stream(tr, max_tasks=10)
    rows = tr.to_records()
    assert rows and all(isinstance(r, dict) for r in rows)
    base_keys = {"seq", "kind", "name", "cat", "track", "t0", "t1", "dur"}
    assert all(base_keys <= set(r) for r in rows
               if r["kind"] in ("span", "instant"))
    seqs = [r["seq"] for r in rows if "seq" in r]
    assert seqs == sorted(seqs)


# ---------------------------------------------------------------------------
# StreamMetrics NaN-safety (satellite regression)
# ---------------------------------------------------------------------------

def test_metrics_summary_empty_pool_is_nan_free():
    ms = StreamMetrics(2, 4)
    s = ms.summary()
    for key, val in s.items():
        assert np.isfinite(val), (key, val)
    assert s["tasks_completed"] == 0.0
    assert s["utilization_mean"] == 0.0 and s["utilization_max"] == 0.0
    assert (ms.utilization() == 0.0).all()


def test_metrics_summary_partial_records_omit_unfinished_keys():
    ms = StreamMetrics(1, 2)
    # a record that never completed: NaN completion, no admit time
    r = TaskRecord(tid=0, master=0, t_arrive=1.0)
    ms.record_unserved(r)
    # one real completion with no queue wait recorded
    done = TaskRecord(tid=1, master=0, t_arrive=0.0)
    done.t_admit = np.nan
    done.t_complete = 5.0
    ms.record_task(done)
    s = ms.summary()
    for key, val in s.items():
        assert np.isfinite(val), (key, val)
    assert "queue_wait_mean" not in s            # omitted, not NaN
    assert s["tasks_completed"] == 1.0
