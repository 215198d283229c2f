"""Tracing inside a coded serve: the detail lane, parent links, the decode
and parity counters, the profiler annotations and the per-step hook.

A tiny coded-head serve on the ``jax`` backend runs the same decode
engine as the Pallas cells (``jax:cpu``: float64 LU factors cached per
frozen block, one LAPACK solve a step), so the sub-spans and counters
under test are the ones the benchmark's cells record.
"""
import glob

import numpy as np
import pytest

from repro.obs import DETAIL_TRACK, Tracer
from repro.serve_coded import CodedServingBridge, StepInfo, synthetic_requests
from repro.serve_coded.bridge import _fill_glue
from repro.stream import AdmissionConfig
from repro.stream import backend as bk


def _bridge(backend="jax", gen=4, **kw):
    b = CodedServingBridge(masters=2, seed=0, slots_per_master=2,
                           coding_scope="head", backend=backend,
                           admission=AdmissionConfig(policy="edf"), **kw)
    b._setup_model(16 + gen + 8)
    return b


def _reqs(b, gen=4):
    return synthetic_requests(4, masters=2, vocab=b._model["cfg"].vocab,
                              prompt_len=16, gen_len=gen, rate=0.02, seed=0)


def _counting_solves(monkeypatch):
    """Record the shape of every ``StackedLU.solve`` right-hand side, and
    every system LAPACK actually factorises, in a dict of two lists."""
    seen = {"solves": [], "factorised": []}
    solve, factor = bk.StackedLU.solve, bk._lu_factor

    def counting_solve(self, rhs):
        seen["solves"].append(rhs.shape[:2])
        return solve(self, rhs)

    def counting_factor(a, **kw):
        seen["factorised"].append(a.shape)
        return factor(a, **kw)
    monkeypatch.setattr(bk.StackedLU, "solve", counting_solve)
    monkeypatch.setattr(bk, "_lu_factor", counting_factor)
    return seen


@pytest.fixture(scope="module")
def traced():
    """One traced serve, with every solve of the packed decode and every
    factorisation it does counted, and every ``on_step`` call kept."""
    b = _bridge()
    reqs = _reqs(b)
    infos = []
    with pytest.MonkeyPatch.context() as mp:
        seen = _counting_solves(mp)
        b.tracer = tr = Tracer()
        rep = b.serve(reqs, on_step=infos.append)
    return tr, rep, seen, infos


def _descendants(tr, root):
    kids = {}
    for sp in tr.spans:
        kids.setdefault(sp.parent, []).append(sp)
    out, todo = [], [root.seq]
    while todo:
        for sp in kids.get(todo.pop(), []):
            out.append(sp)
            todo.append(sp.seq)
    return out


def test_detail_spans_leave_stage_rollup_and_glue_unchanged(traced):
    tr, _rep, _seen, _infos = traced
    detail = [sp for sp in tr.spans if sp.track == DETAIL_TRACK]
    names = {sp.name for sp in detail}
    assert names >= {
        "decode.rhs", "decode.solve", "decode.scatter",
        "trunk.decode", "trunk.prefill", "parity.derive", "parity.cond"}
    # the packed decode solves on the host: nothing is put on a device
    assert "decode.put" not in names
    plain = Tracer()
    plain.spans = [sp for sp in tr.spans if sp.track != DETAIL_TRACK]
    assert plain.summary()["per_stage_wall"] == \
        tr.summary()["per_stage_wall"]
    steps = [sp for sp in tr.spans
             if sp.cat == "step" and sp.name.startswith("step:")]
    assert steps
    for step in steps:
        leaves = [sp for sp in _descendants(tr, step) if sp.cat != "glue"]
        recorded = sorted((g.t0, g.t1) for g in tr.spans
                          if g.cat == "glue" and g.parent == step.seq)
        for keep in (True, False):
            fake = Tracer()
            fake.spans = [sp for sp in leaves
                          if keep or sp.track != DETAIL_TRACK] + [step]
            _fill_glue(fake, 0)
            assert sorted((g.t0, g.t1) for g in fake.spans
                          if g.cat == "glue") == recorded


def test_parent_links_form_one_tree_rooted_at_serve(traced):
    tr, _rep, _seen, _infos = traced
    by_seq = {sp.seq: sp for sp in tr.spans}
    roots = [sp for sp in tr.spans if sp.parent is None]
    assert [(sp.name, sp.cat) for sp in roots] == [("serve", "run")]
    for sp in tr.spans + tr.instants:
        seen, cur = set(), sp
        while cur.parent is not None:
            assert cur.seq not in seen
            seen.add(cur.seq)
            cur = by_seq[cur.parent]
        assert cur is roots[0]
        par = by_seq.get(sp.parent)
        if par is not None and sp.track.startswith("wall"):
            assert par.t0 <= sp.t0 and sp.t1 <= par.t1, (sp, par)
    # no decode-stage span opens inside another: the stage rollup counts
    # each decode once
    for sp in tr.spans:
        if sp.track == "wall" and sp.cat == "decode":
            cur = by_seq.get(sp.parent)
            while cur is not None:
                assert not (cur.track == "wall" and cur.cat == "decode")
                cur = by_seq.get(cur.parent)
    # the step spans name the requests they served
    served = [sp.args["rids"] for sp in tr.spans
              if sp.cat == "step" and sp.name.startswith("step:")]
    assert all(served) and set(sum(served, [])) == set(_rep.tokens)


def test_decode_counters_match_the_solves(traced):
    tr, _rep, seen, _infos = traced
    calls = seen["solves"]
    assert calls, "the jax engine solved no system"
    c = tr.counters
    assert c["decode_lu_factorizations"] == len(seen["factorised"]) > 0
    assert c["decode_system_rows"] == sum(g * n for g, n in calls)
    solves = [sp for sp in tr.spans if sp.name == "decode.solve"]
    assert len(solves) == len(calls)
    assert sum(sp.args["systems"] * sp.args["order"] for sp in solves) \
        == c["decode_system_rows"]
    # the first serve derives its parity blocks, each guard evaluated
    assert c["parity_blocks_derived"] == sum(
        1 for sp in tr.spans if sp.name == "parity.derive")
    assert sum(1 for sp in tr.spans if sp.name == "parity.cond") \
        == c["parity_blocks_derived"] + c.get("parity_redraws", 0)


@pytest.mark.parametrize("backend", ("numpy", "pallas"))
def test_engine_reuses_factors_on_a_current_plan_cache(backend):
    b = _bridge(backend=backend)
    reqs = _reqs(b)
    b.tracer = first = Tracer()
    b.serve(reqs)
    assert first.counters["decode_lu_factorizations"] > 0
    b.tracer = again = Tracer()
    rep = b.serve(reqs)
    assert rep.plan_cache_misses == 0
    assert again.counters["decode_system_rows"] > 0
    assert again.counters.get("decode_lu_factorizations", 0) == 0
    # the parity blocks are memoised: nothing is derived again
    assert again.counters.get("parity_blocks_derived", 0) == 0


def test_pallas_engine_factorises_every_step_without_a_plan_cache(
        monkeypatch):
    b = _bridge(backend="pallas", plan_cache=False)
    seen = _counting_solves(monkeypatch)
    b.tracer = tr = Tracer()
    rep = b.serve(_reqs(b))
    assert rep.plan_cache_misses == 0 and rep.plan_cache_hits == 0
    # a fresh structure every step: each solve factorises its systems
    solved = {sp.parent for sp in tr.spans if sp.name == "decode.solve"}
    assert len(solved) > 1
    assert len(seen["factorised"]) == sum(g for g, _n in seen["solves"])
    assert tr.counters["decode_lu_factorizations"] == \
        len(seen["factorised"])


def test_on_step_sees_every_step_of_the_step_log(traced):
    tr, rep, _seen, infos = traced
    assert all(isinstance(i, StepInfo) for i in infos)
    assert [i.master for i in infos] == \
        [sp.args["master"] for sp in tr.spans
         if sp.cat == "step" and sp.name.startswith("step:")]
    assert sorted(i.master for i in infos) == \
        sorted(s["master"] for s in rep.steps)
    assert sum(len(i.rids) for i in infos) == \
        sum(s["batch"] for s in rep.steps)
    # each request is prefilled once, at its prompt length, then its
    # positions count up by one per step
    seen = {}
    for i in infos:
        for rid, pos, pre in zip(i.rids, i.positions, i.prefill):
            assert pre == (rid not in seen)
            assert pos == (16 if pre else seen[rid] + 1)
            seen[rid] = pos
    assert {rid: len(t) for rid, t in rep.tokens.items()} == \
        {rid: pos - 15 for rid, pos in seen.items()}


def test_raising_from_on_step_ends_the_serve():
    class Stop(Exception):
        pass

    b = _bridge()
    calls = []

    def hook(info):
        calls.append(info)
        if len(calls) == 3:
            raise Stop()
    with pytest.raises(Stop):
        b.serve(_reqs(b), on_step=hook)
    assert len(calls) == 3


def test_profiler_host_plane_holds_the_context_spans_nested(tmp_path):
    import jax
    b = _bridge(gen=3)
    reqs = _reqs(b, gen=3)
    b.serve(reqs)                                # compile outside the trace
    b.tracer = tr = Tracer(jax_profiler=True)
    po = jax.profiler.ProfileOptions()
    po.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=po):
        b.serve(reqs)
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    # the context spans: every wall-domain span but the backfilled glue
    ctx = [sp for sp in tr.spans
           if sp.track.startswith("wall") and sp.cat != "glue"]
    names = {sp.name for sp in ctx}
    events = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        events.setdefault(e.name, []).append(
                            (e.start_ns, e.end_ns))
    # one host event per span, matched in start order
    ev_of = {}
    for name in names:
        spans = sorted((sp for sp in ctx if sp.name == name),
                       key=lambda sp: sp.t0)
        evs = sorted(events.get(name, []))
        assert len(evs) == len(spans), name
        ev_of.update({sp.seq: ev for sp, ev in zip(spans, evs)})
    for sp in ctx:
        if sp.parent in ev_of:
            (a, b_), (pa, pb) = ev_of[sp.seq], ev_of[sp.parent]
            assert pa <= a and b_ <= pb, (sp.name, sp.parent)
    assert any(n.startswith("step:m") for n in events)
    assert np.isfinite(tr.summary()["per_cat_wall"]["decode.solve"])
