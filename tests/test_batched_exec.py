"""Device-resident batched shard execution: bit-identity and structure.

The batched engine (persistent encoded caches → ragged-shard packing →
one pass per dependency stage → stacked grouped decode, executed once at
barrier completion) must be *bit-identical* to the serial shard-by-shard
reference on numpy — same shard products, same decoded outputs, same
greedy tokens — and token-identical on every backend.  These tests pin
that, plus the satellite fixes that ride along: explicit decode-backend
routing, the parity-generator conditioning guard, the per-scope decode
error bound, and the per-execution-mode bench schema.
"""
import json
import pathlib

import numpy as np
import pytest

from repro.core import mds
from repro.serve_coded import (CODING_SCOPES, CodedLinear,
                               CodedServingBridge, PackedStage,
                               ShardProblem, synthetic_requests)
from repro.serve_coded.coded_linear import shard_products
from repro.stream import AdmissionConfig, WorkerEvent
from repro.stream import backend as bk

jax = pytest.importorskip("jax")


def _serve(scope, *, execution="batched", coded=True, backend="numpy",
           steps=1, churn=(), n=4, gen=3, seed=0, **kw):
    bridge = CodedServingBridge(
        masters=2, seed=seed, slots_per_master=2, coding_scope=scope,
        steps_per_dispatch=steps, backend=backend, coded=coded,
        execution=execution, admission=AdmissionConfig(policy="edf"), **kw)
    bridge._setup_model(16 + gen + 8)
    reqs = synthetic_requests(
        n, masters=2, vocab=bridge._model["cfg"].vocab, prompt_len=16,
        gen_len=gen, rate=0.02, seed=seed)
    return bridge.serve(reqs, churn=churn)


def _ragged_problems(rng, D=24, Ls=(48, 48, 96)):
    """Linears + prefix plans across ragged shard splits, mixed
    systematic/parity prefixes (incl. 0 < s < L substitution groups)."""
    problems, linears, plans = [], [], []
    for i, L in enumerate(Ls):
        lin = CodedLinear(rng.normal(size=(L, D)), name=f"m{i}", seed=i)
        l_int = np.array([0, L // 3, L // 2, L // 2, L])       # Σ > L
        finish = rng.permutation(np.arange(5).astype(float) + 1.0)
        finish[0] = np.inf
        plan = lin.prefix_plan(l_int, finish, t_complete=5.0)
        problems.append(ShardProblem(key=f"m{i}", linear=lin,
                                     rows=plan.rows,
                                     used_solve=plan.used_solve))
        linears.append(lin)
        plans.append((l_int, finish, plan))
    return problems, linears, plans


# ---------------------------------------------------------------------------
# Packed execution == serial execution, bit for bit (numpy)
# ---------------------------------------------------------------------------

def test_packed_shard_products_bit_identical_to_serial():
    rng = np.random.default_rng(0)
    problems, linears, plans = _ragged_problems(rng)
    X = rng.normal(size=(5, 24))
    stage = PackedStage(problems)
    packed = {p.key: y for p, y in zip(
        stage.problems, stage.pack.products(X))}
    for p, lin, (l_int, finish, plan) in zip(problems, linears, plans):
        enc = lin._enc[:lin._n_enc]
        serial = np.concatenate([shard_products(enc[sl], X)
                                 for sl in plan.slices])
        assert (packed[p.key] == serial).all()          # exact, not close


def test_packed_stage_decode_bit_identical_to_serial_step():
    rng = np.random.default_rng(1)
    problems, linears, plans = _ragged_problems(rng)
    X = rng.normal(size=(3, 24))
    outs = PackedStage(problems).execute(X)
    any_solve = False
    for p, lin, (l_int, finish, plan) in zip(problems, linears, plans):
        res = lin.step(X, l_int, finish, 5.0)
        assert (outs[p.key] == res.out).all()           # exact, not close
        np.testing.assert_allclose(outs[p.key], X @ lin.W.T, atol=1e-8)
        any_solve |= res.used_solve
    assert any_solve                       # prefixes did hit the solve path


def test_bridge_batched_vs_serial_bit_identical_per_scope():
    for scope in CODING_SCOPES:
        ser = _serve(scope, execution="serial")
        bat = _serve(scope, execution="batched")
        assert bat.tokens == ser.tokens
        assert bat.max_err == ser.max_err, scope     # decodes match in bits
        assert [s["t_done"] for s in bat.steps] == \
            [s["t_done"] for s in ser.steps]         # identical scheduling
        assert bat.execution == "batched" and ser.execution == "serial"
        assert all(s["execution"] == "batched" for s in bat.steps)


@pytest.mark.parametrize("backend", ("jax", "pallas"))
def test_bridge_batched_tokens_match_uncoded_on_device_backends(backend):
    bat = _serve("trunk", backend=backend)
    plain = _serve("trunk", coded=False, backend=backend)
    assert bat.tokens == plain.tokens
    assert bat.decode_ok, bat.max_err


def test_batched_churn_mass_leave_redispatch_matches_serial():
    churn = [WorkerEvent(60.0, w, "leave") for w in range(1, 9)]
    bat = _serve("trunk", churn=churn)
    ser = _serve("trunk", execution="serial", churn=churn)
    plain = _serve("trunk", coded=False, churn=churn)
    assert bat.redispatches > 0
    assert bat.tokens == ser.tokens == plain.tokens
    assert bat.decode_ok


def test_pallas_frozen_factors_decode_bit_identical_to_refactorising():
    """A frozen packed stage factorises its parity blocks once and solves
    every later step with the cached factors: the decoded logits and the
    tokens are bit-identical to factorising afresh on every step."""

    class Fresh(bk.StackedLU):
        __slots__ = ("keep",)

        def __init__(self, A):
            super().__init__(A)
            self.keep = A

        def solve(self, b):
            self.A, self._fac, self._checked = self.keep, None, False
            return super().solve(b)

    def run(lu_cls):
        logits, factorised = [], []
        execute, factor = PackedStage.execute, bk._lu_factor

        def keep(self, X, **kw):
            out = execute(self, X, **kw)
            logits.append({k: v.copy() for k, v in out.items()})
            return out

        def counting(a, **kw):
            factorised.append(a.shape)
            return factor(a, **kw)
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(bk, "StackedLU", lu_cls)
            mp.setattr(bk, "_lu_factor", counting)
            mp.setattr(PackedStage, "execute", keep)
            rep = _serve("head", backend="pallas", n=4, gen=4)
        return rep, logits, len(factorised)

    reused, reused_logits, n_reused = run(bk.StackedLU)
    fresh, fresh_logits, n_fresh = run(Fresh)
    assert 0 < n_reused < n_fresh          # the replayed steps reused
    assert reused.plan_cache_hits > 0
    assert reused.tokens == fresh.tokens
    assert reused.max_err == fresh.max_err
    assert len(reused_logits) == len(fresh_logits)
    for a, b in zip(reused_logits, fresh_logits):
        assert a.keys() == b.keys()
        for k in a:
            assert (a[k] == b[k]).all(), k           # exact, not close


def test_batched_multi_token_dispatch_reuses_plans():
    b4 = _serve("trunk", steps=4, n=4, gen=4)
    s4 = _serve("trunk", execution="serial", steps=4, n=4, gen=4)
    assert b4.tokens == s4.tokens
    assert b4.tokens_generated == 16


def test_batched_slots_admitted_mid_flight_wait_for_next_dispatch():
    """Deferred execution freezes the dispatch's slot set: a request
    admitted between dispatch and completion must ride the *next* step —
    exactly the eager engine's token set (asserted via bit-equality on a
    workload with more requests than slots)."""
    ser = _serve("ffn", execution="serial", n=8, gen=3)
    bat = _serve("ffn", execution="batched", n=8, gen=3)
    assert bat.tokens == ser.tokens


# ---------------------------------------------------------------------------
# Decode-backend routing (satellite: no silent pallas→jax fallthrough)
# ---------------------------------------------------------------------------

def test_decode_backend_recorded_explicitly():
    rng = np.random.default_rng(2)
    W = rng.normal(size=(32, 8))
    l_int = np.array([16, 16, 16])
    finish = np.array([1.0, 2.0, 3.0])
    for backend, engine in (("numpy", "numpy"), ("jax", "jax:cpu"),
                            ("pallas", "jax:cpu")):
        lin = CodedLinear(W, name="t", seed=0, backend=backend)
        res = lin.step(rng.normal(size=(2, 8)), l_int, finish, 3.0)
        assert lin.decode_backend == engine
        assert res.decode_backend == engine
    rep = _serve("head", backend="pallas")
    assert rep.decode_backend == "jax:cpu"
    assert all(s["decode_backend"] == "jax:cpu" for s in rep.steps)
    rep = _serve("head", coded=False)
    assert rep.decode_backend == "local"


def test_step_log_schema_parity_serial_vs_batched():
    """Both execution engines must emit the *same* step_log schema — every
    key present in one appears in the other, per-step decode backends agree
    with the report-level routing, and the covering-prefix attribution
    (critical_task/critical_worker) is populated, not defaulted."""
    ser = _serve("trunk", execution="serial")
    bat = _serve("trunk", execution="batched")
    assert ser.steps and bat.steps
    keys_ser = {k for s in ser.steps for k in s}
    keys_bat = {k for s in bat.steps for k in s}
    assert keys_ser == keys_bat
    assert {"decode_backend", "critical_task", "critical_worker",
            "execution", "t_done"} <= keys_ser
    for rep in (ser, bat):
        assert all(s["decode_backend"] == rep.decode_backend
                   for s in rep.steps)
        crit_tasks = [s["critical_task"] for s in rep.steps]
        assert any(t is not None for t in crit_tasks)
        assert any(s["critical_worker"] >= 0 for s in rep.steps)
    # the two engines attribute the same critical tasks: identical
    # scheduling (asserted above via t_done) implies identical attribution
    assert [s["critical_task"] for s in ser.steps] == \
        [s["critical_task"] for s in bat.steps]
    assert [s["critical_worker"] for s in ser.steps] == \
        [s["critical_worker"] for s in bat.steps]


# ---------------------------------------------------------------------------
# Conditioning guard + per-scope decode error bound (satellite)
# ---------------------------------------------------------------------------

def test_parity_cond_flags_degenerate_blocks():
    rng = np.random.default_rng(3)
    good = rng.normal(0, 1 / np.sqrt(64), size=(128, 64))
    assert mds.parity_cond(good) < mds.PARITY_COND_LIMIT
    bad = np.ones((64, 64)) * 0.1                     # rank-1: cond = inf
    assert mds.parity_cond(bad) == np.inf
    assert mds.parity_cond(np.zeros((0, 8))) == 1.0


def test_ensure_parity_redraws_degenerate_chunk(monkeypatch):
    # rig the counter derivation: draw 0 of every block is rank-1, so the
    # conditioning guard must bump the redraw byte deterministically
    real = mds.counter_parity_rows

    def rigged(key, ctrs, L, **kw):
        if not (np.asarray(ctrs, dtype=np.uint32) >> 24).any():
            return np.ones((np.asarray(ctrs).size, L)) * 0.1   # draw 0
        return real(key, ctrs, L, **kw)

    monkeypatch.setattr(mds, "counter_parity_rows", rigged)
    lin = CodedLinear(np.eye(16), name="guard", seed=0, parity_chunk=16)
    lin.ensure_parity(16)
    assert lin.parity_redraws >= 1
    assert mds.parity_cond(lin.R) < mds.PARITY_COND_LIMIT
    # the redraw index is part of the packed counter (high byte), so the
    # frozen plan metadata replays the *redrawn* rows
    assert (lin.parity_ctrs(np.arange(16)) >> 24 >= 1).all()
    # decode through the redrawn parity block stays exact
    X = np.random.default_rng(8).normal(size=(2, 16))
    res = lin.step(X, np.array([8, 24]), np.array([5.0, 1.0]), 6.0)
    assert res.used_solve
    np.testing.assert_allclose(res.out, X @ lin.W.T, atol=1e-9)
    # a virtual-mode twin walks the identical deterministic guard and
    # derives bit-identical rows despite never materialising the cache
    vlin = CodedLinear(np.eye(16), name="guard", seed=0, parity_chunk=16,
                      parity_storage="virtual")
    assert np.array_equal(vlin.parity_rows(np.arange(16)), lin.R)
    assert vlin.parity_redraws >= 1


def test_per_scope_decode_error_stays_bounded():
    """The trunk scope's many small mixed-row solves have a fatter
    conditioning tail than the head's (2.6e-11 vs 1.2e-12 in the seed
    BENCH_serve.json); the parity conditioning guard keeps every scope's
    worst per-matmul relative error under 1e-9 on float64."""
    for scope in CODING_SCOPES:
        rep = _serve(scope, n=6, gen=4)
        assert rep.decode_ok
        assert rep.max_err < 1e-9, (scope, rep.max_err)


# ---------------------------------------------------------------------------
# Backend plumbing: solve bypass, draw_n, batched kernel, device cache
# ---------------------------------------------------------------------------

def test_solve_stacked_bit_identical_to_public_solve():
    rng = np.random.default_rng(4)
    for g, n, c in ((1, 3, 1), (4, 22, 2), (2, 96, 3)):
        A = rng.normal(size=(g, n, n))
        b = rng.normal(size=(g, n, c))
        assert (bk.solve_stacked(A, b) == np.linalg.solve(A, b)).all()


def test_draw_n_matches_successive_draws():
    mk = lambda: bk.ExponentialBlock(np.random.default_rng(5), width=6,
                                     block=8, uniform_rows=1)
    a, b = mk(), mk()
    singles = np.stack([a.draw() for _ in range(64)])
    # spans: within-buffer, across one refill, and n > block (multiple
    # refills — a deep trunk's 1 + 7·n_layers tasks per dispatch)
    batched = np.concatenate([b.draw_n(5), b.draw_n(6), b.draw_n(29),
                              b.draw_n(24)])
    assert (singles == batched).all()      # stream-identical across refills
    assert b.block == 8                    # block size never mutates
    with pytest.raises(ValueError):
        b.draw_n(0)


def test_solve_stacked_raises_on_singular():
    with pytest.raises(np.linalg.LinAlgError):
        bk.solve_stacked(np.zeros((1, 3, 3)), np.ones((1, 3, 2)))


def test_coded_shard_matmul_batch_modes_agree():
    import jax.numpy as jnp
    from repro.kernels import ops
    rng = np.random.default_rng(6)
    tiles = jnp.asarray(rng.normal(size=(3, 128, 128)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(128, 4)), jnp.float32)
    vm = np.asarray(ops.coded_shard_matmul_batch(tiles, x, mode="vmap"))
    pl = np.asarray(ops.coded_shard_matmul_batch(tiles, x, mode="pallas"))
    ref = np.stack([np.asarray(tiles[i]) @ np.asarray(x) for i in range(3)])
    assert np.abs(vm - ref).max() < 1e-4
    assert np.abs(pl - ref).max() < 1e-4
    with pytest.raises(ValueError):
        ops.coded_shard_matmul_batch(tiles, x, mode="nope")
    with pytest.raises(ValueError):
        ops.coded_shard_matmul_batch(tiles[:, :100], x, mode="pallas")


def test_device_cache_grows_incrementally():
    rng = np.random.default_rng(9)
    lin = CodedLinear(rng.normal(size=(32, 16)), name="dev", seed=0,
                      backend="jax", parity_chunk=8)
    d1 = lin.device_rows(40)                          # 8 parity rows
    assert d1.shape == (40, 16)
    n_dev_first = lin._n_dev
    d2 = lin.device_rows(56)                          # grows by 16 more
    assert d2.shape == (56, 16) and lin._n_dev >= 56
    assert n_dev_first < lin._n_dev
    np.testing.assert_allclose(
        np.asarray(d2, dtype=np.float64),
        lin._enc[:56].astype(np.float32).astype(np.float64))


def test_packed_stage_device_products_match_host():
    rng = np.random.default_rng(10)
    problems, _, _ = _ragged_problems(rng)
    for backend in ("jax", "pallas"):
        stage = PackedStage(problems, backend=backend)
        X = rng.normal(size=(4, 24))
        host = stage.pack.products(X)
        dev = stage.pack.products_device(X, backend=backend)
        for h, d in zip(host, dev):
            assert np.abs(h - d).max() < 1e-3          # float32 device path
    prob, row = stage.pack.gather_index()
    assert (prob >= 0).sum() == stage.pack.total
    assert stage.pack.n_tiles == -(-stage.pack.total // 128)


# ---------------------------------------------------------------------------
# Expected-delay row assignment (systematic rows on the fast nodes)
# ---------------------------------------------------------------------------

def test_prefix_plan_small_matrix_parity_first_delivery():
    """L below MIN_PARITY_BLOCK with a parity shard delivering first: the
    parity-fill budget must cap at L (regression: an uncapped floor drove
    the systematic quota negative and emitted > L rows)."""
    rng = np.random.default_rng(12)
    lin = CodedLinear(rng.normal(size=(4, 6)), name="tiny", seed=0)
    l_int = np.array([4, 8])
    finish = np.array([5.0, 1.0])                # parity shard lands first
    plan = lin.prefix_plan(l_int, finish, 2.0)
    assert plan.rows.size == 4
    X = rng.normal(size=(3, 6))
    res = lin.step(X, l_int, finish, 2.0)
    np.testing.assert_allclose(res.out, X @ lin.W.T, atol=1e-9)
    outs = PackedStage([ShardProblem(key="tiny", linear=lin,
                                     rows=plan.rows,
                                     used_solve=plan.used_solve)]).execute(X)
    assert (outs["tiny"] == res.out).all()


def test_prefix_assign_places_systematic_rows_on_expected_fast_nodes():
    rng = np.random.default_rng(11)
    lin = CodedLinear(rng.normal(size=(32, 8)), name="as", seed=0)
    l_int = np.array([16, 16, 16])
    finish = np.array([1.0, 2.0, 3.0])
    # node order: node 0 holds [0,16) — but expected delays say node 2
    # is fastest, so with assign node 2 holds the systematic start
    assign = np.array([2.0, 3.0, 1.0])
    plain = lin.prefix_plan(l_int, finish, 3.0)
    ranked = lin.prefix_plan(l_int, finish, 3.0, assign=assign)
    assert (plain.slices[0] == np.arange(0, 16)).all()
    # delivery order is still by finish (node 0 first), but node 0 now
    # holds the *second* range in expected-delay order: rows [16, 32)
    assert (ranked.slices[0] == np.arange(16, 32)).all()
    X = rng.normal(size=(2, 8))
    for assign_key in (None, assign):
        res = lin.step(X, l_int, finish, 3.0, assign=assign_key)
        np.testing.assert_allclose(res.out, X @ lin.W.T, atol=1e-9)


# ---------------------------------------------------------------------------
# Bench schema (satellite: per-execution-mode rows + gates)
# ---------------------------------------------------------------------------

def test_bench_serve_schema_has_execution_modes_and_wall_ratios():
    record = json.loads(
        (pathlib.Path(__file__).parent.parent / "BENCH_serve.json")
        .read_text())
    for scope in CODING_SCOPES:
        assert set(record["scopes"][scope]) == {"serial", "batched"}
    assert record["trunk_wall_vs_head"] > 0
    assert set(record["batched_wall_speedup"]) == set(CODING_SCOPES)
    assert record["timing_reps"] >= 1


def test_check_regression_min_floor_gate(tmp_path, capsys):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "check_regression",
        pathlib.Path(__file__).parent.parent / "benchmarks"
        / "check_regression.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    gate = mod.main
    rec = {"scopes": {"trunk": {"batched": {"tokens_per_wall_second": 10}}},
           "trunk_wall_vs_head": 0.9}
    base = tmp_path / "base.json"
    fresh = tmp_path / "fresh.json"
    base.write_text(json.dumps(rec))
    fresh.write_text(json.dumps(rec))
    ok = gate(["--baseline", str(base), "--fresh", str(fresh),
               "--key", "scopes.trunk.batched.tokens_per_wall_second",
               "--min", "trunk_wall_vs_head=0.4"])
    assert ok == 0
    bad = dict(rec, trunk_wall_vs_head=0.2)
    fresh.write_text(json.dumps(bad))
    assert gate(["--baseline", str(base), "--fresh", str(fresh),
                 "--min", "trunk_wall_vs_head=0.4"]) == 1
