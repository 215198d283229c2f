"""Backend equivalence at the new seams: numpy vs jax vs (interpret-mode)
Pallas must agree on ``completion_times``, ``decode_batch`` (dense and the
systematic fast path), ``simulate_batch`` statistics, and full
``CodedExecutor.run`` reports on fixed seeds.
"""
import warnings

import numpy as np
import pytest

from repro.core import iterated_greedy, plan_from_assignment, uncoded_uniform
from repro.core.problem import Scenario
from repro.runtime import CodedExecutor
from repro.sim import simulate_plan
from repro.stream.backend import (ExponentialBlock, completion_times,
                                  decode_batch, has_jax, simulate_batch)

needs_jax = pytest.mark.skipif(not has_jax(), reason="jax not installed")


def _scenario(M=3, N=10, L=96.0, seed=3):
    rng = np.random.default_rng(seed)
    a = np.zeros((M, N + 1))
    a[:, 0] = 0.5
    a[:, 1:] = rng.uniform(0.2, 0.4, size=(M, N))
    return Scenario(a=a, u=1 / a, gamma=2 / a, L=np.full(M, L))


# ---------------------------------------------------------------------------
# completion_times
# ---------------------------------------------------------------------------

@needs_jax
def test_completion_jax_matches_numpy_with_dead_and_poisoned():
    rng = np.random.default_rng(0)
    T = rng.exponential(1.0, size=(300, 7))
    T[rng.random(T.shape) < 0.10] = np.inf
    T[rng.random(T.shape) < 0.05] = np.nan
    loads = rng.uniform(0.0, 3.0, size=7)
    loads[2] = 0.0
    for need in (1.0, 5.0, loads.sum() + 1.0):
        np.testing.assert_allclose(
            completion_times(T, loads, need, backend="jax"),
            completion_times(T, loads, need), rtol=1e-6)
    np.testing.assert_allclose(
        completion_times(T, loads, 2.0, needs_all=True, backend="jax"),
        completion_times(T, loads, 2.0, needs_all=True), rtol=1e-6)


@needs_jax
def test_completion_jax_batched_leading_axes():
    rng = np.random.default_rng(1)
    T = rng.exponential(1.0, size=(40, 3, 6))
    loads = rng.uniform(0.5, 2.0, size=(3, 6))
    need = np.array([3.0, 4.0, 2.0])
    np.testing.assert_allclose(
        completion_times(T, loads[None], need[None], backend="jax"),
        completion_times(T, loads[None], need[None]), rtol=1e-6)


def test_unknown_backend_rejected():
    with pytest.raises(ValueError):
        completion_times(np.ones((2, 3)), np.ones(3), 1.0, backend="tpu")


# ---------------------------------------------------------------------------
# decode_batch: systematic fast path + stacked/ragged generators
# ---------------------------------------------------------------------------

def _decode_case(seed=0, L=16, B=14):
    rng = np.random.default_rng(seed)
    Lt = 2 * L
    G = np.vstack([np.eye(L), rng.normal(0, 1 / np.sqrt(L), (Lt - L, L))])
    x_true = rng.normal(size=(B, L))
    # even tasks: pure systematic prefix (permutation); odd: mixed rows
    rows = np.stack([rng.permutation(L if i % 2 == 0 else Lt)[:L]
                     for i in range(B)])
    y = np.einsum("bij,bj->bi", G[rows], x_true)
    return G, rows, y, x_true


def test_decode_fast_path_bitwise_equals_solve():
    G, rows, y, x_true = _decode_case()
    out_auto = decode_batch(G, rows, y)
    out_solve = decode_batch(G, rows, y, systematic="never")
    out_prefix = decode_batch(G, rows, y, systematic="prefix")
    np.testing.assert_allclose(out_auto, x_true, atol=1e-8)
    pure = (rows < G.shape[1]).all(axis=1)
    assert pure.any() and not pure.all()
    # LU of a permutation matrix is exact, so scatter == solve bit-for-bit
    assert (out_auto[pure] == out_solve[pure]).all()
    # "prefix" keeps the pre-substitution behaviour: mixed tasks take the
    # full L×L solve, bit-for-bit
    assert (out_prefix[~pure] == out_solve[~pure]).all()
    # "auto" substitutes; it still agrees with the full solve to solver
    # precision on the mixed tasks
    np.testing.assert_allclose(out_auto[~pure], out_solve[~pure],
                               rtol=1e-9, atol=1e-9)


# ---------------------------------------------------------------------------
# decode_batch: mixed-row substitution (s < L systematic rows)
# ---------------------------------------------------------------------------

def _mixed_case(seed, L, counts):
    """One task per entry of ``counts``: s systematic + (L-s) parity rows."""
    rng = np.random.default_rng(seed)
    Lt = 2 * L + 3
    G = np.vstack([np.eye(L), rng.normal(0, 1 / np.sqrt(L), (Lt - L, L))])
    x_true = rng.normal(size=(len(counts), L))
    rows = np.stack([
        np.concatenate([rng.permutation(L)[:s],
                        L + rng.permutation(Lt - L)[:L - s]])
        for s in counts])
    for r in rows:                      # interleave systematic/parity order
        rng.shuffle(r)
    y = np.einsum("bij,bj->bi", G[rows], x_true)
    return G, rows, y, x_true


@pytest.mark.parametrize("counts", [
    (3,), (0, 5, 5, 12), (1, 1, 7, 0, 16, 7, 3), tuple(range(17))])
def test_decode_mixed_substitution_group_shapes(counts):
    """Substitution solves only the (L-s)-sized parity block, grouped by s:
    every group shape decodes to the truth, the pinned systematic
    coordinates are bit-identical to the received values, and the result
    agrees with the full solve to solver precision."""
    L = 16
    G, rows, y, x_true = _mixed_case(seed=7 + len(counts), L=L, counts=counts)
    out = decode_batch(G, rows, y)
    out_full = decode_batch(G, rows, y, systematic="prefix")
    np.testing.assert_allclose(out, x_true, atol=1e-9)
    np.testing.assert_allclose(out, out_full, rtol=1e-9, atol=1e-9)
    for b in range(rows.shape[0]):
        sys_m = rows[b] < L
        # each received systematic row pins x[row] = y exactly (scatter)
        assert (out[b, rows[b][sys_m]] == y[b, sys_m]).all()
    # matrix right-hand sides ride the same substitution path
    y3 = np.stack([y, -0.5 * y], axis=-1)
    out3 = decode_batch(G, rows, y3)
    np.testing.assert_allclose(out3[..., 0], x_true, atol=1e-9)
    np.testing.assert_allclose(out3[..., 1], -0.5 * x_true, atol=1e-9)


def test_decode_mixed_substitution_generator_forms_and_jax():
    counts = (0, 2, 9, 9, 15, 16)
    L = 16
    G, rows, y, x_true = _mixed_case(seed=3, L=L, counts=counts)
    B = rows.shape[0]
    base = decode_batch(G, rows, y)
    assert (decode_batch(np.stack([G] * B), rows, y) == base).all()
    assert (decode_batch([G] * B, rows, y) == base).all()
    if has_jax():
        np.testing.assert_allclose(decode_batch(G, rows, y, backend="jax"),
                                   x_true, rtol=1e-4, atol=1e-4)


def test_decode_batch_matrix_rhs_and_stacked_generators():
    G, rows, y, x_true = _decode_case(seed=2)
    B = rows.shape[0]
    # (B, L, C) right-hand sides
    y3 = np.stack([y, 2 * y], axis=-1)
    out3 = decode_batch(G, rows, y3)
    np.testing.assert_allclose(out3[..., 0], x_true, atol=1e-8)
    np.testing.assert_allclose(out3[..., 1], 2 * x_true, atol=1e-8)
    # per-task generators: 3-D stack and list forms match the shared-G path
    base = decode_batch(G, rows, y)
    assert (decode_batch(np.stack([G] * B), rows, y) == base).all()
    assert (decode_batch([G] * B, rows, y) == base).all()


@needs_jax
def test_decode_jax_matches_numpy():
    G, rows, y, x_true = _decode_case(seed=3)
    np.testing.assert_allclose(decode_batch(G, rows, y, backend="jax"),
                               x_true, rtol=1e-4, atol=1e-4)


def test_jax_decode_solve_is_float64_on_named_cpu_device():
    """float64 LU does not compile for the TPU, so the jax decode engine
    places its solve on the host CPU by name and keeps full precision."""
    import jax
    from repro.stream.backend import decode_device, solve_jax
    assert decode_device() == jax.devices("cpu")[0]
    rng = np.random.default_rng(0)
    A = rng.normal(size=(3, 12, 12)) + 4 * np.eye(12)
    b = rng.normal(size=(3, 12, 2))
    out = solve_jax(A, b)
    assert out.dtype == np.float64
    np.testing.assert_allclose(out, np.linalg.solve(A, b), rtol=1e-12,
                               atol=1e-12)


def test_decode_device_refuses_a_platform_list_without_cpu(monkeypatch):
    """No silent fallback: without JAX's CPU backend the jax decode engine
    raises and says how to keep it."""
    import jax
    from repro.stream import backend as bk

    def no_cpu(*_a):
        raise RuntimeError("Unknown backend cpu")

    bk.decode_device.cache_clear()
    monkeypatch.setattr(jax, "devices", no_cpu)
    try:
        with pytest.raises(RuntimeError, match="tpu,cpu"):
            bk.decode_device()
    finally:
        bk.decode_device.cache_clear()


@pytest.mark.parametrize("g", (1, 3))
def test_cached_lu_matches_the_jitted_solve_and_factorises_once(g):
    """The jax engine's square decodes solve through ``StackedLU``: each
    solve equals the one-shot jitted ``solve_jax`` bit for bit, and only
    the first one factorises."""
    from repro.obs import Tracer, use_tracer
    from repro.stream.backend import StackedLU, solve_jax
    rng = np.random.default_rng(g)
    n = 40
    A = rng.normal(size=(g, n, n))
    lu = StackedLU(A.copy())
    with use_tracer(tr := Tracer()):
        for step in range(2):
            b = rng.normal(size=(g, n, 4))
            assert (lu.solve(b) == solve_jax(A, b)).all(), step
            if step == 0:
                # solve_jax counts its own factorisation: one each
                assert tr.counters["decode_lu_factorizations"] == 2 * g
    assert tr.counters["decode_lu_factorizations"] == 3 * g
    assert tr.counters["decode_system_rows"] == 4 * g * n
    # a single column rounds differently in XLA's triangular solve; the
    # cached path stays LAPACK's gesv, bit for bit
    b1 = rng.normal(size=(g, n, 1))
    assert (lu.solve(b1) == np.linalg.solve(A, b1)).all()
    np.testing.assert_allclose(lu.solve(b1), solve_jax(A, b1), rtol=1e-12,
                               atol=1e-12)
    # singularity is still caught on the first solve
    S = A.copy()
    S[-1, :, 0] = 0.0
    with warnings.catch_warnings(), pytest.raises(np.linalg.LinAlgError):
        warnings.simplefilter("ignore")       # scipy's zero-pivot warning
        StackedLU(S).solve(rng.normal(size=(g, n, 4)))


# ---------------------------------------------------------------------------
# simulate_batch / simulate_plan(backend="jax")
# ---------------------------------------------------------------------------

@needs_jax
def test_simulate_jax_statistically_matches_numpy():
    sc = _scenario()
    plan = plan_from_assignment(sc, iterated_greedy(sc, rng=0))
    r_np = simulate_plan(sc, plan, trials=20_000, rng=1)
    r_jx = simulate_plan(sc, plan, trials=20_000, rng=1, backend="jax")
    # independent RNG streams: agree to Monte-Carlo precision
    np.testing.assert_allclose(r_jx.per_master_mean, r_np.per_master_mean,
                               rtol=0.03)
    assert abs(r_jx.overall_mean / r_np.overall_mean - 1) < 0.02


@needs_jax
def test_simulate_jax_uncoded_needs_all():
    sc = _scenario()
    plan = uncoded_uniform(sc)
    r_np = simulate_plan(sc, plan, trials=20_000, rng=2)
    r_jx = simulate_plan(sc, plan, trials=20_000, rng=2, backend="jax")
    assert abs(r_jx.overall_mean / r_np.overall_mean - 1) < 0.03


@needs_jax
def test_simulate_jax_straggle_and_determinism():
    sc = _scenario()
    plan = plan_from_assignment(sc, iterated_greedy(sc, rng=0))
    kw = dict(straggle_p=0.25, straggle_factor=8.0, backend="jax")
    r1 = simulate_plan(sc, plan, trials=10_000, rng=3, keep_samples=True, **kw)
    r2 = simulate_plan(sc, plan, trials=10_000, rng=3, keep_samples=True, **kw)
    assert (r1.overall_samples == r2.overall_samples).all()
    base = simulate_plan(sc, plan, trials=10_000, rng=3, backend="jax")
    assert r1.overall_mean > base.overall_mean      # throttling hurts
    r_np = simulate_plan(sc, plan, trials=20_000, rng=3,
                         straggle_p=0.25, straggle_factor=8.0)
    r_jx = simulate_plan(sc, plan, trials=20_000, rng=3,
                         straggle_p=0.25, straggle_factor=8.0, backend="jax")
    assert abs(r_jx.overall_mean / r_np.overall_mean - 1) < 0.05


@needs_jax
def test_simulate_batch_trials_not_multiple_of_chunk():
    sc = _scenario(M=2, N=6)
    plan = plan_from_assignment(sc, iterated_greedy(sc, rng=0))
    comp = simulate_batch(plan.l, plan.k, plan.b, sc.a, sc.u, sc.gamma,
                          sc.L, 1000, seed=5, chunk=256)
    assert comp.shape == (1000, sc.M)
    assert np.isfinite(comp).all()


def test_exponential_block_uniform_rows():
    blk = ExponentialBlock(np.random.default_rng(0), width=5, block=4,
                           uniform_rows=1)
    rows = [blk.draw() for _ in range(10)]          # spans a refill
    for r in rows:
        assert r.shape == (3, 5)
        assert (r[2] >= 0).all() and (r[2] < 1).all()     # uniform row
    # deterministic replay
    blk2 = ExponentialBlock(np.random.default_rng(0), width=5, block=4,
                            uniform_rows=1)
    assert all((a == blk2.draw()).all() for a in rows)


# ---------------------------------------------------------------------------
# CodedExecutor: stacked run vs the legacy per-master loop
# ---------------------------------------------------------------------------

def _exec_case(seed=0):
    sc = _scenario()
    plan = plan_from_assignment(sc, iterated_greedy(sc, rng=0))
    rng = np.random.default_rng(seed)
    A = [rng.normal(size=(96, 8)) for _ in range(sc.M)]
    x = [rng.normal(size=8) for _ in range(sc.M)]
    return sc, plan, A, x


@pytest.mark.parametrize("dead", [(), (1,), (2, 5)])
def test_coded_executor_batched_bit_for_bit(dead):
    sc, plan, A, x = _exec_case()
    for seed in range(4):
        res_n, rep_n = CodedExecutor(sc, plan, rng=seed).run(
            A, x, dead_workers=dead)
        res_o, rep_o = CodedExecutor(sc, plan, rng=seed)._run_loop(
            A, x, dead_workers=dead)
        assert np.array_equal(rep_n.completion, rep_o.completion)
        assert np.array_equal(rep_n.decode_ok, rep_o.decode_ok)
        assert np.array_equal(rep_n.max_err, rep_o.max_err)
        for u, v in zip(rep_n.used_nodes, rep_o.used_nodes):
            assert np.array_equal(u, v)
        for a, b in zip(res_n, res_o):
            assert np.array_equal(np.nan_to_num(a, nan=-1.0),
                                  np.nan_to_num(b, nan=-1.0))


def test_coded_executor_matrix_rhs_and_mixed_shapes():
    """Matrix right-hand sides (x (S, C)) and heterogeneous RHS shapes in
    one run() — the legacy loop accepted both, the stacked path must too."""
    sc, plan, A, _ = _exec_case()
    rng = np.random.default_rng(9)
    x = [rng.normal(size=8), rng.normal(size=(8, 3)), rng.normal(size=(8, 2))]
    res_n, rep_n = CodedExecutor(sc, plan, rng=0).run(A, x)
    res_o, rep_o = CodedExecutor(sc, plan, rng=0)._run_loop(A, x)
    assert np.array_equal(rep_n.completion, rep_o.completion)
    assert np.array_equal(rep_n.max_err, rep_o.max_err)
    for a, b in zip(res_n, res_o):
        assert np.array_equal(a, b)
    if has_jax():
        _, rep_j = CodedExecutor(sc, plan, rng=0, backend="jax").run(A, x)
        assert rep_j.decode_ok.all() and \
            np.array_equal(rep_j.completion, rep_o.completion)


def test_simulate_plan_numpy_bit_equals_simulate_batch_numpy():
    """One shared Generator-chunk implementation: same seed + chunk give the
    same samples through both entry points."""
    sc = _scenario(M=2, N=6)
    plan = plan_from_assignment(sc, iterated_greedy(sc, rng=0))
    r = simulate_plan(sc, plan, trials=5000, rng=11, keep_samples=True)
    comp = simulate_batch(plan.l, plan.k, plan.b, sc.a, sc.u, sc.gamma,
                          sc.L, 5000, seed=np.random.default_rng(11),
                          backend="numpy", chunk=20_000)
    assert (r.per_master_samples == comp).all()


def test_coded_executor_gaussian_generator_still_equivalent():
    sc, plan, A, x = _exec_case(seed=1)
    kw = dict(generator_kind="gaussian", rng=2)
    _, rep_n = CodedExecutor(sc, plan, **kw).run(A, x)
    _, rep_o = CodedExecutor(sc, plan, **kw)._run_loop(A, x)
    assert np.array_equal(rep_n.completion, rep_o.completion)
    assert np.array_equal(rep_n.max_err, rep_o.max_err)
    assert rep_n.decode_ok.all()


@needs_jax
@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("kind", ["systematic", "gaussian"])
def test_coded_executor_accelerator_backends(backend, kind):
    sc, plan, A, x = _exec_case()
    _, rep_b = CodedExecutor(sc, plan, rng=0, backend=backend,
                             generator_kind=kind).run(
        A, x, dead_workers=(1,))
    _, rep_r = CodedExecutor(sc, plan, rng=0,
                             generator_kind=kind)._run_loop(
        A, x, dead_workers=(1,))
    # randomness and the completion rule stay on the host: identical
    assert np.array_equal(rep_b.completion, rep_r.completion)
    # float32 linear algebra: verified decode, looser error floor
    assert rep_b.decode_ok.all(), rep_b.max_err
    assert rep_b.max_err.max() < 1e-3
