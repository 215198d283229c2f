"""Virtual parity rows: counter-generated MDS parity, cross-mode parity.

The tentpole invariant: serving with ``parity_storage="virtual"`` — parity
generator rows derived in-kernel (or per host block) from packed threefry
counters, never materialised as a ``[W; WR]`` cache — produces
**bit-identical greedy tokens** to the materialised mode, at every
``coding_scope`` and on every backend (numpy | jax | pallas-interpret).
Underneath it, the replay fix: every parity row is a pure function of
``(seed, name, row index)``, independent of the cache's growth history.
"""
import warnings

import numpy as np
import pytest

from repro.core import mds
from repro.serve_coded import (CODING_SCOPES, CodedLinear,
                               CodedServingBridge, synthetic_requests)
from repro.serve_coded.coded_linear import DECODE_ENGINE
from repro.serve_coded.packing import PackedStage, ShardProblem
from repro.stream import AdmissionConfig
from repro.stream import backend as bk

jax = pytest.importorskip("jax")

BACKENDS = ("numpy", "jax", "pallas")


def _serve(scope, parity_storage, *, backend="numpy", n=3, gen=2, seed=0):
    bridge = CodedServingBridge(
        masters=2, seed=seed, slots_per_master=2, coding_scope=scope,
        backend=backend, parity_storage=parity_storage,
        admission=AdmissionConfig(policy="edf"))
    bridge._setup_model(16 + gen + 8)
    reqs = synthetic_requests(
        n, masters=2, vocab=bridge._model["cfg"].vocab, prompt_len=16,
        gen_len=gen, rate=0.02, seed=seed)
    return bridge.serve(reqs)


def _linear(storage, *, L=48, D=16, seed=0, chunk=8, backend="numpy"):
    rng = np.random.default_rng(seed)
    return CodedLinear(rng.normal(size=(L, D)), name=f"v{L}x{D}", seed=seed,
                       parity_chunk=chunk, backend=backend,
                       parity_storage=storage)


# ---------------------------------------------------------------------------
# The acceptance matrix: scope × backend, virtual vs materialised serving
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("scope", CODING_SCOPES)
def test_virtual_serving_token_identical(scope, backend):
    mat = _serve(scope, "materialized", backend=backend)
    virt = _serve(scope, "virtual", backend=backend)
    assert virt.tokens == mat.tokens             # bit-identical token ids
    assert virt.decode_ok and mat.decode_ok, (scope, backend, virt.max_err)
    assert virt.max_err == mat.max_err           # same decoded values
    assert virt.parity_storage == "virtual"
    assert mat.parity_storage == "materialized"
    # satellite: the report says which backend actually ran
    assert virt.backend == backend
    assert virt.decode_backend == DECODE_ENGINE[backend]
    for s in virt.steps:
        assert s["parity_storage"] == "virtual"
        assert s["backend"] == backend


# ---------------------------------------------------------------------------
# Replay fix: rows are growth-history independent, cross-mode bit-equal
# ---------------------------------------------------------------------------

def test_parity_rows_independent_of_growth_history():
    a = _linear("materialized")
    b = _linear("materialized")
    a.ensure_parity(10)       # grows by 8-row chunks: two appends
    a.ensure_parity(40)
    b.ensure_parity(40)       # one append of the same blocks
    assert np.array_equal(a.R, b.R)
    assert np.array_equal(a._enc[:a._n_enc], b._enc[:b._n_enc])
    # virtual twin, gathered in arbitrary order, carries identical bits
    v = _linear("virtual")
    ids = np.array([37, 2, 19, 5])
    assert np.array_equal(v.parity_rows(ids), a.R[ids])
    rows = np.array([0, 47, 48, 50, 85, 3])
    assert np.array_equal(v.gather_encoded(rows),
                          a.gather_encoded(rows))
    assert np.array_equal(v.parity_ctrs(ids), a.parity_ctrs(ids))


def test_serial_step_bit_identical_across_modes():
    X = np.random.default_rng(1).normal(size=(4, 16))
    l_int = np.array([12, 18, 18, 24, 24])
    finish = np.array([99.0, 2.0, 3.0, 1.0, 4.0])    # straggler → solve
    outs = {}
    for storage in ("materialized", "virtual"):
        lin = _linear(storage)
        res = lin.step(X, l_int, finish, 4.0)
        assert res.used_solve
        outs[storage] = res.out
        np.testing.assert_allclose(res.out, X @ lin.W.T, atol=1e-8)
    assert np.array_equal(outs["materialized"], outs["virtual"])


def test_prefix_plan_carries_packed_counters():
    lin = _linear("virtual")
    plan = lin.prefix_plan(np.array([12, 18, 18, 24, 24]),
                           np.array([99.0, 2.0, 3.0, 1.0, 4.0]), 4.0)
    assert plan.used_solve and plan.parity_ctrs is not None
    par = plan.rows[plan.rows >= lin.L] - lin.L
    assert np.array_equal(plan.parity_ctrs, lin.parity_ctrs(par))
    # counters alone reproduce the rows (the frozen-plan replay contract)
    assert np.array_equal(
        mds.counter_parity_rows(lin.pkey, plan.parity_ctrs, lin.L),
        lin.parity_rows(par))


# ---------------------------------------------------------------------------
# Packed execution: host bit-identity, device generated-parity kernel
# ---------------------------------------------------------------------------

def _stage_pair(backend="numpy", D=24, Ls=(48, 48, 96)):
    stages = {}
    for storage in ("materialized", "virtual"):
        rng = np.random.default_rng(0)
        problems = []
        for i, L in enumerate(Ls):
            lin = CodedLinear(rng.normal(size=(L, D)), name=f"m{i}", seed=i,
                              backend=backend, parity_storage=storage)
            l_int = np.array([0, L // 3, L // 2, L // 2, L])
            finish = rng.permutation(np.arange(5).astype(float) + 1.0)
            finish[0] = np.inf
            plan = lin.prefix_plan(l_int, finish, t_complete=5.0)
            problems.append(ShardProblem(key=f"m{i}", linear=lin,
                                         rows=plan.rows,
                                         used_solve=plan.used_solve))
        stages[storage] = PackedStage(problems, backend=backend)
    return stages


def test_packed_stage_host_bit_identical_across_modes():
    stages = _stage_pair()
    X = np.random.default_rng(2).normal(size=(5, 24))
    assert np.array_equal(stages["materialized"].pack.W_packed,
                          stages["virtual"].pack.W_packed)
    mat = stages["materialized"].execute(X)
    virt = stages["virtual"].execute(X)
    assert set(mat) == set(virt)
    for k in mat:
        assert np.array_equal(mat[k], virt[k])


@pytest.mark.parametrize("backend", ("jax", "pallas"))
def test_packed_stage_device_generated_parity_matches(backend):
    stages = _stage_pair(backend=backend)
    X = np.random.default_rng(3).normal(size=(5, 24))
    host = stages["materialized"].execute(X, device_products=False)
    mat = stages["materialized"].execute(X, device_products=True)
    virt = stages["virtual"].execute(X, device_products=True)
    for k in host:
        # float32 device products (materialised gather vs in-kernel
        # generation) both track the float64 host decode
        assert np.abs(mat[k] - host[k]).max() < 1e-3, (backend, k)
        assert np.abs(virt[k] - host[k]).max() < 1e-3, (backend, k)


def test_kernel_generator_bit_equals_host_derivation():
    from repro.kernels import ops
    key = (0xDEADBEEF, 41)
    L = 200                                   # non-multiple of the block
    ctrs = mds.parity_counters(np.array([0, 3, 129, 500]), [0, 1, 0, 2])
    host = mds.counter_parity_rows(key, ctrs, L, dtype=np.float32)
    dev = np.asarray(ops.counter_parity_rows(key, L, ctrs))
    assert np.array_equal(host, dev)


EDGE_BITS = np.array([0, 255, 256, (1 << 24) - 1, 1 << 24, (1 << 31) - 1,
                      1 << 31, (1 << 32) - 1], dtype=np.uint32)


def test_uniform24_host_jnp_kernel_bits_on_edge_counters():
    """``_uniform24`` converts through int32 (the TPU kernel compiler has
    no uint32 → float32 cast); host, jitted jnp and a Pallas kernel must
    give the same float32 bits, including at the uint32 extremes."""
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    host = mds._uniform24(EDGE_BITS)
    assert host.dtype == np.float32
    assert host[0] == 0.0 and host[-1] == np.float32(1 - 2.0 ** -24)
    bits = jnp.asarray(np.broadcast_to(EDGE_BITS, (8, 8)))
    via_jnp = np.asarray(jax.jit(mds._uniform24)(bits))[0]

    def kern(b_ref, o_ref):
        o_ref[...] = mds._uniform24(b_ref[...])
    via_kernel = np.asarray(pl.pallas_call(
        kern, out_shape=jax.ShapeDtypeStruct((8, 8), jnp.float32),
        interpret=True)(bits))[0]
    for got in (via_jnp, via_kernel):
        assert np.array_equal(got.view(np.uint32), host.view(np.uint32))
    # the same edge values as packed row counters: kernel rows ≡ host rows
    from repro.kernels import ops
    key = (0xFFFFFFFF, 0)
    host_rows = mds.counter_parity_rows(key, EDGE_BITS, 64,
                                        dtype=np.float32)
    dev_rows = np.asarray(ops.counter_parity_rows(key, 64, EDGE_BITS,
                                                  interpret=True))
    assert np.array_equal(dev_rows.view(np.uint32),
                          host_rows.view(np.uint32))


def test_fused_generation_kernel_matches_xla_twin():
    """The TPU-path fused kernel (R derived in-VMEM, tile contraction)
    agrees with the XLA twin `gen_parity_products` routes to off-TPU —
    same rows, reduction order differs, so float32 tolerance."""
    import jax.numpy as jnp
    from repro.kernels import ops
    from repro.kernels.mds_encode import gen_parity_matvec_pallas
    rng = np.random.default_rng(5)
    L, D, C = 96, 40, 3
    key = (123, 456)
    ctrs = mds.parity_counters(np.arange(7), 0)
    w = jnp.asarray(rng.normal(size=(L, D)), jnp.float32)
    x = jnp.asarray(rng.normal(size=(D, C)), jnp.float32)
    xla = np.asarray(ops.gen_parity_products(key, ctrs, w, x,
                                             interpret=True))
    key_arr = jnp.asarray(np.asarray(key, np.uint32)[None, :])
    scale = jnp.full((1, 1), np.float32(np.sqrt(3.0 / L)), jnp.float32)
    ctrs_p = jnp.zeros((128, 1), jnp.uint32).at[:7, 0].set(
        jnp.asarray(ctrs))
    wp = jnp.zeros((128, 128), jnp.float32).at[:L, :D].set(w)
    xp = jnp.zeros((128, C), jnp.float32).at[:D].set(x)
    fused = np.asarray(gen_parity_matvec_pallas(
        key_arr, scale, ctrs_p, wp, xp, block_rows=128, block_k=128,
        interpret=True))[:7]
    exact = mds.counter_parity_rows(key, ctrs, L) @ (
        np.asarray(w, np.float64) @ np.asarray(x, np.float64))
    assert np.abs(fused - xla).max() < 1e-4
    assert np.abs(xla - exact).max() < 1e-3


# ---------------------------------------------------------------------------
# The backend is never downgraded: the layer runs what was asked
# ---------------------------------------------------------------------------

def test_backend_fallback_warns_and_records(monkeypatch):
    # jax is a hard dependency: even a has_jax() probe that says "no" must
    # not turn a pallas request into a silent numpy layer
    monkeypatch.setattr(bk, "has_jax", lambda: False)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        lin = CodedLinear(np.eye(8), name="nb", backend="pallas")
    assert lin.backend == "pallas"
    assert lin.decode_backend == "jax:cpu"


def test_backend_kept_when_jax_present():
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        lin = CodedLinear(np.eye(8), name="ok", backend="jax")
    assert lin.backend == "jax" and lin.decode_backend == "jax:cpu"
    with pytest.raises(ValueError):
        CodedLinear(np.eye(8), parity_storage="sparse")


# ---------------------------------------------------------------------------
# Memory: virtual keeps ≤ 0.55× the encoded bytes at redundancy 2
# ---------------------------------------------------------------------------

def test_virtual_encoded_cache_bytes_under_055x():
    L, D, chunk = 256, 64, 64
    mat = CodedLinear(np.random.default_rng(4).normal(size=(L, D)),
                      name="mem", parity_chunk=chunk)
    virt = CodedLinear(mat.W, name="mem", parity_chunk=chunk,
                       parity_storage="virtual")
    for lin in (mat, virt):
        lin.ensure_parity(L)                  # redundancy 2
    # steady-state gather footprint: one frozen prefix touching parity
    rows = np.concatenate([np.arange(L - 40), np.arange(L, L + 48)])
    for lin in (mat, virt):
        lin.gather_encoded(rows)
    assert virt.encoded_cache_bytes() <= 0.55 * mat.encoded_cache_bytes()


def test_virtual_mode_refuses_materialised_surfaces():
    v = _linear("virtual")
    with pytest.raises(RuntimeError):
        v.R
    with pytest.raises(RuntimeError):
        v.WR
    with pytest.raises(RuntimeError):
        v.device_rows(50)


# ---------------------------------------------------------------------------
# Satellite: stacked least-squares decode over extra parity rows
# ---------------------------------------------------------------------------

def _ls_fixture(B=6, L=24, R=32, C=3, seed=7):
    lin = _linear("virtual", L=L, D=16, seed=seed)
    rng = np.random.default_rng(seed)
    rows = np.stack([np.sort(rng.choice(L + L, size=R, replace=False))
                     for _ in range(B)])
    x = rng.normal(size=(B, L, C))
    G = bk.SystematicRows(L, 2 * L, lin.parity_rows)
    y = np.stack([G.take(rows[b]) @ x[b] for b in range(B)])
    return lin, G, rows, x, y


def test_ls_decode_bit_parity_with_lstsq_loop():
    lin, G, rows, x, y = _ls_fixture()
    plan = bk.plan_decode_ls(G, rows)
    out = plan.apply(y)
    ref = np.empty_like(x)
    for b in range(rows.shape[0]):                 # the reference, literally
        ref[b], *_ = np.linalg.lstsq(G.take(rows[b]), y[b], rcond=None)
    assert np.array_equal(out, ref)
    np.testing.assert_allclose(out, x, atol=1e-9)
    # dense-G input plans the same systems
    Gd = np.concatenate([np.eye(lin.L), lin.parity_rows(np.arange(lin.L))])
    assert np.array_equal(bk.plan_decode_ls(Gd, rows).Gs, plan.Gs)


def test_ls_decode_matches_exact_decode_on_exactly_L_rows():
    lin, G, _, _, _ = _ls_fixture()
    rng = np.random.default_rng(8)
    L = lin.L
    rows = np.stack([np.sort(rng.choice(L + L, size=L, replace=False))
                     for _ in range(4)])
    x = rng.normal(size=(4, L, 2))
    y = np.stack([G.take(rows[b]) @ x[b] for b in range(4)])
    ls = bk.decode_ls_batch(G, rows, y)
    exact = bk.decode_batch(Gd := np.concatenate(
        [np.eye(L), lin.parity_rows(np.arange(L))]), rows, y)
    np.testing.assert_allclose(ls, exact, atol=1e-8)
    np.testing.assert_allclose(ls, x, atol=1e-8)


def test_ls_decode_jax_path_and_validation():
    lin, G, rows, x, y = _ls_fixture()
    out_np = bk.decode_ls_batch(G, rows, y, backend="numpy")
    out_jx = bk.decode_ls_batch(G, rows, y, backend="jax")
    np.testing.assert_allclose(out_jx, out_np, atol=1e-8)
    with pytest.raises(ValueError, match="needs >= L"):
        bk.plan_decode_ls(G, rows[:, :lin.L - 1])
    # 2-D y (one column squeezed) round-trips shape; bit-parity only holds
    # per identical lstsq call (LAPACK treats 1- and C-column RHS blocks
    # differently at the last bit), so compare to the 1-column reference
    out2 = bk.decode_ls_batch(G, rows, y[..., 0])
    assert out2.shape == x[..., 0].shape
    ref = np.stack([np.linalg.lstsq(G.take(rows[b]), y[b, :, 0],
                                    rcond=None)[0]
                    for b in range(rows.shape[0])])
    assert np.array_equal(out2, ref)
