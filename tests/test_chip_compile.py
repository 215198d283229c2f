"""Compile-only checks for one described TPU v5e chip (no chip needed).

The TPU compiler is installed even where no chip is attached: it compiles
for a topology that is described, not present.  That refuses what the
Pallas interpreter accepts — an unsupported cast, a slice not aligned to
the tiling, a kernel over its VMEM, a program over the chip's memory — so
these tests compile the main path's kernels at llama3.2-1b widths, and
its full-width decode step, for one chip of a described ``v5e:2x2``.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
every test file.
"""
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

L, K, C, N_PAR = 8192, 2048, 8, 2048     # FFN up projection: d_ff × d_model


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # else libtpu logs to /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip — keep it out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _kernel_case(name):
    """(fn, [(shape, dtype), ...]) of one Pallas kernel at real widths."""
    from repro.kernels.coded_matvec import coded_matvec_pallas
    from repro.kernels.matmul import matmul_pallas
    from repro.kernels.mds_encode import (counter_parity_rows_pallas,
                                          gen_parity_matvec_pallas)
    key, scale = ((1, 2), jnp.uint32), ((1, 1), jnp.float32)
    ctrs = ((N_PAR, 1), jnp.uint32)
    f32 = jnp.float32
    return {
        "coded_matvec": (
            lambda a, x: coded_matvec_pallas(a, x, interpret=False),
            [((L, K), f32), ((K, C), f32)]),
        "counter_parity_rows": (
            lambda k, s, c: counter_parity_rows_pallas(
                k, s, c, n_cols=L, interpret=False),
            [key, scale, ctrs]),
        "gen_parity_matvec": (
            lambda k, s, c, w, x: gen_parity_matvec_pallas(
                k, s, c, w, x, interpret=False),
            [key, scale, ctrs, ((L, K), f32), ((K, C), f32)]),
        "matmul": (
            lambda a, b: matmul_pallas(a, b, interpret=False),
            [((1024, 1024), f32), ((1024, 1024), f32)]),
    }[name]


@pytest.mark.parametrize("name", ["coded_matvec", "counter_parity_rows",
                                  "gen_parity_matvec", "matmul"])
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, specs = _kernel_case(name)
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_full_width_decode_step_compiles_for_v5e(one_chip,
                                                 no_persistent_cache):
    from repro.configs import get_config
    from repro.models import decode_step, init_cache_shapes, init_model
    cfg = get_config("llama3.2-1b")
    B, max_len = 4, 256

    def placed(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    params = placed(jax.eval_shape(
        lambda: init_model(jax.random.PRNGKey(0), cfg)))
    caches = placed(init_cache_shapes(cfg, B, max_len))
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, t, q, c: decode_step(
        p, t, q, c, cfg=cfg)).lower(params, tok, pos, caches).compile()
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert held < 16e9, held                   # one v5e chip: 16 GB HBM
