"""MDS encoding kernels: Ã = G @ A, plus counter-generated parity.

The generator is (L̃, L) with L̃ ≈ 2L under Theorem-1 loads, so encoding is
a skinny-times-wide matmul over the task matrix.  Systematic generators make
the top L rows an identity — the wrapper in ops.py skips them and only runs
the kernel over the parity rows, which halves encode FLOPs for the default
redundancy (a beyond-paper optimization recorded in EXPERIMENTS.md §Perf).

Virtual parity ("generated" mode) goes one step further: parity rows are a
pure function of ``(layer key, packed row counter)`` through the shared
threefry derivation in :mod:`repro.core.mds`, so the kernels here *derive*
each parity tile inside the grid instead of reading a materialised ``R`` or
``WR`` from HBM:

* :func:`counter_parity_rows_pallas` — the standalone generator (encode /
  verify paths): R rows, bit-identical to the host
  :func:`repro.core.mds.counter_parity_rows` twin.
* :func:`gen_parity_matvec_pallas` — the fused serving kernel:
  ``y = R_gen @ (W @ x)`` accumulated tile-by-tile against the
  device-resident W, so the encoded parity block ``WR`` is never stored.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import mds
from .matmul import DEFAULT_BLOCK, F32_PRECISION, matmul_pallas

__all__ = ["mds_encode_pallas", "counter_parity_rows_pallas",
           "gen_parity_matvec_pallas"]


def mds_encode_pallas(g: jnp.ndarray, a: jnp.ndarray,
                      block=DEFAULT_BLOCK, interpret: bool = False) -> jnp.ndarray:
    """Ã = G @ A with VMEM-tiled accumulation (see matmul.py)."""
    return matmul_pallas(g, a, block=block, interpret=interpret)


def _parity_tile(key_ref, scale_ref, ctr_ref, j, block_cols: int):
    """One (block_rows, block_cols) tile of counter-derived parity values.

    Shared by both generated-parity kernels: the arithmetic is the
    numpy/jnp-generic :func:`repro.core.mds.counter_gaussian_tile`, so the
    tile is bit-identical to the host derivation for the same counters.
    """
    cols = jax.lax.broadcasted_iota(jnp.uint32, (1, block_cols), 1) \
        + (j * block_cols).astype(jnp.uint32)
    return mds.counter_gaussian_tile(key_ref[0, 0], key_ref[0, 1],
                                     ctr_ref[...], cols, scale_ref[0, 0])


def _rows_kernel(key_ref, scale_ref, ctr_ref, o_ref, *, block_cols: int):
    o_ref[...] = _parity_tile(key_ref, scale_ref, ctr_ref,
                              pl.program_id(1), block_cols)


@functools.partial(jax.jit,
                   static_argnames=("n_cols", "block_rows", "block_cols",
                                    "interpret"))
def counter_parity_rows_pallas(key: jnp.ndarray, scale: jnp.ndarray,
                               ctrs: jnp.ndarray, *, n_cols: int,
                               block_rows: int = 128, block_cols: int = 128,
                               interpret: bool = False) -> jnp.ndarray:
    """Counter-derived parity rows R[ctrs] — the in-kernel generator.

    ``key`` (1, 2) uint32 layer key, ``scale`` (1, 1) float32
    ``sqrt(3/L)``, ``ctrs`` (Rp, 1) packed row counters
    (:func:`repro.core.mds.parity_counters`); Rp and ``n_cols`` must be
    block multiples (ops.py pads and slices).  Output (Rp, n_cols)
    float32 — bit-identical to the host twin for the same counters.
    """
    Rp = ctrs.shape[0]
    assert Rp % block_rows == 0 and n_cols % block_cols == 0
    return pl.pallas_call(
        functools.partial(_rows_kernel, block_cols=block_cols),
        grid=(Rp // block_rows, n_cols // block_cols),
        in_specs=[
            pl.BlockSpec((1, 2), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((block_rows, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, block_cols),
                               lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((Rp, n_cols), jnp.float32),
        interpret=interpret,
    )(key, scale, ctrs)


def _gen_matvec_kernel(key_ref, scale_ref, ctr_ref, w_ref, x_ref, o_ref,
                       acc_ref, *, k_steps: int, block_k: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    r_blk = _parity_tile(key_ref, scale_ref, ctr_ref,
                         pl.program_id(1), block_k)
    # contract the generated tile against the resident W tile: the encoded
    # parity row (R @ W) is never formed — only its product with x
    wx = jnp.dot(w_ref[...], x_ref[...],
                 preferred_element_type=jnp.float32,
                 precision=F32_PRECISION)                 # (bk, C)
    acc_ref[...] += jnp.dot(r_blk, wx,
                            preferred_element_type=jnp.float32,
                            precision=F32_PRECISION)

    @pl.when(pl.program_id(1) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "block_k", "interpret"))
def gen_parity_matvec_pallas(key: jnp.ndarray, scale: jnp.ndarray,
                             ctrs: jnp.ndarray, w: jnp.ndarray,
                             x: jnp.ndarray, *,
                             block_rows: int = 128, block_k: int = 128,
                             interpret: bool = False) -> jnp.ndarray:
    """Generated-parity products y = R_gen @ (W @ x), WR never stored.

    ``ctrs`` (Rp, 1) packed parity-row counters, ``w`` (Lp, D) the
    device-resident systematic weights (zero rows pad L→Lp — generated
    values beyond L contract against them to exactly zero), ``x`` (D, C).
    Grid (Rp/block_rows, Lp/block_k): each step derives one R tile from
    the counters, multiplies the matching W tile into x, and accumulates
    — the per-tile memory high-water is one (block_rows, block_k) R tile
    in registers/VMEM instead of a resident (n_parity, D) ``WR`` mirror.
    """
    Rp = ctrs.shape[0]
    Lp, D = w.shape
    assert Rp % block_rows == 0 and Lp % block_k == 0
    k_steps = Lp // block_k
    C = x.shape[1]
    return pl.pallas_call(
        functools.partial(_gen_matvec_kernel, k_steps=k_steps,
                          block_k=block_k),
        grid=(Rp // block_rows, k_steps),
        in_specs=[
            pl.BlockSpec((1, 2), lambda i, k: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, k: (0, 0)),
            pl.BlockSpec((block_rows, 1), lambda i, k: (i, 0)),
            pl.BlockSpec((block_k, D), lambda i, k: (k, 0)),
            pl.BlockSpec((D, C), lambda i, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, C), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, C), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_rows, C), jnp.float32)],
        interpret=interpret,
    )(key, scale, ctrs, w, x)
