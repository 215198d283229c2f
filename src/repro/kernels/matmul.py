"""Tiled matmul Pallas kernel — the shared engine for MDS encoding (G @ A)
and the per-worker coded products (Ã_n @ X).

TPU adaptation (DESIGN.md §2): blocks are MXU-aligned (multiples of 128 on
the contracting/lane dims), partial products accumulate in a float32 VMEM
scratch across the k-grid, and the output is written once on the final k
step.  Grid order is (i, j, k) with k innermost, so each output tile stays
resident in VMEM for its whole reduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["matmul_pallas", "DEFAULT_BLOCK"]

DEFAULT_BLOCK = (128, 128, 128)  # (bm, bn, bk)

#: float32 operands contract at full float32 precision on the MXU (Mosaic
#: and XLA may otherwise take fewer bf16 passes): coded products feed an
#: MDS decode that amplifies product error by the parity block's condition
#: number.  Argued, not measured: no chip run has yet compared it with the
#: default precision against the decode tolerance, or timed its cost.
F32_PRECISION = jax.lax.Precision.HIGHEST


def _matmul_kernel(a_ref, b_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32,
                            precision=F32_PRECISION)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def matmul_pallas(a: jnp.ndarray, b: jnp.ndarray,
                  block: tuple[int, int, int] = DEFAULT_BLOCK,
                  interpret: bool = False) -> jnp.ndarray:
    """C = A @ B via a VMEM-tiled Pallas kernel.

    A: (M, K), B: (K, N) → C: (M, N).  Shapes must be divisible by ``block``
    (the ops.py wrappers pad); accumulation is float32 regardless of input
    dtype.
    """
    (M, K), (K2, N) = a.shape, b.shape
    assert K == K2, (a.shape, b.shape)
    bm, bn, bk = block
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (a.shape, b.shape, block)
    k_steps = K // bk

    return pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps),
        grid=(M // bm, N // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(a, b)
