"""Per-worker coded product kernel: y = Ã_n @ X (paper §II worker compute).

Each worker holds its slice Ã_n (l_n, S) resident and multiplies incoming
model vectors X (S, B) (B = 1 for matrix-vector, B > 1 for the iterated /
batched tasks of the paper's Remark 2).  The kernel keeps the X tile in VMEM
across the whole row-block sweep and accumulates in float32.

Grid is (rows, k) with k innermost — each output row-block's reduction
finishes before moving on, so only one (bm, B) accumulator tile lives in
VMEM at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .matmul import F32_PRECISION

__all__ = ["coded_matvec_pallas"]


def _matvec_kernel(a_ref, x_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], x_ref[...],
                            preferred_element_type=jnp.float32,
                            precision=F32_PRECISION)

    @pl.when(pl.program_id(1) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "block_k", "interpret"))
def coded_matvec_pallas(a_tilde: jnp.ndarray, x: jnp.ndarray,
                        block_rows: int = 128, block_k: int = 128,
                        interpret: bool = False) -> jnp.ndarray:
    """y = Ã @ X;  Ã (L, S), X (S, B) → y (L, B).

    L % block_rows == 0 and S % block_k == 0 required (ops.py pads); B is
    kept whole in VMEM (pad to a lane multiple for real-TPU efficiency).
    """
    (L, S), (S2, B) = a_tilde.shape, x.shape
    assert S == S2, (a_tilde.shape, x.shape)
    assert L % block_rows == 0 and S % block_k == 0
    k_steps = S // block_k

    return pl.pallas_call(
        functools.partial(_matvec_kernel, k_steps=k_steps),
        grid=(L // block_rows, k_steps),
        in_specs=[
            pl.BlockSpec((block_rows, block_k), lambda i, k: (i, k)),
            pl.BlockSpec((block_k, B), lambda i, k: (k, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, B), lambda i, k: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((L, B), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_rows, B), jnp.float32)],
        interpret=interpret,
    )(a_tilde, x)
