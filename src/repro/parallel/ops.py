"""Sharding-aware primitive ops used inside the model.

* ``sharded_embed`` — token embedding against a vocab-sharded table via
  shard_map masked-gather + psum (the standard TP embedding; avoids XLA's
  involuntary full-remat fallback for gathers over a sharded dim).
* ``token_nll`` — cross-entropy against vocab-sharded logits without
  ``take_along_axis`` over the sharded axis (iota-compare trick; the
  softmax's max/sum reductions lower to small all-reduces).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["sharded_embed", "token_nll"]


def sharded_embed(table: jnp.ndarray, tokens: jnp.ndarray,
                  mesh: Optional[Mesh], model_axis: str = "model",
                  data_axes: Optional[tuple] = None) -> jnp.ndarray:
    """tokens (B, T) → (B, T, d) with table (V, d) sharded on V."""
    if mesh is None or model_axis not in mesh.axis_names \
            or table.shape[0] % mesh.shape[model_axis]:
        return jnp.take(table, tokens, axis=0)
    daxes = data_axes or tuple(a for a in mesh.axis_names if a != model_axis)
    S = mesh.shape[model_axis]
    rows = table.shape[0] // S
    import numpy as np
    dp = int(np.prod([mesh.shape[a] for a in daxes])) if daxes else 1
    shardable = tokens.shape[0] % dp == 0 and dp > 1
    tok_spec = P(daxes) if shardable else P()

    def emb(tab, tok):
        r = jax.lax.axis_index(model_axis)
        lo = r * rows
        idx = jnp.clip(tok - lo, 0, rows - 1)
        out = jnp.take(tab, idx, axis=0)
        ok = (tok >= lo) & (tok < lo + rows)
        out = jnp.where(ok[..., None], out, 0)
        return jax.lax.psum(out, model_axis)

    out_spec = P(daxes, None, None) if shardable else P(None, None, None)
    # replication checking off: the psum body is not closed under it
    return jax.shard_map(
        emb, mesh=mesh,
        in_specs=(P(model_axis, None), tok_spec),
        out_specs=out_spec, check_vma=False,
    )(table, tokens)


def token_nll(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    """-log p(labels) per token; safe when the vocab axis is sharded.

    logits (B, T, V) any dtype; labels (B, T) int32 → (B, T) float32."""
    lg = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(lg.max(axis=-1, keepdims=True))
    shifted = lg - m
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, lg.shape, dimension=2)
    picked = jnp.sum(jnp.where(vocab_iota == labels[..., None], shifted, 0.0),
                     axis=-1)
    return lse - picked
