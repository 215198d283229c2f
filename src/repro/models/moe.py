"""Mixture-of-Experts layer with capacity-bounded expert-choice dispatch and
an expert-parallel (EP) all-to-all path.

Dispatch: tokens pick their top-k experts (token choice); each expert then
keeps its top-C tokens by router probability (capacity dropping by lowest
affinity, not arrival order — strictly better than Switch-style dropping and
the same scheme DeepSeek's aux-loss-free balancing approximates).

Why this shape: the (T, E) score matrix is tiny compared to a (T, E, C)
one-hot dispatch tensor, and per-expert ``top_k`` + ``take`` lowers to
gathers that the SPMD partitioner handles without materializing anything
token-quadratic.

Paper tie-in (DESIGN.md §2): expert capacity is exactly a Theorem-1 load
allocation — experts are "workers" with unit-delay θ_e and the capacity
vector can be reweighted by ``repro.parallel.hetero`` for heterogeneous
expert shards.

EP path: under ``shard_map`` the expert axis is sharded over the "model"
mesh axis; per-device expert buffers are exchanged with two all-to-alls
(dispatch + return), the canonical MoE collective pattern on TPU pods.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from .config import ArchConfig, MoEConfig

__all__ = ["init_moe", "apply_moe", "moe_capacity"]


def moe_capacity(m: MoEConfig, n_tokens: int) -> int:
    c = int(math.ceil(n_tokens * m.top_k / m.num_experts * m.capacity_factor))
    c = max(8, -(-c // 8) * 8)      # pad to a sublane multiple
    return min(c, n_tokens)         # never more slots than tokens


def init_moe(rng, cfg: ArchConfig, dtype) -> dict:
    m = cfg.moe
    d, f, E = cfg.d_model, m.d_expert, m.num_experts
    keys = jax.random.split(rng, 7)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p = {
        "router": jax.random.normal(keys[0], (d, E), jnp.float32) * s_in,
        "w_in": jax.random.normal(keys[1], (E, d, f), dtype) * s_in,
        "w_gate": jax.random.normal(keys[2], (E, d, f), dtype) * s_in,
        "w_out": jax.random.normal(keys[3], (E, f, d), dtype) * s_out,
    }
    if m.n_shared:
        p["shared_in"] = jax.random.normal(keys[4], (d, m.n_shared * f), dtype) * s_in
        p["shared_gate"] = jax.random.normal(keys[5], (d, m.n_shared * f), dtype) * s_in
        p["shared_out"] = jax.random.normal(keys[6], (m.n_shared * f, d), dtype) * s_out
    return p


def _expert_ffn(w_in, w_gate, w_out, xs):
    """xs: (E, C, d) → (E, C, d), SwiGLU experts."""
    h = jnp.einsum("ecd,edf->ecf", xs, w_in)
    g = jnp.einsum("ecd,edf->ecf", xs, w_gate)
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, w_out)


def _dispatch(probs: jnp.ndarray, top_k: int, capacity: int):
    """Expert-choice-of-token-choice dispatch tables.

    probs: (T, E) router probabilities.  Returns (idx, weight):
      idx    (E, C) token index each expert processes,
      weight (E, C) combine weight (0 where the slot is empty/dropped).
    """
    T, E = probs.shape
    topv, topi = jax.lax.top_k(probs, top_k)              # (T, k)
    chosen = jnp.zeros((T, E), probs.dtype)
    chosen = jax.vmap(lambda row, idx, val: row.at[idx].set(val))(
        chosen, topi, topv)                               # (T, E) sparse scores
    score_te = chosen.T                                    # (E, T)
    w, idx = jax.lax.top_k(score_te, capacity)             # (E, C)
    return idx, w


def apply_moe(params: dict, x: jnp.ndarray, *, cfg: ArchConfig,
              mesh: Optional[jax.sharding.Mesh] = None,
              model_axis: str = "model", ep_full: bool = False,
              a2a_fp8: bool = False) -> jnp.ndarray:
    """x: (B, T, d) → (B, T, d).

    With ``mesh`` the dispatch runs under shard_map with the expert axis
    sharded on ``model_axis`` (two all-to-alls); without it, a single-device
    reference path (smoke tests / CPU).

    ``ep_full`` (hillclimb lever): experts sharded over the data axes AND
    their hidden width over the model axis — expert weights become fully
    mesh-sharded (no FSDP all-gather), dispatch all-to-alls run over the
    data axes, and one psum over the model axis reduces the split-f expert
    product.  Requires num_experts % dp == 0 and enough tokens.
    """
    m = cfg.moe
    B, T, d = x.shape
    xf = x.reshape(B * T, d)
    n_tok = B * T

    def local_moe(xt, router, w_in, w_gate, w_out):
        probs = jax.nn.softmax(xt.astype(jnp.float32) @ router, axis=-1)
        cap = moe_capacity(m, xt.shape[0])
        idx, w = _dispatch(probs, m.top_k, cap)            # (E, C)
        xs = jnp.take(xt, idx.reshape(-1), axis=0).reshape(
            m.num_experts, cap, d)
        ys = _expert_ffn(w_in, w_gate, w_out, xs)
        ys = ys * w[..., None].astype(ys.dtype)
        out = jnp.zeros_like(xt).at[idx.reshape(-1)].add(
            ys.reshape(-1, d), mode="drop")
        return out

    if mesh is None or model_axis not in mesh.axis_names:
        out = local_moe(xf, params["router"], params["w_in"],
                        params["w_gate"], params["w_out"])
    else:
        from jax.sharding import PartitionSpec as P
        import numpy as np
        S = mesh.shape[model_axis]
        Eps = m.num_experts // S
        data_axes = tuple(a for a in mesh.axis_names if a != model_axis)
        dp = int(np.prod([mesh.shape[a] for a in data_axes]))
        tokens_per_shard = n_tok // max(dp, 1)

        def ep_small(xt, router, w_in, w_gate, w_out):
            # Decode-scale token counts: tokens replicated over the model
            # axis, each rank runs its local experts on all of them, psum
            # combines.  One small all-reduce instead of all-to-alls.
            r = jax.lax.axis_index(model_axis)
            probs = jax.nn.softmax(xt.astype(jnp.float32) @ router, -1)
            T_loc = xt.shape[0]
            topv, topi = jax.lax.top_k(probs, m.top_k)
            chosen = jnp.zeros((T_loc, m.num_experts), probs.dtype)
            chosen = jax.vmap(lambda row, i, v: row.at[i].set(v))(
                chosen, topi, topv)
            my = jax.lax.dynamic_slice_in_dim(chosen, r * Eps, Eps, axis=1)
            cap = moe_capacity(m, T_loc)
            w, idx = jax.lax.top_k(my.T, cap)              # (Eps, C)
            xs = jnp.take(xt, idx.reshape(-1), 0).reshape(Eps, cap, d)
            ys = _expert_ffn(w_in, w_gate, w_out, xs)
            ys = ys * w[..., None].astype(ys.dtype)
            out = jnp.zeros_like(xt).at[idx.reshape(-1)].add(
                ys.reshape(-1, d), mode="drop")
            return jax.lax.psum(out, model_axis)

        def ep_moe(xt, router, w_in, w_gate, w_out):
            # xt: (T_loc, d) tokens of this data shard (replicated over model
            # axis entry: we slice our model-rank's token chunk instead).
            r = jax.lax.axis_index(model_axis)
            t_chunk = xt.shape[0] // S
            xt_loc = jax.lax.dynamic_slice_in_dim(xt, r * t_chunk, t_chunk, 0)
            probs = jax.nn.softmax(xt_loc.astype(jnp.float32) @ router, -1)
            cap = moe_capacity(m, t_chunk)
            idx, w = _dispatch(probs, m.top_k, cap)        # (E, C)
            xs = jnp.take(xt_loc, idx.reshape(-1), 0).reshape(
                m.num_experts, cap, d)
            # dispatch all-to-all: (S, Eps, C, d) → experts gather their slice
            xs = xs.reshape(S, Eps, cap, d)
            xs = jax.lax.all_to_all(xs, model_axis, split_axis=0,
                                    concat_axis=0, tiled=False)
            # now (S, Eps, C, d): tokens from every source shard for MY experts
            xs = xs.transpose(1, 0, 2, 3).reshape(Eps, S * cap, d)
            ys = _expert_ffn(w_in, w_gate, w_out, xs)
            ys = ys.reshape(Eps, S, cap, d).transpose(1, 0, 2, 3)
            ys = jax.lax.all_to_all(ys, model_axis, split_axis=0,
                                    concat_axis=0, tiled=False)
            ys = ys.reshape(m.num_experts, cap, d) * w[..., None].astype(ys.dtype)
            out_loc = jnp.zeros_like(xt_loc).at[idx.reshape(-1)].add(
                ys.reshape(-1, d), mode="drop")
            # reassemble the full token block across the model axis
            out = jax.lax.all_gather(out_loc, model_axis, axis=0, tiled=True)
            return out

        def ep_full_body(xt, router, w_in, w_gate, w_out):
            # xt (T_loc, d) identical across model ranks; w_* blocks are
            # (E/dp, d, f/tp).  Dispatch is duplicated across model ranks
            # (cheap); expert matmuls split f over the model axis.
            probs = jax.nn.softmax(xt.astype(jnp.float32) @ router, -1)
            T_loc = xt.shape[0]
            cap = moe_capacity(m, T_loc)
            idx, w = _dispatch(probs, m.top_k, cap)          # (E, C)
            xs = jnp.take(xt, idx.reshape(-1), 0).reshape(
                m.num_experts, cap, d)
            Edp = m.num_experts // dp
            xs = xs.reshape(dp, Edp, cap, d)
            if a2a_fp8:
                # DeepSeek-V3-style fp8 dispatch: halve the dominant
                # all-to-all payload (combine stays bf16 for accuracy)
                xs = xs.astype(jnp.float8_e4m3fn)
            xs = jax.lax.all_to_all(xs, data_axes, split_axis=0,
                                    concat_axis=0, tiled=False)
            xs = xs.astype(x.dtype)
            xs = xs.transpose(1, 0, 2, 3).reshape(Edp, dp * cap, d)
            h = jnp.einsum("ecd,edf->ecf", xs, w_in)
            g = jnp.einsum("ecd,edf->ecf", xs, w_gate)
            ys = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * h, w_out)
            ys = jax.lax.psum(ys, model_axis)                # reduce f shards
            ys = ys.reshape(Edp, dp, cap, d).transpose(1, 0, 2, 3)
            ys = jax.lax.all_to_all(ys, data_axes, split_axis=0,
                                    concat_axis=0, tiled=False)
            ys = ys.reshape(m.num_experts, cap, d) * w[..., None].astype(ys.dtype)
            out = jnp.zeros_like(xt).at[idx.reshape(-1)].add(
                ys.reshape(-1, d), mode="drop")
            return out

        use_full = (ep_full and m.num_experts % dp == 0
                    and tokens_per_shard >= dp and n_tok % dp == 0)
        if use_full:
            body = ep_full_body
            # (E, d, f) in/gate split f on model; (E, f, d) out splits f=dim1
            wspec_in = P(data_axes, None, model_axis)
            wspec_out = P(data_axes, model_axis, None)
        else:
            body = ep_moe if tokens_per_shard >= S else ep_small
            wspec_in = wspec_out = P(model_axis)
        # batch-of-1 decode can't shard the token axis at all: replicate
        xspec = P(data_axes) if (n_tok % dp == 0 and n_tok >= dp) else P()
        # replication checking off: the psum/all_to_all bodies are not
        # closed under it
        out = jax.shard_map(
            body, mesh=mesh,
            in_specs=(xspec, P(), wspec_in, wspec_in, wspec_out),
            out_specs=xspec, check_vma=False,
        )(xf, params["router"], params["w_in"], params["w_gate"],
          params["w_out"])

    if m.n_shared:
        h = jnp.einsum("td,df->tf", xf, params["shared_in"])
        g = jnp.einsum("td,df->tf", xf, params["shared_gate"])
        out = out + jnp.einsum("tf,fd->td", jax.nn.silu(g) * h,
                               params["shared_out"])
    return out.reshape(B, T, d)
