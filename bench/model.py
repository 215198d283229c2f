"""The benchmark's side of the model: its sizes, its weights and the plain
float32 reference forward.  Nothing here imports the program.

Sizes come from a configuration file in ``configs/`` (transformers-style
keys).  Weights are made here, from ``--seed``, on the device, in one
jitted call, in the dtype the configuration serves (``torch_dtype``), in
the parameter layout the program's decoder stack takes: a token table, an
untied output head, one scanned block of ``num_hidden_layers`` stacked
layers (RMSNorm, GQA attention with rotate-half RoPE, a SwiGLU or squared
ReLU FFN), a final RMSNorm.  The harness hands the same arrays to the
program and, after the window, to :func:`reference_logits`.

The reference is straightforward ``jax.numpy`` in float32 at
``Precision.HIGHEST``: no kernel, no cache, no batching tricks; attention
is the plain masked softmax, taken over blocks of queries so that long
prompts fit.  :func:`control_logits` is the same forward with every
matmul's two operands rounded to float8_e4m3fn (scaled per row and per
output column): the control, one precision below the served bfloat16.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import math
import os
from typing import Dict

import jax
import jax.numpy as jnp

HERE = os.path.dirname(os.path.abspath(__file__))
HI = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class Sizes:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    act: str              # "silu" (SwiGLU) | "relu2" (squared ReLU)
    rope_theta: float
    eps: float
    dtype: str


def load_config(name: str, root: str = HERE) -> Dict:
    with open(os.path.join(root, "configs", f"{name}.json")) as f:
        return json.load(f)


def sizes_of(cfg: Dict) -> Sizes:
    # the decoder this forward (and the program's trunk) computes: RoPE over
    # the whole head, no attention bias
    if float(cfg["partial_rotary_factor"]) != 1.0 or cfg["attention_bias"]:
        raise ValueError(f"{cfg['name']}: only a whole-head RoPE without "
                         "attention bias is run")
    return Sizes(layers=int(cfg["num_hidden_layers"]), d=int(cfg["hidden_size"]),
                 heads=int(cfg["num_attention_heads"]),
                 kv_heads=int(cfg["num_key_value_heads"]),
                 head_dim=int(cfg["head_dim"]),
                 d_ff=int(cfg["intermediate_size"]),
                 vocab=int(cfg["vocab_size"]), act=str(cfg["hidden_act"]),
                 rope_theta=float(cfg["rope_theta"]),
                 eps=float(cfg["rms_norm_eps"]),
                 dtype=str(cfg["torch_dtype"]))


# ---------------------------------------------------------------------------
# weights
# ---------------------------------------------------------------------------

def _layer_shapes(s: Sizes) -> Dict:
    ffn = {"w_in": (s.d, s.d_ff), "w_out": (s.d_ff, s.d)}
    if s.act == "silu":
        ffn["w_gate"] = (s.d, s.d_ff)
    return {"norm1": (s.d,), "norm2": (s.d,),
            "mixer": {"wq": (s.d, s.heads, s.head_dim),
                      "wk": (s.d, s.kv_heads, s.head_dim),
                      "wv": (s.d, s.kv_heads, s.head_dim),
                      "wo": (s.heads, s.head_dim, s.d)},
            "ffn": ffn}


def _scale(name: str, s: Sizes) -> float:
    return {"wq": 1 / math.sqrt(s.d), "wk": 1 / math.sqrt(s.d),
            "wv": 1 / math.sqrt(s.d), "wo": 1 / math.sqrt(s.heads * s.head_dim),
            "w_in": 1 / math.sqrt(s.d), "w_gate": 1 / math.sqrt(s.d),
            "w_out": 1 / math.sqrt(s.d_ff)}[name]


def make_params(s: Sizes, seed: int):
    """All weights from ``seed`` in one jitted call on the default device:
    N(0, scale²) matrices, unit norm gains, in the served dtype.  Stacked
    layer weights are drawn one layer at a time (``lax.map``) so that no
    float32 temporary of a whole stack is ever live."""
    dt = jnp.dtype(s.dtype)
    shapes = _layer_shapes(s)
    names = [("mixer", k) for k in shapes["mixer"]] + \
        [("ffn", k) for k in shapes["ffn"]]

    def one_layer(key):
        ks = jax.random.split(key, len(names))
        out = {"norm1": jnp.ones((s.d,), dt), "norm2": jnp.ones((s.d,), dt),
               "mixer": {}, "ffn": {}}
        for k, (grp, nm) in zip(ks, names):
            out[grp][nm] = (jax.random.normal(k, shapes[grp][nm], jnp.float32)
                            * _scale(nm, s)).astype(dt)
        return out

    def build(key):
        k_tok, k_out, k_layers = jax.random.split(key, 3)
        tok = (jax.random.normal(k_tok, (s.vocab, s.d), jnp.float32) * 0.02).astype(dt)
        out = (jax.random.normal(k_out, (s.d, s.vocab), jnp.float32)
               / math.sqrt(s.d)).astype(dt)
        layers = jax.lax.map(one_layer, jax.random.split(k_layers, s.layers))
        return {"embed": {"tok": tok, "out": out},
                "blocks": {"layer0": layers},
                "final_norm": jnp.ones((s.d,), dt)}

    seed = int(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0xFFFFFFFF)
    return jax.jit(build)(key)


# ---------------------------------------------------------------------------
# reference forward
# ---------------------------------------------------------------------------

def _qdq(x, axis):
    """Round ``x`` to float8_e4m3fn, scaled so that the largest magnitude
    along ``axis`` (the contraction axis) maps to e4m3's largest (448)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    sc = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / sc).astype(jnp.float8_e4m3fn).astype(jnp.float32) * sc


def _mm(spec, a, b, fp8, a_axis, b_axis):
    if fp8:
        a, b = _qdq(a, a_axis), _qdq(b, b_axis)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, :, None].astype(jnp.float32) * freqs            # (B, T, half)
    c, s_ = jnp.cos(ang)[:, :, None], jnp.sin(ang)[:, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s_, x1 * s_ + x2 * c], -1)


def _layer(x, w, s: Sizes, fp8: bool, block_q: int):
    f32 = lambda a: a.astype(jnp.float32)
    B, T, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(T), (B, T))
    h = _rms(x, f32(w["norm1"]), s.eps)
    mx = w["mixer"]
    q = _rope(_mm("btd,dhx->bthx", h, f32(mx["wq"]), fp8, -1, 0), pos, s.rope_theta)
    k = _rope(_mm("btd,dhx->bthx", h, f32(mx["wk"]), fp8, -1, 0), pos, s.rope_theta)
    v = _mm("btd,dhx->bthx", h, f32(mx["wv"]), fp8, -1, 0)
    G = s.heads // s.kv_heads
    k = jnp.repeat(k, G, axis=2)            # query head h reads kv head h // G
    v = jnp.repeat(v, G, axis=2)
    outs = []
    for q0 in range(0, T, block_q):
        qb = q[:, q0:q0 + block_q]
        sc = jnp.einsum("bqhx,bkhx->bhqk", qb, k, precision=HI) / math.sqrt(s.head_dim)
        qi = q0 + jnp.arange(qb.shape[1])[:, None]
        sc = jnp.where(jnp.arange(T)[None, :] <= qi, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("bhqk,bkhx->bqhx", p, v, precision=HI))
    o = jnp.concatenate(outs, axis=1)
    x = x + _mm("bthx,hxd->btd", o, f32(mx["wo"]), fp8, (-2, -1), (0, 1))
    h2 = _rms(x, f32(w["norm2"]), s.eps)
    ff = w["ffn"]
    u = _mm("btd,df->btf", h2, f32(ff["w_in"]), fp8, -1, 0)
    if s.act == "silu":
        g = _mm("btd,df->btf", h2, f32(ff["w_gate"]), fp8, -1, 0)
        u = jax.nn.silu(g) * u
    elif s.act == "relu2":
        u = jnp.square(jax.nn.relu(u))
    else:
        raise ValueError(f"unknown hidden_act {s.act!r}")
    return x + _mm("btf,fd->btd", u, f32(ff["w_out"]), fp8, -1, 0)


def reference_logits(params, tokens, s: Sizes, *, block_q: int = 512):
    """(B, T) token ids → (B, T, vocab) float32 logits, one jitted program
    per layer (the weights of the layer sliced inside it), so that only one
    layer's float32 copy is ever live."""
    return _forward(params, tokens, s, False, block_q)


def control_logits(params, tokens, s: Sizes, *, block_q: int = 512):
    """:func:`reference_logits` with every matmul's operands rounded to
    float8_e4m3fn: the control, which the check has to read as not
    correct."""
    return _forward(params, tokens, s, True, block_q)


def _forward(params, tokens, s: Sizes, fp8: bool, block_q: int):
    layer, head = _programs(s, fp8, block_q)
    x = jnp.take(params["embed"]["tok"], jnp.asarray(tokens, jnp.int32),
                 axis=0).astype(jnp.float32)
    for i in range(s.layers):
        x = layer(x, params["blocks"]["layer0"], jnp.int32(i))
    return head(x, params["final_norm"], params["embed"]["out"])


@functools.lru_cache(maxsize=None)
def _programs(s: Sizes, fp8: bool, block_q: int):
    layer = jax.jit(lambda x, st, i: _layer(
        x, jax.tree.map(lambda a: a[i], st), s, fp8, block_q))
    head = jax.jit(lambda x, g, w: _mm(
        "btd,dv->btv", _rms(x, g.astype(jnp.float32), s.eps),
        w.astype(jnp.float32), fp8, -1, 0))
    return layer, head


@jax.jit
def gaps_of(logits, targets):
    """Per position: the reference's best logit minus its logit of
    ``targets`` (≥ 0; 0 where the target is the reference's argmax)."""
    best = logits.max(axis=-1)
    got = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return best - got
