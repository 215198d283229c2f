"""Public jit'd wrappers around the Pallas kernels.

These handle shape padding to MXU-aligned blocks, (S,) vs (S,B) vector
conventions, systematic-generator fast paths, and the interpret switch
(interpret=True on CPU so the kernels run everywhere; real lowering on TPU).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ref
from ..core import mds
from ..obs import device_span
from .coded_matvec import coded_matvec_pallas
from .matmul import F32_PRECISION, matmul_pallas
from .mds_encode import (counter_parity_rows_pallas, gen_parity_matvec_pallas,
                         mds_encode_pallas)
from .wkv6 import wkv6_pallas

__all__ = ["matmul", "mds_encode", "mds_encode_batch", "coded_matvec",
           "coded_matvec_batch", "coded_shard_matmul_batch",
           "counter_parity_rows", "gen_parity_products", "GeneratedParity",
           "wkv6", "default_interpret"]


def default_interpret() -> bool:
    """Pallas interpret mode unless we are actually on TPU."""
    return jax.default_backend() != "tpu"


def _pad_to(x: jnp.ndarray, axis: int, mult: int) -> jnp.ndarray:
    rem = (-x.shape[axis]) % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, rem)
    return jnp.pad(x, pad)


def matmul(a: jnp.ndarray, b: jnp.ndarray, *, block=(128, 128, 128),
           interpret: bool | None = None) -> jnp.ndarray:
    """C = A @ B, padding both operands up to the block grid."""
    interpret = default_interpret() if interpret is None else interpret
    M, K = a.shape
    N = b.shape[1]
    bm, bn, bk = block
    ap = _pad_to(_pad_to(a, 0, bm), 1, bk)
    bp = _pad_to(_pad_to(b, 0, bk), 1, bn)
    out = matmul_pallas(ap, bp, block=block, interpret=interpret)
    return out[:M, :N]


def mds_encode(g: jnp.ndarray, a: jnp.ndarray, *, systematic: bool = True,
               block=(128, 128, 128),
               interpret: bool | None = None) -> jnp.ndarray:
    """Ã = G @ A.  With ``systematic`` the identity prefix is copied through
    and only the parity rows hit the MXU (halves encode FLOPs at the default
    2× redundancy)."""
    interpret = default_interpret() if interpret is None else interpret
    L = g.shape[1]
    if systematic and g.shape[0] > L:
        parity = matmul(g[L:], a, block=block, interpret=interpret)
        return jnp.concatenate([a.astype(parity.dtype), parity], axis=0)
    return matmul(g, a, block=block, interpret=interpret)


def mds_encode_batch(g: jnp.ndarray, a: jnp.ndarray, *,
                     systematic: bool = True, block=(128, 128, 128),
                     interpret: bool | None = None) -> jnp.ndarray:
    """Batched Ã_b = G_b @ A_b over a leading task/master axis.

    ``g`` is (B, L̃, L) per-task generators or a shared (L̃, L); ``a`` is
    (B, L, S).  ``vmap`` of the Pallas call adds a grid dimension, so the
    whole stack is one kernel launch."""
    interpret = default_interpret() if interpret is None else interpret
    enc = functools.partial(mds_encode, systematic=systematic, block=block,
                            interpret=interpret)
    if g.ndim == 2:
        return jax.vmap(lambda ab: enc(g, ab))(a)
    return jax.vmap(enc)(g, a)


def coded_matvec_batch(a_tilde: jnp.ndarray, x: jnp.ndarray, *,
                       block_rows: int = 128, block_k: int = 128,
                       interpret: bool | None = None) -> jnp.ndarray:
    """Batched per-task coded products y_b = Ã_b @ x_b.

    ``a_tilde`` (B, L, S), ``x`` (B, S) or (B, S, C) → (B, L[, C])."""
    interpret = default_interpret() if interpret is None else interpret
    mv = functools.partial(coded_matvec, block_rows=block_rows,
                           block_k=block_k, interpret=interpret)
    return jax.vmap(mv)(a_tilde, x)


def _parity_key_arrays(key: Tuple[int, int],
                       L: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Layer key / scale as the (1, 2) uint32 + (1, 1) f32 kernel operands
    (array operands, so layers re-use one compiled kernel)."""
    key_arr = jnp.asarray(np.asarray(key, dtype=np.uint32)[None, :])
    scale = jnp.full((1, 1), np.float32(np.sqrt(3.0 / L)), jnp.float32)
    return key_arr, scale


def counter_parity_rows(key: Tuple[int, int], L: int, ctrs, *,
                        block_rows: int = 128, block_cols: int = 128,
                        interpret: bool | None = None) -> jnp.ndarray:
    """Counter-derived parity generator rows R[ctrs] (n, L) float32.

    The standalone in-kernel generator for encode/verify paths — pads the
    row counters up to the block grid and slices back; bit-identical to
    :func:`repro.core.mds.counter_parity_rows` for the same ``(key,
    ctrs)`` (the shared threefry tile arithmetic guarantees it).
    """
    interpret = default_interpret() if interpret is None else interpret
    ctrs = jnp.asarray(np.asarray(ctrs, dtype=np.uint32))[:, None]
    n = ctrs.shape[0]
    key_arr, scale = _parity_key_arrays(key, L)
    ctrs_p = _pad_to(ctrs, 0, block_rows)
    cols = -(-L // block_cols) * block_cols
    out = counter_parity_rows_pallas(key_arr, scale, ctrs_p, n_cols=cols,
                                     block_rows=block_rows,
                                     block_cols=block_cols,
                                     interpret=interpret)
    return out[:n, :L]


@functools.lru_cache(maxsize=None)
def _derive_rows_xla(L: int):
    """Jitted XLA twin of the parity-row derivation for off-TPU runs.

    Off-TPU the fused Pallas kernel only executes in interpret mode —
    Python-level emulation, orders of magnitude slower than the compiled
    materialised path it must keep pace with.  The counter tile
    arithmetic is backend-generic, so the same derivation runs as
    straight XLA ops (same threefry rounds, same fixed-order float32
    adds) — bit-identical rows by construction."""
    def f(key_arr, scale, ctrs):
        cols = jax.lax.broadcasted_iota(jnp.uint32, (1, L), 1)
        return mds.counter_gaussian_tile(key_arr[0, 0], key_arr[0, 1],
                                         ctrs, cols, scale)
    return jax.jit(f)


def _mm(a, b):
    return jnp.matmul(a, b, precision=F32_PRECISION)


@functools.lru_cache(maxsize=None)
def _gen_contract():
    return jax.jit(lambda r, w, x: _mm(r, _mm(w, x)))


#: steady-state serving replays one frozen counter schedule per plan
#: entry, so the derived R_gen coefficient rows (n, L) — NOT the encoded
#: WR mirror — are memoised on device across steps.  Bounded LRU; only
#: the off-TPU XLA path uses it (on TPU the fused kernel regenerates
#: in-VMEM for free).
GEN_ROWS_MEMO = 8
_gen_rows_memo: "dict[tuple, jnp.ndarray]" = {}


def _gen_rows_device(key: Tuple[int, int], ctrs: np.ndarray,
                     L: int) -> jnp.ndarray:
    mk = (int(key[0]), int(key[1]), int(L),
          np.asarray(ctrs, np.uint32).tobytes())
    r = _gen_rows_memo.pop(mk, None)
    if r is None:
        key_arr, scale = _parity_key_arrays(key, L)
        cj = jnp.asarray(np.asarray(ctrs, dtype=np.uint32))[:, None]
        r = _derive_rows_xla(L)(key_arr, scale, cj)
    _gen_rows_memo[mk] = r                     # re-insert: LRU order
    while len(_gen_rows_memo) > GEN_ROWS_MEMO:
        _gen_rows_memo.pop(next(iter(_gen_rows_memo)))
    return r


@functools.lru_cache(maxsize=None)
def _gen_vmap_step(n_specs: int):
    """One compiled step for vmap-mode generated parity: base tile
    matmul + every spec's ``R_gen @ (W @ x)`` + lane scatter, fused so
    the virtual path costs one dispatch like the materialised one."""
    def f(tiles, x, lanes, rs, ws):
        T, R, _ = tiles.shape
        flat = jax.vmap(lambda t: _mm(t, x))(tiles).reshape(T * R, -1)
        for i in range(n_specs):
            # x may carry zero rows padding D up to the tile width
            flat = flat.at[lanes[i]].set(
                _mm(rs[i], _mm(ws[i], x[:ws[i].shape[1]])).astype(flat.dtype))
        return flat.reshape(T, R, -1)
    return jax.jit(f)


def gen_parity_products(key: Tuple[int, int], ctrs, w: jnp.ndarray,
                        x: jnp.ndarray, *,
                        block_rows: int = 128, block_k: int = 128,
                        interpret: bool | None = None) -> jnp.ndarray:
    """Generated-parity shard products (n, C): ``R_gen[ctrs] @ (W @ x)``.

    ``w`` (L, D) float32 systematic weights (device-resident), ``x``
    (D', C) with D' ≥ D (rows past D are the zero padding of the packed
    tiles' contraction width).  The fused kernel derives each parity tile from the packed
    row counters and contracts it against W tile-by-tile — the virtual
    parity path's device execution, with no ``WR`` mirror in HBM.
    """
    interpret = default_interpret() if interpret is None else interpret
    ctrs_host = np.asarray(ctrs, dtype=np.uint32)
    ctrs = jnp.asarray(ctrs_host)[:, None]
    n = ctrs.shape[0]
    L, D = w.shape
    key_arr, scale = _parity_key_arrays(key, L)
    with device_span("gen_parity_products", cat="kernel",
                     args={"rows": int(n), "L": int(L)}) as fence:
        if interpret:
            r = _gen_rows_device(key, ctrs_host, L)
            out = fence(_gen_contract()(r, w, x[:D]))
        else:
            ctrs_p = _pad_to(ctrs, 0, block_rows)
            wp = _pad_to(_pad_to(w, 0, block_k), 1, 128)
            xp = _pad_to(x, 0, 128)[:wp.shape[1]]
            out = fence(gen_parity_matvec_pallas(
                key_arr, scale, ctrs_p, wp, xp, block_rows=block_rows,
                block_k=block_k, interpret=False))
    return out[:n]


@dataclasses.dataclass
class GeneratedParity:
    """Virtual-parity lane spec for one packed problem.

    ``lanes`` index into the flattened (T·R,) tile row space; their
    products come from the generated kernel instead of the materialised
    tiles (whose corresponding rows are zero-filled).  ``ctrs`` are the
    packed (row | draw << 24) counters — the per-row seed schedule frozen
    into the plan — and ``w`` the layer's device-resident systematic
    weights.
    """
    lanes: np.ndarray           # (n,) flat lane indices in tile space
    ctrs: np.ndarray            # (n,) packed parity-row counters (uint32)
    key: Tuple[int, int]        # per-layer threefry key
    w: jnp.ndarray              # (L, D) float32 systematic weights


def coded_shard_matmul_batch(tiles: jnp.ndarray, x: jnp.ndarray, *,
                             block_rows: int = 128, block_k: int = 128,
                             mode: str = "pallas",
                             parity_mode: str = "materialized",
                             parity: Optional[Sequence[GeneratedParity]]
                             = None,
                             interpret: bool | None = None) -> jnp.ndarray:
    """Every packed shard tile of a serving step against one operand, in
    one pass: ``tiles`` (T, R, K) 128-aligned encoded-row tiles (the
    ragged per-worker shard slices of a whole step barrier, bucketed and
    zero-padded by ``repro.serve_coded.packing``), ``x`` (K, C) the shared
    right-hand activations → (T, R, C).

    ``mode="pallas"`` flattens the tile axis into the row grid of the
    ``coded_matvec`` kernel — because R and K are already block-aligned,
    the whole stack is exactly one kernel launch with a (T·R/block_rows,
    K/block_k) grid (the same block layout ``coded_matvec_batch`` uses,
    without the vmap-added grid dimension).  ``mode="vmap"`` is the plain
    jnp fallback for the jax backend.  Per-row results are independent of
    the tile bucketing (each output row is one dot), which is what lets
    the packing layer re-bucket ragged shards freely.

    ``parity_mode="generated"`` is the virtual-parity execution: parity
    lanes are zero rows in ``tiles`` and each :class:`GeneratedParity`
    entry of ``parity`` re-derives those lanes' products through the
    fused :func:`gen_parity_products` kernel (threefry counters against
    the layer's device-resident W) — the encoded parity rows never exist
    in HBM.  ``"materialized"`` (default) reads every lane from the
    tiles, exactly the historical behaviour.
    """
    interpret = default_interpret() if interpret is None else interpret
    T, R, K = tiles.shape
    if mode not in ("vmap", "pallas"):
        raise ValueError(f"unknown mode {mode!r}; expected pallas | vmap")
    if parity_mode not in ("materialized", "generated"):
        raise ValueError(f"unknown parity_mode {parity_mode!r}; expected "
                         f"materialized | generated")
    if mode == "pallas" and (R % block_rows or K % block_k):
        raise ValueError(f"tiles must be block-aligned, got R={R} K={K} "
                         f"for block ({block_rows}, {block_k})")
    gen = parity_mode == "generated" and parity
    # the exit fence (block_until_ready) only engages while a tracer is
    # recording; the untraced path keeps jax's async dispatch
    with device_span("coded_shard_matmul_batch", cat="kernel",
                     args={"tiles": T, "rows": T * R, "k": K, "mode": mode,
                           "parity_mode": parity_mode}) as fence:
        if gen and mode == "vmap" and interpret:
            # one compiled dispatch: base matmul + generated lanes, with
            # the derived R_gen rows memoised across steps of the plan
            specs = list(parity)
            lanes = tuple(jnp.asarray(np.asarray(s.lanes, dtype=np.int64))
                          for s in specs)
            rs = tuple(_gen_rows_device(s.key, s.ctrs, s.w.shape[0])
                       for s in specs)
            ws = tuple(s.w for s in specs)
            return fence(_gen_vmap_step(len(specs))(tiles, x, lanes,
                                                    rs, ws))
        if mode == "vmap":
            out = fence(jax.vmap(lambda t: _mm(t, x))(tiles))
        else:
            flat = coded_matvec_pallas(tiles.reshape(T * R, K), x,
                                       block_rows=block_rows,
                                       block_k=block_k, interpret=interpret)
            out = fence(flat.reshape(T, R, -1))
    if not gen:
        return out
    flat = out.reshape(T * R, -1)
    for spec in parity:
        yp = gen_parity_products(spec.key, spec.ctrs, spec.w, x,
                                 block_rows=block_rows, block_k=block_k,
                                 interpret=interpret)
        flat = flat.at[jnp.asarray(np.asarray(spec.lanes,
                                              dtype=np.int64))].set(
            yp.astype(flat.dtype))
    return flat.reshape(T, R, -1)


def coded_matvec(a_tilde: jnp.ndarray, x: jnp.ndarray, *,
                 block_rows: int = 128, block_k: int = 128,
                 interpret: bool | None = None) -> jnp.ndarray:
    """y = Ã @ x for x (S,) or (S, B); pads rows/contraction, keeps B whole."""
    interpret = default_interpret() if interpret is None else interpret
    squeeze = x.ndim == 1
    xm = x[:, None] if squeeze else x
    L, S = a_tilde.shape
    ap = _pad_to(_pad_to(a_tilde, 0, block_rows), 1, block_k)
    xp = _pad_to(xm, 0, block_k)
    y = coded_matvec_pallas(ap, xp, block_rows=block_rows, block_k=block_k,
                            interpret=interpret)[:L]
    return y[:, 0] if squeeze else y


def wkv6(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, w: jnp.ndarray,
         u: jnp.ndarray, *, chunk: int = 64,
         interpret: bool | None = None) -> jnp.ndarray:
    """Batched chunk-parallel WKV6.  r,k,w (BH,T,K), v (BH,T,V), u (K,)."""
    interpret = default_interpret() if interpret is None else interpret
    BH, T, K = r.shape
    if T % chunk:
        pad = chunk - T % chunk
        r = _pad_to(r, 1, chunk)
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
        w = jnp.pad(w, ((0, 0), (0, pad), (0, 0)), constant_values=1.0)
    out = wkv6_pallas(r, k, v, w, u, chunk=chunk, interpret=interpret)
    return out[:, :T]
