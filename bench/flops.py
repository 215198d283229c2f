"""Operations and bytes the benchmark counts, from shapes alone.

Model operations are the useful ones: the trunk's matrix products, causal
attention over the positions that exist, and the output head's 2·V·d per
served token, with no coded redundancy and none of the work the program
repeats or pads.  Kernel operations and bytes are those each kernel call
needs from its shapes (float32 operands and results).
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple


def matmul_params_per_layer(s) -> int:
    attn = s.d * s.heads * s.head_dim * 2 + 2 * s.d * s.kv_heads * s.head_dim
    ffn = (3 if s.act == "silu" else 2) * s.d * s.d_ff
    return attn + ffn


def prefill_flops(s, P: int) -> float:
    """A P-token prompt: every position through the trunk (causal
    attention over 1..P keys), one served token through the head."""
    dense = 2.0 * matmul_params_per_layer(s) * s.layers * P
    attn = 4.0 * s.heads * s.head_dim * s.layers * (P * (P + 1) / 2)
    return dense + attn + 2.0 * s.vocab * s.d


def decode_flops(s, n_tokens: int, keys: int) -> float:
    """``n_tokens`` continuing slots whose positions add up to ``keys``
    (each attends over its own pos + 1 keys)."""
    dense = 2.0 * matmul_params_per_layer(s) * s.layers * n_tokens
    attn = 4.0 * s.heads * s.head_dim * s.layers * keys
    return dense + attn + 2.0 * s.vocab * s.d * n_tokens


def model_flops(s, prefills: Iterable[int], decodes: Iterable[Tuple[int, int]]) -> float:
    return sum(prefill_flops(s, P) for P in prefills) + \
        sum(decode_flops(s, n, k) for n, k in decodes)


def shard_matmul_cost(tiles: Tuple[int, int, int], x: Tuple[int, int]) -> Tuple[float, float]:
    """``coded_matvec`` over packed tiles (T, R, K) against x (K, C)."""
    T, R, K = tiles
    C = x[1]
    return 2.0 * T * R * K * C, 4.0 * (T * R * K + K * C + T * R * C)


def gen_parity_cost(n: int, L: int, D: int, C: int) -> Tuple[float, float]:
    """Generated parity ``R_gen @ (W @ x)``: W (L, D) read once, n rows of
    R derived from counters (no operand bytes), n×C results."""
    return 2.0 * C * (L * D + n * L), 4.0 * (L * D + D * C + n * C + n)


def products_least_seconds(calls: Iterable[Dict], peaks: Dict) -> Tuple[float, str]:
    """Σ over products kernel calls of max(ops / peak, bytes / bandwidth),
    and which bound set most of it ("compute" | "memory")."""
    f_peak, bw = float(peaks["bf16_flops_per_s"]), float(peaks["hbm_bytes_per_s"])
    total, by = 0.0, {"compute": 0.0, "memory": 0.0}
    for c in calls:
        parts = [shard_matmul_cost(c["tiles"], c["x"])]
        parts += [gen_parity_cost(n, L, D, c["x"][1]) for n, L, D in c["parity"]]
        for fl, by_ in parts:
            tc, tm = fl / f_peak, by_ / bw
            total += max(tc, tm)
            by["compute" if tc >= tm else "memory"] += max(tc, tm)
    return total, max(by, key=by.get)
