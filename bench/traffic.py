"""Traffic generator: one general reader of the mix files in ``traffic/``.

A mix file gives the tenants (``masters``), each tenant's batch capacity
(``slots_per_master``), prompt-length buckets, an output-length
distribution, a burst at t=0, a Poisson arrival rate per master in
simulated milliseconds, deadline slacks, the warm-up probe and the size
of the correctness sample.  Everything here is plain data derived from
``--seed``; the harness turns it into the program's request objects.

Sizes are stratified: within each block of ``block`` requests of a
master, every seed gets the same multiset of prompt buckets, output
lengths and inter-arrival gaps (quantiles of the distribution at
``(i + 0.5) / block``), in an order drawn from the seed.  Two seeds
therefore offer the same work in another order.  Prompt token ids are
slices of one seeded token pool, so a long stream costs little memory.

Arrivals follow ``serve_coded.requests.synthetic_requests`` (Poisson per
master, a slack drawn from ``slack_choices``, request ids in arrival
order), extended with length buckets, an output-length distribution and
the t=0 burst that fills every slot.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Req:
    """One generated request (converted to the program's ServeRequest)."""
    rid: int
    master: int
    prompt: np.ndarray
    gen_len: int
    t_arrive: float
    slack: float


def load_mix(name: str, root: str = HERE) -> Dict:
    with open(os.path.join(root, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng((int(seed), 0xB3AC, stream))


def _strata(block: int) -> np.ndarray:
    return (np.arange(block) + 0.5) / block


def output_quantile(out: Dict, u: np.ndarray) -> np.ndarray:
    """Inverse CDF of the mix's output-length distribution at ``u``."""
    lo, hi = float(out["min"]), float(out["max"])
    if out["dist"] == "loguniform":
        v = np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
    elif out["dist"] == "uniform":
        v = lo + u * (hi + 1 - lo) - 0.5
    else:
        raise ValueError(f"unknown output distribution {out['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def _token_pool(rng: np.random.Generator, vocab: int, longest: int) -> np.ndarray:
    return rng.integers(0, vocab, size=max(1 << 20, 4 * longest),
                        dtype=np.int32)


def _prompt(pool: np.ndarray, rng: np.random.Generator, length: int) -> np.ndarray:
    o = int(rng.integers(0, pool.size - length))
    return pool[o:o + length]


def window_requests(mix: Dict, vocab: int, seed: int) -> List[Req]:
    """The measured stream: per master, a burst that fills every slot at
    t=0, then ``stream_requests_per_master`` stratified Poisson arrivals,
    long enough to outlast the window at many times today's speed."""
    M, block = int(mix["masters"]), int(mix["block"])
    n = int(mix["stream_requests_per_master"])
    burst = int(mix["burst_per_master"])
    if n % block:
        raise ValueError("stream_requests_per_master must be a multiple of block")
    rate = float(mix["rate_per_master_per_ms"])
    buckets = np.asarray(mix["prompt_buckets"], np.int64)
    slacks = np.asarray(mix["slack_choices"], np.float64)
    pool = _token_pool(_rng(seed, 0), vocab, int(buckets.max()))
    prng = _rng(seed, 1)

    def tuples(k):
        # k (prompt, output, slack) tuples, the same for every seed
        i = np.arange(k)
        return np.stack([buckets[i % buckets.size],
                         output_quantile(mix["output"], _strata(k)),
                         (i // buckets.size) % slacks.size], axis=1)
    arrivals = []
    for m in range(M):
        rng = _rng(seed, 100 + m)
        blocks = [tuples(burst)[rng.permutation(burst)]] + \
            [tuples(block)[rng.permutation(block)] for _ in range(n // block)]
        blk = np.concatenate(blocks)
        gaps = np.concatenate([rng.permutation(-np.log1p(-_strata(block)) / rate)
                               for _ in range(n // block)])
        t = np.concatenate([np.zeros(burst), np.cumsum(gaps)])
        for i in range(burst + n):
            arrivals.append((float(t[i]), m, i, int(blk[i, 0]), int(blk[i, 1]),
                             float(slacks[blk[i, 2]])))
    arrivals.sort()
    return [Req(rid=rid, master=m, prompt=_prompt(pool, prng, p), gen_len=g,
                t_arrive=ta, slack=s)
            for rid, (ta, m, _i, p, g, s) in enumerate(arrivals)]


def probe_requests(mix: Dict, vocab: int, seed: int) -> List[Req]:
    """The warm-up probe: per master, one request per entry of
    ``probe.output_lens``, all at t=0, prompts cycling through every
    bucket.  The first request of a master is dispatched alone and the
    rest join at the next step; with output lengths [S+2, 2, 3, ..., S]
    one request finishes per step after that, so each master's batch takes
    every size 1..S and every prefill bucket compiles before the window."""
    M = int(mix["masters"])
    outs = [int(g) for g in mix["probe"]["output_lens"]]
    buckets = [int(b) for b in mix["prompt_buckets"]]
    slacks = [float(x) for x in mix["slack_choices"]]
    pool = _token_pool(_rng(seed, 2), vocab, max(buckets))
    rng = _rng(seed, 3)
    out: List[Req] = []
    for m in range(M):
        for i, g in enumerate(outs):
            out.append(Req(rid=len(out), master=m,
                           prompt=_prompt(pool, rng, buckets[i % len(buckets)]),
                           gen_len=g, t_arrive=0.0,
                           slack=slacks[i % len(slacks)]))
    return out


def max_len(mix: Dict) -> int:
    """KV-cache length that covers every request of the mix (+8, as the
    bridge pads), so the window never regrows the cache."""
    longest_out = max(int(mix["output"]["max"]),
                      max(int(x) for x in mix["probe"]["output_lens"]))
    return int(max(mix["prompt_buckets"])) + longest_out + 8
