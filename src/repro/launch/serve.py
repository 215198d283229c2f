"""Serving launcher: batched prefill + decode with continuous batching.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --smoke \
        --requests 16 --prompt-len 32 --gen-len 24

Runs a small request pool through prefill → token-by-token decode with a
shared jitted decode step, reporting throughput and verifying every step's
logits against a teacher-forced full forward pass (``DECODE_TOL``; a
mismatch exits 1).  ``--no-smoke`` serves the arch at its published
widths.

With ``--coded`` the same model is served through the coded-computation
bridge (:mod:`repro.serve_coded`): per ``--coding-scope`` the output-head
matmul (``head``), the FFN up/down projections too (``ffn``), or the whole
trunk including attention q/k/v/o (``trunk``) of every token batch is
MDS-encoded and executed as per-worker shards scheduled by the
``StreamingExecutor`` plan, with ``--policy fifo|edf|fair`` picking the
admission policy and ``--steps-per-dispatch`` batching several decode
tokens per admission:

    PYTHONPATH=src python -m repro.launch.serve --coded --policy edf \
        --coding-scope trunk --requests 12 --gen-len 8

The building blocks (``build_model`` / ``serving_fns`` / ``zero_caches`` /
``head_matrix``) are shared with the bridge so both paths serve the exact
same model.
"""
from __future__ import annotations

import argparse
import sys
import time

import numpy as np

__all__ = ["build_model", "serving_fns", "zero_caches", "head_matrix",
           "build_parser", "decode_vs_forward_err", "DECODE_TOL", "main"]


_MODEL_CACHE: dict = {}


def build_model(arch, *, smoke: bool = True, seed: int = 0):
    """Config + initialised parameters for ``arch`` (smoke-sized or full).

    ``arch`` is a registry name, or an :class:`~repro.models.ArchConfig`
    served as given (``smoke`` is then ignored) — how a run cuts a
    published config's depth or vocabulary to fit a budget.

    Memoised per (arch, smoke, seed): init is deterministic and params are
    treated as read-only everywhere, so repeated bridge/test construction
    shares one copy instead of re-initialising the model."""
    key = (arch, bool(smoke), int(seed))
    if key not in _MODEL_CACHE:
        import jax
        from repro.configs import get_config, get_smoke_config
        from repro.models import init_model
        if not isinstance(arch, str):
            cfg = arch
        else:
            cfg = get_smoke_config(arch) if smoke else get_config(arch)
        params = init_model(jax.random.PRNGKey(seed), cfg)
        _MODEL_CACHE[key] = (cfg, params)
    return _MODEL_CACHE[key]


def serving_fns(cfg, *, return_hidden: bool = False):
    """Jitted (prefill_fn, decode_fn) closures over ``cfg``.

    ``return_hidden`` threads the final-norm hidden states out of both —
    the input the coded output head distributes across workers.  Memoised
    per (cfg, return_hidden): ArchConfig is a frozen dataclass, so repeated
    bridge construction reuses the compiled functions instead of
    re-tracing."""
    key = (cfg, bool(return_hidden))
    if key not in _FNS_CACHE:
        import jax
        from repro.models import decode_step, prefill
        prefill_fn = jax.jit(lambda p, b, c: prefill(
            p, b, c, cfg=cfg, return_hidden=return_hidden))
        decode_fn = jax.jit(lambda p, t, pos, c: decode_step(
            p, t, pos, c, cfg=cfg, return_hidden=return_hidden))
        _FNS_CACHE[key] = (prefill_fn, decode_fn)
    return _FNS_CACHE[key]


_FNS_CACHE: dict = {}


def zero_caches(cfg, batch: int, max_len: int):
    """Zero-initialised decode caches for ``batch`` slots."""
    import jax
    import jax.numpy as jnp
    from repro.models import init_cache_shapes
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        init_cache_shapes(cfg, batch, max_len))


def head_matrix(cfg, params) -> np.ndarray:
    """The output-head weight W (padded_vocab, d_model) as float64.

    ``logits = hidden @ W.T`` — exactly the paper's A·x task per request,
    with L = padded_vocab useful rows."""
    if cfg.tie_embeddings:
        W = np.asarray(params["embed"]["tok"])
    else:
        W = np.asarray(params["embed"]["out"]).T
    return W.astype(np.float64)


#: decode-vs-full-forward tolerance on ``max|Δlogit| / (1 + max|logit|)``
#: per parameter dtype.  The cached decode and the full forward run the
#: same weights through different contraction shapes (one query against
#: the KV cache vs the whole causal sequence), so they round differently:
#: float32 agrees to ~1e-6 (1e-4 leaves room for 16 layers of it); bf16
#: keeps 8 mantissa bits (ε = 2^-8 ≈ 3.9e-3) in the residual stream of
#: every layer, so its logits drift by a few ε — 5e-2 is about a dozen ε.
#: A decode position off by one either way (so a wrong cache slot) reads
#: 0.13-0.28 on the smoke config in both dtypes (tests/test_launch_serve.py).
DECODE_TOL = {"float32": 1e-4, "bfloat16": 5e-2}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="serve the arch's reduced smoke config "
                         "(--no-smoke: its published widths)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--coded", action="store_true",
                    help="serve through the coded-computation bridge "
                         "(StreamingExecutor-planned shards)")
    ap.add_argument("--policy", default="edf",
                    choices=("fifo", "edf", "fair"),
                    help="admission policy for --coded serving")
    ap.add_argument("--coding-scope", default="head",
                    choices=("head", "ffn", "trunk"),
                    help="which matmuls run coded: the output head only, "
                         "+FFN up/down, or the full trunk incl. attention "
                         "q/k/v/o (--coded serving)")
    ap.add_argument("--backend", default="numpy",
                    choices=("numpy", "jax", "pallas"),
                    help="coded encode / product / decode backend "
                         "(--coded serving)")
    ap.add_argument("--device-products", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="run the packed shard products on the device "
                         "kernels in float32 (jax/pallas backends, "
                         "--coded serving)")
    ap.add_argument("--parity-storage", default="materialized",
                    choices=("materialized", "virtual"),
                    help="cache encoded parity rows, or derive them from "
                         "threefry counters on demand (--coded serving)")
    ap.add_argument("--steps-per-dispatch", type=int, default=1,
                    help="decode tokens generated per coded admission "
                         "(--coded serving)")
    ap.add_argument("--execution", default="batched",
                    choices=("serial", "batched"),
                    help="shard-execution engine: packed per-stage passes "
                         "(batched) or the shard-by-shard reference "
                         "(serial) (--coded serving)")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record per-step spans (plan/pack/kernel/decode "
                         "stages, sim deliveries, cache counters) and "
                         "write a Chrome/Perfetto trace here "
                         "(--coded serving)")
    ap.add_argument("--faults", default=None, metavar="SPEC",
                    help="arm the chaos layer: comma-separated fault "
                         "spec, e.g. 'corrupt=0.25,kind=sign_flip,"
                         "crash=0.05,retries=4,seed=5' — injected faults "
                         "are detected, localised and recovered during "
                         "the serve; 'none' = zero rates with detection "
                         "armed (--coded serving)")
    ap.add_argument("--ls-tail", action="store_true",
                    help="route every coded decode through the "
                         "stacked-LS tail (bit-identical at exactly L "
                         "rows) (--coded serving)")
    return ap


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.coded:
        from repro.serve_coded import run_coded_smoke
        return run_coded_smoke(arch=args.arch, smoke=args.smoke,
                               policies=(args.policy,),
                               n_requests=args.requests,
                               prompt_len=args.prompt_len,
                               gen_len=args.gen_len, seed=args.seed,
                               coding_scope=args.coding_scope,
                               backend=args.backend,
                               device_products=args.device_products,
                               parity_storage=args.parity_storage,
                               steps_per_dispatch=args.steps_per_dispatch,
                               execution=args.execution,
                               trace=args.trace, faults=args.faults,
                               ls_tail=args.ls_tail)

    import jax
    import jax.numpy as jnp

    cfg, params = build_model(args.arch, smoke=args.smoke, seed=args.seed)
    B, P, G = args.requests, args.prompt_len, args.gen_len
    rng = np.random.default_rng(args.seed)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab, size=(B, P)), jnp.int32)

    batch = {"tokens": prompts}
    if cfg.enc_dec:
        batch["enc_feats"] = jnp.full((B, cfg.frontend_len, cfg.frontend_dim),
                                      0.1, jnp.float32)

    caches = zero_caches(cfg, B, P + G + 8)
    prefill_fn, decode_fn = serving_fns(cfg)

    t0 = time.time()
    logits, caches = prefill_fn(params, batch, caches)
    step_logits = [logits[:, -1]]
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    t_prefill = time.time() - t0

    out_tokens = [tok]
    t1 = time.time()
    for i in range(G - 1):
        pos = jnp.full((B,), P + i, jnp.int32)
        logits, caches = decode_fn(params, tok, pos, caches)
        step_logits.append(logits[:, -1])
        tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
        out_tokens.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t1

    gen = np.concatenate([np.asarray(t) for t in out_tokens], axis=1)
    print(f"[serve] {B} requests, prompt {P}, generated {gen.shape[1]} toks")
    print(f"[serve] prefill {t_prefill*1e3:.0f}ms  decode "
          f"{t_decode*1e3:.0f}ms  ({B*(G-1)/max(t_decode,1e-9):.0f} tok/s)")
    print(f"[serve] sample continuation: {gen[0][:12].tolist()}")
    if cfg.enc_dec:
        return 0
    # teacher-forced check: the full forward over prompt + generated
    # tokens must give the logits each cached step produced, position by
    # position (logits, not argmax tokens — random weights make near-ties)
    err = decode_vs_forward_err(cfg, params, prompts, gen, step_logits)
    tol = DECODE_TOL[cfg.dtype]
    ok = bool(err <= tol)
    print(f"[serve] decode vs full forward: max_rel_err={err:.3e} "
          f"tol={tol:.1e} {'ok' if ok else 'FAILED'}")
    return 0 if ok else 1


def decode_vs_forward_err(cfg, params, prompts, gen, step_logits) -> float:
    """``max|Δlogit| / (1 + max|logit|)`` between the served per-step
    logits and a teacher-forced full :func:`repro.models.model_fwd`.

    ``step_logits[i]`` (B, V) is what the server emitted for position
    ``P - 1 + i`` (the prefill's last position, then each decode step);
    the full forward runs over the prompt plus every generated token that
    was fed back."""
    import jax
    import jax.numpy as jnp
    from repro.models import model_fwd
    P = prompts.shape[1]
    G = len(step_logits)
    seq = jnp.concatenate([prompts, jnp.asarray(gen[:, :G - 1], jnp.int32)],
                          axis=1)
    fwd = jax.jit(lambda p, t: model_fwd(p, {"tokens": t}, cfg=cfg)["logits"])
    ref = np.asarray(fwd(params, seq)[:, P - 1:], np.float32)
    got = np.stack([np.asarray(x, np.float32) for x in step_logits], axis=1)
    return float(np.abs(got - ref).max() / (1.0 + np.abs(ref).max()))


if __name__ == "__main__":
    sys.exit(main())
