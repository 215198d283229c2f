"""Multi-backend batched numerics shared by the streaming engine,
``repro.sim.montecarlo`` and ``repro.runtime.coded_exec``.

The paper's delay pipeline — encode → per-worker partial products → prefix
completion → exactly-L decode — used to exist in two and a half
implementations (a per-master Python loop in the Monte-Carlo simulator, a
per-arrival loop in ``CodedExecutor``, and implicitly in the straggler
policies).  This module is the single implementation, with three backends:

* ``numpy`` — the authoritative reference.  Batched sort + cumsum over the
  node axis, stacked ``np.linalg.solve`` decode.  Bit-for-bit equal to the
  legacy per-master loops (asserted by tests).
* ``jax`` — jitted and device-resident.  ``completion_times`` /
  ``decode_batch`` run as cached jitted functions; ``simulate_batch`` is a
  full Monte-Carlo kernel (delay sampling + completion) that gathers each
  master's *active* worker columns, draws float32 exponentials with the
  fast ``rbg`` generator, and evaluates the completion rule sort-free in
  cache-sized ``lax.map`` chunks — nothing round-trips to the host until
  the final sample array.
* ``pallas`` — the encode / coded-product kernels from ``repro.kernels``
  (real lowering on TPU, ``interpret=True`` everywhere else), consumed by
  ``CodedExecutor`` and the streaming verification path; decode reuses the
  jitted jax solve.

Public entry points:

* ``completion_times`` — earliest time the cumulative received coded rows
  reach L, batched over any leading axes (realizations, masters, tasks).
  NaN and ±inf delays are "never arrives" instead of poisoning the prefix.
* ``sample_delays`` — turn pre-drawn Exp(1) variates into T = T_tr + T_cp
  delays, with optional heavy-tail ``straggle_p``/``straggle_factor``
  throttling (burstable-instance CPU-credit exhaustion).
* ``simulate_batch`` — (trials, M) Monte-Carlo completion delays for a full
  plan in one call; the jitted path behind ``simulate_plan(backend="jax")``.
* ``decode_batch`` — batched exactly-L MDS decode with a systematic-prefix
  fast path: when the generator's top L rows are the identity and a task
  received only those rows, the "solve" is a row permutation and is applied
  by a scatter (bit-identical to LAPACK on a permutation matrix, no O(L^3)
  factorization).
* ``ExponentialBlock`` — block-amortised standard-exponential (and
  optionally uniform) draws so the event loop consumes pre-sampled
  randomness (deterministic replay, no per-event RNG overhead).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import numpy as np

from ..obs import DETAIL_TRACK, NULL_SPAN, current_tracer, device_span

__all__ = [
    "has_jax",
    "completion_times",
    "delivered_by",
    "sample_delays",
    "simulate_batch",
    "simulate_chunks_np",
    "decode_batch",
    "plan_decode",
    "DecodePlan",
    "SystematicRows",
    "plan_decode_ls",
    "LSDecodePlan",
    "decode_ls_batch",
    "plan_verify",
    "VerifyPlan",
    "verify_decode",
    "localize_faulty_worker",
    "solve_stacked",
    "solve_jax",
    "decode_device",
    "StackedLU",
    "ExponentialBlock",
]

_EPS = 1e-12
BACKENDS = ("numpy", "jax", "pallas")


def check_backend(backend: str) -> str:
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of "
                         f"{BACKENDS}")
    return backend


@functools.lru_cache(maxsize=1)
def has_jax() -> bool:
    try:
        import jax  # noqa: F401
        return True
    except Exception:  # pragma: no cover - jax is baked into the image
        return False


def _use_jax(backend: str) -> bool:
    return backend in ("jax", "pallas")


@functools.lru_cache(maxsize=1)
def _rng_key_impl() -> Optional[str]:
    """Fastest counter-based PRNG available ("rbg" beats threefry ~2x on
    CPU and lowers to the hardware RNG on TPU); None → jax default."""
    import jax.random as jr
    try:
        jr.key(0, impl="rbg")
        return "rbg"
    except Exception:  # pragma: no cover - rbg exists on all supported jax
        return None


def _make_key(seed: int):
    import jax.random as jr
    impl = _rng_key_impl()
    return jr.key(seed, impl=impl) if impl else jr.key(seed)


# ---------------------------------------------------------------------------
# Completion times
# ---------------------------------------------------------------------------

def _completion_np(T: np.ndarray, loads: np.ndarray, need: np.ndarray,
                   needs_all: bool) -> np.ndarray:
    active = loads > 0
    # NaN (poisoned sample) and inf (dead worker) both mean "never arrives".
    Ti = np.where(active & np.isfinite(T), T, np.inf)
    if needs_all:
        out = np.where(active, Ti, -np.inf).max(axis=-1)
        out = np.where(active.any(axis=-1), out, np.inf)
        return np.where(np.isfinite(out), out, np.inf)
    order = np.argsort(Ti, axis=-1, kind="stable")
    T_s = np.take_along_axis(Ti, order, axis=-1)
    l_s = np.take_along_axis(np.where(active, loads, 0.0), order, axis=-1)
    cum = np.cumsum(l_s, axis=-1)
    hit = cum >= need[..., None] - 1e-9
    first = np.argmax(hit, axis=-1)
    reachable = np.take_along_axis(hit, first[..., None], axis=-1)[..., 0]
    out = np.take_along_axis(T_s, first[..., None], axis=-1)[..., 0]
    return np.where(reachable & np.isfinite(out), out, np.inf)


@functools.lru_cache(maxsize=None)
def _completion_jit(needs_all: bool):
    """Cached jitted batched completion kernel (device arrays in and out)."""
    import jax
    import jax.numpy as jnp

    def core(T, loads, need):
        active = loads > 0
        Ti = jnp.where(active & jnp.isfinite(T), T, jnp.inf)
        if needs_all:
            out = jnp.where(active, Ti, -jnp.inf).max(axis=-1)
            out = jnp.where(active.any(axis=-1), out, jnp.inf)
            return jnp.where(jnp.isfinite(out), out, jnp.inf)
        T_s, l_s = jax.lax.sort(
            [Ti, jnp.where(active, loads, 0.0)], num_keys=1, is_stable=True)
        cum = jnp.cumsum(l_s, axis=-1)
        hit = cum >= need[..., None] - 1e-9
        first = jnp.argmax(hit, axis=-1)
        ok = jnp.take_along_axis(hit, first[..., None], axis=-1)[..., 0]
        out = jnp.take_along_axis(T_s, first[..., None], axis=-1)[..., 0]
        return jnp.where(ok & jnp.isfinite(out), out, jnp.inf)

    return jax.jit(core)


def completion_times(T, loads, need, *, needs_all: bool = False,
                     backend: str = "numpy") -> np.ndarray:
    """Earliest t per batch row with Σ_{n: T_n <= t} l_n >= need.

    T:     (..., K) arrival times (absolute or relative — any monotone scale).
    loads: broadcastable to T; zero-load nodes are ignored.
    need:  broadcastable to T's leading axes.
    needs_all: the uncoded rule — wait for *every* positive-load node.

    Non-finite delays (inf dead workers, NaN poisoned samples) never arrive:
    they are skipped by the prefix, and the result is inf only if the
    remaining live nodes cannot cover ``need``.

    The jax backend runs one cached jitted kernel over the whole batch; the
    host boundary is a single transfer each way.
    """
    check_backend(backend)
    T = np.asarray(T, dtype=np.float64)
    loads = np.broadcast_to(np.asarray(loads, dtype=np.float64), T.shape)
    need = np.broadcast_to(np.asarray(need, dtype=np.float64), T.shape[:-1])
    if _use_jax(backend):
        return np.asarray(_completion_jit(bool(needs_all))(T, loads, need))
    return _completion_np(T, loads, need, needs_all)


def delivered_by(T, loads, t) -> np.ndarray:
    """Rows delivered by time ``t``: Σ_{n: T_n <= t} l_n (batched)."""
    T = np.asarray(T, dtype=np.float64)
    loads = np.broadcast_to(np.asarray(loads, dtype=np.float64), T.shape)
    t = np.asarray(t, dtype=np.float64)
    arrived = np.isfinite(T) & (T <= t[..., None]) & (loads > 0)
    return np.where(arrived, loads, 0.0).sum(axis=-1)


# ---------------------------------------------------------------------------
# Delay sampling
# ---------------------------------------------------------------------------

def sample_delays(e_tr: np.ndarray, e_cp: np.ndarray, l, k, b, a, u, gamma,
                  *, local_col0: bool = True,
                  straggle_p: float = 0.0, straggle_factor: float = 8.0,
                  straggle_u: Optional[np.ndarray] = None) -> np.ndarray:
    """Turn standard-exponential draws into T = T_tr + T_cp delays.

    ``e_tr``/``e_cp`` are ~Exp(1) draws of the same (batched) shape as ``l``;
    the transformation matches ``repro.core.delays.sample_total`` exactly, so
    an ``ExponentialBlock`` + ``sample_delays`` pipeline is distributionally
    identical to the legacy per-call sampler while being batchable and
    replayable.

    ``straggle_p`` / ``straggle_factor``: per-node probability that the node
    is in a degraded state for this task, multiplying its whole delay by
    ``factor`` — the heavy-tailed *measured* behaviour of burstable cloud
    instances (CPU-credit throttling) that the fitted shifted exponential
    underestimates.  ``straggle_u`` supplies the uniform draws (same shape
    as ``l``; see ``ExponentialBlock(uniform_rows=1)``) so replay stays
    deterministic.
    """
    l = np.asarray(l, dtype=np.float64)
    lsafe = np.maximum(l, _EPS)
    ksafe = np.maximum(k, _EPS)
    bsafe = np.maximum(b, _EPS)
    t_tr = e_tr * lsafe / (bsafe * gamma)
    if local_col0:
        t_tr = t_tr.copy()
        t_tr[..., 0] = 0.0
    t_cp = a * l / ksafe + e_cp * lsafe / (ksafe * u)
    total = t_tr + t_cp
    if straggle_p > 0.0:
        if straggle_u is None:
            raise ValueError("straggle_p > 0 requires straggle_u draws "
                             "(use ExponentialBlock(uniform_rows=1))")
        total = np.where(np.asarray(straggle_u) < straggle_p,
                         total * straggle_factor, total)
    return np.where(l > 0, total, 0.0)


class ExponentialBlock:
    """Pre-sampled Exp(1) (+ optional Uniform(0,1)) draws consumed row-by-row.

    The event loop needs one (2, N+1) standard-exponential row per admitted
    task (plus one uniform row when heavy-tail throttling is on); drawing
    them one event at a time costs a Generator call per event.  This draws
    ``block`` tasks' worth at once and hands out views — deterministic
    replay at block-amortised cost.
    """

    def __init__(self, rng: np.random.Generator, width: int,
                 block: int = 512, uniform_rows: int = 0):
        self.rng = rng
        self.width = int(width)
        self.block = int(block)
        self.uniform_rows = int(uniform_rows)
        self.rows = 2 + self.uniform_rows
        self._buf = np.empty((0, self.rows, self.width))
        self._pos = 0

    def _refill(self) -> None:
        exp = self.rng.exponential(
            1.0, size=(self.block, 2, self.width))
        if self.uniform_rows:
            uni = self.rng.random(
                size=(self.block, self.uniform_rows, self.width))
            self._buf = np.concatenate([exp, uni], axis=1)
        else:
            self._buf = exp
        self._pos = 0

    def draw(self) -> np.ndarray:
        if self._pos >= self._buf.shape[0]:
            self._refill()
        row = self._buf[self._pos]
        self._pos += 1
        return row

    def draw_n(self, n: int) -> np.ndarray:
        """``n`` consecutive draws as one (n, rows, width) view — the
        multi-task serving dispatch consumes one row per coded matmul and
        samples all of a step barrier's delays in a single batched
        :func:`sample_delays` call.  The stream is identical to ``n``
        successive :meth:`draw` calls."""
        if n <= 0:
            raise ValueError("draw_n needs n >= 1")
        if self._pos + n <= self._buf.shape[0]:
            out = self._buf[self._pos:self._pos + n]
            self._pos += n
            return out
        # keep the stream identical to n draw() calls: consume the tail,
        # then refill block-by-block for the remainder (n may exceed one
        # block — e.g. a deep trunk's 1 + 7·n_layers tasks per dispatch)
        parts = [self._buf[self._pos:]]
        need = n - parts[0].shape[0]
        while need > 0:
            self._refill()
            take = min(need, self._buf.shape[0])
            parts.append(self._buf[:take])
            self._pos = take
            need -= take
        return np.concatenate([p for p in parts if p.size])


# ---------------------------------------------------------------------------
# Jitted Monte-Carlo (sample + complete, device-resident)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _simulate_jit(needs_all: bool, straggle: bool, n_nodes: int):
    """Cached jitted Monte-Carlo kernel over active-node arrays.

    Works on per-master *gathered* parameter rows (M, A) where A is the
    max active-node count — a 3-4x cut in RNG and completion work versus
    the dense (M, N+1) layout when workers are partitioned across masters.

    The completion rule is evaluated sort-free: for each candidate arrival
    i, S_i = Σ_n l_n·[T_n <= T_i]; the completion is min{T_i : S_i >= L}.
    XLA's CPU sort is ~5x slower than this O(A²) unrolled reduction at the
    A ≤ 64 widths that occur in practice, and the ``lax.map`` chunking
    keeps every temporary cache-resident, so the whole kernel runs at
    memory speed of the (trials, M) output.
    """
    import jax
    import jax.numpy as jnp
    import jax.random as jr

    def run(key, c_tr, shift, c_cp, loads, need, p, factor, nch, chunk):
        dt = c_tr.dtype
        # need-1e-9 matches numpy; the relative term absorbs float32 cumsum
        # rounding when coverage is exact (never larger than a fraction of
        # one coded row at L ~ 1e4).
        rel = 1e-6 if dt == jnp.float32 else 0.0
        thresh = need[None, :] - 1e-9 - rel * need[None, :]
        keys = jr.split(key, (nch, 2))

        def one(kk):
            e = jr.exponential(kk[0], (2, chunk) + c_tr.shape, dt)
            T = c_tr * e[0] + shift + c_cp * e[1]      # padded nodes: +inf
            if straggle:
                u01 = jr.uniform(kk[1], (chunk,) + c_tr.shape, dt)
                T = jnp.where(u01 < p, T * factor, T)
            if needs_all:
                act = loads > 0
                out = jnp.where(act, T, -jnp.inf).max(axis=-1)
                out = jnp.where(act.any(axis=-1), out, jnp.inf)
                return jnp.where(jnp.isfinite(out), out, jnp.inf)
            comp = jnp.full(T.shape[:-1], jnp.inf, dt)
            for i in range(n_nodes):
                Ti = T[..., i]
                S = jnp.where(T <= Ti[..., None], loads, 0.0).sum(axis=-1)
                comp = jnp.minimum(
                    comp, jnp.where(S >= thresh, Ti, jnp.inf))
            return comp

        return jax.lax.map(one, keys).reshape(nch * chunk, -1)

    return jax.jit(run, static_argnames=("nch", "chunk"))


def _gather_active(l, k, b, a, u, gamma, dtype):
    """Per-master active-column gather → (idx, loads, c_tr, shift, c_cp).

    Returns (M, A) coefficient arrays with T = c_tr·e1 + shift + c_cp·e2;
    padded slots have shift = +inf (never arrive) and zero load.  Column 0
    (the master's local processor) gets c_tr = 0 — no communication.
    """
    M = l.shape[0]
    counts = (l > 0).sum(axis=1)
    A = max(int(counts.max()), 1)
    idx = np.zeros((M, A), dtype=np.int64)
    pad = np.ones((M, A), dtype=bool)
    for m in range(M):
        nz = np.nonzero(l[m] > 0)[0]
        idx[m, :nz.size] = nz
        pad[m, nz.size:] = False
    act = pad          # True where a real node sits
    ga = lambda arr: np.take_along_axis(np.asarray(arr, np.float64), idx, 1)
    l_a = np.where(act, ga(l), 0.0)
    k_a, b_a = ga(k), ga(b)
    a_a, u_a, g_a = ga(a), ga(u), ga(gamma)
    c_tr = np.where(act, l_a / np.maximum(b_a * g_a, _EPS), 0.0)
    c_tr[idx == 0] = 0.0                       # local node: no comm delay
    shift = np.where(act, a_a * l_a / np.maximum(k_a, _EPS), np.inf)
    c_cp = np.where(act, l_a / np.maximum(k_a * u_a, _EPS), 0.0)
    return (idx, l_a.astype(dtype), c_tr.astype(dtype),
            shift.astype(dtype), c_cp.astype(dtype))


def simulate_chunks_np(rng: np.random.Generator, l, k, b, a, u, gamma, L,
                       trials: int, *, needs_all: bool = False,
                       straggle_p: float = 0.0, straggle_factor: float = 8.0,
                       chunk: int = 20_000):
    """Yield (r, M) completion-delay chunks from the Generator-based
    sampler — the single numpy Monte-Carlo loop behind both
    ``simulate_batch(backend="numpy")`` and ``sim.montecarlo``'s
    streaming aggregation (bit-stable for a given Generator + chunk)."""
    from ..core.delays import sample_total
    l = np.asarray(l, dtype=np.float64)
    L = np.atleast_1d(np.asarray(L, dtype=np.float64))
    chunk = max(int(chunk), 1)
    done = 0
    while done < trials:
        r = min(chunk, trials - done)
        T = sample_total(rng, (r,), l, k, b, a, u, gamma, local_col0=True)
        if straggle_p > 0:
            throttled = rng.random(T.shape) < straggle_p
            T = np.where(throttled, T * straggle_factor, T)
        yield completion_times(T, l[None], L[None], needs_all=needs_all)
        done += r


def simulate_batch(l, k, b, a, u, gamma, L, trials: int, *,
                   seed: "int | np.random.Generator" = 0,
                   needs_all: bool = False,
                   straggle_p: float = 0.0, straggle_factor: float = 8.0,
                   backend: str = "jax", dtype=np.float32,
                   chunk: int = 4096) -> np.ndarray:
    """(trials, M) Monte-Carlo completion delays for a full plan, one call.

    All inputs are the dense (M, N+1) plan/scenario arrays (column 0 = the
    master's local processor, communication-free).  The jax path is the
    jitted device-resident kernel described in :func:`_simulate_jit`;
    float32 by default — delay-model rounding is orders of magnitude below
    Monte-Carlo noise at any trial count this path exists for.  Seeding is
    by integer ``seed`` (counter-based key), so results are reproducible
    but *not* bit-equal to the numpy Generator stream — the two backends
    agree statistically, which is what the tests assert.

    The numpy fallback runs :func:`simulate_chunks_np` (a Generator is
    also accepted as ``seed`` there, for bit-stable shared streams).
    """
    check_backend(backend)
    l = np.asarray(l, dtype=np.float64)
    trials = int(trials)
    if backend == "numpy" or not has_jax():
        rng = (seed if isinstance(seed, np.random.Generator)
               else np.random.default_rng(seed))
        return np.concatenate(list(simulate_chunks_np(
            rng, l, k, b, a, u, gamma, L, trials, needs_all=needs_all,
            straggle_p=straggle_p, straggle_factor=straggle_factor,
            chunk=chunk)))
    if isinstance(seed, np.random.Generator):
        seed = int(seed.integers(np.iinfo(np.int64).max))
    L = np.atleast_1d(np.asarray(L, dtype=np.float64))

    import jax.numpy as jnp
    dtype = jnp.dtype(dtype)
    _, l_a, c_tr, shift, c_cp = _gather_active(l, k, b, a, u, gamma, dtype)
    chunk = max(min(int(chunk), trials), 1)
    nch = math.ceil(trials / chunk)
    fn = _simulate_jit(bool(needs_all), straggle_p > 0.0, l_a.shape[1])
    # device_span fences with block_until_ready only while a tracer records,
    # so the async dispatch pipeline is untouched when tracing is off
    with device_span("simulate_batch", cat="kernel",
                     args={"trials": trials, "M": int(l.shape[0]),
                           "chunks": nch}) as fence:
        comp = fence(fn(_make_key(int(seed)), jnp.asarray(c_tr),
                        jnp.asarray(shift), jnp.asarray(c_cp),
                        jnp.asarray(l_a), jnp.asarray(L.astype(dtype)),
                        dtype.type(straggle_p), dtype.type(straggle_factor),
                        nch, chunk))
    return np.asarray(comp[:trials], dtype=np.float64)


# ---------------------------------------------------------------------------
# Batched MDS decode
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def decode_device():
    """The device the jax decode solves run on: the host CPU, by name.

    The exact decode needs float64 LU, and the TPU compiler implements
    ``LuDecomposition`` for F32/C64 only — so the solve is placed on the
    CPU explicitly, also when an accelerator is the default device, and
    never runs in float32.  Raises where JAX has no CPU backend (a
    ``JAX_PLATFORMS`` that lists platforms must list ``cpu`` too, after
    the accelerator: ``tpu,cpu``).
    """
    import jax
    try:
        return jax.devices("cpu")[0]
    except RuntimeError as e:
        raise RuntimeError(
            "the jax decode solve needs JAX's CPU backend for float64 LU; "
            f"JAX_PLATFORMS={jax.config.jax_platforms!r} leaves it out "
            "(use e.g. 'tpu,cpu')") from e


@functools.lru_cache(maxsize=1)
def _solve_jit():
    import jax
    import jax.numpy as jnp
    return jax.jit(lambda Gs, y: jnp.linalg.solve(Gs, y))


def _on_decode_device(*arrays):
    import jax
    dev = decode_device()
    return tuple(jax.device_put(np.asarray(a, dtype=np.float64), dev)
                 for a in arrays)


def solve_jax(A: np.ndarray, b: np.ndarray) -> np.ndarray:
    """One-shot stacked float64 solve, jitted on :func:`decode_device`.

    It keeps no factors: each call factorises all ``g`` stacked systems
    afresh (counted as ``decode_lu_factorizations`` while a tracer
    records).  No decode calls it: every engine's square solves go
    through :class:`StackedLU`, which factorises a frozen structure once.
    It stays as the jax reference those cached solves are checked
    against, bit for bit wherever the right-hand side has two or more
    columns (with one, XLA's triangular solve rounds differently in the
    last bits).  The call must enter ``jax.enable_x64`` every time: jit
    avals canonicalise by the flag's state at trace *and* call time.
    """
    import jax
    tr = current_tracer()
    if tr is not None:
        g, n = A.shape[0], A.shape[1]
        tr.count("decode_lu_factorizations", g)
        tr.count("decode_system_rows", g * n)
    with jax.enable_x64(True):
        with (tr.span("decode.put", cat="decode.put", track=DETAIL_TRACK)
              if tr is not None else NULL_SPAN):
            args = _on_decode_device(A, b)
        return np.asarray(_solve_jit()(*args))


try:                                   # the gufunc behind np.linalg.solve
    from numpy.linalg import _umath_linalg as _gu
    _gu.solve(np.eye(2)[None], np.ones((1, 2, 1)), signature="dd->d")
except Exception:  # pragma: no cover - exotic numpy builds
    _gu = None


try:
    from scipy.linalg import lu_factor as _lu_factor, lu_solve as _lu_solve
    from scipy.linalg.lapack import dgetrs as _dgetrs
except Exception:  # pragma: no cover - no-scipy builds
    _lu_factor = _lu_solve = _dgetrs = None


class StackedLU:
    """Lazily cached LU factorization of stacked (g, n, n) systems.

    ``np.linalg.solve`` (LAPACK ``gesv``) re-factorizes on every call.  A
    *frozen* decode plan solves the same parity sub-blocks for every step
    of a serve with only the right-hand side changing, so the ``getrf`` is
    paid on the first solve and each later one replays the O(n²)
    ``getrs``.  Solutions are bit-identical to :func:`solve_stacked` —
    ``gesv`` *is* ``getrf`` + ``getrs`` — and every engine's exact square
    decode routes through this, so the engines cannot drift from each
    other.  The factors replace the matrices: ``A`` is dropped once
    factorised, so a cached structure holds one n² block per system.
    Falls back to the one-shot solve when scipy is unavailable.
    """

    __slots__ = ("A", "_fac", "_checked")

    def __init__(self, A: np.ndarray):
        self.A = A
        self._fac = None
        self._checked = False

    def solve(self, b: np.ndarray) -> np.ndarray:
        tr = current_tracer()
        if tr is not None:
            g, n = b.shape[0], b.shape[1]
            tr.count("decode_system_rows", g * n)
            if self._fac is None:
                tr.count("decode_lu_factorizations", g)
        if _lu_factor is None:
            return solve_stacked(self.A, b)
        if self._fac is None:
            self._fac = [_lu_factor(a, check_finite=False) for a in self.A]
            self.A = None
        # raw getrs: same triangular sweeps as lu_solve minus its per-call
        # argument validation (thousands of tiny serving solves per run)
        if len(self._fac) == 1:
            lu, piv = self._fac[0]
            out = _dgetrs(lu, piv, b[0])[0][None]
        else:
            out = np.empty(b.shape)
            for i, (lu, piv) in enumerate(self._fac):
                out[i] = _dgetrs(lu, piv, b[i])[0]
        # singularity is a property of the frozen matrices, not the RHS —
        # one finiteness pass on the first solve is enough
        if not self._checked:
            if not np.isfinite(out).all():
                raise np.linalg.LinAlgError("Singular matrix")
            self._checked = True
        return out


def solve_stacked(A: np.ndarray, b: np.ndarray) -> np.ndarray:
    """``np.linalg.solve(A, b)`` for stacked (g, n, n) · (g, n, C) systems,
    minus the per-call wrapper overhead.

    The serving decode issues thousands of tiny (n ≲ 50) solves per run;
    ``np.linalg.solve``'s Python wrapper (shape juggling, errstate, extobj
    plumbing) costs more than LAPACK ``gesv`` itself at those sizes.  This
    calls the same gufunc directly — results are bit-identical — and falls
    back to the public API when the private entry point is unavailable.
    Singular inputs still raise ``LinAlgError`` (the gufunc emits
    non-finite rows; the finiteness check costs one cheap pass, and a
    silent NaN would otherwise reach ``argmax`` as token 0 in the
    verify-off serving configuration).
    """
    if _gu is not None and A.dtype == np.float64 and b.dtype == np.float64:
        with np.errstate(invalid="ignore", over="ignore", divide="ignore"):
            out = _gu.solve(A, b, signature="dd->d")
        if not np.isfinite(out).all():
            raise np.linalg.LinAlgError("Singular matrix")
        return out
    return np.linalg.solve(A, b)


class SystematicRows:
    """Lazy row-view of a systematic generator ``[I; R]`` — no dense G.

    Virtual parity storage keeps no materialised generator; what decode
    planning actually consumes is *rows* of G (the mixed groups' square
    minors, the full-solve gathers).  This adapter satisfies exactly that:
    ``take(rows)`` synthesises identity rows for indices < L and asks
    ``parity_rows_fn(ids)`` (e.g. :meth:`CodedLinear.parity_rows`, the
    counter derivation) for the rest.  ``plan_decode`` accepts it wherever
    a shared 2-D generator is accepted; the identity prefix holds by
    construction.
    """

    __slots__ = ("L", "total", "parity_rows_fn")
    ndim = 2

    def __init__(self, L: int, total: int, parity_rows_fn):
        self.L = int(L)
        self.total = int(total)
        self.parity_rows_fn = parity_rows_fn

    @property
    def shape(self):
        return (self.total, self.L)

    def take(self, rows: np.ndarray) -> np.ndarray:
        """Gather G[rows] (float64) for any integer index array — the
        result has shape ``rows.shape + (L,)``."""
        rows = np.asarray(rows)
        flat = rows.ravel()
        out = np.zeros((flat.size, self.L))
        sys_m = flat < self.L
        out[np.nonzero(sys_m)[0], flat[sys_m]] = 1.0
        if (~sys_m).any():
            out[~sys_m] = np.asarray(
                self.parity_rows_fn(flat[~sys_m] - self.L), dtype=np.float64)
        return out.reshape(rows.shape + (self.L,))

    def __getitem__(self, rows):
        return self.take(rows)


def _identity_prefix(G: np.ndarray) -> bool:
    """True iff the generator's (shared) top L rows are exactly I_L."""
    L = G.shape[-1]
    if G.shape[-2] < L:
        return False
    top = G[..., :L, :]
    eye = np.eye(L, dtype=G.dtype)
    return bool((top == eye).all())


def _gather_generator_rows(G, glist: bool, idx: np.ndarray,
                           rows: np.ndarray) -> np.ndarray:
    """Stack G[rows[i]] for the selected task indices → (len(idx), R, L)."""
    if glist:
        return np.stack([np.asarray(G[i], dtype=np.float64)[rows[j]]
                         for j, i in enumerate(idx)])
    if G.ndim == 2:
        return G[rows]
    return G[idx[:, None], rows]


class _MixedGroup:
    """One mixed-row substitution group of a :class:`DecodePlan`: every
    task that received exactly ``s`` systematic rows (0 < s < L)."""

    __slots__ = ("grp", "sys_rows", "unk", "lu", "Gk", "sys_pos", "par_pos")

    def __init__(self, grp, sys_rows, unk, A, Gk, sys_pos, par_pos):
        self.grp = grp                # (g,) task indices in the batch
        self.sys_rows = sys_rows      # (g, s) pinned coordinate ids
        self.unk = unk                # (g, L-s) coordinates to solve for
        self.lu = StackedLU(A)        # (g, L-s, L-s) parity sub-blocks
        self.Gk = Gk                  # (g, L-s, s) known-coordinate columns
        self.sys_pos = sys_pos        # (g, s) receive positions of sys rows
        self.par_pos = par_pos        # (g, L-s) receive positions of parity


class DecodePlan:
    """The X-independent structure of one stacked exactly-L decode.

    Everything :func:`decode_batch` derives from ``(G, rows)`` alone — the
    systematic/mixed/full partition of the batch, the per-``s`` substitution
    groups, the gathered generator sub-blocks — is computed once here, so a
    caller that decodes many right-hand sides against the *same* received
    rows (the serving bridge's step barrier: one delivery prefix, one
    decode problem per coded matmul, re-applied for every token of a
    multi-token dispatch) pays the planning overhead once.  ``apply(y)``
    runs the solves; ``decode_batch(G, rows, y)`` is literally
    ``plan_decode(G, rows).apply(y)``, so the two can never drift.
    """

    __slots__ = ("B", "L", "fast_idx", "fast_rows", "full_idx", "full_lu",
                 "mixed_groups")

    def __init__(self, B: int, L: int, fast_idx, fast_rows, full_idx,
                 full_G, mixed_groups):
        self.B = B
        self.L = L
        self.fast_idx = fast_idx          # (f,) tasks decoded by scatter
        self.fast_rows = fast_rows        # (f, L) their received row ids
        self.full_idx = full_idx          # (n,) tasks needing the full solve
        # (n, L, L) gathered generators, factorised on the first apply
        self.full_lu = StackedLU(full_G)
        # list of (grp_idx, sys_rows, unk, A, Gk) per distinct s count
        self.mixed_groups = mixed_groups

    def apply(self, y: np.ndarray, *, backend: str = "numpy") -> np.ndarray:
        """Solve the planned systems for one stacked right-hand side
        ``y`` (B, L) or (B, L, C)."""
        check_backend(backend)
        tr = current_tracer()
        t0 = tr.now() if tr is not None else 0.0
        y = np.asarray(y, dtype=np.float64)
        squeeze = y.ndim == 2
        if squeeze:
            y = y[..., None]
        out = np.empty((self.B, self.L, y.shape[-1]))
        # every engine solves through the cached getrf + per-apply getrs
        # (bit-identical to gesv)
        if self.fast_idx.size:
            # permutation decode: out[b, rows[b, i]] = y[b, i]
            out[self.fast_idx[:, None], self.fast_rows] = y[self.fast_idx]
        if self.full_idx.size:
            out[self.full_idx] = self.full_lu.solve(y[self.full_idx])
        for mg in self.mixed_groups:
            # receive-order partitions were frozen at plan time as position
            # index arrays; partition y the same row-major way
            yg = y[mg.grp]
            sys_y = np.take_along_axis(yg, mg.sys_pos[:, :, None], axis=1)
            par_y = np.take_along_axis(yg, mg.par_pos[:, :, None], axis=1)
            sol = mg.lu.solve(par_y - mg.Gk @ sys_y)
            out[mg.grp[:, None], mg.sys_rows] = sys_y        # exact pins
            out[mg.grp[:, None], mg.unk] = sol
        if tr is not None:
            tr.add_span("decode_apply", t0, tr.now(), cat="decode",
                        track="wall",
                        args={"tasks": self.B, "backend": backend,
                              "scatter": int(self.fast_idx.size),
                              "solved": int(self.full_idx.size),
                              "mixed": sum(int(mg.grp.size)
                                           for mg in self.mixed_groups)})
        return out[..., 0] if squeeze else out


def plan_decode(G, rows: np.ndarray, *, systematic: str = "auto",
                identity_prefix: Optional[bool] = None) -> DecodePlan:
    """Build the :class:`DecodePlan` for stacked received rows.

    ``identity_prefix`` short-circuits the O(L²) top-rows-are-identity
    check when the caller constructed G as a systematic [I; R] generator
    (``CodedLinear`` always does) — pass ``True``/``False`` to assert the
    structure, ``None`` (default) to detect it.
    """
    if systematic not in ("auto", "prefix", "never"):
        raise ValueError(f"systematic must be 'auto', 'prefix' or 'never', "
                         f"got {systematic!r}")
    tr = current_tracer()
    t0 = tr.now() if tr is not None else 0.0
    rows = np.asarray(rows)
    glist = isinstance(G, (list, tuple))
    if not glist and not isinstance(G, SystematicRows):
        G = np.asarray(G, dtype=np.float64)
    B, L = rows.shape

    sys_ok = False
    if systematic != "never" and B:
        if identity_prefix is not None:
            sys_ok = bool(identity_prefix)
        elif isinstance(G, SystematicRows):
            sys_ok = True            # systematic by construction
        else:
            sys_ok = (all(_identity_prefix(np.asarray(g)) for g in G)
                      if glist else _identity_prefix(G))
    sys_counts = (rows < L).sum(axis=1) if sys_ok else np.zeros(B, dtype=int)
    fast = sys_counts == L
    fast_idx = np.nonzero(fast)[0]

    if systematic == "auto" and sys_ok:
        full_idx = np.nonzero(sys_counts == 0)[0]
    else:
        full_idx = np.nonzero(~fast)[0]
    full_G = (np.empty((0, L, L)) if not full_idx.size else
              _gather_generator_rows(G, glist, full_idx, rows[full_idx]))

    mixed_groups = []
    if systematic == "auto" and sys_ok:
        mixed = (sys_counts > 0) & (sys_counts < L)
        for s in np.unique(sys_counts[mixed]):
            grp = np.nonzero(sys_counts == s)[0]
            g = grp.size
            m_sys = rows[grp] < L                            # (g, L)
            # boolean indexing is row-major, so per-task receive order is
            # preserved inside both partitions
            sys_pos = np.nonzero(m_sys)[1].reshape(g, s)
            par_pos = np.nonzero(~m_sys)[1].reshape(g, L - s)
            sys_rows = np.take_along_axis(rows[grp], sys_pos, axis=1)
            par_rows = np.take_along_axis(rows[grp], par_pos, axis=1)
            # unknown coordinates: per-task complement of the pinned ones
            known = np.zeros((g, L), dtype=bool)
            known[np.arange(g)[:, None], sys_rows] = True
            unk = np.nonzero(~known)[1].reshape(g, L - s)
            Gp = _gather_generator_rows(G, glist, grp, par_rows)
            Gk = np.take_along_axis(Gp, sys_rows[:, None, :], axis=2)
            A = np.take_along_axis(Gp, unk[:, None, :], axis=2)
            mixed_groups.append(
                _MixedGroup(grp, sys_rows, unk, A, Gk, sys_pos, par_pos))
    if tr is not None:
        tr.add_span("plan_decode", t0, tr.now(), cat="plan", track="wall",
                    args={"tasks": B, "L": L, "scatter": int(fast_idx.size),
                          "solved": int(full_idx.size),
                          "mixed_groups": len(mixed_groups)})
    return DecodePlan(B, L, fast_idx, rows[fast_idx], full_idx, full_G,
                      mixed_groups)


def decode_batch(G: np.ndarray, rows: np.ndarray, y: np.ndarray,
                 *, backend: str = "numpy", systematic: str = "auto",
                 identity_prefix: Optional[bool] = None) -> np.ndarray:
    """Recover B systems A_t x_t from exactly-L received coded results each.

    G:    (L̃, L) shared generator, (B, L̃, L) per-task generators, or a
          length-B list of (L̃_b, L) generators (avoids stacking the full
          generators when only the received rows are needed).
    rows: (B, L) int — received coded-row indices per task.
    y:    (B, L) or (B, L, C) received results.

    systematic="auto" (default) exploits an identity prefix (G's top L rows
    are exactly I_L) at every straggler pattern:

    * a task that received *only* systematic rows is a permutation decode —
      ``out[rows] = y``, a scatter, bit-identical to the general solve (LU
      of a permutation matrix is exact) at O(L) instead of O(L³);
    * a task with ``0 < s < L`` systematic rows *substitutes* the known
      coordinates (each received systematic row pins one entry of x
      exactly) and solves only the (L−s)-sized parity block for the rest —
      tasks are grouped by s so each group is one stacked solve.  The
      pinned coordinates are bit-identical to the received values; the
      parity block agrees with the full L×L solve to solver precision.

    "prefix" keeps only the pure-systematic scatter and sends every mixed
    task through the full solve (the pre-substitution behaviour; the
    benchmark baseline for the substitution speedup).  "never" forces the
    general solve for everything.

    ``identity_prefix=True`` skips the O(L²) identity-prefix scan when the
    caller built G systematically (see :func:`plan_decode`).

    Solves run through :class:`StackedLU` (LAPACK ``getrf`` + ``getrs``
    in float64 on the host) on every backend.  This function is the
    composition ``plan_decode(G, rows).apply(y)``; callers re-decoding
    against fixed received rows should hold the plan and call ``apply``.
    """
    check_backend(backend)
    return plan_decode(G, rows, systematic=systematic,
                       identity_prefix=identity_prefix).apply(
                           y, backend=backend)


# ---------------------------------------------------------------------------
# Batched least-squares decode (> L received rows)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=1)
def _lstsq_jit():
    import jax
    import jax.numpy as jnp
    return jax.jit(jax.vmap(lambda A, y: jnp.linalg.lstsq(A, y)[0]))


class LSDecodePlan:
    """X-independent structure of a stacked *least-squares* decode.

    The exact :class:`DecodePlan` consumes exactly L rows per task; when a
    prefix delivered R > L rows (extra parity arrived before the cut), the
    overdetermined solve averages out the float32 encode noise of the
    jax/pallas product path instead of discarding the surplus — the
    streaming analogue of :func:`repro.core.mds.decode_ls`.  Gathered
    generator blocks are frozen at plan time; ``apply`` re-solves per
    right-hand side.  The numpy engine is *literally* a per-task
    ``np.linalg.lstsq`` sweep, so it is bit-identical to the reference by
    construction; jax runs a vmapped jitted ``jnp.linalg.lstsq``.
    """

    __slots__ = ("B", "L", "Gs", "_lu")

    def __init__(self, B: int, L: int, Gs: np.ndarray):
        self.B = B
        self.L = L
        self.Gs = Gs                     # (B, R, L) gathered generator rows
        # R == L is a square system: route it through the same cached-LU
        # solve the exact decode uses, so "least squares with no surplus"
        # is bit-identical to the square decode (tested) instead of
        # merely close via the QR in lstsq
        self._lu = StackedLU(Gs) if Gs.shape[1] == L else None

    def apply(self, y: np.ndarray, *, backend: str = "numpy") -> np.ndarray:
        """Least-squares solve for stacked received results ``y`` of shape
        (B, R) or (B, R, C) → (B, L[, C])."""
        check_backend(backend)
        tr = current_tracer()
        t0 = tr.now() if tr is not None else 0.0
        y = np.asarray(y, dtype=np.float64)
        squeeze = y.ndim == 2
        if squeeze:
            y = y[..., None]
        if self._lu is not None:
            out = self._lu.solve(y)
        elif _use_jax(backend):
            import jax
            with jax.enable_x64(True):
                out = np.asarray(_lstsq_jit()(
                    *_on_decode_device(self.Gs, y)), dtype=np.float64)
        else:
            out = self._apply_np(y)
        if tr is not None:
            tr.add_span("decode_ls_apply", t0, tr.now(), cat="decode",
                        track="wall",
                        args={"tasks": self.B, "L": self.L,
                              "rows": int(self.Gs.shape[1]),
                              "backend": backend})
        return out[..., 0] if squeeze else out

    def _apply_np(self, y: np.ndarray) -> np.ndarray:
        out = np.empty((self.B, self.L, y.shape[-1]))
        for b in range(self.B):
            out[b], *_ = np.linalg.lstsq(self.Gs[b], y[b], rcond=None)
        return out


def plan_decode_ls(G, rows: np.ndarray, *,
                   allow_underdetermined: bool = False) -> LSDecodePlan:
    """Build the :class:`LSDecodePlan` for stacked received rows (B, R),
    R ≥ L.  ``G`` accepts the same forms as :func:`plan_decode` —
    including :class:`SystematicRows` for virtual parity.

    ``allow_underdetermined`` admits R < L for the *degraded* recovery
    path (fault verification rejected rows below coverage): ``lstsq``
    then returns the minimum-norm solution — explicitly reported as
    degraded by the caller, never silently exact."""
    rows = np.asarray(rows)
    glist = isinstance(G, (list, tuple))
    B, R = rows.shape
    if glist:
        L = np.asarray(G[0]).shape[-1]
    else:
        L = G.shape[-1]
    if R < L and not allow_underdetermined:
        raise ValueError(f"least-squares decode needs >= L={L} rows per "
                         f"task, got {R}")
    if not glist and not isinstance(G, SystematicRows):
        G = np.asarray(G, dtype=np.float64)
    Gs = _gather_generator_rows(G, glist, np.arange(B), rows)
    return LSDecodePlan(B, int(L), np.asarray(Gs, dtype=np.float64))


def decode_ls_batch(G, rows: np.ndarray, y: np.ndarray,
                    *, backend: str = "numpy") -> np.ndarray:
    """Least-squares decode of B tasks from ≥ L received rows each —
    the composition ``plan_decode_ls(G, rows).apply(y)``."""
    return plan_decode_ls(G, rows).apply(y, backend=backend)


# ---------------------------------------------------------------------------
# Parity-residual verification (fault detection over surplus rows)
# ---------------------------------------------------------------------------

class VerifyPlan:
    """X-independent structure of a batched parity-residual check.

    A decode consumes exactly L delivered rows; every row delivered
    *beyond* the covering prefix is a free integrity check on the result:
    for surplus row r with generator row G[r],

        resid_r = | y_r − G[r] · x̂ | / (1 + |y_r|)

    is ≈ 0 (float noise) when worker deliveries are honest and O(1) when
    any consumed or surplus row was corrupted.  The gathered surplus
    generator block is frozen at plan time (cached alongside the decode's
    :class:`StackedLU` in the serving step-plan cache); ``residuals``
    re-checks per right-hand side.
    """

    __slots__ = ("B", "L", "Gs")

    def __init__(self, B: int, L: int, Gs: np.ndarray):
        self.B = B
        self.L = L
        self.Gs = Gs                     # (B, S, L) surplus generator rows

    def residuals(self, x_hat: np.ndarray,
                  y_surplus: np.ndarray) -> np.ndarray:
        """Relative parity residual per surplus row.

        ``x_hat`` (B, L) or (B, L, C); ``y_surplus`` (B, S) or (B, S, C)
        → (B, S), the max over C of the relative residuals."""
        x_hat = np.asarray(x_hat, dtype=np.float64)
        y_surplus = np.asarray(y_surplus, dtype=np.float64)
        pred = np.einsum("bsl,bl...->bs...", self.Gs, x_hat)
        r = np.abs(y_surplus - pred) / (1.0 + np.abs(y_surplus))
        if r.ndim == 3:
            r = r.max(axis=-1)
        return r


def plan_verify(G, surplus_rows: np.ndarray) -> VerifyPlan:
    """Build the :class:`VerifyPlan` for stacked surplus rows (B, S).
    ``G`` accepts the same forms as :func:`plan_decode`."""
    surplus_rows = np.asarray(surplus_rows)
    glist = isinstance(G, (list, tuple))
    B = surplus_rows.shape[0]
    if glist:
        L = np.asarray(G[0]).shape[-1]
    else:
        L = G.shape[-1]
    if not glist and not isinstance(G, SystematicRows):
        G = np.asarray(G, dtype=np.float64)
    Gs = _gather_generator_rows(G, glist, np.arange(B), surplus_rows)
    return VerifyPlan(B, int(L), np.asarray(Gs, dtype=np.float64))


def verify_decode(G, rows: np.ndarray, y: np.ndarray,
                  surplus_rows: np.ndarray, y_surplus: np.ndarray, *,
                  tol: float = 1e-6, backend: str = "numpy"):
    """Decode from the earliest covering prefix and parity-check every
    surplus delivered row.

    ``rows`` (B, L) and ``y`` (B, L[, C]) feed the exact decode;
    ``surplus_rows`` (B, S) and ``y_surplus`` (B, S[, C]) are the extra
    deliveries to check.  Returns ``(x_hat, resid, bad)``: the decoded
    (B, L[, C]) result, the (B, S) relative residuals, and the boolean
    flag mask ``resid > tol``.  A flagged row means the system is
    inconsistent — either that surplus row or a row *inside* the decoded
    prefix is corrupt; :func:`localize_faulty_worker` disambiguates.
    """
    x_hat = plan_decode(G, np.asarray(rows)).apply(y, backend=backend)
    resid = plan_verify(G, surplus_rows).residuals(x_hat, y_surplus)
    return x_hat, resid, resid > tol


def localize_faulty_worker(G, rows: np.ndarray, y: np.ndarray,
                           row_workers: np.ndarray, *, tol: float = 1e-6,
                           candidates=None, backend: str = "numpy"):
    """Leave-one-worker-out sweep over ONE task's delivered rows.

    ``rows`` (R,) delivered coded-row ids (prefix + surplus, R > L),
    ``y`` (R,) or (R, C) their products, ``row_workers`` (R,) the worker
    that delivered each row.  For each candidate worker w (most-suspect
    first when ``candidates`` orders them): exclude w's rows; if ≥ L
    remain, decode from the earliest L and residual-check the rest — the
    first exclusion that restores consistency names the culprit.

    Returns ``(worker, x_hat, keep)``: the localised worker (or None
    when no exclusion is consistent), the clean decode over the kept
    rows, and the boolean keep-mask.  Guaranteed to localise when the
    corrupt worker's rows number ≤ R − L − 1 (enough surplus remains to
    re-check after exclusion); with exactly R − L the sweep still
    localises unless the corruption hides in an uncheckable exact-L
    remainder, which candidate ordering makes vanishingly rare.
    """
    rows = np.asarray(rows)
    y = np.asarray(y, dtype=np.float64)
    row_workers = np.asarray(row_workers)
    L = G.shape[-1] if not isinstance(G, (list, tuple)) \
        else np.asarray(G[0]).shape[-1]
    if candidates is None:
        candidates = sorted(set(int(w) for w in row_workers))
    for w in candidates:
        keep = row_workers != w
        if not (~keep).any() or int(keep.sum()) < L:
            continue
        kept_rows = rows[keep]
        kept_y = y[keep]
        x_hat = plan_decode(G, kept_rows[:L][None]).apply(
            kept_y[:L][None], backend=backend)[0]
        if kept_rows.size > L:
            resid = plan_verify(G, kept_rows[L:][None]).residuals(
                x_hat[None], kept_y[L:][None])[0]
            if (resid > tol).any():
                continue
        return int(w), x_hat, keep
    return None, None, None
