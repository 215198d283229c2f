"""Ragged-shard packing: a step's coded shard products as one pass.

The serial engine executes a coded matmul shard-by-shard: one small host
matmul per worker per matrix, then one decode per matmul — a trunk-scope
step with 15 per-layer tasks pays that Python loop ~75 times per token
(`BENCH_serve.json`'s head-vs-trunk wall gap).  This module is the batched
alternative: the *prefix plans* of all matmuls that share a right-hand
operand (one dependency stage of the forward — q/k/v share the
post-norm hidden states, up/gate share the FFN input) are packed into one
row-gather over the layers' persistent encoded caches, executed as a
single product, and decoded through one stacked
:func:`repro.stream.backend.plan_decode` per row-count group.

Layout.  A :class:`PackedShards` concatenates each problem's prefix rows
(gathered from :attr:`CodedLinear._enc`) into one (P, D) float64 buffer
with per-problem offsets — rows stay in delivery order, so slicing the
packed product at the offsets reproduces the serial per-task results
*bit-identically* (the product primitive is row-stable; see
:func:`repro.serve_coded.coded_linear.shard_products`).  For the device
path the same buffer is padded to ``tile``-aligned row tiles and a
128-aligned contraction width::

    problem 0: rows r00 r01 r02 …   ┐ gather            ┌ tile 0 (128, Dp)
    problem 1: rows r10 r11 …       ├──────▶ (P, D) ──▶ │ tile 1 (128, Dp)
    problem 2: rows r20 …           ┘  pad P→T·128,     └ …   (zero rows)
                                       D→Dp=⌈D/128⌉·128

and :func:`repro.kernels.ops.coded_shard_matmul_batch` runs every tile in
one launch (Pallas grid on TPU, ``vmap`` fallback elsewhere).  The
float32 device products are a verification/offload path — decode-feeding
products stay float64 host-side so greedy tokens remain bit-identical to
the uncoded pipeline on every backend.

X-independence.  Everything here is built from dispatch timing alone
(prefix rows, packed gathers, stacked decode plans), so the bridge packs
a whole :class:`~repro.stream.barrier.StepBarrier` when the step is
dispatched and only the products + solves run inside the token loop —
and a multi-token dispatch (``steps_per_dispatch``) re-uses the packs for
every token.
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..obs import DETAIL_TRACK, NULL_SPAN, current_tracer
from ..stream import backend as bk
from .coded_linear import DECODE_ENGINE, CodedLinear, shard_products

__all__ = ["ShardProblem", "PackedShards", "PackedStage",
           "pack_shard_problems"]


@dataclasses.dataclass
class ShardProblem:
    """One coded matmul's prefix execution spec inside a packed stage."""
    key: str
    linear: CodedLinear
    rows: np.ndarray            # (L,) coded-row ids, delivery order
    used_solve: bool


class PackedShards:
    """Packed row-gather over the problems' persistent encoded caches.

    ``products(X)`` is the one-pass host execution; ``device_tiles()`` /
    ``products_device(X)`` are the 128-aligned tile layout and the
    one-launch kernel execution for the jax/pallas backends.
    """

    def __init__(self, problems: Sequence[ShardProblem], *, tile: int = 128):
        if not problems:
            raise ValueError("pack needs at least one problem")
        D = {p.linear.D for p in problems}
        if len(D) != 1:
            raise ValueError(f"packed problems must share the contraction "
                             f"width D, got {sorted(D)}")
        self.problems = list(problems)
        self.D = D.pop()
        self.tile = int(tile)
        counts = np.array([p.rows.size for p in self.problems])
        self.offsets = np.concatenate([[0], np.cumsum(counts)])
        self.total = int(self.offsets[-1])
        # the packed host buffer: one storage-agnostic gather per problem
        # (materialised: the packed [W; WR] cache; virtual: W rows + the
        # memoised per-block counter-derived encodes — same bits)
        self.W_packed = np.empty((self.total, self.D))
        for i, p in enumerate(self.problems):
            p.linear.gather_encoded(
                p.rows,
                out=self.W_packed[self.offsets[i]:self.offsets[i + 1]])
        self._tiles = None
        self._gen_specs = None

    # -- host one-pass execution (float64, bit-identical to serial) ---------

    def products(self, X: np.ndarray) -> List[np.ndarray]:
        """All problems' shard products in one contraction → per-problem
        (L_t, B) float64 slices (bit-identical to the serial per-worker
        loop: the primitive is row-stable)."""
        Y = shard_products(self.W_packed, np.asarray(X, dtype=np.float64))
        return [Y[self.offsets[i]:self.offsets[i + 1]]
                for i in range(len(self.problems))]

    # -- device tile layout + one-launch execution (float32) ----------------

    @property
    def n_tiles(self) -> int:
        return -(-self.total // self.tile)

    def gather_index(self) -> Tuple[np.ndarray, np.ndarray]:
        """(T·tile,) per-lane (problem, local-row) gather indices; padding
        lanes carry (-1, -1).  This is the scatter map back from tile
        space to per-problem outputs."""
        lanes = self.n_tiles * self.tile
        prob = np.full(lanes, -1, dtype=np.int64)
        row = np.full(lanes, -1, dtype=np.int64)
        for i, p in enumerate(self.problems):
            o = self.offsets[i]
            prob[o:o + p.rows.size] = i
            row[o:o + p.rows.size] = np.arange(p.rows.size)
        return prob, row

    def device_tiles(self):
        """(T, tile, Dp) float32 device tiles of the packed rows, gathered
        from each layer's incremental device cache (zero rows pad the last
        tile; Dp pads D to the 128-lane MXU width).

        Virtual-parity problems gather only their *systematic* lanes from
        the device-resident W; parity lanes are zeroed here and their
        products written by the generated-parity kernel at execution time
        (:meth:`products_device`) — no ``[W; WR]`` mirror ever exists."""
        import jax.numpy as jnp
        parts = []
        for p in self.problems:
            r = np.asarray(p.rows)
            if p.linear.parity_storage == "virtual":
                sys_m = r < p.linear.L
                gat = jnp.asarray(np.where(sys_m, r, 0))
                part = p.linear.device_W()[gat]
                parts.append(part * jnp.asarray(
                    sys_m[:, None].astype(np.float32)))
            else:
                n = max(int(r.max()) + 1, p.linear.L)
                parts.append(p.linear.device_rows(n)[r])
        packed = jnp.concatenate(parts) if len(parts) > 1 else parts[0]
        lanes = self.n_tiles * self.tile
        Dp = -(-self.D // 128) * 128
        packed = jnp.pad(packed, ((0, lanes - self.total),
                                  (0, Dp - self.D)))
        return packed.reshape(self.n_tiles, self.tile, Dp)

    def products_device(self, X: np.ndarray, *, backend: str = "pallas",
                        interpret: Optional[bool] = None) -> List[np.ndarray]:
        """One-launch device execution of every packed product.

        ``backend="pallas"`` runs the tiles through one
        :func:`~repro.kernels.ops.coded_shard_matmul_batch` Pallas grid;
        ``"jax"`` takes the ``vmap`` fallback.  Float32 — the offload /
        verification path, not the decode-feeding one.
        """
        import jax.numpy as jnp
        from ..kernels import ops
        if self._tiles is None:
            self._tiles = self.device_tiles()
        if self._gen_specs is None:
            # virtual-parity lane specs, frozen once per pack: the flat
            # tile-space lane, its packed threefry counter, and the layer
            # key/W the generated kernel derives the row from
            self._gen_specs = []
            for i, p in enumerate(self.problems):
                if p.linear.parity_storage != "virtual":
                    continue
                r = np.asarray(p.rows)
                par_pos = np.nonzero(r >= p.linear.L)[0]
                if not par_pos.size:
                    continue
                self._gen_specs.append(ops.GeneratedParity(
                    lanes=self.offsets[i] + par_pos,
                    ctrs=p.linear.parity_ctrs(r[par_pos] - p.linear.L),
                    key=p.linear.pkey,
                    w=p.linear.device_W()))
        X = np.asarray(X, dtype=np.float64)
        Dp = self._tiles.shape[-1]
        Xp = jnp.pad(jnp.asarray(X.T, jnp.float32), ((0, Dp - self.D),
                                                     (0, 0)))
        Y = ops.coded_shard_matmul_batch(
            self._tiles, Xp, mode="pallas" if backend == "pallas" else "vmap",
            parity_mode="generated" if self._gen_specs else "materialized",
            parity=self._gen_specs or None,
            interpret=interpret)
        flat = np.asarray(Y, dtype=np.float64).reshape(-1, X.shape[0])
        return [flat[self.offsets[i]:self.offsets[i + 1]]
                for i in range(len(self.problems))]


def pack_shard_problems(problems: Sequence[ShardProblem], *,
                        tile: int = 128) -> PackedShards:
    """Bucket a stage's ragged shard row-slices into one packed gather."""
    return PackedShards(problems, tile=tile)


class _DecodeGroup:
    """Stacked decode structure for one (L, s) group of a stage.

    The same substitution decomposition :func:`repro.stream.backend
    .plan_decode` builds — received systematic rows pin coordinates, the
    (L−s)-sized parity block solves the rest — specialised to the serving
    layout: the systematic generator is ``[I; R]`` by construction, so the
    parity sub-blocks gather straight from each layer's parity rows
    (:meth:`CodedLinear.parity_rows` — dense-R slice or counter
    derivation, no dense generator), and every index set is one
    fancy-index array.  Per-item
    solve inputs are value-identical to the serial engine's, and LAPACK's
    ``getrf`` + ``getrs`` is deterministic per matrix, so the decoded
    outputs match the serial path bit-for-bit regardless of how tasks are
    stacked.
    """

    __slots__ = ("sel", "perm", "rows", "sys_pos", "par_pos", "sys_rows",
                 "unk", "lu", "Gk")

    def __init__(self, sel, problems, rows, s):
        self.sel = sel                          # (gs,) indices into L-group
        L = rows.shape[1]
        if s == L:
            self.perm = True
            self.rows = rows
            return
        self.perm = False
        gs = sel.size
        if gs == 1:                             # the dominant serving case
            r = rows[0]
            m_sys = r < L
            sys_pos = np.nonzero(m_sys)[0]
            par_pos = np.nonzero(~m_sys)[0]
            self.sys_pos = sys_pos[None]
            self.par_pos = par_pos[None]
            sys_rows = r[sys_pos]
            self.sys_rows = sys_rows[None]
            known = np.zeros(L, dtype=bool)
            known[sys_rows] = True
            unk = np.nonzero(~known)[0]
            self.unk = unk[None]
            # parity generator sub-blocks via the storage-agnostic row
            # gather (materialised: a dense-R slice; virtual: the counter
            # derivation) — then the two needed column gathers
            pr = r[par_pos] - L
            Rr = problems[sel[0]].linear.parity_rows(pr)
            # single-axis fancy column gathers come out F-ordered; the
            # serial engine's blocks are C-ordered, and BLAS results are
            # layout-sensitive at the last bit — copy to C for bit-parity
            self.Gk = np.ascontiguousarray(Rr[:, sys_rows])[None]
            self.lu = bk.StackedLU(np.ascontiguousarray(Rr[:, unk])[None])
            return
        m_sys = rows < L
        self.sys_pos = np.nonzero(m_sys)[1].reshape(gs, s)
        self.par_pos = np.nonzero(~m_sys)[1].reshape(gs, L - s)
        self.sys_rows = np.take_along_axis(rows, self.sys_pos, axis=1)
        par_rows = np.take_along_axis(rows, self.par_pos, axis=1)
        known = np.zeros((gs, L), dtype=bool)
        known[np.arange(gs)[:, None], self.sys_rows] = True
        self.unk = np.nonzero(~known)[1].reshape(gs, L - s)
        Rg = [problems[i].linear.parity_rows(par_rows[j] - L)
              for j, i in enumerate(sel)]
        self.Gk = np.stack(
            [Rg[j][:, self.sys_rows[j]]
             for j in range(gs)])                           # (gs, L-s, s)
        self.lu = bk.StackedLU(np.stack(
            [Rg[j][:, self.unk[j]]
             for j in range(gs)]))                          # (gs, L-s, L-s)

    def apply(self, yg: np.ndarray, z: np.ndarray, tr=None) -> None:
        """Decode this group's slice of the stacked products into ``z``.

        Every engine solves through the group's cached float64 LU factors
        on the host: getrf on the first solve of a frozen stage, one
        LAPACK getrs per step after that.  With a tracer ``tr``, the
        right-hand side, the solve and the scatter are ``decode.rhs`` /
        ``decode.solve`` / ``decode.scatter`` sub-spans on the detail
        lane."""
        if self.perm:
            with (tr.span("decode.scatter", cat="decode.scatter",
                          track=DETAIL_TRACK) if tr is not None
                  else NULL_SPAN):
                z[self.sel[:, None], self.rows] = yg[self.sel]
            return
        g = self.sel.size
        with (tr.span("decode.rhs", cat="decode.rhs", track=DETAIL_TRACK)
              if tr is not None else NULL_SPAN):
            if g == 1:
                # dominant serving case: 1D gathers + a 2D gemm gather the
                # same values as the stacked path below (one dgemm either
                # way), minus the broadcast-index overhead per call
                y0 = yg[self.sel[0]]
                sys_y = y0[self.sys_pos[0]]
                rhs = (y0[self.par_pos[0]] - self.Gk[0] @ sys_y)[None]
            else:
                ys = yg[self.sel]
                g_ar = np.arange(g)[:, None]
                sys_y = ys[g_ar, self.sys_pos]
                rhs = ys[g_ar, self.par_pos] - self.Gk @ sys_y
        with (tr.span("decode.solve", cat="decode.solve", track=DETAIL_TRACK,
                      args={"systems": g, "order": int(rhs.shape[1])})
              if tr is not None else NULL_SPAN):
            sol = self.lu.solve(rhs)
        with (tr.span("decode.scatter", cat="decode.scatter",
                      track=DETAIL_TRACK) if tr is not None else NULL_SPAN):
            if g == 1:
                z0 = z[self.sel[0]]
                z0[self.sys_rows[0]] = sys_y                 # exact pins
                z0[self.unk[0]] = sol[0]
            else:
                sel2 = self.sel[:, None]
                z[sel2, self.sys_rows] = sys_y               # exact pins
                z[sel2, self.unk] = sol


class PackedStage:
    """One dependency stage of a step: packed products + grouped decode.

    Problems are ordered by matrix height L at pack time, so each height
    group's stacked products are a contiguous *view* of the packed
    product buffer, and each (L, s) straggler group decodes as one
    stacked substitution solve (:class:`_DecodeGroup`) — a stage costs
    one contraction plus one solve launch per group instead of a Python
    loop of per-matmul decodes.
    """

    def __init__(self, problems: Sequence[ShardProblem], *,
                 backend: str = "numpy", tile: int = 128):
        if len(problems) > 1:
            order = sorted(range(len(problems)),
                           key=lambda i: (problems[i].linear.L, i))
            self.problems = [problems[i] for i in order]
        else:
            self.problems = list(problems)
        self.backend = backend
        # the decode-solve engine this stage runs — the bridge logs it
        self.solve_backend = DECODE_ENGINE[backend]
        self.pack = pack_shard_problems(self.problems, tile=tile)
        # decode groups: (offset problem index, L, member count, subgroups)
        self.groups: List[Tuple[int, int, int, List[_DecodeGroup]]] = []
        if len(self.problems) == 1:
            p = self.problems[0]
            L = p.linear.L
            s = int((p.rows < L).sum())
            self.groups.append(
                (0, L, 1, [_DecodeGroup(np.zeros(1, dtype=np.int64),
                                        self.problems, p.rows[None],
                                        s)]))
            return
        i = 0
        n = len(self.problems)
        while i < n:
            L = self.problems[i].linear.L
            j = i
            while j < n and self.problems[j].linear.L == L:
                j += 1
            members = self.problems[i:j]
            rows = np.stack([p.rows for p in members]) if j - i > 1 \
                else members[0].rows[None]
            s_counts = (rows < L).sum(axis=1)
            subs = [_DecodeGroup(np.nonzero(s_counts == s)[0],
                                 self.problems[i:j], rows[s_counts == s],
                                 int(s))
                    for s in np.unique(s_counts)]
            self.groups.append((i, L, j - i, subs))
            i = j

    def execute(self, X: np.ndarray, *,
                device_products: bool = False,
                mutate=None) -> Dict[str, np.ndarray]:
        """Decode every problem of the stage for one activation batch →
        ``{key: (B, L) exact product}``.

        ``mutate``, when given, is called with the packed product buffer
        ``Y`` (total_rows, B) after the products and before the decode —
        the fault injector's hook for corrupting a worker's returned
        rows exactly where a real Byzantine worker would (the per-problem
        row ranges are ``self.pack.offsets`` / ``self.problems``).  The
        buffer is freshly materialised here, so in-place edits never
        touch the packed weight cache."""
        tr = current_tracer()
        if device_products and self.backend != "numpy":
            # the kernel launch inside products_device times itself
            # (repro.kernels.ops device_span) — no outer kernel span here,
            # stage categories must not double count
            y = self.pack.products_device(X, backend=self.backend)
            Y = np.concatenate(y) if len(y) > 1 else y[0]
        else:
            ctx = tr.span("stage:products", cat="kernel",
                          args={"rows": self.pack.total,
                                "problems": len(self.problems)}) \
                if tr is not None else contextlib.nullcontext()
            with ctx:
                Y = shard_products(self.pack.W_packed,
                                   np.asarray(X, dtype=np.float64))
        if mutate is not None:
            mutate(Y)
        out: Dict[str, np.ndarray] = {}
        B = Y.shape[-1]
        off = self.pack.offsets
        ctx = tr.span("stage:decode", cat="decode",
                      args={"groups": len(self.groups),
                            "solve": self.solve_backend}) \
            if tr is not None else contextlib.nullcontext()
        with ctx:
            for i0, L, g, subs in self.groups:
                yg = Y[off[i0]:off[i0] + g * L].reshape(g, L, B)  # a view
                z = np.empty((g, L, B))
                for sub in subs:
                    sub.apply(yg, z, tr)
                for j in range(g):
                    out[self.problems[i0 + j].key] = z[j].T
        return out
