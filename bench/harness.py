"""One run of one cell: set-up, the measured window, the correctness check.

The system under test is ``repro.serve_coded.CodedServingBridge`` as the
configuration's ``deployment.serving`` block sets it (the coded output
head on the Pallas backend, device products, virtual parity, fractional
plans, EDF admission, no per-step reference product).

Set-up: weights from the seed (``model.make_params``), the bridge with its
KV caches sized for the whole mix, then the probe (``traffic.probe_
requests``) served to completion: it compiles every prefill bucket and
the decode program, walks every master's batch through every size, and
fills the parity draws and the plan cache.

Window: one ``serve()`` of the seeded stream.  The harness watches the
served path where the bridge calls the jitted trunk (the ``prefill_fn`` /
``decode_fn`` it keeps in ``bridge._model``): at each call it stamps the
clock, counts tokens, keeps the request's slot (prompt and served tokens)
and, once ``--seconds`` have passed, ends the serve by raising
:class:`WindowClosed` from that call.  Every step that started before the
close has completed by then, so tokens over the window's wall time is the
served rate.

Check: after the window the program's state is freed and the plain
float32 reference (``model.reference_logits``) runs over a seeded sample
of the served requests (prompt plus served tokens, teacher forced).  The
number compared is the widest gap by which a served token's reference
logit lies below the reference's best at that position; its limit is in
``checks/<workload>.json``.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

import model
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)


class WindowClosed(Exception):
    """Raised from a trunk call once the measured window is over."""


class BenchError(RuntimeError):
    """A run that cannot produce a result (no trunk call, stream too short,
    a compile inside the window, ...)."""


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------

def load_benchmark(path: str = os.path.join(REPO, "BENCHMARK.json")) -> Dict:
    with open(path) as f:
        return json.load(f)


def find_cell(bench: Dict, workload: str) -> Dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise BenchError(f"no workload {workload!r} in BENCHMARK.json")


def load_check(root: str, workload: str) -> Dict:
    with open(os.path.join(root, "checks", f"{workload}.json")) as f:
        return json.load(f)


def load_peaks(kind: str) -> Dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# compile watch
# ---------------------------------------------------------------------------

class CompileWatch:
    """Counts JAX compile events (``jax.monitoring``) while armed."""

    def __init__(self):
        self.armed = False
        self.counts: Dict[str, int] = {}
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
        if self.armed and ("compile" in event or "trace_duration" in event):
            self.counts[event] = self.counts.get(event, 0) + 1

    def in_window(self) -> int:
        return sum(self.counts.values())


# ---------------------------------------------------------------------------
# the observer on the served path
# ---------------------------------------------------------------------------

class Observer:
    """Wraps the bridge's jitted trunk programs.

    The bridge calls ``decode_fn`` from ``hidden_states_jit(st, slot_ids)``
    (locals ``st``: the master's state, ``cont``: continuing slots) and
    ``prefill_fn`` per admitted slot (local ``slot``), both inside
    ``_execute_step(m, sp)``.  The observer reads those locals to know the
    master, the step and the request; it keeps the slot objects, whose
    ``tokens`` list the bridge extends with every served token."""

    def __init__(self, prefill_fn, decode_fn):
        self.prefill_fn, self.decode_fn = prefill_fn, decode_fn
        self.deadline: Optional[float] = None
        self.reset()

    def reset(self) -> None:
        self.t_close: Optional[float] = None
        self.tokens = 0
        self.gaps: List[float] = []
        self.step_start: Dict[int, float] = {}     # master -> current step t
        self.step_obj: Dict[int, object] = {}      # master -> current step
        self.steps = 0
        self.prefills: List[int] = []              # prompt lengths
        self.decodes: List[tuple] = []             # (slots, sum of pos + 1)
        self.slots: Dict[int, object] = {}         # rid -> bridge slot

    def _enter(self, depth: int):
        now = time.perf_counter()
        if self.deadline is not None and now >= self.deadline:
            self.t_close = now
            raise WindowClosed()
        f = sys._getframe(depth)
        step = f.f_back.f_locals
        m, sp = step["m"], step["sp"]
        prev = None
        if self.step_obj.get(m) is not sp:
            prev = self.step_start.get(m)
            self.step_obj[m] = sp
            self.step_start[m] = now
            self.steps += 1
        return now, f.f_locals, prev

    def prefill(self, params, batch, caches):
        _now, loc, _prev = self._enter(2)
        slot = loc["slot"]
        self.slots[slot.rid] = slot
        self.tokens += 1
        self.prefills.append(int(batch["tokens"].shape[1]))
        return self.prefill_fn(params, batch, caches)

    def decode(self, params, toks, pos, caches):
        now, loc, prev = self._enter(2)
        st, cont = loc["st"], loc["cont"]
        if prev is not None:
            # every continuing slot waited from its master's previous step
            self.gaps.extend([now - prev] * len(cont))
        possum = 0
        for s in cont:
            sl = st.slots[s]
            self.slots[sl.rid] = sl
            possum += sl.pos + 1
        self.tokens += len(cont)
        self.decodes.append((len(cont), possum))
        return self.decode_fn(params, toks, pos, caches)


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RunData:
    """What the per-layer metric readers read (``metrics/<name>.py``)."""
    sizes: model.Sizes
    peaks: Dict
    window_s: float
    steps: int
    prefills: List[int]
    decodes: List[tuple]
    probe_steps: List[Dict]
    stage_wall: Optional[Dict[str, float]] = None    # tracer, traced run
    device: Optional[Dict] = None                    # profiler reduction
    kernel_calls: Optional[List[Dict]] = None        # products kernel shapes


def _device_info(jax) -> Dict:
    devs = jax.devices()
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def _memory_peak(jax) -> int:
    peaks = []
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            peaks.append(int(st["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


def _record_kernel_calls(ops_mod, calls: List[Dict]):
    """Wrap ``ops.coded_shard_matmul_batch`` (the products entry the
    packing layer calls) to record each call's shapes."""
    inner = ops_mod.coded_shard_matmul_batch

    def wrapped(tiles, x, **kw):
        par = kw.get("parity") or []
        calls.append({"tiles": tuple(int(v) for v in tiles.shape),
                      "x": tuple(int(v) for v in x.shape),
                      "parity": [(int(len(p.lanes)),) + tuple(int(v) for v in p.w.shape)
                                 for p in par]})
        return inner(tiles, x, **kw)
    ops_mod.coded_shard_matmul_batch = wrapped
    return lambda: setattr(ops_mod, "coded_shard_matmul_batch", inner)


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             t_process: float, bench: Optional[Dict] = None,
             root: str = HERE, patch: Optional[Callable] = None,
             control: bool = False) -> Dict:
    """One run; returns the result line's dict.  With ``control`` it also
    holds, under ``"control"``, the control's reading on the same served
    requests and what the same comparison makes of it."""
    import jax
    from repro.launch import serve as launch_serve
    from repro.models import ArchConfig, LayerSpec, init_model
    from repro.serve_coded import CodedServingBridge, ServeRequest
    from repro.sim.cluster import ec2_cluster
    from repro.stream import AdmissionConfig, StreamConfig

    bench = bench if bench is not None else load_benchmark()
    cell = find_cell(bench, workload)
    cfg = model.load_config(cell["config"], root)
    mix = traffic.load_mix(cell["traffic"], root)
    limits = load_check(root, workload)
    s = model.sizes_of(cfg)
    dep = cfg["deployment"]
    srv, pool = dep["serving"], dep["pool"]
    watch = CompileWatch()
    jax.monitoring.register_event_duration_secs_listener(watch)
    dev = _device_info(jax)

    # ---- set-up ------------------------------------------------------------
    marks = [("start", time.time())]
    params = model.make_params(s, seed)
    jax.block_until_ready(params)
    marks.append(("weights", time.time()))
    arch = ArchConfig(
        name=cfg["name"], family="dense", d_model=s.d, n_heads=s.heads,
        n_kv_heads=s.kv_heads, d_head=s.head_dim, d_ff=s.d_ff, vocab=s.vocab,
        block=(LayerSpec(mixer="attn", ffn="swiglu" if s.act == "silu" else s.act),),
        n_repeats=s.layers, ffn_act="swiglu" if s.act == "silu" else s.act,
        rope_base=s.rope_theta, norm_eps=s.eps, dtype=s.dtype)
    want = jax.eval_shape(lambda k: init_model(k, arch), jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise BenchError("the benchmark's weights do not match the program's "
                         "parameter layout for this configuration")
    # the program memoises (arch, smoke, seed) -> (cfg, params): the bridge
    # then serves the benchmark's weights instead of initialising its own
    # ``--seed`` drives the weights and the traffic; the bridge's own seed
    # (plan solve, delay stream, parity generator) is the configuration's
    # ``stream_seed``: it decides how many parity rows each step's decode
    # solves, so a per-run seed there would change the work per run
    sseed = int(srv["stream_seed"])
    launch_serve._MODEL_CACHE[(arch, False, sseed)] = (arch, params)
    profile = ec2_cluster(N=int(pool["workers"]), n_fast=int(pool["fast_workers"]),
                          rng=int(pool["seed"]),
                          gamma_over_u=float(pool["gamma_over_u"]))
    bridge = CodedServingBridge(
        profile, masters=int(mix["masters"]), arch=arch, smoke=False,
        config=StreamConfig(policy=srv["plan_policy"],
                            admission=AdmissionConfig(policy=srv["admission"]),
                            rng=sseed),
        slots_per_master=int(mix["slots_per_master"]),
        coding_scope=srv["coding_scope"], backend=srv["backend"],
        device_products=bool(srv["device_products"]),
        parity_storage=srv["parity_storage"], verify=bool(srv["verify"]))
    bridge._setup_model(traffic.max_len(mix))
    marks.append(("bridge", time.time()))
    mdl = bridge._model
    obs = Observer(mdl["prefill_fn"], mdl["decode_fn"])
    mdl["prefill_fn"], mdl["decode_fn"] = obs.prefill, obs.decode

    def to_serve(reqs):
        return [ServeRequest(rid=r.rid, master=r.master, prompt=r.prompt,
                             gen_len=r.gen_len, t_arrive=r.t_arrive,
                             slack=r.slack) for r in reqs]

    probe_rep = bridge.serve(to_serve(traffic.probe_requests(mix, s.vocab, seed)))
    probe_steps = [dict(st) for st in probe_rep.steps]
    marks.append(("probe", time.time()))
    window_reqs = to_serve(traffic.window_requests(mix, s.vocab, seed))
    marks.append(("stream", time.time()))
    if patch is not None:
        patch(bridge, obs)
    obs.reset()

    tracer = kernel_calls = restore = trace_dir = None
    if trace:
        from repro.kernels import ops as ops_mod
        from repro.obs import Tracer
        tracer = Tracer(jax_profiler=True)
        bridge.tracer = tracer
        kernel_calls = []
        restore = _record_kernel_calls(ops_mod, kernel_calls)
        os.makedirs(os.path.join(REPO, ".bench_cache"), exist_ok=True)
        trace_dir = tempfile.mkdtemp(prefix="trace-",
                                     dir=os.path.join(REPO, ".bench_cache"))
        po = jax.profiler.ProfileOptions()
        po.python_tracer_level = 0
        po.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=po)

    # ---- window ------------------------------------------------------------
    gc.collect()
    watch.armed = True
    t_open = time.perf_counter()
    setup_s = time.time() - t_process
    obs.deadline = t_open + float(seconds)
    closed = False
    try:
        if trace:
            with jax.profiler.TraceAnnotation("bench:window"):
                t_anchor = time.perf_counter()
                bridge.serve(window_reqs)
        else:
            bridge.serve(window_reqs)
    except WindowClosed:
        closed = True
    watch.armed = False
    if trace:
        jax.profiler.stop_trace()
        restore()
    if not closed:
        raise BenchError("the request stream ran out before the window closed")
    t_close = obs.t_close
    window_s = t_close - t_open
    if obs.steps == 0 or obs.tokens == 0:
        raise BenchError("the window observed no call into the trunk")
    compiles = watch.in_window()
    print(f"[bench] compile events inside the window: {compiles} "
          f"{json.dumps(watch.counts)}", flush=True)
    print(f"[bench] setup {setup_s:.3f} s: jax start {marks[0][1] - t_process:.1f} s, "
          + ", ".join(f"{b[0]} {b[1] - a[1]:.1f} s" for a, b in zip(marks, marks[1:]))
          + f"; {len(probe_steps)} probe steps; backend compile or cache load "
          f"{watch.seconds:.1f} s", flush=True)
    print(f"[bench] window {window_s:.3f} s, {obs.steps} coded steps, "
          f"{obs.tokens} tokens, {len(obs.prefills)} prefills, "
          f"{len(obs.gaps)} token gaps", flush=True)
    if compiles:
        raise BenchError(f"{compiles} compile events inside the window")
    mem_peak = _memory_peak(jax)

    # ---- metrics -----------------------------------------------------------
    peaks = load_peaks(dev["kind"]) if dev["platform"] == "tpu" else {}
    run = RunData(sizes=s, peaks=peaks, window_s=window_s, steps=obs.steps,
                  prefills=list(obs.prefills), decodes=list(obs.decodes),
                  probe_steps=probe_steps, kernel_calls=kernel_calls)
    device_extra = {}
    breakdown = None
    if trace:
        import devtrace
        run.stage_wall = dict(tracer.summary()["per_stage_wall"])
        if dev["platform"] == "tpu":
            red = devtrace.reduce_dir(
                trace_dir, anchor_perf=t_anchor, window_perf=(t_open, t_close),
                host_spans=[(sp.t0 + tracer.epoch, sp.t1 + tracer.epoch, sp.cat, sp.name)
                            for sp in tracer.spans if sp.track == "wall"])
            run.device = red
            device_extra = {"busy_s": red["busy_s"], "window_s": red["window_s"]}
            breakdown = {"device_ops": red["top_ops"], "idle_gaps": red["top_gaps"]}
            print(f"[bench] trace: {red['trace_bytes']} bytes, busy {red['busy_s']:.3f} s "
                  f"of {red['window_s']:.3f} s", flush=True)
            top = sorted(red["module_s"].items(), key=lambda kv: -kv[1])[:15]
            print(f"[bench] device programs: {json.dumps(top)}", flush=True)
        shutil.rmtree(trace_dir, ignore_errors=True)
        metric_defs = [m for m in bench["per_layer"]
                       if workload in m.get("workloads", [workload])]
        metrics = {}
        for m in metric_defs:
            v = load_reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        gaps_ms = 1e3 * np.asarray(obs.gaps)
        values = {"tokens_per_s": obs.tokens / window_s,
                  "setup_s": setup_s}
        if gaps_ms.size >= 20:
            values["tbt_p95_ms"] = float(np.percentile(gaps_ms, 95))
        print(f"[bench] tbt samples {gaps_ms.size}: p50 "
              f"{np.median(gaps_ms) if gaps_ms.size else float('nan'):.1f} ms",
              flush=True)
        metrics = {}
        for m in bench["end_to_end"]:
            if workload in m.get("workloads", [workload]) and m["name"] in values:
                metrics[m["name"]] = {"value": float(values[m["name"]]),
                                      "unit": m["unit"]}

    # ---- check -------------------------------------------------------------
    sample = _sample(obs.slots, seed, int(mix["check"]["max_requests"]))
    served = {rid: list(sl.tokens) for rid, sl in obs.slots.items()}
    prompts = {rid: np.asarray(sl.prompt) for rid, sl in obs.slots.items()}
    del obs.slots, obs, bridge, mdl, probe_rep
    launch_serve._MODEL_CACHE.clear()
    gc.collect()
    t_check = time.perf_counter()
    seqs = [(prompts[r], served[r]) for r in sample]
    gaps = reference_gaps(params, s, seqs)
    gap = max((float(g.max()) for g in gaps), default=float("inf"))
    n_tok = int(sum(g.size for g in gaps))
    limit = float(limits["max_logit_gap"])
    failed = sum(1 for g in gaps if g.size and float(g.max()) > limit)
    correct = judge(gap, n_tok, limit)
    check_s = time.perf_counter() - t_check
    check = {"max_logit_gap": {"value": gap, "limit": limit},
             "tokens_compared": {"value": n_tok, "limit": 1}}
    ctrl = None
    if control:
        ctrl_gap = control_gaps(params, s, seqs)
        ctrl = {"correct": judge(ctrl_gap, n_tok, limit),
                "max_logit_gap": {"value": ctrl_gap, "limit": limit}}
    print(f"[bench] check: {len(seqs)} requests, {n_tok} served tokens, "
          f"{check_s:.1f} s", flush=True)
    out = {"correct": correct, "attempted": len(seqs), "failed": failed,
           "metrics": metrics,
           "device": dict(dev, memory_peak_bytes=mem_peak, **device_extra)}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if ctrl is not None:
        out["control"] = ctrl
    out["check"] = check
    return out


def judge(max_logit_gap: float, tokens_compared: int, limit: float) -> bool:
    """The comparison that decides ``correct``, for the program and for the
    control alike."""
    return bool(tokens_compared >= 1 and max_logit_gap <= limit)


def load_reader(name: str):
    import importlib.util
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _sample(slots: Dict, seed: int, k: int) -> List[int]:
    """The longest served request plus a seeded sample of the others."""
    rids = sorted(r for r, sl in slots.items() if len(sl.tokens) > 0)
    if len(rids) <= k:
        return rids
    longest = max(rids, key=lambda r: (len(slots[r].tokens), -r))
    rest = [r for r in rids if r != longest]
    rng = np.random.default_rng((int(seed), 0xC4EC))
    pick = rng.choice(len(rest), size=k - 1, replace=False)
    return sorted([longest] + [rest[i] for i in pick])


def _chunks(seqs, tokens_per_chunk: int = 4096, bucket: int = 512):
    """Group sequences into fixed-shape (b, T_pad) batches: right padding
    leaves a causal forward's valid positions unchanged."""
    order = sorted(range(len(seqs)), key=lambda i: len(seqs[i][0]) + len(seqs[i][1]))
    i = 0
    while i < len(order):
        T = len(seqs[order[i]][0]) + len(seqs[order[i]][1])
        T_pad = -(-T // bucket) * bucket
        b = max(1, tokens_per_chunk // T_pad)
        grp = []
        while i < len(order) and len(grp) < b:
            j = order[i]
            if len(seqs[j][0]) + len(seqs[j][1]) > T_pad:
                break
            grp.append(j)
            i += 1
        yield grp, b, T_pad


def _batch(seqs, grp, b, T_pad):
    toks = np.zeros((b, T_pad), np.int32)
    tgt = np.zeros((b, T_pad), np.int32)
    for r, j in enumerate(grp):
        p, g = seqs[j]
        full = np.concatenate([np.asarray(p, np.int32), np.asarray(g, np.int32)])
        toks[r, :full.size] = full
        # logits at position t predict the token at t + 1
        tgt[r, :full.size - 1] = full[1:]
    return toks, tgt


def reference_gaps(params, s: model.Sizes, seqs) -> List[np.ndarray]:
    """Per sequence, the gap of every served token (reference's best logit
    minus its logit of the served token) at its position."""
    out: List[Optional[np.ndarray]] = [None] * len(seqs)
    for grp, b, T_pad in _chunks(seqs):
        toks, tgt = _batch(seqs, grp, b, T_pad)
        logits = model.reference_logits(params, toks, s)
        g = np.asarray(model.gaps_of(logits, tgt))
        for r, j in enumerate(grp):
            P, n = len(seqs[j][0]), len(seqs[j][1])
            out[j] = g[r, P - 1:P - 1 + n]
    return out


def control_gaps(params, s: model.Sizes, seqs) -> float:
    """The control's widest gap: at each position of the same sequences,
    the reference's gap of the token that the fp8 forward
    (:func:`model.control_logits`) puts first."""
    import jax.numpy as jnp
    worst = 0.0
    for grp, b, T_pad in _chunks(seqs):
        toks, _ = _batch(seqs, grp, b, T_pad)
        ref = model.reference_logits(params, toks, s)
        low = model.control_logits(params, toks, s)
        top = jnp.argmax(low, axis=-1).astype(jnp.int32)
        g = np.asarray(model.gaps_of(ref, top))
        del ref, low
        for r, j in enumerate(grp):
            P, n = len(seqs[j][0]), len(seqs[j][1])
            worst = max(worst, float(g[r, P - 1:P - 1 + n].max()))
    return worst
