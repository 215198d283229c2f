"""Reduction of a JAX profiler trace to the benchmark's device numbers.

Input: the ``.xplane.pb`` that ``jax.profiler.start_trace`` wrote, read
with ``jax.profiler.ProfileData`` (nothing but JAX).  Device planes are
those named ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one
event per executed operation and the ``XLA Modules`` line one per
executed program.  Host and device events share the trace's clock.

Clock alignment: the harness opens a ``TraceAnnotation("bench:window")``
and reads ``time.perf_counter()`` just inside it; that annotation's start
in the trace maps perf-counter seconds onto trace nanoseconds, for the
window's bounds and for the tracer's host spans that label idle gaps.
On a v5e the device's events sit about a millisecond early against the
host's on this clock (``tests/data/small.xplane.pb``): nothing for a
window of seconds, but a gap's label is only as good as that.

Outputs (all seconds, all within the window):

* ``busy_s`` — length of the union of op intervals, averaged over chips;
* ``window_s`` — the window's length;
* ``module_s`` / ``op_s`` — device time per program / per ``program/op``;
* ``top_ops`` — the ten ops that took most time;
* ``top_gaps`` — the ten longest idle gaps, each named by the innermost
  host span open at its midpoint (the span's category: ``plan``,
  ``pack``, ``kernel``, ``decode``; a step span with no stage open is
  ``glue``; none open is ``none``).
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

ANCHOR = "bench:window"
_ID = re.compile(r"\(\d+\)$")


def _merge(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def module_name(raw: str) -> str:
    """Program name without the run id the profiler appends."""
    return _ID.sub("", raw).strip()


def op_name(raw: str) -> str:
    """An ``XLA Ops`` event is named by its HLO text
    (``%fusion.3 = bf16[...] fusion(...)``): keep the instruction name."""
    return raw.split(" = ", 1)[0].strip().lstrip("%")


def label_of(cat: str) -> str:
    if cat in ("plan", "pack", "kernel", "decode", "glue"):
        return cat
    return "glue" if cat == "step" else "none"


def reduce_events(devices: Sequence[Dict[str, list]], window: Tuple[float, float],
                  host_spans: Sequence[Tuple[float, float, str, str]] = ()) -> Dict:
    """``devices``: per chip, ``{"ops": [(name, t0, t1)], "modules":
    [(name, t0, t1)]}`` on the trace clock (ns); ``window`` (lo, hi) ns;
    ``host_spans``: (t0, t1, cat, name) ns.  Returns the numbers above."""
    lo, hi = window
    busy, op_s, module_s = [], {}, {}
    first_busy = None
    for dev in devices:
        mods = sorted((t0, t1, module_name(n)) for n, t0, t1 in dev["modules"])
        iv = []
        mi = 0
        for name, t0, t1 in sorted(dev["ops"], key=lambda e: e[1]):
            c = _clip([(t0, t1)], lo, hi)
            if not c:
                continue
            iv.append(c[0])
            while mi + 1 < len(mods) and mods[mi + 1][0] <= t0:
                mi += 1
            mod = mods[mi][2] if mods and mods[mi][0] <= t0 < mods[mi][1] else "?"
            key = f"{mod}/{op_name(name)}"
            op_s[key] = op_s.get(key, 0.0) + (c[0][1] - c[0][0]) * 1e-9
        for name, t0, t1 in dev["modules"]:
            c = _clip([(t0, t1)], lo, hi)
            if c:
                nm = module_name(name)
                module_s[nm] = module_s.get(nm, 0.0) + (c[0][1] - c[0][0]) * 1e-9
        merged = _merge(iv)
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        if first_busy is None:
            first_busy = merged
    n = max(len(devices), 1)
    for k in op_s:
        op_s[k] /= n
    for k in module_s:
        module_s[k] /= n
    gaps = []
    cur = lo
    for a, b in (first_busy or []) + [(hi, hi)]:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    spans = sorted(host_spans, key=lambda sp: (sp[0], -sp[1]))
    top_gaps = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:10]:
        mid = 0.5 * (a + b)
        inner = None
        for sp in spans:
            if sp[0] > mid:
                break
            if sp[1] >= mid and (inner is None or sp[1] - sp[0] <= inner[1] - inner[0]):
                inner = sp
        top_gaps.append([label_of(inner[2]) if inner else "none", (b - a) * 1e-9])
    top_ops = sorted(op_s.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": sum(busy) / n, "window_s": (hi - lo) * 1e-9,
            "module_s": module_s, "op_s": op_s,
            "top_ops": [[k, v] for k, v in top_ops], "top_gaps": top_gaps}


def load_events(path: str):
    """(devices, anchor_start_ns) from one ``.xplane.pb``."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices, anchor = [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:") and re.fullmatch(
                r"/device:TPU:\d+", plane.name):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(line.name)
                if key:
                    dev[key] = [(e.name, e.start_ns, e.end_ns) for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == ANCHOR and anchor is None:
                        anchor = e.start_ns
    return devices, anchor


def reduce_dir(trace_dir: str, *, anchor_perf: float,
               window_perf: Tuple[float, float],
               host_spans: Sequence[Tuple[float, float, str, str]]) -> Dict:
    """Reduce the trace under ``trace_dir``; times in perf-counter seconds
    are mapped through the window annotation."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one profiler trace under {trace_dir}, "
                           f"found {len(paths)}")
    devices, anchor = load_events(paths[0])
    if anchor is None:
        raise RuntimeError(f"no {ANCHOR!r} annotation in the trace")
    if not devices:
        raise RuntimeError("the trace has no TPU device plane")

    def to_ns(t: float) -> float:
        return anchor + (t - anchor_perf) * 1e9
    spans = [(to_ns(a), to_ns(b), cat, name) for a, b, cat, name in host_spans]
    out = reduce_events(devices, (to_ns(window_perf[0]), to_ns(window_perf[1])), spans)
    out["trace_bytes"] = os.path.getsize(paths[0])
    return out
