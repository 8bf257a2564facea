"""Reduction of a profiler trace (`.xplane.pb`) to the benchmark's numbers.

The traced window is the host span the harness opened around it
(`jax.profiler.TraceAnnotation(WINDOW)`). On each TPU plane the events of
the "XLA Ops" line are the device's operations, named by their HLO text
("%fusion.3 = f32[...] fusion(...)"), and the "XLA Modules" line says
which compiled program each ran in ("jit_bcsr_spmm_pallas(<hash>)"). The
window's device time is reduced to:

  busy_s       the union of the operations' intervals inside the window,
               averaged over the chips that ran any;
  kernel_s     per kernel, the summed durations of the operations whose
               "<module> <HLO text>" a pattern matches;
  device_ops   the operations that took most time, per chip, named
               "<module>/<instruction>" without XLA's numeric suffixes;
               an operation that holds others (a while loop) is left out;
  idle_gaps    the stretches with no operation on the device, summed by
               what the host was doing: the innermost host event covering
               the gap's middle on the thread that opened the window, with
               the innermost harness span around it.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

import numpy as np

WINDOW = "bench.traced"
SPAN_PREFIX = "bench."
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_MODULE = re.compile(r"\(\d+\)$")
_INSTRUCTION = re.compile(r"^%?([\w-]+?)(?:\.\d+)*(?: =|$)")


def module_name(event_name: str) -> str:
    return _MODULE.sub("", event_name)


def instruction(event_name: str) -> str:
    """"%closed_call.10 = f32[...] custom-call(...)" -> "closed_call"."""
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else event_name.split(" ")[0]


def find_xplane(directory: str) -> str:
    found = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {directory}, "
                           f"found {found}")
    return found[0]


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def _events(line) -> List[tuple]:
    """(start_ns, end_ns, name) of a line's events."""
    return [(float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
            for e in line.events]


def union_ns(intervals: Iterable[Tuple[float, float]]) -> Tuple[float, list]:
    """Length of the union of intervals, and the merged intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _innermost(events: List[tuple], starts: np.ndarray, t: float,
               keep=lambda ev: True) -> Optional[tuple]:
    """The latest-starting event that covers t: in properly nested spans of
    one thread, the innermost."""
    i = int(np.searchsorted(starts, t, side="right")) - 1
    while i >= 0:
        ev = events[i]
        if ev[1] >= t and keep(ev):
            return ev
        i -= 1
    return None


def reduce(pd, kernels: Dict[str, str], top: int = 10,
           window_span: str = WINDOW) -> Optional[dict]:
    """Numbers of the traced window; None where the trace has no window or
    no device operation in it."""
    host_lines = [ln for p in pd.planes if p.name.startswith("/host:")
                  for ln in p.lines]
    window, main = None, None
    for ln in host_lines:
        for ev in _events(ln):
            if ev[2] == window_span:
                window, main = ev, ln
    if window is None:
        return None
    w0, w1 = window[0], window[1]
    # Outer spans first where two start together.
    host = sorted(_events(main), key=lambda ev: (ev[0], -ev[1]))
    host_starts = np.array([ev[0] for ev in host])

    per_device, kernel_ns, op_ns, gap_ns = [], {}, {}, {}
    patterns = {k: re.compile(p) for k, p in kernels.items()}
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {ln.name: _events(ln) for ln in plane.lines}
        modules = sorted(lines.get(MODULES_LINE, []))
        mod_starts = np.array([m[0] for m in modules])
        # Clipped to the window; an operation before those it holds.
        ops = sorted(((max(s, w0), min(e, w1), name)
                      for s, e, name in lines.get(OPS_LINE, [])
                      if e > w0 and s < w1), key=lambda op: (op[0], -op[1]))
        if not ops:
            continue
        busy, merged = union_ns((s, e) for s, e, _ in ops)
        per_device.append(busy)
        for i, (s, e, name) in enumerate(ops):
            mod = _innermost(modules, mod_starts, s)
            where = module_name(mod[2]) if mod is not None else "?"
            for k, pat in patterns.items():
                if pat.search(f"{where} {name}"):
                    kernel_ns[k] = kernel_ns.get(k, 0.0) + (e - s)
            holds_others = i + 1 < len(ops) and ops[i + 1][0] < e
            if not holds_others:
                key = f"{where}/{instruction(name)}"
                op_ns[key] = op_ns.get(key, 0.0) + (e - s)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[0::2], edges[1::2]):
            if g1 <= g0:
                continue
            mid = 0.5 * (g0 + g1)
            inner = _innermost(host, host_starts, mid)
            span = _innermost(host, host_starts, mid,
                              keep=lambda ev: ev[2].startswith(SPAN_PREFIX))
            name = inner[2] if inner is not None else "(no host event)"
            if span is not None and span is not inner:
                name = f"{name} in {span[2]}"
            gap_ns[name] = gap_ns.get(name, 0.0) + (g1 - g0)
    if not per_device:
        return None
    chips = len(per_device)
    ranked = lambda d: [[k, v * 1e-9 / chips] for k, v in
                        sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": float(np.mean(per_device)) * 1e-9,
        "chips": chips,
        "kernel_s": {k: kernel_ns.get(k, 0.0) * 1e-9 / chips
                     for k in kernels},
        "device_ops": ranked(op_ns),
        "idle_gaps": ranked(gap_ns),
    }
