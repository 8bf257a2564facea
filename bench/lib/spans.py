"""The device's idle time of a traced window, split by the program's spans.

The program opens `aires.*` spans (`src/repro/trace.py`) on the thread
that runs it, which is the thread that opened the harness's window
(`trace.WINDOW`). `program_spans` takes that thread's `aires.*` events,
clipped to the window, and cuts the window into stretches, each labelled
with the innermost span open in it (or none). The device's idle stretches
are worked out the way `trace.reduce` does (the window less the union of
the operations of each chip that ran any) and intersected with those
stretches exactly. Per span name it returns:

  idle_s   idle seconds in which the span was the innermost `aires.*` span,
           averaged over the chips;
  total_s  its time inside the window, summed over its occurrences;
  self_s   that time less the time of the spans inside it;

and `unattributed_idle_s`, the idle seconds under no `aires.*` span. Their
idle seconds sum to the trace's `window_s - busy_s`.
"""
from __future__ import annotations

from typing import Dict, Iterable, List, Optional, Tuple

from bench.lib import trace as trace_lib

PREFIX = "aires."
CACHE_PREFIX = "aires.cache."


def _window(pd, window: str):
    """The window event and the events of the host line that opened it."""
    found = None
    for p in pd.planes:
        if not p.name.startswith("/host:"):
            continue
        for ln in p.lines:
            events = trace_lib._events(ln)
            for ev in events:
                if ev[2] == window:
                    found = (ev, events)
    return found


def innermost_stretches(spans: Iterable[tuple], w0: float,
                        w1: float) -> List[Tuple[float, float, Optional[str]]]:
    """Cut [w0, w1] into (start, end, name) stretches labelled with the
    latest-started span open there (in spans that nest, the innermost), or
    None where no span is open."""
    out: List[Tuple[float, float, Optional[str]]] = []
    stack: list = []
    t = w0

    def advance(to: float) -> None:
        nonlocal t
        while t < to:
            while stack and stack[-1][1] <= t:
                stack.pop()
            nxt = min(to, stack[-1][1]) if stack else to
            out.append((t, nxt, stack[-1][2] if stack else None))
            t = nxt

    for s, e, name in sorted(spans, key=lambda ev: (ev[0], -ev[1])):
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        advance(s)
        stack.append((s, e, name))
    advance(w1)
    return out


def _idle_gaps(ops: List[tuple], w0: float, w1: float) -> List[tuple]:
    """The window less the union of the operations' intervals."""
    _, merged = trace_lib.union_ns(
        (max(s, w0), min(e, w1)) for s, e, _ in ops)
    edges = [w0] + [x for iv in merged for x in iv] + [w1]
    return [(g0, g1) for g0, g1 in zip(edges[0::2], edges[1::2]) if g1 > g0]


def _intersect(gaps: List[tuple], stretches: List[tuple],
               into: Dict[Optional[str], float]) -> None:
    """Adds each gap's overlap with each stretch to `into[label]`; both
    lists are sorted and each is disjoint."""
    j = 0
    for g0, g1 in gaps:
        while j < len(stretches) and stretches[j][1] <= g0:
            j += 1
        k = j
        while k < len(stretches) and stretches[k][0] < g1:
            s0, s1, name = stretches[k]
            overlap = min(g1, s1) - max(g0, s0)
            if overlap > 0:
                into[name] = into.get(name, 0.0) + overlap
            k += 1


def program_spans(pd, window: str = trace_lib.WINDOW) -> Optional[dict]:
    """The split of the window's idle time by `aires.*` span; None where
    the trace has no window or no device operation in it."""
    found = _window(pd, window)
    if found is None:
        return None
    (w0, w1, _), host = found
    spans = [ev for ev in host if ev[2].startswith(PREFIX)]
    stretches = innermost_stretches(spans, w0, w1)

    idle_ns: Dict[Optional[str], float] = {}
    chips = 0
    for plane in pd.planes:
        if not trace_lib.DEVICE_PLANE.match(plane.name):
            continue
        ops_line = {ln.name: ln for ln in plane.lines}.get(trace_lib.OPS_LINE)
        ops = [] if ops_line is None else [
            op for op in trace_lib._events(ops_line)
            if op[1] > w0 and op[0] < w1]
        if not ops:
            continue
        chips += 1
        _intersect(_idle_gaps(ops, w0, w1), stretches, idle_ns)
    if not chips:
        return None

    total_ns: Dict[str, float] = {}
    for s, e, name in spans:
        s, e = max(s, w0), min(e, w1)
        if e > s:
            total_ns[name] = total_ns.get(name, 0.0) + (e - s)
    self_ns: Dict[str, float] = {}
    for s, e, name in stretches:
        if name is not None:
            self_ns[name] = self_ns.get(name, 0.0) + (e - s)
    return {
        "spans": {name: {"idle_s": idle_ns.get(name, 0.0) * 1e-9 / chips,
                         "total_s": total_ns[name] * 1e-9,
                         "self_s": self_ns.get(name, 0.0) * 1e-9}
                  for name in sorted(total_ns)},
        "unattributed_idle_s": idle_ns.get(None, 0.0) * 1e-9 / chips,
    }


def span_idle_share(record: dict, match) -> Optional[float]:
    """% of the traced part in which the device sat idle under a span whose
    name `match` accepts; None where the record holds no span split or no
    such span (a program that emits none)."""
    trace = record.get("trace") or {}
    program = trace.get("program")
    if not program or trace.get("window_s", 0) <= 0:
        return None
    idle = [v["idle_s"] for k, v in program["spans"].items() if match(k)]
    if not idle:
        return None
    return 100.0 * sum(idle) / trace["window_s"]
