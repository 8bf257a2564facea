"""The split of a traced window's idle time by the program's `aires.*` spans
(`bench/lib/spans.py`) and the readers of the metrics built on it.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q tests/bench_harness

On hand-made events, where every number can be worked out by hand; on the
trace recorded on a TPU v5e before the program had spans, whose reduction
by `trace.reduce` is held to what it gave then; and on a trace recorded on
a TPU v5e with them: two streamed passes (F = 128) of a 256-vertex graph
in two segments, through a segment cache whose device tier holds one of
the two bricks, inside `bench.traced` and `bench.epoch`.
"""
from __future__ import annotations

import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import registry  # noqa: E402
from bench.lib import spans, trace  # noqa: E402
from bench.lib.common import KERNELS  # noqa: E402

RECORDED = os.path.join(DATA, "v5e_two_passes.xplane.pb")
RECORDED_SPANS = os.path.join(DATA, "v5e_spans_two_passes.xplane.pb")


def _event(name, start, dur):
    return types.SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def _profile(host, *devices):
    line = lambda name, evs: types.SimpleNamespace(name=name, events=evs)
    return types.SimpleNamespace(planes=[
        types.SimpleNamespace(name="/host:CPU", lines=[
            line("other thread", [_event("aires.pass", 0, 20_000)]),
            line("main", host)])] + [
        types.SimpleNamespace(name=f"/device:TPU:{i}",
                              lines=[line("XLA Ops", ops),
                                     line("XLA Modules", [])])
        for i, ops in enumerate(devices)])


# Window [1000, 10000]. Spans: a step clipped by the window's start, and a
# pass holding a kernel dispatch (holding its sync) and a cache store
# (holding a demotion); a host event that is no span.
HOST = [_event("aires.train.step", 500, 700),
        _event("bench.traced", 1_000, 9_000),
        _event("aires.pass", 1_500, 7_000),
        _event("aires.kernel", 2_000, 1_000),
        _event("aires.kernel.sync", 2_200, 500),
        _event("np.asarray(jax.Array)", 2_200, 500),
        _event("aires.cache.store", 5_000, 2_000),
        _event("aires.cache.demote", 5_500, 1_000)]
# Busy [1000,2400], [2600,5800], [6000,6200], [9500,10000]; idle [2400,2600]
# (sync), [5800,6000] (demote), [6200,9000] (demote 300, store 500, pass
# 1500, none 500), [9500,10000] (none).
OPS = [_event("%fusion.1 = f32[8] fusion(...)", 1_000, 1_400),
       _event("%fusion.2 = f32[8] fusion(...)", 2_600, 3_200),
       _event("%copy.1 = f32[8] copy(...)", 6_000, 200),
       _event("%copy.2 = f32[8] copy(...)", 9_000, 500)]
IDLE_NS = {"aires.kernel.sync": 200, "aires.cache.demote": 500,
           "aires.cache.store": 500, "aires.pass": 1_500,
           "aires.kernel": 0, "aires.train.step": 0}
TOTAL_NS = {"aires.train.step": 200, "aires.pass": 7_000,
            "aires.kernel": 1_000, "aires.kernel.sync": 500,
            "aires.cache.store": 2_000, "aires.cache.demote": 1_000}
SELF_NS = {"aires.train.step": 200, "aires.pass": 4_000,
           "aires.kernel": 500, "aires.kernel.sync": 500,
           "aires.cache.store": 1_000, "aires.cache.demote": 1_000}


@pytest.mark.parametrize("chips", [1, 2])
def test_idle_split_by_hand(chips):
    """A second chip busy all through halves every idle reading."""
    pd = _profile(HOST, OPS, *[[_event("%f = f32[8] fusion(...)", 0,
                                       20_000)]] * (chips - 1))
    got = spans.program_spans(pd)
    assert set(got["spans"]) == set(TOTAL_NS)
    for name, v in got["spans"].items():
        assert v["idle_s"] == pytest.approx(IDLE_NS[name] * 1e-9 / chips)
        assert v["total_s"] == pytest.approx(TOTAL_NS[name] * 1e-9)
        assert v["self_s"] == pytest.approx(SELF_NS[name] * 1e-9)
    assert got["unattributed_idle_s"] == pytest.approx(1e-6 / chips)
    reduced = trace.reduce(pd, {})
    idle = sum(v["idle_s"] for v in got["spans"].values())
    assert idle + got["unattributed_idle_s"] == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], abs=1e-15)


def test_stretches_label_the_innermost_span():
    got = spans.innermost_stretches(
        [(0, 10, "a"), (2, 4, "b"), (2, 3, "c"), (6, 12, "d")], 1, 11)
    assert got == [(1, 2, "a"), (2, 3, "c"), (3, 4, "b"), (4, 6, "a"),
                   (6, 11, "d")]
    assert spans.innermost_stretches([], 0, 5) == [(0, 5, None)]


def test_idle_split_without_window_or_device_is_empty():
    assert spans.program_spans(_profile([_event("aires.pass", 0, 9)],
                                        OPS)) is None
    assert spans.program_spans(_profile(HOST)) is None


def test_recorded_trace_without_spans_is_all_unattributed():
    """The recorded trace predates the spans: all its idle time is under
    none, and it sums to what `idle_share` reads, to 1e-6 s."""
    pd = trace.load(RECORDED)
    got = spans.program_spans(pd)
    reduced = trace.reduce(pd, KERNELS)
    assert got["spans"] == {}
    assert got["unattributed_idle_s"] == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], abs=1e-6)


def test_recorded_trace_with_spans_against_a_grid():
    """Each span's idle seconds agree with a brute-force count over a 50 ns
    grid; they and the unattributed rest sum to `window_s - busy_s`; the
    program emits only listed names, and they cover most of the idle."""
    from repro.trace import SPANS

    pd = trace.load(RECORDED_SPANS)
    got = spans.program_spans(pd)
    reduced = trace.reduce(pd, KERNELS)
    idle = sum(v["idle_s"] for v in got["spans"].values())
    assert idle + got["unattributed_idle_s"] == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], abs=1e-9)
    assert set(got["spans"]) <= set(SPANS)
    assert {"aires.pass", "aires.kernel.sync", "aires.cache.demote",
            "aires.cache.promote"} <= set(got["spans"])
    assert idle > 0.9 * (idle + got["unattributed_idle_s"])

    (w0, w1, _), host = spans._window(pd, trace.WINDOW)
    device, = [p for p in pd.planes if trace.DEVICE_PLANE.match(p.name)]
    ops, = [trace._events(ln) for ln in device.lines
            if ln.name == trace.OPS_LINE]
    grid = np.arange(w0, w1, 50.0)
    busy = np.zeros(grid.shape, bool)
    for s, e, _ in ops:
        busy |= (grid >= s) & (grid < e)
    names = sorted({n for _, _, n in host if n.startswith("aires.")})
    label = np.full(grid.shape, -1)
    # Later starts overwrite earlier ones: in nested spans, the innermost.
    for s, e, n in sorted((ev for ev in host if ev[2] in names),
                          key=lambda ev: (ev[0], -ev[1])):
        label[(grid >= s) & (grid < e)] = names.index(n)
    counted = np.bincount(label[~busy] + 1, minlength=len(names) + 1) * 50e-9
    assert got["unattributed_idle_s"] == pytest.approx(counted[0],
                                                      rel=2e-2, abs=1e-6)
    for i, n in enumerate(names):
        assert got["spans"][n]["idle_s"] == pytest.approx(
            counted[i + 1], rel=2e-2, abs=1e-6), n


def test_reduction_of_the_recorded_trace_is_unchanged():
    with open(os.path.join(DATA, "v5e_two_passes.reduce.json")) as f:
        want = json.load(f)
    got = json.loads(json.dumps(trace.reduce(trace.load(RECORDED), KERNELS)))
    assert got == want


@pytest.mark.parametrize("metric,want", [
    ("sync_idle_share.train", 100.0 * 200 / 9_000),
    ("sync_idle_share.serve", 100.0 * 200 / 9_000),
    ("cache_idle_share.serve", 100.0 * 1_000 / 9_000),
    ("demote_gb.serve", 3.0)])
def test_readers(metric, want):
    """Each reads its record, and finds nothing in one of a program that
    has no spans or counts no demotions."""
    pd = _profile(HOST, OPS)
    trace_ = dict(trace.reduce(pd, {}), program=spans.program_spans(pd))
    record = {"trace": trace_,
              "counters": {"units": 4, "demoted_bytes": 12e9}}
    read = registry.load_metric(ROOT, metric)
    assert read(record) == pytest.approx(want)
    bare = dict(trace.reduce(_profile(HOST[1:2], OPS), {}),
                program=spans.program_spans(_profile(HOST[1:2], OPS)))
    for old in ({}, {"trace": trace.reduce(pd, {}), "counters": {"units": 4}},
                {"trace": bare, "counters": {"units": 4}}):
        assert read(old) is None
