"""What the drivers share: the run's context, checks, seeds, tracing."""
from __future__ import annotations

import contextlib
import dataclasses
import math
import shutil
import tempfile
import time
from typing import Dict, List, Optional

import numpy as np

from bench.lib import trace as trace_lib
from bench.lib.compile_counter import CompileCounter

# Kernels whose device time the trace reduction sums: a pattern over
# "<compiled program> <HLO text>" of each device operation. The Block-ELL
# SpMM is the Mosaic custom call of `bcsr_spmm_pallas`'s program.
KERNELS = {"bcsr_spmm":
           r'^jit_bcsr_spmm_pallas .*custom_call_target="tpu_custom_call"'}


@dataclasses.dataclass
class Context:
    workload: str
    config: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float                      # process start, for setup_s
    compiles: CompileCounter
    interpret: Optional[bool] = None    # None: the program decides


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    end_to_end: Dict[str, float]
    record: dict
    checks: List[Check]
    memory_peak_bytes: Optional[int]


def prng_key(seed: int):
    """A JAX key from any non-negative seed below 2**64."""
    import jax

    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed & 0xFFFFFFFF)


def program_csr(a):
    """The program's CSR of the benchmark's Â (what the system is given)."""
    from repro.sparse.formats import CSR

    return CSR(indptr=a.indptr, indices=a.indices, data=a.data,
               shape=(a.n, a.n))


def device_edges(a):
    import jax.numpy as jnp

    return (jnp.asarray(a.rows(), dtype=jnp.int32),
            jnp.asarray(a.indices, dtype=jnp.int32), jnp.asarray(a.data))


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest chip, where the backend says."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def rel_max_gap(x, ref) -> float:
    """max |x - ref| / max |ref|."""
    x, ref = np.asarray(x, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(x - ref)) / np.max(np.abs(ref)))


def leaf_norm_gaps(got: dict, want: dict, keep=None) -> Dict[str, float]:
    """Per leaf, |‖got‖ - ‖want‖| over the larger of ‖want‖ and the median
    leaf's ‖want‖ (some gradients are all but zero)."""
    norm = lambda x: float(np.linalg.norm(np.asarray(x, np.float64)))
    want_n = {k: norm(v) for k, v in want.items()}
    median = float(np.median(list(want_n.values())))
    return {k: abs(norm(got[k]) - want_n[k]) / max(want_n[k], median)
            for k in want if keep is None or k in keep}


class Window:
    """Host-clock window, with an optional profiler trace of one part."""

    def __init__(self, ctx: Context):
        self.ctx = ctx
        self.t0 = self.t1 = None
        self.trace_dir = None

    def start(self) -> None:
        self.mark = self.ctx.compiles.mark()
        self.t0 = time.perf_counter()

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def stop(self) -> None:
        self.t1 = time.perf_counter()
        self.compiles = self.ctx.compiles.since(self.mark)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    @contextlib.contextmanager
    def traced(self, on: bool):
        """Trace the body where `on`: one part of a `--trace 1` run."""
        import jax

        if not on:
            yield
            return
        self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.trace_dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(trace_lib.WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()

    def reduce_trace(self) -> Optional[dict]:
        if self.trace_dir is None:
            return None
        try:
            pd = trace_lib.load(trace_lib.find_xplane(self.trace_dir))
            return trace_lib.reduce(pd, KERNELS)
        finally:
            shutil.rmtree(self.trace_dir, ignore_errors=True)


@contextlib.contextmanager
def span(name: str, into: Optional[dict] = None):
    """A harness span: a host-clock duration, and a profiler annotation."""
    import jax

    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(f"bench.{name}"):
        yield
    if into is not None:
        into[name] = into.get(name, 0.0) + time.perf_counter() - t0
