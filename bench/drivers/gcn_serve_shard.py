"""`gcn_serve` over a segment cache whose device tier spans several chips.

The engine is built over `make_cache_mesh(shards)`: the device tier,
`cache_device_bytes` over the whole mesh, is split into one LRU shard per
chip, each brick kept on its owner (CRC owners, `partition_shards` 0), and
a hit on another chip crosses ICI to the chip that computes. Everything
else, from the inputs and the closed loop to the reference and the checks,
is `gcn_serve`'s: this driver runs `gcn_serve.run` with its `Program`
and `Window` swapped for the sharded ones, and adds the ICI bytes to the
record's counters and the chip count to the traced work. After the
warm-up it logs the brick bytes each device holds.

The engine models ICI as all-to-all (`ICI_ALL_TO_ALL`: one hop from any
chip to any other), so `BatchReport.ici_bytes` counts the bytes of each
remote hit and each placement on another chip once. Only the chip that
computes runs the kernels; the others hold bricks and send them. So the
traced batch is reduced on that chip alone (`reduce_on_computing_chip`),
with the mesh's average beside it.
"""
from __future__ import annotations

import contextlib
import shutil
import sys
import types

from bench.drivers import gcn_serve
from bench.lib import trace as trace_lib
from bench.lib.common import KERNELS, Context, Outcome, Window, program_csr


def brick_bytes_by_device() -> dict:
    """Bytes of live 4-D arrays (Block-ELL bricks) on each device."""
    import jax

    held: dict = {}
    for arr in jax.live_arrays():
        if arr.ndim != 4:
            continue
        for shard in arr.addressable_shards:
            held[shard.device.id] = held.get(shard.device.id, 0) + (
                shard.data.nbytes)
    return held


def sharded_program(traffic: dict, counters: dict):
    """A `gcn_serve.Program` whose engine shards its cache over
    `traffic["shards"]` chips and which sums each batch's ICI bytes into
    `counters`."""

    class Program(gcn_serve.Program):
        def __init__(self, config: dict, a, interpret=None, spans=None):
            from repro.io.tiers import ICI_ALL_TO_ALL
            from repro.launch.mesh import make_cache_mesh
            from repro.runtime import EngineConfig, ServingEngine

            tile = config["tile"]
            self.engine = ServingEngine(EngineConfig(
                device_budget_bytes=int(config["device_budget_bytes"]),
                cache_device_bytes=int(traffic["cache_device_bytes"]),
                max_batch_features=config["feature_dim"], bm=tile["bm"],
                bk=tile["bk"], align=tile["align"], interpret=interpret,
                ici_topology=ICI_ALL_TO_ALL),
                mesh=make_cache_mesh(traffic["shards"]))
            self.engine.register_graph(gcn_serve.GRAPH, program_csr(a))
            self.spans = spans
            self.warm = False

        def serve(self, inputs, ids: list):
            outputs, report = super().serve(inputs, ids)
            if not self.warm:
                self.warm = True
                print(f"bench: brick bytes by device after the warm-up "
                      f"{brick_bytes_by_device()}", file=sys.stderr,
                      flush=True)
            else:
                counters["ici_bytes"] += report.ici_bytes
            return outputs, report

    return Program


def reduce_on_computing_chip(pd, kernels: dict, kernel: str):
    """`trace.reduce` of the traced part on the chip that ran `kernel`
    longest, as if it were the only chip, plus `mesh_busy_s` and
    `mesh_chips`: the busy time averaged over every chip that ran an
    operation, and their number. None where no chip ran an operation."""
    device = [p for p in pd.planes if trace_lib.DEVICE_PLANE.match(p.name)]
    rest = [p for p in pd.planes if not trace_lib.DEVICE_PLANE.match(p.name)]
    mesh = trace_lib.reduce(pd, kernels)
    if mesh is None:
        return None
    chips = [r for r in (trace_lib.reduce(
        types.SimpleNamespace(planes=rest + [plane]), kernels)
        for plane in device) if r is not None]
    best = max(chips, key=lambda r: r["kernel_s"][kernel])
    return dict(best, mesh_busy_s=mesh["busy_s"], mesh_chips=mesh["chips"])


class ShardWindow(Window):
    """The harness's window; its trace is reduced on the chip that runs
    the SpMM kernel."""

    def reduce_trace(self):
        if self.trace_dir is None:
            return None
        try:
            pd = trace_lib.load(trace_lib.find_xplane(self.trace_dir))
            return reduce_on_computing_chip(pd, KERNELS, "bcsr_spmm")
        finally:
            shutil.rmtree(self.trace_dir, ignore_errors=True)


@contextlib.contextmanager
def _swapped(module, name: str, value):
    old = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, old)


def run(ctx: Context) -> Outcome:
    counters = {"ici_bytes": 0}
    with _swapped(gcn_serve, "Program",
                  sharded_program(ctx.traffic, counters)), _swapped(
                      gcn_serve, "Window", ShardWindow):
        out = gcn_serve.run(ctx)
    out.record["counters"].update(counters)
    if out.record["traced"] is not None:
        out.record["traced"]["chips"] = ctx.traffic["shards"]
    return out
