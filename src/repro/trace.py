"""Named host spans at the streamed pass's layer boundaries.

`span(name, **attrs)` is `jax.profiler.TraceAnnotation("aires." + name)`.
Without an active profiler it records nothing and costs about a
microsecond. Under `jax.profiler.start_trace` it lands in the trace's host
plane, on the thread that opened it and on the clock of the device's
operations, so a reduction can say what the host was doing while the
device sat idle. `SPANS` lists every name the program emits; where two
nest, the second named is the inner:

  aires.pass              one streamed SpMM pass (`AiresSpGEMM._stream`)
  aires.pass.wait         its final block_until_ready (the streamer)
  aires.assemble          the row concatenation of the segments' outputs
  aires.upload            device_put of one segment's bricks
  aires.cache.probe       the segment cache lookup before an upload
  aires.cache.promote     a host-tier (or peer) brick put back on device
  aires.cache.store       the segment cache insert after an upload
  aires.cache.demote      a device brick moved down to the host tier, with
                          the bytes it copied device->host (`copied`)
  aires.kernel            host dispatch of one segment's Pallas kernel,
                          with its grid_steps and the bricks it walks
  aires.kernel.sync       its read of the brick's largest column tile
  aires.attn              host dispatch of one segment's GAT attention
                          kernel, with its grid_steps, bricks and heads
                          (its column tile read is aires.kernel.sync too)
  aires.engine.group      `ServingEngine.serve_group`
  aires.engine.inputs     the requests' features and weights to device
  aires.engine.project    one GAT request's projection and scores
  aires.engine.combine    one request's combination matmul and relu, or a
                          GAT layer's heads, skip and ELU
  aires.engine.readback   one request's output copied to the host
  aires.train.step        `make_gcn_train_step`'s step
  aires.train.update      its optimizer update
  aires.prep              planning and densifying one streamed direction
  aires.prep.robw         its RoBW partition
  aires.prep.densify      its Block-ELL densification
"""
from __future__ import annotations

import jax

PREFIX = "aires."

SPANS = tuple(PREFIX + n for n in (
    "pass", "pass.wait", "assemble", "upload",
    "cache.probe", "cache.promote", "cache.store", "cache.demote",
    "kernel", "kernel.sync", "attn",
    "engine.group", "engine.inputs", "engine.project", "engine.combine",
    "engine.readback",
    "train.step", "train.update",
    "prep", "prep.robw", "prep.densify",
))


def span(name: str, **attrs) -> jax.profiler.TraceAnnotation:
    """The profiler annotation `aires.<name>`, with `attrs` as its stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **attrs)
