"""Double-buffered host→device streamer (Phase II of Alg. 2).

JAX's async dispatch is the TPU-native version of CUDA stream overlap: while
the device executes the segment-k kernel, `jax.device_put` of segment k+1
proceeds concurrently. `DoubleBufferedStreamer` provides prefetch-ahead
iteration, straggler re-issue, and per-segment accounting; it is shared by
the AIRES SpGEMM scheduler and the out-of-core weight provider (MoE experts,
embeddings).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Iterable, Iterator, List, Optional

import jax

from repro.trace import span


@dataclasses.dataclass
class StreamStats:
    segments: int = 0
    reissues: int = 0              # straggler mitigations
    uploaded_bytes: int = 0        # wire bytes (when payload_nbytes is given)
    cache_hits: int = 0            # segments served from the segment cache
    cache_hit_bytes: int = 0       # wire bytes served from the cache
    promoted_bytes: int = 0        # of those, host-tier promotions that DID
    #                                re-cross the bus (true bus traffic is
    #                                uploaded_bytes + promoted_bytes)
    ici_bytes: int = 0             # sharded cache: bytes that crossed the
    #                                inter-chip path (remote-shard hits and
    #                                shard placements) during this stream
    directory_hit_bytes: int = 0   # wire bytes served from a peer worker's
    #                                host copy via the CacheDirectory
    demoted_bytes: int = 0         # bytes the cache moved down from its
    #                                device tier (demotions) during this
    #                                stream
    demote_copied_bytes: int = 0   # of those, bytes really copied
    #                                device->host (values with no host form)


class DoubleBufferedStreamer:
    """Prefetch-ahead pipeline over host segments.

    produce(i) -> host payload (numpy arrays / pytrees)
    upload(payload) -> device payload (typically jax.device_put with sharding)
    consume(device_payload, i) -> result (device computation, async)

    depth=2 is classic double buffering (paper Phase II); larger depths
    pipeline deeper when segments are small. A deadline (seconds) per
    segment triggers re-issue of the upload — the straggler mitigation used
    in multi-host deployments where a slow host NIC stalls one pipeline.

    Optional cache hooks (the tiered segment cache, io/segment_cache.py):
    `cache_lookup(payload)` returning non-None short-circuits the upload —
    the segment is already device-resident, so its wire bytes land in
    `cache_hit_bytes` instead of `uploaded_bytes`; after a miss's upload,
    `cache_store(payload, device_payload)` retains it for the next epoch.
    """

    def __init__(
        self,
        upload: Callable[[Any], Any],
        consume: Callable[[Any, int], Any],
        depth: int = 2,
        deadline_s: Optional[float] = None,
        max_reissue: int = 1,
        payload_nbytes: Optional[Callable[[Any], int]] = None,
        cache_lookup: Optional[Callable[[Any], Optional[Any]]] = None,
        cache_store: Optional[Callable[[Any, Any], None]] = None,
    ):
        if depth < 1:
            raise ValueError("depth must be >= 1")
        self.upload = upload
        self.consume = consume
        self.depth = depth
        self.deadline_s = deadline_s
        self.max_reissue = max_reissue
        self.payload_nbytes = payload_nbytes
        self.cache_lookup = cache_lookup
        self.cache_store = cache_store
        self.stats = StreamStats()

    def _upload_with_deadline(self, payload: Any, segment: int) -> Any:
        nbytes = (int(self.payload_nbytes(payload))
                  if self.payload_nbytes is not None else 0)
        if self.cache_lookup is not None:
            with span("cache.probe", segment=segment):
                cached = self.cache_lookup(payload)
            if cached is not None:
                self.stats.cache_hits += 1
                self.stats.cache_hit_bytes += nbytes
                return cached
        self.stats.uploaded_bytes += nbytes
        t0 = time.perf_counter()
        with span("upload", segment=segment, bytes=nbytes):
            dev = self.upload(payload)
        if self.deadline_s is not None:
            for _ in range(self.max_reissue):
                if time.perf_counter() - t0 <= self.deadline_s:
                    break
                # Straggler: re-issue the transfer (idempotent device_put);
                # the retransmit is real wire traffic, so count it.
                self.stats.reissues += 1
                self.stats.uploaded_bytes += nbytes
                t0 = time.perf_counter()
                with span("upload", segment=segment, bytes=nbytes):
                    dev = self.upload(payload)
        if self.cache_store is not None:
            with span("cache.store", segment=segment):
                self.cache_store(payload, dev)
        return dev

    def run(self, payloads: Iterable[Any]) -> Iterator[Any]:
        """Yield consume() results in order, depth-deep pipelined."""
        it = enumerate(payloads)
        inflight: List[Any] = []
        # Prime the pipeline.
        for j, payload in it:
            inflight.append(self._upload_with_deadline(payload, j))
            if len(inflight) >= self.depth:
                break
        i = 0
        while inflight:
            dev = inflight.pop(0)
            result = self.consume(dev, i)
            self.stats.segments += 1
            # Refill the pipeline before blocking on the result.
            try:
                j, nxt = next(it)
                inflight.append(self._upload_with_deadline(nxt, j))
            except StopIteration:
                pass
            yield result
            i += 1

    def run_all(self, payloads: Iterable[Any]) -> List[Any]:
        out = list(self.run(payloads))
        # Block once at the end (paper Phase III store) rather than per segment.
        with span("pass.wait"):
            jax.block_until_ready([o for o in out if o is not None])
        return out
