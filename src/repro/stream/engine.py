"""MapReduce-style block executor with double-buffered host->device transfer.

`map_reduce(store, map_fn, combine_fn, init)` is the generic program shape of
the whole paper: an embarrassingly-parallel map over row blocks and a small
associative combine. Embedding (Algorithm 1) and assignment (Algorithm 2's map
+ in-mapper combiner) are its two map_fns.

Pipelining: a background producer thread pulls block i+1 from the store (this
is where the real host cost lives — synthetic generation, memmap page-in) and
`jax.device_put`s it while the device is busy with block i. jax dispatch is
async, so the main thread only blocks when the bounded prefetch queue is empty
— i.e. when the producer, not the device, is the bottleneck. `prefetch=0`
degrades to the fully synchronous one-block-at-a-time baseline (get, transfer,
compute, block_until_ready), which `benchmarks/stream_bench.py` uses as the
overlap reference.

Deferred emit: `emit` never waits on the device. After block i's map is
dispatched the engine starts an asynchronous device-to-host copy of the part
of its output that `emit` reads (`emit_pick`, e.g. a Lloyd pass's labels),
and hands that host copy to `emit(i, ...)` only after block i+L has been
dispatched, L being the prefetch depth. By then the copy has landed, so the
consumer thread keeps queuing blocks on the device instead of waiting out a
round trip per block. At most L outputs are pending per consumer, which keeps
device memory O(block) and bounds how far the host runs ahead of the device.

A block is any pytree of host arrays: a dense block, the wire form of a
compressed cache (`EncodedBlock`) or sparse rows (`CSRBlock`). The producer's
`device_put` moves it leaf by leaf, and the deferred host copy reads only the
leaves `emit` picks from the map's output.

Device placement: `device=` commits every produced block to one specific
device instead of the default. This is the per-device-queue building block of
the sharded executor (`repro.stream.sharded`): each device of a mesh gets its
own `BlockPrefetcher` over its round-robin block shard, so D producers feed D
devices concurrently — D mappers pulling their own HDFS blocks.
"""
from __future__ import annotations

import collections
import queue
import threading
import time
from typing import Any, Callable

import jax

from repro import obs
from repro.stream.blockstore import BlockStore, WritableBlockStore

_STOP = object()


def fetch_block(store: BlockStore, i: int):
    """The engine's one block-read seam: the codec wire form (EncodedBlock:
    quantized payload + scale — the cheap H2D copy, dequantized on device by
    the Lloyd plan) when the store stages a compressed codec, else the plain
    decoded block. Every executor (producer thread, synchronous path, pool
    workers) reads through here so compressed caches stream compressed
    everywhere."""
    if store.codec != "f32":
        enc = store.get_encoded(i)
        if enc is not None:
            return enc
    return store.get(i)


def block_nbytes(blk) -> int:
    """Host->device bytes of one produced block: the sum over its pytree
    leaves (an array, the wire form of an EncodedBlock, the three arrays of
    a CSRBlock)."""
    return sum(getattr(leaf, "nbytes", 0) for leaf in jax.tree.leaves(blk))

# Labeled engine-pass telemetry lives in the obs metrics registry under
# "engine.passes.<label>"; reset_pass_counts() scopes a measurement.


def _count_pass(label: str) -> None:
    obs.counter(f"engine.passes.{label}").inc()


def reset_pass_counts() -> None:
    """Zero the engine-pass telemetry (test / measurement scoping)."""
    obs.reset_metrics("engine.passes.")


def pass_count(label: str) -> int:
    """Engine passes recorded under `label` since the last reset."""
    return int(obs.counter(f"engine.passes.{label}").value)


def _offer(q: "queue.Queue", item, stop: threading.Event) -> bool:
    """Bounded-queue put that aborts when `stop` is set. Every producer-side
    put MUST go through this: an unconditional `q.put` on a full maxsize-1
    queue after `close()` has drained once would block forever and deadlock
    the `join()` in `close()` (the poison-pill/_STOP put at end-of-stream was
    exactly that bug)."""
    while not stop.is_set():
        try:
            q.put(item, timeout=0.05)
            return True
        except queue.Full:
            continue
    return False


def _producer(store: BlockStore, q: "queue.Queue", stop: threading.Event,
              device, lane: str):
    # One metrics lane per producer thread: the per-device block counter is
    # what a sharded FitReport reports as per_device_blocks, and the span lane
    # is what renders as this producer's Perfetto row.
    obs.set_lane(lane)
    blocks = obs.counter("engine.blocks_read")
    dev_blocks = obs.counter(f"engine.device_blocks.{lane.split(':', 1)[-1]}")
    nbytes = obs.counter("engine.bytes_h2d")
    try:
        for i in range(store.num_blocks):
            if stop.is_set():
                return
            with obs.span("block.get", cat="ingest", block=i):
                blk = fetch_block(store, i)  # host cost: generation / disk read
            with obs.span("h2d", cat="ingest", block=i):
                dev = jax.device_put(blk, device)  # starts the H2D copy
            blocks.inc()
            dev_blocks.inc()
            nbytes.inc(block_nbytes(blk))
            if not _offer(q, (i, dev, None), stop):
                return
        _offer(q, _STOP, stop)
    except BaseException as e:  # noqa: BLE001 - re-raised on the consumer side
        _offer(q, (None, None, e), stop)


class BlockPrefetcher:
    """Iterator of (local_i, device_block) over a store, in block order, with
    a background producer keeping a bounded queue of already-device_put blocks
    ahead of the consumer.

    `device=` commits blocks to that device (None = default device). Always
    `close()` (or exhaust) the iterator — a dropped prefetcher would leave its
    producer thread blocked on the queue.
    """

    def __init__(self, store: BlockStore, *, prefetch: int = 2, device=None):
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, prefetch))
        self._stop = threading.Event()
        self._done = False
        self.lane = f"producer:{device if device is not None else 'default'}"
        self._stall = obs.counter("engine.prefetch_stall_s")
        self._t = threading.Thread(
            target=_producer, name=f"block-{self.lane}",
            args=(store, self._q, self._stop, device, self.lane), daemon=True,
        )
        self._t.start()

    def __iter__(self):
        return self

    def __next__(self):
        if self._done:
            raise StopIteration
        # Time spent blocked on an empty queue is THE ingest-bound signal:
        # the producer (host generation / disk / H2D), not the device, is the
        # bottleneck. Accumulated always; a span only when tracing.
        with obs.span("stall.queue_empty", cat="stall", producer=self.lane):
            t0 = time.perf_counter()
            item = self._q.get()
            self._stall.inc(time.perf_counter() - t0)
        if item is _STOP:
            self._done = True
            raise StopIteration
        i, dev, err = item
        if err is not None:
            self._done = True
            raise err
        return i, dev

    def close(self):
        """Stop and join the producer; safe to call more than once.

        Drain and join interleave in a loop: a single drain is not enough,
        because a producer that was blocked mid-`put` can enqueue one more
        item after the drain (its in-flight block, then the _STOP pill) and
        refill a maxsize-1 queue before `join` is reached. The producer's
        `_offer` puts give up once the stop flag is set, so this converges.
        """
        self._stop.set()
        while self._t.is_alive():
            try:
                self._q.get_nowait()
            except queue.Empty:
                pass
            self._t.join(timeout=0.05)
        # final sweep so queued device blocks are released promptly
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
        self._done = True


def _start_host_copy(tree) -> None:
    """Start the device-to-host copy of every device array in `tree`; the
    later `jax.device_get` then reads the landed host value."""
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Array):
            leaf.copy_to_host_async()


def map_reduce(
    store: BlockStore,
    map_fn: Callable[[Any], Any],
    combine_fn: Callable[[Any, Any], Any],
    init: Any,
    *,
    prefetch: int = 2,
    emit: Callable[[int, Any], None] | None = None,
    emit_pick: Callable[[Any], Any] | None = None,
    device=None,
    label: str = "map_reduce",
) -> Any:
    """Fold `combine_fn(acc, map_fn(block))` over every block of `store`.

    map_fn runs on device (jit it for anything hot); combine_fn must be
    associative-enough that per-block accumulation matches the monolithic
    computation (sums, counts, min/max — the paper's (Z, g) case).

    emit(i, host), when given, receives `emit_pick(out)` of each block's map
    output as host (numpy) arrays — used to spill per-block results (labels,
    embeddings) back to a host store. `emit_pick` selects the leaves `emit`
    reads (default: the whole output); only those are copied to the host, the
    rest stays on the device for the combine. Every block is emitted exactly
    once, in block order, on the consumer thread, and all emits have run when
    map_reduce returns.

    Deferred emit (pipelined loop): after block i's map is dispatched, the
    copy of its picked leaves starts asynchronously, and `emit(i, ...)` runs
    only once block i+prefetch has been dispatched, or at the end of the pass,
    which drains what is pending in block order. So the consumer never waits
    on the device for a block it just dispatched; at most `prefetch` picked
    outputs are pending. Counters: `engine.emits_deferred` (emits handed over
    after their asynchronous copy) and `engine.emit_wait_s` (time the host
    fetch of a deferred emit still took, i.e. the copy had not landed).

    prefetch: depth of the producer queue and of the pending emits. 0 =
    synchronous baseline: every block is fetched, transferred, computed,
    emitted and *waited on* before the next block is touched.

    device: commit blocks (and therefore the map computation) to one specific
    device; None keeps the default-device behaviour.

    label: telemetry tag — each call bumps `engine.passes.<label>` by one full
    pass, and tags the pipelined loop's `block.consume` spans.

    In the pipelined loop each block is one `block.consume` span on the
    consumer's lane, with children `block.map` (the map dispatch),
    `block.combine`, and the `block.emit` (attr `block`: the emitted block,
    an earlier one; host fetch plus callback) of each deferred emit that runs
    in it; the last block's span holds the end-of-pass drain. The wait on the
    prefetch queue is outside it.
    """
    _count_pass(label)
    dispatches = obs.counter("engine.map_dispatches")
    pick = emit_pick if emit_pick is not None else (lambda out: out)
    if prefetch <= 0:
        blocks = obs.counter("engine.blocks_read")
        nbytes = obs.counter("engine.bytes_h2d")
        with obs.span(f"pass.{label}", cat="pass", blocks=store.num_blocks,
                      prefetch=prefetch):
            acc = init
            for i in range(store.num_blocks):
                blk = fetch_block(store, i)
                blocks.inc()
                nbytes.inc(block_nbytes(blk))
                dev = jax.device_put(blk, device)
                out = map_fn(dev)
                dispatches.inc()
                if emit is not None:
                    emit(i, jax.device_get(pick(out)))
                acc = combine_fn(acc, out)
                jax.block_until_ready(acc)
        return acc

    deferred = obs.counter("engine.emits_deferred")
    emit_wait = obs.counter("engine.emit_wait_s")
    pending: collections.deque = collections.deque()
    last = store.num_blocks - 1

    def emit_oldest() -> None:
        j, picked = pending.popleft()
        with obs.span("block.emit", cat="block", block=j):
            t0 = time.perf_counter()
            host = jax.device_get(picked)
            emit_wait.inc(time.perf_counter() - t0)
            deferred.inc()
            emit(j, host)

    with obs.span(f"pass.{label}", cat="pass", blocks=store.num_blocks,
                  prefetch=prefetch):
        pf = BlockPrefetcher(store, prefetch=prefetch, device=device)
        acc = init
        try:
            for i, dev in pf:
                with obs.span("block.consume", cat="block", block=i, label=label):
                    with obs.span("block.map", cat="block"):
                        out = map_fn(dev)
                    dispatches.inc()
                    if emit is not None:
                        picked = pick(out)
                        _start_host_copy(picked)
                        pending.append((i, picked))
                    with obs.span("block.combine", cat="block"):
                        acc = combine_fn(acc, out)
                    while pending and (len(pending) > prefetch or i == last):
                        emit_oldest()
        finally:
            pf.close()
    return acc


def cache_embedding(
    store: BlockStore,
    map_fn: Callable[[Any], Any],
    *,
    d_out: int,
    out: WritableBlockStore | None = None,
    codec: str = "f32",
    prefetch: int = 2,
    device=None,
    label: str = "cache_embedding",
) -> WritableBlockStore:
    """Materialize `map_fn` over every block of `store` into a staged host
    store, through the same double-buffered prefetcher as any other pass.

    This is the embed-ONCE pass of the sweep engine: X blocks stream in,
    Y = map_fn(X) blocks are written back to host RAM by GLOBAL block id (so a
    shard's local block i lands at its global offset and sharded writers can
    share one `out`). The returned store is a `WritableBlockStore`, whose
    unwritten-block guard turns any read of a block this pass never produced
    into an error instead of silent zeros.

    `out=` lets D sharded cache passes (one per device, disjoint round-robin
    block subsets) fill one shared staging area; by default a fresh store
    sized (store.n, d_out) is allocated, staged under `codec` ("f32" | "bf16"
    | "int8" — the policy's cache_dtype; DESIGN.md §17). Each put bumps the
    `cache.bytes_staged` counter by the block's WIRE size, and the pass sets
    the `cache.compression_ratio` gauge (f32 bytes / staged bytes).
    """
    if out is None:
        out = BlockStore.empty(
            n=store.n, d=d_out, block_rows=store.block_rows, codec=codec,
        )

    bytes_staged = obs.counter("cache.bytes_staged")
    sized = hasattr(out, "staged_nbytes")

    def emit(i, y):
        gid = store.block_id(i)
        out.put(gid, y)
        if sized:
            bytes_staged.inc(out.staged_nbytes(gid))

    map_reduce(
        store, map_fn, lambda acc, _: acc, None,
        prefetch=prefetch, emit=emit, device=device, label=label,
    )
    if sized:  # same value from every sharded writer: gauge, not a sum
        obs.gauge("cache.compression_ratio").set(
            (out.n * out.d * 4) / max(out.nbytes_staged, 1)
        )
    return out
