"""Double-buffered host→device feed: overlap ingest with compute.

The reference overlaps nothing — each Spark task alternates between gRPC
reads and the accumulation loop. Here a background thread runs the (IO- and
Python-bound) block producer and stages blocks onto the device with
``jax.device_put`` while the previous block's matmul executes; the consumer
pops already-transferred arrays. Equivalent of the PP row in SURVEY.md
§2.10's strategy table (ingest on DCN/host overlapped with ICI compute).
"""

from __future__ import annotations

import queue
import threading
from typing import Iterable, Iterator

import jax
import numpy as np

from spark_examples_tpu import obs

__all__ = ["device_prefetch"]

_SENTINEL = object()


def device_prefetch(
    blocks: Iterable[np.ndarray],
    depth: int = 2,
    device=None,
    sharding=None,
) -> Iterator:
    """Yield device arrays for ``blocks``, staged ``depth`` ahead.

    The producer thread re-raises its exception in the consumer (ingest
    failures must surface, not hang — the retry story relies on them).
    ``sharding`` takes precedence over ``device`` for mesh layouts.
    """
    q: queue.Queue = queue.Queue(maxsize=depth)
    err: list = []
    stop = threading.Event()

    def _put(item) -> bool:
        # Bounded put that gives up when the consumer cancelled — a
        # blocked q.put with no reader would leak the thread, the staged
        # device blocks, and the open ingest source.
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def produce() -> None:
        put_bytes = obs.get_registry().counter(
            "feed_put_bytes_total",
            "Host bytes staged onto the device by the prefetch feed",
        )
        try:
            for block in blocks:
                if stop.is_set():
                    return
                target = sharding if sharding is not None else device
                arr = np.asarray(block)
                # The span ends when the transfer has landed, not when
                # device_put returns: this thread is off the dispatch
                # path, and the consumer would wait for the same copy.
                with obs.span("ingest.put", bytes=int(arr.nbytes)):
                    staged = (
                        jax.device_put(arr, target)
                        if target is not None
                        else jax.device_put(arr)
                    )
                    staged.block_until_ready()
                put_bytes.inc(arr.nbytes)
                if not _put(staged):
                    return
        except BaseException as e:  # noqa: BLE001 — re-raised in consumer
            err.append(e)
        finally:
            _put(_SENTINEL)

    t = threading.Thread(target=produce, daemon=True)
    t.start()
    try:
        while True:
            # The dispatching thread's wait for its next input; the last
            # one finds the end of the stream.
            with obs.span("feed.wait"):
                item = q.get()
            if item is _SENTINEL:
                if err:
                    raise err[0]
                return
            yield item
    finally:
        # Consumer abandoned the generator (close/GeneratorExit or an
        # exception in its loop body): release the producer.
        stop.set()
