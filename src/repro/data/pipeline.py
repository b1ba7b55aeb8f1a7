"""Batches of bag records for jitted user logic.

Fixed-layout and columnar batch builders (the BinPipedRDD frame stage,
shaped for :func:`repro.kernels.sensor_decode.sensor_decode`), message
batching for the batched replay path, and a background-thread prefetch
iterator.
"""

from __future__ import annotations

import queue
import threading
import time
from typing import Iterator, Optional, Sequence

import numpy as np

from repro.core.bag import Message


def assemble_message_batch(messages: Sequence[Message], align: int = 128,
                           scale: float = 1.0 / 255.0,
                           zero_point: float = 0.0) -> dict[str, np.ndarray]:
    """Fixed-layout batch assembly for jitted user logic (the BinPipedRDD
    frame stage, shaped for :func:`repro.kernels.sensor_decode.sensor_decode`).

    Packs a replay micro-batch (see ``RosPlay.run_batched``) into one
    record-per-row matrix: ``payload`` (R, Nb) uint8 with Nb = max payload
    length rounded up to ``align`` (128 = TPU lane width), plus per-record
    ``lengths`` i32, ``timestamps`` i64, and dequantization ``scale`` /
    ``zero_point`` f32 vectors.  One numpy copy per record; everything a
    TPU step needs, nothing variable-length.
    """
    if not messages:
        raise ValueError("empty message batch")
    lengths = np.fromiter((len(m.data) for m in messages),
                          dtype=np.int32, count=len(messages))
    nb = max(int(lengths.max()), 1)
    nb = (nb + align - 1) // align * align
    payload = np.zeros((len(messages), nb), dtype=np.uint8)
    for i, m in enumerate(messages):
        payload[i, :lengths[i]] = np.frombuffer(m.data, dtype=np.uint8)
    return {
        "payload": payload,
        "lengths": lengths,
        "timestamps": np.fromiter((m.timestamp for m in messages),
                                  dtype=np.int64, count=len(messages)),
        "scale": np.full(len(messages), scale, dtype=np.float32),
        "zero_point": np.full(len(messages), zero_point, dtype=np.float32),
    }


def payload_matrix(blob, lengths, align: int = 128) -> np.ndarray:
    """Record-per-row (R, Nb) uint8 matrix from a concatenated payload blob.

    The vectorized twin of :func:`assemble_message_batch`'s per-message copy
    loop: ``blob`` is the concatenation of R payloads whose byte counts are
    ``lengths`` — exactly the payload column of a wire DATA body or a
    ``binpipe`` partition.  Layout parameters (Nb = max length rounded up to
    ``align``, zero padding) are identical to ``assemble_message_batch``, so
    the two construction paths are bit-interchangeable for the decode
    kernels and the digest algebra.

    When every record is already Nb bytes (uniform, align-multiple payloads
    — the steady state of sensor streams), this is a pure ``reshape`` view
    of the blob: zero copies between the wire frame and the device feed.
    Ragged batches fall back to one vectorized scatter (no Python loop).
    """
    lengths = np.asarray(lengths)
    R = int(lengths.shape[0])
    if R == 0:
        raise ValueError("empty message batch")
    if isinstance(blob, (bytes, bytearray, memoryview)):
        blob = np.frombuffer(blob, dtype=np.uint8)
    else:
        blob = np.asarray(blob, dtype=np.uint8)
    nb = max(int(lengths.max()), 1)
    nb = (nb + align - 1) // align * align
    if int(lengths.min()) == nb:        # uniform aligned records
        return blob.reshape(R, nb)
    out = np.zeros((R, nb), dtype=np.uint8)
    l64 = lengths.astype(np.int64)
    ends = np.cumsum(l64)
    starts = ends - l64
    rows = np.repeat(np.arange(R, dtype=np.int64), l64)
    cols = np.arange(int(ends[-1]), dtype=np.int64) - np.repeat(starts, l64)
    out.reshape(-1)[rows * nb + cols] = blob
    return out


def payload_blob(payload: np.ndarray, lengths) -> np.ndarray:
    """Inverse of :func:`payload_matrix`: the concatenated valid bytes of
    each row as one flat uint8 array (a reshape view when rows are full)."""
    payload = np.ascontiguousarray(payload, dtype=np.uint8)
    R, nb = payload.shape
    l64 = np.asarray(lengths).astype(np.int64)
    if R and int(l64.min()) == nb:
        return payload.reshape(-1)
    ends = np.cumsum(l64)
    starts = ends - l64
    rows = np.repeat(np.arange(R, dtype=np.int64), l64)
    total = int(ends[-1]) if R else 0
    cols = np.arange(total, dtype=np.int64) - np.repeat(starts, l64)
    return payload.reshape(-1)[rows * nb + cols]


def batch_from_columns(topics: Sequence[str], topic_idx, timestamps,
                       lengths, blob, *, align: int = 128,
                       scale: float = 1.0 / 255.0,
                       zero_point: float = 0.0) -> dict:
    """Build the ``assemble_message_batch`` dict straight from columnar
    arrays — the zero-copy seam between the wire codec and the device path.

    Returns the usual five batch keys (bit-identical layout to
    ``assemble_message_batch`` of the equivalent ``Message`` list) plus the
    routing columns a batch-level consumer needs in place of per-message
    ``Message.topic``: ``topics`` (tuple of names) and ``topic_idx`` (R,)
    uint32 into it.  Kernels read the five core keys and ignore the extras.
    """
    lengths_i32 = np.asarray(lengths).astype(np.int32)
    return {
        "payload": payload_matrix(blob, lengths_i32, align),
        "lengths": lengths_i32,
        "timestamps": np.asarray(timestamps, dtype=np.int64),
        "scale": np.full(len(lengths_i32), scale, dtype=np.float32),
        "zero_point": np.full(len(lengths_i32), zero_point,
                              dtype=np.float32),
        "topics": tuple(topics),
        "topic_idx": np.asarray(topic_idx).astype(np.uint32),
    }


def iter_message_batches(messages: "Iterator[Message] | Sequence[Message]",
                         batch_size: int,
                         prefetch: int = 0) -> Iterator[list[Message]]:
    """Slice a message stream into non-empty lists of up to ``batch_size``
    messages — the framing step between a replayed/merged bag and
    :func:`assemble_message_batch` (used by both batched user logic and the
    aggregation layer's jitted metric reductions).

    ``prefetch > 0`` runs the framing loop — and therefore the upstream
    bag read (chunk decode + time-order merge) — on a background reader
    thread that keeps up to ``prefetch`` batches buffered ahead of the
    consumer (``prefetch=2`` is classic double buffering).  This is the
    read stage of the staged replay pipeline: disk I/O overlaps whatever
    consumes the batches downstream.
    """
    if batch_size < 1:
        raise ValueError("batch_size must be >= 1")

    def frames() -> Iterator[list[Message]]:
        batch: list[Message] = []
        for msg in messages:
            batch.append(msg)
            if len(batch) >= batch_size:
                yield batch
                batch = []
        if batch:
            yield batch

    if prefetch > 0:
        return iter(PrefetchIterator(frames(), depth=prefetch))
    return frames()


class PrefetchIterator:
    """Background-thread prefetch (overlaps host data prep with device
    compute — the single-host analogue of the platform's worker pipelining).

    ``close()`` stops the reader thread even mid-stream: a consumer that
    abandons the iterator early (subscriber error, timeout) must not leave
    the reader blocked forever on the bounded queue, pinning whatever the
    source iterator holds open (a bag, its memory image).  Consumers that
    may bail early should ``close()`` in a ``finally``.
    """

    def __init__(self, it: Iterator, depth: int = 2):
        self._q: "queue.Queue" = queue.Queue(maxsize=depth)
        self._done = object()
        self._err: Optional[BaseException] = None
        self._stop = threading.Event()

        def worker():
            try:
                for item in it:
                    while not self._stop.is_set():
                        try:
                            self._q.put(item, timeout=0.05)
                            break
                        except queue.Full:
                            continue
                    if self._stop.is_set():
                        return
            except BaseException as e:   # noqa: BLE001
                self._err = e
            finally:
                # blocking stop-aware put: the done sentinel must reach a
                # live consumer even through a full queue, but must not
                # wedge the thread when the consumer closed us instead
                while not self._stop.is_set():
                    try:
                        self._q.put(self._done, timeout=0.05)
                        break
                    except queue.Full:
                        continue

        self._thread = threading.Thread(target=worker, daemon=True)
        self._thread.start()

    def __iter__(self):
        return self

    def __next__(self):
        # Never a bare blocking get: after close() — or a drain race that
        # consumed the done sentinel — nothing will ever arrive, and a
        # consumer parked in q.get() would hang forever.  Poll with a short
        # timeout and re-check the liveness facts each round; the timeout
        # only matters on an empty queue (a ready item wakes us
        # immediately).
        while True:
            if self._stop.is_set():
                raise StopIteration
            try:
                item = self._q.get(timeout=0.05)
            except queue.Empty:
                if not self._thread.is_alive():
                    # worker gone and its sentinel already consumed:
                    # surface the error once, then end the stream
                    err, self._err = self._err, None
                    if err is not None:
                        raise err
                    raise StopIteration
                continue
            if item is self._done:
                err, self._err = self._err, None
                if err is not None:
                    raise err
                raise StopIteration
            return item

    def close(self) -> None:
        """Stop the reader thread, join it, and release buffered items.

        Safe in every worker state — mid-stream, finished, or dead from a
        source-iterator exception: the drain below keeps unblocking any
        stop-aware put until the thread exits, so close() cannot wedge
        against a full queue.  Only a source iterator stuck in native code
        can outlive the join deadline; the worker is a daemon thread, so
        even that cannot pin interpreter shutdown.
        """
        self._stop.set()
        deadline = time.monotonic() + 5.0
        while self._thread.is_alive() and time.monotonic() < deadline:
            try:                         # unblock a full-queue put promptly
                self._q.get_nowait()
            except queue.Empty:
                pass
            self._thread.join(timeout=0.05)
        while True:                      # drop whatever remained buffered
            try:
                self._q.get_nowait()
            except queue.Empty:
                break
