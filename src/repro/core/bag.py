"""Bag format: the paper's two-tier logical structure (Fig 2).

Upper tier:  :class:`Bag` — user-facing record API (topic, timestamp, payload),
             grouping records into chunks with a time/topic index.
Lower tier:  :class:`ChunkedFile` — chunk store on disk;
             :class:`MemoryChunkedFile` — the paper's contribution (Fig 6):
             inherits ChunkedFile and overrides every I/O method to read and
             write chunks in RAM instead of the disk, so ROSPlay/ROSRecord
             stream through memory ("ROSBag cache", §3.2).

Binary layout (disk):
    [8s magic "REPROBAG"][u32 version]
    chunk*:  [u32 crc-less header: record_count][u64 payload_len][payload]
    footer:  written by Bag.close() via the index block (see Bag._write_index)

Chunk payload = concatenated records:
    [u32 topic_id][u64 timestamp_ns][u32 data_len][data]
"""

from __future__ import annotations

import hashlib
import heapq
import io
import mmap
import os
import struct
import threading
from dataclasses import dataclass, field
from typing import (Callable, Iterable, Iterator, NamedTuple, Optional,
                    Sequence, Union)

from repro.obs import metrics as obs_metrics
from repro.shm import SegmentHandle, read_segment

_MAGIC = b"REPROBAG"
_VERSION = 2
_HDR = struct.Struct("<IQ")          # record_count, payload_len
_REC = struct.Struct("<IQI")         # topic_id, timestamp_ns, data_len
DEFAULT_CHUNK_BYTES = 768 * 1024     # rosbag's default chunk threshold

#: how each bag-cache fill carried its chunks: ``raw_chunks`` copied as
#: bytes, ``decoded_chunks`` cut by the selection and re-encoded record by
#: record (both registered up front, so a suite's manifest shows the zero)
_FILL = obs_metrics.scope("bag_cache")
_RAW_CHUNKS = _FILL.counter("raw_chunks")
_DECODED_CHUNKS = _FILL.counter("decoded_chunks")

#: how :func:`iter_time_ordered` let each message out: ``watermark`` once
#: the index proved no unread chunk holds an earlier one, ``window`` by the
#: heap-window cap, ``drained`` after the selection's last chunk
_ORDER = obs_metrics.scope("replay_order")
_BY_WATERMARK = _ORDER.counter("watermark")
_BY_WINDOW = _ORDER.counter("window")
_DRAINED = _ORDER.counter("drained")


@dataclass(frozen=True)
class Message:
    topic: str
    timestamp: int           # nanoseconds
    data: bytes


@dataclass
class ChunkInfo:
    offset: int               # opaque handle given by the ChunkedFile tier
    record_count: int
    t_min: int
    t_max: int
    topics: set = field(default_factory=set)


class SelectionImage(NamedTuple):
    """A memory-bag image of a bag selection (:meth:`Bag.selection_image`)
    and how its chunks got there."""
    image: "bytes | mmap.mmap"
    raw_chunks: int          # carried as the source's own chunk bytes
    decoded_chunks: int      # cut by the selection: re-encoded per record


class ChunkedFile:
    """Lower tier: sequential chunk store backed by the disk.

    The Bag tier only ever calls :meth:`write_chunk`, :meth:`read_chunk`,
    :meth:`flush` and :meth:`close`, so a subclass that overrides those —
    like :class:`MemoryChunkedFile` — transparently changes the medium.
    """

    def __init__(self, path: Optional[str] = None, mode: str = "r"):
        self.path = path
        self.mode = mode
        self._lock = threading.Lock()
        if mode == "w":
            self._f: io.BufferedIOBase = open(path, "wb")
            self._f.write(_MAGIC + struct.pack("<I", _VERSION))
        elif mode == "r":
            self._f = open(path, "rb")
            magic = self._f.read(8)
            if magic != _MAGIC:
                raise ValueError(f"not a repro bag: {path!r}")
            (version,) = struct.unpack("<I", self._f.read(4))
            if version != _VERSION:
                raise ValueError(f"bag version {version} != {_VERSION}")
        else:
            raise ValueError(mode)

    # -- methods a subclass overrides to change the storage medium ---------

    def write_chunk(self, payload: bytes, record_count: int) -> int:
        """Append one chunk; returns its opaque offset handle."""
        with self._lock:
            off = self._f.tell()
            self._f.write(_HDR.pack(record_count, len(payload)))
            self._f.write(payload)
            return off

    def read_chunk(self, offset: int) -> tuple[bytes, int]:
        """Return (payload, record_count) for the chunk at ``offset``."""
        with self._lock:
            self._f.seek(offset)
            record_count, payload_len = _HDR.unpack(self._f.read(_HDR.size))
            return self._f.read(payload_len), record_count

    def read_into(self, offset: int, buf: memoryview) -> None:
        """Fill ``buf`` with the bytes at ``offset``: one ranged read,
        which runs without the GIL (the bag-cache fill's whole-chunk copy)."""
        with self._lock:
            self._f.seek(offset)
            got = self._f.readinto(buf)
        if got != len(buf):
            raise ValueError(f"short read at {offset}: {got} of "
                             f"{len(buf)} bytes")

    def write_blob(self, blob: bytes) -> int:
        """Raw append (used for the index block)."""
        with self._lock:
            off = self._f.tell()
            self._f.write(blob)
            return off

    def read_blob(self, offset: int, length: int) -> bytes:
        with self._lock:
            self._f.seek(offset)
            return self._f.read(length)

    def size(self) -> int:
        with self._lock:
            pos = self._f.tell()
            self._f.seek(0, os.SEEK_END)
            end = self._f.tell()
            self._f.seek(pos)
            return end

    def flush(self) -> None:
        self._f.flush()
        os.fsync(self._f.fileno())

    def close(self) -> None:
        try:
            self._f.close()
        except Exception:
            pass


class MemoryChunkedFile(ChunkedFile):
    """The paper's ROSBag cache (§3.2, Fig 6).

    Inherits from ChunkedFile and overrides *all* of its I/O methods; chunks
    live in process memory, so playback and recording never touch the disk.

    Write mode stores chunk payloads as *references* in a segment list
    (zero-copy appends; the disk-format image is only materialised by
    ``image()``/``persist()``); read mode wraps a single immutable buffer
    with a memoryview (zero upfront copy).  ``persist()``/``from_file()``
    move whole images between RAM and disk, which is how a worker
    materialises a partition it received over the wire.
    """

    def __init__(self, image: Optional[bytes] = None):
        # NOTE: deliberately does NOT call super().__init__ — no file handle.
        self.path = None
        self.mode = "rw"
        self._closed = False
        self._lock = threading.Lock()
        header = _MAGIC + struct.pack("<I", _VERSION)
        if image is not None:
            if bytes(image[:8]) != _MAGIC:
                raise ValueError("not a repro bag image")
            self._ro: Optional[memoryview] = memoryview(image)
            self._size = len(image)
            self._chunks: dict[int, tuple[int, bytes]] = {}
            self._segs: list[bytes] = []
        else:
            self._ro = None
            self._size = len(header)
            self._chunks = {}
            self._segs = [header]

    def write_chunk(self, payload: bytes, record_count: int) -> int:
        with self._lock:
            if self._closed:
                raise RuntimeError("memory bag is closed")
            off = self._size
            self._chunks[off] = (record_count, payload)   # reference, no copy
            self._segs.append(None)                       # placeholder
            self._segs[-1] = (off, record_count, payload)  # type: ignore
            self._size += _HDR.size + len(payload)
            return off

    def read_chunk(self, offset: int) -> tuple[bytes, int]:
        """Read mode returns a view into the image, not a copy: the reader
        copies each record's data out once, and nothing else."""
        with self._lock:
            if self._ro is not None:
                record_count, payload_len = _HDR.unpack_from(self._ro, offset)
                start = offset + _HDR.size
                return self._ro[start:start + payload_len], record_count
            record_count, payload = self._chunks[offset]
            return payload, record_count

    def write_blob(self, blob: bytes) -> int:
        with self._lock:
            if self._closed:
                raise RuntimeError("memory bag is closed")
            off = self._size
            self._segs.append((off, None, blob))  # type: ignore
            self._size += len(blob)
            return off

    def read_blob(self, offset: int, length: int) -> bytes:
        with self._lock:
            if self._ro is not None:
                return bytes(self._ro[offset:offset + length])
        # write-mode read (rare: only the index loader) — materialise
        img = self.image()
        return img[offset:offset + length]

    def read_into(self, offset: int, buf: memoryview) -> None:
        with self._lock:
            buf[:] = self._ro[offset:offset + len(buf)]

    def size(self) -> int:
        with self._lock:
            return self._size

    def flush(self) -> None:  # RAM is always "flushed"
        pass

    def close(self) -> None:
        """Close the cache.  The disk-format image is captured at close time,
        so :meth:`image` stays valid afterwards (close-safe by contract —
        workers ship ``bag.close(); bag.chunked_file.image()`` as the task
        result); further writes raise."""
        with self._lock:
            if self._closed:
                return
            if self._ro is None:
                # consolidate segments into the final image now, while the
                # write-mode state is guaranteed intact
                img = self._join_segs()
                self._segs = [img]
            self._closed = True

    # -- RAM <-> disk interchange ------------------------------------------

    def _join_segs(self) -> bytes:
        """Single-join materialisation of the write-mode segment list.
        Caller holds the lock."""
        parts: list[bytes] = []
        for seg in self._segs:
            if isinstance(seg, bytes):
                parts.append(seg)
            else:
                off, rc, payload = seg
                if rc is None:
                    parts.append(payload)
                else:
                    parts.append(_HDR.pack(rc, len(payload)))
                    parts.append(payload)
        return b"".join(parts)

    def image(self) -> bytes:
        """Materialise the disk-format byte image (single join).  Safe to
        call before or after :meth:`close`.  Read mode over a full bytes
        image returns it as-is (zero copy — bytes is immutable), so
        image -> open_read -> image round-trips don't duplicate fleets of
        merged output on the driver."""
        with self._lock:
            if self._ro is not None:
                base = self._ro.obj
                if type(base) is bytes and len(base) == self._size:
                    return base
                return bytes(self._ro)
            return self._join_segs()

    def persist(self, path: str) -> None:
        with open(path, "wb") as f:
            f.write(self.image())

    @classmethod
    def from_file(cls, path: str) -> "MemoryChunkedFile":
        with open(path, "rb") as f:
            return cls(f.read())


class Bag:
    """Upper tier: topic/timestamp record API over a ChunkedFile.

    ``Bag.open_write(...)`` / ``Bag.open_read(...)`` choose the backend:
    ``backend="disk"`` uses :class:`ChunkedFile`, ``backend="memory"`` uses
    :class:`MemoryChunkedFile` (the paper's cache).
    """

    _INDEX = struct.Struct("<QIQQ")   # chunk offset, record_count, t_min, t_max

    def __init__(self, chunked: ChunkedFile, writable: bool,
                 chunk_bytes: int = DEFAULT_CHUNK_BYTES):
        self._cf = chunked
        self._writable = writable
        self._chunk_bytes = chunk_bytes
        self._topics: dict[str, int] = {}
        self._topic_names: list[str] = []
        self._chunks: list[ChunkInfo] = []
        self._pending = bytearray()
        self._pending_records: list[tuple[int, int]] = []  # (topic_id, t)
        self._closed = False
        if not writable:
            self._load_index()

    # -- constructors --------------------------------------------------------

    @classmethod
    def open_write(cls, path: Optional[str] = None, backend: str = "disk",
                   chunk_bytes: int = DEFAULT_CHUNK_BYTES) -> "Bag":
        if backend == "disk":
            return cls(ChunkedFile(path, "w"), True, chunk_bytes)
        elif backend == "memory":
            return cls(MemoryChunkedFile(), True, chunk_bytes)
        raise ValueError(backend)

    @classmethod
    def open_read(cls, path: Optional[str] = None, backend: str = "disk",
                  image: Optional[bytes] = None) -> "Bag":
        if backend == "disk":
            return cls(ChunkedFile(path, "r"), False)
        elif backend == "memory":
            return cls(MemoryChunkedFile(image), False)
        raise ValueError(backend)

    @property
    def chunked_file(self) -> ChunkedFile:
        return self._cf

    # -- write path -----------------------------------------------------------

    def _topic_id(self, topic: str) -> int:
        tid = self._topics.get(topic)
        if tid is None:
            tid = len(self._topic_names)
            self._topics[topic] = tid
            self._topic_names.append(topic)
        return tid

    def write(self, topic: str, timestamp: int, data: bytes) -> None:
        if not self._writable or self._closed:
            raise RuntimeError("bag not writable")
        tid = self._topic_id(topic)
        if not self._pending_records and len(data) >= self._chunk_bytes:
            # large-record fast path: one record = one chunk, single copy
            payload = _REC.pack(tid, timestamp, len(data)) + data
            self._chunks.append(ChunkInfo(
                offset=self._cf.write_chunk(payload, 1), record_count=1,
                t_min=timestamp, t_max=timestamp, topics={tid}))
            return
        self._pending += _REC.pack(tid, timestamp, len(data))
        self._pending += data
        self._pending_records.append((tid, timestamp))
        if len(self._pending) >= self._chunk_bytes:
            self._flush_chunk()

    def write_message(self, msg: Message) -> None:
        self.write(msg.topic, msg.timestamp, msg.data)

    def _flush_chunk(self) -> None:
        if not self._pending_records:
            return
        ts = [t for _, t in self._pending_records]
        info = ChunkInfo(
            offset=self._cf.write_chunk(bytes(self._pending),
                                        len(self._pending_records)),
            record_count=len(self._pending_records),
            t_min=min(ts), t_max=max(ts),
            topics={tid for tid, _ in self._pending_records},
        )
        self._chunks.append(info)
        self._pending.clear()
        self._pending_records.clear()

    @classmethod
    def _index_blob(cls, topic_names: Sequence[str],
                    chunks: Sequence[ChunkInfo]) -> bytes:
        blob = bytearray()
        names = "\x00".join(topic_names).encode()
        blob += struct.pack("<I", len(names)) + names
        blob += struct.pack("<I", len(chunks))
        for c in chunks:
            blob += cls._INDEX.pack(c.offset, c.record_count, c.t_min, c.t_max)
            blob += struct.pack("<I", len(c.topics))
            for tid in sorted(c.topics):
                blob += struct.pack("<I", tid)
        return bytes(blob)

    def _write_index(self) -> None:
        blob = self._index_blob(self._topic_names, self._chunks)
        off = self._cf.write_blob(blob)
        self._cf.write_blob(struct.pack("<QQ", off, len(blob)) + b"RIDX")

    def close(self) -> None:
        if self._closed:
            return
        if self._writable:
            self._flush_chunk()
            self._write_index()
            self._cf.flush()
        self._cf.close()
        self._closed = True

    def __enter__(self) -> "Bag":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- read path -------------------------------------------------------------

    def _load_index(self) -> None:
        size = self._cf.size()
        if size < 32:
            raise ValueError("bag missing index (not closed?)")
        tail = self._cf.read_blob(size - 20, 20)
        off, blen = struct.unpack("<QQ", tail[:16])
        if tail[16:] != b"RIDX" or off + blen > size:
            raise ValueError("bag missing index (not closed?)")
        self._index_offset = off      # the chunks end where the index starts
        blob = self._cf.read_blob(off, blen)
        pos = 0
        (nlen,) = struct.unpack_from("<I", blob, pos); pos += 4
        names = blob[pos:pos + nlen].decode(); pos += nlen
        self._topic_names = names.split("\x00") if names else []
        self._topics = {n: i for i, n in enumerate(self._topic_names)}
        (nchunks,) = struct.unpack_from("<I", blob, pos); pos += 4
        for _ in range(nchunks):
            o, rc, tmin, tmax = self._INDEX.unpack_from(blob, pos)
            pos += self._INDEX.size
            (ntop,) = struct.unpack_from("<I", blob, pos); pos += 4
            tops = set(struct.unpack_from(f"<{ntop}I", blob, pos)); pos += 4 * ntop
            self._chunks.append(ChunkInfo(o, rc, tmin, tmax, tops))

    @property
    def topics(self) -> list[str]:
        return list(self._topic_names)

    @property
    def indexed_topics(self) -> list[str]:
        """Topics that some chunk holds, in table order: a
        :meth:`selection_image` keeps its source's whole topic table."""
        held = set().union(*(c.topics for c in self._chunks))
        return [t for i, t in enumerate(self._topic_names) if i in held]

    @property
    def num_chunks(self) -> int:
        return len(self._chunks)

    @property
    def num_messages(self) -> int:
        return sum(c.record_count for c in self._chunks)

    def chunk_infos(self) -> list[ChunkInfo]:
        return list(self._chunks)

    @staticmethod
    def _kept_records(payload: bytes, record_count: int,
                      want: Optional[set[int]], start: Optional[int],
                      end: Optional[int],
                      ) -> Iterator[tuple[int, int, int, int]]:
        """``(topic_id, timestamp, first byte, end byte)`` of each record of
        one chunk payload that the selection keeps; its data follows the
        record's ``_REC`` header."""
        pos = 0
        for _ in range(record_count):
            tid, ts, dlen = _REC.unpack_from(payload, pos)
            nxt = pos + _REC.size + dlen
            if ((want is None or tid in want)
                    and (start is None or ts >= start)
                    and (end is None or ts < end)):
                yield tid, ts, pos, nxt
            pos = nxt

    def content_digest(self) -> str:
        """Streaming chunk-level SHA-256 of the bag's logical content.

        Covers the format version, topic table and every chunk (record
        count, index time bounds, raw payload bytes) — one chunk resident
        at a time, **no record decode**: the per-record framing inside a
        chunk payload is hashed as raw bytes, so digesting costs one
        sequential sweep of the storage tier, not a replay.  Any flipped
        payload byte, timestamp, topic rename or re-chunking changes the
        digest.  This is the bag term of the result-cache key
        (:mod:`repro.cache`): disk and memory backends with identical
        images digest identically.
        """
        if self._writable:
            raise RuntimeError("content_digest requires a read-mode bag")
        h = hashlib.sha256()
        h.update(_MAGIC + struct.pack("<I", _VERSION))
        names = "\x00".join(self._topic_names).encode()
        h.update(struct.pack("<I", len(names)) + names)
        for info in self._chunks:
            payload, record_count = self._cf.read_chunk(info.offset)
            h.update(struct.pack("<IQQ", record_count, info.t_min,
                                 info.t_max))
            h.update(payload)
        return h.hexdigest()

    def _selected_chunks(self, want: Optional[set[int]],
                         start: Optional[int], end: Optional[int],
                         chunk_range: Optional[tuple[int, int]],
                         ) -> Iterator[int]:
        """Indices of the chunks the index says hold part of a selection;
        ``want`` is the selection's topic ids (None: every topic)."""
        if want is not None and not want:
            return
        idx = range(len(self._chunks))
        if chunk_range is not None:
            idx = idx[chunk_range[0]:chunk_range[1]]
        for i in idx:
            info = self._chunks[i]
            if start is not None and info.t_max < start:
                continue
            if end is not None and info.t_min >= end:
                continue
            if want is not None and not (info.topics & want):
                continue
            yield i

    def _topic_ids(self, topics: Optional[Sequence[str]]
                   ) -> Optional[set[int]]:
        if topics is None:
            return None
        return {self._topics[t] for t in topics if t in self._topics}

    def read_messages(self, topics: Optional[Sequence[str]] = None,
                      start: Optional[int] = None,
                      end: Optional[int] = None,
                      chunk_range: Optional[tuple[int, int]] = None,
                      ) -> Iterator[Message]:
        """Replay in chunk order, then record order (globally time-ordered
        replay is :func:`iter_time_ordered`).  ``chunk_range=(lo, hi)``
        restricts to a chunk slice — this is the partitioning handle the
        scheduler uses."""
        for _, msgs in self._read_chunks(topics, start, end, chunk_range):
            yield from msgs

    def _read_chunks(self, topics: Optional[Sequence[str]],
                     start: Optional[int], end: Optional[int],
                     chunk_range: Optional[tuple[int, int]],
                     ) -> list[tuple[ChunkInfo, Iterator[Message]]]:
        """The chunks :meth:`read_messages` selects, in selection order,
        each with an iterator of its kept messages in record order; a
        chunk is read when its iterator is first advanced."""
        want = self._topic_ids(topics)
        return [(self._chunks[i], self._chunk_messages(i, want, start, end))
                for i in self._selected_chunks(want, start, end, chunk_range)]

    def _chunk_messages(self, i: int, want: Optional[set[int]],
                        start: Optional[int], end: Optional[int],
                        ) -> Iterator[Message]:
        payload, record_count = self._cf.read_chunk(self._chunks[i].offset)
        names = self._topic_names
        for tid, ts, pos, nxt in self._kept_records(
                payload, record_count, want, start, end):
            # bytes() copies a memory image's view once; a disk payload's
            # slice is already bytes, and passes as is
            yield Message(names[tid], ts, bytes(payload[pos + _REC.size:nxt]))

    def selection_image(self, topics: Optional[Sequence[str]] = None,
                        start: Optional[int] = None,
                        end: Optional[int] = None,
                        chunk_range: Optional[tuple[int, int]] = None,
                        ) -> SelectionImage:
        """Memory-bag image of what :meth:`read_messages` selects: the
        ROSBag cache fill (§3.2), built chunk by chunk from the index.

        A chunk the selection holds wholly (every topic wanted, every
        timestamp in ``[start, end)``) is carried as its own bytes, and each
        contiguous run of such chunks is one ranged read of the lower tier
        (on disk, a ``readinto`` that runs without the GIL).  Only chunks
        the selection cuts are decoded, filtered record by record and
        re-encoded.  Message order is chunk order, then record order, as
        :meth:`read_messages` yields it.  The image keeps this bag's whole
        topic table, so raw chunks keep their topic ids: use
        :attr:`indexed_topics` for the topics the selection holds.  A
        memory bag selected whole returns its own image.
        """
        if self._writable:
            raise RuntimeError("selection_image requires a read-mode bag")
        want = self._topic_ids(topics)
        chunks = self._chunks
        pos = len(_MAGIC) + 4
        index: list[ChunkInfo] = []
        # what goes between the header and the index: [source offset,
        # length] of a raw run, or the bytes of a re-encoded chunk
        parts: list = []
        raw = decoded = 0
        for i in self._selected_chunks(want, start, end, chunk_range):
            info = chunks[i]
            if ((want is None or info.topics <= want)
                    and (start is None or info.t_min >= start)
                    and (end is None or info.t_max < end)):
                nxt = (chunks[i + 1].offset if i + 1 < len(chunks)
                       else self._index_offset)
                n = nxt - info.offset
                if parts and isinstance(parts[-1], list) \
                        and sum(parts[-1]) == info.offset:
                    parts[-1][1] += n
                else:
                    parts.append([info.offset, n])
                index.append(ChunkInfo(pos, info.record_count, info.t_min,
                                       info.t_max, set(info.topics)))
                pos += n
                raw += 1
                continue
            decoded += 1
            payload, record_count = self._cf.read_chunk(info.offset)
            kept = list(self._kept_records(payload, record_count, want,
                                           start, end))
            if not kept:
                continue
            view = memoryview(payload)
            body = b"".join(view[a:b] for _, _, a, b in kept)
            stamps = [ts for _, ts, _, _ in kept]
            index.append(ChunkInfo(pos, len(kept), min(stamps), max(stamps),
                                   {tid for tid, _, _, _ in kept}))
            parts.append(_HDR.pack(len(kept), len(body)) + body)
            pos += _HDR.size + len(body)
        _RAW_CHUNKS.inc(raw)
        _DECODED_CHUNKS.inc(decoded)
        blob = self._index_blob(self._topic_names, index)
        tail = struct.pack("<QQ", pos, len(blob)) + b"RIDX"
        total = pos + len(blob) + len(tail)
        if (not decoded and raw == len(chunks)
                and isinstance(self._cf, MemoryChunkedFile)):
            whole = self._cf.image()
            if len(whole) == total:
                return SelectionImage(whole, raw, 0)
        # an anonymous mapping, not bytearray(total): its pages arrive
        # zeroed inside the reads that fill them, with the GIL released,
        # where a bytearray would first memset them all holding it
        image = mmap.mmap(-1, total,
                          flags=mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS)
        out = memoryview(image)
        out[:len(_MAGIC) + 4] = _MAGIC + struct.pack("<I", _VERSION)
        at = len(_MAGIC) + 4
        for part in parts:
            if isinstance(part, list):
                src, n = part
                self._cf.read_into(src, out[at:at + n])
            else:
                n = len(part)
                out[at:at + n] = part
            at += n
        # the raw runs' extents came from the index: every chunk header
        # must agree with them
        for info, nxt in zip(index, [c.offset for c in index[1:]] + [pos]):
            record_count, payload_len = _HDR.unpack_from(image, info.offset)
            if (record_count != info.record_count
                    or info.offset + _HDR.size + payload_len != nxt):
                raise ValueError("bag chunks are not where its index says")
        out[pos:] = blob + tail
        out.release()
        return SelectionImage(image, raw, decoded)


def iter_time_ordered(bag: Bag, topics: Optional[Sequence[str]] = None,
                      start: Optional[int] = None, end: Optional[int] = None,
                      chunk_range: Optional[tuple[int, int]] = None,
                      window: int = 4096) -> Iterator[Message]:
    """Globally time-ordered replay over a bag selection.

    Bag chunks are time-ordered per chunk but may interleave across chunk
    boundaries (e.g. jittered multi-topic writes); a merge-sort over a
    heap of at most ``window`` messages restores global order.  The bag
    index bounds what is still unread: after each chunk, every heap entry
    at or below the least ``t_min`` of the selected chunks not yet read
    is yielded, since nothing unread can sort before it (an unread message
    with an equal timestamp comes later in the selection, and ties keep
    selection order), so a time-ordered bag streams out chunk by chunk.
    The sequence is the plain heap window's, message for message; the
    ``replay_order`` metrics scope counts the messages each rule let out.
    This is the ordering contract ``RosPlay`` publishes with and
    :func:`merge_bags` merges with.
    """
    chunks = bag._read_chunks(topics, start, end, chunk_range)
    # marks[k]: the least t_min of the chunks after chunk k (None after the
    # last), below which no unread message can carry a timestamp
    marks: list[Optional[int]] = [None] * len(chunks)
    for k in range(len(chunks) - 1, 0, -1):
        t = chunks[k][0].t_min
        marks[k - 1] = t if marks[k] is None else min(marks[k], t)
    heap: list[tuple[int, int, Message]] = []
    seq = by_mark = by_window = drained = 0
    try:
        for (_, msgs), mark in zip(chunks, marks):
            for msg in msgs:
                heapq.heappush(heap, (msg.timestamp, seq, msg))
                seq += 1
                if len(heap) > window:
                    by_window += 1
                    yield heapq.heappop(heap)[2]
            while heap and mark is not None and heap[0][0] <= mark:
                by_mark += 1
                yield heapq.heappop(heap)[2]
        while heap:
            drained += 1
            yield heapq.heappop(heap)[2]
    finally:
        _BY_WATERMARK.inc(by_mark)
        _BY_WINDOW.inc(by_window)
        _DRAINED.inc(drained)


def bag_content_digest(source: "Bag | bytes | str") -> str:
    """:meth:`Bag.content_digest` over any bag-backed source — an open
    read-mode ``Bag``, a memory-bag image (``bytes``) or a disk path."""
    bag, owned = _open_source(source)
    try:
        return bag.content_digest()
    finally:
        if owned:
            bag.close()


BagSource = Union["Bag", bytes, bytearray, memoryview, str, SegmentHandle,
                  Iterable[Message], "Callable[[], object]"]


def _open_source(source: BagSource) -> tuple[Bag, bool]:
    """Open a bag-backed merge source; returns (bag, owned).  Accepts an
    already-open ``Bag``, a memory-bag image (``bytes``), a disk path
    (``str``), or a shared-memory spill (:class:`~repro.shm.SegmentHandle`
    — the segment stays linked for retries; its owner unlinks it)."""
    if isinstance(source, Bag):
        return source, False
    if isinstance(source, SegmentHandle):
        return Bag.open_read(backend="memory",
                             image=read_segment(source)), True
    if isinstance(source, (bytes, bytearray, memoryview)):
        return Bag.open_read(backend="memory", image=bytes(source)), True
    return Bag.open_read(str(source), backend="disk"), True


def _iter_source(source: BagSource) -> Iterator[Message]:
    """Time-ordered message stream out of any merge source.

    Bag-backed sources (``Bag`` / image / path) are opened lazily inside
    the generator and closed as soon as they are exhausted, so a k-way
    merge holds each owned source only while it is still feeding the
    heap.  A zero-argument callable is resolved on first pull (deferred
    open — e.g. a temp-file spill that appears once a worker lands); any
    other iterable is streamed as-is — the hook that lets shard iterators
    (worker result streams, spilled partitions) merge without ever
    materialising their partition image on the driver.
    """
    if callable(source):
        source = source()
    if isinstance(source, (Bag, bytes, bytearray, memoryview, str,
                           SegmentHandle)):
        bag, owned = _open_source(source)
        try:
            yield from iter_time_ordered(bag)
        finally:
            if owned:
                bag.close()
    else:
        yield from source


def merge_bags(sources: Iterable[BagSource], path: Optional[str] = None,
               chunk_bytes: int = DEFAULT_CHUNK_BYTES) -> Bag:
    """Timestamp-ordered k-way merge of bags into one output bag with a
    rebuilt time/topic index — the bag-layer half of the aggregation stage
    (shard/partition output images -> one fleet-level result bag).

    ``sources`` are ``Bag`` instances, memory-bag images (``bytes``),
    disk paths, time-ordered ``Message`` iterators, or zero-argument
    callables resolving to any of those; source order breaks timestamp
    ties, so merging partition images in (shard, partition) order is
    deterministic.  Iterator/callable sources are the **streaming mode**:
    nothing is materialised per source on the driver — shard outputs
    spilled to disk merge through index-only disk readers, and exhausted
    sources are closed mid-merge instead of being held until the end.
    Returns a read-mode ``Bag``: memory-backed when ``path`` is None,
    else persisted to ``path`` on disk.  Merging zero sources yields a
    valid empty bag.

    Each source must come out of :func:`iter_time_ordered` monotonic —
    true for anything recorded from time-ordered replay.  A pathological
    source whose internal disorder exceeds the heap window would silently
    poison ``heapq.merge``, so monotonicity is checked and raises
    ``ValueError`` instead.
    """
    def keyed(idx: int, source: BagSource,
              ) -> Iterator[tuple[tuple[int, int, int], Message]]:
        last = None
        for seq, msg in enumerate(_iter_source(source)):
            if last is not None and msg.timestamp < last:
                raise ValueError(
                    f"merge source {idx} is out of timestamp order beyond "
                    "the ordering window; re-record it through time-ordered "
                    "replay before merging")
            last = msg.timestamp
            yield (msg.timestamp, idx, seq), msg

    backend = "disk" if path is not None else "memory"
    out = Bag.open_write(path=path, backend=backend, chunk_bytes=chunk_bytes)
    streams = [keyed(i, s) for i, s in enumerate(sources)]
    for _, msg in heapq.merge(*streams, key=lambda kv: kv[0]):
        out.write_message(msg)
    out.close()
    if path is not None:
        return Bag.open_read(path, backend="disk")
    return Bag.open_read(backend="memory", image=out.chunked_file.image())


def partition_bag(bag: Bag, num_partitions: int) -> list[tuple[int, int]]:
    """Split a bag into ``num_partitions`` contiguous chunk ranges with
    roughly equal record counts — the RDD-partitioning step of the platform."""
    counts = [c.record_count for c in bag.chunk_infos()]
    total = sum(counts)
    if not counts:
        return []
    num_partitions = max(1, min(num_partitions, len(counts)))
    target = total / num_partitions
    parts: list[tuple[int, int]] = []
    acc, lo = 0, 0
    for i, c in enumerate(counts):
        acc += c
        if acc >= target and len(parts) < num_partitions - 1:
            parts.append((lo, i + 1))
            lo, acc = i + 1, 0
    parts.append((lo, len(counts)))
    return parts
