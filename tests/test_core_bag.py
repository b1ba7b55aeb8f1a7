"""Bag / ChunkedFile / MemoryChunkedFile tests (the invariant the whole
platform rests on: replay == record); hypothesis round-trips live in
test_property_based.py."""

import heapq
import random

import pytest

from repro.core import Bag, MemoryChunkedFile, iter_time_ordered, partition_bag
from repro.data.pipeline import iter_message_batches
from repro.obs import metrics as obs_metrics


def _write(bag, msgs):
    for t, ts, d in msgs:
        bag.write(t, ts, d)
    bag.close()


def _msgs(n=100, topics=3, size=50):
    return [(f"/t{i % topics}", i * 10, bytes([i % 256]) * size)
            for i in range(n)]


class TestDiskBag:
    def test_roundtrip(self, tmp_path):
        p = str(tmp_path / "a.bag")
        msgs = _msgs(500)
        _write(Bag.open_write(p, chunk_bytes=2048), msgs)
        r = Bag.open_read(p)
        got = [(m.topic, m.timestamp, m.data) for m in r.read_messages()]
        assert got == msgs
        assert r.num_messages == 500
        assert r.num_chunks > 1           # chunking actually happened

    def test_topic_filter(self, tmp_path):
        p = str(tmp_path / "a.bag")
        _write(Bag.open_write(p), _msgs(300))
        r = Bag.open_read(p)
        got = list(r.read_messages(topics=["/t1"]))
        assert got and all(m.topic == "/t1" for m in got)
        assert len(got) == 100

    def test_time_filter(self, tmp_path):
        p = str(tmp_path / "a.bag")
        _write(Bag.open_write(p, chunk_bytes=1024), _msgs(300))
        r = Bag.open_read(p)
        got = list(r.read_messages(start=500, end=1500))
        assert all(500 <= m.timestamp < 1500 for m in got)
        assert len(got) == 100

    def test_unclosed_bag_rejected(self, tmp_path):
        p = str(tmp_path / "a.bag")
        b = Bag.open_write(p)
        b.write("/t", 0, b"x")
        b._cf.flush()                      # bytes on disk but no index
        with pytest.raises(ValueError, match="index"):
            Bag.open_read(p)
        b.close()


class TestMemoryBag:
    def test_memory_equals_disk(self, tmp_path):
        """MemoryChunkedFile must be a drop-in for ChunkedFile (Fig 6)."""
        msgs = _msgs(400)
        p = str(tmp_path / "d.bag")
        _write(Bag.open_write(p, chunk_bytes=1024), msgs)
        mb = Bag.open_write(backend="memory", chunk_bytes=1024)
        _write(mb, msgs)
        disk = [(m.topic, m.timestamp, m.data)
                for m in Bag.open_read(p).read_messages()]
        mem = [(m.topic, m.timestamp, m.data)
               for m in Bag.open_read(
                   backend="memory",
                   image=mb.chunked_file.image()).read_messages()]
        assert disk == mem == msgs

    def test_persist_and_reload(self, tmp_path):
        mb = Bag.open_write(backend="memory")
        _write(mb, _msgs(50))
        p = str(tmp_path / "m.bag")
        mb.chunked_file.persist(p)
        # a persisted memory image is a valid DISK bag too
        r = Bag.open_read(p, backend="disk")
        assert r.num_messages == 50
        # and can be rehydrated into memory
        m2 = MemoryChunkedFile.from_file(p)
        r2 = Bag(m2, writable=False)
        assert r2.num_messages == 50


class TestPartitioning:
    def test_partitions_cover_exactly(self, tmp_path):
        p = str(tmp_path / "a.bag")
        _write(Bag.open_write(p, chunk_bytes=512), _msgs(1000))
        r = Bag.open_read(p)
        for k in (1, 2, 3, 7, 16, 1000):
            parts = partition_bag(r, k)
            # contiguous, non-overlapping, covering
            assert parts[0][0] == 0 and parts[-1][1] == r.num_chunks
            for (a, b), (c, d) in zip(parts, parts[1:]):
                assert b == c
            tot = sum(len(list(r.read_messages(chunk_range=pr)))
                      for pr in parts)
            assert tot == 1000



def _heap_window_order(bag, topics=None, start=None, end=None,
                       chunk_range=None, window=4096):
    """``iter_time_ordered`` before the index watermark, kept as the oracle:
    push every message of the selection, pop once the heap exceeds
    ``window``, drain at the end."""
    heap = []
    for seq, msg in enumerate(bag.read_messages(
            topics=topics, start=start, end=end, chunk_range=chunk_range)):
        heapq.heappush(heap, (msg.timestamp, seq, msg))
        if len(heap) > window:
            yield heapq.heappop(heap)[2]
    while heap:
        yield heapq.heappop(heap)[2]


def _random_bag(rng):
    """A memory bag whose chunks hold one record to many, with jittered
    multi-topic timestamps, equal timestamps across chunks, and now and
    then a record far behind its neighbours (disorder past small
    windows)."""
    b = Bag.open_write(backend="memory",
                       chunk_bytes=rng.choice([1, 64, 256, 1024, 8192]))
    tick = rng.choice([1, 7, 50])
    for i in range(rng.randint(0, 300)):
        ts = i * 10 + rng.randint(-30, 30)
        if rng.random() < 0.05:
            ts -= rng.randint(100, 2000)
        b.write(rng.choice(["/a", "/b", "/c"]), max(0, ts // tick * tick),
                i.to_bytes(2, "little") * rng.randint(1, 40))
    b.close()
    return Bag.open_read(backend="memory", image=b.chunked_file.image())


def _random_selection(rng, bag):
    sel = {}
    if rng.random() < 0.4:
        sel["topics"] = rng.choice([["/a"], ["/b", "/c"], ["/nope"], []])
    if rng.random() < 0.4:
        sel["start"] = rng.randint(-50, 2000)
    if rng.random() < 0.4:
        sel["end"] = rng.randint(0, 3200)
    if rng.random() < 0.4 and bag.num_chunks:
        lo = rng.randint(0, bag.num_chunks)
        sel["chunk_range"] = (lo, rng.randint(lo, bag.num_chunks))
    return sel


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("window", [1, 2, 3, 8, 4096])
def test_time_ordered_matches_heap_window(window, seed):
    """The index watermark yields the plain heap window's sequence, message
    for message, on every bag and selection and at every window: also
    where disorder overflows the window."""
    rng = random.Random(window * 1000 + seed)
    for _ in range(6):
        bag = _random_bag(rng)
        for _ in range(6):
            sel = _random_selection(rng, bag)
            want = [(m.topic, m.timestamp, m.data)
                    for m in _heap_window_order(bag, window=window, **sel)]
            got = [(m.topic, m.timestamp, m.data)
                   for m in iter_time_ordered(bag, window=window, **sel)]
            assert got == want, sel


def test_time_ordered_first_batch_reads_its_own_chunks():
    """A time-ordered bag's first batch of 16 waits for the chunks that hold
    it, not for the whole selection; the index watermark lets out all but
    the last chunk's records."""
    b = Bag.open_write(backend="memory", chunk_bytes=200)
    for i in range(400):
        b.write(f"/t{i % 2}", i * 10, bytes([i % 256]) * 100)
    b.close()
    bag = Bag.open_read(backend="memory", image=b.chunked_file.image())
    assert bag.num_chunks == 200
    reads = []
    read_chunk = bag.chunked_file.read_chunk

    def counted(offset):
        reads.append(offset)
        return read_chunk(offset)

    bag.chunked_file.read_chunk = counted
    before = obs_metrics.snapshot()["replay_order"]
    batches = iter_message_batches(iter_time_ordered(bag), batch_size=16)
    first = next(batches)
    assert [m.timestamp for m in first] == [i * 10 for i in range(16)]
    assert len(reads) <= 9
    rest = [m for batch in batches for m in batch]
    assert len(first) + len(rest) == 400 and len(reads) == 200
    after = obs_metrics.snapshot()["replay_order"]
    split = {k: after[k] - before[k] for k in before}
    assert split == {"watermark": 398, "window": 0, "drained": 2}
