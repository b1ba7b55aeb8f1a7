"""The bag-cache fill (``Bag.selection_image``): for each kind of selection
and of source, the cache image replays exactly what the record-by-record
fill replayed, and the ``bag_cache`` counters split the chunks between raw
copies and re-encoded cuts as the index says they should."""

import pytest

from repro.core.bag import Bag
from repro.obs import metrics as obs_metrics
from repro.shm import (new_prefix, read_segment, shm_available,
                       unlink_segment, write_segment)

REC = 16 + 64          # record header + data: 5 records fill a 400 B chunk


def _mixed_bag(path):
    """12 chunks of 5 records, one record a microsecond: chunks 0-3 and
    8-11 hold /lidar alone, chunks 4-7 /lidar and /imu interleaved."""
    b = Bag.open_write(path, chunk_bytes=5 * REC)
    for i in range(60):
        topic = "/imu" if 20 <= i < 40 and i % 2 else "/lidar"
        b.write(topic, i * 1000, bytes([i]) * 64)
    b.close()


def _large_bag(path):
    """8 records over the chunk threshold: one chunk a record."""
    b = Bag.open_write(path, chunk_bytes=1024)
    for i in range(8):
        b.write("/cam", i * 1000, bytes([i]) * 2000)
    b.close()


def _record_fill(src, topics=None, start=None, end=None, chunk_range=None):
    """The fill as it was: every selected record decoded and written again."""
    cache = Bag.open_write(backend="memory")
    for m in src.read_messages(chunk_range=chunk_range):
        if ((topics is None or m.topic in topics)
                and (start is None or m.timestamp >= start)
                and (end is None or m.timestamp < end)):
            cache.write_message(m)
    cache.close()
    return Bag.open_read(backend="memory", image=cache.chunked_file.image())


def _open(path, kind):
    """The source as a partition task receives it: a disk path, an inline
    image, or an image parked in shared memory."""
    if kind == "disk":
        return Bag.open_read(path, backend="disk"), None
    with open(path, "rb") as f:
        image = f.read()
    if kind == "bytes":
        return Bag.open_read(backend="memory", image=image), None
    handle = write_segment(new_prefix("t"), image)
    return Bag.open_read(backend="memory", image=read_segment(handle)), handle


SELECTIONS = [
    # name, bag, selection, raw chunks, decoded chunks
    ("whole", _mixed_bag, {"chunk_range": (0, 12)}, 12, 0),
    ("no_chunk_range", _mixed_bag, {}, 12, 0),
    ("topic_subset", _mixed_bag, {"topics": ["/lidar"]}, 8, 4),
    ("topic_only_in_mixed", _mixed_bag, {"topics": ["/imu", "/none"]}, 0, 4),
    ("window_cuts_both_ends", _mixed_bag, {"start": 7000, "end": 52500}, 8, 2),
    ("window_on_chunk_edges", _mixed_bag, {"start": 10000, "end": 49000}, 7, 1),
    ("chunk_range_slice", _mixed_bag, {"chunk_range": (3, 9)}, 6, 0),
    ("slice_topic_window", _mixed_bag,
     {"chunk_range": (2, 10), "topics": ["/lidar"], "start": 12000}, 3, 5),
    ("nothing", _mixed_bag, {"topics": ["/none"]}, 0, 0),
    ("large_records", _large_bag, {"start": 2000, "end": 6000}, 4, 0),
]
SOURCES = ["disk", "bytes", pytest.param("segment", marks=pytest.mark.skipif(
    not shm_available(), reason="no usable POSIX shared memory here"))]


@pytest.mark.parametrize("kind", SOURCES)
@pytest.mark.parametrize("name,make,sel,raw,decoded", SELECTIONS,
                         ids=[s[0] for s in SELECTIONS])
def test_selection_image_replays_the_record_fill(tmp_path, kind, name, make,
                                                 sel, raw, decoded):
    path = str(tmp_path / "src.bag")
    make(path)
    src, handle = _open(path, kind)
    try:
        before = obs_metrics.snapshot()["bag_cache"]
        fill = src.selection_image(**sel)
        after = obs_metrics.snapshot()["bag_cache"]
        ref = _record_fill(src, **sel)
    finally:
        if handle is not None:
            unlink_segment(handle)
    cache = Bag.open_read(backend="memory", image=fill.image)
    assert list(cache.read_messages()) == list(ref.read_messages())
    assert cache.num_messages == ref.num_messages
    assert set(cache.indexed_topics) == set(ref.topics)
    assert (fill.raw_chunks, fill.decoded_chunks) == (raw, decoded)
    assert (after["raw_chunks"] - before["raw_chunks"],
            after["decoded_chunks"] - before["decoded_chunks"]) \
        == (raw, decoded)
    if raw == src.num_chunks:
        # a whole bag carried raw is its own image, to the byte
        with open(path, "rb") as f:
            assert bytes(fill.image) == f.read()
    src.close()


def test_selection_image_refuses_chunks_off_their_index(tmp_path):
    """Raw runs take their extents from the index; a chunk header that
    disagrees with it is an error, not a silently wrong cache."""
    path = str(tmp_path / "src.bag")
    _mixed_bag(path)
    with open(path, "rb") as f:
        image = bytearray(f.read())
    src = Bag.open_read(backend="memory", image=bytes(image))
    off = src.chunk_infos()[3].offset
    image[off] += 1                    # record count of chunk 3
    bad = Bag.open_read(backend="memory", image=bytes(image))
    with pytest.raises(ValueError, match="index"):
        bad.selection_image(chunk_range=(2, 5))
