"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the records the window served, drawn from the seed and stratified over the
topics (so the longest records are in it), is run through the plain float32
reference of the configuration's family.  The logits the window wrote to its
output bags are compared with the reference's:

``logit_gap``
    the widest gap, over the sample, between a served logit and the
    reference's, as a share of the largest reference logit in the sample.
    A timestamp that two topics share has one output per topic; a record is
    held to the nearest of them.

Every verdict of the window is also checked whole:

``digest_mismatch``
    topics whose checksum in the verdict differs from the checksum this
    file computes over the output bag's records (exact: limit 0);
``output_count_mismatch``
    records missing or extra, summed over scenarios (exact: limit 0);
``verdicts_failed``
    scenarios whose verdict is not PASS, including any of a suite that
    raised (exact: limit 0).

The limits of ``logit_gap`` are in ``bench/cells/<workload>.json`` with the
readings they were set from.
"""

from __future__ import annotations

import sys

import numpy as np

from reference.common import decode_features

_U32 = np.uint64(0xFFFFFFFF)


def record_digests(payload: np.ndarray, lengths: np.ndarray,
                   ts_low: np.ndarray) -> np.ndarray:
    """Per-record checksum: the wrapping-uint32 sum of each valid byte
    times a per-column weight, mixed with the timestamp's low 32 bits and
    the length.  The arithmetic the system's metrics sink documents."""
    p = payload.astype(np.uint32)
    col = np.arange(payload.shape[1], dtype=np.uint32)
    mask = col[None, :] < lengths.astype(np.uint32)[:, None]
    w = col * np.uint32(2246822519) + np.uint32(0x9E3779B9)
    rec = np.where(mask, p * w[None, :], np.uint32(0)).sum(
        axis=1, dtype=np.uint32)
    rec = (rec ^ ts_low.astype(np.uint32)) * np.uint32(2654435761)
    return rec + lengths.astype(np.uint32) * np.uint32(40503)


def topic_checksum(records: list) -> int:
    """Checksum of one topic's (timestamp, bytes) records."""
    if not records:
        return 0
    lengths = np.array([len(d) for _, d in records], np.int64)
    nb = max(-(-int(lengths.max()) // 128) * 128, 128)
    payload = np.zeros((len(records), nb), np.uint8)
    for i, (_, d) in enumerate(records):
        payload[i, :len(d)] = np.frombuffer(d, np.uint8)
    ts = np.array([t for t, _ in records], np.int64)
    ts_low = (ts.astype(np.uint64) & _U32).astype(np.uint32)
    digests = record_digests(payload, lengths, ts_low).astype(np.uint64)
    return int(digests.sum(dtype=np.uint64) & _U32)


def read_outputs(image: bytes) -> dict:
    """{topic: [(timestamp, bytes)]} of an output bag image."""
    from repro.core import Bag
    bag = Bag.open_read(backend="memory", image=image)
    try:
        out: dict = {}
        for m in bag.read_messages():
            out.setdefault(m.topic, []).append((m.timestamp, m.data))
        return out
    finally:
        bag.close()


def sample(clips, traffic: dict, runs: list, seed: int) -> list:
    """(run index, clip index, record index) of the records to compare:
    ``traffic["sample"]`` of them, the same number from every topic."""
    rng = np.random.default_rng([seed % (1 << 64), 0x5A3])
    ok = [i for i, r in enumerate(runs) if r.verdicts is not None]
    topics = [t["topic"] for t in traffic["topics"]]
    per = max(1, traffic["sample"] // len(topics))
    picks = []
    for topic in topics:
        cands = [(c, i) for c, clip in enumerate(clips)
                 for i, t in enumerate(clip.topics) if t == topic]
        for j in rng.choice(len(cands), size=min(per, len(cands)),
                            replace=False):
            picks.append((int(rng.choice(ok)) if ok else -1,) + cands[j])
    return picks


def logit_gap(served: list, ref: np.ndarray) -> float:
    """Widest gap of each record's nearest served candidate from its
    reference row, over the largest reference logit."""
    scale = float(np.abs(ref).max())
    worst = 0.0
    for cands, r in zip(served, ref):
        if not cands:
            return float("inf")
        worst = max(worst, min(float(np.abs(c - r).max()) for c in cands))
    return worst / scale if scale > 0 else float("inf")


def reference_logits(cell, wseed: int, clips, picks, precision="float32"):
    """Reference logits of the picked records, one call per topic (records
    of a topic share a length)."""
    d_model = cell.config["model"]["d_model"]
    rows = {}
    for k, (_, c, i) in enumerate(picks):
        rows.setdefault(len(clips[c].payloads[i]), []).append(k)
    out = np.zeros((len(picks), cell.config["out_features"]), np.float32)
    for ks in rows.values():
        embeds = np.stack([decode_features(
            clips[picks[k][1]].payloads[picks[k][2]], d_model) for k in ks])
        out[ks] = cell.reference.forward(cell.config, wseed, embeds,
                                         precision)
    return out


def compare(cell, wseed: int, seed: int, clips, runs: list,
            control=()) -> dict:
    """Every number compared, as {name: value}; for each precision in
    ``control`` also ``control_gap.<precision>``, the gap of the reference
    computed at that precision in the program's place."""
    failed = digest_bad = count_bad = 0
    outputs = {}
    for ri, r in enumerate(runs):
        if r.verdicts is None:
            failed += len(clips)
            continue
        for ci, clip in enumerate(clips):
            v = r.verdicts.get(f"clip{ci:03d}")
            if v is None or v.status != "PASS":
                failed += 1
            if v is None or v.report is None:
                continue
            outs = read_outputs(v.report.output_image)
            outputs[(ri, ci)] = outs
            n_out = sum(len(x) for x in outs.values())
            count_bad += abs(n_out - len(clip.topics))
            for topic, m in v.metrics.items():
                if topic_checksum(outs.get(topic, [])) != m.checksum:
                    digest_bad += 1
    picks = sample(clips, cell.traffic, runs, seed)
    served = []
    for ri, ci, i in picks:
        ts = int(clips[ci].timestamps[i])
        outs = outputs.get((ri, ci), {})
        served.append([np.frombuffer(d, np.float32)
                       for recs in outs.values() for t, d in recs
                       if t == ts])
    ref = reference_logits(cell, wseed, clips, picks)
    got = {"logit_gap": logit_gap(served, ref),
           "digest_mismatch": digest_bad,
           "output_count_mismatch": count_bad,
           "verdicts_failed": failed}
    for precision in control:
        low = reference_logits(cell, wseed, clips, picks, precision)
        got["control_gap." + precision] = logit_gap([[row] for row in low],
                                                    ref)
    return got


def limits(cell) -> dict:
    return {"logit_gap": cell.limits["logit_gap"], "digest_mismatch": 0,
            "output_count_mismatch": 0, "verdicts_failed": 0}


def judge(values: dict, lim: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) over the numbers with limits."""
    shown = {k: {"value": values[k], "limit": lim[k]} for k in lim}
    return all(values[k] <= lim[k] for k in lim), shown


def print_checks(shown: dict) -> None:
    for k, v in shown.items():
        print(f"check {k} {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
