"""The plain reference for Sort Benchmark records, in NumPy alone.

A record is 100 bytes: a 10-byte key, then a 90-byte payload. The
reference orders records by their keys as unsigned bytes (memcmp), and
records with equal keys by their whole 100 bytes, so its answer is
unique. A sort may put records with equal keys in any order; the
comparison therefore orders each run of equal keys in the answer it is
given by whole record too, and then counts the positions whose 100 bytes
differ. It imports nothing of the program under test.
"""
from __future__ import annotations

import numpy as np

RECORD_BYTES = 100
KEY_BYTES = 10


def _as_records(records) -> np.ndarray:
    r = np.ascontiguousarray(np.asarray(records, np.uint8))
    if r.ndim != 2 or r.shape[1] != RECORD_BYTES:
        raise ValueError(f"records are (n, {RECORD_BYTES}) uint8, got "
                         f"{r.shape}")
    return r


def _key_columns(r: np.ndarray) -> list:
    """The key as two unsigned words, most significant first: bytes 0-7
    big-endian, then bytes 8-9."""
    return [r[:, :8].view(">u8")[:, 0].astype(np.uint64),
            r[:, 8:12].view(">u4")[:, 0] >> np.uint32(16)]


def records_from_words(words) -> np.ndarray:
    """(25, n) uint32 words, each four bytes big-endian -> (n, 100) uint8
    records."""
    w = np.ascontiguousarray(np.asarray(words, np.uint32).T)
    return w.astype(">u4").view(np.uint8).reshape(-1, RECORD_BYTES)


def _equal_key_runs(r: np.ndarray) -> list:
    """(start, end) of each run of two or more equal keys in key order."""
    keys = r[:, :KEY_BYTES]
    same = np.all(keys[1:] == keys[:-1], axis=1)
    if not same.any():
        return []
    edges = np.flatnonzero(np.diff(np.concatenate([[0], same, [0]])))
    return [(int(s), int(e) + 1) for s, e in zip(edges[::2], edges[1::2])]


def _order_runs_by_record(r: np.ndarray) -> np.ndarray:
    """Order every run of equal keys by whole record (r is in key order)."""
    out = r.copy()
    for s, e in _equal_key_runs(r):
        run = r[s:e]
        cols = run.view(">u4").astype(np.uint32)
        out[s:e] = run[np.lexsort(cols.T[::-1])]
    return out


def reference_sort(records) -> np.ndarray:
    """(n, 100) uint8 records -> the same records in memcmp order of their
    keys, equal keys in memcmp order of their whole records."""
    r = _as_records(records)
    hi, lo = _key_columns(r)
    return _order_runs_by_record(r[np.lexsort((lo, hi))])


def mismatched_records(got, want) -> int:
    """Positions at which `got`, its runs of equal keys ordered by whole
    record, differs from `want` in any of the 100 bytes, plus the records
    one of them has beyond the other's length."""
    got, want = _as_records(got), _as_records(want)
    m = min(len(got), len(want))
    g = _order_runs_by_record(got[:m])
    diff = np.any(g.view(">u4") != want[:m].view(">u4"), axis=1)
    return int(np.count_nonzero(diff)) + abs(len(got) - len(want))
