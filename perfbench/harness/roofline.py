"""The work a sort needs, and its share of the chip's roofline.

A sort of n keys needs at least one read and one write of every key,
whatever algorithm runs: 2 * n * itemsize bytes through HBM. Its least
time is those bytes at the peak HBM bandwidth (a sort does no
floating-point work, so bandwidth bounds it). The share is that least
time over the device time measured for the work; padding, extra passes
or a network that streams the keys many times all show as a lower share.
"""
from __future__ import annotations


def sort_bytes(n_keys: float, itemsize: int = 4) -> float:
    """Bytes a sort of n_keys keys must move: one read and one write."""
    return 2.0 * n_keys * itemsize


def least_time_s(n_bytes: float, peaks: dict) -> float:
    return n_bytes / peaks["hbm_bytes_per_s"]


def share_pct(n_bytes: float, device_s: float, peaks: dict) -> float | None:
    """Roofline share in percent; None when nothing was timed."""
    if device_s <= 0 or n_bytes <= 0:
        return None
    return 100.0 * least_time_s(n_bytes, peaks) / device_s
