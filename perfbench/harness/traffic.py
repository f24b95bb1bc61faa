"""The one traffic generator: keys and arrivals from a seed and a mix file.

A mix names its key distribution and, for an open loop, its arrival
process; each is a file of its own, found by that name:

    perfbench/traffic/keys/<distribution>.py      draw(key, n, mix)
    perfbench/traffic/arrivals/<arrivals>.py      due(rng, n_req, seconds, mix)

so a later mix with another distribution or arrival process adds a file,
and no file here changes. Keys are made on the device with `jax.random`,
in one jitted call each.

Arrivals of an open loop keep the same work for every seed: the number of
requests is rate * seconds, the sizes are the mix's weights apportioned
over them, and the arrival process spreads them over the window from a
fixed set of gaps. The seed only orders them, and picks where each
request's keys start in the key pool. So seeds differ in order and
content, not in how much work a run offers.
"""
from __future__ import annotations

import numpy as np

from harness import bench

INT32_MIN = -(2 ** 31)
INT32_MAX = 2 ** 31 - 1          # the untagged path's pad sentinel


def _part(kind: str, name: str):
    return bench.load_module(bench.PERFBENCH / "traffic" / kind / f"{name}.py")


def rng_key(seed: int, stream: int):
    """A jax PRNG key for one stream of a seed; bits above 32 fold in."""
    import jax
    key = jax.random.key(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    return jax.random.fold_in(key, stream)


def device_keys(seed: int, stream: int, n: int, mix: dict, *, sharding=None):
    """n keys of the mix's distribution on the device (placed by
    `sharding` if given)."""
    import jax
    draw = _part("keys", mix["distribution"]).draw
    make = jax.jit(lambda k: draw(k, n, mix), out_shardings=sharding)
    return make(rng_key(seed, stream))


def host_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([stream, seed])


def exponential_times(rng: np.random.Generator, n_req: int,
                      seconds: float) -> np.ndarray:
    """Arrival times of a Poisson process that fills `seconds` with n_req
    requests: the gaps are n_req fixed exponential quantiles, scaled to
    sum to `seconds`, in an order drawn from `rng`."""
    q = (np.arange(n_req) + 0.5) / n_req
    gaps = -np.log1p(-q)
    gaps = rng.permutation(gaps * (seconds / gaps.sum()))
    return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])


def apportion(total: int, weights) -> np.ndarray:
    """Split `total` into integer parts proportional to `weights`
    (largest remainder, ties to the earlier class)."""
    w = np.asarray(weights, np.float64)
    exact = total * w / w.sum()
    parts = np.floor(exact).astype(np.int64)
    order = np.argsort(-(exact - parts), kind="stable")
    parts[order[:total - int(parts.sum())]] += 1
    return parts


def open_schedule(seed: int, mix: dict, seconds: float) -> dict:
    """Requests of an open loop over `seconds`.

    Returns arrays of length N = round(rate * seconds): `due_s` (arrival
    time from the window's start), `size` (keys), `offset` (start in the
    key pool) and `check` (whether the run compares this answer)."""
    arrivals = _part("arrivals", mix["arrivals"])
    rng = host_rng(seed, 1)
    n_req = max(1, int(round(mix["rate_per_s"] * seconds)))
    sizes = np.asarray(mix["sizes"], np.int64)
    size = np.repeat(sizes, apportion(n_req, mix["weights"]))
    size = size[rng.permutation(n_req)]
    due = np.asarray(arrivals.due(rng, n_req, seconds, mix), np.float64)
    pool = int(mix["pool_keys"])
    if sizes.max() > pool:
        raise ValueError(f"pool_keys {pool} < largest request {sizes.max()}")
    offset = rng.integers(0, pool - size + 1)
    check = np.zeros(n_req, bool)
    check[rng.choice(n_req, size=min(n_req, int(mix["check_requests"])),
                     replace=False)] = True
    check[np.flatnonzero(size == sizes.max())[:1]] = True   # the longest
    return {"due_s": due, "size": size, "offset": offset, "check": check}


def checked_calls(seed: int, mix: dict) -> list:
    """Which calls of a closed loop are compared: `check_calls` distinct
    indices among the first `check_among_first`, drawn from the seed."""
    rng = host_rng(seed, 2)
    picks = rng.choice(int(mix["check_among_first"]),
                       size=int(mix["check_calls"]), replace=False)
    return sorted(int(i) for i in picks)
