"""Paper Figure 5: HSS under every paper input distribution (robustness).
Duplicated-key distributions run through implicit tagging (Section 6.3).

The fig5/adv_* rows extend the sweep with the adversarial family
(DESIGN.md Section 9) — degenerate, aliasing, and heavy-hitter inputs —
and track the achieved partition quality (achieved_eps = max_load - 1)
so the trajectory catches any drift past the paper's (1+eps) bound."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import timeit
from repro.core import ExchangeConfig, HSSConfig, hss_sort
from repro.core.tagging import pack_tagged
from repro.data.distributions import (ADVERSARIAL, DISTRIBUTIONS,
                                      make_adversarial, make_distribution)
from repro.parallel.compat import make_mesh


def _tagged_row(label, keys, *, p, n_per, mesh, eps):
    """Tag-pack per shard and time hss_sort; derived field carries the
    achieved load balance (the paper's (1+eps) quantity)."""
    n = p * n_per
    kb = max(1, int(np.ceil(np.log2(int(keys.max()) + 1))) if keys.max() else 1)
    tagged = np.concatenate([
        np.asarray(pack_tagged(jnp.asarray(keys[i * n_per:(i + 1) * n_per]),
                               i, p=p, n_local=n_per, key_bits=kb))
        for i in range(p)])
    x = jnp.asarray(tagged)
    res = hss_sort(x, mesh=mesh, hss_cfg=HSSConfig(eps=eps),
                   ex_cfg=ExchangeConfig(strategy="allgather"))
    us = timeit(lambda: hss_sort(
        x, mesh=mesh, hss_cfg=HSSConfig(eps=eps),
        ex_cfg=ExchangeConfig(strategy="allgather")).shards)
    balance = float(np.asarray(res.counts).max() * p / n)
    return (label, round(us, 1),
            f"rounds={int(res.stats.rounds_used)} "
            f"max_load={balance:.3f} achieved_eps={balance - 1:.3f} "
            f"overflow={int(res.overflow)}")


def run(n_per: int = 32768, eps: float = 0.05):
    rows = []
    p = min(8, len(jax.devices()))
    mesh = make_mesh((p,), ("sort",), devices=jax.devices()[:p])
    n = p * n_per
    for name in sorted(DISTRIBUTIONS):
        # 12-bit keys leave room for the 18 tag bits in int32 packing
        keys = make_distribution(name, n, seed=7) >> 18
        rows.append(_tagged_row(f"fig5/{name}", keys, p=p, n_per=n_per,
                                mesh=mesh, eps=eps))
    for name in sorted(ADVERSARIAL):
        if name == "DTYPE_EXTREME":
            continue   # leaves the tagging envelope; covered by tests
        keys = make_adversarial(name, n, seed=7) >> 18
        rows.append(_tagged_row(f"fig5/adv_{name}", keys, p=p, n_per=n_per,
                                mesh=mesh, eps=eps))
    return rows
