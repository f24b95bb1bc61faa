"""Paper Figure 3: overhead of duplicate handling via implicit tagging.

Runs the same UNIF workload raw (distinct keys) and tag-packed; the delta is
the tagging overhead (paper: ~4% at 32K processors).

The fig3/adv_* rows push duplicate-pileup adversaries (all-equal, zipf
heavy hitters) through the public `repro.sort` API with the device-side
audit on (DESIGN.md Section 9): auto-tagging must keep the achieved
partition imbalance near 1 even when one key owns most of the mass, and
the derived field records the audited achieved_eps."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import timeit
from repro.core import ExchangeConfig, HSSConfig, hss_sort
from repro.core.tagging import pack_tagged
from repro.data.distributions import make_adversarial
from repro.sort import SortSpec, sort as api_sort
from repro.parallel.compat import make_mesh


def run(n_per: int = 65536, eps: float = 0.05):
    p = min(8, len(jax.devices()))
    mesh = make_mesh((p,), ("sort",), devices=jax.devices()[:p])
    n = p * n_per
    rng = np.random.default_rng(1)
    raw = rng.permutation(n).astype(np.int32)  # distinct keys, 19 bits @ 8x64k
    x_raw = jnp.asarray(raw)
    kb = int(np.ceil(np.log2(n)))
    tagged = np.concatenate([
        np.asarray(pack_tagged(jnp.asarray(raw[i * n_per:(i + 1) * n_per] >> 8),
                               i, p=p, n_local=n_per, key_bits=kb - 8))
        for i in range(p)])
    x_tag = jnp.asarray(tagged)

    cfg = HSSConfig(eps=eps)
    ex = ExchangeConfig(strategy="allgather")
    us_raw = timeit(lambda: hss_sort(x_raw, mesh=mesh, hss_cfg=cfg,
                                     ex_cfg=ex).shards)
    us_tag = timeit(lambda: hss_sort(x_tag, mesh=mesh, hss_cfg=cfg,
                                     ex_cfg=ex).shards)
    rows = [
        ("fig3/untagged", round(us_raw, 1), "distinct keys"),
        ("fig3/tagged", round(us_tag, 1),
         f"overhead={100 * (us_tag - us_raw) / us_raw:.1f}% (paper ~4%)"),
    ]

    # adversarial duplicate pileups through the audited public API:
    # auto-tagging (tag=None) must hold achieved imbalance near 1 even
    # when one key owns most of the mass. 11-bit keys: 11 + 19 tag bits
    # fits the int32 packing budget, so auto-tagging engages rather than
    # falling back untagged (where the pileup would truncate and the
    # audit would — correctly — fail the launch).
    adv_spec = SortSpec(exchange="allgather", eps=eps, verify="cheap")
    for name in ("ALL_EQUAL", "ZIPF_HH"):
        x = jnp.asarray(make_adversarial(name, n, seed=3) >> 19)
        out = api_sort(x, adv_spec)
        imb = float(out.recovery.achieved_imbalance)
        us = timeit(lambda: api_sort(x, adv_spec).shards)
        rows.append((f"fig3/adv_{name}", round(us, 1),
                     f"auto-tag duplicate pileup; verify=cheap "
                     f"achieved_eps={imb - 1:.3f}"))
    return rows
