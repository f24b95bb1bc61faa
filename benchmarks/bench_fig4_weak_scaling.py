"""Paper Figure 4: weak scaling of the full distributed sort (wall time at
fixed keys/shard while p grows), HSS vs sample sort vs AMS.

Host devices stand in for chips (relative comparison; absolute numbers are
CPU-bound). Keys/shard is scaled down from the paper's 2M accordingly."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import timeit
from repro.core import (ExchangeConfig, HSSConfig, ams_sort, hss_sort,
                        sample_sort)
from repro.parallel.compat import make_mesh


def run(n_per: int = 65536, eps: float = 0.05):
    rows = []
    rng = np.random.default_rng(0)
    for p in (2, 4, 8):
        if p > len(jax.devices()):
            continue
        mesh = make_mesh((p,), ("sort",), devices=jax.devices()[:p])
        x = jnp.asarray(rng.permutation(p * n_per).astype(np.int32))

        us_h = timeit(lambda x=x, m=mesh: hss_sort(
            x, mesh=m, hss_cfg=HSSConfig(eps=eps)).shards)
        us_s = timeit(lambda x=x, m=mesh: sample_sort(
            x, mesh=m, eps=eps, ex_cfg=ExchangeConfig(out_slack=1.3)).shards)
        us_a = timeit(lambda x=x, m=mesh: ams_sort(
            x, mesh=m, eps=eps, ex_cfg=ExchangeConfig(out_slack=1.2)).shards)
        rows.append((f"fig4/hss_p{p}", round(us_h, 1),
                     f"keys/shard={n_per} (host shards share one core: "
                     "comm is free here, so multi-round HSS pays wall time "
                     "for the 933x comm saving sortcoll measures)"))
        rows.append((f"fig4/samplesort_p{p}", round(us_s, 1),
                     f"ratio_vs_hss={us_s / us_h:.2f}"))
        rows.append((f"fig4/ams_p{p}", round(us_a, 1),
                     f"ratio_vs_hss={us_a / us_h:.2f}"))
    return rows
