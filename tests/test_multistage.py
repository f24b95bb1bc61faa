"""Two-stage HSS (paper Sections 5.3/6.1) on a 2-D host mesh."""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import two_stage_sort
from repro.parallel.compat import make_mesh


@pytest.mark.parametrize("shape", [(2, 4), (4, 2)])
def test_two_stage_exact(rng, shape):
    n = 8 * 2048
    x = rng.permutation(n).astype(np.int32)
    mesh = make_mesh(shape, ("outer", "inner"))
    out, counts, ovf = two_stage_sort(jnp.asarray(x), mesh)
    assert int(ovf) == 0
    shards = np.asarray(out).reshape(8, -1)
    counts = np.asarray(counts).reshape(-1)
    g = np.concatenate([shards[i, :counts[i]] for i in range(8)])
    np.testing.assert_array_equal(np.sort(g), np.sort(x))
    assert np.all(np.diff(g.astype(np.int64)) >= 0)
    assert np.all(counts <= (1 + 0.05) * n / 8 + 1)


def test_two_stage_stage1_locality(rng):
    """Stage-2 traffic stays within a group: group-level key ranges nest."""
    n = 8 * 1024
    x = rng.permutation(n).astype(np.int32)
    mesh = make_mesh((2, 4), ("outer", "inner"))
    out, counts, ovf = two_stage_sort(jnp.asarray(x), mesh)
    shards = np.asarray(out).reshape(2, 4, -1)
    counts = np.asarray(counts).reshape(2, 4)
    g0max = shards[0, 3, counts[0, 3] - 1]
    g1min = shards[1, 0, 0]
    assert g0max < g1min
