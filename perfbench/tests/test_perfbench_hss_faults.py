"""A whole run of the direct-sort system on host devices: `correct` holds
for the program, and comes out false when the timed path is broken
underneath, once for each fault a sort across chips can have."""
import json

import jax.numpy as jnp
import pytest

import rehearsal
from harness import reference, traffic


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearsal.make_root(tmp_path_factory.mktemp("bench"))


def _blocks(x, p):
    return x.reshape(p, -1)


def _set_shards(out, rows, counts):
    p, cap = out.shards.shape
    pad = jnp.full((p, cap - rows.shape[1]), traffic.INT32_MAX, rows.dtype)
    out.shards = jnp.concatenate([rows, pad], axis=1)
    out.counts = jnp.asarray(counts, jnp.int32)


def _unchanged(out, x):            # the call hands back its input
    p = out.shards.shape[0]
    rows = _blocks(x, p)
    _set_shards(out, rows, [rows.shape[1]] * p)


def _half_left_out(out, x):        # half of the shards' keys dropped
    p = out.shards.shape[0]
    out.counts = out.counts.at[p // 2:].set(0)


def _no_exchange(out, x):          # each chip sorts only its own keys
    p = out.shards.shape[0]
    rows = jnp.sort(_blocks(x, p), axis=1)
    _set_shards(out, rows, [rows.shape[1]] * p)


def _altered(out, x):              # one key changed where it is produced
    out.shards = out.shards.at[0, 0].add(1)


FAULTS = {"state_unchanged": _unchanged, "half_left_out": _half_left_out,
          "exchange_left_out": _no_exchange, "answer_altered": _altered}


def test_program_runs_correct(root):
    result = rehearsal.run_cell(root, "t.hss")
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["checks"]["mismatched_keys"] == {"value": 0, "limit": 0}
    assert result["metrics"]["sort_keys_per_s"]["value"] > 0
    assert set(result["metrics"]) == {"sort_keys_per_s", "setup_s"}
    printed = json.loads(result["_stdout"].splitlines()[-1])
    assert list(printed)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(root, monkeypatch, fault):
    import repro.sort

    real = repro.sort.sort

    def broken(x, spec=None, **kw):
        out = real(x, spec, **kw)
        FAULTS[fault](out, x)
        return out

    monkeypatch.setattr(repro.sort, "sort", broken)
    result = rehearsal.run_cell(root, "t.hss", seed=4)
    assert result["correct"] is False
    assert result["checks"]["mismatched_keys"]["value"] > 0


def test_control_is_not_correct(root):
    result = rehearsal.run_cell(root, "t.hss", seed=5,
                                produce=reference.control_sort)
    assert result["correct"] is False
    assert result["checks"]["mismatched_keys"]["value"] > 0


def test_traced_run_reports_per_layer_metrics(root):
    result = rehearsal.run_cell(root, "t.hss", seed=6, trace=1)
    assert result["correct"] is True
    assert {"front_door_ms.sort", "device_idle.sort"} <= set(
        result["metrics"])
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    ops = result["breakdown"]["device_ops"]
    assert 1 <= len(ops) <= 10 and all(s > 0 for _, s in ops)
