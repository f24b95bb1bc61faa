"""A whole run of the sort-service system on one host device: `correct`
holds for the program, and comes out false when the served answers are
broken underneath, once for each fault a one-chip service can have."""
import numpy as np
import pytest

import rehearsal
from harness import reference


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return rehearsal.make_root(tmp_path_factory.mktemp("bench"))


def _unchanged(results, reqs):      # each request answered with itself
    return [np.array(r.x) for r in reqs]


def _half_left_out(results, reqs):  # the second half of a batch unserved
    keep = len(reqs) // 2
    return results[:keep] + [np.zeros_like(r) for r in results[keep:]]


def _altered(results, reqs):        # one key changed where it is produced
    first = np.array(results[0])
    first[0] ^= 1
    return [first] + results[1:]


FAULTS = {"state_unchanged": _unchanged, "half_left_out": _half_left_out,
          "answer_altered": _altered}


def test_program_runs_correct(root):
    result = rehearsal.run_cell(root, "t.serve")
    assert result["correct"] is True
    assert result["attempted"] == 60 and result["failed"] == 0
    assert result["checks"]["unanswered"] == {"value": 0, "limit": 0}
    p50 = result["metrics"]["serve_p50_ms"]["value"]
    assert 0 < p50 <= result["metrics"]["serve_p95_ms"]["value"]
    assert "generator lateness ms" in result["_stdout"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_served_answers_are_not_correct(root, monkeypatch, fault):
    from repro.serve.service import SortService

    real = SortService._run_batch

    def broken(self, reqs):
        results, delta, bad = real(self, reqs)
        return FAULTS[fault](results, reqs), delta, bad

    monkeypatch.setattr(SortService, "_run_batch", broken)
    result = rehearsal.run_cell(root, "t.serve", seed=4)
    assert result["correct"] is False
    assert result["checks"]["mismatched_keys"]["value"] > 0


def test_control_is_not_correct(root):
    result = rehearsal.run_cell(root, "t.serve", seed=5,
                                produce=reference.control_sort)
    assert result["correct"] is False
    assert result["checks"]["mismatched_keys"]["value"] > 0


def test_traced_run_reports_per_layer_metrics(root):
    result = rehearsal.run_cell(root, "t.serve", seed=6, trace=1)
    assert result["correct"] is True
    assert {"queue_wait_ms.serve", "device_idle.serve"} <= set(
        result["metrics"])
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
