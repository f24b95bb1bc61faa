"""The benchmark's arithmetic against hand-worked cases: percentiles,
spreads, roofline shares, the traffic generator and the comparison."""
import math

import numpy as np
import pytest

import rehearsal  # noqa: F401  (puts the harness on sys.path)
from harness import reference, roofline, stats, traffic


@pytest.mark.parametrize("q, want", [(0.50, 3), (0.95, 5), (0.0, 1),
                                     (1.0, 5), (0.2, 1), (0.3, 2)])
def test_nearest_rank_percentile(q, want):
    assert stats.percentile([5, 1, 4, 2, 3], q) == want


def test_percentile_counts_failures_as_infinitely_late():
    lat = [10.0] * 19 + [math.inf]
    assert stats.percentile(lat, 0.95) == 10.0
    assert stats.percentile(lat + [math.inf], 0.95) == math.inf


def test_spread_is_quartile_distance_over_median():
    # statistics.quantiles (exclusive) at positions 7k/4: 1.75, 3.5, 5.25
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)
    assert stats.spread([10, 10, 10, 11]) == pytest.approx(0.75 / 10)


def test_roofline_share():
    peaks = {"hbm_bytes_per_s": 819e9}
    # 2^27 int32 keys: 1 GiB moved, 1.3108 ms at 819 GB/s
    assert roofline.sort_bytes(2 ** 27) == 2 ** 30
    least = roofline.least_time_s(2 ** 30, peaks)
    assert least == pytest.approx(2 ** 30 / 819e9)
    assert roofline.share_pct(2 ** 30, 10 * least, peaks) == pytest.approx(10)
    assert roofline.share_pct(2 ** 30, 0.0, peaks) is None


def test_apportion_sums_and_follows_weights():
    assert traffic.apportion(15, [8, 4, 2, 1]).tolist() == [8, 4, 2, 1]
    assert traffic.apportion(10, [8, 4, 2, 1]).tolist() == [5, 3, 1, 1]
    assert traffic.apportion(7, [2, 1]).sum() == 7


MIX = {"arrivals": "poisson", "rate_per_s": 50, "sizes": [16, 64, 256],
       "weights": [4, 2, 1], "pool_keys": 1024, "check_requests": 10}
UNIFORM = {"distribution": "uniform"}


def test_schedule_repeats_for_a_seed_and_differs_across_seeds():
    a = traffic.open_schedule(2 ** 31 + 11, MIX, 4.0)
    b = traffic.open_schedule(2 ** 31 + 11, MIX, 4.0)
    c = traffic.open_schedule(2 ** 31 + 12, MIX, 4.0)
    for k in a:
        assert np.array_equal(a[k], b[k])
    assert not np.array_equal(a["due_s"], c["due_s"])
    assert not np.array_equal(a["offset"], c["offset"])


def test_schedule_offers_the_same_work_to_every_seed():
    a = traffic.open_schedule(1, MIX, 4.0)
    c = traffic.open_schedule(2, MIX, 4.0)
    assert len(a["size"]) == 200
    assert sorted(a["size"]) == sorted(c["size"])
    # one fixed set of gaps, reordered: all but the last gap are shared
    da, dc = (np.round(np.diff(s["due_s"]), 9) for s in (a, c))
    assert len(np.intersect1d(da, dc)) >= len(da) - 1
    assert a["due_s"][-1] < 4.0
    assert np.all(a["offset"] + a["size"] <= MIX["pool_keys"])
    assert a["check"].sum() >= 10
    assert a["check"][a["size"] == 256].any()


@pytest.mark.parametrize("field, name", [("distribution", "no_such_keys"),
                                         ("arrivals", "no_such_arrivals")])
def test_a_mix_names_a_part_that_must_exist(field, name):
    with pytest.raises(FileNotFoundError, match=name):
        if field == "arrivals":
            traffic.open_schedule(1, dict(MIX, arrivals=name), 1.0)
        else:
            traffic.device_keys(1, 0, 16, {"distribution": name})


def test_checked_calls_repeat_for_a_seed():
    mix = {"check_calls": 2, "check_among_first": 4}
    assert traffic.checked_calls(7, mix) == traffic.checked_calls(7, mix)
    picks = {tuple(traffic.checked_calls(s, mix)) for s in range(20)}
    assert len(picks) > 1
    assert all(len(set(p)) == 2 and max(p) < 4 for p in picks)


def test_device_keys_repeat_for_a_seed_and_stay_below_the_sentinel():
    a = np.asarray(traffic.device_keys(2 ** 33 + 5, 0, 4096, UNIFORM))
    b = np.asarray(traffic.device_keys(2 ** 33 + 5, 0, 4096, UNIFORM))
    c = np.asarray(traffic.device_keys(5, 0, 4096, UNIFORM))
    d = np.asarray(traffic.device_keys(2 ** 33 + 5, 1, 4096, UNIFORM))
    assert a.dtype == np.int32 and np.array_equal(a, b)
    assert not np.array_equal(a, c) and not np.array_equal(a, d)
    assert a.max() < traffic.INT32_MAX and a.min() < 0 < a.max()


def test_mismatched_keys():
    want = np.arange(10)
    assert reference.mismatched_keys(want, want) == 0
    assert reference.mismatched_keys(want[::-1], want) == 10
    assert reference.mismatched_keys(want[:7], want) == 3
    got = want.copy()
    got[[2, 3]] = got[[3, 2]]
    assert reference.mismatched_keys(got, want) == 2


def test_control_fails_the_comparison_at_a_size_a_test_can_hold():
    """The control (a sort by the keys' top 24 bits) misorders keys that
    share those bits, so the exact comparison fails it."""
    keys = np.asarray(traffic.device_keys(9, 0, 1 << 16, UNIFORM))
    want = reference.reference_sort(keys)
    got = reference.control_sort(keys)
    assert np.array_equal(np.sort(got), want)   # every key, misordered
    assert reference.mismatched_keys(got, want) > 0


def test_comparison_is_correct_only_within_every_limit():
    cmp = reference.Comparison()
    assert not cmp.correct
    cmp.add("mismatched_keys", 0, 0)
    assert cmp.correct
    cmp.add("unanswered", 1, 0)
    assert not cmp.correct
    assert cmp.as_dict()["unanswered"] == {"value": 1, "limit": 0}
