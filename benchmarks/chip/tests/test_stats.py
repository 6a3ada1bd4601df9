import stats


def test_percentile_by_hand():
    assert stats.percentile([], 50) is None
    assert stats.percentile([4.0], 95) == 4.0
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile(list(range(1, 101)), 95) == 95.05
    assert stats.percentile([10, 0, 5], 100) == 10


def test_events_group_bursts():
    burst = [1.0 + i * 1e-5 for i in range(10)]
    ev = stats.events(burst + [3.0, 3.0001] + [5.0])
    assert [e[2] for e in ev] == [10, 2, 1]


def test_between_events_rate_ignores_window_edges():
    # a burst of 16 tokens every 2 s; the window cuts nothing in two
    times = [t + i * 1e-5 for t in (1.0, 3.0, 5.0, 7.0) for i in range(16)]
    rate = stats.between_events_rate(times, 0.0, 8.0)
    assert abs(rate - 48 / (7.00015 - 1.00015)) < 1e-6
    # the event outside the window does not count
    assert abs(stats.between_events_rate(times, 0.0, 6.0) - 32 / 4.0) < 1e-6
    assert stats.between_events_rate(times[:16], 0.0, 8.0) is None


def test_union_seconds():
    assert stats.union_seconds([(0, 1), (0.5, 2), (3, 4), (3.2, 3.5)]) == 3.0
    assert stats.union_seconds([]) == 0.0
