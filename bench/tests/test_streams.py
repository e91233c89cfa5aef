"""The seeded generators: the same seed gives the same inputs."""
import numpy as np

from bench.lib import streams


def test_stream_is_a_function_of_its_seed():
    a = streams.stream(50, 20, 500, 172, seed=2**33 + 5)
    b = streams.stream(50, 20, 500, 172, seed=2**33 + 5)
    c = streams.stream(50, 20, 500, 172, seed=5)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], c[0])
    assert not np.array_equal(a[3], c[3])


def test_stream_shapes_ranges_and_order():
    src, dst, t, feat = streams.stream(50, 20, 500, 172, seed=7)
    assert src.dtype == dst.dtype == np.int32
    assert src.min() >= 0 and src.max() < 50
    assert dst.min() >= 50 and dst.max() < 70
    assert np.all(np.diff(t) > 0)
    assert feat.shape == (500, 172) and feat.dtype == np.float32
    # one column per event carries the user's preference signal
    assert np.all(feat.max(axis=1) > 0.85)


def test_stream_prefix_does_not_depend_on_length():
    a = streams.stream(50, 20, 300, 8, seed=11)
    b = streams.stream(50, 20, 500, 8, seed=11)
    for x, y in zip(a[:3], b[:3]):
        np.testing.assert_array_equal(x, y[:300])


def test_arrival_clocks_are_seeded():
    a = streams.poisson_arrival_clock(1000, 200.0, seed=3)
    np.testing.assert_array_equal(a, streams.poisson_arrival_clock(
        1000, 200.0, seed=3))
    assert np.all(np.diff(a) > 0)
    assert abs(a[-1] - 5.0) < 1.0             # 1,000 events at 200 per s
    order = streams.late_arrival_order(1000, 0.2, 5, seed=3)
    np.testing.assert_array_equal(order, streams.late_arrival_order(
        1000, 0.2, 5, seed=3))
    assert sorted(order) == list(range(1000))
    # no event arrives more than max_late positions after its slot
    pos = np.empty(1000, int)
    pos[order] = np.arange(1000)
    assert np.all(pos - np.arange(1000) <= 5)
