"""The references' arithmetic at a tiny size, on the CPU."""
import numpy as np
import pytest

import neubot_data as data
from reference import q2_mean

N, PER, THINGS = 4096, 256, 8


def _data(seed):
    key = data.root_key(seed)
    hist = np.stack([np.asarray(r) for r in data.history(
        key, things=THINGS, n=N, scale=20e6)])
    new = np.asarray(data.ingest(key, slides=3, things=THINGS, per=PER,
                                 scale=20e6))
    return key, hist, new


def test_ring_means_match_a_plain_mean_of_the_ring():
    key, hist, new = _data(5)
    slide, thing = np.array([0, 2, 1]), np.array([3, 0, 7])
    got = q2_mean.ring_means(key, new, slide, thing, n=N, scale=20e6)
    for j, (k, i) in enumerate(zip(slide, thing)):
        ring = hist[i].astype(np.float64).copy()
        ring[:(k + 1) * PER] = new[:k + 1, i].reshape(-1)
        assert got[j] == pytest.approx(ring.mean(), rel=1e-12)


@pytest.mark.parametrize("slides", [1, 3, N // PER])
def test_thing_means_match_every_window_of_a_thing(slides):
    rng = np.random.default_rng(slides)
    row0 = rng.random(N, dtype=np.float32) * 20e6
    new_i = rng.random((slides, PER), dtype=np.float32) * 20e6
    got = q2_mean.thing_means(row0, new_i)
    for k in range(slides):
        ring = row0.astype(np.float64)
        ring[:(k + 1) * PER] = new_i[:k + 1].reshape(-1)
        assert got[k] == pytest.approx(ring.mean(), rel=1e-12)


def test_thing_means_refuse_a_wrapped_ring():
    with pytest.raises(ValueError):
        q2_mean.thing_means(np.ones(N, np.float32),
                            np.ones((N // PER + 1, PER), np.float32))


def test_history_rows_are_made_again_exactly():
    key, hist, _ = _data(2 ** 33 + 1)
    row = np.asarray(data.history_row(key, np.int32(5), n=N, scale=20e6))
    np.testing.assert_array_equal(row, hist[5])
    assert not np.array_equal(hist[4], hist[5])


def test_q2_control_reads_far_from_the_reference():
    key, _, new = _data(9)
    slide, thing = np.array([0, 1, 2, 2]), np.array([1, 2, 3, 4])
    ref = q2_mean.ring_means(key, new, slide, thing, n=N, scale=20e6)
    ctl = q2_mean.control_means(key, new, slide, thing, n=N, scale=20e6)
    assert (np.abs(ctl - ref) / ref).max() > 1e-4
