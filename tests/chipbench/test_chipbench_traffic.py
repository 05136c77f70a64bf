"""Each generator is a pure function of the run's seed."""
import numpy as np

import neubot_data as data

BIG = 2 ** 31 + 12345      # a run's seed may pass 32 signed bits


def _q2(seed):
    key = data.root_key(seed)
    return (np.stack([np.asarray(r) for r in data.history(
                key, things=8, n=2048, scale=20e6)]),
            np.asarray(data.ingest(key, slides=2, things=8, per=128,
                                   scale=20e6)))


def test_q2_records_repeat_for_a_seed_and_differ_between_seeds():
    a, b, c = _q2(BIG), _q2(BIG), _q2(BIG + 1)
    for x, y, z in zip(a, b, c):
        np.testing.assert_array_equal(x, y)
        assert not np.array_equal(x, z)
        assert (x >= 0).all() and np.isfinite(x).all()


def test_seeds_wider_than_32_bits_are_kept_apart():
    a, b = _q2(5), _q2(5 + 2 ** 32)
    assert not np.array_equal(a[0], b[0])
