"""The arithmetic of the yardstick on known inputs."""
import collections

import numpy as np
import pytest

from benchmark import traffic_gen, yardstick


def test_quantile_and_median_on_known_inputs():
    xs = [5, 1, 4, 2, 3]
    assert yardstick.median(xs) == 3
    assert yardstick.median([1, 2, 3, 4]) == 2.5
    assert yardstick.quantile(xs, 0.0) == 1
    assert yardstick.quantile(xs, 1.0) == 5
    ys = list(range(1, 101))
    assert yardstick.quantile(ys, 0.95) == pytest.approx(
        np.percentile(ys, 95))
    assert yardstick.quantile([7.0], 0.95) == 7.0
    with pytest.raises(ValueError):
        yardstick.quantile([], 0.5)


def test_peaks_table_has_no_default():
    assert yardstick.peaks("TPU v5 lite")["flops_per_s"] == 197e12
    assert yardstick.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        yardstick.peaks("cpu")


def test_large_seeds_fold_into_32_signed_bits():
    assert 0 <= yardstick.fold_seed(2 ** 31 + 12345) < 2 ** 31
    assert yardstick.fold_seed(7) == 7


MIX = {"pool": 64, "clients": 4,
       "prompt_tokens": {"dist": "lognormal", "median": 128, "sigma": 0.9,
                         "min": 16, "max": 768},
       "output_tokens": {"dist": "lognormal", "median": 64, "sigma": 0.7,
                         "min": 8, "max": 256}}


def test_stratified_lengths_follow_the_distribution():
    n = traffic_gen.stratified_lengths(MIX["prompt_tokens"], 513)
    assert n.min() >= 16 and n.max() <= 768
    assert sorted(n)[256] == 128            # the median is the median
    assert list(n) == sorted(n)


def test_every_seed_sends_the_same_sizes_in_another_order():
    a = traffic_gen.RequestStream(MIX, 1, 1000)
    b = traffic_gen.RequestStream(MIX, 2 ** 31 + 99, 1000)
    ra = [a.next() for _ in range(64)]
    rb = [b.next() for _ in range(64)]
    sizes = lambda rs: collections.Counter(  # noqa: E731
        (len(p), o) for p, o in rs)
    assert sizes(ra) == sizes(rb)
    assert [len(p) for p, _ in ra] != [len(p) for p, _ in rb]
    assert all(1 <= t < 1000 for p, _ in ra for t in p)
    # the same seed gives the same inputs
    c = traffic_gen.RequestStream(MIX, 1, 1000)
    assert [c.next() for _ in range(64)] == ra


def test_training_batches_come_from_the_seed():
    mix = {"pool": 3, "batch": 2, "seq": 8}
    a = traffic_gen.token_batches(mix, 5, 100)
    assert a.shape == (3, 2, 9) and a.dtype == np.int32
    assert (a == traffic_gen.token_batches(mix, 5, 100)).all()
    assert (a != traffic_gen.token_batches(mix, 6, 100)).any()
