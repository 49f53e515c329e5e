"""The benchmark's traffic: a pure function of (seed, step), the same
shapes for every seed, rows that differ from step to step."""
import numpy as np
import pytest

from bench import data, reflib


@pytest.mark.parametrize("seed", [0, 2**31 + 7, 3_000_000_019])
def test_batches_repeat_per_seed(seed):
    a = data.SyntheticLM(512, 64, 4, seed).batch(3)
    b = data.SyntheticLM(512, 64, 4, seed).batch(3)
    for k in ("tokens", "labels"):
        assert a[k].dtype == np.int32 and a[k].shape == (4, 64)
        np.testing.assert_array_equal(a[k], b[k])
    np.testing.assert_array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert a["tokens"].min() >= 0 and a["tokens"].max() < 512


def test_steps_and_seeds_differ():
    g = data.SyntheticLM(512, 64, 4, 11)
    assert not np.array_equal(g.batch(0)["tokens"], g.batch(1)["tokens"])
    h = data.SyntheticLM(512, 64, 4, 12)
    assert not np.array_equal(g.batch(0)["tokens"], h.batch(0)["tokens"])


def test_seed_key_uses_all_bits():
    k1 = np.asarray(reflib.seed_key(5))
    k2 = np.asarray(reflib.seed_key(5 + 2**32))
    assert not np.array_equal(k1, k2)
    np.testing.assert_array_equal(k1, np.asarray(reflib.seed_key(5)))
