import numpy as np
import pytest

import gen


@pytest.mark.parametrize("vocab", [100, 10_000, 10_000_000])
def test_zipf_law_is_truncated_and_renormalised(vocab):
    ids = gen.zipf_ids(gen.rng(2**40 + 7, 0), 400_000, vocab, 1.1)
    assert ids.dtype == np.int32
    assert ids.min() >= 0 and ids.max() < vocab
    ks = np.unique(np.geomspace(1, vocab, 12).astype(np.int64) - 1)
    emp = np.searchsorted(np.sort(ids), ks, side="right") / ids.size
    np.testing.assert_allclose(emp, gen.zipf_cdf(ks, vocab, 1.1), atol=4e-3)
    assert gen.zipf_cdf(vocab - 1, vocab, 1.1) == pytest.approx(1.0)
    # no clipped overflow: the last id holds only its own share
    last = np.mean(ids == vocab - 1)
    want = 1.0 - gen.zipf_cdf(vocab - 2, vocab, 1.1)
    assert last < want + 4e-3


def test_zipf_rejects_exponent_at_most_one():
    with pytest.raises(ValueError):
        gen.zipf_ids(gen.rng(0, 0), 4, 10, 1.0)


def test_arrivals_same_gaps_for_every_seed():
    a = gen.arrivals(500.0, 10.0, gen.rng(1, 2))
    b = gen.arrivals(500.0, 10.0, gen.rng(2**33, 2))
    assert len(a) == len(b) == 5000
    assert a[-1] == pytest.approx(10.0) and b[-1] == pytest.approx(10.0)
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0.0)),
                               np.sort(np.diff(b, prepend=0.0)))
    assert not np.allclose(a, b)
    assert np.all(np.diff(a) > 0)


def test_requests_and_teacher_follow_the_seed():
    vocabs = [1000, 100]
    x = gen.request_ids(gen.rng(5, 1), 3, 64, vocabs, 1.1)
    y = gen.request_ids(gen.rng(5, 1), 3, 64, vocabs, 1.1)
    assert x.shape == (3, 64, 2) and np.array_equal(x, y)
    assert x[..., 1].max() < 100
    t = gen.Teacher(vocabs, gen.rng(5, 3))
    lab = t.labels(x[0], gen.rng(5, 4))
    assert set(np.unique(lab)) <= {0.0, 1.0}
    assert np.array_equal(lab, gen.Teacher(vocabs, gen.rng(5, 3)).labels(
        x[0], gen.rng(5, 4)))
