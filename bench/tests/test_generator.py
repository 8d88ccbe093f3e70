import json

import numpy as np
import pytest

import generator
import run


def mixes():
    return sorted(p.stem for p in (run.BENCH / "traffic").glob("*.json"))


def load(mix):
    return json.loads((run.BENCH / "traffic" / f"{mix}.json").read_text())


@pytest.mark.parametrize("mix", mixes())
def test_same_seed_same_tokens(mix):
    a = generator.Traffic(load(mix), 2**33 + 7, 1 << 30)
    b = generator.Traffic(load(mix), 2**33 + 7, 1 << 30)
    c = generator.Traffic(load(mix), 2**33 + 8, 1 << 30)
    for k in (0, 1, 57):
        np.testing.assert_array_equal(a.token(k), b.token(k))
        assert a.token(k).shape == (a.lanes,)
        assert a.token(k).min() >= 0 and a.token(k).max() < 1 << 30
    assert not np.array_equal(a.token(3), c.token(3))


def test_uniform_covers_the_tier():
    t = generator.Traffic(load("array_rand"), 5, 1 << 30)
    ids = np.concatenate([t.token(k) for k in range(64)])
    assert len(np.unique(ids)) > 0.99 * ids.size
    assert 0.45 < ids.mean() / (1 << 30) < 0.55


def test_unknown_pick_is_an_error():
    with pytest.raises(ValueError):
        generator.Traffic(dict(load("array_rand"), pick="zipfian"), 1, 16)
