import jax.numpy as jnp
import numpy as np

import datagen
import values as V
from conftest import SEED


def test_device_tier_equals_host_formula(monkeypatch):
    monkeypatch.setattr(datagen, "CHUNK_BYTES", 4096)   # several chunks
    words = V.seed_words(SEED)
    tier = datagen.array_tier(5000, words)
    want = V.array_values(np.arange(5000, dtype=np.uint32), words, np)
    np.testing.assert_array_equal(tier.view(np.uint32), want.view(np.uint32))
    assert 0.45 < tier.mean() < 0.55 and tier.min() >= 0 and tier.max() < 1


def test_jnp_and_numpy_agree_at_the_top_of_the_range():
    words = V.seed_words(7)
    idx = np.arange((1 << 30) - 64, 1 << 30, dtype=np.uint32)
    a = V.array_values(idx, words, np)
    b = np.asarray(V.array_values(jnp.asarray(idx), words, jnp))
    np.testing.assert_array_equal(a.view(np.uint32), b.view(np.uint32))


def test_seeds_differ():
    assert V.seed_words(1) != V.seed_words(2)
    assert V.seed_words(2**31 + 1) == V.seed_words(2**31 + 1)
