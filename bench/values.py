"""The data every cell serves, as a function of ``(seed, index)``.

One formula, written once over an array module ``xp``: ``datagen`` evaluates
it on the device to make the storage tier, and the numpy reference evaluates
it on the host to recompute what each served value must be.  Integer
arithmetic wraps at 32 bits in both, and the float is the top 24 bits of a
hash times 2**-24, so both sides agree bit for bit.
"""
from __future__ import annotations

import numpy as np

M1, M2 = 0x85EBCA6B, 0xC2B2AE35


def seed_words(seed: int) -> tuple[int, int, int, int]:
    """Four uint32 words drawn from ``seed`` (any whole number)."""
    w = np.random.SeedSequence(int(seed) % (1 << 64)).generate_state(4)
    return tuple(int(x) for x in w)


def fmix32(x, xp):
    """MurmurHash3's 32-bit finaliser (a bijection of uint32)."""
    x = x ^ (x >> 16)
    x = x * xp.uint32(M1)
    x = x ^ (x >> 13)
    x = x * xp.uint32(M2)
    return x ^ (x >> 16)


def unit_float(x, xp):
    """Top 24 bits of a uint32 as a float32 in [0, 1), exactly."""
    return (x >> 8).astype(xp.float32) * xp.float32(2.0 ** -24)


def array_values(idx, words, xp):
    """Element ``idx`` of the array tier (float32)."""
    k0, k1 = xp.uint32(words[0]), xp.uint32(words[1])
    x = fmix32(idx.astype(xp.uint32) ^ k0, xp)
    return unit_float(fmix32(x + k1, xp), xp)
