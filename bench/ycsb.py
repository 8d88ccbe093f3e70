"""YCSB's key space and request distribution, and the records a key holds.

YCSB (Cooper et al., SoCC 2010), ``site.ycsb.Utils`` and ``site.ycsb.
generator``: with ``insertorder=hashed`` the record of key number ``n`` is
stored under ``fnvhash64(n)``; ``requestdistribution=zipfian`` draws key
numbers with the ``ScrambledZipfianGenerator``: a zipfian rank over an item
space of 10**10 at theta 0.99 (with the precomputed ``ZETAN``), hashed with
``fnvhash64`` into the records; ``requestdistribution=uniform`` draws them
with the ``UniformLongGenerator``.  A cell's configuration states which,
with the generator's constants (:func:`request_keynums`).

Here a key is ``fnvhash64(n) mod 2**31`` as int32 (YCSB's key is the string
``"user" + fnvhash64(n)``), and the uniform variate that YCSB draws per
request is ``u = id * 2**-53`` of an item id drawn uniformly from
``[0, 2**53)`` by the traffic generator.  All arithmetic is numpy on the
host, in 64-bit integers that wrap as Java's ``long`` does.
"""
from __future__ import annotations

import numpy as np

import values as V

FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211

N_ITEMS = 1 << 53                 # item ids the traffic draws (u = id / N_ITEMS)
KEY_MASK = (1 << 31) - 1


def fnvhash64(v) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` of int64 values: FNV-1a over the eight
    low-first bytes, then Java's ``Math.abs``.  Returned as uint64 (the
    absolute value; ``Long.MIN_VALUE`` stays ``2**63``)."""
    v = np.asarray(v, np.int64).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, np.uint64)
    prime = np.uint64(FNV_PRIME_64)
    with np.errstate(over="ignore"):
        for i in range(8):
            h = (h ^ ((v >> np.uint64(8 * i)) & np.uint64(0xFF))) * prime
        neg = (h >> np.uint64(63)) == 1
        return np.where(neg, ~h + np.uint64(1), h)


def record_key(keynum) -> np.ndarray:
    """The int32 key of key number ``keynum`` (``insertorder=hashed``)."""
    return (fnvhash64(keynum) & np.uint64(KEY_MASK)).astype(np.int32)


def zipfian_rank(u: np.ndarray, theta: float, item_count: int,
                 zetan: float) -> np.ndarray:
    """``ZipfianGenerator.nextLong(items)`` for uniform variates ``u`` in
    [0, 1): ``items = item_count + 1`` (the generator spans ``[0,
    item_count]``), with YCSB's precomputed ``zetan`` for that span."""
    items = float(item_count + 1)
    zeta2 = 1.0 + 0.5 ** theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    tail = (items * np.power(eta * u - eta + 1.0, alpha)).astype(np.int64)
    return np.where(uz < 1.0, 0,
                    np.where(uz < 1.0 + 0.5 ** theta, 1, tail))


def _variate(ids) -> np.ndarray:
    """YCSB's uniform variate of each item id: ``u = id * 2**-53``."""
    return np.asarray(ids, np.int64).astype(np.float64) * 2.0 ** -53


def scrambled_keynum(ids, record_count: int, theta: float,
                     item_count: int, zetan: float) -> np.ndarray:
    """``ScrambledZipfianGenerator.nextValue()`` for item ids in ``[0,
    N_ITEMS)``: the key number ``fnvhash64(rank) % record_count``."""
    rank = zipfian_rank(_variate(ids), theta, item_count, zetan)
    return (fnvhash64(rank) % np.uint64(record_count)).astype(np.int64)


def uniform_keynum(ids, record_count: int) -> np.ndarray:
    """``UniformLongGenerator(0, record_count - 1)``: ``(long)(u *
    record_count)``."""
    return np.floor(_variate(ids) * record_count).astype(np.int64)


def check_workload(cfg: dict) -> None:
    """Raise unless ``cfg`` states a workload this module generates: reads
    only, ``insertorder=hashed``, and a request distribution of
    :func:`request_keynums`."""
    if float(cfg["read_proportion"]) != 1.0:
        raise ValueError(f"read_proportion {cfg['read_proportion']}: only "
                         "read-only workloads are generated")
    if cfg["insert_order"] != "hashed":
        raise ValueError(f"insert_order {cfg['insert_order']!r}: only "
                         "'hashed' is generated")
    if cfg["request_distribution"] not in ("scrambled_zipfian", "uniform"):
        raise ValueError(f"request_distribution "
                         f"{cfg['request_distribution']!r} is not generated")


def request_keynums(ids, cfg: dict) -> np.ndarray:
    """The key numbers that item ids request under ``cfg``'s
    ``request_distribution``: ``scrambled_zipfian`` (over
    ``zipfian_item_count`` at ``zipfian_constant``, with ``zetan``) or
    ``uniform``, each over ``record_count`` records."""
    check_workload(cfg)
    records = int(cfg["record_count"])
    if cfg["request_distribution"] == "uniform":
        return uniform_keynum(ids, records)
    return scrambled_keynum(ids, records, float(cfg["zipfian_constant"]),
                            int(cfg["zipfian_item_count"]),
                            float(cfg["zetan"]))


def request_keys(ids, cfg: dict) -> np.ndarray:
    """The int32 keys that item ids request under ``cfg``."""
    return record_key(request_keynums(ids, cfg))


def record_values(key, col, words, xp):
    """Column ``col`` of the record stored under ``key`` (float32), under
    the seed's ``words``: YCSB's ten 100-byte fields as 256 float32."""
    k2, k3 = xp.uint32(words[2]), xp.uint32(words[3])
    x = V.fmix32(key.astype(xp.uint32) ^ k2, xp)
    x = V.fmix32((x + col.astype(xp.uint32) * xp.uint32(V.M1)) ^ k3, xp)
    return V.unit_float(x, xp)
