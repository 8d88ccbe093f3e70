"""Small shared utilities: pytree dataclasses, hashing, padding helpers."""
from __future__ import annotations

import dataclasses
import os
import pathlib
from typing import Any, TypeVar

import jax
import jax.numpy as jnp

T = TypeVar("T")

# Sentinel for "no key / invalid slot" throughout the BaM core.
INVALID = jnp.int32(-1)
INVALID_I32 = -1


def pytree_dataclass(cls: type | None = None, *, meta_fields: tuple[str, ...] = ()):
    """Register a dataclass as a JAX pytree.

    ``meta_fields`` are static (hashable, not traced); everything else is a
    leaf/data field.
    """

    def wrap(c):
        c = dataclasses.dataclass(frozen=True)(c)
        data_fields = tuple(
            f.name for f in dataclasses.fields(c) if f.name not in meta_fields
        )
        jax.tree_util.register_dataclass(
            c, data_fields=data_fields, meta_fields=tuple(meta_fields)
        )
        return c

    if cls is None:
        return wrap
    return wrap(cls)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing is set here.  Otherwise the cache goes to the fixed
    ``<checkout>/.jax_cache`` (gitignored): the path is part of the cache
    key, so it never moves between runs.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(pathlib.Path(__file__).resolve().parents[2] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def replace(obj: T, **kwargs: Any) -> T:
    return dataclasses.replace(obj, **kwargs)


def mix_hash(key: jnp.ndarray) -> jnp.ndarray:
    """Cheap integer mixing (Knuth multiplicative) for cache set hashing.

    Works on int32; deliberately avoids 64-bit so it runs with x64 disabled.
    """
    k = key.astype(jnp.uint32)
    k = (k * jnp.uint32(2654435761)) & jnp.uint32(0xFFFFFFFF)
    k = k ^ (k >> 16)
    return k.astype(jnp.int32) & jnp.int32(0x7FFFFFFF)


def cdiv(a: int, b: int) -> int:
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    return cdiv(a, b) * b


def pad_to(x: jnp.ndarray, n: int, fill) -> jnp.ndarray:
    """Pad axis 0 of ``x`` to length ``n`` with ``fill``."""
    if x.shape[0] == n:
        return x
    pad_width = [(0, n - x.shape[0])] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad_width, constant_values=fill)


def tree_bytes(tree: Any) -> int:
    """Total bytes of all array leaves (ShapeDtypeStructs count too)."""
    leaves = jax.tree_util.tree_leaves(tree)
    total = 0
    for leaf in leaves:
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            n = 1
            for d in leaf.shape:
                n *= int(d)
            total += n * jnp.dtype(leaf.dtype).itemsize
    return total


def fold_rng(key: jax.Array, *ints: int) -> jax.Array:
    for i in ints:
        key = jax.random.fold_in(key, i)
    return key


@pytree_dataclass(meta_fields=("kind",))
class Tagged:
    """A pytree value tagged with a *static* kind string (e.g. cache
    entries: 'ring' vs 'paged' vs 'mlstm')."""

    kind: str
    value: Any


def segment_rank(ids: jnp.ndarray, valid: jnp.ndarray) -> jnp.ndarray:
    """Rank of each element among same-id elements (0-based); invalid -> 0.

    The deterministic prefix-sum replacement for 'threads racing on a shared
    counter' — used by the cache's per-set clock and the MoE expert queues.
    """
    m = ids.shape[0]
    big = jnp.int32(jnp.iinfo(jnp.int32).max)
    keyed = jnp.where(valid, ids, big)
    order = jnp.argsort(keyed, stable=True)
    ss = keyed[order]
    prev = jnp.concatenate([jnp.full((1,), -2, ss.dtype), ss[:-1]])
    start = ss != prev
    pos = jnp.arange(m, dtype=jnp.int32)
    start_pos = jax.lax.cummax(jnp.where(start, pos, 0))
    rank_sorted = pos - start_pos
    return jnp.zeros((m,), jnp.int32).at[order].set(rank_sorted)
