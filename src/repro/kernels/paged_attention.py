"""Paged decode attention Pallas-TPU kernel — the BaM-paged-KV hot path.

Decode attention where the KV cache is a *paged pool* (the BaM software
cache's data array): physical pages are gathered on demand through a page
table, exactly like BamArray lines.  The page table is a **scalar-prefetch**
operand so each grid step's ``BlockSpec`` index map points the next DMA at
the right physical page while the current page is being processed — the
Pallas analogue of BaM overlapping in-flight NVMe requests with compute.

Pool layout is per-sequence: ``(B, P_phys, page, Hkv, D)``; on the
production mesh the batch dim shards over ``data`` and the physical-page
dim stripes over ``model`` — the TPU mapping of BaM's blocks-round-robin-
over-SSDs.  ``page_table[b, i]`` gives the physical page (within sequence
b's pool row) backing logical page i; -1 marks a hole (spilled page).

Grid: ``(batch, kv_heads, num_logical_pages)`` with the page axis
sequential; online-softmax state (m, l, acc) for the ``group`` query heads
of this kv head sits in VMEM scratch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _paged_kernel(page_table_ref, seq_lens_ref,    # scalar prefetch
                  q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *,
                  scale: float, page_size: int, n_pages: int):
    b = pl.program_id(0)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)            # (G, d)
    k = k_ref[0, 0, :, 0].astype(jnp.float32)      # (page, d)
    v = v_ref[0, 0, :, 0].astype(jnp.float32)      # (page, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    # logical positions covered by this logical page index
    pos = i * page_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1)                     # (G, page)
    live = (pos < seq_lens_ref[b]) & (page_table_ref[b, i] >= 0)
    s = jnp.where(live, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_next = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next[:, :1])
    p = jnp.where(live, p, 0.0)
    l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_next

    @pl.when(i == n_pages - 1)
    def _finish():
        l = l_scr[...][:, :1]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_attention_pallas(
    q: jax.Array,             # (B, Hq, D) — one new token per sequence
    k_pages: jax.Array,       # (B, P_phys, page_size, Hkv, D) pool
    v_pages: jax.Array,       # (B, P_phys, page_size, Hkv, D)
    page_table: jax.Array,    # (B, n_pages) int32 physical page id, -1 hole
    seq_lens: jax.Array,      # (B,) int32 — tokens live in the cache
    *, scale: float | None = None, interpret: bool = False,
) -> jax.Array:
    """Flash-decoding over a BaM-paged KV pool. Returns (B, Hq, D)."""
    B, Hq, D = q.shape
    _, P, page_size, Hkv, _ = k_pages.shape
    n_pages = page_table.shape[1]
    assert Hq % Hkv == 0
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    qg = q.reshape(B, Hkv, G, D)
    kernel = functools.partial(_paged_kernel, scale=scale,
                               page_size=page_size, n_pages=n_pages)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, Hkv, n_pages),
        in_specs=[
            pl.BlockSpec((1, 1, G, D), lambda b, h, i, pt, sl: (b, h, 0, 0)),
            pl.BlockSpec(
                (1, 1, page_size, 1, D),
                lambda b, h, i, pt, sl:
                    (b, jnp.maximum(pt[b, i], 0), 0, h, 0)),
            pl.BlockSpec(
                (1, 1, page_size, 1, D),
                lambda b, h, i, pt, sl:
                    (b, jnp.maximum(pt[b, i], 0), 0, h, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, D),
                               lambda b, h, i, pt, sl: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, 128), jnp.float32),
            pltpu.VMEM((G, D), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, G, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(page_table, seq_lens, qg, k_pages, v_pages)
    return out.reshape(B, Hq, D)
