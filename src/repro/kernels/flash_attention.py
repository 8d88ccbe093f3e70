"""Blockwise (flash) attention Pallas-TPU kernel — prefill hot path.

VMEM tiling: the grid is ``(batch, q_heads, q_blocks, kv_blocks)`` with the
last axis sequential ("arbitrary") so the online-softmax running state
(m, l, acc) lives in VMEM scratch across kv blocks.  GQA is folded into the
``BlockSpec`` index maps: the kv index map divides the query-head index by
the group size, so no repeated/materialised KV.

Supports causal masking and a sliding window (`window` kv positions behind
the query) — the gemma3/hymba local-attention pattern.  Masked kv blocks are
still visited but contribute -inf scores; the block-skip optimisation is
recorded as a §Perf candidate.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: int | None,
                  bq: int, bkv: int, nkv: int, kv_len: int):
    ikv = pl.program_id(3)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)          # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)          # (bkv, d)
    v = v_ref[0, 0].astype(jnp.float32)          # (bkv, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    iq = pl.program_id(2)
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kv_pos = ikv * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = kv_pos < kv_len
    if causal:
        mask &= kv_pos <= q_pos
    if window is not None:
        mask &= kv_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                          # (bq, 128) lanes replicated
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)   # (bq, 1)
    m_next = jnp.maximum(m_prev, m_cur)          # (bq, 128)
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next[:, :1])               # (bq, bkv)
    # fully-masked rows: keep p at exactly 0 (exp(NEG_INF - NEG_INF) = 1 trap)
    p = jnp.where(mask, p, 0.0)
    l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_next

    @pl.when(ikv == nkv - 1)
    def _finish():
        l = l_scr[...][:, :1]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _flash_kernel_dyn(meta_ref, q_ref, k_ref, v_ref, o_ref,
                      m_scr, l_scr, acc_scr, *,
                      scale: float, causal: bool,
                      bq: int, bkv: int, nkv: int, kv_len: int):
    """Variant with a *traced* sliding window (meta_ref[0]).

    Used when the window size is a scanned per-layer value (gemma3's 5:1
    local:global interleave inside one scan-over-layers); a window >= kv_len
    means global attention.  Scalar-prefetched so it is resident before the
    first tile arrives.
    """
    ikv = pl.program_id(3)

    @pl.when(ikv == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    iq = pl.program_id(2)
    q_pos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
    kv_pos = ikv * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
    mask = kv_pos < kv_len
    if causal:
        mask &= kv_pos <= q_pos
    mask &= kv_pos > q_pos - meta_ref[0]
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    l_prev = l_scr[...]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_next = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next[:, :1])
    p = jnp.where(mask, p, 0.0)
    l_scr[...] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha[:, :1] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_next

    @pl.when(ikv == nkv - 1)
    def _finish():
        l = l_scr[...][:, :1]
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, window: int | jax.Array | None = None,
    scale: float | None = None,
    block_q: int = 256, block_kv: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, Hq, Sq, D); k, v: (B, Hkv, Skv, D) with Hq % Hkv == 0.

    ``window`` may be a traced int32 scalar (per-layer scanned value)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    assert Hq % Hkv == 0, (Hq, Hkv)
    group = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)

    bq = min(block_q, Sq)
    bkv = min(block_kv, Skv)
    pq = (-Sq) % bq
    pkv = (-Skv) % bkv
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0))) if pq else q
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pkv), (0, 0))) if pkv else k
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pkv), (0, 0))) if pkv else v
    nq = qp.shape[2] // bq
    nkv = kp.shape[2] // bkv

    dynamic_window = window is not None and not isinstance(window, int)
    scratch = [
        pltpu.VMEM((bq, 128), jnp.float32),
        pltpu.VMEM((bq, 128), jnp.float32),
        pltpu.VMEM((bq, D), jnp.float32),
    ]
    if dynamic_window:
        kernel = functools.partial(
            _flash_kernel_dyn, scale=scale, causal=causal,
            bq=bq, bkv=bkv, nkv=nkv, kv_len=Skv)
        meta = jnp.asarray(window, jnp.int32).reshape(1)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hq, nq, nkv),
            in_specs=[
                pl.BlockSpec((1, 1, bq, D),
                             lambda b, h, i, j, m: (b, h, i, 0)),
                pl.BlockSpec((1, 1, bkv, D),
                             lambda b, h, i, j, m, g=group: (b, h // g, j, 0)),
                pl.BlockSpec((1, 1, bkv, D),
                             lambda b, h, i, j, m, g=group: (b, h // g, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, bq, D),
                                   lambda b, h, i, j, m: (b, h, i, 0)),
            scratch_shapes=scratch,
        )
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "parallel",
                                     "arbitrary")),
            interpret=interpret,
        )(meta, qp, kp, vp)
        return out[:, :, :Sq, :]

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bkv=bkv, nkv=nkv, kv_len=Skv)

    grid = (B, Hq, nq, nkv)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bkv, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bkv, D),
                         lambda b, h, i, j, g=group: (b, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qp, kp, vp)
    return out[:, :, :Sq, :]
