"""Public jit'd wrappers for the Pallas kernels.

Backend policy: on TPU the Pallas kernels compile natively; everywhere else
(this CPU container) they run under ``interpret=True``, which executes the
kernel body in Python per grid step — bit-faithful, slow.  Because interpret
mode is too slow for the big model graphs, the callers pass ``impl='auto'``,
which :func:`resolve_impl` turns into one implementation by a single static
rule:

  * ``'pallas'`` on a TPU backend for the kernels in :data:`PALLAS_ON_TPU`,
  * ``'ref'`` (the pure-jnp oracle, an XLA graph) for every other kernel
    and on every other backend — so smoke tests and the CPU dry-run use
    honest XLA HLO that ``cost_analysis()`` can account.

``probe_allocate`` is not in the set: its single-block kernel keeps an
``(m, num_sets)`` one-hot and an ``(m, m)`` rank matrix in VMEM; for a
4096-lane wavefront the rank matrix alone is 64 MiB, and the v5e compiler
refuses the kernel.  The compile rehearsals in
``tests/test_tpu_compile.py`` compile what this rule picks at deployment
shapes.  Nothing catches a compile error to swap implementations at run
time.

Tests pin ``impl='pallas', interpret=True`` and sweep shapes/dtypes against
``impl='ref'``.
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.cache_probe import cache_probe_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.gather_blocks import gather_blocks_pallas
from repro.kernels.paged_attention import paged_attention_pallas
from repro.kernels.probe_allocate import probe_allocate_pallas

Impl = Literal["auto", "pallas", "ref"]


# Kernels that run as Pallas under impl="auto" on a TPU backend.  The BaM
# hot-path entries compile for v5e at deployment shapes; the attention
# kernels serve the LM stack, outside the BaM request path.
PALLAS_ON_TPU = frozenset({"gather_blocks", "cache_probe",
                           "flash_attention", "paged_attention"})


def _platform() -> str:
    return jax.default_backend()


def _on_tpu() -> bool:
    return _platform() == "tpu"


def resolve_impl(kernel: str, impl: Impl = "auto",
                 platform: str | None = None) -> str:
    """The implementation ``kernel`` runs under ``impl`` on ``platform``
    (default: JAX's default backend) — see the module docstring's rule."""
    if impl != "auto":
        return impl
    platform = _platform() if platform is None else platform
    return "pallas" if platform == "tpu" and kernel in PALLAS_ON_TPU \
        else "ref"


def flash_attention(q, k, v, *, causal=True, window=None, scale=None,
                    block_q=256, block_kv=256, tile_f32: bool = True,
                    impl: Impl = "auto", interpret: bool | None = None):
    if resolve_impl("flash_attention", impl) == "ref":
        # blockwise XLA path once the score matrix would exceed ~16M elems
        # per (batch, head) — bounded memory for the 32k/500k cells.
        if q.shape[2] * k.shape[2] > (1 << 22):
            return _ref.flash_attention_xla(q, k, v, causal=causal,
                                            window=window, scale=scale,
                                            tile_f32=tile_f32)
        return _ref.flash_attention_ref(q, k, v, causal=causal,
                                        window=window, scale=scale)
    itp = (not _on_tpu()) if interpret is None else interpret
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, scale=scale,
        block_q=block_q, block_kv=block_kv, interpret=itp)


def paged_attention(q, k_pages, v_pages, page_table, seq_lens, *, scale=None,
                    impl: Impl = "auto", interpret: bool | None = None):
    if resolve_impl("paged_attention", impl) == "ref":
        return _ref.paged_attention_ref(q, k_pages, v_pages, page_table,
                                        seq_lens, scale=scale)
    itp = (not _on_tpu()) if interpret is None else interpret
    return paged_attention_pallas(q, k_pages, v_pages, page_table, seq_lens,
                                  scale=scale, interpret=itp)


def gather_blocks(data, slots, *, off=None, impl: Impl = "auto",
                  interpret: bool | None = None):
    """Paged line gather; with ``off``, an element gather ``data[slots,
    off]``.  The Pallas path always DMAs whole lines (the TPU moves
    line-granular anyway) and selects the element after; the ref/XLA path
    gathers just the elements.
    """
    if resolve_impl("gather_blocks", impl) == "ref":
        return _ref.gather_blocks_ref(data, slots, off=off)
    itp = (not _on_tpu()) if interpret is None else interpret
    lines = gather_blocks_pallas(data, slots, interpret=itp)
    if off is None:
        return lines
    return lines[jnp.arange(off.shape[0]), off]


def cache_probe(tags, keys, *, owner=None, tenant=0, block_m=512,
                impl: Impl = "auto", interpret: bool | None = None):
    if resolve_impl("cache_probe", impl) == "ref":
        return _ref.cache_probe_ref(tags, keys, owner=owner, tenant=tenant)
    itp = (not _on_tpu()) if interpret is None else interpret
    return cache_probe_pallas(tags, keys, owner=owner, tenant=tenant,
                              block_m=block_m, interpret=itp)


def sq_enqueue(sq_key, sq_dst, sq_is_write, sq_prio, sq_tenant, sq_ticket,
               sq_tail, sq_head, rr_ptr, dev_enqueued,
               keys, dst, is_write, prio, valid, *,
               seg_bounds, n_devices, stripe_blocks, tenant,
               failed_devices=(),
               impl: Impl = "auto", interpret: bool | None = None):
    """Fused multi-segment SQ enqueue (one scatter round per ring field)
    — see :func:`repro.kernels.ref.sq_enqueue_ref` for exact semantics.

    The op is scatter-bound with no matmul/reduction structure for a TPU
    kernel to exploit, so every backend (including ``impl="pallas"``) runs
    the jnp oracle as an XLA graph; the ``impl`` knob is accepted for
    dispatch-layer symmetry with the probe/gather ops.
    """
    del impl, interpret
    return _ref.sq_enqueue_ref(
        sq_key, sq_dst, sq_is_write, sq_prio, sq_tenant, sq_ticket,
        sq_tail, sq_head, rr_ptr, dev_enqueued,
        keys, dst, is_write, prio, valid,
        seg_bounds=seg_bounds, n_devices=n_devices,
        stripe_blocks=stripe_blocks, tenant=tenant,
        failed_devices=failed_devices)


def wfq_drain(sq_key, sq_is_write, sq_tenant, sq_ticket=None, *,
              n_devices, n_tenants, fault=None,
              impl: Impl = "auto", interpret: bool | None = None):
    """Closed-form drain accounting (no completion-stream sort) — see
    :func:`repro.kernels.ref.wfq_drain_ref`.  Reduction-only; all backends
    share the jnp oracle (same rationale as :func:`sq_enqueue`).
    """
    del impl, interpret
    return _ref.wfq_drain_ref(sq_key, sq_is_write, sq_tenant, sq_ticket,
                              n_devices=n_devices, n_tenants=n_tenants,
                              fault=fault)


def probe_allocate(tags, owner, refcount, dirty, speculative, clock_hand,
                   keys, *, valid=None, alloc_mask=None, protect_slots=None,
                   tenant=0, way_lo=0, way_hi=None, spec_insert=False,
                   protect_hits=True, impl: Impl = "auto",
                   interpret: bool | None = None):
    """Fused cache probe + clock-sweep victim select (the BaM submission
    hot path, one set-local pass).  Returns ``(hit, hit_slot, way, ok,
    evicted_key, evicted_dirty)`` — see
    :func:`repro.kernels.ref.probe_allocate_ref` for the exact semantics.
    """
    if valid is None:
        valid = keys >= 0
    if resolve_impl("probe_allocate", impl) == "ref":
        return _ref.probe_allocate_ref(
            tags, owner, refcount, dirty, speculative, clock_hand, keys,
            valid, alloc_mask, protect_slots, tenant=tenant, way_lo=way_lo,
            way_hi=way_hi, spec_insert=spec_insert,
            protect_hits=protect_hits)
    itp = (not _on_tpu()) if interpret is None else interpret
    return probe_allocate_pallas(
        tags, owner, refcount, dirty, speculative, clock_hand, keys, valid,
        alloc_mask, protect_slots, tenant=tenant, way_lo=way_lo,
        way_hi=way_hi, spec_insert=spec_insert, protect_hits=protect_hits,
        interpret=itp)
