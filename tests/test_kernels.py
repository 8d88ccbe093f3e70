"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _mk(rng, shape, dtype):
    x = rng.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


TOL = {jnp.float32: dict(atol=3e-5, rtol=3e-5),
       jnp.bfloat16: dict(atol=3e-2, rtol=3e-2)}


@pytest.mark.parametrize("B,Hq,Hkv,Sq,Skv,D", [
    (1, 1, 1, 32, 32, 16),
    (2, 4, 2, 96, 96, 64),
    (1, 8, 1, 64, 64, 32),     # MQA
    (2, 3, 3, 33, 65, 16),     # ragged, no GQA
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 16)])
def test_flash_attention_sweep(B, Hq, Hkv, Sq, Skv, D, dtype, causal,
                               window):
    if causal and Sq != Skv:
        pytest.skip("causal needs square")
    rng = np.random.default_rng(0)
    q = _mk(rng, (B, Hq, Sq, D), dtype)
    k = _mk(rng, (B, Hkv, Skv, D), dtype)
    v = _mk(rng, (B, Hkv, Skv, D), dtype)
    o_pal = ops.flash_attention(q, k, v, causal=causal, window=window,
                                impl="pallas", interpret=True,
                                block_q=32, block_kv=32)
    o_ref = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o_pal, np.float32),
                               np.asarray(o_ref, np.float32), **TOL[dtype])
    # the blockwise XLA path must agree too
    o_xla = ref.flash_attention_xla(q, k, v, causal=causal, window=window,
                                    block_q=32, block_kv=32)
    np.testing.assert_allclose(np.asarray(o_xla, np.float32),
                               np.asarray(o_ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("B,Hq,Hkv,D,P,page,NP", [
    (2, 4, 2, 32, 8, 8, 6),
    (1, 8, 8, 16, 4, 16, 4),
    (3, 5, 5, 64, 6, 8, 5),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(B, Hq, Hkv, D, P, page, NP, dtype):
    rng = np.random.default_rng(1)
    q = _mk(rng, (B, Hq, D), dtype)
    kp = _mk(rng, (B, P, page, Hkv, D), dtype)
    vp = _mk(rng, (B, P, page, Hkv, D), dtype)
    pt = jnp.stack([jnp.asarray(rng.permutation(P)[:NP], jnp.int32)
                    for _ in range(B)])
    pt = pt.at[0, NP - 1].set(-1)                  # a hole
    sl = jnp.asarray(rng.integers(1, NP * page, B), jnp.int32)
    o_pal = ops.paged_attention(q, kp, vp, pt, sl, impl="pallas",
                                interpret=True)
    o_ref = ref.paged_attention_ref(q, kp, vp, pt, sl)
    np.testing.assert_allclose(np.asarray(o_pal, np.float32),
                               np.asarray(o_ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("n,lines,elems", [(7, 16, 32), (64, 8, 128),
                                           (1, 4, 8), (9, 13, 128),
                                           (40, 21, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_gather_blocks_sweep(n, lines, elems, dtype):
    rng = np.random.default_rng(2)
    data = _mk(rng, (lines, elems), dtype) if dtype != jnp.int32 else \
        jnp.asarray(rng.integers(0, 100, (lines, elems)), jnp.int32)
    slots = jnp.asarray(rng.integers(-1, lines, n), jnp.int32)
    o_pal = ops.gather_blocks(data, slots, impl="pallas", interpret=True)
    o_ref = ref.gather_blocks_ref(data, slots)
    np.testing.assert_array_equal(np.asarray(o_pal), np.asarray(o_ref))


@pytest.mark.parametrize("n,lines,elems", [(7, 16, 32), (64, 8, 128)])
def test_gather_blocks_element_mode(n, lines, elems):
    """`off=` gathers single elements; pallas(line-DMA + select) == ref."""
    rng = np.random.default_rng(6)
    data = _mk(rng, (lines, elems), jnp.float32)
    slots = jnp.asarray(rng.integers(-1, lines, n), jnp.int32)
    off = jnp.asarray(rng.integers(0, elems, n), jnp.int32)
    o_pal = ops.gather_blocks(data, slots, off=off, impl="pallas",
                              interpret=True)
    o_ref = ops.gather_blocks(data, slots, off=off, impl="ref")
    expect = np.where(np.asarray(slots) >= 0,
                      np.asarray(data)[np.maximum(np.asarray(slots), 0),
                                       np.asarray(off)], 0)
    np.testing.assert_array_equal(np.asarray(o_pal), expect)
    np.testing.assert_array_equal(np.asarray(o_ref), expect)


@pytest.mark.parametrize("sets,ways,m", [(16, 4, 33), (64, 8, 256),
                                         (4, 1, 7)])
def test_cache_probe_sweep(sets, ways, m):
    rng = np.random.default_rng(3)
    tags = jnp.asarray(rng.integers(-1, 5000, (sets, ways)), jnp.int32)
    keys = jnp.concatenate([
        tags.reshape(-1)[:m // 2],
        jnp.asarray(rng.integers(0, 10000, m - m // 2), jnp.int32)])
    h_pal, s_pal = ops.cache_probe(tags, keys, impl="pallas",
                                   interpret=True, block_m=32)
    h_ref, s_ref = ref.cache_probe_ref(tags, keys)
    np.testing.assert_array_equal(np.asarray(h_pal), np.asarray(h_ref))
    np.testing.assert_array_equal(np.asarray(s_pal), np.asarray(s_ref))


@pytest.mark.parametrize("sets,ways,m,block_s", [(20, 4, 50, 8),
                                                 (64, 2, 100, 16)])
def test_cache_probe_set_tiled(sets, ways, m, block_s):
    """Directories larger than one set block (the deployment case): the
    grid walks set slices, the last one padded, with the owner check."""
    from repro.kernels.cache_probe import cache_probe_pallas
    rng = np.random.default_rng(7)
    tags = jnp.asarray(rng.integers(-1, 5000, (sets, ways)), jnp.int32)
    owner = jnp.asarray(rng.integers(0, 2, (sets, ways)), jnp.int32)
    keys = jnp.concatenate([
        tags.reshape(-1)[:m // 2],
        jnp.asarray(rng.integers(-1, 10000, m - m // 2), jnp.int32)])
    h_pal, s_pal = cache_probe_pallas(tags, keys, owner=owner, tenant=1,
                                      block_m=16, block_s=block_s,
                                      interpret=True)
    h_ref, s_ref = ref.cache_probe_ref(tags, keys, owner=owner, tenant=1)
    np.testing.assert_array_equal(np.asarray(h_pal), np.asarray(h_ref))
    np.testing.assert_array_equal(np.asarray(s_pal), np.asarray(s_ref))


def test_cache_probe_matches_core_cache():
    """The kernel is bit-identical to the functional cache's probe."""
    from repro.core import cache as C
    rng = np.random.default_rng(4)
    cache = C.make_cache(8, 2, 4)
    keys = jnp.asarray(rng.integers(0, 50, 16), jnp.int32)
    cache, alloc = C.allocate(cache, keys, jnp.ones(16, bool))
    probe_keys = jnp.asarray(rng.integers(0, 60, 40), jnp.int32)
    pr = C.probe(cache, probe_keys)
    h2, s2 = ops.cache_probe(cache.tags, probe_keys, impl="pallas",
                             interpret=True, block_m=16)
    np.testing.assert_array_equal(np.asarray(pr.hit), np.asarray(h2))
    np.testing.assert_array_equal(np.asarray(pr.slot), np.asarray(s2))


def test_flash_dynamic_window_traced():
    rng = np.random.default_rng(5)
    q = _mk(rng, (1, 2, 64, 32), jnp.float32)
    k = _mk(rng, (1, 2, 64, 32), jnp.float32)
    v = _mk(rng, (1, 2, 64, 32), jnp.float32)
    o1 = ops.flash_attention(q, k, v, causal=True, window=jnp.int32(16),
                             impl="pallas", interpret=True,
                             block_q=32, block_kv=32)
    o2 = ref.flash_attention_ref(q, k, v, causal=True, window=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=3e-5,
                               rtol=3e-5)


def test_flash_xla_backward_stays_f32_under_x64():
    """Regression (bamlint BAM303): the manual backward's dk/dv scan
    accumulators were built without a dtype — float64 under x64 — which
    promoted (or broke) the whole custom-vjp backward."""
    rng = np.random.default_rng(6)
    q = _mk(rng, (1, 2, 32, 16), jnp.float32)
    k = _mk(rng, (1, 2, 32, 16), jnp.float32)
    v = _mk(rng, (1, 2, 32, 16), jnp.float32)
    with jax.enable_x64(True):
        dq, dk, dv = jax.grad(
            lambda q, k, v: ref.flash_attention_xla(
                q, k, v, causal=True, block_q=16, block_kv=16).sum(),
            argnums=(0, 1, 2))(q, k, v)
    assert dq.dtype == jnp.float32
    assert dk.dtype == jnp.float32
    assert dv.dtype == jnp.float32
