"""Decoder-only and encoder-decoder transformer LMs.

Covers the dense / MoE / VLM / audio families of the assigned pool:
  * scan-over-layers with stacked params (compact HLO at 48 layers);
  * per-layer sliding windows as *scanned traced values* so gemma3's 5:1
    local:global interleave lives inside one uniform scan body;
  * MoE blocks (olmoe / moonshot) via the scatter-based dispatch in
    :mod:`repro.models.layers`;
  * whisper-style enc-dec (audio frames from the stub frontend);
  * decode with a hybrid KV cache: sliding-window layers use ring buffers,
    global layers use the **BaM-paged pool** (page-table indirection,
    pages striped over the ``model`` mesh axis).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import constrain
from repro.kernels import ops
from repro.models import layers as L
from repro.utils import Tagged


BIG_WINDOW = 1 << 30


# ------------------------------------------------------------------ block ---
def init_block(cfg: ArchConfig, key, dtype=jnp.float32):
    ks = jax.random.split(key, 6)
    p, a = {}, {}
    p["ln1"], a["ln1"] = L.init_norm(cfg, cfg.d_model, dtype)
    p["attn"], a["attn"] = L.init_attention(cfg, ks[0], dtype)
    p["ln2"], a["ln2"] = L.init_norm(cfg, cfg.d_model, dtype)
    if cfg.moe:
        p["moe"], a["moe"] = L.init_moe(cfg, ks[1], dtype)
    else:
        p["mlp"], a["mlp"] = L.init_mlp(cfg, ks[1], dtype)
    if cfg.enc_dec:
        p["ln_x"], a["ln_x"] = L.init_norm(cfg, cfg.d_model, dtype)
        p["xattn"], a["xattn"] = L.init_attention(
            cfg.replace(qkv_bias=False), ks[2], dtype)
    return p, a


def block_apply(cfg: ArchConfig, p, x, *, window, positions, impl="auto",
                enc_out=None, causal=True):
    """One decoder block. window may be a traced scalar (scanned)."""
    h = L.attention(cfg, p["attn"], L.norm_apply(cfg, p["ln1"], x),
                    window=window, positions=positions, causal=causal,
                    impl=impl)
    x = x + h
    if enc_out is not None:
        # cross attention: kv from encoder output
        xq = L.norm_apply(cfg, p["ln_x"], x)
        B, S, _ = xq.shape
        dtype = cfg.compute_dtype
        hd = cfg.hd
        q = L.dense(p["xattn"]["wq"], xq, dtype).reshape(
            B, S, cfg.n_heads, hd).transpose(0, 2, 1, 3)
        k = L.dense(p["xattn"]["wk"], enc_out, dtype).reshape(
            B, -1, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
        v = L.dense(p["xattn"]["wv"], enc_out, dtype).reshape(
            B, -1, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
        o = ops.flash_attention(q, k, v, causal=False, impl=impl)
        o = o.transpose(0, 2, 1, 3).reshape(B, S, cfg.n_heads * hd)
        x = x + L.dense(p["xattn"]["wo"], o, dtype)
    xi = L.norm_apply(cfg, p["ln2"], x)
    if cfg.moe:
        y, aux = L.moe_ffn(cfg, p["moe"], xi)
    else:
        y, aux = L.mlp(cfg, p["mlp"], xi), {}
    return x + y, aux


# ------------------------------------------------------------------- init ---
def init_lm(cfg: ArchConfig, key, max_seq: int = 0) -> Tuple[Any, Any]:
    """Returns (params, axes). Layer params stacked along a leading axis."""
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    p, a = {}, {}
    p["embed"], a["embed"] = L.init_embedding(cfg, ks[0], dtype)

    def one_block(k):
        return init_block(cfg, k, dtype)

    bp, ba = one_block(ks[1])
    blocks = jax.vmap(lambda k: one_block(k)[0])(
        jax.random.split(ks[2], cfg.n_layers))
    p["blocks"] = blocks
    a["blocks"] = jax.tree_util.tree_map(
        lambda ax: (None,) + ax, ba,
        is_leaf=lambda x: isinstance(x, tuple))

    p["ln_f"], a["ln_f"] = L.init_norm(cfg, cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        p["head"], a["head"] = L.init_dense(
            ks[3], cfg.d_model, cfg.vocab, ("w_embed", "vocab"), dtype=dtype)

    if cfg.pos_emb == "learned":
        n_pos = max(max_seq, 1024)
        p["pos"] = L._normal(ks[4], (n_pos, cfg.d_model), 0.02, dtype)
        a["pos"] = (None, "w_embed")

    if cfg.enc_dec:
        enc_cfg = cfg.replace(moe=False, enc_dec=False)
        ebp, eba = init_block(enc_cfg, ks[5], dtype)
        p["enc_blocks"] = jax.vmap(
            lambda k: init_block(enc_cfg, k, dtype)[0])(
                jax.random.split(ks[6], cfg.n_enc_layers))
        a["enc_blocks"] = jax.tree_util.tree_map(
            lambda ax: (None,) + ax, eba,
            is_leaf=lambda x: isinstance(x, tuple))
        p["enc_pos"] = L._normal(ks[7], (cfg.enc_seq, cfg.d_model), 0.02,
                                 dtype)
        a["enc_pos"] = ("enc_seq", "w_embed")
        p["enc_ln_f"], a["enc_ln_f"] = L.init_norm(cfg, cfg.d_model, dtype)
    return p, a


def layer_window_array(cfg: ArchConfig, seq_len: int) -> jax.Array:
    nl, ng = cfg.local_ratio
    period = max(nl + ng, 1)
    out = []
    for i in range(cfg.n_layers):
        if cfg.window is not None and nl > 0 and (i % period) < nl:
            out.append(cfg.window)
        else:
            out.append(BIG_WINDOW)
    return jnp.asarray(out, jnp.int32)


# ---------------------------------------------------------------- forward ---
def _remat_policy(cfg: ArchConfig):
    if cfg.remat == "none":
        return None
    if cfg.remat == "dots_saveable":
        return jax.checkpoint_policies.dots_saveable
    return jax.checkpoint_policies.nothing_saveable


def _scan_blocks(cfg: ArchConfig, blocks, x, windows, positions, impl,
                 enc_out=None, causal=True):
    aux0 = {"load_balance": jnp.zeros(()), "router_z": jnp.zeros(())} \
        if cfg.moe else {}

    def body(carry, layer):
        xc, aux = carry
        bp, w = layer
        xc2, aux_l = block_apply(cfg, bp, xc, window=w, positions=positions,
                                 impl=impl, enc_out=enc_out, causal=causal)
        for k in aux:
            aux[k] = aux[k] + aux_l[k]
        xc2 = constrain(xc2, ("batch", "seq", "act_embed"))
        return (xc2, aux), None

    if cfg.remat != "none":
        body = jax.checkpoint(body, policy=_remat_policy(cfg),
                              prevent_cse=False)
    (x, aux), _ = jax.lax.scan(body, (x, aux0), (blocks, windows))
    return x, aux


def encode(cfg: ArchConfig, params, frames, impl="auto"):
    """Whisper-style encoder over precomputed frame embeddings (stub
    frontend): frames (B, Senc, D)."""
    x = frames.astype(cfg.compute_dtype)
    S = x.shape[1]
    x = x + params["enc_pos"][:S].astype(cfg.compute_dtype)
    windows = jnp.full((cfg.n_enc_layers,), BIG_WINDOW, jnp.int32)
    positions = jnp.arange(S)
    x, _ = _scan_blocks(cfg, params["enc_blocks"], x, windows, positions,
                        impl, causal=False)
    return L.norm_apply(cfg, params["enc_ln_f"], x)


def forward(cfg: ArchConfig, params, batch: Dict[str, jax.Array],
            impl: str = "auto", last_only: bool = False,
            return_hidden: bool = False) -> Tuple[jax.Array, Dict]:
    """Full-sequence forward -> (logits (B, S, V), aux).

    batch: tokens (B, S[text]) int32; optional patch_embeds (B, P, D)
    (vlm — prepended), enc_frames (B, Senc, D) (audio).
    """
    tokens = batch["tokens"]
    x = L.embed(cfg, params["embed"], tokens)
    if cfg.family == "vlm" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(cfg.compute_dtype)
        x = jnp.concatenate([pe, x], axis=1)
    B, S, _ = x.shape
    if cfg.pos_emb == "learned":
        x = x + params["pos"][:S].astype(cfg.compute_dtype)
    x = constrain(x, ("batch", "seq", "act_embed"))

    enc_out = None
    if cfg.enc_dec:
        enc_out = encode(cfg, params, batch["enc_frames"], impl)

    positions = jnp.arange(S)
    windows = layer_window_array(cfg, S)
    x, aux = _scan_blocks(cfg, params["blocks"], x, windows, positions,
                          impl, enc_out=enc_out, causal=True)
    x = L.norm_apply(cfg, params["ln_f"], x)
    if last_only:
        x = x[:, -1:, :]
    if return_hidden:
        return x, aux
    logits = L.logits_head(cfg, params.get("head"), params["embed"], x)
    return logits, aux


def loss_fn(cfg: ArchConfig, params, batch, impl: str = "auto"):
    """Next-token cross entropy (+ MoE aux losses), chunked over seq so the
    (B, S, V) logits tensor is never materialised."""
    hidden, aux = forward(cfg, params, batch, impl, return_hidden=True)
    tokens = batch["tokens"]
    B, S = tokens.shape
    n_prefix = hidden.shape[1] - S          # vlm patches prepended
    hidden_text = hidden[:, n_prefix:, :]
    labels = batch.get("labels")
    if labels is None:
        labels = jnp.concatenate(
            [tokens[:, 1:], jnp.zeros((B, 1), tokens.dtype)], axis=1)
        mask = jnp.concatenate(
            [jnp.ones((B, S - 1)), jnp.zeros((B, 1))], axis=1)
    else:
        mask = batch.get("loss_mask", jnp.ones_like(labels,
                                                    dtype=jnp.float32))
    loss = L.lm_loss_from_hidden(cfg, params.get("head"), params["embed"],
                                 hidden_text, labels, mask)
    metrics = {"nll": loss}
    if cfg.moe:
        lb = aux["load_balance"] / cfg.n_layers
        z = aux["router_z"] / cfg.n_layers
        metrics.update(load_balance=lb, router_z=z)
        loss = loss + 0.01 * lb + 1e-3 * z
    return loss, metrics


# =========================================================== decode caches ==
def _ring_spec(cfg, B, W):
    hd = cfg.hd
    return {
        "k": jnp.zeros((B, cfg.n_kv_heads, W, hd), cfg.compute_dtype),
        "v": jnp.zeros((B, cfg.n_kv_heads, W, hd), cfg.compute_dtype),
        "pos": jnp.full((B, W), -1, jnp.int32),
    }


_RING_AXES = {
    "k": ("batch", "act_kv_heads", None, None),
    "v": ("batch", "act_kv_heads", None, None),
    "pos": ("batch", None),
}


def _paged_spec(cfg, B, max_seq):
    hd = cfg.hd
    page = cfg.kv_page_size
    n_pages = -(-max_seq // page)
    return {
        "k_pages": jnp.zeros((B, n_pages, page, cfg.n_kv_heads, hd),
                             cfg.compute_dtype),
        "v_pages": jnp.zeros((B, n_pages, page, cfg.n_kv_heads, hd),
                             cfg.compute_dtype),
        # identity mapping at init; the indirection is the BaM page table
        "page_table": jnp.broadcast_to(
            jnp.arange(n_pages, dtype=jnp.int32)[None], (B, n_pages)),
    }


_PAGED_AXES = {
    "k_pages": ("batch", "kv_pages", None, None, None),
    "v_pages": ("batch", "kv_pages", None, None, None),
    "page_table": ("batch", None),
}


def init_decode_cache(cfg: ArchConfig, B: int, max_seq: int,
                      enc_out: Optional[jax.Array] = None):
    """Hybrid cache: ring buffers for window layers, BaM-paged pools for
    global layers.  Returns (cache, axes)."""
    windows = cfg.layer_windows(max_seq)
    layers, axes = [], []
    for w in windows:
        if w < max_seq:                       # sliding-window layer
            layers.append(Tagged("ring", _ring_spec(cfg, B, w)))
            axes.append(Tagged("ring", _RING_AXES))
        else:
            layers.append(Tagged("paged", _paged_spec(cfg, B, max_seq)))
            axes.append(Tagged("paged", _PAGED_AXES))
    cache = {
        "seq_lens": jnp.zeros((B,), jnp.int32),
        "layers": tuple(layers),
    }
    cache_axes = {
        "seq_lens": ("batch",),
        "layers": tuple(axes),
    }
    if cfg.enc_dec:
        # cross-attention KV per decoder layer, computed once at prefill
        Senc = cfg.enc_seq
        hd = cfg.hd
        cache["xkv"] = jnp.zeros(
            (cfg.n_layers, 2, B, cfg.n_kv_heads, Senc, hd),
            cfg.compute_dtype)
        cache_axes["xkv"] = (None, None, "batch", "act_kv_heads",
                             "enc_seq", None)
    return cache, cache_axes


def _decode_attn_ring(cfg, p, xq, entry, pos, impl):
    """xq: (B, 1, D) normed input; returns attn output (B, 1, D)."""
    B = xq.shape[0]
    dtype = cfg.compute_dtype
    hd = cfg.hd
    q = L.dense(p["wq"], xq, dtype).reshape(B, 1, cfg.n_heads, hd)
    k = L.dense(p["wk"], xq, dtype).reshape(B, 1, cfg.n_kv_heads, hd)
    v = L.dense(p["wv"], xq, dtype).reshape(B, 1, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = L.rms_norm_simple(q, p["q_norm"])
        k = L.rms_norm_simple(k, p["k_norm"])
    q = q.transpose(0, 2, 1, 3)
    k = k.transpose(0, 2, 1, 3)
    if cfg.pos_emb == "rope":
        pb = pos[:, None]                     # (B, 1)
        q = L.rope(q, pb[:, None, :], cfg.rope_theta)
        k = L.rope(k, pb[:, None, :], cfg.rope_theta)
    v = v.transpose(0, 2, 1, 3)

    W = entry["k"].shape[2]
    slot = pos % W                            # (B,)
    bidx = jnp.arange(B)
    k_ring = entry["k"].at[bidx, :, slot].set(k[:, :, 0])
    v_ring = entry["v"].at[bidx, :, slot].set(v[:, :, 0])
    ring_pos = entry["pos"].at[bidx, slot].set(pos)

    # masked attention over the ring (GQA: fold group)
    kr = k_ring.astype(jnp.float32)
    G = cfg.group
    qg = q.reshape(B, cfg.n_kv_heads, G, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,bkwd->bkgw", qg, kr) / math.sqrt(hd)
    valid = (ring_pos >= 0) & (ring_pos > (pos[:, None] - W)) \
        & (ring_pos <= pos[:, None])
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgw,bkwd->bkgd", pr, v_ring.astype(jnp.float32))
    o = o.reshape(B, 1, cfg.n_heads * hd).astype(dtype)
    out = L.dense(p["wo"], o, dtype)
    return out, {"k": k_ring, "v": v_ring, "pos": ring_pos}


def _paged_attention_flash_decode(cfg, q, k_pages, v_pages, page_table,
                                  seq_lens, mesh):
    """Shard-local flash-decoding over the model-striped page pool.

    The naive SPMD lowering of the page-table gather replicates the whole
    pool per step (XLA 'involuntary full rematerialization').  Here each
    model shard attends over only the physical pages it owns and the
    partial (m, l, acc) softmax states are psum-combined — the TPU
    flash-decoding schedule, and exactly what the Pallas paged kernel does
    across cores.  Collective payload per step: O(B x Hq x hd), not O(pool).
    """
    import math as _math
    from jax.sharding import PartitionSpec as PS
    B, Hq, D = q.shape
    P_total, page = k_pages.shape[1], k_pages.shape[2]
    Hkv = k_pages.shape[3]
    NP = page_table.shape[1]
    G = Hq // Hkv
    scale = 1.0 / _math.sqrt(D)

    def shard_fn(qb, kp, vp, pt, sl):
        # kp/vp: (B, P_total/n_shards, page, Hkv, D) local slice
        s = jax.lax.axis_index("model")
        p_loc = kp.shape[1]
        base = s * p_loc
        mine = (pt >= base) & (pt < base + p_loc)          # (B, NP)
        safe = jnp.where(mine, pt - base, 0)
        idx = safe[:, :, None, None, None]
        k = jnp.take_along_axis(kp, idx, axis=1)           # local gather
        v = jnp.take_along_axis(vp, idx, axis=1)
        S = NP * page
        k = k.transpose(0, 3, 1, 2, 4).reshape(B, Hkv, S, D)
        v = v.transpose(0, 3, 1, 2, 4).reshape(B, Hkv, S, D)
        qg = qb.reshape(B, Hkv, G, D).astype(jnp.float32)
        sc = jnp.einsum("bhgd,bhkd->bhgk", qg,
                        k.astype(jnp.float32)) * scale
        pos = jnp.arange(S)[None, :]
        live = (pos < sl[:, None]) & jnp.repeat(mine, page, axis=1)
        sc = jnp.where(live[:, None, None], sc, -1e30)
        m = sc.max(-1)                                      # (B,Hkv,G)
        m_g = jax.lax.pmax(m, "model")
        pr = jnp.where(live[:, None, None],
                       jnp.exp(sc - m_g[..., None]), 0.0)
        l = jax.lax.psum(pr.sum(-1), "model")
        acc = jax.lax.psum(
            jnp.einsum("bhgk,bhkd->bhgd", pr, v.astype(jnp.float32)),
            "model")
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out.reshape(B, Hq, D).astype(q.dtype)

    return jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(PS(), PS(None, "model"), PS(None, "model"), PS(), PS()),
        out_specs=PS(), axis_names={"model"}, check_vma=False,
    )(q, k_pages, v_pages, page_table, seq_lens)


def _decode_attn_paged(cfg, p, xq, entry, pos, impl):
    B = xq.shape[0]
    dtype = cfg.compute_dtype
    hd = cfg.hd
    q = L.dense(p["wq"], xq, dtype).reshape(B, 1, cfg.n_heads, hd)
    k = L.dense(p["wk"], xq, dtype).reshape(B, 1, cfg.n_kv_heads, hd)
    v = L.dense(p["wv"], xq, dtype).reshape(B, 1, cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        q = L.rms_norm_simple(q, p["q_norm"])
        k = L.rms_norm_simple(k, p["k_norm"])
    q = q.transpose(0, 2, 1, 3)
    k = k.transpose(0, 2, 1, 3)
    if cfg.pos_emb == "rope":
        pb = pos[:, None]
        q = L.rope(q, pb[:, None, :], cfg.rope_theta)
        k = L.rope(k, pb[:, None, :], cfg.rope_theta)
    v = v.transpose(0, 2, 1, 3)

    page = entry["k_pages"].shape[2]
    lpage = pos // page                         # (B,) logical page
    slot_in = pos % page
    bidx = jnp.arange(B)
    ppage = entry["page_table"][bidx, lpage]    # physical page
    ppage = jnp.maximum(ppage, 0)
    k_pages = entry["k_pages"].at[bidx, ppage, slot_in].set(k[:, :, 0])
    v_pages = entry["v_pages"].at[bidx, ppage, slot_in].set(v[:, :, 0])

    from repro.distributed.sharding import current_mesh
    mesh = current_mesh()
    if cfg.flash_decode_shards and mesh is not None \
            and "model" in mesh.axis_names:
        o = _paged_attention_flash_decode(
            cfg, q[:, :, 0], k_pages, v_pages, entry["page_table"],
            pos + 1, mesh)
    else:
        o = ops.paged_attention(
            q[:, :, 0], k_pages, v_pages, entry["page_table"], pos + 1,
            impl=impl)                          # (B, Hq, hd)
    o = o.reshape(B, 1, cfg.n_heads * hd)
    out = L.dense(p["wo"], o.astype(dtype), dtype)
    return out, {"k_pages": k_pages, "v_pages": v_pages,
                 "page_table": entry["page_table"]}


def _decode_xattn(cfg, p, xq, xkv_l):
    """Cross-attention for decode; xkv_l: (2, B, Hkv, Senc, hd)."""
    B = xq.shape[0]
    dtype = cfg.compute_dtype
    hd = cfg.hd
    q = L.dense(p["wq"], xq, dtype).reshape(
        B, 1, cfg.n_heads, hd).transpose(0, 2, 1, 3)
    k, v = xkv_l[0], xkv_l[1]
    G = cfg.group
    qg = q.reshape(B, cfg.n_kv_heads, G, hd).astype(jnp.float32)
    s = jnp.einsum("bkgd,bksd->bkgs", qg,
                   k.astype(jnp.float32)) / math.sqrt(hd)
    pr = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgs,bksd->bkgd", pr, v.astype(jnp.float32))
    o = o.reshape(B, 1, cfg.n_heads * hd).astype(dtype)
    return L.dense(p["wo"], o, dtype)


def decode_step(cfg: ArchConfig, params, cache, tokens: jax.Array,
                impl: str = "auto"):
    """One decode step for all transformer families.

    tokens: (B,) int32 — the tokens generated at the previous step.
    Returns (logits (B, V), cache').
    """
    B = tokens.shape[0]
    pos = cache["seq_lens"]                         # (B,)
    x = L.embed(cfg, params["embed"], tokens[:, None])   # (B, 1, D)
    if cfg.pos_emb == "learned":
        x = x + params["pos"][pos][:, None].astype(cfg.compute_dtype)
    x = constrain(x, ("batch", None, "act_embed"))

    new_layers = []
    for i, tagged in enumerate(cache["layers"]):
        kind, entry = tagged.kind, tagged.value
        bp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
        xq = L.norm_apply(cfg, bp["ln1"], x)
        if kind == "ring":
            h, entry2 = _decode_attn_ring(cfg, bp["attn"], xq, entry, pos,
                                          impl)
        else:
            h, entry2 = _decode_attn_paged(cfg, bp["attn"], xq, entry, pos,
                                           impl)
        x = x + h
        if cfg.enc_dec:
            xq2 = L.norm_apply(cfg, bp["ln_x"], x)
            x = x + _decode_xattn(cfg, bp["xattn"], xq2, cache["xkv"][i])
        xi = L.norm_apply(cfg, bp["ln2"], x)
        if cfg.moe:
            y, _ = L.moe_ffn(cfg, bp["moe"], xi)
        else:
            y = L.mlp(cfg, bp["mlp"], xi)
        x = x + y
        new_layers.append(Tagged(kind, entry2))

    x = L.norm_apply(cfg, params["ln_f"], x)
    logits = L.logits_head(cfg, params.get("head"), params["embed"], x)
    cache2 = dict(cache)
    cache2["layers"] = tuple(new_layers)
    cache2["seq_lens"] = pos + 1
    return logits[:, 0, :], cache2


def prefill(cfg: ArchConfig, params, batch, max_seq: int,
            impl: str = "auto"):
    """Run the full prompt, return (last-token logits, filled cache).

    Correct (matches decode_step semantics) and used by the examples and
    integration tests; the 32k dry-run cells lower `forward` (prefill
    compute) and `decode_step` separately.
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    enc_out = None
    if cfg.enc_dec:
        enc_out = encode(cfg, params, batch["enc_frames"], impl)
    cache, _ = init_decode_cache(cfg, B, max_seq)
    if cfg.enc_dec:
        # fill cross-KV once
        xkv = []
        dtype = cfg.compute_dtype
        hd = cfg.hd
        for i in range(cfg.n_layers):
            bp = jax.tree_util.tree_map(lambda a: a[i], params["blocks"])
            k = L.dense(bp["xattn"]["wk"], enc_out, dtype).reshape(
                B, -1, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
            v = L.dense(bp["xattn"]["wv"], enc_out, dtype).reshape(
                B, -1, cfg.n_kv_heads, hd).transpose(0, 2, 1, 3)
            xkv.append(jnp.stack([k, v]))
        cache["xkv"] = jnp.stack(xkv)
    # sequential prefill through decode_step (exact; fine at test scale)
    logits = None

    def body(carry, t):
        cache = carry
        logits_t, cache = decode_step(cfg, params, cache, tokens[:, t], impl)
        return cache, logits_t

    cache, all_logits = jax.lax.scan(body, cache, jnp.arange(S))
    return all_logits[-1], cache
