"""Attention variants: GQA (RoPE, bias, sliding-window), MLA, cross-attn.

All attention in the model path goes through ``flash_attn`` — a blocked,
online-softmax attention written with ``jax.lax.scan`` so that the S^2
score matrix is never materialized (required for the 32k-prefill dry-run
to fit HBM) and so XLA sees a streaming loop it can pipeline.

Decode-time attention over a (possibly sequence-sharded) KV cache is a
separate masked one-token path: softmax reductions over the sharded
sequence dim lower to all-reduces over the ``model`` mesh axis — the
TPU-native "sequence-parallel decode" described in DESIGN.md §5.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.config import ModelConfig, MLAConfig

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float
               ) -> jnp.ndarray:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..,S,hd/2)
    cos = jnp.cos(angles)[..., :, None, :]              # (.., S, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Blocked flash attention (pure jnp + lax.scan)
# ---------------------------------------------------------------------------
def flash_attn(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
               causal: bool = True, q_offset=0,
               window: int = 0, kv_len: Optional[jnp.ndarray] = None,
               block_kv: int = 1024) -> jnp.ndarray:
    """Online-softmax attention.

    q: (b, Sq, H, hd); k/v: (b, Sk, KV, hd) with H % KV == 0.
    ``q_offset``: absolute position of q[0] (chunked prefill).
    ``window``: sliding window size (0 = unlimited).
    ``kv_len``: number of valid KV tokens (rest is padding).
    Returns (b, Sq, H, hd).
    """
    b, sq, h, hd = q.shape
    _, sk, kvh, _ = k.shape
    hd_v = v.shape[-1]                      # may differ from hd (MLA)
    rep = h // kvh
    scale = hd ** -0.5

    nblk = -(-sk // block_kv)
    pad = nblk * block_kv - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # (nblk, b, block_kv, kvh, hd)
    kb = k.reshape(b, nblk, block_kv, kvh, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblk, block_kv, kvh, hd_v).transpose(1, 0, 2, 3, 4)

    qg = q.reshape(b, sq, kvh, rep, hd).astype(jnp.float32)
    q_pos = q_offset + jnp.arange(sq)

    def body(carry, blk):
        m, l, acc = carry
        kblk, vblk, blk_idx = blk
        k_pos = blk_idx * block_kv + jnp.arange(block_kv)
        # scores: (b, kvh, rep, sq, block_kv)
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qg,
                       kblk.astype(jnp.float32)) * scale
        mask = jnp.ones((sq, block_kv), dtype=bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        if kv_len is not None:
            mask &= k_pos[None, :] < kv_len
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bgrqk,bkgd->bgrqd", p, vblk.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kvh, rep, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, kvh, rep, sq), jnp.float32)
    a0 = jnp.zeros((b, kvh, rep, sq, hd_v), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), (m0, l0, a0),
        (kb, vb, jnp.arange(nblk)))
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd_v)
    return out.astype(q.dtype)


def decode_attn(q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
                pos: jnp.ndarray, *, window: int = 0,
                ring: bool = False) -> jnp.ndarray:
    """One-token attention over the full cache.

    q: (b, 1, H, hd); k_cache/v_cache: (b, S, KV, hd); pos: () next index.
    ``ring``: cache is a ring buffer of size ``window`` (sliding archs) —
    every slot < min(pos, S) is valid.
    Softmax reductions over S lower to all-reduces when S is sharded.
    """
    b, s, kvh, hd = k_cache.shape
    h = q.shape[2]
    rep = h // kvh
    qg = q.reshape(b, 1, kvh, rep, hd).astype(jnp.float32)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg,
                        k_cache.astype(jnp.float32)) * hd ** -0.5
    idx = jnp.arange(s)
    if ring:
        valid = idx < jnp.minimum(pos + 1, s)
    else:
        valid = idx <= pos
        if window:
            valid &= idx > pos - window
    scores = jnp.where(valid[None, None, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", p, v_cache.astype(jnp.float32))
    return out.reshape(b, 1, h, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA self-attention module
# ---------------------------------------------------------------------------
def init_gqa(key, cfg: ModelConfig, dtype) -> dict:
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    sc = d ** -0.5
    p = {
        "wq": jax.random.normal(ks[0], (d, h * hd), dtype) * sc,
        "wk": jax.random.normal(ks[1], (d, kvh * hd), dtype) * sc,
        "wv": jax.random.normal(ks[2], (d, kvh * hd), dtype) * sc,
        "wo": jax.random.normal(ks[3], (h * hd, d), dtype) * (h * hd) ** -0.5,
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kvh * hd,), dtype)
        p["bv"] = jnp.zeros((kvh * hd,), dtype)
    return p


def gqa_qkv(p: dict, cfg: ModelConfig, x: jnp.ndarray, positions):
    b, s, _ = x.shape
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, kvh, hd)
    v = v.reshape(b, s, kvh, hd)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def gqa_forward(p: dict, cfg: ModelConfig, x: jnp.ndarray, *,
                q_offset=0, window: int = 0,
                kv_len=None) -> jnp.ndarray:
    """Full-sequence (train / prefill-chunk) self-attention."""
    from repro.models import sharding as SH
    b, s, _ = x.shape
    positions = q_offset + jnp.arange(s)[None, :]
    q, k, v = gqa_qkv(p, cfg, x, positions)
    if SH.opt_on("attn2d"):
        # heads unshardeable over the model axis (e.g. qwen2's 14): make
        # attention pure 2D batch-parallel instead of replicating scores
        q = SH.batch2d_constrain(q)
        k = SH.batch2d_constrain(k)
        v = SH.batch2d_constrain(v)
    out = flash_attn(q, k, v, causal=True, q_offset=q_offset,
                     window=window, kv_len=kv_len)
    if SH.opt_on("attn2d"):
        out = SH.act_constrain(out)
    return out.reshape(b, s, -1) @ p["wo"]


def seq_sharded_attn(q, k_cache, v_cache, *, q_offset, kv_len,
                     window: int = 0) -> jnp.ndarray:
    """Masked partial-softmax attention over a sequence-sharded cache
    (the "seqkv" optimization): each chip scores q against its local KV
    shard; the softmax max/sum and the PV product reduce over the sharded
    seq dim as small all-reduces — no cache all-gather per chunk.

    q: (b, sq, h, hd); caches: (b, S, kvh, hd) with S sharded over
    ``model``.  O(S) temp per (chunk, layer): scores (b,kvh,rep,sq,S/16).
    """
    from repro.models import sharding as SH
    b, sq, h, hd = q.shape
    _, s_cache, kvh, hd_v = v_cache.shape
    rep = h // kvh
    k_cache = SH.seq_constrain(k_cache, 1)
    v_cache = SH.seq_constrain(v_cache, 1)
    qg = q.reshape(b, sq, kvh, rep, hd)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg.astype(k_cache.dtype),
                        k_cache,
                        preferred_element_type=jnp.float32) * hd ** -0.5
    scores = SH.seq_constrain(scores, 4)
    q_pos = q_offset + jnp.arange(sq)
    k_pos = jnp.arange(s_cache)
    mask = (q_pos[:, None] >= k_pos[None, :]) \
        & (k_pos[None, :] < kv_len)
    if window:
        mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
    scores = jnp.where(mask[None, None, None], scores, NEG_INF)
    pattn = jax.nn.softmax(scores, axis=-1)          # reduces over shard
    out = jnp.einsum("bgrqk,bkgd->bqgrd", pattn.astype(v_cache.dtype),
                     v_cache, preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, hd_v).astype(q.dtype)


def gqa_prefill(p: dict, cfg: ModelConfig, x: jnp.ndarray, cache: dict, *,
                q_offset=0, window: int = 0):
    """Prefill chunk: attend to (written cache ++ this chunk), write cache."""
    from repro.models import sharding as SH
    b, s, _ = x.shape
    positions = q_offset + jnp.arange(s)[None, :]
    q, k, v = gqa_qkv(p, cfg, x, positions)
    k_cache = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, q_offset, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, q_offset, 0, 0))
    kv_len = q_offset + s
    if SH.opt_on("seqkv"):
        out = seq_sharded_attn(q, k_cache, v_cache, q_offset=q_offset,
                               kv_len=kv_len, window=window)
    else:
        out = flash_attn(q, k_cache, v_cache, causal=True,
                         q_offset=q_offset, window=window, kv_len=kv_len)
    out = out.reshape(b, s, -1) @ p["wo"]
    return out, {"k": k_cache, "v": v_cache}


def gqa_prefill_paged(p: dict, cfg: ModelConfig, x: jnp.ndarray,
                      k_pool: jnp.ndarray, v_pool: jnp.ndarray, layer, *,
                      positions, q_offset, kv_len, block_tables,
                      pages_idx, offs_idx, window: int = 0):
    """Fused chunk prefill against layer ``layer`` of the page pool.

    x: (segs, sq, d) — the packed segments of one fixed-size chunk;
    k_pool/v_pool: (L, n_pages, page, kvh * hd) every layer's pool;
    positions: (segs, sq) absolute token positions;
    pages_idx/offs_idx: (segs, sq) physical (page, in-page) slot per
    token (pad tokens point at the engine's scratch page).  The chunk's
    K/V is scattered into the pool in place first, then the Pallas
    paged-prefill kernel attends over (written prefix ++ this chunk)
    through the block tables.  Returns (attn_out, k_pool, v_pool).
    """
    from repro.kernels import ops
    b, s, _ = x.shape
    q, k, v = gqa_qkv(p, cfg, x, positions)
    k_pool = k_pool.at[layer, pages_idx, offs_idx].set(
        k.reshape(b, s, -1).astype(k_pool.dtype))
    v_pool = v_pool.at[layer, pages_idx, offs_idx].set(
        v.reshape(b, s, -1).astype(v_pool.dtype))
    out = ops.prefill_attention(q, k_pool, v_pool, kv_len, q_offset,
                                block_table=block_tables, layer=layer,
                                window=window)
    return out.reshape(b, s, -1) @ p["wo"], k_pool, v_pool


def gqa_decode_paged(p: dict, cfg: ModelConfig, x: jnp.ndarray,
                     k_pool: jnp.ndarray, v_pool: jnp.ndarray, layer, *,
                     pos, pages, offs, block_tables, lens,
                     window: int = 0):
    """Batched one-token decode against layer ``layer`` of the page pool.

    x: (slots, 1, d); k_pool/v_pool: (L, n_pages, page, kvh * hd);
    pos: (slots,) append position per slot; pages/offs: (slots,)
    physical slot of the appended token (dead slots point at the scratch
    page); lens: (slots,) valid tokens incl. the append.
    Returns (attn_out, k_pool, v_pool).
    """
    from repro.kernels import ops
    b = x.shape[0]
    q, k, v = gqa_qkv(p, cfg, x, pos[:, None])
    k_pool = k_pool.at[layer, pages, offs].set(
        k.reshape(b, -1).astype(k_pool.dtype))
    v_pool = v_pool.at[layer, pages, offs].set(
        v.reshape(b, -1).astype(v_pool.dtype))
    out = ops.decode_attention(q[:, 0], k_pool, v_pool, layer,
                               block_tables, lens, window=window)
    return out.reshape(b, 1, -1) @ p["wo"], k_pool, v_pool


def gqa_decode(p: dict, cfg: ModelConfig, x: jnp.ndarray, cache: dict,
               pos, *, window: int = 0):
    """One-token decode. Cache seq dim may be a ring buffer (window mode)."""
    b = x.shape[0]
    h, kvh, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    positions = jnp.full((b, 1), pos)
    q, k, v = gqa_qkv(p, cfg, x, positions)
    s_cache = cache["k"].shape[1]
    ring = window > 0 and s_cache <= window
    slot = jax.lax.rem(pos, s_cache) if ring else pos
    k_cache = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, slot, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, slot, 0, 0))
    out = decode_attn(q, k_cache, v_cache, pos, window=window, ring=ring)
    out = out.reshape(b, 1, -1) @ p["wo"]
    return out, {"k": k_cache, "v": v_cache}


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, DeepSeek-V2) — absorbed decode form
# ---------------------------------------------------------------------------
def init_mla(key, cfg: ModelConfig, dtype) -> dict:
    m: MLAConfig = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    ks = jax.random.split(key, 6)
    sc = d ** -0.5
    p = {}
    if m.q_lora_rank:
        p["wq_a"] = jax.random.normal(ks[0], (d, m.q_lora_rank), dtype) * sc
        p["q_norm"] = jnp.ones((m.q_lora_rank,), dtype)
        p["wq_b"] = jax.random.normal(
            ks[1], (m.q_lora_rank, h * qk), dtype) * m.q_lora_rank ** -0.5
    else:
        p["wq"] = jax.random.normal(ks[0], (d, h * qk), dtype) * sc
    p["wkv_a"] = jax.random.normal(
        ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim), dtype) * sc
    p["kv_norm"] = jnp.ones((m.kv_lora_rank,), dtype)
    p["wkv_b"] = jax.random.normal(
        ks[3], (m.kv_lora_rank, h * (m.qk_nope_head_dim + m.v_head_dim)),
        dtype) * m.kv_lora_rank ** -0.5
    p["wo"] = jax.random.normal(
        ks[4], (h * m.v_head_dim, d), dtype) * (h * m.v_head_dim) ** -0.5
    return p


def _rms(x, w, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def _mla_q(p, cfg, x, positions):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    qk = m.qk_nope_head_dim + m.qk_rope_head_dim
    if "wq_a" in p:
        q = _rms(x @ p["wq_a"], p["q_norm"]) @ p["wq_b"]
    else:
        q = x @ p["wq"]
    q = q.reshape(b, s, h, qk)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_kv_latent(p, cfg, x, positions):
    """Per-token compressed latent: c_kv (b,s,lora), k_rope (b,s,1,rope)."""
    m = cfg.mla
    kv = x @ p["wkv_a"]
    c_kv, k_rope = jnp.split(kv, [m.kv_lora_rank], axis=-1)
    c_kv = _rms(c_kv, p["kv_norm"])
    k_rope = apply_rope(k_rope[:, :, None, :], positions, cfg.rope_theta)
    return c_kv, k_rope[:, :, 0, :]


def mla_forward(p: dict, cfg: ModelConfig, x: jnp.ndarray, *,
                q_offset=0, window: int = 0, kv_len=None) -> jnp.ndarray:
    """Full-sequence MLA: decompress latent into per-head K/V, flash attend."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    positions = q_offset + jnp.arange(s)[None, :]
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    c_kv, k_rope = _mla_kv_latent(p, cfg, x, positions)
    kvb = (c_kv @ p["wkv_b"]).reshape(b, s, h, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kvb, [m.qk_nope_head_dim], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, s, h, m.qk_rope_head_dim))], axis=-1)
    out = flash_attn(q, k, v, causal=True, q_offset=q_offset,
                     window=window, kv_len=kv_len)
    return out.reshape(b, s, -1) @ p["wo"]


def mla_prefill(p: dict, cfg: ModelConfig, x: jnp.ndarray, cache: dict, *,
                q_offset=0, window: int = 0):
    """Chunked prefill with the compressed-latent cache.

    cache: {"ckv": (b, S, lora), "krope": (b, S, rope)} — the 14x-smaller
    MLA cache is exactly what the dispatcher ships to decode instances.
    """
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    positions = q_offset + jnp.arange(s)[None, :]
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    c_kv, k_rope = _mla_kv_latent(p, cfg, x, positions)
    ckv_cache = jax.lax.dynamic_update_slice(
        cache["ckv"], c_kv.astype(cache["ckv"].dtype), (0, q_offset, 0))
    kr_cache = jax.lax.dynamic_update_slice(
        cache["krope"], k_rope.astype(cache["krope"].dtype), (0, q_offset, 0))
    kv_len = q_offset + s
    from repro.models import sharding as SH
    if SH.opt_on("seqkv"):
        # absorbed latent attention over the seq-sharded compressed cache:
        # scores/PV reduce over the sharded seq dim; no decompression of
        # the whole cache and no all-gather ("seqkv" optimization).
        out = _mla_absorbed_attn(p, cfg, q_nope, q_rope, ckv_cache,
                                 kr_cache, q_offset=q_offset,
                                 kv_len=kv_len, window=window)
        out = out.reshape(b, s, -1).astype(x.dtype) @ p["wo"]
        return out, {"ckv": ckv_cache, "krope": kr_cache}
    # decompress the *valid prefix* lazily per flash block would need a
    # custom kernel; for the model path decompress the written cache.
    s_cache = ckv_cache.shape[1]
    kvb = (ckv_cache @ p["wkv_b"]).reshape(
        b, s_cache, h, m.qk_nope_head_dim + m.v_head_dim)
    k_nope, v = jnp.split(kvb, [m.qk_nope_head_dim], axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(kr_cache[:, :, None, :],
                                  (b, s_cache, h, m.qk_rope_head_dim))],
        axis=-1)
    out = flash_attn(q, k, v, causal=True, q_offset=q_offset,
                     window=window, kv_len=kv_len)
    out = out.reshape(b, s, -1) @ p["wo"]
    return out, {"ckv": ckv_cache, "krope": kr_cache}


def _mla_absorbed_attn(p, cfg, q_nope, q_rope, ckv_cache, kr_cache, *,
                       q_offset, kv_len, window: int = 0):
    """Absorbed MLA attention for a chunk of queries directly in the
    compressed latent space.  q_nope/q_rope: (b, sq, h, ·);
    caches: (b, S, lora) / (b, S, rope).  Returns (b, sq, h, v)."""
    from repro.models import sharding as SH
    m = cfg.mla
    b, sq, h, _ = q_nope.shape
    s_cache = ckv_cache.shape[1]
    ckv_cache = SH.seq_constrain(ckv_cache, 1)
    kr_cache = SH.seq_constrain(kr_cache, 1)
    w_uk, w_uv = _mla_absorb(p, cfg)
    f32 = jnp.float32
    # bf16 stays bf16 on the wire; accumulation in f32 via
    # preferred_element_type (halves any cache gather traffic)
    q_lat = jnp.einsum("bqhd,lhd->bqhl", q_nope, w_uk,
                       preferred_element_type=f32)
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    # NOTE: scores contract the (head-sharded) q_lat against the
    # (seq-sharded) latent — one of the two must reshard; gathering the
    # ~14x-compressed latent (bf16) is the cheap direction, so we do NOT
    # pin scores to the seq shard here (§Perf iteration 2).
    scores = (jnp.einsum("bqhl,bsl->bhqs", q_lat.astype(ckv_cache.dtype),
                         ckv_cache, preferred_element_type=f32)
              + jnp.einsum("bqhr,bsr->bhqs", q_rope, kr_cache,
                           preferred_element_type=f32)) * scale
    q_pos = q_offset + jnp.arange(sq)
    k_pos = jnp.arange(s_cache)
    mask = (q_pos[:, None] >= k_pos[None, :]) & (k_pos[None, :] < kv_len)
    if window:
        mask = mask & (k_pos[None, :] > q_pos[:, None] - window)
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    pattn = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhqs,bsl->bqhl", pattn.astype(ckv_cache.dtype),
                       ckv_cache, preferred_element_type=f32)
    return jnp.einsum("bqhl,lhv->bqhv", o_lat.astype(w_uv.dtype), w_uv,
                      preferred_element_type=f32)


def mla_decode(p: dict, cfg: ModelConfig, x: jnp.ndarray, cache: dict,
               pos, *, window: int = 0):
    """Absorbed one-token MLA decode: score/attend in the latent space.

    q_nope is absorbed through W_uk so scores are computed directly against
    the (b, S, lora) latent — per-step FLOPs O(S * lora) instead of
    O(S * h * qk), and the cache read is the compressed latent only.
    """
    m = cfg.mla
    b = x.shape[0]
    positions = jnp.full((b, 1), pos)
    q_nope, q_rope = _mla_q(p, cfg, x, positions)          # (b,1,h,·)
    c_kv, k_rope = _mla_kv_latent(p, cfg, x, positions)
    ckv_cache = jax.lax.dynamic_update_slice(
        cache["ckv"], c_kv.astype(cache["ckv"].dtype), (0, pos, 0))
    kr_cache = jax.lax.dynamic_update_slice(
        cache["krope"], k_rope.astype(cache["krope"].dtype), (0, pos, 0))
    w_uk, w_uv = _mla_absorb(p, cfg)       # (lora, h, nope) / (lora, h, v)
    q_lat = jnp.einsum("bqhd,lhd->bqhl", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))           # (b,1,h,lora)
    s_cache = ckv_cache.shape[1]
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    scores = (jnp.einsum("bqhl,bsl->bhqs", q_lat,
                         ckv_cache.astype(jnp.float32))
              + jnp.einsum("bqhr,bsr->bhqs", q_rope.astype(jnp.float32),
                           kr_cache.astype(jnp.float32))) * scale
    idx = jnp.arange(s_cache)
    valid = idx <= pos
    if window:
        valid &= idx > pos - window
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    pattn = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhqs,bsl->bqhl", pattn,
                       ckv_cache.astype(jnp.float32))      # (b,1,h,lora)
    out = jnp.einsum("bqhl,lhv->bqhv", o_lat, w_uv.astype(jnp.float32))
    out = out.reshape(b, 1, -1).astype(x.dtype) @ p["wo"]
    return out, {"ckv": ckv_cache, "krope": kr_cache}


def _mla_absorb(p: dict, cfg: ModelConfig):
    """Split wkv_b into the absorbed up-projections (W_uk, W_uv)."""
    m = cfg.mla
    wkv_b = p["wkv_b"].reshape(m.kv_lora_rank, cfg.n_heads,
                               m.qk_nope_head_dim + m.v_head_dim)
    return wkv_b[:, :, :m.qk_nope_head_dim], wkv_b[:, :, m.qk_nope_head_dim:]


def mla_prefill_paged(p: dict, cfg: ModelConfig, x: jnp.ndarray,
                      ckv_layer: jnp.ndarray, kr_layer: jnp.ndarray, *,
                      positions, q_offset, kv_len, block_tables,
                      pages_idx, offs_idx, window: int = 0):
    """Fused chunk prefill against one layer's paged LATENT pool.

    x: (segs, sq, d) packed segments; ckv_layer: (n_pages, page, lora)
    compressed-latent pages; kr_layer: (n_pages, page, rope) decoupled
    RoPE keys.  The chunk's latent is scattered into the pool, then the
    segments attend in ABSORBED form against the block-table gather of
    the latent — never decompressing the cache to per-head K/V (the
    gather moves the ~14x-compressed latent only).  Returns
    (attn_out, ckv_layer, kr_layer).
    """
    m = cfg.mla
    b, s, _ = x.shape
    q_nope, q_rope = _mla_q(p, cfg, x, positions)
    c_kv, k_rope = _mla_kv_latent(p, cfg, x, positions)
    ckv_layer = ckv_layer.at[pages_idx, offs_idx].set(
        c_kv.astype(ckv_layer.dtype))
    kr_layer = kr_layer.at[pages_idx, offs_idx].set(
        k_rope.astype(kr_layer.dtype))
    n_pages, page, lora = ckv_layer.shape
    n_slots = block_tables.shape[1]
    ckv_seq = ckv_layer[block_tables].reshape(b, n_slots * page, lora)
    kr_seq = kr_layer[block_tables].reshape(b, n_slots * page, -1)
    w_uk, w_uv = _mla_absorb(p, cfg)
    f32 = jnp.float32
    q_lat = jnp.einsum("bqhd,lhd->bqhl", q_nope.astype(f32),
                       w_uk.astype(f32))
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    scores = (jnp.einsum("bqhl,bsl->bhqs", q_lat, ckv_seq.astype(f32))
              + jnp.einsum("bqhr,bsr->bhqs", q_rope.astype(f32),
                           kr_seq.astype(f32))) * scale
    k_pos = jnp.arange(n_slots * page)
    mask = (positions[:, :, None] >= k_pos[None, None, :]) \
        & (k_pos[None, None, :] < kv_len[:, None, None])
    if window:
        mask = mask & (k_pos[None, None, :] > positions[:, :, None] - window)
    scores = jnp.where(mask[:, None], scores, NEG_INF)
    pattn = jax.nn.softmax(scores, axis=-1)
    o_lat = jnp.einsum("bhqs,bsl->bqhl", pattn, ckv_seq.astype(f32))
    out = jnp.einsum("bqhl,lhv->bqhv", o_lat, w_uv.astype(f32))
    out = out.reshape(b, s, -1).astype(x.dtype) @ p["wo"]
    return out, ckv_layer, kr_layer


def mla_decode_paged(p: dict, cfg: ModelConfig, x: jnp.ndarray,
                     ckv_layer: jnp.ndarray, kr_layer: jnp.ndarray, *,
                     pos, pages, offs, block_tables, lens,
                     window: int = 0):
    """Batched one-token MLA decode against one layer's latent pool via
    the Pallas paged-MLA kernel: queries are absorbed through W_uk on
    the way in, the kernel streams latent pages and accumulates o_lat in
    the latent space, and W_uv up-projects once on the way out.
    Returns (attn_out, ckv_layer, kr_layer)."""
    from repro.kernels import ops
    m = cfg.mla
    b = x.shape[0]
    positions = pos[:, None]
    q_nope, q_rope = _mla_q(p, cfg, x, positions)          # (b,1,h,·)
    c_kv, k_rope = _mla_kv_latent(p, cfg, x, positions)
    ckv_layer = ckv_layer.at[pages, offs].set(
        c_kv[:, 0].astype(ckv_layer.dtype))
    kr_layer = kr_layer.at[pages, offs].set(
        k_rope[:, 0].astype(kr_layer.dtype))
    w_uk, w_uv = _mla_absorb(p, cfg)
    f32 = jnp.float32
    q_lat = jnp.einsum("bhd,lhd->bhl", q_nope[:, 0].astype(f32),
                       w_uk.astype(f32))
    scale = (m.qk_nope_head_dim + m.qk_rope_head_dim) ** -0.5
    o_lat = ops.mla_decode_attention(
        q_lat, q_rope[:, 0].astype(f32), ckv_layer, kr_layer,
        block_tables, lens, scale=scale, window=window)
    out = jnp.einsum("bhl,lhv->bhv", o_lat.astype(f32), w_uv.astype(f32))
    out = out.reshape(b, 1, -1).astype(x.dtype) @ p["wo"]
    return out, ckv_layer, kr_layer


# ---------------------------------------------------------------------------
# Cross-attention (VLM image layers / whisper encoder-decoder)
# ---------------------------------------------------------------------------
def init_cross(key, cfg: ModelConfig, dtype) -> dict:
    d, h, kvh, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    sc = d ** -0.5
    return {
        "wq": jax.random.normal(ks[0], (d, h * hd), dtype) * sc,
        "wk": jax.random.normal(ks[1], (d, kvh * hd), dtype) * sc,
        "wv": jax.random.normal(ks[2], (d, kvh * hd), dtype) * sc,
        "wo": jax.random.normal(ks[3], (h * hd, d), dtype) * (h * hd) ** -0.5,
    }


def cross_kv(p: dict, cfg: ModelConfig, enc: jnp.ndarray):
    """Precompute cross K/V from frontend embeddings (prefilled once,
    shipped to decode instances with the self KV)."""
    b, s, _ = enc.shape
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    k = (enc @ p["wk"]).reshape(b, s, kvh, hd)
    v = (enc @ p["wv"]).reshape(b, s, kvh, hd)
    return k, v


def cross_forward(p: dict, cfg: ModelConfig, x: jnp.ndarray,
                  k: jnp.ndarray, v: jnp.ndarray) -> jnp.ndarray:
    b, s, _ = x.shape
    h, hd = cfg.n_heads, cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(b, s, h, hd)
    # kv_len masks the zero padding flash_attn's kv blocking appends
    # (enc_ctx is usually far below block_kv) — without it the pad
    # tokens dilute the non-causal softmax
    out = flash_attn(q, k, v, causal=False, kv_len=k.shape[1])
    return out.reshape(b, s, -1) @ p["wo"]


def cross_prefill_paged(p: dict, cfg: ModelConfig, x: jnp.ndarray,
                        k_pool: jnp.ndarray, v_pool: jnp.ndarray, layer, *,
                        enc_h, cross_bt, cross_len, cross_pg, cross_off):
    """Cross-attention sublayer of one fused paged prefill chunk.

    x: (segs, sq, d) normed decoder activations; enc_h: (segs, enc_ctx,
    d) encoder output per segment; cross_bt: (segs, cross_slots) the
    read-only cross block table; cross_pg/cross_off: (segs, enc_ctx)
    physical (page, in-page) slot for the one-shot cross-KV write into
    layer ``layer`` of the pool — segments past their request's first
    chunk point these at the scratch page, so the encoder K/V is
    prefilled exactly once per request.  The read is non-causal: every
    decoder query attends all ``cross_len`` encoder tokens through the
    block table.  Returns (attn_out, k_pool, v_pool)."""
    b, e, _ = enc_h.shape
    ck, cv = cross_kv(p, cfg, enc_h)
    k_pool = k_pool.at[layer, cross_pg, cross_off].set(
        ck.reshape(b, e, -1).astype(k_pool.dtype))
    v_pool = v_pool.at[layer, cross_pg, cross_off].set(
        cv.reshape(b, e, -1).astype(v_pool.dtype))
    return cross_attend_paged(p, cfg, x, k_pool, v_pool, layer,
                              cross_bt=cross_bt, cross_len=cross_len)


def cross_attend_paged(p: dict, cfg: ModelConfig, x: jnp.ndarray,
                       k_pool: jnp.ndarray, v_pool: jnp.ndarray, layer, *,
                       cross_bt, cross_len):
    """Read-only cross-attention sublayer of a fused paged prefill
    chunk: the chunk carries NO encoder work — every segment's cross
    pages already hold their encoder K/V (the request's first chunk
    scattered them earlier, or they were aliased from the cross-page
    cache), so the encoder stack and the one-shot scatter are skipped
    entirely.  Same read as ``cross_prefill_paged``.
    Returns (attn_out, k_pool, v_pool)."""
    from repro.kernels import ops
    b, s, _ = x.shape
    h, hd = cfg.n_heads, cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(b, s, h, hd)
    out = ops.prefill_attention(
        q, k_pool, v_pool, cross_len, jnp.zeros_like(cross_len),
        block_table=cross_bt, layer=layer, causal=False)
    return out.reshape(b, s, -1) @ p["wo"], k_pool, v_pool


def cross_decode_paged(p: dict, cfg: ModelConfig, x: jnp.ndarray,
                       k_pool: jnp.ndarray, v_pool: jnp.ndarray, layer, *,
                       cross_bt, cross_len):
    """Batched one-token cross attention against the read-only cross
    pages of layer ``layer`` — no scatter: the encoder K/V was installed
    at admission and never changes.  x: (slots, 1, d); cross_bt:
    (slots, cross_slots); cross_len: (slots,) encoder tokens per slot
    (0 for empty slots).  Returns (attn_out, k_pool, v_pool)."""
    from repro.kernels import ops
    b = x.shape[0]
    h, hd = cfg.n_heads, cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(b, 1, h, hd)
    out = ops.cross_decode_attention(q[:, 0], k_pool, v_pool, layer,
                                     cross_bt, cross_len)
    return out.reshape(b, 1, -1) @ p["wo"], k_pool, v_pool
