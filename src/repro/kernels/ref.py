"""Pure-jnp oracles for the Pallas kernels (tests assert allclose)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def ref_chunked_prefill_attention(q, k_cache, v_cache, kv_len, q_offset, *,
                                  window: int = 0, causal: bool = True):
    """Oracle for kernels.chunked_prefill_attention (naive softmax)."""
    b, sq, h, hd = q.shape
    _, skv, kvh, hd_v = v_cache.shape
    rep = h // kvh
    qf = q.astype(jnp.float32).reshape(b, sq, kvh, rep, hd)
    kf = k_cache.astype(jnp.float32)
    vf = v_cache.astype(jnp.float32)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qf, kf) * hd ** -0.5
    q_pos = q_offset[0] + jnp.arange(sq)
    k_pos = jnp.arange(skv)
    mask = k_pos[None, None, :] < kv_len[:, None, None]     # (b,1,skv)
    if causal:
        mask = mask & (q_pos[None, :, None] >= k_pos[None, None, :])
    if window:
        mask = mask & (k_pos[None, None, :] > q_pos[None, :, None] - window)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", p, vf)
    return out.reshape(b, sq, h, hd_v).astype(q.dtype)


def _gather_pages(pool, layer, block_table, kvh):
    """Layer ``layer`` of an (L, n_pages, page, kvh * hd) pool gathered
    through ``block_table`` into dense (b, n_slots * page, kvh, hd)."""
    b, n_slots = block_table.shape
    _, _, page, w = pool.shape
    return pool[layer][block_table].reshape(b, n_slots * page, kvh,
                                            w // kvh)


def ref_paged_prefill_attention(q, k_pool, v_pool, layer, block_table,
                                kv_len, q_offset, *, window: int = 0,
                                causal: bool = True):
    """Oracle for kernels.paged_prefill_attention: gather each segment's
    pages densely, then run the dense chunked-prefill oracle with
    per-segment ``q_offset``."""
    b, sq, h, hd = q.shape
    kvh = k_pool.shape[3] // hd
    rep = h // kvh
    k = _gather_pages(k_pool, layer, block_table, kvh)
    v = _gather_pages(v_pool, layer, block_table, kvh)
    hd_v = v.shape[3]
    qf = q.astype(jnp.float32).reshape(b, sq, kvh, rep, hd)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qf, k.astype(jnp.float32)) \
        * hd ** -0.5
    q_pos = q_offset[:, None] + jnp.arange(sq)[None, :]       # (b, sq)
    k_pos = jnp.arange(k.shape[1])
    mask = k_pos[None, None, :] < kv_len[:, None, None]       # (b,1,K)
    if causal:
        mask = mask & (q_pos[:, :, None] >= k_pos[None, None, :])
    if window:
        mask = mask & (k_pos[None, None, :] > q_pos[:, :, None] - window)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", p, v.astype(jnp.float32))
    return out.reshape(b, sq, h, hd_v).astype(q.dtype)


def ref_paged_decode_attention(q, k_pool, v_pool, layer, block_table,
                               lens, *, window: int = 0):
    """Oracle for kernels.paged_decode_attention: gather pages densely,
    then masked single-token attention."""
    b, h, hd = q.shape
    kvh = k_pool.shape[3] // hd
    rep = h // kvh
    k = _gather_pages(k_pool, layer, block_table, kvh)
    v = _gather_pages(v_pool, layer, block_table, kvh)
    hd_v = v.shape[3]
    qf = q.astype(jnp.float32).reshape(b, kvh, rep, hd)
    s = jnp.einsum("bgrd,bkgd->bgrk", qf, k.astype(jnp.float32)) * hd ** -0.5
    tok = jnp.arange(k.shape[1])
    mask = tok[None, :] < lens[:, None]
    if window:
        mask = mask & (tok[None, :] > lens[:, None] - 1 - window)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrk,bkgd->bgrd", p, v.astype(jnp.float32))
    return out.reshape(b, h, hd_v).astype(q.dtype)


def ref_paged_cross_decode_attention(q, k_pool, v_pool, layer,
                                     block_table, enc_lens):
    """Oracle for kernels.paged_cross_decode_attention: gather the cross
    pages densely, non-causal masked attention over the encoder length."""
    b, h, hd = q.shape
    kvh = k_pool.shape[3] // hd
    rep = h // kvh
    k = _gather_pages(k_pool, layer, block_table, kvh)
    v = _gather_pages(v_pool, layer, block_table, kvh)
    hd_v = v.shape[3]
    qf = q.astype(jnp.float32).reshape(b, kvh, rep, hd)
    s = jnp.einsum("bgrd,bkgd->bgrk", qf, k.astype(jnp.float32)) * hd ** -0.5
    tok = jnp.arange(k.shape[1])
    mask = tok[None, :] < enc_lens[:, None]
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrk,bkgd->bgrd", p, v.astype(jnp.float32))
    return out.reshape(b, h, hd_v).astype(q.dtype)


def ref_paged_mla_decode_attention(q_lat, q_rope, ckv_pool, kr_pool,
                                   block_table, lens, *, scale: float,
                                   window: int = 0):
    """Oracle for kernels.paged_mla_decode_attention: gather latent pages
    densely, absorbed scores (latent + RoPE terms), masked softmax, PV in
    the latent space."""
    b, h, lora = q_lat.shape
    n_pages, page, rope = kr_pool.shape
    n_slots = block_table.shape[1]
    ckv = ckv_pool[block_table].reshape(b, n_slots * page, lora)
    kr = kr_pool[block_table].reshape(b, n_slots * page, rope)
    s = (jnp.einsum("bhl,bkl->bhk", q_lat.astype(jnp.float32),
                    ckv.astype(jnp.float32))
         + jnp.einsum("bhr,bkr->bhk", q_rope.astype(jnp.float32),
                      kr.astype(jnp.float32))) * scale
    tok = jnp.arange(n_slots * page)
    mask = tok[None, :] < lens[:, None]
    if window:
        mask = mask & (tok[None, :] > lens[:, None] - 1 - window)
    s = jnp.where(mask[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhk,bkl->bhl", p, ckv.astype(jnp.float32))
    return out.astype(q_lat.dtype)
