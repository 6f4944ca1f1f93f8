"""Pallas TPU kernel: chunked-prefill flash attention over a PAGED cache.

The serving-path companion of ``chunked_prefill_attention``: instead of a
dense per-request (b, skv, kvh, hd) cache, K/V live in the shared device
page pool (n_pages, page, kvh, hd) and each packed segment addresses its
pages through a block table.  This is what lets one fused call execute a
whole fixed-size chunk whose segments belong to *different* requests —
the batch dim is "segments of the current chunk", each with its own
``q_offset`` (absolute position of the segment start) and ``kv_len``
(valid tokens after this segment is appended).

TPU adaptation: the block table is a scalar-prefetch operand, so the K/V
BlockSpec ``index_map`` resolves the physical page for each
(segment, page-slot) grid step and Pallas streams exactly the live pages
HBM->VMEM — the kv block size IS the page size.  Blocks carry ALL heads,
so Mosaic's rule that the last two block dims divide by (8, 128) or
equal the array's holds at any head count (a one-head block over
qwen2's 14 heads is refused): a page block is (page, kvh, hd) of the
pool as it lies, and q / out go head-major around the call, so a q block
is (h, block_q, hd) and each head's (block_q, hd) slice is a plain tile
(Mosaic refuses a bf16 store into one head of a (block_q, h, 64) block).
The kernel loops over heads statically; per-head online-softmax state
lives in VMEM scratch and carries across the page grid dim.

Grid: (segments, q_blocks, page_slots); page slots innermost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_Q = 128


def _kernel(bt_ref, kv_len_ref, q_off_ref,  # scalar prefetch
            q_ref, k_ref, v_ref,            # VMEM blocks
            o_ref,                          # VMEM out block
            m_ref, l_ref, acc_ref,          # VMEM scratch
            *, block_q: int, page_size: int, n_slots: int, rep: int,
            window: int, causal: bool):
    bi = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = kv_len_ref[bi]
    q_off = q_off_ref[bi]

    # skip pages beyond the valid length / entirely a-causal pages / pages
    # wholly outside the sliding window of every query in this q block
    blk_k_min = ki * page_size
    blk_q_max = q_off + (qi + 1) * block_q - 1
    live = blk_k_min < kv_len
    if causal:
        live = jnp.logical_and(live, blk_k_min <= blk_q_max)
    if window:
        blk_q_min = q_off + qi * block_q
        live = jnp.logical_and(
            live, blk_k_min + page_size - 1 > blk_q_min - window)

    @pl.when(live)
    def _update():
        q_pos = q_off + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, page_size), 0)
        k_pos = ki * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, page_size), 1)
        mask = k_pos < kv_len
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        if window:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        # operands stay in the pool dtype (bf16 feeds the MXU directly);
        # f32 pools ask for the full-precision contraction
        prec = (jax.lax.Precision.HIGHEST if k_ref.dtype == jnp.float32
                else jax.lax.Precision.DEFAULT)
        scale = q_ref.shape[-1] ** -0.5
        for g in range(k_ref.shape[2]):                  # kv heads
            k = k_ref[0, :, g, :]                        # (page, hd)
            v = v_ref[0, :, g, :]                        # (page, hd_v)
            for hi in range(g * rep, (g + 1) * rep):     # its q heads
                q = q_ref[0, hi].astype(k.dtype)         # (bq, hd)
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())), precision=prec,
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(mask, s, NEG_INF)
                m_prev = m_ref[hi]                       # (bq, 1)
                m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
                p = jnp.exp(s - m_new)
                corr = jnp.exp(m_prev - m_new)
                l_ref[hi] = l_ref[hi] * corr + p.sum(axis=1, keepdims=True)
                acc_ref[hi] = acc_ref[hi] * corr + jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    precision=prec, preferred_element_type=jnp.float32)
                m_ref[hi] = m_new

    @pl.when(ki == n_slots - 1)
    def _finalize():
        for hi in range(o_ref.shape[1]):
            l = jnp.maximum(l_ref[hi], 1e-20)
            o_ref[0, hi] = (acc_ref[hi] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("window", "causal", "block_q", "interpret"))
def paged_prefill_attention(
        q: jnp.ndarray, k_pool: jnp.ndarray, v_pool: jnp.ndarray,
        block_table: jnp.ndarray, kv_len: jnp.ndarray,
        q_offset: jnp.ndarray, *,
        window: int = 0, causal: bool = True,
        block_q: int = DEFAULT_BLOCK_Q,
        interpret: bool = False) -> jnp.ndarray:
    """q: (segs, sq, h, hd); k_pool/v_pool: (n_pages, page, kvh, hd) with
    each segment's tokens already scattered into its pages; block_table:
    (segs, n_slots) physical page ids (pad slots may repeat a live or
    scratch page — masked by ``kv_len``); kv_len: (segs,) valid tokens
    after the segment append; q_offset: (segs,) absolute position of each
    segment's first query.  Returns (segs, sq, h, hd_v)."""
    b, sq, h, hd = q.shape
    n_pages, page_size, kvh, hd_v = v_pool.shape
    n_slots = block_table.shape[1]
    block_q = min(block_q, sq)
    assert sq % block_q == 0, (sq, block_q)
    nq = sq // block_q

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, nq, n_slots),
        in_specs=[
            pl.BlockSpec((1, h, block_q, hd),
                         lambda bi, qi, ki, *_: (bi, 0, qi, 0)),
            pl.BlockSpec((1, page_size, kvh, hd),
                         lambda bi, qi, ki, bt, *_: (bt[bi, ki], 0, 0, 0)),
            pl.BlockSpec((1, page_size, kvh, hd_v),
                         lambda bi, qi, ki, bt, *_: (bt[bi, ki], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, h, block_q, hd_v),
                               lambda bi, qi, ki, *_: (bi, 0, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, block_q, 1), jnp.float32),
            pltpu.VMEM((h, block_q, 1), jnp.float32),
            pltpu.VMEM((h, block_q, hd_v), jnp.float32),
        ])
    kern = functools.partial(
        _kernel, block_q=block_q, page_size=page_size, n_slots=n_slots,
        rep=h // kvh, window=window, causal=causal)
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd_v), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), kv_len.astype(jnp.int32),
      q_offset.astype(jnp.int32), q.transpose(0, 2, 1, 3), k_pool, v_pool)
    return out.transpose(0, 2, 1, 3)
