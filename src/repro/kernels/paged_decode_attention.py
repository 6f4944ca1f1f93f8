"""Pallas TPU kernel: paged decode attention (one token vs paged KV).

The decode phase is memory-bound (§2.1): each step streams the whole KV
cache once.  vLLM's PagedAttention CUDA kernel becomes, on TPU, a
scalar-prefetched page gather: the layer and the block table are
scalar-prefetch operands, so the BlockSpec ``index_map`` of the K/V
pools resolves (layer, physical page) for each (request, page-slot)
grid step and Pallas streams exactly the live pages HBM->VMEM — no
gather materialization, and no slice of the layer out of the
``(L, n_pages, page, kvh * hd)`` pool (kernels/pool_lanes.py).

A page block is ``(page, kvh * hd)``; the kv heads are split in VMEM
by lane block, each against its spread query heads.  The
per-(request, head) online-softmax state lives in VMEM scratch and
carries across the page grid dim.

Grid: (batch, n_page_slots) — page slots innermost.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.pool_lanes import (gather_heads, heads_per_block,
                                      spread_heads)

NEG_INF = -1e30


def _kernel(layer_ref, block_table_ref, lens_ref,   # scalar prefetch
            q_ref, k_ref, v_ref,                    # VMEM blocks
            o_ref,                                  # VMEM out
            m_ref, l_ref, acc_ref,                  # VMEM scratch
            *, page_size: int, n_slots: int, scale: float, window: int):
    bi = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = lens_ref[bi]

    # skip pages past the valid length; with a sliding window also skip
    # pages that slid wholly out of it (their table slots may point at
    # freed/scratch pages — never read them)
    live = pi * page_size < length
    if window:
        live = jnp.logical_and(live, (pi + 1) * page_size > length - window)

    @pl.when(live)
    def _update():
        k = k_ref[0, 0].astype(jnp.float32)          # (page, kvh*hd)
        v = v_ref[0, 0].astype(jnp.float32)          # (page, kvh*hd_v)
        n_blk, rows, lb = q_ref.shape[1:]
        lbv = acc_ref.shape[2]
        tok = pi * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (rows, page_size), 1)
        mask = tok < length
        if window:
            # the query sits at position length-1: keep k > q - window
            mask = jnp.logical_and(mask, tok > length - 1 - window)
        for j in range(n_blk):                       # lane blocks
            q = q_ref[0, j].astype(jnp.float32)      # (rows, lb) spread
            s = jax.lax.dot_general(
                q, k[:, j * lb:(j + 1) * lb], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(mask, s, NEG_INF)           # (rows, page)
            m_prev = m_ref[j]                         # (rows, 1)
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[j] = l_ref[j] * corr + p.sum(axis=1, keepdims=True)
            acc_ref[j] = acc_ref[j] * corr + jax.lax.dot_general(
                p, v[:, j * lbv:(j + 1) * lbv], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[j] = m_new

    @pl.when(pi == n_slots - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-20)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(
        q: jnp.ndarray, k_pool: jnp.ndarray, v_pool: jnp.ndarray,
        layer: jnp.ndarray, block_table: jnp.ndarray, lens: jnp.ndarray,
        *, window: int = 0, interpret: bool = False) -> jnp.ndarray:
    """q: (b, h, hd); k_pool/v_pool: (L, n_pages, page, kvh * hd) —
    every layer's pool; layer: scalar layer to read; block_table:
    (b, n_slots) physical page ids (pad slots and slots that slid out of
    ``window`` may point at a scratch page — they are masked/skipped);
    lens: (b,) tokens in cache per request; window: sliding window in
    tokens (0 = unlimited).  Returns (b, h, hd_v)."""
    b, h, hd = q.shape
    _, _, page_size, w = k_pool.shape
    kvh = w // hd
    wv = v_pool.shape[3]
    g = heads_per_block(kvh, hd)
    n_blk, rows = kvh // g, g * h // kvh
    lb, lbv = g * hd, g * wv // kvh
    n_slots = block_table.shape[1]
    qs = spread_heads(q, kvh, g).reshape(b, n_blk, rows, lb)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, n_slots),
        in_specs=[
            pl.BlockSpec((1, n_blk, rows, lb),
                         lambda bi, pi, *_: (bi, 0, 0, 0)),
            pl.BlockSpec((1, 1, page_size, w),
                         lambda bi, pi, ly, bt, ln: (ly[0], bt[bi, pi], 0, 0)),
            pl.BlockSpec((1, 1, page_size, wv),
                         lambda bi, pi, ly, bt, ln: (ly[0], bt[bi, pi], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, n_blk, rows, lbv),
                               lambda bi, pi, *_: (bi, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((n_blk, rows, 1), jnp.float32),
            pltpu.VMEM((n_blk, rows, 1), jnp.float32),
            pltpu.VMEM((n_blk, rows, lbv), jnp.float32),
        ])
    kern = functools.partial(_kernel, page_size=page_size, n_slots=n_slots,
                             scale=hd ** -0.5, window=window)
    out = pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, n_blk, rows, lbv), q.dtype),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32),
      block_table.astype(jnp.int32), lens.astype(jnp.int32),
      qs, k_pool, v_pool)
    return gather_heads(out.reshape(b, h, lbv), kvh, g)
