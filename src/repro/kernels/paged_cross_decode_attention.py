"""Paged CROSS-attention decode (one token vs the read-only encoder
pages).

VLM / encoder-decoder decode attends two KV populations per layer: the
growing self-attention pages and a FIXED set of cross pages holding the
encoder output's K/V (prefilled once per request, never appended to).
The cross pages are ordinary pages of the same pool, addressed by a
second, read-only block table.  The attention is non-causal — every
decode query attends every valid encoder token — so the only mask is
``tok < enc_len`` and there is no sliding-window skip: exactly the
paged-decode kernel with ``lens = enc_lens`` and no window, which this
runs (same grid, page skipping and online softmax).

Because the cross pages are read-only, consecutive decode iterations
stream identical pages; the scatter the self-attention kernel needs per
step never happens here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.paged_decode_attention import paged_decode_attention


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_cross_decode_attention(
        q: jnp.ndarray, k_pool: jnp.ndarray, v_pool: jnp.ndarray,
        layer: jnp.ndarray, block_table: jnp.ndarray,
        enc_lens: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """q: (b, h, hd) one decode query per request; k_pool/v_pool:
    (L, n_pages, page, kvh * hd) — the SHARED pool whose cross pages
    hold the encoder K/V; layer: scalar layer to read; block_table:
    (b, n_slots) the per-request read-only cross block table (pad slots
    may point at a scratch page — masked by ``enc_lens``); enc_lens:
    (b,) valid encoder tokens per request.  Returns (b, h, hd_v)."""
    return paged_decode_attention(q, k_pool, v_pool, layer, block_table,
                                  enc_lens, interpret=interpret)
