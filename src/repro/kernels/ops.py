"""Public jit'd wrappers for the Pallas kernels.

On the CPU backend (the test suite) the kernels execute in
``interpret=True`` mode; on a TPU they compile to Mosaic.  Any other
backend is an error, never a silent fall back to interpretation.  The
engines call these — never ``pallas_call`` directly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.chunked_prefill_attention import chunked_prefill_attention
from repro.kernels.paged_cross_decode_attention import (
    paged_cross_decode_attention)
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.kernels.paged_mla_decode_attention import paged_mla_decode_attention
from repro.kernels.paged_prefill_attention import paged_prefill_attention


@functools.cache
def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels compile for TPU or run interpreted on the "
            f"CPU; JAX backend {backend!r} is neither")
    return backend == "cpu"


def prefill_attention(q, k_cache, v_cache, kv_len, q_offset, *,
                      block_table=None, layer=None, window: int = 0,
                      causal: bool = True, block_q: int = 0,
                      block_kv: int = 0):
    """Chunked-prefill attention.

    Dense form (``block_table=None``): k_cache/v_cache are per-request
    (b, skv, kvh, hd) caches and ``q_offset`` is a (1,) shared chunk start.

    Paged form: k_cache/v_cache are the shared page pools
    (L, n_pages, page, kvh * hd) of which ``layer`` is read,
    ``block_table`` is (b, n_slots) physical page ids and
    ``q_offset``/``kv_len`` are per-segment (b,) scalars — one fused
    call covers a whole multi-request chunk.
    """
    if block_table is not None:
        kwargs = {"block_q": block_q} if block_q else {}
        return paged_prefill_attention(
            q, k_cache, v_cache, jnp.asarray(layer),
            jnp.asarray(block_table),
            jnp.asarray(kv_len), jnp.asarray(q_offset),
            window=window, causal=causal, interpret=_interpret(), **kwargs)
    kwargs = {}
    if block_q:
        kwargs["block_q"] = block_q
    if block_kv:
        kwargs["block_kv"] = block_kv
    return chunked_prefill_attention(
        q, k_cache, v_cache, jnp.asarray(kv_len), jnp.asarray(q_offset),
        window=window, causal=causal, interpret=_interpret(), **kwargs)


def decode_attention(q, k_pool, v_pool, layer, block_table, lens, *,
                     window: int = 0):
    """One query per request against layer ``layer`` of the
    (L, n_pages, page, kvh * hd) pools."""
    return paged_decode_attention(
        q, k_pool, v_pool, jnp.asarray(layer), block_table,
        jnp.asarray(lens), window=window, interpret=_interpret())


def cross_decode_attention(q, k_pool, v_pool, layer, block_table,
                           enc_lens):
    """Non-causal decode attention over the read-only cross pages
    (encoder K/V) of layer ``layer`` via the per-request cross block
    table."""
    return paged_cross_decode_attention(
        q, k_pool, v_pool, jnp.asarray(layer), block_table,
        jnp.asarray(enc_lens), interpret=_interpret())


def mla_decode_attention(q_lat, q_rope, ckv_pool, kr_pool, block_table,
                         lens, *, scale: float, window: int = 0):
    """Absorbed MLA decode over the paged latent pool: scores/PV run in
    the compressed latent space; the caller up-projects the returned
    (b, h, lora) through W_uv."""
    return paged_mla_decode_attention(
        q_lat, q_rope, ckv_pool, kr_pool, block_table, jnp.asarray(lens),
        scale=scale, window=window, interpret=_interpret())
