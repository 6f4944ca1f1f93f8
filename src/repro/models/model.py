"""Model assembly: embeddings, scan-over-layers stack, heads, caches.

Entry points (all pure functions of (params, cfg, ...)):
  * ``init_params``      — random init (smoke/runtime scale)
  * ``abstract_params``  — ShapeDtypeStruct params via eval_shape (dry-run)
  * ``init_cache``       — per-layer decode/prefill cache pytree
  * ``forward_train``    — full-sequence logits (+ MoE aux loss)
  * ``prefill``          — one chunk: logits of last position + cache
  * ``prefill_chunked``  — the paper's fixed-size chunk loop (lax.scan)
  * ``decode_step``      — one token per request, per-request positions
  * ``classify``         — length-predictor classification head

Paged serving entry points (the engines' default execution backend —
attention runs through the Pallas kernels in ``kernels/ops.py`` against
a shared device page pool instead of per-request dense caches):
  * ``paged_supported``  — whether a config can use the paged backend
  * ``prefill_paged``    — one WHOLE fixed-size chunk as a single fused
                           call: segments of multiple requests packed on
                           the batch dim with per-segment q_offset/kv_len
  * ``decode_step_paged``— full-slot-batch decode against the pool via
                           block tables; argmax stays on device
  * ``decode_step_greedy`` — dense decode with on-device argmax (the
                           dense fallback's serving step)

The paged backend covers every uniform-attention config — GQA and MLA
(latent pages), full and sliding-window attention, and cross-attention
archs (VLM / encoder-decoder) whose encoder K/V lives in read-only
cross pages of the same pool.  The dense cache path
(``init_cache``/``prefill``/``decode_step``) remains the substrate for
training, recurrent/hybrid architectures, and the coupled vLLM-style
baseline.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import blocks as B
from repro.models import mlp as MLP
from repro.models import sharding as SH
from repro.models.config import ATTN, CROSS_ATTN, ModelConfig


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_params(key, cfg: ModelConfig) -> Dict[str, Any]:
    cfg.validate()
    dtype = _dtype(cfg)
    d = cfg.d_model
    keys = jax.random.split(key, 8)
    params: Dict[str, Any] = {
        "embed": jax.random.normal(keys[0], (cfg.vocab_size, d),
                                   dtype) * d ** -0.5,
        "final_norm": jnp.ones((d,), dtype),
    }
    if cfg.n_positions:
        params["pos_embed"] = jax.random.normal(
            keys[1], (cfg.n_positions, d), dtype) * d ** -0.5
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            keys[2], (d, cfg.vocab_size), dtype) * d ** -0.5
    if cfg.n_classes:
        params["cls_head"] = jax.random.normal(
            keys[3], (d, cfg.n_classes), dtype) * d ** -0.5

    # prefix / suffix blocks (unrolled).  MoE rule: when cfg.moe is set,
    # prefix blocks are dense (DeepSeek-V2 first-k-dense), others routed.
    pkeys = jax.random.split(keys[4], max(1, len(cfg.prefix)))
    params["prefix"] = tuple(
        B.init_block(pkeys[i], k, cfg, dtype, use_moe=False)
        for i, k in enumerate(cfg.prefix))
    skeys = jax.random.split(keys[5], max(1, len(cfg.suffix)))
    params["suffix"] = tuple(
        B.init_block(skeys[i], k, cfg, dtype, use_moe=cfg.moe is not None)
        for i, k in enumerate(cfg.suffix))

    # scanned body: stacked params, one stack entry per repeat
    if cfg.n_repeats:
        def one_group(k):
            gks = jax.random.split(k, len(cfg.pattern))
            return tuple(
                B.init_block(gks[i], kind, cfg, dtype,
                             use_moe=cfg.moe is not None)
                for i, kind in enumerate(cfg.pattern))
        gkeys = jax.random.split(keys[6], cfg.n_repeats)
        params["body"] = jax.vmap(one_group)(gkeys)
    else:
        params["body"] = ()

    # encoder stack (whisper): bidirectional ATTN blocks, unrolled
    if cfg.is_encoder_decoder:
        ekeys = jax.random.split(keys[7], cfg.encoder.n_layers + 1)
        params["encoder"] = {
            "blocks": tuple(
                B.init_block(ekeys[i], "attn", cfg, dtype, use_moe=False)
                for i in range(cfg.encoder.n_layers)),
            "norm": jnp.ones((d,), dtype),
        }
    return params


def abstract_params(cfg: ModelConfig):
    """ShapeDtypeStruct pytree of params — no allocation (dry-run)."""
    return jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))


def param_count(cfg: ModelConfig) -> int:
    import math
    return sum(math.prod(l.shape)
               for l in jax.tree_util.tree_leaves(abstract_params(cfg)))


def active_param_count(cfg: ModelConfig) -> int:
    """Active params per token (MoE: top_k of routed experts)."""
    total = param_count(cfg)
    if cfg.moe is None:
        return total
    # subtract inactive routed expert params in body+suffix layers
    moe_layers = sum(1 for i, k in enumerate(cfg.layer_kinds)
                     if i >= len(cfg.prefix))
    ff = cfg.moe.expert_ff or cfg.d_ff
    glu = 3 if cfg.mlp_act == "swiglu" else 2
    per_expert = glu * cfg.d_model * ff
    inactive = moe_layers * (cfg.moe.n_experts - cfg.moe.top_k) * per_expert
    return total - inactive


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=None, ring: bool = False) -> Dict[str, Any]:
    dtype = dtype or _dtype(cfg)
    enc_ctx = cfg.encoder.n_ctx if cfg.encoder is not None else 0

    def mk(kind):
        return B.init_block_cache(kind, cfg, batch, max_seq, dtype,
                                  enc_ctx=enc_ctx, ring=ring)
    cache: Dict[str, Any] = {
        "prefix": tuple(mk(k) for k in cfg.prefix),
        "suffix": tuple(mk(k) for k in cfg.suffix),
    }
    if cfg.n_repeats:
        group = tuple(mk(k) for k in cfg.pattern)
        cache["body"] = jax.tree_util.tree_map(
            lambda l: jnp.zeros((cfg.n_repeats,) + l.shape, l.dtype), group)
    else:
        cache["body"] = ()
    return cache


def abstract_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None,
                   ring: bool = False):
    return jax.eval_shape(
        functools.partial(init_cache, cfg, batch, max_seq, dtype, ring))


def _batch_axis(path) -> int:
    """Batch axis of a cache leaf: scanned body leaves carry a leading
    repeats dim, so batch sits at axis 1 there, else axis 0."""
    for e in path:
        if hasattr(e, "key") and str(e.key) == "body":
            return 1
    return 0


def cache_insert(dst_cache, src_cache, slot: int):
    """Copy a batch=1 cache pytree into slot ``slot`` of a slot-batched
    cache with identical structure/seq dims (decode-engine admission)."""
    def ins(path, dst, src):
        ax = _batch_axis(path)
        start = [0] * dst.ndim
        start[ax] = slot
        return jax.lax.dynamic_update_slice(dst, src.astype(dst.dtype),
                                            tuple(start))
    return jax.tree_util.tree_map_with_path(ins, dst_cache, src_cache)


def cache_select(src_cache, slot: int):
    """Extract slot ``slot`` as a batch=1 cache pytree."""
    def sel(path, leaf):
        ax = _batch_axis(path)
        return jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=ax)
    return jax.tree_util.tree_map_with_path(sel, src_cache)


# ---------------------------------------------------------------------------
# layer runner
# ---------------------------------------------------------------------------
def _run_layers(params, cfg: ModelConfig, h, *, mode: str, caches=None,
                pos=None, q_offset=0, enc=None):
    aux = jnp.zeros((), jnp.float32)
    new_caches: Dict[str, Any] = {"prefix": [], "suffix": [], "body": ()}

    def _run_block(kind, p, x, c):
        return B.apply_block(kind, p, cfg, x, mode=mode, cache=c, pos=pos,
                             q_offset=q_offset, enc=enc)

    if mode == "train":
        # per-layer remat: backward stores only layer inputs, recomputes
        # attention/MLP internals — required for 4k-seq training to fit
        run_one = jax.checkpoint(_run_block, static_argnums=(0,))
    else:
        run_one = _run_block

    h = SH.act_constrain(h)
    for i, kind in enumerate(cfg.prefix):
        c = caches["prefix"][i] if caches is not None else None
        h, nc, a = run_one(kind, params["prefix"][i], h, c)
        h = SH.act_constrain(h)
        aux += a
        new_caches["prefix"].append(nc)

    if cfg.n_repeats:
        def body_fn(carry, xs):
            x, aux_c = carry
            if caches is not None:
                gp, gc = xs
            else:
                gp, gc = xs, tuple({} for _ in cfg.pattern)
            ncs = []
            for j, kind in enumerate(cfg.pattern):
                x, nc, a = run_one(kind, gp[j],
                                   x, gc[j] if caches is not None else None)
                x = SH.act_constrain(x)
                aux_c += a
                ncs.append(nc if nc is not None else {})
            return (x, aux_c), tuple(ncs)

        xs = ((params["body"], caches["body"]) if caches is not None
              else params["body"])
        (h, aux), body_caches = jax.lax.scan(body_fn, (h, aux), xs)
        new_caches["body"] = body_caches

    for i, kind in enumerate(cfg.suffix):
        c = caches["suffix"][i] if caches is not None else None
        h, nc, a = run_one(kind, params["suffix"][i], h, c)
        h = SH.act_constrain(h)
        aux += a
        new_caches["suffix"].append(nc)

    new_caches["prefix"] = tuple(new_caches["prefix"])
    new_caches["suffix"] = tuple(new_caches["suffix"])
    return h, (new_caches if caches is not None else None), aux


# ---------------------------------------------------------------------------
# embeddings / heads
# ---------------------------------------------------------------------------
def _embed(params, cfg: ModelConfig, tokens, positions):
    h = jnp.take(params["embed"], tokens, axis=0)
    if cfg.n_positions:
        idx = jnp.minimum(positions, cfg.n_positions - 1)
        h = h + jnp.take(params["pos_embed"], idx, axis=0)
    return h


def _head(params, cfg: ModelConfig, h):
    h = B.rms_norm(h, params["final_norm"], cfg.norm_eps)
    logits = (h @ params["embed"].T if cfg.tie_embeddings
              else h @ params["lm_head"])
    return SH.act_constrain(logits, vocab_dim=True)


def encoder_forward(params, cfg: ModelConfig, enc_embeds):
    """Bidirectional encoder stack over stub-frontend embeddings."""
    h = enc_embeds
    for p in params["encoder"]["blocks"]:
        n = B.rms_norm(h, p["norm1"], cfg.norm_eps)
        from repro.models import attention as A
        b, s, _ = n.shape
        positions = jnp.arange(s)[None, :]
        q, k, v = A.gqa_qkv(p["attn"], cfg, n, positions)
        # kv_len masks the zero padding the kv blocking appends — the
        # bidirectional softmax must span exactly the s real frames
        a = A.flash_attn(q, k, v, causal=False, kv_len=s)
        h = h + a.reshape(b, s, -1) @ p["attn"]["wo"]
        n2 = B.rms_norm(h, p["norm2"], cfg.norm_eps)
        from repro.models import mlp as M
        h = h + M.mlp_forward(p["mlp"], cfg, n2)
    return B.rms_norm(h, params["encoder"]["norm"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------
def forward_train(params, cfg: ModelConfig, tokens, *,
                  enc_embeds=None) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """tokens: (b, s) int32 -> (logits (b,s,V), aux_loss)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    h = _embed(params, cfg, tokens, positions)
    enc = None
    if enc_embeds is not None:
        enc = (encoder_forward(params, cfg, enc_embeds)
               if cfg.is_encoder_decoder else enc_embeds)
    h, _, aux = _run_layers(params, cfg, h, mode="train", enc=enc)
    return _head(params, cfg, h), aux


def prefill(params, cfg: ModelConfig, tokens, cache, *, q_offset=0,
            enc_embeds=None):
    """One prefill chunk. tokens: (b, chunk). Returns (logits_last, cache)."""
    b, s = tokens.shape
    positions = q_offset + jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    h = _embed(params, cfg, tokens, positions)
    enc = None
    if enc_embeds is not None:
        enc = (encoder_forward(params, cfg, enc_embeds)
               if cfg.is_encoder_decoder else enc_embeds)
    h, cache, _ = _run_layers(params, cfg, h, mode="prefill", caches=cache,
                              q_offset=q_offset, enc=enc)
    logits = _head(params, cfg, h[:, -1:])
    return logits, cache


def prefill_chunked(params, cfg: ModelConfig, tokens, cache, *,
                    chunk_size: int, enc_embeds=None):
    """The paper's chunked prefill: fixed-size chunks via lax.scan.

    tokens: (b, S) with S % chunk_size == 0 (pre-padded by the engine).
    The first chunk also prefills encoder/cross KV (enc_embeds).
    """
    b, s = tokens.shape
    assert s % chunk_size == 0, "pad prompts to a multiple of ChunkSize"
    nchunks = s // chunk_size
    enc = None
    if enc_embeds is not None:
        enc = (encoder_forward(params, cfg, enc_embeds)
               if cfg.is_encoder_decoder else enc_embeds)
    chunks = tokens.reshape(b, nchunks, chunk_size).transpose(1, 0, 2)

    def step(cache, xs):
        idx, chunk = xs
        q_offset = idx * chunk_size
        positions = q_offset + jnp.arange(chunk_size)[None, :]
        h = _embed(params, cfg, chunk,
                   jnp.broadcast_to(positions, (b, chunk_size)))
        h, cache, _ = _run_layers(params, cfg, h, mode="prefill",
                                  caches=cache, q_offset=q_offset, enc=enc)
        return cache, h[:, -1]

    cache, last_h = jax.lax.scan(step, cache, (jnp.arange(nchunks), chunks))
    logits = _head(params, cfg, last_h[-1][:, None])
    return logits, cache


def decode_step(params, cfg: ModelConfig, tokens, cache, pos):
    """tokens: (b, 1); pos: (b,) current positions. -> (logits, cache)."""
    h = _embed(params, cfg, tokens, pos[:, None])
    h, cache, _ = _run_layers(params, cfg, h, mode="decode", caches=cache,
                              pos=pos)
    return _head(params, cfg, h), cache


def decode_step_greedy(params, cfg: ModelConfig, tokens, cache, pos):
    """``decode_step`` with token selection folded in: returns
    (next_tokens (b,) int32, cache) so one jitted serving iteration
    transfers a single int per slot instead of (b, vocab) logits."""
    logits, cache = decode_step(params, cfg, tokens, cache, pos)
    return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32), cache


# ---------------------------------------------------------------------------
# paged execution backend (serving hot path)
# ---------------------------------------------------------------------------
def paged_supported(cfg: ModelConfig) -> bool:
    """True if the paged backend can serve this config: uniform
    attention layers over a page pool — plain self-attention (GQA or
    MLA, full or sliding-window) and CROSS_ATTN layers whose encoder
    K/V lives in read-only cross pages of the same pool (VLM and
    encoder-decoder archs).  Only recurrent/hybrid archs stay on the
    dense path; MLA+cross has no arch in the pool and is unhandled."""
    kinds = set(cfg.layer_kinds)
    if not kinds <= {ATTN, CROSS_ATTN}:
        return False
    return not (cfg.mla is not None and CROSS_ATTN in kinds)


def _paged_attn_block(p, cfg: ModelConfig, x, k_pool, v_pool, layer, attn,
                      cross=None):
    """One ATTN/CROSS_ATTN block (norm, attention-vs-pool, optional
    cross-attention-vs-cross-pages, MLP/MoE) on the paged path.
    ``attn(p_attn, h, k_pool, v_pool, layer)`` performs the pool scatter
    + kernel call for the current mode at layer ``layer``;
    ``cross(p_cross, hc, k_pool, v_pool, layer)`` does the same against
    the request's read-only cross block table (CROSS_ATTN blocks only —
    the ``"cross" in p`` check is structural, so non-cross layers trace
    no cross code)."""
    h = B.rms_norm(x, p["norm1"], cfg.norm_eps)
    a, k_pool, v_pool = attn(p["attn"], h, k_pool, v_pool, layer)
    x = x + a
    if cross is not None and "cross" in p:
        hc = B.rms_norm(x, p["norm_c"], cfg.norm_eps)
        ac, k_pool, v_pool = cross(p["cross"], hc, k_pool, v_pool, layer)
        x = x + ac
    h2 = B.rms_norm(x, p["norm2"], cfg.norm_eps)
    if "moe" in p:
        m, _ = MLP.moe_forward(p["moe"], cfg, h2)
    else:
        m = MLP.mlp_forward(p["mlp"], cfg, h2)
    return x + m, k_pool, v_pool


def _one_layer(fn):
    """Run ``fn(p, x, k_layer, v_layer)``, an attention over one
    layer's pool (MLA's latent pages), as a whole-pool ``attn``: slice
    layer ``layer`` out and write it back."""
    def attn(p, x, k_pool, v_pool, layer):
        k_layer = jax.lax.dynamic_index_in_dim(k_pool, layer, 0,
                                               keepdims=False)
        v_layer = jax.lax.dynamic_index_in_dim(v_pool, layer, 0,
                                               keepdims=False)
        a, k_layer, v_layer = fn(p, x, k_layer, v_layer)
        return (a, jax.lax.dynamic_update_index_in_dim(k_pool, k_layer,
                                                       layer, 0),
                jax.lax.dynamic_update_index_in_dim(v_pool, v_layer,
                                                    layer, 0))
    return attn


def _run_layers_paged(params, cfg: ModelConfig, h, k_pool, v_pool, attn,
                      cross=None):
    """Layer runner over the page pools — (L, n_pages, page, kvh * hd)
    K/V for GQA, (L, n_pages, page, width) (latent, rope-key) for MLA:
    prefix and suffix unrolled, body scanned.  Each block gets the whole
    pools and its absolute layer id, so the engines' PagePool layout is
    position-stable and the GQA kernels read and write the pool in
    place.  CROSS_ATTN layers additionally run ``cross`` against the
    same layer (self and cross pages share the pool; the tables are
    distinct)."""
    npre = len(cfg.prefix)
    pat = len(cfg.pattern)

    def one(p_block, h, k_pool, v_pool, layer):
        h, k_pool, v_pool = _paged_attn_block(p_block, cfg, h, k_pool,
                                              v_pool, layer, attn, cross)
        return SH.act_constrain(h), k_pool, v_pool

    h = SH.act_constrain(h)
    for i in range(npre):
        h, k_pool, v_pool = one(params["prefix"][i], h, k_pool, v_pool, i)
    if cfg.n_repeats:
        def body(carry, xs):
            h, kp, vp = carry
            gp, ridx = xs
            for j in range(pat):
                h, kp, vp = one(gp[j], h, kp, vp, npre + ridx * pat + j)
            return (h, kp, vp), None
        (h, k_pool, v_pool), _ = jax.lax.scan(
            body, (h, k_pool, v_pool),
            (params["body"], jnp.arange(cfg.n_repeats)))
    for i in range(len(cfg.suffix)):
        h, k_pool, v_pool = one(params["suffix"][i], h, k_pool, v_pool,
                                npre + cfg.n_repeats * pat + i)
    return h, k_pool, v_pool


def prefill_paged(params, cfg: ModelConfig, tokens, q_offset, kv_len,
                  last_idx, block_tables, pages_idx, offs_idx,
                  k_pool, v_pool, enc_embeds=None, cross_bt=None,
                  cross_len=None, cross_pg=None, cross_off=None):
    """One WHOLE fixed-size chunk as a single fused call (paper §3.3.3).

    The chunk's segments — slices of *different* requests — are packed on
    the batch dim; every layer scatters the chunk's K/V straight into the
    shared page pool and attends through ``kernels.ops.prefill_attention``
    with per-segment scalars (no per-segment dispatch, no dense caches).

    tokens: (segs, sq) right-padded segment tokens;
    q_offset: (segs,) absolute position of each segment start;
    kv_len: (segs,) valid KV tokens after this segment (q_offset + len);
    last_idx: (segs,) index of each segment's last valid token;
    block_tables: (segs, n_slots) physical page ids (pad slots -> scratch
    page); pages_idx/offs_idx: (segs, sq) physical slot per token;
    k_pool/v_pool: (L, n_pages, page, kvh * hd).

    Cross-attention archs (VLM / enc-dec) thread a SECOND block table:
    enc_embeds: (segs, enc_ctx, d) frontend embeddings (run through the
    encoder stack for enc-dec archs); cross_bt: (segs, cross_slots)
    read-only cross pages; cross_len: (segs,) valid encoder tokens;
    cross_pg/cross_off: (segs, enc_ctx) one-shot cross-KV write slots
    (scratch page for every chunk after a request's first).

    Returns (next_tokens (segs,) int32, last_logits (segs, V),
    k_pool, v_pool) — next_tokens[i] is only meaningful for segments that
    complete their request's prompt.
    """
    sq = tokens.shape[1]
    positions = q_offset[:, None] + jnp.arange(sq)[None, :]
    h = _embed(params, cfg, tokens, positions)
    kw = dict(positions=positions, q_offset=q_offset, kv_len=kv_len,
              block_tables=block_tables, pages_idx=pages_idx,
              offs_idx=offs_idx, window=cfg.sliding_window)
    if cfg.mla is not None:
        attn = _one_layer(lambda p, x, k, v: A.mla_prefill_paged(
            p, cfg, x, k, v, **kw))
    else:
        def attn(p, x, k_pool, v_pool, layer):
            return A.gqa_prefill_paged(p, cfg, x, k_pool, v_pool, layer,
                                       **kw)

    cross = None
    if enc_embeds is not None:
        enc_h = (encoder_forward(params, cfg, enc_embeds)
                 if cfg.is_encoder_decoder else enc_embeds)

        def cross(p, x, k_pool, v_pool, layer):
            return A.cross_prefill_paged(
                p, cfg, x, k_pool, v_pool, layer, enc_h=enc_h,
                cross_bt=cross_bt, cross_len=cross_len,
                cross_pg=cross_pg, cross_off=cross_off)
    elif cross_bt is not None:
        # read-only cross chunk: every segment's cross pages are already
        # written (first chunk ran earlier, or the pages came from the
        # cross cache) — skip the O(enc_ctx²) encoder stack + scatter
        def cross(p, x, k_pool, v_pool, layer):
            return A.cross_attend_paged(p, cfg, x, k_pool, v_pool, layer,
                                        cross_bt=cross_bt,
                                        cross_len=cross_len)

    h, k_pool, v_pool = _run_layers_paged(params, cfg, h, k_pool, v_pool,
                                          attn, cross)
    last_h = jnp.take_along_axis(h, last_idx[:, None, None], axis=1)
    logits = _head(params, cfg, last_h)            # (segs, 1, V)
    next_tok = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
    return next_tok, logits[:, 0], k_pool, v_pool


def sample_tokens(logits, temps, top_ks, seeds):
    """Batched on-device token selection: greedy argmax where
    ``temps == 0``, else a temperature/top-k categorical draw.

    logits: (slots, V); temps: (slots,) float32; top_ks: (slots,) int32
    (0 = no top-k restriction); seeds: (slots,) uint32 per-slot PRNG
    seeds.  Callers derive each seed from (request seed, n_generated) on
    the host, so a request's sample stream is independent of its decode
    slot and of batch composition.  The greedy lane bypasses the
    categorical entirely, so temperature-0 slots stay byte-identical to
    plain ``argmax`` even when they share a batch with sampled slots.
    """
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def draw(lg, temp, k, seed):
        # top-k: keep logits >= the k-th largest (k == 0 keeps all)
        kth = jnp.sort(lg)[::-1][jnp.clip(k - 1, 0, lg.shape[0] - 1)]
        masked = jnp.where((k > 0) & (lg < kth), -jnp.inf, lg)
        safe_t = jnp.where(temp > 0, temp, 1.0)
        key = jax.random.fold_in(jax.random.PRNGKey(0), seed)
        return jax.random.categorical(key, masked / safe_t)

    sampled = jax.vmap(draw)(logits, temps, top_ks, seeds)
    return jnp.where(temps > 0.0, sampled.astype(jnp.int32), greedy)


def decode_step_paged(params, cfg: ModelConfig, tokens, pos, pages, offs,
                      block_tables, lens, k_pool, v_pool,
                      cross_bt=None, cross_len=None,
                      temps=None, top_ks=None, seeds=None):
    """Full-slot-batch decode iteration against the shared page pool.

    tokens: (slots, 1) last emitted token per slot; pos: (slots,) append
    position (== tokens already cached); pages/offs: (slots,) physical
    slot of the appended token (dead slots -> scratch page);
    block_tables: (slots, n_slots); lens: (slots,) valid tokens including
    the append.  Cross-attention archs also stream the request's
    read-only cross pages: cross_bt: (slots, cross_slots); cross_len:
    (slots,) encoder tokens per slot — no cross scatter ever happens at
    decode (the pages were installed once at admission).  Token
    selection stays on device: argmax when ``temps is None``, else
    per-slot temperature/top-k sampling via ``sample_tokens`` (greedy
    slots keep the argmax result exactly).  Returns
    (next_tokens (slots,) int32, k_pool, v_pool).
    """
    h = _embed(params, cfg, tokens, pos[:, None])
    kw = dict(pos=pos, pages=pages, offs=offs, block_tables=block_tables,
              lens=lens, window=cfg.sliding_window)
    if cfg.mla is not None:
        attn = _one_layer(lambda p, x, k, v: A.mla_decode_paged(
            p, cfg, x, k, v, **kw))
    else:
        def attn(p, x, k_pool, v_pool, layer):
            return A.gqa_decode_paged(p, cfg, x, k_pool, v_pool, layer,
                                      **kw)

    cross = None
    if cross_bt is not None:
        def cross(p, x, k_pool, v_pool, layer):
            return A.cross_decode_paged(p, cfg, x, k_pool, v_pool, layer,
                                        cross_bt=cross_bt,
                                        cross_len=cross_len)

    h, k_pool, v_pool = _run_layers_paged(params, cfg, h, k_pool, v_pool,
                                          attn, cross)
    logits = _head(params, cfg, h)                 # (slots, 1, V)
    if temps is None:
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    else:
        next_tok = sample_tokens(logits[:, -1], temps, top_ks, seeds)
    return next_tok, k_pool, v_pool


def classify(params, cfg: ModelConfig, tokens, lengths):
    """Length-predictor head: mean-pool valid tokens -> (b, n_classes)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    h = _embed(params, cfg, tokens, positions)
    h, _, _ = _run_layers(params, cfg, h, mode="train")
    mask = (jnp.arange(s)[None, :] < lengths[:, None]).astype(h.dtype)
    pooled = (h * mask[..., None]).sum(1) / jnp.maximum(
        mask.sum(1, keepdims=True), 1.0)
    return pooled @ params["cls_head"]
