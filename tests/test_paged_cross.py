"""Paged cross-attention KV (VLM / encoder-decoder serving) ≡ dense.

The cross pages are read-only pool pages holding the encoder output's
K/V — prefilled once per request, attended through a second block table
by every decoder token, shipped once with the self KV, freed exactly
once.  Like every other paged layout, the path must not change a single
emitted token vs the dense fallback.
"""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.decode_engine import DecodeEngine
from repro.core.prefill_engine import PrefillEngine
from repro.kernels import ops, ref
from repro.kvcache.paged import OutOfPages, PagedAllocator, PagePool
from repro.models import model as M
from repro.runtime.workload import generate

PAGE = 4
KEY = jax.random.PRNGKey(17)


def _mk(shape, k, dtype=jnp.float32):
    return jax.random.normal(jax.random.fold_in(KEY, k), shape, dtype)


@pytest.fixture(scope="module")
def encdec_setup():
    cfg = dataclasses.replace(get_smoke_config("whisper_tiny"),
                              dtype="float32")
    params = M.init_params(jax.random.PRNGKey(3), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def vlm_setup():
    cfg = dataclasses.replace(get_smoke_config("llama_3_2_vision_11b"),
                              dtype="float32")
    params = M.init_params(jax.random.PRNGKey(4), cfg)
    return cfg, params


def _gen(cfg, n, seed, max_prompt=20, max_decode=5):
    return generate("Mixed", n, seed=seed, max_prompt=max_prompt,
                    max_decode=max_decode, vocab_size=cfg.vocab_size,
                    enc_ctx=cfg.cross_ctx, enc_dim=cfg.d_model)


# ---------------------------------------------------------------------------
# kernel sweeps: page-boundary encoder lengths
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
# encoder lengths straddling page boundaries: sub-page, exactly one
# page, one-past, mid-table, exactly full table
@pytest.mark.parametrize("enc_lens", [(1, 3), (4, 5), (16, 31), (64, 64)])
def test_cross_decode_kernel_sweep(dtype, enc_lens):
    """paged_cross_decode_attention vs the dense-gather oracle at
    page-boundary encoder lengths (non-causal, no window)."""
    b, h, kvh, hd, npages, page, nslots = 2, 4, 2, 32, 12, 16, 4
    q = _mk((b, h, hd), 1).astype(dtype)
    kp = _mk((1, npages, page, kvh * hd), 2).astype(dtype)
    vp = _mk((1, npages, page, kvh * hd), 3).astype(dtype)
    bt = jax.random.randint(jax.random.fold_in(KEY, 4), (b, nslots), 0,
                            npages)
    lens = jnp.asarray(enc_lens, jnp.int32)
    out = ops.cross_decode_attention(q, kp, vp, 0, bt, lens)
    exp = ref.ref_paged_cross_decode_attention(q, kp, vp, 0, bt, lens)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    assert out.shape == exp.shape
    assert not bool(jnp.isnan(out.astype(jnp.float32)).any())
    assert float(jnp.abs(out.astype(jnp.float32)
                         - exp.astype(jnp.float32)).max()) < tol


def test_cross_decode_kernel_ignores_pad_slots():
    """Table slots past the encoder length may point at a garbage
    scratch page — they must never reach the softmax."""
    b, h, kvh, hd, npages, page = 1, 4, 2, 32, 4, 8
    q = _mk((b, h, hd), 5)
    kp = _mk((1, npages, page, kvh * hd), 6)
    vp = _mk((1, npages, page, kvh * hd), 7)
    lens = jnp.asarray([8], jnp.int32)           # exactly one page valid
    out_a = ops.cross_decode_attention(q, kp, vp, 0,
                                       jnp.asarray([[0, 1, 2]]), lens)
    out_b = ops.cross_decode_attention(q, kp, vp, 0,
                                       jnp.asarray([[0, 3, 3]]), lens)
    assert float(jnp.abs(out_a - out_b).max()) == 0.0


@pytest.mark.parametrize("enc_len", [3, 16, 17, 48])
def test_cross_prefill_noncausal_kernel(enc_len):
    """The decoder-side cross read during chunked prefill reuses the
    paged prefill kernel with causal=False: every query attends every
    valid encoder token, pad pages skipped — vs the oracle at
    page-boundary encoder lengths."""
    b, sq, h, kvh, hd, npages, page, nslots = 2, 16, 4, 2, 32, 12, 16, 3
    q = _mk((b, sq, h, hd), 8)
    kp = _mk((1, npages, page, kvh * hd), 9)
    vp = _mk((1, npages, page, kvh * hd), 10)
    bt = jax.random.randint(jax.random.fold_in(KEY, 11), (b, nslots), 0,
                            npages)
    lens = jnp.asarray([enc_len, max(1, enc_len - 2)], jnp.int32)
    zero = jnp.zeros_like(lens)
    out = ops.prefill_attention(q, kp, vp, lens, zero, block_table=bt,
                                layer=0, causal=False)
    exp = ref.ref_paged_prefill_attention(q, kp, vp, 0, bt, lens, zero,
                                          causal=False)
    assert not bool(jnp.isnan(out).any())
    assert float(jnp.abs(out - exp).max()) < 2e-5


# ---------------------------------------------------------------------------
# engine-level parity
# ---------------------------------------------------------------------------
def _drain_prefill(pe, reqs):
    for r in reqs:
        pe.submit(r)
    out, t = {}, 0.0
    for _ in range(200):
        for pk in pe.step(t):
            out[pk.req.rid] = pk
        t += 0.01
        if pe.idle():
            break
    return out


def _run_disagg(cfg, params, reqs, backend):
    pe = PrefillEngine("p0", cfg, params, chunk_size=8, max_seq=64,
                       backend=backend, page_size=PAGE, n_pages=128)
    de = DecodeEngine("d0", cfg, params, max_slots=4, max_seq=64,
                      backend=backend, page_size=PAGE, n_pages=128)
    for r in reqs:
        pe.submit(r)
    out, t = {}, 0.0
    for _ in range(2000):
        for pk in pe.step(t):
            de.receive(pk)
        de.admit(t)
        for f in de.step(t):
            out[f.req.rid] = f.tokens
        t += 0.01
        if pe.idle() and de.idle():
            break
    return out, pe, de


def _dense_layer_kv(cfg, cache, layer, key):
    """Dense body-cache leaf for absolute layer id (smoke configs have
    no prefix/suffix): cache["body"][pattern_idx][key][repeat, 0]."""
    j = layer % len(cfg.pattern)
    r = layer // len(cfg.pattern)
    return np.asarray(cache["body"][j][key])[r, 0]


@pytest.mark.parametrize("setup_name", ["encdec_setup", "vlm_setup"])
def test_cross_prefill_parity_tokens_and_pool(setup_name, request):
    """Fused paged prefill ≡ dense prefill for cross archs: same first
    tokens AND the shipped pages hold the same self K/V and encoder
    (cross) K/V the dense cache holds."""
    cfg, params = request.getfixturevalue(setup_name)
    kvh, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    reqs = _gen(cfg, 4, seed=41, max_prompt=30)
    kw = dict(chunk_size=8, max_seq=64, page_size=PAGE, n_pages=128)
    out_p = _drain_prefill(
        PrefillEngine("pp", cfg, params, backend="paged", **kw),
        copy.deepcopy(reqs))
    out_d = _drain_prefill(
        PrefillEngine("pd", cfg, params, backend="dense", **kw),
        copy.deepcopy(reqs))
    assert len(out_p) == len(out_d) == 4
    for rid, pkp in out_p.items():
        pkd = out_d[rid]
        assert pkp.first_token == pkd.first_token
        plen = pkp.req.prompt_len
        assert pkp.enc_len == cfg.cross_ctx
        kp = np.asarray(pkp.pages_k).reshape(cfg.n_layers, -1, kvh, hd)
        ck = np.asarray(pkp.cross_k).reshape(cfg.n_layers, -1, kvh, hd)
        cv = np.asarray(pkp.cross_v).reshape(cfg.n_layers, -1, kvh, hd)
        for layer, kind in enumerate(cfg.layer_kinds):
            kd = _dense_layer_kv(cfg, pkd.cache, layer, "k")
            assert np.abs(kp[layer, :plen] - kd[:plen]).max() < 1e-4
            if kind == "cross_attn":
                ckd = _dense_layer_kv(cfg, pkd.cache, layer, "ck")
                cvd = _dense_layer_kv(cfg, pkd.cache, layer, "cv")
                ec = cfg.cross_ctx
                assert np.abs(ck[layer, :ec] - ckd).max() < 1e-4
                assert np.abs(cv[layer, :ec] - cvd).max() < 1e-4


@pytest.mark.parametrize("setup_name", ["encdec_setup", "vlm_setup"])
def test_cross_roundtrip_paged_vs_dense(setup_name, request):
    """Full prefill→transfer→decode round trip for enc-dec and VLM
    archs: token-identical to the dense path, and every page (self and
    cross) is back on the free list when the workload drains."""
    cfg, params = request.getfixturevalue(setup_name)
    reqs = _gen(cfg, 4, seed=42, max_prompt=24, max_decode=6)
    out_p, pe_p, de_p = _run_disagg(cfg, params, copy.deepcopy(reqs),
                                    "paged")
    out_d, _, _ = _run_disagg(cfg, params, copy.deepcopy(reqs), "dense")
    assert len(out_p) == len(out_d) == 4
    assert out_p == out_d
    assert pe_p.alloc.used_pages == 0
    assert de_p.alloc.used_pages == 0


@pytest.mark.parametrize("setup_name", ["encdec_setup", "vlm_setup"])
def test_cross_prefill_logits_parity(setup_name, request):
    """Model-level: prefill_paged over cross pages emits the same last
    logits as the dense prefill (not just the same argmax token)."""
    cfg, params = request.getfixturevalue(setup_name)
    kvh, hd, L = cfg.n_kv_heads, cfg.resolved_head_dim, cfg.n_layers
    rng = np.random.default_rng(9)
    n, ec = 11, cfg.cross_ctx
    toks = rng.integers(1, cfg.vocab_size, n).astype(np.int32)
    enc = rng.standard_normal((1, ec, cfg.d_model)).astype(np.float32)

    cache = M.init_cache(cfg, 1, 32)
    lg_d, _ = M.prefill(params, cfg, jnp.asarray(toks[None]), cache,
                        enc_embeds=jnp.asarray(enc))

    trash = 16
    pool = PagePool.create(L, trash + 1, PAGE, kvh, hd, jnp.float32)
    sq = 16
    tok = np.zeros((1, sq), np.int32)
    tok[0, :n] = toks
    tab = [0, 1, 2, 3]
    bt = np.full((1, 8), trash, np.int32)
    bt[0, :4] = tab
    pg = np.full((1, sq), trash, np.int32)
    off = (np.arange(sq, dtype=np.int32) % PAGE)[None]
    for j in range(n):
        pg[0, j] = tab[j // PAGE]
        off[0, j] = j % PAGE
    ctab = list(range(8, 8 - (-ec // PAGE)))
    cbt = np.asarray([ctab], np.int32)
    cpg = np.asarray([[ctab[j // PAGE] for j in range(ec)]], np.int32)
    coff = (np.arange(ec, dtype=np.int32) % PAGE)[None]
    _, lg_p, _, _ = M.prefill_paged(
        params, cfg, jnp.asarray(tok), jnp.zeros(1, jnp.int32),
        jnp.asarray([n], np.int32), jnp.asarray([n - 1], np.int32),
        jnp.asarray(bt), jnp.asarray(pg), jnp.asarray(off),
        pool.k, pool.v, jnp.asarray(enc), jnp.asarray(cbt),
        jnp.asarray([ec], np.int32), jnp.asarray(cpg),
        jnp.asarray(coff))
    assert float(np.abs(np.asarray(lg_p[0])
                        - np.asarray(lg_d[0, -1])).max()) < 1e-4


# ---------------------------------------------------------------------------
# allocator: cross pages freed exactly once
# ---------------------------------------------------------------------------
def test_cross_pages_freed_exactly_once():
    a = PagedAllocator(n_pages=16, page_size=4, cross_tokens=10)
    assert a.cross_pages_per_request == 3
    a.alloc("r", 8)                          # 2 self + 3 cross pages
    assert a.used_pages == 5
    ctab = a.cross_table("r")
    assert len(ctab) == 3
    assert len(set(ctab) | set(a.live_pages("r"))) == 5   # disjoint
    # read-only: appends grow the SELF table only
    for _ in range(5):
        a.append_token("r")
    assert a.cross_table("r") == ctab
    a.free("r")
    assert a.free_pages == 16                # every page back, once
    with pytest.raises(KeyError):
        a.free("r")                          # double free is loud
    # freed cross pages are reusable
    a.alloc("s", 40)
    assert a.used_pages == 13


def test_cross_admission_accounts_cross_pages():
    """can_admit must reserve the cross pages too: a pool with room for
    the self KV alone must refuse a cross-attention request."""
    a = PagedAllocator(n_pages=4, page_size=4, cross_tokens=12)
    assert not a.can_admit(8)                # 2 self + 3 cross > 4
    assert a.can_admit(4)                    # 1 self + 3 cross == 4
    with pytest.raises(OutOfPages):
        a.alloc("r", 8)
    assert a.used_pages == 0                 # failed alloc left no debris


# ---------------------------------------------------------------------------
# transfer accounting
# ---------------------------------------------------------------------------
def test_cross_transfer_ships_one_shot_encoder_pages(encdec_setup):
    """kv_page_bytes with enc_len adds exactly the encoder page payload
    (page-aligned, all cross layers), on top of the self-KV pages."""
    from repro.core.kv_transfer import kv_page_bytes
    cfg, _ = encdec_setup
    base = kv_page_bytes(cfg, 16, PAGE, dtype_bytes=4)
    with_cross = kv_page_bytes(cfg, 16, PAGE, dtype_bytes=4,
                               enc_len=cfg.cross_ctx)
    cross_pages = -(-cfg.cross_ctx // PAGE)
    expected = (cross_pages * PAGE
                * cfg.cross_kv_bytes_per_token(dtype_bytes=4))
    assert with_cross - base == expected
    assert cfg.cross_kv_bytes_per_token(4) \
        == cfg.n_cross_layers * 2 * cfg.n_kv_heads \
        * cfg.resolved_head_dim * 4
