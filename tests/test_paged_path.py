"""Paged serving path ≡ dense path: fused chunk prefill, paged decode,
and the full prefill→transfer→decode round trip through both engines.

The paged backend is a systems transformation (shared page pool + Pallas
kernels instead of per-request dense caches) — it must not change a
single emitted token.
"""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.decode_engine import DecodeEngine
from repro.core.kv_transfer import NetworkStack
from repro.core.prefill_engine import PrefillEngine
from repro.kvcache.paged import PagePool
from repro.models import model as M
from repro.runtime.workload import generate

PAGE = 4


@pytest.fixture(scope="module")
def setup():
    cfg = dataclasses.replace(get_smoke_config("qwen2_0_5b"),
                              dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


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


def test_fused_chunk_prefill_matches_per_segment_dense(setup):
    """One fused call per multi-segment chunk ≡ one dense model call per
    segment: same first tokens AND same KV contents."""
    cfg, params = setup
    reqs = generate("LPLD", 4, seed=11, max_prompt=30, max_decode=4,
                    vocab_size=cfg.vocab_size)
    kw = dict(chunk_size=8, max_seq=64)
    pe_paged = PrefillEngine("pp", cfg, params, backend="paged",
                             page_size=PAGE, n_pages=128, **kw)
    pe_dense = PrefillEngine("pd", cfg, params, backend="dense", **kw)
    out_p = _drain_prefill(pe_paged, copy.deepcopy(reqs))
    out_d = _drain_prefill(pe_dense, copy.deepcopy(reqs))
    assert len(out_p) == len(out_d) == 4
    # each chunk step — even multi-segment ones — was exactly ONE fused
    # model call
    assert pe_paged.fused_calls == pe_paged.chunk_steps > 0
    for rid, pkp in out_p.items():
        pkd = out_d[rid]
        assert pkp.first_token == pkd.first_token
        plen = pkp.req.prompt_len
        # paged payload: (L, n_pages, page, kvh * hd) -> (L, plen, kvh, hd)
        kp = np.asarray(pkp.pages_k).reshape(
            cfg.n_layers, -1, cfg.n_kv_heads, cfg.resolved_head_dim)
        vp = np.asarray(pkp.pages_v).reshape(
            cfg.n_layers, -1, cfg.n_kv_heads, cfg.resolved_head_dim)
        # dense payload: body cache leaves (n_repeats, 1, max_seq, kvh, hd)
        kd = np.asarray(pkd.cache["body"][0]["k"])[:, 0]
        vd = np.asarray(pkd.cache["body"][0]["v"])[:, 0]
        assert np.abs(kp[:, :plen] - kd[:, :plen]).max() < 1e-4
        assert np.abs(vp[:, :plen] - vd[:, :plen]).max() < 1e-4


def test_paged_decode_matches_dense_over_ragged_multipage(setup):
    """decode_step_paged over multi-page sequences with ragged lengths
    emits the same tokens as the dense decode_step."""
    cfg, params = setup
    rng = np.random.default_rng(3)
    kvh, hd, L = cfg.n_kv_heads, cfg.resolved_head_dim, cfg.n_layers
    slots, max_seq, trash = 3, 32, 16
    lens = [11, 6, 1]                       # 3, 2 and 1 pages at PAGE=4
    tables = {0: [0, 1, 2], 1: [3, 4], 2: [5]}
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in lens]

    # dense: per-request prefill, slot-batched cache
    cache = M.init_cache(cfg, slots, max_seq)
    first = []
    for i, toks in enumerate(prompts):
        c = M.init_cache(cfg, 1, max_seq)
        lg, c = M.prefill(params, cfg, jnp.asarray(toks[None]), c)
        cache = M.cache_insert(cache, c, i)
        first.append(int(jnp.argmax(lg[0, -1])))

    # paged: seed the pool with the same prompts via prefill_paged
    pool = PagePool.create(L, trash + 1, PAGE, kvh, hd, jnp.float32)
    for i, toks in enumerate(prompts):
        n = len(toks)
        sq = 1 << max(0, n - 1).bit_length()
        tok = np.zeros((1, sq), np.int32)
        tok[0, :n] = toks
        tab = tables[i]
        bt = np.full((1, 8), trash, np.int32)
        bt[0, :len(tab)] = tab
        pg = np.full((1, sq), trash, np.int32)
        off = (np.arange(sq, dtype=np.int32) % PAGE)[None]
        for j in range(n):
            pg[0, j] = tab[j // PAGE]
            off[0, j] = j % PAGE
        nxt, _, kp, vp = M.prefill_paged(
            params, cfg, jnp.asarray(tok), jnp.zeros(1, jnp.int32),
            jnp.asarray([n], np.int32), jnp.asarray([n - 1], np.int32),
            jnp.asarray(bt), jnp.asarray(pg), jnp.asarray(off),
            pool.k, pool.v)
        pool = PagePool(k=kp, v=vp)
        assert int(nxt[0]) == first[i]

    last_p, last_d = list(first), list(first)
    cur = list(lens)
    free_page = 6
    for _ in range(4):
        pos = np.asarray(cur, np.int32)
        pages = np.zeros(slots, np.int32)
        offs = pos % PAGE
        bt = np.full((slots, 8), trash, np.int32)
        for i in range(slots):
            tab = tables[i]
            if cur[i] >= len(tab) * PAGE:       # grow page-at-a-time
                tab.append(free_page)
                free_page += 1
            pages[i] = tab[cur[i] // PAGE]
            bt[i, :len(tab)] = tab
        toks = np.asarray(last_p, np.int32)[:, None]
        nxt, kp, vp = M.decode_step_paged(
            params, cfg, jnp.asarray(toks), jnp.asarray(pos),
            jnp.asarray(pages), jnp.asarray(offs), jnp.asarray(bt),
            jnp.asarray(pos + 1), pool.k, pool.v)
        pool = PagePool(k=kp, v=vp)
        lg, cache = M.decode_step(
            params, cfg, jnp.asarray(np.asarray(last_d)[:, None]),
            cache, jnp.asarray(pos))
        dn = np.asarray(jnp.argmax(lg[:, 0], axis=-1))
        assert np.asarray(nxt).tolist() == dn.tolist()
        last_p = np.asarray(nxt).tolist()
        last_d = dn.tolist()
        cur = [c + 1 for c in cur]


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
    return out


def test_roundtrip_paged_vs_dense_engines(setup):
    """prefill→transfer→decode through both engine backends: identical
    token streams for every request."""
    cfg, params = setup
    reqs = generate("Mixed", 5, seed=12, max_prompt=24, max_decode=6,
                    vocab_size=cfg.vocab_size)
    out_p = _run_disagg(cfg, params, copy.deepcopy(reqs), "paged")
    out_d = _run_disagg(cfg, params, copy.deepcopy(reqs), "dense")
    assert len(out_p) == len(out_d) == 5
    assert out_p == out_d


def test_prefill_page_backpressure(setup):
    """A pool too small for the whole scheduler batch defers requests at
    the queue head instead of crashing; everything still completes as
    pages free up."""
    cfg, params = setup
    reqs = generate("LPLD", 4, seed=13, max_prompt=30, max_decode=2,
                    vocab_size=cfg.vocab_size)
    # pages for ~1 request at a time (max_prompt 30 -> <=8 pages @ PAGE=4)
    pe = PrefillEngine("p0", cfg, params, chunk_size=8, max_seq=64,
                       backend="paged", page_size=PAGE, n_pages=10)
    out = _drain_prefill(pe, reqs)
    assert len(out) == 4
    assert pe.alloc.used_pages == 0          # everything shipped + freed


def test_kv_transfer_page_granularity(setup):
    """Paged transfer accounting ships whole live pages: bytes round up
    to the page boundary and never below the raw token payload."""
    from repro.core.kv_transfer import kv_bytes, kv_page_bytes
    cfg, _ = setup
    assert kv_page_bytes(cfg, 16, 16) == kv_bytes(cfg, 16)
    assert kv_page_bytes(cfg, 17, 16) == kv_bytes(cfg, 32)
    assert kv_page_bytes(cfg, 1, 16) == kv_bytes(cfg, 16)
    net = NetworkStack()
    d = net.send_kv(cfg, 17, page_size=16)
    assert net.bytes_sent == kv_bytes(cfg, 32)
    assert d > 0


@pytest.fixture(scope="module")
def windowed_setup():
    cfg = dataclasses.replace(get_smoke_config("mistral_nemo_12b"),
                              dtype="float32", sliding_window=6)
    params = M.init_params(jax.random.PRNGKey(1), cfg)
    return cfg, params


@pytest.fixture(scope="module")
def mla_setup():
    cfg = dataclasses.replace(get_smoke_config("deepseek_v2_236b"),
                              dtype="float32")
    params = M.init_params(jax.random.PRNGKey(2), cfg)
    return cfg, params


def test_backend_for_matrix():
    """Single-source backend selection: every uniform-attention arch —
    GQA, sliding-window, MLA, and the cross-attention VLM/enc-dec pair —
    resolves to the paged backend; only recurrent/hybrid archs stay
    dense.  Both engines construct through backend_for and must agree
    with it."""
    from repro.core.backend import backend_for
    gqa = get_smoke_config("qwen2_0_5b")
    assert backend_for(gqa).backend == "paged"
    assert backend_for(gqa).layout == "gqa"
    assert backend_for(gqa).cross == "none"
    win = dataclasses.replace(get_smoke_config("mistral_nemo_12b"),
                              sliding_window=6)
    assert (backend_for(win).backend, backend_for(win).window) \
        == ("paged", 6)
    mla = get_smoke_config("deepseek_v2_236b")
    assert backend_for(mla).layout == "latent"
    assert backend_for(mla).token_width \
        == mla.mla.kv_lora_rank + mla.mla.qk_rope_head_dim
    for cross_arch in ("whisper_tiny", "llama_3_2_vision_11b"):
        spec = backend_for(get_smoke_config(cross_arch))
        assert (spec.backend, spec.cross) == ("paged", "pages"), cross_arch
        assert spec.cross_ctx > 0 and spec.n_cross_layers > 0
    for dense_arch in ("recurrentgemma_9b", "xlstm_1_3b"):
        spec = backend_for(get_smoke_config(dense_arch))
        assert spec.backend == "dense", dense_arch
        with pytest.raises(ValueError):
            backend_for(get_smoke_config(dense_arch), "paged")
    # engines resolve through the same helper
    params = M.init_params(jax.random.PRNGKey(0),
                           dataclasses.replace(gqa, dtype="float32"))
    pe = PrefillEngine("p", dataclasses.replace(gqa, dtype="float32"),
                       params, page_size=PAGE, n_pages=64, max_seq=64)
    de = DecodeEngine("d", dataclasses.replace(gqa, dtype="float32"),
                      params, page_size=PAGE, n_pages=64, max_seq=64)
    assert pe.backend == de.backend == "paged"


def test_windowed_prefill_parity_logits_and_pool(windowed_setup):
    """Sliding-window fused paged prefill ≡ dense windowed prefill:
    same first tokens AND the live pool pages hold the same K/V the
    dense cache holds for the in-window suffix."""
    cfg, params = windowed_setup
    reqs = generate("LPLD", 4, seed=21, max_prompt=30, max_decode=4,
                    vocab_size=cfg.vocab_size)
    kw = dict(chunk_size=8, max_seq=64)
    pe_paged = PrefillEngine("pp", cfg, params, backend="paged",
                             page_size=PAGE, n_pages=128, **kw)
    pe_dense = PrefillEngine("pd", cfg, params, backend="dense", **kw)
    out_p = _drain_prefill(pe_paged, copy.deepcopy(reqs))
    out_d = _drain_prefill(pe_dense, copy.deepcopy(reqs))
    assert len(out_p) == len(out_d) == 4
    for rid, pkp in out_p.items():
        pkd = out_d[rid]
        assert pkp.first_token == pkd.first_token
        plen = pkp.req.prompt_len
        # payload is the LIVE (in-window) page suffix only
        n_live = pe_paged.alloc.pages_for(plen) \
            - max(0, plen - cfg.sliding_window + 1) // PAGE
        assert pkp.pages_k.shape[1] == n_live
        kp = np.asarray(pkp.pages_k).reshape(
            cfg.n_layers, -1, cfg.n_kv_heads, cfg.resolved_head_dim)
        kd = np.asarray(pkd.cache["body"][0]["k"])[:, 0]
        # compare the tokens the window still needs (queries >= plen)
        lo = (pe_paged.alloc.pages_for(plen) - n_live) * PAGE
        valid = plen - lo
        assert np.abs(kp[:, :valid] - kd[:, lo:plen]).max() < 1e-4


def test_windowed_roundtrip_paged_vs_dense(windowed_setup):
    """Full disaggregated round trip for the sliding-window arch:
    token-identical to the dense path."""
    cfg, params = windowed_setup
    reqs = generate("Mixed", 4, seed=22, max_prompt=24, max_decode=8,
                    vocab_size=cfg.vocab_size)
    out_p = _run_disagg(cfg, params, copy.deepcopy(reqs), "paged")
    out_d = _run_disagg(cfg, params, copy.deepcopy(reqs), "dense")
    assert len(out_p) == len(out_d) == 4
    assert out_p == out_d


def test_windowed_decode_holds_o_window_pages(windowed_setup):
    """Acceptance bound: after the window fills, a decoding slot holds
    at most pages_for(window)+1 physical pages — O(window), not O(seq)."""
    cfg, params = windowed_setup
    w = cfg.sliding_window
    bound = -(-w // PAGE) + 1
    reqs = generate("LPHD", 2, seed=23, max_prompt=16, max_decode=24,
                    vocab_size=cfg.vocab_size)
    pe = PrefillEngine("p0", cfg, params, chunk_size=8, max_seq=64,
                       backend="paged", page_size=PAGE, n_pages=128)
    de = DecodeEngine("d0", cfg, params, max_slots=2, max_seq=64,
                      backend="paged", page_size=PAGE, n_pages=128)
    for r in reqs:
        pe.submit(r)
    t, filled_checks = 0.0, 0
    for _ in range(2000):
        for pk in pe.step(t):
            de.receive(pk)
        de.admit(t)
        de.step(t)
        for st in de.slots.values():
            held = de.alloc.pages_held(st.req.rid)
            n = de.alloc.length(st.req.rid)
            if n > w:
                filled_checks += 1
                assert held <= bound, (n, held, bound)
        t += 0.01
        if pe.idle() and de.idle():
            break
    assert filled_checks > 0          # the bound was actually exercised


def test_mla_prefill_parity_logits_and_pool(mla_setup):
    """Paged MLA fused prefill ≡ dense MLA prefill: same first tokens
    AND the latent pages hold the same (ckv, krope) the dense latent
    cache holds."""
    cfg, params = mla_setup
    m = cfg.mla
    reqs = generate("LPLD", 4, seed=31, max_prompt=30, max_decode=4,
                    vocab_size=cfg.vocab_size)
    kw = dict(chunk_size=8, max_seq=64)
    pe_paged = PrefillEngine("pp", cfg, params, backend="paged",
                             page_size=PAGE, n_pages=128, **kw)
    pe_dense = PrefillEngine("pd", cfg, params, backend="dense", **kw)
    out_p = _drain_prefill(pe_paged, copy.deepcopy(reqs))
    out_d = _drain_prefill(pe_dense, copy.deepcopy(reqs))
    assert len(out_p) == len(out_d) == 4
    for rid, pkp in out_p.items():
        pkd = out_d[rid]
        assert pkp.first_token == pkd.first_token
        plen = pkp.req.prompt_len
        # latent payload: (L, n_pages, page, lora) / (..., rope)
        ckv = np.asarray(pkp.pages_k).reshape(
            cfg.n_layers, -1, m.kv_lora_rank)
        kr = np.asarray(pkp.pages_v).reshape(
            cfg.n_layers, -1, m.qk_rope_head_dim)
        ckv_d = np.asarray(pkd.cache["body"][0]["ckv"])[:, 0]
        kr_d = np.asarray(pkd.cache["body"][0]["krope"])[:, 0]
        assert np.abs(ckv[:, :plen] - ckv_d[:, :plen]).max() < 1e-4
        assert np.abs(kr[:, :plen] - kr_d[:, :plen]).max() < 1e-4


def test_mla_roundtrip_paged_vs_dense(mla_setup):
    """Full disaggregated round trip for the MLA arch (latent page
    pool + Pallas paged-MLA decode): token-identical to the dense
    absorbed-decode path."""
    cfg, params = mla_setup
    reqs = generate("Mixed", 4, seed=32, max_prompt=24, max_decode=6,
                    vocab_size=cfg.vocab_size)
    out_p = _run_disagg(cfg, params, copy.deepcopy(reqs), "paged")
    out_d = _run_disagg(cfg, params, copy.deepcopy(reqs), "dense")
    assert len(out_p) == len(out_d) == 4
    assert out_p == out_d


def test_mla_transfer_ships_latent_width(mla_setup):
    """kv_page_bytes for MLA reflects the compressed latent width —
    the wire payload per token is lora+rope, not 2*kvh*hd."""
    from repro.core.backend import backend_for
    from repro.core.kv_transfer import kv_page_bytes
    cfg, _ = mla_setup
    m = cfg.mla
    spec = backend_for(cfg)
    assert spec.token_width == m.kv_lora_rank + m.qk_rope_head_dim
    per_layer_tok = m.kv_lora_rank + m.qk_rope_head_dim
    assert kv_page_bytes(cfg, 16, 16, dtype_bytes=4) \
        == cfg.n_layers * per_layer_tok * 16 * 4


def test_windowed_transfer_ships_live_pages_only():
    """kv_page_bytes for sliding-window configs counts the in-window
    page suffix, not the whole logical length."""
    from repro.core.kv_transfer import kv_bytes, kv_page_bytes
    cfg = dataclasses.replace(get_smoke_config("mistral_nemo_12b"),
                              sliding_window=6)
    # 24 tokens @ page 4, window 6: slots 0..3 dead -> 2 live pages
    assert kv_page_bytes(cfg, 24, 4) == kv_bytes(cfg, 8)
    # window not yet filled: everything ships
    assert kv_page_bytes(cfg, 5, 4) == kv_bytes(cfg, 8)


def test_pool_gather_install_roundtrip():
    """PagePool.gather on one pool == the transfer payload a second pool
    installs — the page-granular KV handoff is lossless."""
    pool_a = PagePool.create(2, 8, PAGE, 2, 16, jnp.float32)
    assert pool_a.k.shape == (2, 8, PAGE, 2 * 16)
    k = jnp.arange(2 * 3 * PAGE * 2 * 16, dtype=jnp.float32).reshape(
        2, 3, PAGE, 2 * 16)
    pool_a = PagePool(k=pool_a.k.at[:, jnp.asarray([1, 4, 6])].set(k),
                      v=pool_a.v.at[:, jnp.asarray([1, 4, 6])].set(2 * k))
    pk, pv = pool_a.gather([1, 4, 6])
    pool_b = PagePool.create(2, 8, PAGE, 2, 16, jnp.float32)
    pool_b = pool_b.install([0, 2, 5], pk, pv)
    bk, bv = pool_b.gather([0, 2, 5])
    assert jnp.array_equal(bk, k)
    assert jnp.array_equal(bv, 2 * k)
