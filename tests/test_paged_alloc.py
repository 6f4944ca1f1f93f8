"""Paged-KV allocator invariants (hypothesis-driven random workload)."""
import pytest
from hypothesis_compat import given, settings, st

from repro.kvcache.paged import OutOfPages, PagedAllocator, PagePool


@given(st.lists(st.tuples(st.sampled_from(["alloc", "append", "free"]),
                          st.integers(0, 9), st.integers(1, 200)),
                max_size=200))
@settings(max_examples=200, deadline=None)
def test_allocator_invariants(ops):
    a = PagedAllocator(n_pages=32, page_size=16)
    live = set()
    for op, ridx, toks in ops:
        rid = f"r{ridx}"
        try:
            if op == "alloc" and rid not in live:
                a.alloc(rid, toks)
                live.add(rid)
            elif op == "append" and rid in live:
                a.append_token(rid)
            elif op == "free" and rid in live:
                a.free(rid)
                live.discard(rid)
        except OutOfPages:
            pass
        # invariants
        assert a.used_pages + a.free_pages == a.n_pages
        held = []
        for r in live:
            pages = a.table(r)
            assert len(set(pages)) == len(pages)       # no dup inside req
            assert len(pages) >= a.pages_for(a.length(r)) or a.length(r) == 0
            held.extend(pages)
        assert len(set(held)) == len(held)             # no double alloc
        assert len(held) == a.used_pages


def test_free_pages_are_reusable():
    a = PagedAllocator(n_pages=4, page_size=16)
    a.alloc("a", 64)                 # all 4 pages
    with pytest.raises(OutOfPages):
        a.alloc("b", 1)
    a.free("a")
    a.alloc("b", 64)                 # reusable after free
    assert a.used_pages == 4


def test_append_grows_page_at_boundary():
    a = PagedAllocator(n_pages=8, page_size=4)
    a.alloc("a", 4)                  # exactly one full page
    assert len(a.table("a")) == 1
    a.append_token("a")              # crosses boundary -> second page
    assert len(a.table("a")) == 2
    assert a.length("a") == 5


def test_windowed_alloc_skips_dead_prefix():
    """Window-aware alloc materializes only in-window pages; the dead
    prefix keeps absolute slot indexing as ``None`` entries."""
    a = PagedAllocator(n_pages=16, page_size=4, window=6)
    a.alloc("a", 20)                 # tokens 0..19, window 6
    table = a.table("a")
    assert len(table) == 5           # pages_for(20): absolute slots kept
    dead = a.dead_slots(20)          # tokens <= 14 dead -> pages 0..2
    assert dead == 3
    assert table[:dead] == [None] * dead
    assert all(p is not None for p in table[dead:])
    assert a.pages_held("a") == a.pages_for_request(20) == 2
    a.free("a")
    assert a.free_pages == 16


def test_windowed_append_frees_slid_out_pages():
    """Decode appends hold O(window) pages: as the window slides, whole
    pages return to the free list but never the pages the CURRENT query
    (the appended token) still attends."""
    ps, w = 4, 6
    a = PagedAllocator(n_pages=8, page_size=ps, window=w)
    a.alloc("a", 1)
    for _ in range(60):
        a.append_token("a")
        n = a.length("a")
        # the query at position n-1 attends keys > n-1-w: those tokens'
        # pages must be live
        table = a.table("a")
        for t in range(max(0, n - w), n):
            assert table[t // ps] is not None, (n, t)
        assert a.pages_held("a") <= a.pages_for(w) + 1
    # window filled long ago: the bound is tight, not just safe
    assert a.pages_held("a") <= a.pages_for(w) + 1
    held = a.pages_held("a")
    a.free("a")
    assert a.free_pages == 8
    assert held < a.pages_for(61)    # O(window), not O(seq)


def test_windowed_append_on_full_pool_reuses_slid_out_page():
    """At a page boundary the window-slide free and the table grow land
    on the same append: the freed page must be reusable for the grow, so
    a pool with exactly the steady-state page count never raises."""
    a = PagedAllocator(n_pages=2, page_size=4, window=5)
    a.alloc("a", 1)
    for _ in range(40):                  # crashes with OutOfPages if the
        a.append_token("a")              # grow runs before the trim
    assert a.pages_held("a") <= 2


def test_windowed_trim_matches_decode_side_alloc():
    """Prefill's materialize_all + trim(prompt_len) leaves exactly the
    live pages a window-aware decode alloc(prompt_len) would create —
    the transfer payload and receiver tables line up by construction."""
    for plen in (1, 5, 8, 13, 24):
        pe = PagedAllocator(n_pages=32, page_size=4, window=6)
        pe.alloc("r", plen, materialize_all=True)
        assert pe.pages_held("r") == pe.pages_for(plen)
        pe.trim("r", plen)
        de = PagedAllocator(n_pages=32, page_size=4, window=6)
        de.alloc("r", plen)
        assert pe.pages_held("r") == de.pages_held("r")
        assert [p is None for p in pe.table("r")] \
            == [p is None for p in de.table("r")]


def test_page_pool_latent_layout():
    """MLA latent pool: (latent, rope-key) pages with narrow trailing
    dims; write/gather/install are layout-generic."""
    import jax.numpy as jnp
    import numpy as np
    pool = PagePool.create_latent(n_layers=2, n_pages=8, page_size=4,
                                  kv_lora_rank=16, rope_dim=8,
                                  dtype=jnp.float32)
    assert pool.k.shape == (2, 8, 4, 16)
    assert pool.v.shape == (2, 8, 4, 8)
    ckv = jnp.arange(8 * 16, dtype=jnp.float32).reshape(8, 16)
    kr = jnp.arange(8 * 8, dtype=jnp.float32).reshape(8, 8)
    pool = pool.write_chunk(1, np.array([2, 6]), ckv, kr)
    pk, pv = pool.gather([2, 6])
    assert pk.shape == (2, 2, 4, 16) and pv.shape == (2, 2, 4, 8)
    pool2 = PagePool.create_latent(2, 8, 4, 16, 8, jnp.float32)
    pool2 = pool2.install([1, 3], pk, pv)
    qk, qv = pool2.gather([1, 3])
    assert jnp.array_equal(qk, pk) and jnp.array_equal(qv, pv)


def test_page_pool_roundtrip():
    import jax.numpy as jnp
    import numpy as np
    pool = PagePool.create(n_layers=2, n_pages=8, page_size=4, kvh=2, hd=8,
                           dtype=jnp.float32)
    assert pool.k.shape == pool.v.shape == (2, 8, 4, 2 * 8)   # lane-dense
    k = jnp.arange(8 * 2 * 8, dtype=jnp.float32).reshape(8, 2, 8)
    pool = pool.write_chunk(1, np.array([3, 5]), k, k * 2)
    kl, vl = pool.layer(1)
    assert float(abs(kl[3].reshape(-1) - k[:4].reshape(-1)).max()) == 0
    assert float(abs(vl[5].reshape(-1) - 2 * k[4:].reshape(-1)).max()) == 0
    pool = pool.write_token(0, 2, 1, k[0], k[1])
    kl0, vl0 = pool.layer(0)
    assert float(abs(kl0[2, 1] - k[0].reshape(-1)).max()) == 0
