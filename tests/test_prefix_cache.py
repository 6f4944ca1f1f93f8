"""Cross-request KV reuse: refcounted prefix cache + copy-on-write
pages (docs/prefix_cache.md).

Four layers of guarantees:

* allocator invariants survive ANY op interleaving — a property suite
  (hypothesis when installed, a seeded random walk always) drives
  alloc / append_token / trim / free / fork / commit sequences and
  checks after every op: free-list conservation, refcounts exactly
  mirror the block tables + caches, no double free, copy-on-write
  never mutates a page another holder can see, ``can_admit`` never
  lies to ``alloc``;
* cache-off runs are byte-identical to the pre-cache goldens — the
  flag defaults off everywhere, and a cache-ON sim run over a workload
  with NO shared prefixes reproduces golden_sim_metrics.json exactly;
* cache-on serving is token- and byte-identical to cache-off on a
  shared-prefix workload — engine round trip, shipped page payloads,
  cancel-mid-stream, and a chaos run that must decref (not free)
  shared pages with zero leaks;
* the encoder runs ONCE per cross-attention request (and once per
  distinct encoder input with the cache on), not once per chunk.
"""
import copy
import dataclasses
import itertools
import json
import os
from collections import Counter

import numpy as np
import pytest

from hypothesis_compat import given, settings, st
from repro.configs import get_config, get_smoke_config
from repro.core.decode_engine import DecodeEngine
from repro.core.prefill_engine import PrefillEngine
from repro.core.sched.prefill_scheduler import PrefillScheduler
from repro.kvcache.paged import (OutOfPages, PagedAllocator, PagePool,
                                 prefix_page_keys)
from repro.runtime.costmodel import CostModel, HardwareSpec
from repro.runtime.request import TERMINAL_PHASES, Phase
from repro.runtime.workload import generate
from repro.serving import Cluster, FaultEvent, FaultSpec
from repro.serving.faults import CRASH

PAGE = 4
NPAGES = 24
GOLDEN = os.path.join(os.path.dirname(__file__),
                      "golden_sim_metrics.json")

# three fixed token templates: same template => shared chain-hash
# prefix, different templates => disjoint keys from page 0
_TEMPLATES = [np.arange(1, 65, dtype=np.int32) * (t + 1)
              for t in range(3)]


# ---------------------------------------------------------------------------
# allocator op-interleaving machine
# ---------------------------------------------------------------------------
def _check_invariants(a: PagedAllocator) -> None:
    """Structural invariants that must hold after EVERY mutation."""
    refs = Counter()
    for t in a._tables.values():
        for p in t:
            if p is not None:
                refs[p] += 1
    for ct in a._cross.values():
        for p in ct:
            refs[p] += 1
    for p in a._cache.values():
        refs[p] += 1
    for plist in a._cross_cache.values():
        for p in plist:
            refs[p] += 1
    # refcounts exactly mirror table + cache membership, all positive
    assert dict(refs) == a._refs
    # the free list never aliases a live page and never double-lists
    assert len(set(a._free)) == len(a._free)
    assert not set(a._free) & set(a._refs)
    assert all(0 <= p < a.n_pages for p in a._free)
    # conservation: every page is free xor referenced
    assert len(a._free) + len(a._refs) == a.n_pages


def _run_ops(ops, prefix_cache=True):
    """Interpret an op list against a PagedAllocator plus a shadow
    content model: ``content[page]`` is the logical payload the engine
    would have written there.  Aliasing a cached page must always
    surface the exact content its key promises; appends must never be
    visible through any other holder's table."""
    a = PagedAllocator(NPAGES, PAGE, prefix_cache=prefix_cache)
    live = []                  # rids in alloc order
    keys_of = {}               # rid -> committed-able (capped) keys
    content = {}               # physical page -> logical content
    counter = itertools.count()
    for code, x, y in ops:
        if code == 0:                                    # alloc
            rid = f"r{next(counter)}"
            n_tokens = 1 + x % 40
            tmpl = y % (len(_TEMPLATES) + 1)
            keys = None
            if tmpl < len(_TEMPLATES):
                toks = _TEMPLATES[tmpl][:n_tokens]
                # prefill's cap: never alias the page holding the last
                # prompt token (its logits must be recomputed)
                keys = prefix_page_keys(toks, PAGE)[
                    :max(0, (n_tokens - 1) // PAGE)]
            ok = a.can_admit(n_tokens, materialize_all=True,
                             page_keys=keys)
            try:
                a.alloc(rid, n_tokens, materialize_all=True,
                        page_keys=keys)
            except OutOfPages:
                assert not ok, "can_admit said yes but alloc raised"
                _check_invariants(a)
                continue
            assert ok, "alloc succeeded after can_admit said no"
            live.append(rid)
            keys_of[rid] = keys
            hits = a.cached_prefix_pages(rid)
            for i, p in enumerate(a.table(rid)):
                if i < hits:
                    # aliased read-only: the cache must hand back a page
                    # holding EXACTLY the content its key identifies
                    assert content[p] == keys[i], \
                        "cache aliased a page with the wrong content"
                elif keys and i < len(keys):
                    content[p] = keys[i]      # prefill writes it
                else:
                    content[p] = ("private", rid, i)
        elif code == 1:                                  # commit
            if not live:
                continue
            rid = live[x % len(live)]
            if keys_of.get(rid):
                a.commit(rid, keys_of[rid])
        elif code == 2:                                  # append (COW)
            if not live:
                continue
            rid = live[x % len(live)]
            ln = a.length(rid)
            table = a._tables[rid]
            slot = ln // PAGE
            grow = ln == len(table) * PAGE
            old = None if grow else table[slot]
            old_ref = 0 if old is None else a.refcount(old)
            others = {r: a.table(r) for r in live if r != rid}
            try:
                page = a.append_token(rid)
            except OutOfPages:
                assert a.free_pages == 0 and a._evictable() == 0
                _check_invariants(a)
                continue
            # the page just written is exclusively owned — COW never
            # mutates a page any other table or cache entry can see
            assert a.refcount(page) == 1, "wrote into a shared page"
            cows = a.take_cow_copies()
            if old is not None and old_ref > 1:
                assert page != old
                assert cows == [(old, page)]
                content[page] = content.get(old)   # pool replay
            else:
                assert cows == []
            for r, t in others.items():
                assert a.table(r) == t, "append mutated another table"
            content[page] = ("appended", rid, ln)
        elif code == 3:                                  # fork (share)
            if not live:
                continue
            src = live[x % len(live)]
            rid = f"r{next(counter)}"
            a.fork(rid, src)
            assert a.table(rid) == a.table(src)
            live.append(rid)
            keys_of[rid] = None      # forks are never committed
        else:                                            # free
            if not live:
                continue
            rid = live.pop(x % len(live))
            a.free(rid)
            keys_of.pop(rid, None)
            with pytest.raises(KeyError):
                a.free(rid)          # double free is loud, not silent
        _check_invariants(a)
    for rid in live:
        a.free(rid)
    _check_invariants(a)
    # zero-leak: with no residents, only cache entries hold pages
    assert a.free_pages + len(a.cache_pages()) == a.n_pages
    return a


_OPS = st.lists(st.tuples(st.integers(0, 4), st.integers(0, 63),
                          st.integers(0, 63)),
                min_size=1, max_size=80)


@given(ops=_OPS)
@settings(max_examples=200, deadline=None)
def test_allocator_interleavings_hypothesis(ops):
    _run_ops(ops, prefix_cache=True)


@given(ops=_OPS)
@settings(max_examples=200, deadline=None)
def test_allocator_interleavings_cache_off_hypothesis(ops):
    # flag off: same ops, keys are ignored, nothing ever aliases —
    # the refcount/COW machinery must still be invariant-clean (fork
    # shares explicitly either way)
    _run_ops(ops, prefix_cache=False)


def test_allocator_interleavings_random_walk():
    """Always-on fallback for the property above: seeded random walks
    through the same op space, so the invariants are exercised even
    where hypothesis is not installed."""
    rng = np.random.default_rng(0)
    for _ in range(80):
        n = int(rng.integers(1, 81))
        ops = [(int(rng.integers(0, 5)), int(rng.integers(0, 64)),
                int(rng.integers(0, 64))) for _ in range(n)]
        _run_ops(ops, prefix_cache=True)
        _run_ops(ops, prefix_cache=False)


def test_windowed_trim_interleavings_conserve_pages():
    """Sliding-window allocators (cache is force-disabled there) under
    random alloc/append/trim/free interleavings: conservation holds and
    a request's physical footprint stays O(window)."""
    rng = np.random.default_rng(7)
    window = 8
    bound = -(-window // PAGE) + 1
    for _ in range(40):
        a = PagedAllocator(32, PAGE, window=window)
        live, counter = [], itertools.count()
        for _ in range(int(rng.integers(5, 60))):
            op = int(rng.integers(0, 4))
            if op == 0:
                rid = f"r{next(counter)}"
                n = int(rng.integers(1, 21))
                if a.can_admit(n, materialize_all=True):
                    a.alloc(rid, n, materialize_all=True)
                    live.append(rid)
            elif op == 1 and live:
                rid = live[int(rng.integers(len(live)))]
                try:
                    a.append_token(rid)
                except OutOfPages:
                    pass
            elif op == 2 and live:
                rid = live[int(rng.integers(len(live)))]
                a.trim(rid, a.length(rid))
                assert a.pages_held(rid) <= max(
                    bound, a.pages_for(a.length(rid))
                    - a.dead_slots(a.length(rid)))
            elif op == 3 and live:
                a.free(live.pop(int(rng.integers(len(live)))))
            _check_invariants(a)
        for rid in live:
            a.free(rid)
        assert a.free_pages == a.n_pages


def test_eviction_under_pressure_prefers_lru_and_spares_hits():
    """Filling the pool evicts cache-ONLY (refcount 1) entries in LRU
    order; entries being aliased by the incoming alloc are spared."""
    a = PagedAllocator(8, PAGE, prefix_cache=True)
    ka = prefix_page_keys(_TEMPLATES[0][:8], PAGE)       # 2 pages
    kb = prefix_page_keys(_TEMPLATES[1][:8], PAGE)
    a.alloc("a", 8, page_keys=ka, materialize_all=True)
    a.commit("a", ka)
    a.free("a")
    a.alloc("b", 8, page_keys=kb, materialize_all=True)
    a.commit("b", kb)
    a.free("b")
    assert a.free_pages == 4 and len(a.cache_pages()) == 4
    # re-alloc under template A: its 2 cached pages alias (hit), and the
    # 6 fresh pages needed force template B's LRU entries out
    t = a.alloc("c", 32, page_keys=ka + [b"x"] * 5, materialize_all=True)
    assert a.cached_prefix_pages("c") == 2
    assert len([p for p in t if p is not None]) == 8
    assert all(k in a._cache for k in ka)        # the hits survived
    assert all(k not in a._cache for k in kb)    # LRU victims
    a.free("c")
    assert a.free_pages + len(a.cache_pages()) == a.n_pages


def test_pool_copy_pages_replays_cow_bytes():
    """PagePool.copy_pages makes dst a byte copy of src on every layer
    — the device-side half of the allocator's COW contract."""
    import jax.numpy as jnp
    pool = PagePool.create(2, 6, PAGE, 2, 8, jnp.float32)
    k = jnp.arange(2 * 1 * PAGE * 2 * 8, dtype=jnp.float32).reshape(
        2, 1, PAGE, 2 * 8)
    pool = PagePool(k=pool.k.at[:, jnp.asarray([1])].set(k),
                    v=pool.v.at[:, jnp.asarray([1])].set(3 * k))
    pool = pool.copy_pages([1], [4])
    ck, cv = pool.gather([4])
    assert jnp.array_equal(ck, k)
    assert jnp.array_equal(cv, 3 * k)


# ---------------------------------------------------------------------------
# golden parity: the cache must be invisible until prefixes are shared
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def opt13b():
    cfg = get_config("opt_13b")
    return cfg, CostModel(cfg, HardwareSpec.v100_tp2(),
                          n_params=13_000_000_000)


def _snap(r):
    return {"metrics": r.metrics, "resource_time": r.resource_time,
            "prefill_busy": r.prefill_busy, "decode_busy": r.decode_busy,
            "swap_events": r.swap_events, "flips": r.flips}


def test_sim_cache_on_without_shared_prefixes_matches_golden(opt13b):
    """prefix_cache=True over a workload with NO shared prefixes must
    reproduce the pre-cache golden metrics bit-for-bit: with no keys to
    hit, the cache may not perturb a single RNG draw or accounting
    step."""
    cfg, cost = opt13b
    want = json.load(open(GOLDEN))["mixed64"]
    reqs = generate("Mixed", 64, seed=1)
    r = Cluster(cfg, runtime="sim", cost=cost, n_prefill=1, n_decode=1,
                prefix_cache=True).serve(copy.deepcopy(reqs))
    got = _snap(r)
    for k, v in want["metrics"].items():
        assert got["metrics"][k] == v, k
    for k in ("resource_time", "prefill_busy", "decode_busy",
              "swap_events", "flips"):
        assert got[k] == want[k], k


def test_sim_shared_prefix_workload_saves_pages_and_ttft(opt13b):
    """The cost-model analogue: zipf-shared system prompts with the
    cache on finish every request, report pages_saved/cache_hit_rate,
    and strictly improve mean TTFT over the cache-off run."""
    cfg, cost = opt13b
    reqs = generate("Mixed", 48, seed=5, prefix_pool=2, prefix_len=256)
    off = Cluster(cfg, runtime="sim", cost=cost,
                  n_prefill=1, n_decode=1).serve(copy.deepcopy(reqs))
    on = Cluster(cfg, runtime="sim", cost=cost, n_prefill=1, n_decode=1,
                 prefix_cache=True).serve(copy.deepcopy(reqs))
    assert off.metrics["n"] == on.metrics["n"] == 48
    assert "pages_saved" not in off.metrics
    assert on.metrics["pages_saved"] > 0
    assert 0.0 < on.metrics["cache_hit_rate"] < 1.0
    assert on.metrics["avg_ttft"] < off.metrics["avg_ttft"]


def test_sim_chaos_with_cache_decrefs_shared_pages_no_leak(opt13b):
    """Kill a decode instance mid-run with the cache on and prefixes
    shared: recovery must DECREF shared pages (not force-free them),
    every request still reaches a terminal phase, and each instance ends
    with every page either free or pinned by a cache entry — no leaks,
    no double frees (those assert loudly inside the allocator)."""
    cfg, cost = opt13b
    reqs = generate("Mixed", 64, seed=1, prefix_pool=4, prefix_len=256)
    faults = FaultSpec(seed=0, drop_kv=0.1, events=(
        FaultEvent(t=2.0, kind=CRASH, iid="i3"),))
    cluster = Cluster(cfg, runtime="sim", cost=cost, n_prefill=2,
                      n_decode=2, prefix_cache=True, faults=faults)
    r = cluster.serve(copy.deepcopy(reqs))
    assert cluster._dead == {"i3"}
    for req in r.requests:
        assert req.phase in TERMINAL_PHASES, (req.rid, req.phase)
    assert r.metrics["n"] + r.metrics.get("failed", 0) == 64
    assert r.metrics.get("recovered", 0) > 0
    hits = 0
    for i in cluster.instances:
        a = i.alloc
        assert a.free_pages + len(a.cache_pages()) == a.n_pages, i.iid
        _check_invariants(a)
        hits += a.cache_hits
    assert hits > 0          # decode-side sharing actually happened


# ---------------------------------------------------------------------------
# engine runtime: token- and byte-identical serving
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def engine_setup():
    import jax

    from repro.models import model as M
    cfg = dataclasses.replace(get_smoke_config("qwen2_0_5b"),
                              dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _shared_reqs(cfg, n=6, seed=11):
    return generate("Mixed", n, seed=seed, max_prompt=40, max_decode=6,
                    vocab_size=cfg.vocab_size, prefix_pool=2,
                    prefix_len=32, prefix_zipf=1.2)


def _drain_prefill(pe, reqs):
    for r in reqs:
        pe.submit(r)
    out, t = {}, 0.0
    for _ in range(400):
        for pk in pe.step(t):
            out[pk.req.rid] = pk
        t += 0.01
        if pe.idle():
            break
    return out


def _mk_prefill(cfg, params, prefix_cache):
    # sched_batch=2 => multi-wave traffic: the cache serves only
    # content-FINAL (committed at finish) entries, so sharing shows up
    # across waves, the steady-state serving shape
    return PrefillEngine("pp", cfg, params, backend="paged",
                         scheduler=PrefillScheduler("fcfs", 2),
                         chunk_size=8, max_seq=64, page_size=PAGE,
                         n_pages=128, prefix_cache=prefix_cache)


def test_engine_prefill_payloads_byte_identical_and_cheaper(engine_setup):
    """Cache-on prefill over shared prefixes ships the SAME first token
    and byte-identical page payloads as cache-off — aliased pages hold
    exactly the KV the cache-off run recomputed — while skipping chunks
    and wire bytes."""
    cfg, params = engine_setup
    reqs = _shared_reqs(cfg)
    on = _mk_prefill(cfg, params, True)
    off = _mk_prefill(cfg, params, False)
    out_on = _drain_prefill(on, copy.deepcopy(reqs))
    out_off = _drain_prefill(off, copy.deepcopy(reqs))
    assert len(out_on) == len(out_off) == len(reqs)
    for rid, pk in out_on.items():
        pk0 = out_off[rid]
        assert pk.first_token == pk0.first_token, rid
        assert np.array_equal(np.asarray(pk.pages_k),
                              np.asarray(pk0.pages_k)), rid
        assert np.array_equal(np.asarray(pk.pages_v),
                              np.asarray(pk0.pages_v)), rid
    assert on.alloc.cache_hits > 0
    assert sum(pk.cached_tokens for pk in out_on.values()) > 0
    assert all(pk.cached_tokens == 0 for pk in out_off.values())
    assert on.chunk_steps < off.chunk_steps          # chunks skipped
    assert on.network.bytes_saved > 0
    assert on.network.bytes_sent < off.network.bytes_sent
    # zero-leak on both: everything shipped + freed, cache entries only
    assert on.alloc.free_pages + len(on.alloc.cache_pages()) \
        == on.alloc.n_pages
    assert off.alloc.used_pages == 0


def _run_cluster(cfg, params, reqs, prefix_cache, cancel_rid=None):
    cluster = Cluster(cfg, runtime="engine", params=params, n_prefill=1,
                      n_decode=1, chunk_size=8, max_seq=64, max_batch=4,
                      n_pages=128, page_size=PAGE, sched_batch=2,
                      prefix_cache=prefix_cache)
    handles = [cluster.submit(request=r) for r in reqs]
    if cancel_rid is not None:
        h = next(h for h in handles if h.rid == cancel_rid)
        # stream a couple of tokens, then cancel mid-stream
        list(itertools.islice(iter(h), 2))
        h.cancel()
    cluster.run()
    toks = {h.rid: h.result().tokens for h in handles
            if h.result().phase == Phase.FINISHED}
    return cluster, toks


def test_engine_cluster_cache_on_token_identical(engine_setup):
    """Full disaggregated serving with the cache on: every request's
    token stream is identical to the cache-off run, with real hits on
    both the prefill and decode side, and no page leaks."""
    cfg, params = engine_setup
    reqs = _shared_reqs(cfg, n=8, seed=3)
    c_off, toks_off = _run_cluster(cfg, params, copy.deepcopy(reqs),
                                   False)
    c_on, toks_on = _run_cluster(cfg, params, copy.deepcopy(reqs), True)
    assert len(toks_on) == len(toks_off) == len(reqs)
    assert toks_on == toks_off
    assert sum(i.pe.alloc.cache_hits for i in c_on.instances) > 0
    assert sum(i.de.alloc.cache_hits for i in c_on.instances) > 0
    assert c_on.network.bytes_saved > 0
    for i in c_on.instances:
        for a in (i.pe.alloc, i.de.alloc):
            assert a.free_pages + len(a.cache_pages()) == a.n_pages
            _check_invariants(a)


def test_engine_cluster_cancel_mid_stream_with_cache(engine_setup):
    """Cancelling a prefix-sharing request mid-stream with the cache on:
    survivors still emit exactly the cache-off tokens, the cancelled
    request's refs are released (shared pages survive via the cache,
    exclusive ones return to the free list)."""
    cfg, params = engine_setup
    reqs = _shared_reqs(cfg, n=6, seed=9)
    victim = reqs[1].rid
    c_off, toks_off = _run_cluster(cfg, params, copy.deepcopy(reqs),
                                   False, cancel_rid=victim)
    c_on, toks_on = _run_cluster(cfg, params, copy.deepcopy(reqs),
                                 True, cancel_rid=victim)
    assert victim not in toks_on and victim not in toks_off
    assert len(toks_on) == len(reqs) - 1
    assert toks_on == toks_off
    for i in c_on.instances:
        for a in (i.pe.alloc, i.de.alloc):
            assert a.free_pages + len(a.cache_pages()) == a.n_pages
            _check_invariants(a)


# ---------------------------------------------------------------------------
# encoder-once (cross-attention archs)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def encdec_setup():
    import jax

    from repro.models import model as M
    cfg = dataclasses.replace(get_smoke_config("whisper_tiny"),
                              dtype="float32")
    params = M.init_params(jax.random.PRNGKey(3), cfg)
    return cfg, params


def test_encoder_runs_once_per_request_not_per_chunk(encdec_setup):
    """Regression for the O(chunks * enc_ctx^2) waste: a multi-chunk
    cross-attention prefill runs the encoder + scatter exactly ONCE (on
    the first chunk); later chunks take the read-only path."""
    cfg, params = encdec_setup
    reqs = generate("Mixed", 1, seed=51, max_prompt=30, max_decode=4,
                    vocab_size=cfg.vocab_size, enc_ctx=cfg.cross_ctx,
                    enc_dim=cfg.d_model)
    reqs[0].prompt_len = 20                   # 3 chunks at chunk_size=8
    reqs[0].prompt_tokens = reqs[0].prompt_tokens[:20]
    pe = PrefillEngine("pe", cfg, params, backend="paged", chunk_size=8,
                       max_seq=64, page_size=PAGE, n_pages=128)
    out = _drain_prefill(pe, reqs)
    assert len(out) == 1
    assert pe.fused_calls == pe.chunk_steps == 3
    assert pe.encoder_calls == 1              # was: once per chunk


def test_cross_pages_dedupe_under_prefix_cache(encdec_setup):
    """Two requests with byte-equal encoder input and the cache on: the
    encoder runs once TOTAL, the second request aliases the committed
    cross pages, ships no cross payload bytes, and emits the same first
    token as its cache-off run."""
    cfg, params = encdec_setup
    base = generate("Mixed", 2, seed=52, max_prompt=20, max_decode=4,
                    vocab_size=cfg.vocab_size, enc_ctx=cfg.cross_ctx,
                    enc_dim=cfg.d_model)
    base[1].enc_embeds = base[0].enc_embeds.copy()   # same audio/image

    def run(flag):
        pe = PrefillEngine("pe", cfg, params, backend="paged",
                           scheduler=PrefillScheduler("fcfs", 1),
                           chunk_size=8, max_seq=64, page_size=PAGE,
                           n_pages=128, prefix_cache=flag)
        return pe, _drain_prefill(pe, copy.deepcopy(base))

    pe_off, out_off = run(False)
    pe_on, out_on = run(True)
    assert len(out_on) == len(out_off) == 2
    assert pe_off.encoder_calls == 2          # one per request
    assert pe_on.encoder_calls == 1           # deduped across requests
    assert pe_on.alloc.cross_hits == 1
    snd = base[1].rid
    assert out_on[snd].cross_cached and not out_off[snd].cross_cached
    for rid in out_on:
        assert out_on[rid].first_token == out_off[rid].first_token
        assert np.array_equal(np.asarray(out_on[rid].cross_k),
                              np.asarray(out_off[rid].cross_k))
    assert pe_on.network.bytes_saved > 0
    assert pe_on.alloc.free_pages + len(pe_on.alloc.cache_pages()) \
        == pe_on.alloc.n_pages


def test_decode_receive_skips_cached_pages_token_identical(engine_setup):
    """Direct pe->de loop: the decode side installs only the uncached
    page suffix for cache-hit requests yet emits identical tokens."""
    cfg, params = engine_setup
    reqs = _shared_reqs(cfg, n=6, seed=13)

    def run(flag):
        pe = _mk_prefill(cfg, params, flag)
        de = DecodeEngine("de", cfg, params, max_slots=4, max_seq=64,
                          backend="paged", page_size=PAGE, n_pages=128,
                          prefix_cache=flag)
        for r in copy.deepcopy(reqs):
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
        return pe, de, out

    pe_on, de_on, out_on = run(True)
    _, _, out_off = run(False)
    assert len(out_on) == len(out_off) == len(reqs)
    assert out_on == out_off
    assert de_on.alloc.cache_hits > 0         # admit-time sharing
    for a in (pe_on.alloc, de_on.alloc):
        assert a.free_pages + len(a.cache_pages()) == a.n_pages
        _check_invariants(a)
