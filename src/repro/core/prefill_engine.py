"""Prefill instance (paper §3.3): local scheduler -> length predictor ->
chunked-prefill LLM engine -> dispatcher.

Real-execution engine: runs the actual JAX model on CPU (tiny configs in
tests/examples).  Cluster-scale behaviour is reproduced by the simulator
(runtime/simulator.py) with the same scheduler/dispatcher objects.

Execution backends (selected by ``core.backend.backend_for``):
  * ``paged`` (default for every uniform-attention arch: GQA, MLA
    latent, full or sliding-window) — the engine owns a device
    ``PagePool``; one ``step`` executes the WHOLE fixed-size chunk as a
    single fused ``model.prefill_paged`` call (segments of multiple
    requests packed on the batch dim), writing K/V — or the compressed
    MLA latent — straight into pages.  Sliding-window configs trim
    pages back to the free list as chunks slide past them.  Finished
    requests ship ``(block table, live page contents)`` — no dense
    cache pytree ever exists on this path.  Cross-attention archs
    (VLM / enc-dec) also hold READ-ONLY cross pages per request: the
    encoder K/V is scattered once on the request's first chunk, every
    chunk attends it through a second block table, and the finished
    request ships the cross pages alongside the self KV.
  * ``dense`` — legacy per-segment ``model.prefill`` against per-request
    dense caches; retained for recurrent/hybrid architectures.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import chunking
from repro.core.backend import backend_for
from repro.core.kv_transfer import NetworkStack
from repro.core.sched.dispatcher import Dispatcher
from repro.core.sched.prefill_scheduler import PrefillScheduler
from repro.kvcache.paged import (OutOfPages, PagedAllocator, PagePool,
                                 request_cross_key, request_page_keys)
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.runtime.request import Phase, Request


@dataclasses.dataclass
class PrefilledKV:
    """What the dispatcher ships to a decode instance.

    Paged backend: ``pages_k``/``pages_v`` hold the request's LIVE page
    contents — (L, n_pages, page, kvh, hd) for the GQA layout, or the
    (latent, rope-key) pair (L, n_pages, page, width) for MLA — plus
    ``kv_len`` valid tokens.  The receiver installs them into its own
    pool and builds a block-table row; for sliding-window configs the
    payload is only the O(window) in-window suffix.  Cross-attention
    archs additionally ship ``cross_k``/``cross_v`` — the read-only
    encoder pages (one-shot payload, amortized over the whole decode)
    covering ``enc_len`` encoder tokens.  Dense backend: ``cache`` is a
    batch=1 cache pytree (cross KV rides inside it as ``ck``/``cv``).
    """
    req: Request
    first_token: int             # argmax token from prefill (the 'first token')
    transfer_delay_s: float      # emulated network wait
    n_chunks: int = 1
    cache: object = None         # dense backend only
    pages_k: object = None       # paged backend only
    pages_v: object = None
    kv_len: int = 0
    cross_k: object = None       # paged cross-attention archs only
    cross_v: object = None
    enc_len: int = 0
    # prefix-cache accounting: leading prompt tokens whose pages the
    # prefill side aliased (skipped recompute + wire bytes), and whether
    # the cross pages were deduped (encoder ran 0 times for this req)
    cached_tokens: int = 0
    cross_cached: bool = False

    @property
    def n_pages(self) -> int:
        """Pages the payload carries, self and cross (0 for dense)."""
        return sum(a.shape[1] for a in (self.pages_k, self.cross_k)
                   if a is not None)

    @property
    def nbytes(self) -> int:
        """Bytes of the payload's arrays, from their shapes and dtypes
        (no device sync)."""
        arrays = [self.pages_k, self.pages_v, self.cross_k, self.cross_v]
        return sum(a.nbytes for a in arrays + jax.tree_util.tree_leaves(
            self.cache) if a is not None)


def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def make_page_pool(cfg: ModelConfig, n_pages: int, page_size: int):
    """Device pool with one extra physical page past the allocator's
    range — the scratch ("trash") page pad tokens and dead slots scatter
    to.  MLA configs get the latent layout (compressed latent + RoPE key
    pages); everything else per-head GQA K/V pages.
    Returns (pool, trash_page_id)."""
    dtype = jnp.dtype(cfg.dtype)
    if backend_for(cfg).layout == "latent":
        pool = PagePool.create_latent(
            cfg.n_layers, n_pages + 1, page_size, cfg.mla.kv_lora_rank,
            cfg.mla.qk_rope_head_dim, dtype=dtype)
    else:
        pool = PagePool.create(cfg.n_layers, n_pages + 1, page_size,
                               cfg.n_kv_heads, cfg.resolved_head_dim,
                               dtype=dtype)
    return pool, n_pages


class PrefillEngine:
    def __init__(self, iid: str, cfg: ModelConfig, params,
                 scheduler: Optional[PrefillScheduler] = None,
                 dispatcher: Optional[Dispatcher] = None,
                 network: Optional[NetworkStack] = None,
                 predictor=None,
                 chunk_size: int = 64, max_seq: int = 512,
                 backend: str = "auto",
                 n_pages: int = 512, page_size: int = 16,
                 prefix_cache: bool = False):
        self.iid = iid
        self.cfg = cfg
        self.params = params
        # explicit None check: an EMPTY scheduler is falsy (__len__), so
        # `scheduler or ...` would silently discard a caller's policy/
        # batch-window configuration
        self.scheduler = scheduler if scheduler is not None \
            else PrefillScheduler()
        self.dispatcher = dispatcher or Dispatcher()
        self.network = network or NetworkStack()
        self.predictor = predictor
        self.chunk_size = chunk_size
        self.max_seq = max_seq
        self.spec = backend_for(cfg, backend)
        self.backend = self.spec.backend
        self.page_size = page_size
        self._chunk_queue: Deque[chunking.Chunk] = collections.deque()
        self._reqs: Dict[str, Request] = {}
        self.chunk_steps = 0         # steps that actually ran a chunk
        self.fused_calls = 0         # one per chunk on the paged backend
        self.encoder_calls = 0       # chunks that ran encoder + scatter
        self.enc_ctx = self.spec.cross_ctx
        # prefix cache needs stable page content (no sliding-window
        # trims) and the paged pool; silently a no-op elsewhere
        self.prefix_cache = (prefix_cache and self.backend == "paged"
                             and not cfg.sliding_window)
        self._page_keys: Dict[str, List[bytes]] = {}
        #: step-phase recorder (repro.obs.tracer.PhaseRecorder), set by
        #: the wall-clock runtime when it has a tracer; None costs one
        #: check per phase boundary
        self.phases = None

        if self.backend == "paged":
            self.alloc = PagedAllocator(
                n_pages=n_pages, page_size=page_size,
                window=cfg.sliding_window,
                cross_tokens=self.enc_ctx if self.spec.cross == "pages"
                else 0,
                prefix_cache=self.prefix_cache)
            self.pool, self._trash = make_page_pool(cfg, n_pages,
                                                    page_size)
            self._bt_width = self.alloc.pages_for(max_seq)
            self._cross_bt_width = self.alloc.cross_pages_per_request

            if self.spec.cross == "pages":
                def _prefill_paged(params, toks, qoff, kvlen, last, bt,
                                   pg, off, kp, vp, enc, cbt, clen, cpg,
                                   coff):
                    return M.prefill_paged(params, cfg, toks, qoff,
                                           kvlen, last, bt, pg, off, kp,
                                           vp, enc, cbt, clen, cpg, coff)

                # read-only cross variant for chunks with NO encoder
                # work (no segment is a first chunk with unwritten cross
                # pages): skips the O(enc_ctx²) encoder stack + scatter
                # that the one-shot path used to rerun and discard every
                # chunk
                def _prefill_paged_ro(params, toks, qoff, kvlen, last,
                                      bt, pg, off, kp, vp, cbt, clen):
                    return M.prefill_paged(params, cfg, toks, qoff,
                                           kvlen, last, bt, pg, off, kp,
                                           vp, None, cbt, clen, None,
                                           None)
                self._prefill_paged_ro = jax.jit(_prefill_paged_ro,
                                                 donate_argnums=(8, 9))
            else:
                def _prefill_paged(params, toks, qoff, kvlen, last, bt,
                                   pg, off, kp, vp):
                    return M.prefill_paged(params, cfg, toks, qoff,
                                           kvlen, last, bt, pg, off, kp,
                                           vp)
            # donate the pools: XLA updates them in place instead of
            # copying the whole KV pool every chunk (no-op on CPU)
            self._prefill_paged = jax.jit(_prefill_paged,
                                          donate_argnums=(8, 9))
        else:
            self._caches: Dict[str, object] = {}

            def _prefill(params, toks, cache, q_offset):
                return M.prefill(params, cfg, toks, cache,
                                 q_offset=q_offset)
            self._prefill = jax.jit(_prefill)

            def _prefill_enc(params, toks, cache, q_offset, enc):
                return M.prefill(params, cfg, toks, cache,
                                 q_offset=q_offset, enc_embeds=enc)
            # first chunk of a cross-attention request: also prefills
            # the cross KV (ck/cv) from the frontend embeddings
            self._prefill_enc = jax.jit(_prefill_enc)

    # ------------------------------------------------------------------
    def submit(self, req: Request) -> None:
        # strict bound: decode must append at least one token at position
        # prompt_len inside a pages_for(max_seq)-wide block-table row
        assert req.prompt_len < self.max_seq, \
            f"{req.rid}: prompt {req.prompt_len} >= max_seq {self.max_seq}"
        self.scheduler.add(req)
        self._reqs[req.rid] = req

    @property
    def queued_tokens(self) -> int:
        return self.scheduler.queued_tokens + sum(
            c.tokens for c in self._chunk_queue)

    def idle(self) -> bool:
        return len(self.scheduler) == 0 and not self._chunk_queue

    def resident(self) -> List[Request]:
        """Requests this engine still owns (queued or mid-prefill) —
        the set a dead instance strands (docs/fault_tolerance.md)."""
        return list(self._reqs.values())

    def cancel(self, rid: str) -> bool:
        """User cancel before/while prefilling: drop the request from the
        local scheduler and the chunk queue and free any pages/cache it
        holds.  Returns whether this engine still owned the request."""
        if rid not in self._reqs:
            return False
        self._reqs.pop(rid)
        self.scheduler.remove(rid)
        self._chunk_queue = collections.deque(
            chunking.drop_rid(self._chunk_queue, rid))
        if self.backend == "paged":
            if self.alloc.has(rid):
                self.alloc.free(rid)
        else:
            self._caches.pop(rid, None)
        self._page_keys.pop(rid, None)
        return True

    # ------------------------------------------------------------------
    def _refill_chunks(self) -> None:
        batch = self.scheduler.next_batch(self.scheduler.sched_batch)
        if not batch:
            return
        if self.backend == "paged":
            # reserve each request's prompt pages up front — prefill
            # writes every prompt position, so ALL pages materialize
            # (windowed configs trim them back to the free list as
            # chunks slide past); requests that don't fit the pool right
            # now go back to the head of the queue — backpressure
            # instead of an OutOfPages crash mid-batch
            fit, defer = [], []
            for r in batch:
                keys = cross_key = None
                if self.prefix_cache:
                    # cap aliasing at the last FULL page strictly before
                    # the final prompt token: the last token is always
                    # recomputed so the finished request still emits its
                    # first-token logits
                    full = request_page_keys(r, self.page_size) or []
                    self._page_keys[r.rid] = full
                    keys = full[:max(0, (r.prompt_len - 1)
                                     // self.page_size)]
                    if self.spec.cross == "pages":
                        cross_key = request_cross_key(r)
                if self.alloc.can_admit(r.prompt_len,
                                        materialize_all=True,
                                        page_keys=keys,
                                        cross_key=cross_key):
                    self.alloc.alloc(r.rid, r.prompt_len,
                                     materialize_all=True,
                                     page_keys=keys, cross_key=cross_key)
                    r.cached_prefix_pages = \
                        self.alloc.cached_prefix_pages(r.rid)
                    r.cached_prefix_tokens = \
                        self.alloc.cached_prefix_tokens(r.rid)
                    fit.append(r)
                else:
                    if self.alloc.pages_for(max(1, r.prompt_len)) \
                            > self.alloc.n_pages:
                        raise OutOfPages(
                            f"{r.rid}: prompt {r.prompt_len} exceeds the "
                            f"whole pool ({self.alloc.n_pages} pages)")
                    defer.append(r)
            if defer:
                self.scheduler.requeue_front(defer)
            batch = fit
            if not batch:
                return
        else:
            for r in batch:
                self._caches[r.rid] = M.init_cache(self.cfg, 1,
                                                   self.max_seq)
        pairs = [(r.rid, r.prompt_len) for r in batch]
        # cached-prefix pages are skipped, not recomputed: each request's
        # segments start at its first uncached token
        starts = {r.rid: r.cached_prefix_tokens for r in batch
                  if r.cached_prefix_tokens}
        self._chunk_queue.extend(chunking.partition(
            pairs, self.chunk_size, starts=starts or None))
        for r in batch:
            r.phase = Phase.PREFILL

    def step(self, now: float) -> List[PrefilledKV]:
        """Run ONE fixed-size chunk (the paper's prefill iteration unit).
        Returns requests whose prefill completed this step."""
        ph = self.phases
        if ph is not None:
            ph.open("prefill_build")     # closed before the dispatch
        if not self._chunk_queue:
            self._refill_chunks()
        if not self._chunk_queue:
            if ph is not None:
                ph.close()
            return []
        chunk = self._chunk_queue.popleft()
        self.chunk_steps += 1
        if self.backend == "paged":
            return self._step_paged(chunk, now)
        if ph is not None:
            ph.close()
        return self._step_dense(chunk, now)

    # -- paged backend -------------------------------------------------
    def _step_paged(self, chunk: chunking.Chunk, now: float
                    ) -> List[PrefilledKV]:
        """Pack the chunk's segments flat and issue exactly ONE fused
        model call for the whole chunk."""
        segs = chunk.segments
        n = len(segs)
        ns = _pow2(n)                          # jit-stable batch dim
        sq = _pow2(max(s.length for s in segs))
        ps, trash = self.page_size, self._trash
        toks = np.zeros((ns, sq), np.int32)
        qoff = np.zeros((ns,), np.int32)
        kvlen = np.zeros((ns,), np.int32)
        last = np.zeros((ns,), np.int32)
        bt = np.full((ns, self._bt_width), trash, np.int32)
        pg = np.full((ns, sq), trash, np.int32)
        off = np.tile(np.arange(sq, dtype=np.int32) % ps, (ns, 1))
        cross = self.spec.cross == "pages"
        scattered: List[str] = []   # rids whose cross pages land this call
        if cross:
            ec = self.enc_ctx
            enc = np.zeros((ns, ec, self.cfg.d_model), np.float32)
            cbt = np.full((ns, self._cross_bt_width), trash, np.int32)
            clen = np.zeros((ns,), np.int32)
            cpg = np.full((ns, ec), trash, np.int32)
            coff = np.tile(np.arange(ec, dtype=np.int32) % ps, (ns, 1))
        for i, seg in enumerate(segs):
            req = self._reqs[seg.rid]
            if req.t_prefill_start < 0:
                req.t_prefill_start = now
            if req.prompt_tokens is not None:
                toks[i, :seg.length] = req.prompt_tokens[
                    seg.req_start: seg.req_start + seg.length]
            qoff[i] = seg.req_start
            kvlen[i] = seg.req_start + seg.length
            last[i] = seg.length - 1
            table = np.asarray(self.alloc.table_padded(seg.rid, trash),
                               np.int32)
            bt[i, :len(table)] = table
            pos = seg.req_start + np.arange(seg.length)
            pg[i, :seg.length] = table[pos // ps]
            off[i, :seg.length] = pos % ps
            if cross:
                ctab = np.asarray(self.alloc.cross_table(seg.rid),
                                  np.int32)
                cbt[i, :len(ctab)] = ctab
                clen[i] = self.enc_ctx
                if (seg.req_start == self.alloc.cached_prefix_tokens(
                        seg.rid)
                        and not self.alloc.cross_cached(seg.rid)):
                    # one-shot cross-KV prefill: only a request's FIRST
                    # segment (which starts right after any cached
                    # prefix) scatters the encoder K/V into its cross
                    # pages — later chunks only read them, and cache-hit
                    # requests alias pages another request already wrote
                    # (cpg stays at the scratch page: write is a no-op)
                    if req.enc_embeds is not None:
                        enc[i] = req.enc_embeds
                    epos = np.arange(self.enc_ctx)
                    cpg[i] = ctab[epos // ps]
                    scattered.append(seg.rid)
        ph = self.phases
        if ph is not None:
            ph.close()
            ph.open("prefill_device")
        if cross and scattered:
            next_tok, _, kp, vp = self._prefill_paged(
                self.params, jnp.asarray(toks), jnp.asarray(qoff),
                jnp.asarray(kvlen), jnp.asarray(last), jnp.asarray(bt),
                jnp.asarray(pg), jnp.asarray(off), self.pool.k,
                self.pool.v, jnp.asarray(enc), jnp.asarray(cbt),
                jnp.asarray(clen), jnp.asarray(cpg), jnp.asarray(coff))
            self.encoder_calls += 1
        elif cross:
            # no segment needs encoder work: read-only cross chunk
            next_tok, _, kp, vp = self._prefill_paged_ro(
                self.params, jnp.asarray(toks), jnp.asarray(qoff),
                jnp.asarray(kvlen), jnp.asarray(last), jnp.asarray(bt),
                jnp.asarray(pg), jnp.asarray(off), self.pool.k,
                self.pool.v, jnp.asarray(cbt), jnp.asarray(clen))
        else:
            next_tok, _, kp, vp = self._prefill_paged(
                self.params, jnp.asarray(toks), jnp.asarray(qoff),
                jnp.asarray(kvlen), jnp.asarray(last), jnp.asarray(bt),
                jnp.asarray(pg), jnp.asarray(off), self.pool.k,
                self.pool.v)
        self.pool = PagePool(k=kp, v=vp)
        self.fused_calls += 1
        for rid in scattered:
            # cross pages now hold real encoder K/V: publish them so
            # later requests with the same encoder input alias them
            self.alloc.commit_cross(rid)
        next_tok = np.asarray(next_tok)
        if ph is not None:
            ph.close(segs=[[s.rid, s.req_start, s.length] for s in segs],
                     rows=ns, cols=sq)
            ph.open("prefill_finish")
        finished: List[PrefilledKV] = []
        for i, seg in enumerate(segs):
            req = self._reqs[seg.rid]
            req.prefilled = seg.req_start + seg.length
            # windowed: pages the processed prefix slid past go back to
            # the free list (no-op for unwindowed configs)
            self.alloc.trim(seg.rid, req.prefilled)
            if req.prefilled >= req.prompt_len:
                finished.append(
                    self._finish_paged(req, int(next_tok[i]), now))
        if ph is not None:
            ph.close(pages=sum(pk.n_pages for pk in finished))
        return finished

    def _finish_paged(self, req: Request, first_tok: int, now: float
                      ) -> PrefilledKV:
        n_chunks = self._note_finished(req, now)
        enc_len = self.enc_ctx if self.spec.cross == "pages" else 0
        cross_cached = self.alloc.cross_cached(req.rid)
        delay = self.network.send_kv(self.cfg, req.prompt_len,
                                     n_chunks=n_chunks,
                                     page_size=self.page_size,
                                     enc_len=enc_len,
                                     cached_tokens=req.cached_prefix_tokens,
                                     cross_cached=cross_cached)
        req.phase = Phase.TRANSFER
        # ship the LIVE pages only: for windowed configs that is the
        # O(window) in-window suffix, exactly what the decode side's
        # window-aware allocator will hold for this request.  The
        # payload still CARRIES any cached-prefix pages (they are live
        # aliases in this pool) so a decode side without those cache
        # entries stays correct; the wire accounting above subtracts
        # them (content-addressed store assumption, docs/prefix_cache.md).
        # gather() materializes a COPY of the page contents, and the
        # pages are freed right below — the payload is double-buffered
        # by construction: a transfer thread can hold it in flight
        # while this engine's next chunk scatters into the freed pages
        # (docs/async_runtime.md)
        pages_k, pages_v = self.pool.gather(self.alloc.live_pages(req.rid))
        cross_k = cross_v = None
        if enc_len:
            # plus the one-shot read-only cross pages (encoder K/V)
            cross_k, cross_v = self.pool.gather(
                self.alloc.cross_table(req.rid))
        # publish the finished request's full prompt pages under their
        # content hashes BEFORE freeing: the cache keeps them alive
        # (refcounted) for the next request sharing this prefix
        if self.prefix_cache:
            self.alloc.commit(req.rid, self._page_keys.pop(req.rid, []))
        self.alloc.free(req.rid)
        self._reqs.pop(req.rid)
        return PrefilledKV(req=req, first_token=first_tok,
                           transfer_delay_s=delay, n_chunks=n_chunks,
                           pages_k=pages_k, pages_v=pages_v,
                           kv_len=req.prompt_len,
                           cross_k=cross_k, cross_v=cross_v,
                           enc_len=enc_len,
                           cached_tokens=req.cached_prefix_tokens,
                           cross_cached=cross_cached)

    # -- dense backend (legacy fallback) --------------------------------
    def _step_dense(self, chunk: chunking.Chunk, now: float
                    ) -> List[PrefilledKV]:
        finished: List[PrefilledKV] = []
        for seg in chunk.segments:
            req = self._reqs[seg.rid]
            if req.t_prefill_start < 0:
                req.t_prefill_start = now
            toks = np.zeros((1, seg.length), np.int32)
            if req.prompt_tokens is not None:
                toks[0] = req.prompt_tokens[
                    seg.req_start: seg.req_start + seg.length]
            if self.enc_ctx and seg.req_start == 0:
                # first chunk of a cross-attention request: prefill the
                # cross KV (ck/cv) from the frontend embeddings (zeros
                # for frontend-less requests — cross output is 0 then)
                enc = np.zeros((1, self.enc_ctx, self.cfg.d_model),
                               np.float32)
                if req.enc_embeds is not None:
                    enc[0] = req.enc_embeds
                logits, cache = self._prefill_enc(
                    self.params, jnp.asarray(toks), self._caches[seg.rid],
                    seg.req_start, jnp.asarray(enc))
            else:
                logits, cache = self._prefill(
                    self.params, jnp.asarray(toks), self._caches[seg.rid],
                    seg.req_start)
            self._caches[seg.rid] = cache
            req.prefilled = seg.req_start + seg.length
            if req.prefilled >= req.prompt_len:
                finished.append(self._finish_dense(req, logits, now))
        return finished

    def _finish_dense(self, req: Request, logits, now: float
                      ) -> PrefilledKV:
        n_chunks = self._note_finished(req, now)
        delay = self.network.send_kv(self.cfg, req.prompt_len,
                                     n_chunks=n_chunks,
                                     enc_len=self.enc_ctx)
        req.phase = Phase.TRANSFER
        first_tok = int(np.asarray(jnp.argmax(logits[0, -1])))
        cache = self._caches.pop(req.rid)
        self._reqs.pop(req.rid)
        return PrefilledKV(req=req, cache=cache, first_token=first_tok,
                           transfer_delay_s=delay, n_chunks=n_chunks,
                           kv_len=req.prompt_len, enc_len=self.enc_ctx)

    # -- shared finish bookkeeping --------------------------------------
    def _note_finished(self, req: Request, now: float) -> int:
        req.t_first_token = now     # chunked prefill emits the first token
        if self.predictor is not None:
            b, lo, hi = self.predictor.predict_range(
                req.prompt_tokens, req.decode_len)
            req.predicted_bucket, req.predicted_lo, req.predicted_hi = \
                b, lo, hi
        # cached-prefix tokens were never chunked, so they also never
        # contribute chunk-granular transfer slices
        return chunking.chunks_for(
            req.prompt_len - req.cached_prefix_tokens, self.chunk_size)

    def select_decode_instance(self, loads, req: Request) -> Optional[str]:
        return self.dispatcher.select(
            loads, req.prompt_len, req.predicted_hi,
            heavy=req.is_heavy_decode())
