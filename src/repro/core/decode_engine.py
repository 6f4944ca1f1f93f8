"""Decode instance (paper §3.4): receiver -> working-set-aware local
scheduler -> continuous-batching decode engine.

Slot-based continuous batching: a fixed-capacity slot batch (XLA-friendly
static shapes) with a validity mask; the admission policy (greedy /
reserve-static / reserve-dynamic) decides which queued requests join each
iteration against the paged-KV allocator.

Execution backends (selected by ``core.backend.backend_for``):
  * ``paged`` (default for every uniform-attention arch: GQA, MLA
    latent, full or sliding-window) — K/V lives in a shared device
    ``PagePool``; admission INSTALLS the received page contents and a
    block-table row (no dense ``cache_insert`` copy), every iteration
    runs the full slot batch through the Pallas paged-decode kernels,
    block tables grow page-at-a-time via the allocator's
    ``append_token`` — which also FREES pages that slide out of the
    attention window, so windowed decode holds O(window) pages — and
    argmax stays on device (one int per slot crosses to host).
    Cross-attention archs (VLM / enc-dec) install the shipped encoder
    pages once at admission; every iteration streams them READ-ONLY
    through a second block table (no cross scatter ever happens at
    decode) and they are freed exactly once when the request finishes.
  * ``dense`` — legacy (max_slots, max_seq) dense cache; retained for
    recurrent/hybrid architectures.
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backend import backend_for
from repro.core.decode_types import FinishedRequest
from repro.core.prefill_engine import PrefilledKV, make_page_pool
from repro.core.sched.decode_scheduler import DecodeScheduler
from repro.kvcache.paged import PagedAllocator, PagePool
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.runtime.request import Phase, Request


def _step_seed(seed: int, n_generated: int) -> int:
    """Per-(request, step) PRNG seed for on-device sampling.  Derived
    from the request's ``SamplingParams.seed`` and how many tokens it
    has generated — never from the decode slot or batch composition —
    so a request's sample stream is identical across engines, admission
    orders and (async-runtime) thread interleavings."""
    return zlib.crc32(f"{seed}:{n_generated}".encode()) & 0xFFFFFFFF


@dataclasses.dataclass
class SlotState:
    req: Request
    last_token: int
    tokens: List[int]


class DecodeEngine:
    def __init__(self, iid: str, cfg: ModelConfig, params, *,
                 max_slots: int = 8, max_seq: int = 512,
                 policy: str = "reserve-dynamic",
                 n_pages: int = 512, page_size: int = 16,
                 backend: str = "auto", prefix_cache: bool = False):
        self.iid = iid
        self.cfg = cfg
        self.params = params
        self.max_slots = max_slots
        self.max_seq = max_seq
        self.spec = backend_for(cfg, backend)
        self.backend = self.spec.backend
        self.enc_ctx = self.spec.cross_ctx
        # same gating as the prefill side: stable page content + paged
        self.prefix_cache = (prefix_cache and self.backend == "paged"
                             and not cfg.sliding_window)
        self.alloc = PagedAllocator(
            n_pages=n_pages, page_size=page_size,
            window=cfg.sliding_window,
            cross_tokens=self.enc_ctx if self.spec.cross == "pages"
            else 0,
            prefix_cache=self.prefix_cache)
        self.scheduler = DecodeScheduler(self.alloc, policy=policy,
                                         max_batch=max_slots)
        self.page_size = page_size
        self.slots: Dict[int, SlotState] = {}
        self._pending: Dict[str, PrefilledKV] = {}
        self.iterations = 0
        # (rid, token) pairs emitted by the LAST step() — the streaming
        # feed the serving Cluster forwards to request handles
        self.stream_events: List[Tuple[str, int]] = []
        #: step-phase recorder (repro.obs.tracer.PhaseRecorder), set by
        #: the wall-clock runtime when it has a tracer; None costs one
        #: check per phase boundary
        self.phases = None

        if self.backend == "paged":
            # the allocator's block tables ARE the physical mapping
            self.pool, self._trash = make_page_pool(cfg, n_pages,
                                                    page_size)
            self._bt_width = self.alloc.pages_for(max_seq)
            self._cross_bt_width = self.alloc.cross_pages_per_request

            if self.spec.cross == "pages":
                def _decode_paged(params, toks, pos, pages, offs, bt,
                                  lens, cbt, clens, kp, vp):
                    return M.decode_step_paged(params, cfg, toks, pos,
                                               pages, offs, bt, lens,
                                               kp, vp, cbt, clens)
                donate = (9, 10)

                def _decode_sampled(params, toks, pos, pages, offs, bt,
                                    lens, cbt, clens, temps, tks, seeds,
                                    kp, vp):
                    return M.decode_step_paged(params, cfg, toks, pos,
                                               pages, offs, bt, lens,
                                               kp, vp, cbt, clens,
                                               temps, tks, seeds)
                donate_s = (12, 13)
            else:
                def _decode_paged(params, toks, pos, pages, offs, bt,
                                  lens, kp, vp):
                    return M.decode_step_paged(params, cfg, toks, pos,
                                               pages, offs, bt, lens,
                                               kp, vp)
                donate = (7, 8)

                def _decode_sampled(params, toks, pos, pages, offs, bt,
                                    lens, temps, tks, seeds, kp, vp):
                    return M.decode_step_paged(params, cfg, toks, pos,
                                               pages, offs, bt, lens,
                                               kp, vp, None, None,
                                               temps, tks, seeds)
                donate_s = (10, 11)
            # donate the pools: in-place pool update per iteration
            # instead of a full KV-pool copy (no-op on CPU)
            self._decode_paged = jax.jit(_decode_paged,
                                         donate_argnums=donate)
            # sampled variant compiles lazily on first use, so pure
            # greedy workloads never pay for it — and greedy batches
            # keep calling the exact pre-sampling executable
            self._decode_paged_sampled = jax.jit(_decode_sampled,
                                                 donate_argnums=donate_s)
        else:
            self.cache = M.init_cache(cfg, max_slots, max_seq)

            def _decode(params, toks, cache, pos):
                return M.decode_step_greedy(params, cfg, toks, cache, pos)
            self._decode = jax.jit(_decode)

    # ------------------------------------------------------------------
    def receive(self, pk: PrefilledKV,
                now: Optional[float] = None) -> None:
        """Receiver module: prefilled KV has arrived (post transfer wait).
        ``now`` (when the caller tracks time) stamps the transfer-done
        timestamp that ``summarize`` turns into ``avg_transfer``."""
        # block-table rows are sized for max_seq; the finish condition in
        # step() keeps every admitted sequence inside that bound
        assert pk.req.prompt_len < self.max_seq, \
            f"{pk.req.rid}: prompt {pk.req.prompt_len} >= max_seq"
        pk.req.phase = Phase.DECODE_QUEUED
        if now is not None:
            pk.req.t_transfer_done = now
        self._pending[pk.req.rid] = pk
        self.scheduler.enqueue(pk.req)

    def _free_slot(self) -> Optional[int]:
        for s in range(self.max_slots):
            if s not in self.slots:
                return s
        return None

    def admit(self, now: float) -> List[Request]:
        ph = self.phases
        if ph is not None:
            ph.open("decode_admit")
        admitted = self.scheduler.admit()
        pages: List[int] = []
        payload_k, payload_v = [], []
        for req in admitted:
            slot = self._free_slot()
            assert slot is not None, "scheduler admitted past slot capacity"
            pk = self._pending.pop(req.rid)
            if self.backend == "paged":
                # stage the received pages for the pages the scheduler's
                # admission just allocated; the block-table row is the
                # allocator's table — no dense cache_insert copy.  For
                # windowed configs both sides hold only the in-window
                # live pages, so the counts line up by construction.
                live = self.alloc.live_pages(req.rid)
                assert pk.pages_k is not None and \
                    pk.pages_k.shape[1] == len(live), \
                    "paged decode engine needs a page-granular payload " \
                    "from a paged prefill engine with the same page_size"
                # prefix-cache hits were aliased by the admission alloc:
                # their contents are already in this pool (written when
                # the cache entry's original request installed them), so
                # only the fresh suffix pages take the payload
                hit = self.alloc.cached_prefix_pages(req.rid)
                if hit:
                    pages.extend(live[hit:])
                    if hit < len(live):
                        payload_k.append(pk.pages_k[:, hit:])
                        payload_v.append(pk.pages_v[:, hit:])
                else:
                    pages.extend(live)
                    payload_k.append(pk.pages_k)
                    payload_v.append(pk.pages_v)
                if self.spec.cross == "pages":
                    # the one-shot cross payload lands in the cross
                    # pages the admission alloc drew from the same pool
                    # — unless the alloc deduped them against another
                    # resident request's encoder pages
                    ctab = self.alloc.cross_table(req.rid)
                    assert pk.cross_k is not None and \
                        pk.cross_k.shape[1] == len(ctab), \
                        "cross-attention arch needs the encoder pages " \
                        "shipped alongside the self KV"
                    if not self.alloc.cross_cached(req.rid):
                        pages.extend(ctab)
                        payload_k.append(pk.cross_k)
                        payload_v.append(pk.cross_v)
                        self.alloc.commit_cross(req.rid)
            else:
                self.cache = M.cache_insert(self.cache, pk.cache, slot)
            self.slots[slot] = SlotState(req=req,
                                         last_token=pk.first_token,
                                         tokens=[pk.first_token])
            req.phase = Phase.DECODE
            if req.t_decode_start < 0:
                req.t_decode_start = now
        if pages:
            # one scatter for the whole admitted batch
            self.pool = self.pool.install(
                pages, jnp.concatenate(payload_k, axis=1),
                jnp.concatenate(payload_v, axis=1))
        # the prefill-emitted first token can itself satisfy the user's
        # stop criteria (e.g. immediate EOS): finish before any decode
        # iteration runs, releasing the slot and pages right away
        admitted_rids = {r.rid for r in admitted}
        for s in list(self.slots):
            st = self.slots[s]
            req = st.req
            if req.rid in admitted_rids and req.sampling is not None \
                    and req.sampling.should_stop(1, st.last_token):
                req.phase = Phase.FINISHED
                req.t_finish = now
                self.scheduler.finish(req.rid)
                del self.slots[s]
        if ph is not None:
            if admitted:
                ph.close(admitted=len(admitted), pages=len(pages))
            else:
                ph.drop()
        return admitted

    def step(self, now: float) -> List[FinishedRequest]:
        """One continuous-batching decode iteration over the slot batch."""
        self.stream_events = []    # even on the empty early return: a
        if not self.slots:         # cancel can drain the batch with a
            return []              # decode_done event still in flight
        self.iterations += 1
        if self.backend == "paged":
            nxt = self._iteration_paged()
        else:
            nxt = self._iteration_dense()
        ph = self.phases
        if ph is not None:
            ph.open("decode_commit")
        finished: List[FinishedRequest] = []
        for s in list(self.slots):
            st = self.slots[s]
            req = st.req
            st.last_token = int(nxt[s])
            st.tokens.append(st.last_token)
            self.stream_events.append((req.rid, st.last_token))
            # stop criteria: the user's SamplingParams when attached
            # (serving API), else the ground-truth decode_len (oracle
            # mode); the max_seq guard always bounds the block table
            if req.sampling is not None:
                stop = req.sampling.should_stop(len(st.tokens),
                                                st.last_token)
            else:
                stop = req.generated >= req.decode_len
            if stop or req.prompt_len + req.generated >= self.max_seq - 1:
                req.phase = Phase.FINISHED
                req.t_finish = now
                self.scheduler.finish(req.rid)
                finished.append(FinishedRequest(req=req, tokens=st.tokens))
                del self.slots[s]
        if ph is not None:
            ph.close()
        return finished

    def cancel(self, rid: str) -> bool:
        """User cancel mid-decode: releases the slot and frees the
        request's pages (running) or drops it from the queue (pending).
        Returns whether this engine knew the request."""
        for s, st in list(self.slots.items()):
            if st.req.rid == rid:
                del self.slots[s]
                return self.scheduler.cancel(rid)
        known = rid in self._pending
        self._pending.pop(rid, None)
        return self.scheduler.cancel(rid) or known

    def _iteration_paged(self) -> np.ndarray:
        """Full-slot-batch fused decode against the page pool."""
        ph = self.phases
        if ph is not None:
            ph.open("decode_build")
        ms, ps, trash = self.max_slots, self.page_size, self._trash
        toks = np.zeros((ms, 1), np.int32)
        pos = np.zeros((ms,), np.int32)
        pages = np.full((ms,), trash, np.int32)
        offs = np.zeros((ms,), np.int32)
        bt = np.full((ms, self._bt_width), trash, np.int32)
        lens = np.zeros((ms,), np.int32)
        cross = self.spec.cross == "pages"
        if cross:
            cbt = np.full((ms, self._cross_bt_width), trash, np.int32)
            clens = np.zeros((ms,), np.int32)
        for s, st in self.slots.items():
            p = st.req.prompt_len + st.req.generated
            # account the token being appended THIS iteration; the
            # returned physical page is where its K/V scatters
            pages[s] = self.scheduler.step_token(st.req.rid)
            toks[s, 0] = st.last_token
            pos[s] = p
            offs[s] = p % ps
            table = self.alloc.table_padded(st.req.rid, trash)
            bt[s, :len(table)] = table
            lens[s] = p + 1
            if cross:
                ctab = self.alloc.cross_table(st.req.rid)
                cbt[s, :len(ctab)] = ctab
                clens[s] = self.enc_ctx
        # copy-on-write: step_token may have redirected a slot's tail
        # page off a shared page — replay the page copies on the device
        # pool BEFORE the kernels scatter this iteration's tokens
        cows = self.alloc.take_cow_copies()
        if cows:
            src, dst = zip(*cows)
            self.pool = self.pool.copy_pages(list(src), list(dst))
        # on-device sampling: only when a resident request asks for it —
        # pure-greedy batches dispatch the original executable, so their
        # tokens stay byte-identical to the pre-sampling engine
        sampled = any(
            st.req.sampling is not None and not st.req.sampling.greedy
            for st in self.slots.values())
        if sampled:
            temps = np.zeros((ms,), np.float32)
            tks = np.zeros((ms,), np.int32)
            seeds = np.zeros((ms,), np.uint32)
            for s, st in self.slots.items():
                sp = st.req.sampling
                if sp is not None and not sp.greedy:
                    temps[s] = sp.temperature
                    tks[s] = sp.top_k
                    seeds[s] = _step_seed(sp.seed, len(st.tokens))
            extra = (jnp.asarray(temps), jnp.asarray(tks),
                     jnp.asarray(seeds))
            fn = self._decode_paged_sampled
        else:
            extra = ()
            fn = self._decode_paged
        if ph is not None:
            ph.close(slots=len(self.slots))
            ph.open("decode_device")
        if cross:
            nxt, kp, vp = fn(
                self.params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(pages), jnp.asarray(offs), jnp.asarray(bt),
                jnp.asarray(lens), jnp.asarray(cbt), jnp.asarray(clens),
                *extra, self.pool.k, self.pool.v)
        else:
            nxt, kp, vp = fn(
                self.params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(pages), jnp.asarray(offs), jnp.asarray(bt),
                jnp.asarray(lens), *extra, self.pool.k, self.pool.v)
        self.pool = PagePool(k=kp, v=vp)
        nxt = np.asarray(nxt)
        if ph is not None:
            ph.close()
        return nxt

    def _iteration_dense(self) -> np.ndarray:
        toks = np.zeros((self.max_slots, 1), np.int32)
        pos = np.zeros((self.max_slots,), np.int32)
        for s, st in self.slots.items():
            toks[s, 0] = st.last_token
            pos[s] = st.req.prompt_len + st.req.generated
            self.scheduler.step_token(st.req.rid)
        nxt, self.cache = self._decode(
            self.params, jnp.asarray(toks), self.cache, jnp.asarray(pos))
        return np.asarray(nxt)

    # ------------------------------------------------------------------
    def load(self) -> dict:
        return self.scheduler.load()

    def idle(self) -> bool:
        return not self.slots and not self.scheduler.queue

    def resident(self) -> List[Request]:
        """Requests this engine still owns (pending install, queued or
        in a slot) — stranded if the instance dies; their KV dies with
        the pool, so recovery re-prefills from the prompt."""
        seen: Dict[str, Request] = {}
        for pk in self._pending.values():
            seen[pk.req.rid] = pk.req
        for r in self.scheduler.queue:
            seen[r.rid] = r
        for st in self.slots.values():
            seen[st.req.rid] = st.req
        return list(seen.values())
