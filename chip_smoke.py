#!/usr/bin/env python3
"""Chip smoke: the disaggregated serving path on one TPU chip.

Serves ``qwen2_0_5b`` at its full published width, in its own dtype
(bf16), with random weights made from ``--seed``, through the entry
points a user calls: ``Cluster(runtime="engine")`` with one prefill and
one decode instance over the paged KV pool, and the Pallas kernels
compiled to Mosaic.  Three phases, each of which must pass:

  serve      bf16, max_seq 2048, chunk 512, 2048-page pools: 8 greedy
             requests with prompts of 64-1500 tokens (chunks pack several
             segments; at least one prompt spans several chunks), 32 new
             tokens each.  Every request finishes with exactly 32 tokens,
             every first-token logit is finite, and the compiled prefill
             and decode steps hold Mosaic kernel calls.
  wallclock  the same model through ``AsyncCluster`` (worker threads of
             this process): every request reaches ``finished``.
  check      float32 under matmul precision "highest": the cluster's
             greedy tokens equal ``CoupledEngine``'s for every request.
             That engine attends with plain ``jax.numpy``, so it checks
             the Pallas kernels independently.

The times printed are smoke times for information, not metrics.  The
last line of stdout is one JSON object naming the device.  When JAX finds
no TPU the script exits non-zero before it builds anything.  The program
runs in this one process: only one process may hold the chip.

    python3 chip_smoke.py               # on a machine with one TPU chip
    python3 chip_smoke.py --rehearse    # same phases, smoke config, CPU
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "qwen2_0_5b"
N_REQUESTS = 8
NEW_TOKENS = 32
N_WALLCLOCK = 3


@dataclasses.dataclass(frozen=True)
class Size:
    smoke_config: bool
    max_seq: int
    chunk: int
    n_pages: int        # per engine, serve and wallclock phases
    check_pages: int    # per engine, float32 check phase
    prompt_lo: int
    prompt_hi: int


# the paper's fixed 512-token chunk at published width
CHIP = Size(smoke_config=False, max_seq=2048, chunk=512, n_pages=2048,
            check_pages=1024, prompt_lo=64, prompt_hi=1500)
REHEARSAL = Size(smoke_config=True, max_seq=256, chunk=64, n_pages=256,
                 check_pages=256, prompt_lo=8, prompt_hi=190)


class SmokeFailure(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class StepTap:
    """Stands in for one of an engine's jitted steps.  On the first call
    it compiles the step ahead of time and keeps the compiled text (to
    find the Mosaic kernel calls); every call's arguments and outputs go
    to ``on_call``."""

    def __init__(self, fn, on_call=None, keep_text: bool = False):
        self.fn = fn
        self.on_call = on_call
        self.keep_text = keep_text
        self.text = None

    def __call__(self, *args):
        if self.keep_text and self.text is None:
            self.text = self.fn.lower(*args).compile().as_text()
        out = self.fn(*args)
        if self.on_call is not None:
            self.on_call(args, out)
        return out


class FirstLogits:
    """Keeps, per prompt, the logits row that produced its first token."""

    def __init__(self, prompts):
        self.prompts = prompts
        self.rows = {}
        self.max_segments = 0

    def _match(self, toks, start, end):
        for i, p in enumerate(self.prompts):
            if len(p) == end and np.array_equal(p[start:end], toks):
                return i
        return None

    def paged(self, args, out):
        """Paged prefill step: args (params, toks, q_offset, kv_len, ...),
        out (next_tok, last_logits, k_pool, v_pool)."""
        toks, qoff, kvlen = (np.asarray(a) for a in args[1:4])
        self.max_segments = max(self.max_segments, int((kvlen > 0).sum()))
        for s in np.flatnonzero(kvlen):
            i = self._match(toks[s, :kvlen[s] - qoff[s]], qoff[s], kvlen[s])
            if i is not None:
                self.rows[i] = np.asarray(out[1][s], np.float32)

    def dense(self, args, out):
        """CoupledEngine prefill: args (params, toks, cache, q_offset),
        out (logits (1, 1, V), cache)."""
        toks = np.asarray(args[1])[0]
        i = self._match(toks, 0, len(toks))
        if i is not None:
            self.rows[i] = np.asarray(out[0][0, -1], np.float32)


def make_prompts(size: Size, vocab: int, seed: int):
    rng = np.random.default_rng(seed)
    lens = rng.integers(size.prompt_lo, size.prompt_hi + 1, N_REQUESTS)
    if lens.max() <= size.chunk:
        lens[0] = size.prompt_hi        # one prompt spans several chunks
    return [rng.integers(1, vocab, int(n), dtype=np.int32) for n in lens]


def engine_cluster(cfg, params, size: Size, n_pages: int):
    from repro.serving import Cluster
    return Cluster(cfg, runtime="engine", params=params, n_prefill=1,
                   n_decode=1, chunk_size=size.chunk, max_seq=size.max_seq,
                   n_pages=n_pages, max_batch=N_REQUESTS)


def serve_phase(cfg, params, prompts, size: Size, on_tpu: bool):
    import jax.numpy as jnp

    from repro.serving import SamplingParams
    cluster = engine_cluster(cfg, params, size, size.n_pages)
    pe, de = cluster.instances[0].pe, cluster.instances[1].de
    logits = FirstLogits(prompts)
    pre = pe._prefill_paged = StepTap(pe._prefill_paged, logits.paged,
                                      keep_text=True)
    dec = de._decode_paged = StepTap(de._decode_paged, keep_text=True)
    sp = SamplingParams(max_new_tokens=NEW_TOKENS)

    t0 = time.perf_counter()
    handles = [cluster.submit(prompts[0], sampling=sp)]
    cluster.run()
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    handles += [cluster.submit(p, sampling=sp) for p in prompts[1:]]
    cluster.run()
    warm = time.perf_counter() - t0

    results = [h.result() for h in handles]
    for i, r in enumerate(results):
        check(r.phase.value == "finished", f"serve: {r.rid} is {r.phase}")
        check(len(r.tokens) == NEW_TOKENS,
              f"serve: {r.rid} has {len(r.tokens)} tokens")
        check(all(0 <= t < cfg.vocab_size for t in r.tokens),
              f"serve: {r.rid} emitted a token outside the vocabulary")
        check(i in logits.rows, f"serve: no first-token logits for {r.rid}")
        check(np.isfinite(logits.rows[i]).all(),
              f"serve: non-finite first-token logits for {r.rid}")
        check(int(np.argmax(logits.rows[i])) == r.tokens[0],
              f"serve: {r.rid}'s first token is not its logits' argmax")
    check(logits.max_segments >= 2, "serve: no chunk packed two segments")
    check(max(map(len, prompts)) > size.chunk,
          "serve: no prompt spans several chunks")
    check(all(bool(jnp.isfinite(x).all()) for x in (de.pool.k, de.pool.v)),
          "serve: the decode pool holds non-finite K/V")
    kernels = {name: tap.text.count("tpu_custom_call")
               for name, tap in (("prefill", pre), ("decode", dec))}
    if on_tpu:
        for name, n in kernels.items():
            check(n > 0, f"serve: the compiled {name} step has no Mosaic "
                         f"kernel call")
    print(f"serve: {len(results)} requests x {NEW_TOKENS} tokens finished; "
          f"prompt lengths {sorted(map(len, prompts))}; "
          f"{pe.fused_calls} prefill chunks, up to {logits.max_segments} "
          f"segments each; {de.iterations} decode steps", flush=True)
    print(f"serve: Mosaic calls in compiled text: {kernels}", flush=True)
    print(f"serve: smoke times (not metrics): first request cold "
          f"{cold:.3f} s (includes compiling), remaining "
          f"{len(prompts) - 1} requests {warm:.3f} s wall", flush=True)
    return [r.tokens for r in results]


def wallclock_phase(cfg, params, prompts, size: Size, served):
    from repro.serving import AsyncCluster, SamplingParams
    sp = SamplingParams(max_new_tokens=NEW_TOKENS)
    t0 = time.perf_counter()
    with AsyncCluster(cfg, params=params, n_prefill=1, n_decode=1,
                      chunk_size=size.chunk, max_seq=size.max_seq,
                      n_pages=size.n_pages,
                      max_batch=N_REQUESTS) as cluster:
        handles = [cluster.submit(p, sampling=sp)
                   for p in prompts[:N_WALLCLOCK]]
        drained = cluster.drain(timeout=600)
        results = [h.result(wait=False) for h in handles]
    wall = time.perf_counter() - t0
    check(drained, "wallclock: requests still in flight after 600 s")
    for r in results:
        check(r.phase.value == "finished", f"wallclock: {r.rid} is {r.phase}")
        check(len(r.tokens) == NEW_TOKENS,
              f"wallclock: {r.rid} has {len(r.tokens)} tokens")
    same = sum(r.tokens == t for r, t in zip(results, served))
    print(f"wallclock: {len(results)} requests finished in {wall:.3f} s "
          f"wall (smoke time, not a metric); {same} token-identical to "
          f"the serve phase", flush=True)


def check_phase(cfg, params, prompts, size: Size):
    import jax
    import jax.numpy as jnp

    from repro.runtime.baseline_vllm import CoupledEngine
    from repro.runtime.request import Request
    from repro.serving import SamplingParams

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    params32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                      params)
    with jax.default_matmul_precision("highest"):
        cluster = engine_cluster(cfg32, params32, size, size.check_pages)
        pe = cluster.instances[0].pe
        ours = FirstLogits(prompts)
        pe._prefill_paged = StepTap(pe._prefill_paged, ours.paged)
        sp = SamplingParams(max_new_tokens=NEW_TOKENS)
        handles = [cluster.submit(p, sampling=sp) for p in prompts]
        cluster.run()
        got = [h.result().tokens for h in handles]
        del cluster, pe
        gc.collect()

        base = CoupledEngine(cfg32, params32, max_slots=N_REQUESTS,
                             max_seq=size.max_seq, n_pages=size.n_pages)
        theirs = FirstLogits(prompts)
        base._prefill = StepTap(base._prefill, theirs.dense)
        for i, p in enumerate(prompts):
            base.submit(Request(rid=f"r{i}", prompt_len=len(p),
                                decode_len=NEW_TOKENS - 1, prompt_tokens=p))
        expect, t = {}, 0.0
        for _ in range(100 * N_REQUESTS * NEW_TOKENS):
            for fin in base.step(t):
                expect[fin.req.rid] = fin.tokens
            t += 0.01
            if base.done():
                break
    check(base.done(), "check: the coupled engine did not finish")
    diff = max(float(np.abs(ours.rows[i] - theirs.rows[i]).max())
               for i in range(len(prompts)))
    want = [expect.get(f"r{i}") for i in range(len(prompts))]
    same = sum(g == w for g, w in zip(got, want))
    print(f"check: float32 greedy tokens identical to CoupledEngine for "
          f"{same}/{len(prompts)} requests; largest first-token logit "
          f"difference {diff:.3e}", flush=True)
    for i, (g, w) in enumerate(zip(got, want)):
        check(g == w, f"check: request {i} differs from CoupledEngine: "
                      f"{g} != {w}")


def run(size: Size, seed: int, on_tpu: bool) -> None:
    """All three phases; raises ``SmokeFailure`` at the first failed
    check."""
    import jax

    from repro.configs import get_config, get_smoke_config
    from repro.models import model as M

    cfg = (get_smoke_config if size.smoke_config else get_config)(ARCH)
    t0 = time.perf_counter()
    params = M.init_params(jax.random.PRNGKey(seed), cfg)
    jax.block_until_ready(params)
    print(f"model: {cfg.name} layers={cfg.n_layers} d={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} dtype={cfg.dtype}; "
          f"{sum(x.size for x in jax.tree_util.tree_leaves(params))} "
          f"params made in {time.perf_counter() - t0:.3f} s", flush=True)
    prompts = make_prompts(size, cfg.vocab_size, seed)

    served = serve_phase(cfg, params, prompts, size, on_tpu)
    gc.collect()
    wallclock_phase(cfg, params, prompts, size, served)
    gc.collect()
    check_phase(cfg, params, prompts, size)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    ap.add_argument("--rehearse", action="store_true",
                    help="run the same phases on the smoke config on the "
                         "CPU (kernels interpreted); never touches a chip")
    args = ap.parse_args(argv)

    import jax
    if args.rehearse:
        jax.config.update("jax_platforms", "cpu")
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    if not (on_tpu or args.rehearse):
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"nothing was run", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache

    size = REHEARSAL if args.rehearse else CHIP
    print(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())};"
          f" compile cache {enable_compile_cache()}", flush=True)
    run(size, args.seed, on_tpu)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
