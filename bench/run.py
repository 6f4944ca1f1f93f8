#!/usr/bin/env python3
"""Chip benchmark of the served disaggregated path.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --rehearse

One run serves one cell of ``BENCHMARK.json``: a model configuration
(``bench/configs/<config>.json``) under an open-loop traffic mix
(``bench/traffic/<traffic>.json``), through ``AsyncCluster`` with one
prefill and one decode instance on one chip.  It

  1. makes the weights on the device from ``--seed`` in one jitted call;
  2. warms every prefill shape the engines can reach (segments x padded
     length), the decode step and the KV hand-off for the page count of
     every prompt it will send, with JAX's compile cache at
     ``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``;
  3. offers the mix's requests at their due times, runs the mix's
     lead-in, and measures the requests due in ``--seconds`` seconds;
  4. checks a sample of the finished requests, drawn from the seed,
     against the plain float32 reference (``bench/references``);
  5. prints one JSON line: end-to-end metrics with ``--trace 0``, the
     per-layer metrics (``bench/metrics/<name>.py``) with ``--trace 1``.

It exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for.  ``--rehearse`` runs the same path at the
configuration's rehearsal size on the CPU with interpreted kernels and
prints no device metric.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse          # noqa: E402
import gc                # noqa: E402
import importlib.util    # noqa: E402
import json              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import threading         # noqa: E402

import numpy as np       # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import flops as FL       # noqa: E402
import traffic as TR     # noqa: E402
import weights as W      # noqa: E402

POLL_S = 0.01            # client poll of the streaming handles
GRACE_S = 30.0           # wait past the window for first tokens
TRACE_S = 4.0            # profiler window inside the measured window
SAMPLE_TOKENS = 1536     # served tokens the reference checks, at most
SAMPLE_MIN = 256         # served tokens a sample must hold
SAMPLE_MAX = 8           # requests the reference checks, at most
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    pass


# -- the cell -------------------------------------------------------------
def load_cell(name: str, rehearse: bool):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {c["name"]: c for c in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; cells: "
                         f"{sorted(cells)}")
    cell = cells[name]
    with open(os.path.join(BENCH, "configs", f"{cell['config']}.json")) as f:
        conf = json.load(f)
    mix = TR.load_mix(BENCH, cell["traffic"])
    model, serving = dict(conf["model"]), dict(conf["serving"])
    if rehearse:
        model.update(conf["rehearsal"]["model"])
        serving.update(conf["rehearsal"]["serving"])
        mix = dict(mix, lead_in_s=conf["rehearsal"]["lead_in_s"])
    metrics = [m for m in spec["per_layer"]
               if name in m.get("workloads", [name])]
    return spec, cell, conf, model, serving, mix, metrics


def model_config(model: dict, serving: dict):
    """The program's ``ModelConfig`` exactly as the file states it."""
    from repro.models.config import ATTN, ModelConfig
    d = W.model_dims(model)
    return ModelConfig(
        name=model["name"], n_layers=d["layers"], d_model=d["d"],
        n_heads=d["h"], n_kv_heads=d["kvh"], d_ff=d["ff"],
        vocab_size=d["vocab"], head_dim=d["hd"], pattern=(ATTN,),
        qkv_bias=d["qkv_bias"], rope_theta=d["rope_theta"],
        mlp_act="swiglu", tie_embeddings=d["tied"], norm_eps=d["eps"],
        dtype=serving["dtype"], source=model["source"])


def seed32(seed: int) -> int:
    """A 31-bit key for JAX from a seed of any size."""
    return int(np.random.default_rng(seed).integers(0, 2 ** 31 - 1))


# -- compile accounting ---------------------------------------------------
class Compiles:
    """Every program JAX lowers (an in-memory cache miss), with the host
    time it happened, and the hits in the persistent cache."""

    def __init__(self):
        self.lowered = []      # (monotonic end, seconds)
        self.cache_hits = 0
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.lowered.append((time.monotonic(), secs))

    def _on_event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def lowered_in(self, t0, t1):
        return [(t, s) for t, s in self.lowered if t0 <= t <= t1]


# -- warm-up ----------------------------------------------------------------
def _pow2(n: int) -> int:
    return 1 << max(0, n - 1).bit_length()


def warm_up(cluster, serving: dict, max_prompt: int, vocab: int) -> dict:
    """Run every step shape the cell can reach through the engines' own
    ``submit``/``step``/``receive``/``admit`` before the cluster starts:
    each (segments, padded length) prefill program, the hand-off of a
    single request and the decode step."""
    from repro.runtime.request import Request, SamplingParams
    pre, dec = cluster.instances[0], cluster.instances[1]
    pe, de = pre.pe, dec.de
    chunk = serving["chunk_size"]
    rng = np.random.default_rng(0)
    top_len = _pow2(min(chunk, max_prompt))
    n_seg = [1 << i for i in range(serving["sched_batch"].bit_length())
             if 1 << i <= serving["sched_batch"]]
    lens = [1 << i for i in range(top_len.bit_length())]
    sp = SamplingParams(max_new_tokens=2)
    shapes, k = 0, 0
    for ns in n_seg:
        for sq in lens:
            big = min(sq, chunk - (ns - 1))
            if _pow2(big) != sq or big > max_prompt:
                continue
            batch = []
            for j, n in enumerate([1] * (ns - 1) + [big]):
                r = Request(rid=f"warm{k}", prompt_len=n, decode_len=2,
                            prompt_tokens=rng.integers(1, vocab, n,
                                                       dtype=np.int32))
                r.sampling = sp
                k += 1
                batch.append(r)
                pe.submit(r)
            while not pe.idle():
                for pk in pe.step(0.0):
                    de.receive(pk, now=0.0)
                    de.admit(0.0)      # one request per install
            while de.slots:
                de.step(0.0)
            shapes += 1
    return {"prefill_shapes": shapes}


def warm_handoff(cluster, schedule, page_size: int) -> int:
    """``PagePool.gather`` and ``install`` compile per page count: run
    them once for the page count of every prompt in the schedule,
    between the scratch pages of the two pools, under each instance's
    lock."""
    pre, dec = cluster.instances[0], cluster.instances[1]
    counts = sorted({-(-len(r.prompt) // page_size) for r in schedule})
    for n in counts:
        with pre.lock:
            k, v = pre.pe.pool.gather([pre.pe.alloc.n_pages] * n)
        with dec.lock:
            de = dec.de
            de.pool = de.pool.install([de.alloc.n_pages] * n, k, v)
    with dec.lock:
        dec.de.pool.k.block_until_ready()
    return len(counts)


# -- the client -------------------------------------------------------------
class Client:
    """Open loop: submits each request at its due time, whatever the
    server is doing, and stamps every streamed token at the handle."""

    def __init__(self, cluster, schedule, t_start: float):
        from repro.runtime.request import Phase
        self._queued = (Phase.WAITING, Phase.PREFILL)
        self.cluster = cluster
        self.schedule = schedule
        self.t_start = t_start
        self.sent = [None] * len(schedule)
        self.handles = [None] * len(schedule)
        self.rids = [None] * len(schedule)
        self.times = [[] for _ in schedule]
        self.final = [None] * len(schedule)
        self.done_at = [None] * len(schedule)
        self.submit_errors = []
        self.stop_submit = threading.Event()
        self.stop_collect = threading.Event()
        self._active = []
        self._lock = threading.Lock()
        self.error = None
        self._threads = [threading.Thread(target=self._submit, daemon=True,
                                          name="bench-submit"),
                         threading.Thread(target=self._collect, daemon=True,
                                          name="bench-collect")]

    def start(self):
        for t in self._threads:
            t.start()

    def _submit(self):
        from repro.runtime.request import SamplingParams
        try:
            for i, r in enumerate(self.schedule):
                due = self.t_start + r.due_s
                wait = due - time.monotonic()
                if wait > 0 and self.stop_submit.wait(wait):
                    return
                if self.stop_submit.is_set():
                    return
                try:
                    h = self.cluster.submit(
                        r.prompt, decode_len=r.max_new_tokens,
                        sampling=SamplingParams(
                            max_new_tokens=r.max_new_tokens))
                except RuntimeError as e:   # the server refused it
                    self.submit_errors.append((i, repr(e)))
                    continue
                self.sent[i] = time.monotonic()
                self.handles[i] = h
                self.rids[i] = h.rid
                with self._lock:
                    self._active.append(i)
        except Exception as e:            # reported by join()
            self.error = e

    def _poll(self):
        """Stamp new tokens.  The runtime marks a request finished inside
        the decode step and streams that step's tokens just after, so a
        finished request is settled once its last token is in, or a
        quarter second after it was first seen finished."""
        with self._lock:
            active = list(self._active)
        done = []
        now = time.monotonic()
        for i in active:
            h = self.handles[i]
            if h.request.phase in self._queued:  # no token can be there
                continue
            finished = h.done()
            n = len(h.tokens_so_far())
            seen = len(self.times[i])
            if n > seen:
                self.times[i].extend([time.monotonic()] * (n - seen))
            if finished:
                self.done_at[i] = self.done_at[i] or now
                r = h.result(wait=False)
                if (r.phase.value != "finished"
                        or len(r.tokens) >= self.schedule[i].max_new_tokens
                        or now - self.done_at[i] > 0.25):
                    self.final[i] = r
                    done.append(i)
        if done:
            with self._lock:
                gone = set(done)
                self._active = [i for i in self._active if i not in gone]

    def _collect(self):
        try:
            while not self.stop_collect.is_set():
                self._poll()
                time.sleep(POLL_S)
            self._poll()
        except Exception as e:
            self.error = e

    def halt_submit(self):
        """Stop submitting; returns once no submit is in progress."""
        self.stop_submit.set()
        self._threads[0].join(timeout=60)

    def join(self):
        self.stop_submit.set()
        self.stop_collect.set()
        for t in self._threads:
            t.join(timeout=60)
        self.handles = None          # the handles hold the cluster
        if self.error is not None:
            raise self.error


# -- correctness ------------------------------------------------------------
def load_reference(conf: dict):
    path = os.path.join(BENCH, "references", f"{conf['reference']}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_ref_{conf['reference']}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def draw_sample(schedule, final, seed: int):
    """Finished requests to check: the one with the most served tokens
    and the one with the longest prompt (several prefill chunks), then
    others drawn from the seed while the sample stays within
    SAMPLE_TOKENS served tokens and SAMPLE_MAX requests."""
    done = [i for i, r in enumerate(final)
            if r is not None and r.phase.value == "finished"]
    if not done:
        return []
    pick = [max(done, key=lambda i: len(final[i].tokens))]
    longest_prompt = max(done, key=lambda i: len(schedule[i].prompt))
    if longest_prompt not in pick:
        pick.append(longest_prompt)
    total = sum(len(final[i].tokens) for i in pick)
    rng = np.random.default_rng([seed, 7])
    for i in rng.permutation(done):
        if len(pick) >= SAMPLE_MAX:
            break
        n = len(final[i].tokens)
        if i in pick or total + n > SAMPLE_TOKENS:
            continue
        pick.append(int(i))
        total += n
    return pick


def logit_gaps(ref, params, dims, prompts, served, control: bool = False):
    """Widest gap by which a served token's reference logit lies below
    the reference's best, per request; with ``control`` also the same
    gap for the token that the float8 control puts first."""
    seqs = [np.concatenate([p, np.asarray(s[:-1], np.int32)])
            for p, s in zip(prompts, served)]
    starts = [len(p) - 1 for p in prompts]
    targets = [np.asarray(s, np.int32)[None] for s in served]
    if control:
        ctl = ref.head_stats(params, dims, seqs, starts, targets,
                             mode="fp8")
        targets = [np.stack([t[0], c[1]]) for t, c in zip(targets, ctl)]
    out = ref.head_stats(params, dims, seqs, starts, targets, mode="f32")
    prog = max(float((mx - at[0]).max()) for mx, _, at in out)
    if not control:
        return prog, None
    return prog, max(float((mx - at[1]).max()) for mx, _, at in out)


def load_limit(cell: str):
    path = os.path.join(BENCH, "limits", f"{cell}.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


# -- per-layer metric readers ----------------------------------------------
def read_metrics(specs, facts: dict) -> dict:
    out = {}
    if os.path.join(BENCH, "metrics") not in sys.path:
        sys.path.insert(0, os.path.join(BENCH, "metrics"))
    for m in specs:
        path = os.path.join(BENCH, "metrics", f"{m['name']}.py")
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{m['name'].replace('.', '_')}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        v = mod.read(facts)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def percentile(xs, q: float):
    return float(np.percentile(np.asarray(xs, np.float64), q)) \
        if len(xs) else None


# -- one run ----------------------------------------------------------------
def build(cell_name: str, seed: int, rehearse: bool, trace: bool,
          compiles: "Compiles" = None, log=print) -> dict:
    """Weights, cluster and warm-up of one cell; nothing served yet."""
    import jax

    if rehearse:
        jax.config.update("jax_platforms", "cpu")
    devs = jax.devices()
    (spec, cell, conf, model, serving, mix,
     metric_specs) = load_cell(cell_name, rehearse)
    if not rehearse and (devs[0].platform != "tpu"
                         or len(devs) < cell["chips"]):
        raise NoChip(f"JAX found {len(devs)} {devs[0].platform} device(s); "
                     f"the cell {cell_name} needs {cell['chips']} TPU chip(s)")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(ROOT, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    compiles = compiles or Compiles()

    from repro.core.predictor import OraclePredictor
    from repro.obs.tracer import Tracer
    from repro.serving import AsyncCluster

    dims = W.model_dims(dict(model, torch_dtype=serving["dtype"]))
    cfg = model_config(model, serving)
    t0 = time.monotonic()
    params = W.make_params(seed32(seed), dims)
    jax.block_until_ready(params)
    t_params = time.monotonic() - t0
    tracer = Tracer("wall") if trace else None
    cluster = AsyncCluster(
        cfg, params=params, n_prefill=1, n_decode=1,
        prefill_policy="sjf", sched_batch=serving["sched_batch"],
        chunk_size=serving["chunk_size"],
        decode_policy="reserve-dynamic", dispatch_policy="power2",
        predictor=OraclePredictor(accuracy=0.749, seed=seed32(seed)),
        n_pages=serving["n_pages"], page_size=serving["page_size"],
        max_batch=serving["decode_slots"], max_seq=serving["max_seq"],
        transfer_delay_scale=0.0, tracer=tracer)
    mem = (devs[0].memory_stats() or {}).get("bytes_in_use", 0)
    log(f"set-up: weights and pools hold {mem} bytes on the device")
    t0 = time.monotonic()
    warm = warm_up(cluster, serving, serving["max_prompt"], dims["vocab"])
    t_warm = time.monotonic() - t0
    log(f"set-up: params {t_params:.3f} s, warm-up {t_warm:.3f} s "
        f"({warm}); programs lowered {len(compiles.lowered)}, of them "
        f"found in the compile cache {compiles.cache_hits}")
    return {"jax": jax, "devs": devs, "cell": cell, "conf": conf,
            "serving": serving, "mix": mix,
            "metric_specs": metric_specs, "compiles": compiles,
            "dims": dims, "params": params, "cluster": cluster,
            "tracer": tracer, "t_params": t_params, "t_warm": t_warm,
            "rehearse": rehearse}


def drive(env: dict, seed: int, seconds: float, trace: bool = False
          ) -> dict:
    """Offer the mix's traffic to the running cluster and measure the
    requests due in the window.  Stops submitting once every request due
    in the window has its first token (or the grace ran out), lets what
    is on the chip finish for a few seconds, and cancels the rest."""
    jax, cluster, mix = env["jax"], env["cluster"], env["mix"]
    schedule = TR.generate(mix, seed, seconds, GRACE_S, env["dims"]["vocab"],
                           env["serving"]["max_seq"],
                           env["serving"]["max_prompt"])
    lead = mix["lead_in_s"]
    t0 = time.monotonic()
    n_counts = warm_handoff(cluster, schedule, env["serving"]["page_size"])
    env["t_warm"] += time.monotonic() - t0
    cluster.start()
    t_start = time.monotonic()
    c_off = t_start - cluster.now()          # cluster clock -> monotonic
    w0, w1 = t_start + lead, t_start + lead + seconds
    n_spans = len(env["tracer"].events) if env["tracer"] else 0
    client = Client(cluster, schedule, t_start)
    client.start()

    trace_dir = os.path.join(ROOT, ".bench_trace", env["cell"]["name"])
    t_trace = None
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        ta = w0 + max(0.0, seconds / 2 - TRACE_S / 2)
        time.sleep(max(0.0, ta - time.monotonic()))
        jax.profiler.start_trace(trace_dir)
        with jax.profiler.TraceAnnotation("bench_clock"):
            mark = time.monotonic()
        time.sleep(TRACE_S)
        jax.profiler.stop_trace()
        t_trace = (mark, time.monotonic())
    time.sleep(max(0.0, w1 - time.monotonic()))

    # keep offering load until every request due in the window has its
    # first token, or the grace runs out
    due = np.array([t_start + r.due_s for r in schedule])
    in_win = [i for i in range(len(schedule)) if w0 <= due[i] <= w1]
    deadline = w1 + GRACE_S
    while time.monotonic() < deadline:
        if all(client.times[i] or (client.final[i] is not None)
               for i in in_win):
            break
        time.sleep(0.05)
    client.halt_submit()
    stats = env["devs"][0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    # the requests already on the chip get a few seconds to finish, for
    # the correctness sample; the rest are cancelled
    cluster.drain(timeout=5.0)
    # a cancel that races the request's last decode step can leave it
    # finished with its last tokens dropped: the requests cancelled here
    # are left out of the correctness check
    cancelled = set()
    for i, h in enumerate(client.handles):
        if h is not None and not h.done():
            cancelled.add(i)
            h.cancel()
    cluster.drain(timeout=30.0)
    client.join()
    spans = env["tracer"].events[n_spans:] if env["tracer"] else []

    res = measure(schedule, client, due, in_win, w0, w1, seconds)
    res["submit_errors"] = len(client.submit_errors)
    res["handoff_page_counts"] = n_counts
    compiles = env["compiles"]
    res.update(setup_s=w0 - T_PROCESS, memory_peak_bytes=peak,
               compiles_in_window=len(compiles.lowered_in(w0, w1)),
               lateness_p99_ms=percentile(
                   [1e3 * (client.sent[i] - due[i]) for i in in_win
                    if client.sent[i] is not None], 99))
    if trace:
        res["facts"] = trace_facts(
            trace_dir, spans, c_off, t_trace, schedule, client, compiles,
            w0, w1, env["dims"], env["devs"][0], env["rehearse"])
    res["_schedule"] = schedule
    res["_final"] = [None if i in cancelled else r
                     for i, r in enumerate(client.final)]
    return res


def check(env: dict, res: dict, seed: int, control: bool = False) -> dict:
    """Compare a sample of the finished requests with the reference.
    Call after ``close``: the reference runs once the program's state is
    freed."""
    jax = env["jax"]
    t0 = time.monotonic()
    schedule, final = res["_schedule"], res["_final"]
    ref = load_reference(env["conf"])
    pick = draw_sample(schedule, final, seed)
    prompts = [schedule[i].prompt for i in pick]
    served = [final[i].tokens for i in pick]
    wrong_len = sum(1 for i, r in enumerate(final)
                    if r is not None and r.phase.value == "finished"
                    and len(r.tokens) != schedule[i].max_new_tokens)
    gap = ctl = None
    if pick:
        with jax.default_matmul_precision("highest"):
            gap, ctl = logit_gaps(ref, env["params"], env["dims"], prompts,
                                  served, control)
    return {"sample_requests": len(pick),
            "sample_tokens": int(sum(len(s) for s in served)),
            "max_logit_gap": gap, "control_logit_gap": ctl,
            "wrong_length": wrong_len,
            "t_reference": time.monotonic() - t0}


def close(env: dict) -> None:
    """Stop the cluster and free its pools (the weights stay)."""
    env["cluster"].close()
    env["cluster"] = None
    gc.collect()


def serve(cell_name: str, seed: int, seconds: float, trace: bool,
          rehearse: bool, control: bool = False, log=print,
          compiles: "Compiles" = None):
    """One whole run: build, drive, close, check.  ``control`` also
    reads the float8 control's gap on the same sample."""
    env = build(cell_name, seed, rehearse, trace, compiles, log)
    res = drive(env, seed, seconds, trace)
    close(env)
    res.update(check(env, res, seed, control))
    res.update(t_params=env["t_params"], t_warm=env["t_warm"],
               metric_specs=env["metric_specs"], cell=env["cell"])
    dev, devs = env["devs"][0], env["devs"]
    res["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                     "count": len(devs),
                     "memory_peak_bytes": res["memory_peak_bytes"]}
    return res


def measure(schedule, client, due, in_win, w0, w1, seconds) -> dict:
    """End-to-end numbers from the client's stamps."""
    ttft, failed = [], 0
    for i in in_win:
        t = client.times[i]
        fin = client.final[i]
        if not t or (fin is not None and fin.phase.value == "failed"):
            failed += 1
            continue
        ttft.append(t[0] - due[i])
    gaps, out_tokens = [], 0
    for ts in client.times:
        for j, t in enumerate(ts):
            if w0 <= t <= w1:
                out_tokens += 1
                if j:
                    gaps.append(t - ts[j - 1])
    def in_flight(t):
        sent = sum(1 for x in client.sent if x is not None and x <= t)
        fin = sum(1 for i, r in enumerate(client.final)
                  if r is not None and r.phase.value == "finished"
                  and client.times[i] and client.times[i][-1] <= t)
        return sent - fin

    finished_in_window = sum(
        1 for i, r in enumerate(client.final)
        if r is not None and r.phase.value == "finished"
        and client.times[i] and w0 <= client.times[i][-1] <= w1)
    return {"attempted": len(in_win), "failed": failed,
            "in_flight_start": in_flight(w0), "in_flight_end": in_flight(w1),
            "finished_per_s": finished_in_window / seconds,
            "ttft_p50_s": percentile(ttft, 50),
            "ttft_p90_s": percentile(ttft, 90),
            "tbt_p99_ms": (percentile(gaps, 99) or 0.0) * 1e3
            if gaps else None,
            "tbt_p50_ms": (percentile(gaps, 50) or 0.0) * 1e3
            if gaps else None,
            "output_tok_per_s": out_tokens / seconds,
            "n_ttft": len(ttft), "n_gaps": len(gaps)}


def trace_facts(trace_dir, spans, c_off, t_trace, schedule, client,
                compiles, w0, w1, dims, dev, rehearse) -> dict:
    """Everything the per-layer readers take: host spans in the window,
    compiles, and the device trace with the work done inside it.  The
    trace directory is removed once read."""
    try:
        return _trace_facts(trace_dir, spans, c_off, t_trace, schedule,
                            client, compiles, w0, w1, dims, dev, rehearse)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def _trace_facts(trace_dir, spans, c_off, t_trace, schedule, client,
                 compiles, w0, w1, dims, dev, rehearse) -> dict:
    import xplane as XP
    win_spans = [dict(s, ts=s["ts"] + c_off) for s in spans
                 if s.get("type") == "span"
                 and w0 <= s["ts"] + c_off + s.get("dur", 0) <= w1]
    facts = {"window": (w0, w1), "spans": win_spans,
             "compiles": compiles.lowered_in(w0, w1),
             "dims": dims, "device_kind": dev.device_kind,
             "platform": dev.platform, "trace": None}
    # decode tokens streamed in the window: (time, context)
    rid_of = {}
    toks = []
    for i, ts in enumerate(client.times):
        if client.rids[i] is None:
            continue
        rid_of[client.rids[i]] = i
        plen = len(schedule[i].prompt)
        for j, t in enumerate(ts):
            if j and w0 <= t <= w1:
                toks.append((t, plen + j))
    facts["decode_tokens"] = toks
    path = None
    try:
        path = XP.find_xplane(trace_dir)
    except FileNotFoundError:
        pass
    if path is None:
        return facts
    devices = XP.load(path)
    marks = XP.host_events(path, ["bench_clock"])
    if not devices or not marks:
        return facts
    # profiler ns -> monotonic seconds, through the clock annotation
    off = t_trace[0] - marks[0][1] * 1e-9
    d = devices[0]
    ta = d.window_ns[0] * 1e-9 + off
    tb = d.window_ns[1] * 1e-9 + off
    pre = [s for s in spans if s.get("type") == "span"
           and s["name"] == "prefill"]
    pw = {"flops": 0.0, "attn_flops": 0.0, "attn_bytes": 0.0}
    for s in pre:
        i = rid_of.get(s.get("rid"))
        if i is None:
            continue
        s0 = s["ts"] + c_off
        s1 = s0 + max(s["dur"], 1e-9)
        f = max(0.0, min(s1, tb) - max(s0, ta)) / (s1 - s0)
        if f <= 0:
            continue
        plen = len(schedule[i].prompt)
        pw["flops"] += f * FL.prefill_flops(dims, plen)
        pw["attn_flops"] += f * FL.prefill_attn_flops(dims, plen)
        pw["attn_bytes"] += f * FL.prefill_attn_bytes(dims, plen)
    dw = {"flops": 0.0, "attn_flops": 0.0, "attn_bytes": 0.0, "tokens": 0}
    for t, ctx in toks:
        if ta <= t <= tb:
            dw["flops"] += FL.decode_flops(dims, ctx)
            dw["attn_flops"] += FL.attn_flops(dims, ctx)
            dw["attn_bytes"] += FL.decode_attn_bytes(dims, ctx)
            dw["tokens"] += 1
    host = [s for s in win_spans if s["name"] in ("prefill_chunk",
                                                  "decode_step")]
    facts["trace"] = {
        "devices": devices, "span_s": (ta, tb), "prefill_work": pw,
        "decode_work": dw, "host_steps": host,
        "peaks": None if rehearse else FL.peaks(BENCH, dev.device_kind)}
    return facts


def breakdown(facts: dict) -> dict:
    """The device ops that took most time, and the longest idle gaps,
    each named by the host step in progress then."""
    tr = facts.get("trace")
    if not tr:
        return {}
    d = tr["devices"][0]
    ops = sorted(d.ops.items(), key=lambda kv: -kv[1])[:10]
    ta = tr["span_s"][0] - d.window_ns[0] * 1e-9
    comp = facts["compiles"]
    gaps = []
    for start, length in d.gaps[:10]:
        s = start * 1e-9 + ta
        e = s + length * 1e-9
        names = sorted({h["name"] for h in tr["host_steps"]
                        if h["ts"] < e and h["ts"] + h["dur"] > s})
        if any(s <= t <= e + 0.5 for t, _ in comp):
            names.append("compile")
        gaps.append(["+".join(names) or "no_step", length * 1e-9])
    return {"device_ops": [[n, v * 1e-9] for n, v in ops],
            "idle_gaps": gaps}


# -- entry point ------------------------------------------------------------
def result_line(res: dict, trace: bool, rehearse: bool, limit) -> dict:
    cell = res["cell"]
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    gap = res["max_logit_gap"]
    lim = None if limit is None else limit["max_logit_gap"]
    checks = {"max_logit_gap": [gap, lim],
              "wrong_length": [res["wrong_length"], 0],
              "sample_tokens": [res["sample_tokens"], SAMPLE_MIN]}
    correct = (gap is not None and lim is not None and gap <= lim
               and res["wrong_length"] == 0
               and res["sample_tokens"] >= SAMPLE_MIN)
    metrics = {}
    if rehearse:
        pass
    elif not trace:
        for m in spec["end_to_end"]:
            if cell["name"] not in m.get("workloads", [cell["name"]]):
                continue
            v = res.get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        metrics = read_metrics(res["metric_specs"], res["facts"])
    device = dict(res["device"])
    line = {"correct": bool(correct), "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if trace and not rehearse and res["facts"].get("trace"):
        d = res["facts"]["trace"]["devices"][0]
        device["busy_s"] = d.busy_ns * 1e-9
        device["window_s"] = (d.window_ns[1] - d.window_ns[0]) * 1e-9
        line["breakdown"] = breakdown(res["facts"])
    line["compared"] = {k: {"value": v, "limit": lim_}
                        for k, (v, lim_) in checks.items()}
    return line, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="the same path at rehearsal size on the CPU")
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    try:
        res = serve(args.workload, args.seed, args.seconds,
                    bool(args.trace), args.rehearse, log=log)
    except NoChip as e:
        log(f"bench: {e}; nothing was run")
        return 2
    limit = load_limit(args.workload)
    line, checks = result_line(res, bool(args.trace), args.rehearse, limit)
    log(f"run: attempted {res['attempted']} failed {res['failed']} "
        f"in flight {res['in_flight_start']} -> {res['in_flight_end']} "
        f"finished/s {res['finished_per_s']:.3f} tbt_p50_ms "
        f"{res['tbt_p50_ms']} ttft n={res['n_ttft']} gaps n={res['n_gaps']} "
        f"lateness_p99_ms {res['lateness_p99_ms']} compiles_in_window "
        f"{res['compiles_in_window']}"
        f" memory_peak_bytes {res['memory_peak_bytes']} reference "
        f"{res['t_reference']:.3f} s; submit errors "
        f"{res['submit_errors']}")
    if args.rehearse:
        log("rehearsal (cpu, not device metrics): " + json.dumps(
            {k: res[k] for k in ("ttft_p50_s", "ttft_p90_s", "tbt_p99_ms",
                                 "output_tok_per_s", "setup_s")}))
    for k, (v, lim) in checks.items():
        log(f"compared {k}: {v} limit {lim}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
