"""Observability-plane tests (repro.obs — docs/observability.md).

Contracts under test:

* **round-trip**: hand-emitted span/instant/counter records survive the
  JSONL writer/reader and render to a structurally valid
  Chrome/Perfetto ``trace_event`` document (instances as processes,
  one ``requests`` process with a thread per rid);
* **off-by-default byte-identity**: a fixed-seed sim run with the full
  obs plane attached (tracer + enabled registry) produces metrics
  byte-identical to the pinned golden run with obs off — observation
  must never perturb the observed system;
* **chain liveness under chaos**: with crashes and KV drops injected,
  every traced rid reaches exactly one terminal instant — on the sim
  event loop AND on the threaded ``AsyncCluster`` (the lock-free
  tracer's concurrency hammer);
* **single source of truth**: the snapshot ``ClusterStallError``
  carries is THE registry's ``instances`` probe, not a parallel copy;
* **SLO attainment**: ``summarize(slo=...)`` adds the goodput block,
  ``slo=None`` adds nothing; the all-failed summary carries its
  guarded diagnostics keys only when they are nonzero.
"""
import copy
import json
import os

import pytest

from repro.configs import get_config
from repro.obs import (SCHEMA_VERSION, EventLoopProfiler, MetricsRegistry,
                       SLOSpec, Tracer, meets_slo, observe_request,
                       read_jsonl, validate_chains, validate_jsonl_records,
                       validate_perfetto)
from repro.runtime.costmodel import CostModel, HardwareSpec
from repro.runtime.request import Phase, Request, summarize
from repro.runtime.workload import generate
from repro.serving import (Cluster, ClusterStallError, FaultEvent,
                           FaultSpec, SamplingParams)
from repro.serving.faults import CRASH

GOLDEN = os.path.join(os.path.dirname(__file__),
                      "golden_sim_metrics.json")


@pytest.fixture(scope="module")
def opt13b():
    cfg = get_config("opt_13b")
    return cfg, CostModel(cfg, HardwareSpec.v100_tp2(),
                          n_params=13_000_000_000)


# -- tracer round-trip -------------------------------------------------------
def _tiny_trace():
    tr = Tracer(clock="virtual")
    tr.span("queued", "cluster", 0.0, 0.5, rid="r0")
    tr.span("prefill", "i0", 0.5, 1.0, rid="r0", chunks=2)
    tr.span("transfer", "i1", 1.5, 0.1, rid="r0")
    tr.span("decode", "i1", 1.6, 2.0, rid="r0")
    tr.instant("finished", "i1", 3.6, rid="r0", tokens=16)
    tr.span("prefill_chunk", "i0", 0.5, 0.4, rid="r0")  # exec-step span
    tr.instant("crash", "i1", 2.0, reason="injected")
    tr.counter("load", "i0", 1.0, queued=3, free_pages=100)
    return tr


def test_tracer_jsonl_roundtrip(tmp_path):
    tr = _tiny_trace()
    path = str(tmp_path / "trace.jsonl")
    tr.write_jsonl(path)
    records = read_jsonl(path)
    assert validate_jsonl_records(records) == []
    assert validate_chains(records) == []
    # meta header + every event, bit-for-bit through json
    assert records[0] == {"type": "meta", "schema": SCHEMA_VERSION,
                          "clock": "virtual"}
    assert records[1:] == tr.events
    # by_rid groups exactly the rid-carrying records
    assert [ev["name"] for ev in tr.by_rid()["r0"]] == [
        "queued", "prefill", "transfer", "decode", "finished",
        "prefill_chunk"]


def test_tracer_perfetto_structure(tmp_path):
    tr = _tiny_trace()
    doc = tr.to_perfetto()
    assert validate_perfetto(doc) == []
    evs = doc["traceEvents"]
    # request-phase records live in the "requests" process (pid 1) on
    # the rid's own thread; the exec-step span stays on its instance
    names = {e["name"]: e for e in evs if e["ph"] != "M"}
    req_tid = names["queued"]["tid"]
    for name in ("queued", "prefill", "transfer", "decode", "finished"):
        assert names[name]["pid"] == 1 and names[name]["tid"] == req_tid
    assert names["prefill_chunk"]["pid"] != 1
    # the owning instance survives the move onto the request row
    assert names["prefill"]["args"]["instance"] == "i0"
    # µs conversion + counter rendering
    assert names["decode"]["ts"] == pytest.approx(1.6e6)
    assert names["decode"]["dur"] == pytest.approx(2.0e6)
    assert names["load"]["ph"] == "C"
    assert names["load"]["args"] == {"queued": 3, "free_pages": 100}
    # process metadata names every instance track
    meta_names = {e["args"]["name"] for e in evs
                  if e["ph"] == "M" and e["name"] == "process_name"}
    assert {"requests", "i0", "i1"} <= meta_names
    path = str(tmp_path / "trace.json")
    tr.write_perfetto(path)
    assert validate_perfetto(json.load(open(path))) == []


def test_validators_reject_malformed_records():
    assert validate_jsonl_records([]) == ["empty trace"]
    assert validate_jsonl_records([{"type": "span"}]) \
        == ["first record is not the meta header"]
    head = {"type": "meta", "schema": SCHEMA_VERSION, "clock": "virtual"}
    bad = [
        head,
        {"type": "span", "name": "x", "track": "i0", "ts": -1.0,
         "dur": -0.5},
        {"type": "wat", "name": "x", "track": "i0", "ts": 0.0},
        {"type": "counter", "name": "c", "track": "i0", "ts": 0.0,
         "values": {"a": "NaN-ish"}},
    ]
    errs = validate_jsonl_records(bad)
    assert len(errs) == 4  # bad ts, bad dur, bad type, bad counter
    # chains: an orphan and a double-terminal
    orphan = [{"type": "span", "name": "prefill", "track": "i0",
               "ts": 0.0, "dur": 1.0, "rid": "a"}]
    assert validate_chains(orphan) == [
        "a: span chain never reaches a terminal event (orphan)"]
    double = orphan + [
        {"type": "instant", "name": "finished", "track": "i0",
         "ts": 1.0, "rid": "a"},
        {"type": "instant", "name": "cancelled", "track": "i0",
         "ts": 2.0, "rid": "a"}]
    assert validate_chains(double) == [
        "a: 2 terminal events (must be exactly 1)"]


# -- obs attached never perturbs the run -------------------------------------
def test_obs_on_keeps_golden_metrics_byte_identical(opt13b):
    """The mixed64 golden pin (test_serving_cluster) with the FULL obs
    plane attached: tracing + live metrics must observe, not perturb."""
    cfg, cost = opt13b
    want = json.load(open(GOLDEN))["mixed64"]
    reqs = generate("Mixed", 64, seed=1)
    tracer, metrics = Tracer(), MetricsRegistry()
    r = Cluster(cfg, runtime="sim", cost=cost, n_prefill=1, n_decode=1,
                tracer=tracer, metrics=metrics).serve(copy.deepcopy(reqs))
    for k, v in want["metrics"].items():
        assert r.metrics[k] == v, k
    # and the trace itself is complete: 64 rids, 64 clean chains
    assert validate_chains(tracer.events) == []
    assert len(tracer.by_rid()) == 64
    snap = metrics.snapshot()
    assert snap["counters"]["requests_finished"] == 64
    assert snap["histograms"]["ttft_s"]["count"] == 64
    assert snap["histograms"]["jct_s"]["avg"] == \
        pytest.approx(r.metrics["avg_jct"])


def test_sim_chaos_chains_and_counters(opt13b):
    """Crash + KV drops: every rid still reaches exactly one terminal,
    and the counters agree with the run's own accounting."""
    cfg, cost = opt13b
    reqs = generate("Mixed", 32, seed=1)
    faults = FaultSpec(seed=0, drop_kv=0.1, events=(
        FaultEvent(t=2.0, kind=CRASH, iid="i3"),))
    tracer, metrics = Tracer(), MetricsRegistry()
    cluster = Cluster(cfg, runtime="sim", cost=cost, n_prefill=2,
                      n_decode=2, faults=faults, tracer=tracer,
                      metrics=metrics)
    r = cluster.serve(copy.deepcopy(reqs))
    assert validate_chains(tracer.events) == []
    names = {ev["name"] for ev in tracer.events}
    assert {"crash", "declared_dead", "recovery", "retransmit"} <= names
    snap = metrics.snapshot()
    c = snap["counters"]
    assert c["kv_retransmits"] == cluster.network.retransmits > 0
    assert c["recoveries"] > 0
    assert c["requests_finished"] == r.metrics["n"]
    assert c.get("requests_failed", 0) == r.metrics.get("failed", 0)
    # the pull-probes see the drained cluster
    inst = snap["probes"]["instances"]
    assert set(inst) == {"i0", "i1", "i2", "i3"}
    assert inst["i3"]["health"] == "dead"
    assert snap["probes"]["network"]["retransmits"] \
        == cluster.network.retransmits


# -- metrics primitives ------------------------------------------------------
def test_histogram_nearest_rank_exact():
    m = MetricsRegistry()
    h = m.histogram("lat")
    for v in [5.0, 1.0, 4.0, 2.0, 3.0]:       # unsorted on purpose
        h.observe(v)
    s = h.summary()
    assert s == {"count": 5, "sum": 15.0, "avg": 3.0, "min": 1.0,
                 "max": 5.0, "p50": 3.0, "p90": 5.0, "p99": 5.0}
    assert m.histogram("empty").summary() == {"count": 0}


def test_disabled_registry_is_inert_and_probes_are_lazy():
    m = MetricsRegistry(enabled=False)
    req = Request(rid="r", prompt_len=4, decode_len=2,
                  phase=Phase.FINISHED, generated=2,
                  t_first_token=1.0, t_finish=2.0)
    observe_request(m, req)
    assert m.counters == {} and m.histograms == {}
    calls = []
    m.register_probe("p", lambda: calls.append(1) or {"x": 1})
    assert calls == []                     # registered, never evaluated
    assert m.snapshot()["probes"]["p"] == {"x": 1}
    assert m.probe("p") == {"x": 1}
    assert len(calls) == 2                 # only on demand


def test_observe_request_guards_missing_timestamps():
    m = MetricsRegistry()
    # failed before first token: outcome counter + retries only
    failed = Request(rid="f", prompt_len=4, decode_len=2,
                     phase=Phase.FAILED, retries=3)
    observe_request(m, failed)
    snap = m.snapshot()
    assert snap["counters"] == {"requests_failed": 1,
                                "request_retries": 3}
    assert snap["histograms"] == {}


# -- SLO attainment ----------------------------------------------------------
def _finished(rid, ttft, tbt, n_tokens=10):
    return Request(rid=rid, prompt_len=8, decode_len=n_tokens,
                   phase=Phase.FINISHED, generated=n_tokens,
                   t_first_token=ttft,
                   t_finish=ttft + tbt * n_tokens)


def test_meets_slo_boundaries():
    slo = SLOSpec(ttft_target_s=1.0, tbt_target_s=0.1)
    assert meets_slo(_finished("a", 1.0, 0.1), slo)       # at target: ok
    assert not meets_slo(_finished("b", 1.01, 0.05), slo)  # ttft miss
    assert not meets_slo(_finished("c", 0.5, 0.11), slo)   # tbt miss
    shed = Request(rid="d", prompt_len=8, decode_len=4, phase=Phase.FAILED)
    assert not meets_slo(shed, slo)        # non-finished never attains
    with pytest.raises(AssertionError):
        SLOSpec(ttft_target_s=0.0)


def test_summarize_slo_block_only_when_asked():
    reqs = [_finished("a", 0.5, 0.05), _finished("b", 2.0, 0.05),
            Request(rid="c", prompt_len=8, decode_len=4,
                    phase=Phase.FAILED)]
    plain = summarize(reqs)
    assert not any(k.startswith("slo") or k == "goodput" for k in plain)
    slo = SLOSpec(ttft_target_s=1.0, tbt_target_s=0.1)
    m = summarize(reqs, slo=slo)
    # goodput over SUBMITTED: 1 of 3 (b misses ttft, c failed)
    assert m["slo_good"] == 1
    assert m["goodput"] == pytest.approx(1 / 3)
    assert m["slo_ttft_s"] == 1.0 and m["slo_tbt_s"] == 0.1
    # non-SLO keys byte-identical either way
    assert {k: v for k, v in m.items()
            if k not in ("slo_good", "goodput", "slo_ttft_s",
                         "slo_tbt_s")} == plain


def test_summarize_all_failed_guarded_keys():
    # no first token, no retries: bare minimum, no latency keys at all
    bare = [Request(rid="a", prompt_len=8, decode_len=4,
                    phase=Phase.FAILED)]
    assert summarize(bare) == {"n": 0, "failed": 1}
    # first tokens + retries present: the guarded diagnostics appear
    rich = [Request(rid="b", prompt_len=8, decode_len=4,
                    phase=Phase.FAILED, t_first_token=1.5, retries=2),
            Request(rid="c", prompt_len=8, decode_len=4,
                    phase=Phase.FAILED, t_first_token=2.5, retries=1)]
    m = summarize(rich)
    assert m["failed"] == 2
    assert m["failed_avg_ttft"] == pytest.approx(2.0)
    assert m["failed_retries"] == 3
    # and the SLO block still works on an all-failed run (goodput 0)
    m2 = summarize(rich, slo=SLOSpec())
    assert m2["goodput"] == 0.0 and m2["slo_good"] == 0


# -- stall snapshot == registry probe ----------------------------------------
def test_stall_snapshot_is_the_registry_probe(opt13b):
    cfg, cost = opt13b
    cluster = Cluster(cfg, runtime="sim", cost=cost, n_pages=2,
                      page_size=16, max_seq=4096)
    cluster.submit(prompt_tokens=list(range(200)),
                   sampling=SamplingParams(max_new_tokens=8))
    with pytest.raises(ClusterStallError) as ei:
        cluster.run()
    # the error's snapshot IS the probe's output — same dict shape,
    # same values, one code path (docs/observability.md)
    assert ei.value.snapshot == cluster.metrics.probe("instances")
    # the registry is always constructed, even with obs off by default
    assert cluster.metrics.enabled is False


# -- promoted profiler keeps its old import path -----------------------------
def test_profiler_promotion_compat():
    from repro.fleet.profile import EventLoopProfiler as OldName
    assert OldName is EventLoopProfiler
    p = EventLoopProfiler()
    p.record("decode_step", 0.5)
    p.record("decode_step", 1.5)
    rep = p.report(wall_s=4.0)
    assert rep["events"] == 2
    assert rep["kinds"]["decode_step"]["events"] == 2
    assert rep["kinds"]["decode_step"]["total_s"] == pytest.approx(2.0)
    assert rep["events_per_s"] == pytest.approx(0.5)


# -- threaded runtime: lock-free tracer under chaos --------------------------
def test_async_chaos_tracer_exactly_one_terminal():
    """The concurrency hammer: 3 worker threads + transfer/timer
    threads all appending to one tracer while crashes and KV drops
    force retries and re-prefills — every rid must still end with
    exactly one terminal instant and zero orphan spans."""
    import dataclasses

    import jax

    from repro.configs import get_smoke_config
    from repro.models import model as M
    from repro.runtime.request import TERMINAL_PHASES
    from repro.serving import AsyncCluster, RecoveryPolicy
    cfg = dataclasses.replace(get_smoke_config("qwen2_0_5b"),
                              dtype="float32")
    params = M.init_params(jax.random.PRNGKey(0), cfg)
    reqs = generate("Mixed", 8, seed=2, max_prompt=48, max_decode=12,
                    vocab_size=1000)
    faults = FaultSpec(seed=15, drop_kv=0.3,
                       events=(FaultEvent(t=2.0, kind="crash", iid="i2"),))
    recovery = RecoveryPolicy(transfer_timeout_s=0.05,
                              retry_backoff_s=0.01, max_retries=5)
    tracer, metrics = Tracer(clock="wall"), MetricsRegistry()
    with AsyncCluster(cfg, params=params, chunk_size=16, max_seq=128,
                      max_batch=8, n_pages=256, n_prefill=1, n_decode=2,
                      faults=faults, recovery=recovery,
                      tracer=tracer, metrics=metrics) as ac:
        hs = [ac.submit(request=r) for r in copy.deepcopy(reqs)]
        assert ac.drain(timeout=240), "chaos run wedged"
        assert all(h.result(wait=False).phase in TERMINAL_PHASES
                   for h in hs)
    assert validate_chains(tracer.events) == []
    assert set(tracer.by_rid()) == {r.rid for r in reqs}
    # the drop schedule guarantees retransmissions were traced
    names = {ev["name"] for ev in tracer.events}
    assert "retransmit" in names and "crash" in names
    snap = metrics.snapshot()
    assert snap["counters"]["kv_retransmits"] > 0
    terminal = sum(snap["counters"].get(f"requests_{p}", 0)
                   for p in ("finished", "cancelled", "failed"))
    assert terminal == len(reqs)
    # the exported document is loadable and valid
    assert validate_perfetto(tracer.to_perfetto()) == []


# -- step phases of the wall-clock runtime -----------------------------------
STEP_PHASES = {"prefill_chunk": ("prefill_lock", "prefill_build",
                                 "prefill_device", "prefill_finish"),
               "decode_step": ("decode_lock", "decode_admit",
                               "decode_build", "decode_device",
                               "decode_commit")}


def _smoke_model():
    import dataclasses

    import jax

    from repro.configs import get_smoke_config
    from repro.models import model as M
    cfg = dataclasses.replace(get_smoke_config("qwen2_0_5b"),
                              dtype="float32")
    return cfg, M.init_params(jax.random.PRNGKey(0), cfg)


def _smoke_async_run(tracer, n=6):
    """A 1P+1D ``AsyncCluster`` at smoke size; returns the requests and
    every engine of its instances."""
    from repro.serving import AsyncCluster
    cfg, params = _smoke_model()
    reqs = generate("Mixed", n, seed=4, max_prompt=48, max_decode=8,
                    vocab_size=1000)
    with AsyncCluster(cfg, params=params, chunk_size=16, max_seq=128,
                      max_batch=8, n_pages=256, tracer=tracer) as ac:
        engines = [e for i in ac.instances for e in (i.pe, i.de)]
        for r in copy.deepcopy(reqs):
            ac.submit(request=r)
        assert ac.drain(timeout=240), "run wedged"
    return reqs, engines


@pytest.fixture(scope="module")
def traced_async_run():
    tracer = Tracer(clock="wall")
    reqs, engines = _smoke_async_run(tracer)
    return reqs, engines, tracer


def test_async_step_phases_nest_in_their_steps(traced_async_run):
    """Every step span has its phase children (same track and ``step``),
    inside it, in order, and not overlapping; the step numbers of a
    worker count up from 0."""
    _, _, tracer = traced_async_run
    spans = [e for e in tracer.events if e["type"] == "span"]
    parents = {(s["track"], s["args"]["step"]): s for s in spans
               if s["name"] in STEP_PHASES}
    kids = {}
    for s in spans:
        if any(s["name"] in v for v in STEP_PHASES.values()):
            kids.setdefault((s["track"], s["args"]["step"]), []).append(s)
    assert set(kids) == set(parents)
    assert {p["name"] for p in parents.values()} == set(STEP_PHASES)
    for track in {t for t, _ in parents}:
        steps = sorted(k for t, k in parents if t == track)
        assert steps == list(range(len(steps)))
    eps = 1e-9
    for key, par in parents.items():
        ch = kids[key]
        names = [c["name"] for c in ch]
        order = STEP_PHASES[par["name"]]
        want = [n for n in order if n in names]
        assert names == want, (key, names)
        required = [n for n in order if n != "decode_admit"]
        assert set(required) <= set(names), (key, names)
        end = par["ts"] + par["dur"]
        for c in ch:
            assert par["ts"] - eps <= c["ts"]
            assert c["ts"] + c["dur"] <= end + eps
        for a, b in zip(ch, ch[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + eps
        for c in ch:
            if c["name"] == "decode_admit":
                assert c["args"]["admitted"] >= 1
                assert c["args"]["pages"] >= 1
            if c["name"] == "decode_build":
                assert 1 <= c["args"]["slots"] <= 8


def test_async_prefill_segments_tile_each_prompt(traced_async_run):
    """The ``segs`` of the ``prefill_device`` spans cover each finished
    prompt exactly once, in order, inside the padded (rows, cols)."""
    reqs, _, tracer = traced_async_run
    got = {}
    for s in tracer.events:
        if s["type"] == "span" and s["name"] == "prefill_device":
            segs = s["args"]["segs"]
            assert len(segs) <= s["args"]["rows"]
            for rid, start, n in segs:
                assert 1 <= n <= s["args"]["cols"]
                got.setdefault(rid, []).append((start, n))
    assert set(got) == {r.rid for r in reqs}
    for r in reqs:
        pos = 0
        for start, n in sorted(got[r.rid]):
            assert start == pos
            pos += n
        assert pos == r.prompt_len
    pages = sum(s["args"]["pages"] for s in tracer.events
                if s["type"] == "span" and s["name"] == "prefill_finish")
    assert pages == sum(-(-r.prompt_len // 16) for r in reqs)


def test_async_submit_and_transfer_spans(traced_async_run):
    """One ``submit`` span per request on the cluster track; each
    ``transfer`` carries its payload's pages and bytes; the records
    still pass every validator."""
    reqs, _, tracer = traced_async_run
    subs = [e for e in tracer.events if e["name"] == "submit"]
    assert sorted(e["rid"] for e in subs) == sorted(r.rid for r in reqs)
    assert all(e["track"] == "cluster" and e["dur"] >= 0 for e in subs)
    # K and V x smoke qwen2's 2 layers x 16 tokens x 2 kv heads x head
    # dim 64 x 4 B (float32), per page
    xfer = [e for e in tracer.events if e["name"] == "transfer"]
    assert len(xfer) == len(reqs)
    by_rid = {r.rid: r for r in reqs}
    for e in xfer:
        n = -(-by_rid[e["rid"]].prompt_len // 16)
        assert e["args"]["pages"] == n
        assert e["args"]["bytes"] == n * 2 * 2 * 16 * 2 * 64 * 4
    records = tracer.to_jsonl_records()
    assert validate_jsonl_records(records) == []
    assert validate_chains(records) == []
    assert validate_perfetto(tracer.to_perfetto()) == []


def test_untraced_runtimes_emit_no_phases(monkeypatch):
    """With no tracer the engines' phase hook is None and nothing calls
    into a recorder or the profiler; the synchronous ``Cluster``, traced,
    emits none of the wall-clock runtime's phase or submit spans."""
    import jax

    from repro.obs.tracer import PhaseRecorder

    def boom(*a, **k):
        raise AssertionError("phase recorder used with tracing off")
    for name in ("__init__", "open", "close", "drop", "end_step"):
        monkeypatch.setattr(PhaseRecorder, name, boom)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    reqs, engines = _smoke_async_run(None, n=3)
    assert all(e.phases is None for e in engines)
    monkeypatch.undo()

    cfg, params = _smoke_model()
    tracer = Tracer()
    Cluster(cfg, runtime="engine", params=params, chunk_size=16,
            max_seq=128, max_batch=8, n_pages=256,
            tracer=tracer).serve(copy.deepcopy(reqs))
    new = {"submit"} | {n for v in STEP_PHASES.values() for n in v}
    names = {e["name"] for e in tracer.events}
    assert names and not names & new
    assert not any("step" in e.get("args", {}) for e in tracer.events)
