"""The benchmark's step-phase readers (``bench/metrics``) on hand-made
facts, with every number worked out by hand: phase spans on the
monotonic clock, and a one-second device trace whose programs and idle
gaps are known."""
import importlib.util
import os
import sys
from types import SimpleNamespace as NS

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
METRICS = os.path.join(BENCH, "metrics")
for _p in (METRICS, BENCH):
    if _p not in sys.path:
        sys.path.append(_p)

import xplane as XP     # noqa: E402

MS = 1_000_000          # ns
# flops.py's hand-checked tiny model: 168 FLOPs of matmuls a token, 16
# of attention a key, 40 for the head
TINY = {"d": 4, "h": 2, "kvh": 1, "hd": 2, "ff": 3, "vocab": 5,
        "layers": 1}
PHASE_READERS = ("submit_wait_p90_ms", "prefill_pad_share",
                 "prefill_segment_mfu", "decode_build_ms",
                 "decode_device_wait_ms", "device_idle_host_share")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur, stats=[])


def device():
    """The trace spans [0, 1000) ms.  A prefill program runs [100, 400)
    ms and a decode program [500, 600) ms, each one op; the chip is idle
    in [0, 100), [400, 500) and [600, 1000) ms: 60 % of the window."""
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench_clock", 0, 10), ev("work", 10, 1000 * MS - 10)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev("jit__prefill_paged(7)", 100 * MS, 300 * MS),
            ev("jit__decode_paged(9)", 500 * MS, 100 * MS)]),
        NS(name="XLA Ops", events=[
            ev("fusion.1", 100 * MS, 300 * MS),
            ev("fusion.2", 500 * MS, 100 * MS)])])
    (d,) = XP.reduce_planes([host, dev])
    return d


def span(name, ts, dur, **args):
    return {"type": "span", "name": name, "track": "i0", "ts": ts,
            "dur": dur, "args": args}


def facts(spans):
    """The trace maps to [10.0, 11.0] s on the spans' clock."""
    return {"spans": spans, "dims": TINY, "trace": {
        "devices": [device()], "span_s": (10.0, 11.0),
        "peaks": {"bf16_flops_per_s": 1e4, "hbm_bytes_per_s": 1e9}}}


def phase_spans():
    return [
        # submit: 1..10 ms
        *[span("submit", 9.0 + k, k * 1e-3) for k in range(1, 11)],
        # prefill: two chunks in the trace (the second half inside it)
        # and one before it
        span("prefill_device", 10.1, 0.3, segs=[["a", 0, 3], ["b", 0, 2]],
             rows=2, cols=4),
        span("prefill_device", 10.9, 0.2, segs=[["b", 2, 2]], rows=1,
             cols=2),
        span("prefill_device", 9.0, 0.1, segs=[["c", 0, 4]], rows=1,
             cols=4),
        # decode: one device phase inside the trace, one half inside
        span("decode_device", 10.45, 0.2),
        span("decode_device", 10.95, 0.1),
        # host phases: [10.05, 10.15] [10.38, 10.42] [10.42, 10.422]
        # [10.69, 10.71] [10.7, 10.704], and one long before the trace
        span("prefill_lock", 10.05, 0.1),
        span("decode_lock", 10.38, 0.04),
        span("decode_build", 10.42, 0.002, slots=3),
        span("decode_commit", 10.69, 0.02),
        span("decode_build", 10.7, 0.004, slots=3),
        span("prefill_finish", 5.0, 1.0, pages=4),
    ]


def read(name, f):
    path = os.path.join(METRICS, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"reader_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(f)


def test_submit_wait_p90():
    # numpy's linear p90 of 1..10 ms: 9 + 0.1 * (10 - 9)
    assert read("submit_wait_p90_ms", facts(phase_spans())) \
        == pytest.approx(9.1)


def test_prefill_pad_share():
    # segment tokens 3 + 2 + 2 + 4 = 11 of 2x4 + 1x2 + 1x4 = 14 slots
    assert read("prefill_pad_share", facts(phase_spans())) \
        == pytest.approx(100 * 3 / 14)


def test_prefill_segment_mfu():
    # a[0,3) ends its prompt: 3*168 + 16*(1+2+3) + 40 = 640
    # b[0,2) does not (b starts again at 2): 2*168 + 16*(1+2) = 384
    # b[2,4) ends it: 2*168 + 16*(3+4) + 40 = 488, half in the trace
    # c lies before the trace; 1268 FLOPs over 0.3 s x 1e4 FLOP/s
    assert read("prefill_segment_mfu", facts(phase_spans())) \
        == pytest.approx(100 * (640 + 384 + 488 / 2) / 3000)


def test_decode_build_ms():
    assert read("decode_build_ms", facts(phase_spans())) \
        == pytest.approx(3.0)


def test_decode_device_wait_ms():
    # 0.2 + 0.05 s of decode_device in the trace, 1.5 steps, 0.1 s of
    # decode program: (0.25 - 0.1) / 1.5 = 100 ms a step
    assert read("decode_device_wait_ms", facts(phase_spans())) \
        == pytest.approx(100.0)


def test_device_idle_host_share_is_part_of_idle_share():
    # idle [10.0, 10.1) meets prefill_lock for 0.05 s; [10.4, 10.5) the
    # decode lock and build for 0.02 + 0.002 s; [10.6, 11.0) the commit,
    # with the build inside it, for 0.02 s: 0.092 s of 1 s
    f = facts(phase_spans())
    share = read("device_idle_host_share", f)
    assert share == pytest.approx(9.2)
    assert share <= read("device_idle_share", f) == pytest.approx(60.0)
    # idle wherever a host phase runs: the whole idle share, no more
    f["spans"].append(span("decode_build", 9.5, 2.0, slots=1))
    assert read("device_idle_host_share", f) == pytest.approx(60.0)


@pytest.mark.parametrize("name", PHASE_READERS)
def test_phase_readers_read_nothing_without_phase_spans(name):
    """A program that emits no phase spans (one older than the spans)
    gives nothing, and the reader does not raise."""
    f = facts([span("decode_step", 10.2, 0.05),
               span("prefill_chunk", 10.3, 0.08)])
    assert read(name, f) is None
    f["trace"] = None
    assert read(name, f) is None
