"""CPU checks of the yardstick: trace reduction on a small hand-made
trace, FLOP and byte counts worked out by hand, the peak table, and the
traffic generator.  Run by path: ``python -m pytest bench/tests``."""
import os
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import flops as FL      # noqa: E402
import traffic as TR    # noqa: E402
import xplane as XP     # noqa: E402


def ev(name, start, dur, **stats):
    return NS(name=name, start_ns=start, duration_ns=dur,
              stats=list(stats.items()))


def hand_made_trace():
    """One host plane and one TPU plane.  Device: a prefill program
    [100, 400) holding a fusion [100, 150) and its kernel [160, 360); a
    decode program [500, 600) holding its kernel [500, 560) and a fusion
    [570, 600); the host spans [0, 1000)."""
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench_clock", 0, 10), ev("work", 10, 990)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev("jit__prefill_paged(7)", 100, 300),
            ev("jit__decode_paged(9)", 500, 100)]),
        NS(name="XLA Ops", events=[
            ev("fusion.1", 100, 50, hlo_category="loop fusion"),
            ev("custom-call.2", 160, 200, hlo_category="custom-call"),
            ev("%_kernel.4 = bf16[8] custom-call(s32[8] %a)", 500, 60),
            ev("fusion.3", 570, 30, hlo_category="loop fusion")])])
    return [host, dev]


def test_device_facts_from_hand_made_trace():
    (d,) = XP.reduce_planes(hand_made_trace())
    assert d.name == "/device:TPU:0"
    assert d.window_ns == (0, 1000)
    assert d.busy_ns == 50 + 200 + 60 + 30
    # idle: [0,100) [150,160) [360,500) [560,570) [600,1000), longest first
    assert d.gaps == [(600, 400), (360, 140), (0, 100), (150, 10),
                      (560, 10)]
    assert d.modules == {"jit__prefill_paged(7)": 300,
                         "jit__decode_paged(9)": 100}
    assert d.kernels == {"jit__prefill_paged(7)": 200,
                         "jit__decode_paged(9)": 60}
    assert d.ops == {"_prefill_paged:fusion": 50,
                     "_prefill_paged:custom-call": 200,
                     "_decode_paged:_kernel": 60,
                     "_decode_paged:fusion": 30}
    assert XP.short_op("%paged_decode_attention.5 = bf16[128,14,64]{2,1,0}"
                       " custom-call(s32[128,256] %x)") \
        == "paged_decode_attention"


def test_union_merges_overlaps():
    assert XP.union([(5, 9), (0, 3), (2, 4), (9, 12)]) == [(0, 4), (5, 12)]


def test_readers_on_hand_made_trace():
    import importlib.util
    (d,) = XP.reduce_planes(hand_made_trace())
    facts = {"trace": {"devices": [d], "peaks": {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9},
        "prefill_work": {"flops": 1e3, "attn_flops": 100.0,
                         "attn_bytes": 10.0},
        "decode_work": {"flops": 50.0, "attn_flops": 2.0,
                        "attn_bytes": 30.0}}}

    def read(name):
        path = os.path.join(BENCH, "metrics", f"{name}.py")
        sys.path.insert(0, os.path.dirname(path))
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read(facts)

    # 1e3 FLOPs over 300 ns at 1e12 FLOP/s: 1e3 / 3e5 = 1/300
    assert read("prefill_step_mfu") == pytest.approx(100 / 300)
    assert read("decode_step_mfu") == pytest.approx(100 * 50 / 1e5)
    # kernel: max(100 / 1e12, 10 / 1e9) = 1e-8 s over 200 ns = 5 %
    assert read("prefill_attn_roofline") == pytest.approx(5.0)
    # decode kernel: max(2e-12, 3e-8) over 60 ns = 50 %
    assert read("decode_attn_roofline") == pytest.approx(50.0)
    assert read("device_idle_share") == pytest.approx(66.0)


TINY = {"d": 4, "h": 2, "kvh": 1, "hd": 2, "ff": 3, "vocab": 5,
        "layers": 1}


def test_flops_by_hand():
    # per token: 2 * (4*4 + 2*4*2 + 4*4 + 3*4*3) = 2 * 84
    assert FL.matmul_flops_per_token(TINY) == 168
    assert FL.head_flops(TINY) == 40
    # causal, 3 queries see 1 + 2 + 3 = 6 keys; 4 * h * hd = 16 per key
    assert FL.prefill_attn_flops(TINY, 3) == 96
    assert FL.prefill_flops(TINY, 3) == 3 * 168 + 96 + 40
    assert FL.decode_flops(TINY, 7) == 168 + 16 * 7 + 40
    two = dict(TINY, layers=2)
    assert FL.decode_flops(two, 7) == 2 * 168 + 2 * 16 * 7 + 40


def test_bytes_by_hand():
    # K and V of one token: 2 * kvh * hd * 2 B = 8 B; q and out: 2*h*hd*2
    assert FL.decode_attn_bytes(TINY, 10) == 10 * 8 + 16
    # 600 tokens: segments [0,512) and [512,600) read 512 and 600 keys
    assert FL.prefill_attn_bytes(TINY, 600) == (512 + 600) * 8 + 600 * 16


def test_peaks_table():
    p = FL.peaks(BENCH, "TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        FL.peaks(BENCH, "TPU v9 imaginary")


MIX = {"classes": [
    {"name": "A", "weight": 0.5, "prompt_median": 18, "prompt_sigma": 0.8,
     "decode_median": 40, "decode_sigma": 0.7},
    {"name": "B", "weight": 0.5, "prompt_median": 1100, "prompt_sigma": 0.5,
     "decode_median": 420, "decode_sigma": 0.6}],
    "max_prompt": 2048, "arrival": {"process": "poisson", "rate_rps": 5.0},
    "lead_in_s": 2.0, "pool_seed": 1}


def test_traffic_same_seed_same_inputs():
    a = TR.generate(MIX, 2 ** 31 + 77, 10, 5, 1000, 4096, 2048)
    b = TR.generate(MIX, 2 ** 31 + 77, 10, 5, 1000, 4096, 2048)
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_traffic_seeds_permute_one_multiset():
    a = TR.generate(MIX, 1, 10, 5, 1000, 4096, 2048)
    b = TR.generate(MIX, 2, 10, 5, 1000, 4096, 2048)
    key = sorted((len(r.prompt), r.max_new_tokens, r.cls) for r in a)
    assert key == sorted((len(r.prompt), r.max_new_tokens, r.cls)
                         for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    assert all(len(r.prompt) + r.max_new_tokens <= 4094 for r in a)


def test_traffic_window_holds_the_same_work_for_every_seed():
    lead, sec = MIX["lead_in_s"], 10
    def window(seed):
        reqs = TR.generate(MIX, seed, sec, 5, 1000, 4096, 2048)
        assert all(x.due_s <= y.due_s for x, y in zip(reqs, reqs[1:]))
        return sorted((len(r.prompt), r.max_new_tokens) for r in reqs
                      if lead <= r.due_s <= lead + sec)
    first = window(3)
    assert len(first) == 50           # 5 req/s x 10 s
    for seed in (4, 2 ** 31 + 9, 3 * 10 ** 9):
        assert window(seed) == first


def test_bursty_keeps_the_mean_rate():
    mix = dict(MIX, lead_in_s=0.0, arrival=dict(
        process="bursty", rate_rps=10.0, period_s=10.0, burst_factor=4.0,
        burst_fraction=0.1))
    t = np.array([r.due_s for r in TR.generate(mix, 5, 200, 0, 1000,
                                                4096, 2048)])
    assert len(t) == 2000 and t[-1] < 200.0
    in_burst = np.mean((t % 10.0) < 1.0)
    assert in_burst == pytest.approx(0.4, abs=0.03)
