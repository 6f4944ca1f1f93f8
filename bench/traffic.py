"""Open-loop traffic from a mix file (``bench/traffic/<name>.json``).

One general generator reads every mix.  A mix file holds:

  classes      list of {name, weight, prompt_median, prompt_sigma,
               decode_median, decode_sigma}: log-normal lengths per
               class (the paper's LPLD/LPHD/HPLD/HPHD table, section 5.1)
  max_prompt   cap on prompt tokens
  arrival      {process: "poisson" | "bursty", rate_rps, and for bursty
               period_s, burst_factor, burst_fraction}
  lead_in_s    seconds of traffic before the measured window opens
  pool_seed    seed of the fixed multisets of sizes and gaps

A run's schedule has three segments: the lead-in, the measured window
and a tail.  Each segment holds a fixed number of requests, its
expected count under the mix's rate profile, rounded, with its own
fixed multiset of sizes (class, prompt length, decode length) and of
inter-arrival spacings, drawn from ``pool_seed`` and the segment's
index.  The run's ``--seed`` only permutes each multiset inside its
segment and draws the token ids, so every seed offers exactly the same
work in the window, in another order.  Given their count, the arrivals
of a Poisson process in an interval are spread as normalised
exponential spacings, which is how they are placed here; a bursty
profile maps them through its cumulative intensity (time rescaling, as
the program's ``fleet/traces._arrival_times`` does).  The class draw is
that of ``runtime/workload.py``.  Both are copied here so that the
yardstick does not move when the program does.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    due_s: float            # offset from traffic start
    cls: str
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int     # tokens streamed, the prefill's first included


def load_mix(bench_dir: str, name: str) -> dict:
    with open(os.path.join(bench_dir, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _rate_profile(arrival: dict, t: np.ndarray) -> np.ndarray:
    """Instantaneous rate; the mean over a period equals ``rate_rps``."""
    rate = arrival["rate_rps"]
    if arrival["process"] == "poisson":
        return np.full_like(t, rate)
    if arrival["process"] != "bursty":
        raise ValueError(f"unknown arrival process {arrival['process']!r}")
    bf, frac = arrival["burst_factor"], arrival["burst_fraction"]
    if bf * frac >= 1.0:
        raise ValueError("bursty arrivals need burst_factor * "
                         "burst_fraction < 1")
    lo = rate * (1.0 - frac * bf) / (1.0 - frac)
    phase = (t % arrival["period_s"]) / arrival["period_s"]
    return np.where(phase < frac, bf * rate, lo)


def cumulative_intensity(arrival: dict, t_end: float):
    """Grid over [0, t_end] and the expected arrivals up to each point."""
    grid = np.linspace(0.0, t_end, 1 + max(1, int(t_end * 200)))
    lam = _rate_profile(arrival, grid)
    cum = np.concatenate(
        [[0.0], np.cumsum(0.5 * (lam[1:] + lam[:-1]) * np.diff(grid))])
    return grid, cum


def pool(mix: dict, segment: int, n: int, max_seq: int, max_prompt: int):
    """One segment's fixed multiset: (classes, prompt lengths, decode
    lengths, n + 1 spacings)."""
    rng = np.random.default_rng([mix["pool_seed"], segment])
    classes = mix["classes"]
    w = np.array([c["weight"] for c in classes], np.float64)
    pick = rng.choice(len(classes), size=n, p=w / w.sum())
    u_prompt = rng.standard_normal(n)
    u_decode = rng.standard_normal(n)
    spacings = rng.exponential(1.0, n + 1)
    plen = np.empty(n, np.int64)
    dlen = np.empty(n, np.int64)
    for i, k in enumerate(pick):
        c = classes[k]
        p = int(math.exp(math.log(c["prompt_median"])
                         + c["prompt_sigma"] * u_prompt[i]))
        d = int(math.exp(math.log(c["decode_median"])
                         + c["decode_sigma"] * u_decode[i]))
        plen[i] = min(max(1, p), max_prompt)
        # the prompt plus every streamed token stays inside max_seq
        dlen[i] = min(max(1, d), max_seq - 2 - plen[i])
    names = [classes[k]["name"] for k in pick]
    return names, plen, dlen, spacings


def generate(mix: dict, seed: int, seconds: float, tail_s: float,
             vocab: int, max_seq: int, max_prompt: int) -> List[Request]:
    """The run's schedule, sorted by due time: lead-in, ``seconds`` of
    window, ``tail_s`` of tail.  ``max_prompt`` is the serving cap (the
    mix's own cap or a smaller one)."""
    lead = mix["lead_in_s"]
    edges = [0.0, lead, lead + seconds, lead + seconds + tail_s]
    grid, cum = cumulative_intensity(mix["arrival"], edges[-1])
    cap = min(max_prompt, mix["max_prompt"])
    rng = np.random.default_rng(seed)
    out = []
    for k in range(3):
        la, lb = np.interp(edges[k:k + 2], grid, cum)
        n = int(round(lb - la))
        if n == 0:
            continue
        names, plen, dlen, spacings = pool(mix, k, n, max_seq, cap)
        order = rng.permutation(n)
        sp = spacings[rng.permutation(n + 1)]
        due = np.interp(la + (lb - la) * np.cumsum(sp)[:n] / sp.sum(),
                        cum, grid)
        for i in range(n):
            j = order[i]
            toks = rng.integers(1, vocab, int(plen[j]), dtype=np.int32)
            out.append(Request(due_s=float(due[i]), cls=names[j],
                               prompt=toks, max_new_tokens=int(dlen[j])))
    return out
