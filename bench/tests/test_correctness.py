"""The correctness check must fail what it is there to catch.  Both
tests run a whole cell through the harness at the configuration's
rehearsal size on the CPU (kernels interpreted), skipping the look for
a chip.  Run by path: ``python -m pytest bench/tests``.

  * the float8 control, read on the same sample and put through the
    run's own comparison in the program's place, comes out not correct
    while the program comes out correct;
  * a served token altered where the decode step produces it makes the
    run come out not correct.
"""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import run  # noqa: E402

CELL = "qwen2_0_5b.mixed"
SECONDS = 6.0


def _line(res):
    line, _ = run.result_line(res, False, True, run.load_limit(CELL))
    return line


def test_control_fails_the_limit_the_program_meets():
    res = run.serve(CELL, 2 ** 31 + 5, SECONDS, False, True, control=True,
                    log=lambda m: None)
    limit = run.load_limit(CELL)["max_logit_gap"]
    assert res["sample_tokens"] >= run.SAMPLE_MIN
    assert res["max_logit_gap"] <= limit
    assert res["control_logit_gap"] > limit
    assert _line(res)["correct"] is True
    control = dict(res, max_logit_gap=res["control_logit_gap"])
    assert _line(control)["correct"] is False


def test_token_altered_where_produced_is_not_correct(monkeypatch):
    from repro.core.decode_engine import DecodeEngine
    produce = DecodeEngine._iteration_paged

    def altered(self):
        nxt = produce(self)
        if self.iterations % 7 == 3:
            nxt = (nxt + 1) % self.cfg.vocab_size
        return nxt

    monkeypatch.setattr(DecodeEngine, "_iteration_paged", altered)
    res = run.serve(CELL, 2 ** 31 + 6, SECONDS, False, True,
                    log=lambda m: None)
    line = _line(res)
    assert res["sample_tokens"] >= run.SAMPLE_MIN
    assert res["max_logit_gap"] > run.load_limit(CELL)["max_logit_gap"]
    assert line["correct"] is False
    assert list(line)[-1] == "compared"
