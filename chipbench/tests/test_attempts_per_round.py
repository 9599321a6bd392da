"""``attempts_per_round``: the harness's ``combine`` spans per committed
round.  One per round under PBComb; under PWFComb every attempt that
applied requests counts, the ones that lost too.  Run by hand:
``python -m pytest chipbench/tests``."""

import pytest

from chipbench import run
from chipbench.tests.small import run_small


def _read(obs):
    return run.load_module(
        run.HERE / "metrics" / "attempts_per_round.py").read(obs)


def _attempts(name):
    res = run_small(name, seconds=0.5, traced=True)
    assert res["correct"], res["checks"]
    return res["metrics"]["attempts_per_round"]["value"]


def test_one_attempt_per_pbcomb_round():
    assert _attempts("heap_pb.pairs") == pytest.approx(1.0, abs=0.05)


def test_pwfcomb_attempts_at_least_once_per_round():
    assert _attempts("heap_pwf.pairs") >= 1


def test_nothing_to_read_reads_none():
    spans = {"combine": {"count": 12, "total_s": 0.5}}
    assert _read({"delta": {"rounds": 8},
                  "trace": {"spans": spans}}) == 1.5
    assert _read({"delta": {"rounds": 0},
                  "trace": {"spans": spans}}) is None
    assert _read({"delta": {"rounds": 8},
                  "trace": {"spans": {}}}) is None
    assert _read({"delta": {"rounds": 8}, "trace": None}) is None
