"""The session log cell's own pieces: its reference with sessions named
twice in one round, its 32-bit control, its argument draw, the copy and
commit metrics, and a small run that the control must fail.  Run by
hand: ``python -m pytest chipbench/tests``."""

import itertools
import random

import pytest

from chipbench import check, run
from chipbench.tests.small import run_small

CELL = "log_pb.records"
REF = run.load_module(run.HERE / "refs" / "log.py")


def test_reference_keeps_the_last_record_of_each_session():
    ref = REF.Ref({"make": {"n_clients": 4}})
    assert ref.apply("record", (2, 10, -7)) == -7
    assert ref.apply("record", (2, 11, 8)) == 8
    assert ref.snapshot() == [(0, None), (0, None), (11, 8), (0, None)]
    # one crash round, clients 0 and 2 on session 1: client 2's stays
    args = [(1, 20, 5), (3, 21, 6), (1, 22, 7)]
    assert ref.apply_round("record", args, [5, 6, 7]) == 0
    assert ref.snapshot() == [(0, None), (22, 7), (11, 8), (21, 6)]
    assert ref.apply_round("record", args, [5, check.MISSING, 8]) == 2


def test_control_wraps_seq_and_response_to_its_bits():
    from repro.core import NVM
    control = REF.Control({}, 32)
    nvm = NVM(64)
    base = nvm.alloc(8)
    nvm.write_range(base, [0, None] * 4)
    assert control.vector_apply(nvm, base, "RECORD",
                                [(1, 2 ** 33 + 5, 2 ** 31), (3, 4, -9)]) \
        == [-2 ** 31, -9]
    assert nvm.read_range(base, 8) == [0, None, 5, -2 ** 31,
                                       0, None, 4, -9]
    assert control.apply(nvm, base, "RECORD", (0, 1, 2 ** 62 + 3)) == 3


def test_records_are_drawn_from_the_seed_over_every_session():
    mix = run.Mix(run.json.loads(
        (run.HERE / "traffic" / "records.json").read_text()))
    first = list(itertools.islice(mix.stream(2 ** 40 + 3, 0), 2000))
    assert first == list(itertools.islice(mix.stream(2 ** 40 + 3, 0), 2000))
    assert {op for op, _ in first} == {"record"}
    spec = mix.data["args"]["record"]
    sessions = [a[0] for _, a in first]
    assert all(0 <= s < spec["sessions"] for s in sessions)
    assert max(sessions) > spec["sessions"] // 2
    assert all(2 ** 33 <= a[1] < 2 ** 62 for _, a in first)
    assert max(abs(a[2]) for _, a in first) > 2 ** 31
    # a round of 64 names one session twice about 3% of the time
    rng = random.Random(7)
    twice = sum(len({mix.draw("record", rng)[0] for _ in range(64)}) < 64
                for _ in range(2000))
    assert 30 < twice < 100


def _metric(name, obs):
    return run.load_module(run.HERE / "metrics" / f"{name}.py").read(obs)


def test_copy_and_commit_per_round():
    obs = {"delta": {"rounds": 40},
           "trace": {"spans": {"copy": {"count": 40, "total_s": 0.08},
                               "commit": {"count": 40, "total_s": 0.12}}}}
    assert _metric("copy_ms_per_round", obs) == pytest.approx(2)
    assert _metric("commit_ms_per_round", obs) == pytest.approx(3)
    for trace in (None, {"spans": {}}):
        obs["trace"] = trace
        assert _metric("copy_ms_per_round", obs) is None
        assert _metric("commit_ms_per_round", obs) is None


def test_traced_small_run_reports_copy_and_commit():
    res = run_small(CELL, traced=True)
    assert res["correct"], res["checks"]
    for name in ("copy_ms_per_round", "commit_ms_per_round"):
        assert res["metrics"][name]["value"] > 0
    assert res["metrics"]["kernel_ops_per_op"]["value"] > 0.9


def test_control_at_32_bits_fails_the_log_cell():
    res = run_small(CELL, control_bits=32)
    assert res["correct"] is False
    assert res["checks"]["wrong_replies"]["value"] > 0
