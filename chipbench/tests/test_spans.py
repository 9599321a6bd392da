"""The program's spans and counters as ``spans.py`` reads them: its
reduction on made-up events and on a recorded v5e trace, its metrics,
and a small traced run on the CPU.  Run by hand: ``python -m pytest
chipbench/tests``."""

from pathlib import Path

import jax
import pytest

from chipbench import run, spans, trace
from chipbench.tests.small import small_cell

T1, T2 = ("/host:CPU", 3), ("/host:CPU", 4)


def _events():
    """A 10 s window; one plane with ops, one without; two seam calls of
    thread T1 and one of T2 whose kernel ends outside it."""
    ops = [(1.2, 1.5, "while"), (3.0, 3.2, "fusion"), (6.0, 6.5, "while")]
    modules = [(1.2, 1.5, "jit_heap_HINSERT(7)"),
               (3.0, 3.2, "jit_heap_HINSERT(7)"),
               (6.0, 6.5, "jit_counter_FAA(9)")]
    return {
        "window": (0.0, 10.0),
        "spans": {"combine": [(0.0, 4.0), (5.5, 7.5)],
                  "seam": [(1.0, 2.0), (2.5, 3.6), (5.8, 7.4)]},
        "device": [ops, []],
        "program": {
            "combine.scan": [(0.0, 0.9, T1), (5.5, 5.7, T2)],
            "seam.gather": [(1.0, 1.1, T1), (2.5, 2.6, T1),
                            (5.8, 5.9, T2)],
            "seam.dispatch": [(1.1, 1.2, T1), (2.6, 2.8, T1),
                              (5.9, 6.0, T2)],
            "seam.fetch": [(1.2, 1.9, T1), (2.8, 3.5, T1), (6.0, 7.0, T2)],
            "seam.scatter": [(1.9, 2.0, T1), (3.5, 3.6, T1),
                             (7.0, 7.4, T2)],
        },
        "planes": [{"name": "/device:TPU:0", "lines": ["XLA Modules",
                                                       "XLA Ops"],
                    "ops": ops, "modules": modules},
                   {"name": "/device:TPU:0 idle", "lines": [], "ops": [],
                    "modules": []}],
    }


def test_wake_is_the_fetch_end_after_the_last_device_op_of_the_call():
    ev = _events()
    woke, calls = spans.wakes(ev["program"], ev["planes"], 0.0, 10.0)
    assert calls == 3
    assert woke == pytest.approx([0.4, 0.3, 0.5])
    # the plane without ops adds nothing, and nothing ends in a call
    # whose kernel ran after it
    ev["program"]["seam.fetch"][2] = (6.0, 6.2, T2)
    woke, calls = spans.wakes(ev["program"], ev["planes"], 0.0, 10.0)
    assert calls == 3 and woke == pytest.approx([0.4, 0.3])


def test_a_fetch_pairs_with_its_own_thread_s_dispatch():
    ev = _events()
    # a fetch on a thread that dispatched nothing is no seam call, though
    # other threads dispatched before it
    ev["program"]["seam.fetch"].append((6.1, 6.9, ("/host:CPU", 5)))
    woke, calls = spans.wakes(ev["program"], ev["planes"], 0.0, 10.0)
    assert calls == 3 and len(woke) == 3


def test_reduction_counts_only_planes_that_hold_ops():
    out = spans.reduce_program(_events())
    assert out["busy_s"] == pytest.approx(1.0)     # not averaged to 0.5
    assert [p["ops_in_window"] for p in out["planes"]] == [3, 0]
    assert out["module_ms"] == pytest.approx(
        {"jit_heap_HINSERT": 500.0, "jit_counter_FAA": 500.0})
    assert out["spans"]["seam.fetch"] == {"count": 3,
                                          "total_s": pytest.approx(2.4)}
    # no gap spans the whole window
    assert max(t for _, t in out["idle_gaps"]) < 10.0


def test_gaps_are_named_after_the_innermost_program_span():
    out = spans.reduce_program(_events())
    named = {round(t, 6): n for n, t in out["idle_gaps"]}
    # [0, 1.2]: scan 0.9, then combine, gather, dispatch 0.1 each
    assert named[1.2] == "combine.scan"
    # [1.5, 3.0]: fetch 0.4 + 0.2, combine 0.5 between the seam calls
    assert named[1.5] == "seam.fetch"
    # [3.2, 6.0]: 1.5 s between rounds, in no span
    assert named[2.8] == "client"
    assert "seam" not in named.values()


def _metric(name, obs):
    return run.load_module(run.HERE / "metrics" / f"{name}.py").read(obs)


def test_metric_arithmetic():
    program = spans.reduce_program(_events())
    obs = {"window_s": 10.0, "n_ops": 40,
           "delta": {"rounds": 4, "waiter_polls": 100, "queue_ns": 8e6,
                     "queued_ops": 40},
           "trace": {"spans": {"seam": {"count": 3, "total_s": 3.7}},
                     "program": program}}
    assert _metric("queue_ms_per_op", obs) == pytest.approx(0.2)
    assert _metric("waiter_polls_per_op", obs) == 2.5
    assert _metric("scan_ms_per_round", obs) == pytest.approx(275)
    assert _metric("host_apply_ms_per_round", obs) == 0
    assert _metric("seam_copy_ms_per_call", obs) == pytest.approx(300)
    assert _metric("dispatch_ms_per_call", obs) == pytest.approx(400 / 3)
    assert _metric("fetch_ms_per_call", obs) == pytest.approx(800)
    assert _metric("seam_wake_ms_per_call", obs) == pytest.approx(400)


def test_new_metrics_read_none_for_a_program_without_them():
    # the counters and the trace of a program that has no tracing module
    obs = {"window_s": 10.0, "n_ops": 40,
           "delta": {"rounds": 4, "ops_combined": 40},
           "trace": {"spans": {"seam": {"count": 3, "total_s": 3.7}},
                     "program": spans.reduce_program(
                         dict(_events(), program={}))}}
    for m in spans.METRICS:
        assert _metric(m["name"], obs) is None, m["name"]
    obs["trace"] = None
    for m in spans.METRICS:
        assert _metric(m["name"], obs) is None, m["name"]


@pytest.mark.parametrize("name", ["heap_pb.mixed", "counter_pb.closed"])
def test_small_traced_run_reports_the_program_s_metrics(name):
    cell, config = small_cell(name)
    cell["per_layer"] = cell["per_layer"] + spans.METRICS + spans.BOTH[:1]
    hooks = spans.Hooks(program_tracing=True)
    hooks.install()
    try:
        res = run.run_cell(cell, 2**33 + 5, 0.5, True, jax.devices(),
                           config=config, log=lambda line, **_: None)
    finally:
        hooks.remove()
    from repro.core import tracing
    assert res["correct"] and not tracing.enabled
    got = {k: v["value"] for k, v in res["metrics"].items()}
    # a CPU trace has no device plane: no wake
    assert set(got) >= {m["name"] for m in spans.METRICS} - {
        "seam_wake_ms_per_call"}
    assert "seam_wake_ms_per_call" not in got
    assert got["queue_ms_per_op"] > 0 and got["scan_ms_per_round"] > 0
    seam = hooks.program["spans"]
    # a call the window's edge cuts counts on one side only
    assert abs(seam["seam.dispatch"]["count"]
               - seam["seam.fetch"]["count"]) <= 1
    parts = (got["seam_copy_ms_per_call"] + got["dispatch_ms_per_call"]
             + got["fetch_ms_per_call"])
    assert parts <= got["seam_ms_per_call"] * 1.05
    line = spans.spans_line(res, hooks.program, cell)
    assert line["planes"] == [] and line["switch_interval_s"] > 0


REAL = Path(__file__).resolve().parent / "data" / "counter_pb.closed.v5e.xplane.pb"


def test_recorded_v5e_trace():
    # 0.5 s of counter_pb.closed on one TPU v5e (--trace 1 --keep-trace)
    events = trace.load(REAL)
    events.update(spans.load_program(REAL))
    harness = trace.reduce(events)
    program = spans.reduce_program(events)
    planes = {p["name"]: p["ops_in_window"] for p in program["planes"]}
    # a device plane with no op: trace.reduce averages it in, halving
    # busy time and reporting one idle gap as long as the window
    assert planes["/device:TPU:0"] > 0
    assert 0 in planes.values() and len(planes) == 2
    assert program["busy_s"] == pytest.approx(2 * harness["busy_s"])
    assert harness["idle_gaps"][0][1] == pytest.approx(harness["window_s"])
    assert max(t for _, t in program["idle_gaps"]) < program["window_s"] / 2
    assert set(program["module_ms"]) == {"jit_counter_FAA"}
    assert set(program["spans"]) == set(spans.PROGRAM_SPANS) - {
        "combine.host_apply"}
    # the host's spans and the device's ops share a clock: a device op
    # ends inside every seam call
    assert program["seam_calls"] > 0
    assert len(program["wake_s"]) >= 0.95 * program["seam_calls"]
    # each harness seam call holds one dispatch and one fetch, and the
    # program's spans cover nearly all of it
    seam = harness["spans"]["seam"]
    for name in ("seam.dispatch", "seam.fetch"):
        assert abs(program["spans"][name]["count"] - seam["count"]) <= 1
    inner = sum(program["spans"][n]["total_s"] for n in (
        "seam.gather", "seam.dispatch", "seam.fetch", "seam.scatter"))
    assert inner >= 0.9 * seam["total_s"]
