"""CPU rehearsal of every cell at a tiny size: the mixes, the references,
the comparison and the metric arithmetic, end to end.  Run by hand:
``python -m pytest chipbench/tests``."""

import itertools

import pytest

from chipbench import check, probes, run
from chipbench.tests.small import CELLS, run_small, small_cell


@pytest.mark.parametrize("name", CELLS)
def test_untraced_run_is_correct_and_reports_end_to_end(name):
    res = run_small(name)
    assert res["correct"], res["checks"]
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert list(res)[-1] == "checks"
    cell, _ = small_cell(name)
    assert set(res["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_per_layer_from_counters_and_spans(name):
    res = run_small(name, traced=True)
    assert res["correct"], res["checks"]
    got = set(res["metrics"])
    assert {"p99_op_ms", "round_degree", "psyncs_per_op", "pwb_lines_per_op",
            "kernel_calls_per_round", "kernel_ops_per_op"} <= got
    # a CPU trace has no device plane: the device's metrics stay out
    assert res["device"]["busy_s"] is None
    assert not got & {"device_ms_per_round", "device_idle_share"}
    assert res["metrics"]["round_degree"]["value"] >= 1
    assert res["metrics"]["kernel_ops_per_op"]["value"] > 0


def _mix(name):
    return run.Mix(run.json.loads(
        (run.HERE / "traffic" / f"{name}.json").read_text()))


def test_op_streams_follow_the_mix_and_the_seed():
    pairs = _mix("pairs")
    first = list(itertools.islice(pairs.stream(2**40 + 3, 0), 128))
    assert [op for op, _ in first] == ["insert", "delete_min"] * 64
    spec = pairs.data["args"]["insert"]
    assert all(spec["lo"] <= a < spec["hi"] for op, a in first
               if op == "insert")
    assert all(a is None for op, a in first if op == "delete_min")
    again = list(itertools.islice(pairs.stream(2**40 + 3, 0), 128))
    other = list(itertools.islice(pairs.stream(2**40 + 4, 0), 128))
    assert first == again and first != other

    mixed = _mix("mixed")
    ops = [op for op, _ in itertools.islice(mixed.stream(5, 1), 4000)]
    assert ops.count("insert") == 2000
    # random within each block of 16, never more than 8 off balance
    assert len({tuple(ops[i:i + 16]) for i in range(0, 4000, 16)}) > 200
    excess = [0]
    for op in ops:
        excess.append(excess[-1] + (1 if op == "insert" else -1))
    assert max(map(abs, excess)) <= 8 and excess[::16] == [0] * 251

    closed = _mix("closed")
    deltas = [a for _, a in itertools.islice(closed.stream(5, 2), 500)]
    assert max(map(abs, deltas)) > 2**31       # a 32-bit body cannot hold them


def test_every_mix_loads_the_code_it_names():
    for path in sorted((run.HERE / "traffic").glob("*.json")):
        mix = run.Mix(run.json.loads(path.read_text()))
        assert mix.data["name"] == path.stem
        assert mix.ops() >= set(mix.data["crash_cycles"])
        assert mix.client


def test_compile_counter_sees_a_new_shape_and_not_a_cached_one():
    import jax
    import jax.numpy as jnp
    counter = probes.CompileCounter()
    try:
        f = jax.jit(lambda x: x * 3 + 1)
        f(jnp.zeros(3))
        seen = counter.events
        f(jnp.ones(3))
        assert counter.events == seen > 0
        f(jnp.zeros(7))
        assert counter.events > seen
    finally:
        counter.close()


def test_heap_reference_and_its_rounds():
    ref = run.load_module(run.HERE / "refs" / "heap.py").Ref({"capacity": 3})
    inserted = [ref.apply("insert", k) for k in (5, -2, 9, 1)]
    assert inserted == [True] * 3 + [False]
    assert ref.apply("delete_min", None) == -2
    assert ref.snapshot() == [5, 9]
    # a round of delete_mins: any order gives the same multiset
    assert ref.apply_round("delete_min", [None, None], [9, 5]) == 0
    ref.apply_round("insert", [1, 2], [True, True])
    assert ref.apply_round("delete_min", [None, None], [1, 3]) == 1


def test_counter_reference_chains_a_round():
    mod = run.load_module(run.HERE / "refs" / "counter.py")
    ref = mod.Ref({})
    assert ref.apply("fetch_add", 10) == 0
    deltas = [5, -3, 7]
    # served in the order 2, 0, 1 from 10: replies 17, 22, 10
    assert ref.apply_round("fetch_add", deltas, [17, 22, 10]) == 0
    assert ref.snapshot() == 19
    assert ref.apply_round("fetch_add", deltas,
                           [19, 24, check.MISSING]) == 1


def test_controls_wrap_to_their_precision():
    heap = run.load_module(run.HERE / "refs" / "heap.py")
    assert heap.wrap(2**31, 32) == -2**31
    assert heap.wrap(-2**31 - 1, 32) == 2**31 - 1
    assert heap.wrap(12345, 32) == 12345


def test_compare_counts_each_kind_of_fault():
    ref_mod = run.load_module(run.HERE / "refs" / "counter.py")
    requests = {"fetch_add": ("FAA", 1)}
    clients = [[("fetch_add", 2, 0), ("fetch_add", 5, 3)],
               [("fetch_add", 1, 2)]]
    order = [(0, "FAA", 2), (1, "FAA", 1), (0, "FAA", 5)]

    def counts(clients=clients, order=order, window_state=8, cycles=(),
               final_state=8):
        return check.compare(ref_mod.Ref({}), (None, []), clients, order,
                             requests, window_state, list(cycles),
                             final_state)

    assert set(counts().values()) == {0}
    bad = [[("fetch_add", 2, 0), ("fetch_add", 5, 4)], clients[1]]
    assert counts(clients=bad)["wrong_replies"] == 1
    assert counts(order=order[:2])["lost_or_extra_ops"] == 1
    assert counts(order=order + [(1, "FAA", 1)])["lost_or_extra_ops"] == 1
    assert counts(window_state=9)["state_diff_after_window"] == 1
    assert counts(cycles=[("fetch_add", [1, 1], [8, 8])],
                  final_state=10)["recovery_wrong_replies"] == 1


def test_state_diff_counts_words():
    assert check.state_diff([1, 2, 2], [1, 2, 2]) == 0
    assert check.state_diff([1, 2, 3], [1, 2, 2]) == 2
    assert check.state_diff([True], [1]) == 2       # types are compared
    assert check.state_diff(3, 3) == 0 and check.state_diff(3, 4) == 1


def _metric(name, obs):
    return run.load_module(run.HERE / "metrics" / f"{name}.py").read(obs)


def test_metric_arithmetic():
    obs = {"window_s": 2.0, "n_ops": 400,
           "latencies_s": [i / 1000 for i in range(1, 401)],
           "recovery_s": [0.1, 0.3], "setup_s": 12.5,
           "delta": {"pwb": 800, "pfence": 40, "psync": 40, "rounds": 40,
                     "ops_combined": 400, "kernel_calls": 60,
                     "seam_ops": 300},
           "trace": {"window_s": 2.0, "busy_s": 0.5,
                     "spans": {"seam": {"count": 4, "total_s": 0.02}}}}
    assert _metric("ops_per_s", obs) == 200
    assert _metric("p99_op_ms", obs) == pytest.approx(396)   # rank 396 of 400
    assert _metric("recovery_ms", obs) == pytest.approx(200)
    assert _metric("setup_s", obs) == 12.5
    assert _metric("round_degree", obs) == 10
    assert _metric("psyncs_per_op", obs) == 0.1
    assert _metric("pwb_lines_per_op", obs) == 2
    assert _metric("kernel_calls_per_round", obs) == 1.5
    assert _metric("kernel_ops_per_op", obs) == 0.75
    assert _metric("seam_ms_per_call", obs) == pytest.approx(5)
    assert _metric("device_ms_per_round", obs) == pytest.approx(12.5)
    assert _metric("device_idle_share", obs) == pytest.approx(75)
    # nothing to read: the reader says so and never reports 0
    obs["trace"] = None
    for name in ("seam_ms_per_call", "device_ms_per_round",
                 "device_idle_share"):
        assert _metric(name, obs) is None
