"""The program's own spans and counters (core/tracing.py): free when off,
and on, written into the profiler's trace with the round they belong to."""

import os
import subprocess
import sys
import threading
import time
from collections import defaultdict
from pathlib import Path

import pytest

from repro.api import CombiningRuntime
from repro.core import tracing
from repro.kernels import vector_rounds

SPANS = ("combine.scan", "combine.host_apply", "seam.gather",
         "seam.dispatch", "seam.fetch", "seam.scatter")
SRC = Path(__file__).resolve().parent.parent / "src"


@pytest.fixture(autouse=True)
def _tracing_off():
    yield
    tracing.disable()


def test_span_is_one_shared_noop_when_off():
    assert not tracing.enabled
    a, b = tracing.span("seam.fetch"), tracing.span("combine.scan", x=1)
    assert a is b
    with a as s:
        s.set_metadata(adopted=3)


def test_importing_the_core_leaves_jax_out():
    code = ("import sys, repro.core, repro.core.tracing, repro.api; "
            "sys.exit('jax' in sys.modules)")
    env = dict(os.environ, PYTHONPATH=str(SRC))
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=120).returncode == 0


def test_kernels_lower_under_their_own_names():
    import jax
    import numpy as np
    with jax.enable_x64(True):
        heap = vector_rounds.kernel("heap.HINSERT").lower(
            np.zeros(8, np.int64), np.int64(0), np.zeros(2, np.int64))
        faa = vector_rounds.kernel("counter.FAA").lower(
            np.int64(0), np.zeros(2, np.int64))
    assert "@jit_heap_HINSERT" in heap.as_text()
    assert "@jit_counter_FAA" in faa.as_text()


def _polls(obj):
    return obj.adapter.degree_stats(obj.core)["waiter_polls"]


def test_waiter_polls_count_a_wait_when_it_ends():
    rt = CombiningRuntime(n_threads=2)
    obj = rt.make("counter", "pbcomb")
    core = obj.core
    assert _polls(obj) == 0
    # a combiner holds the lock: the caller waits in _wait_while
    core._elect.acquire()
    core.lock.store(core.lock.load() + 1)
    t = threading.Thread(target=rt.attach(1).bind(obj).fetch_add, args=(5,))
    t.start()
    time.sleep(0.05)
    assert _polls(obj) == 0          # added when the wait ends
    core.lock.store(core.lock.load() + 1)
    core._elect.release()
    t.join(30)
    assert not t.is_alive()
    assert _polls(obj) > 0
    rt.close()


def _hold_lock(core):
    """Take the lock as a combiner would; returns the odd value held."""
    core._elect.acquire()
    held = core.lock.load() + 1
    core.lock.store(held)
    return held


def test_a_thread_waiter_blocks_instead_of_polling():
    rt = CombiningRuntime(n_threads=2)
    obj = rt.make("counter", "pbcomb")
    core = obj.core
    held = _hold_lock(core)
    t = threading.Thread(target=rt.attach(1).bind(obj).fetch_add, args=(5,),
                         daemon=True)
    t.start()
    time.sleep(0.1)
    core.lock.store(held + 1)
    core._elect.release()
    t.join(10)
    assert not t.is_alive()
    assert 1 <= _polls(obj) <= 2
    assert obj.snapshot() == 5
    rt.close()


def test_a_plain_store_wakes_a_blocked_waiter_at_once():
    rt = CombiningRuntime(n_threads=2)
    core = rt.make("counter", "pbcomb").core
    delays = []
    for _ in range(5):
        held = _hold_lock(core)
        left = []

        def wait():
            core._wait_while(1, held)
            left.append(time.perf_counter())
        t = threading.Thread(target=wait, daemon=True)
        t.start()
        time.sleep(0.02)
        assert not left                  # still waiting on the held lock
        unlocked = time.perf_counter()
        core.lock.store(held + 1)
        t.join(10)
        assert not t.is_alive()
        core._elect.release()
        delays.append(left[0] - unlocked)
    assert sorted(delays)[2] < 0.010
    rt.close()


def test_an_shm_waiter_still_polls_and_leaves_on_halted():
    from repro.core import SimulatedCrash
    from repro.core.shm import ShmAtomicInt
    rt = CombiningRuntime(n_threads=2, backend="shm")
    try:
        core = rt.make("counter", "pbcomb").core
        assert isinstance(core.lock, ShmAtomicInt)
        held = _hold_lock(core)
        ended = []

        def wait():
            try:
                core._wait_while(1, held)
                ended.append("unlocked")
            except SimulatedCrash:
                ended.append("halted")
        t = threading.Thread(target=wait, daemon=True)
        t.start()
        time.sleep(0.05)
        assert not ended
        rt.nvm.crash()
        t.join(10)
        assert not t.is_alive() and ended == ["halted"]
        assert core.lock.load() == held  # the lock was never released
        assert core.waiter_polls[1] == 0  # a wait that raises adds nothing
        rt.recover()
    finally:
        rt.close()


def _drive(rt, obj, calls, n_ops):
    """``n_ops`` calls per client thread, ``calls(bound, i)`` each."""
    def client(p):
        bound = rt.attach(p).bind(obj)
        for i in range(n_ops):
            calls(bound, i)
    threads = [threading.Thread(target=client, args=(p,))
               for p in range(rt.n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads)


def _load(trace_dir):
    """Every program span of the trace: name -> list of its ids."""
    from jax.profiler import ProfileData
    (path,) = Path(trace_dir).rglob("*.xplane.pb")
    found = defaultdict(list)
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                name = e.name.split("#")[0]
                if name in SPANS:
                    found[name].append(dict(e.stats))
    return found


def test_traced_rounds_write_every_span_with_its_round(tmp_path):
    import jax
    rt = CombiningRuntime(n_threads=4)
    heap = rt.make("heap", "pbcomb", vector_apply=True, capacity=64)
    counter = rt.make("counter", "pbcomb", vector_apply=True)
    n_ops = 40
    # compile every batch length outside the trace
    _drive(rt, heap, lambda b, i: b.insert(i) if i % 2 else b.delete_min(),
           n_ops)
    _drive(rt, counter, lambda b, i: b.fetch_add(i), n_ops)
    before = {o.name: o.adapter.degree_stats(o.core)
              for o in (heap, counter)}
    calls0 = vector_rounds.kernel_calls()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    tracing.enable()
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _drive(rt, heap,
               lambda b, i: b.insert(i) if i % 2 else b.delete_min(), n_ops)
        _drive(rt, counter, lambda b, i: b.fetch_add(i), n_ops)
        # a mixed pass: three announced inserts served with a delete_min
        handles = [rt.attach(p) for p in range(4)]
        for p in (1, 2, 3):
            handles[p].announce(heap, "insert", 100 + p)
        handles[0].invoke(heap, "delete_min")
        for p in (1, 2, 3):
            handles[p].perform(heap)
    finally:
        jax.profiler.stop_trace()
        tracing.disable()
    calls = vector_rounds.kernel_calls() - calls0
    found = _load(tmp_path)
    assert set(found) == set(SPANS)
    for name in SPANS:
        assert all(ids.get("round", 0) % 2 == 1 for ids in found[name]), name
    assert len(found["seam.dispatch"]) == calls
    assert {ids["kernel"] for ids in found["seam.dispatch"]} == {
        "heap.HINSERT", "heap.HDELETEMIN", "counter.FAA"}
    assert all("pass" in ids and "adopted" in ids
               for ids in found["combine.scan"])
    # every op called while tracing was on (the threads', and the
    # staged round's invoke), and none announced through the staged API,
    # which stamps nothing
    for obj, served in ((heap, 4 * n_ops + 1), (counter, 4 * n_ops)):
        d0, d1 = before[obj.name], obj.adapter.degree_stats(obj.core)
        assert d1["queued_ops"] - d0["queued_ops"] == served
        assert d1["queue_ns"] > d0["queue_ns"]
    rt.close()


def test_tracing_switched_off_again_stops_counting():
    rt = CombiningRuntime(n_threads=2)
    obj = rt.make("counter", "pbcomb", vector_apply=True)
    tracing.enable()
    tracing.disable()
    _drive(rt, obj, lambda b, i: b.fetch_add(1), 20)
    stats = obj.adapter.degree_stats(obj.core)
    assert stats["queued_ops"] == 0 and stats["queue_ns"] == 0
    rt.close()


def test_copied_words_count_the_whole_record_then_the_last_rounds_lines():
    """PBComb's ``copied_words``: the first round after ``recover()``
    copies the whole StateRec, each later one only the lines the round
    before it wrote (the log names its writes; DESIGN.md §3)."""
    rt = CombiningRuntime(n_threads=2)
    obj = rt.make("log", "pbcomb", n_clients=64)
    rec = rt.attach(0).bind(obj).record

    def copied(args):
        before = obj.adapter.degree_stats(obj.core)["copied_words"]
        rec(args)
        return obj.adapter.degree_stats(obj.core)["copied_words"] - before

    # StateRec: sessions in words 0..127 (lines 0..15), then thread 0's
    # reply (word 128) and deactivate bit (word 130), in line 16 of
    # its 132 words
    words = obj.core.rec_words
    assert words == 132
    copied((5, 1, 7))
    rt.crash()
    rt.recover()
    assert copied((40, 2, 8)) == words
    # the last round wrote line 10 (session 40) and line 16 (4 words)
    assert copied((20, 3, 9)) == 8 + 4
    assert copied((20, 4, 9)) == 8 + 4       # line 5, line 16
    assert copied((21, 5, 1)) == 8 + 4       # session 20 again: line 5
    rt.close()
