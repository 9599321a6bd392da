"""PWFComb's combiner seen by its counters and spans: every attempt ends
published, abandoned at a VL or lost at the SC, every op ends in a win, an
early finish or the fallback, and many threads attempting at once through
the heap kernels still give the sequential answers in the committed
order."""

import heapq
import sys
import threading
from collections import Counter, defaultdict
from pathlib import Path

import numpy as np
import pytest

from repro.api import CombiningRuntime
from repro.core import NVM, tracing
from repro.core.objects import FetchAddObject
from repro.core.pbcomb import RequestRec
from repro.core.pwfcomb import PWFComb
from repro.kernels import vector_rounds

ATTEMPT_SPANS = ("combine.attempt", "combine.scan", "combine.host_apply")


@pytest.fixture(autouse=True)
def _tracing_off():
    yield
    tracing.disable()


def _core():
    return PWFComb(NVM(1 << 12), 2, FetchAddObject(), backoff=False)


def _identities(core, ops):
    """The two identities of ``PWFComb.TRACE_COUNTERS``; returns them."""
    c = core.trace_counters()
    rounds = core.stats.snapshot()["rounds"]
    assert c["attempts"] == rounds + c["aborted_vl"] + c["lost_sc"]
    assert ops == rounds + c["finished_early"] + c["fallbacks"]
    return dict(c, rounds=rounds)


def _once(fn):
    """``fn`` on the first call only."""
    state = {"armed": True}

    def call():
        if state["armed"]:
            state["armed"] = False
            fn()
    return call


def _other(core, replies):
    """Thread 1's op, once, its reply kept in ``replies``."""
    return _once(lambda: replies.append(core.op(1, "FAA", 10, 0)))


def _vl_at_line_18(core):
    # thread 1 publishes between thread 0's LL and its line-18 VL: the
    # first StateRec copy is thread 0's (thread 1's own copy comes later)
    nvm, copy = core.nvm, core.nvm.copy_range
    theirs = []
    other = _other(core, theirs)

    def copy_then_publish(dst, src, n):
        copy(dst, src, n)
        other()
    nvm.copy_range = copy_then_publish
    return [core.op(0, "FAA", 1, 0)] + theirs, 2


def _vl_at_line_26(core):
    # thread 1 publishes, serving both, while thread 0 simulates
    begin = core._begin_attempt
    theirs = []
    other = _other(core, theirs)

    def begin_then_publish(slot, p):
        begin(slot, p)
        if p == 0:
            other()
    core._begin_attempt = begin_then_publish
    return [core.op(0, "FAA", 1, 0)] + theirs, 2


def _lost_sc(core):
    # thread 1 publishes, serving both, between thread 0's line 26 and
    # its SC; thread 0 had applied its own request alone
    pre = core._pre_publish
    theirs = []
    other = _other(core, theirs)

    def publish_first(slot, p):
        if p == 0:
            other()
        return pre(slot, p)
    core._pre_publish = publish_first
    return [core.op(0, "FAA", 1, 0)] + theirs, 2


def _early_finish(core):
    # thread 1 announces, thread 0's round serves it, thread 1 finds its
    # response in the published StateRec
    core.request[1] = RequestRec("FAA", 10, 1 - core.request[1].activate, 1)
    first = core.op(0, "FAA", 1, 0)
    return [first, core._perform_request(1)], 2


def _fallback(core):
    # thread 1 scans before thread 0 announces and publishes while
    # thread 0 attempts; then thread 1's next op serves thread 0 during
    # thread 0's second attempt: two lost SCs, and the fallback returns
    paused, go = threading.Event(), threading.Event()
    pre = core._pre_publish
    calls = defaultdict(int)
    replies = {}

    def scripted(slot, p):
        calls[p] += 1
        if p == 1 and calls[1] == 1:
            paused.set()
            assert go.wait(30)
        elif p == 0 and calls[0] == 1:
            go.set()
            t1.join(30)
        elif p == 0 and calls[0] == 2:
            replies["second"] = core.op(1, "FAA", 20, 1)
        return pre(slot, p)
    core._pre_publish = scripted
    t1 = threading.Thread(
        target=lambda: replies.setdefault("first", core.op(1, "FAA", 10, 0)))
    t1.start()
    assert paused.wait(30)
    mine = core.op(0, "FAA", 1, 0)
    assert not t1.is_alive()
    return [mine, replies["first"], replies["second"]], 3


#: per script: thread 0's reply, then thread 1's; the counter's state;
#: (pwb, pfence, psync), as the same interleavings issued them before
#: PWFComb counted anything; the counters
SCRIPTS = {
    "vl_line_18": (_vl_at_line_18, [0, 1], 11, (2, 1, 1),
                   {"attempts": 2, "rounds": 1, "aborted_vl": 1,
                    "lost_sc": 0, "finished_early": 1, "fallbacks": 0,
                    "wasted_ops": 0}),
    "vl_line_26": (_vl_at_line_26, [0, 1], 11, (2, 1, 1),
                   {"attempts": 2, "rounds": 1, "aborted_vl": 1,
                    "lost_sc": 0, "finished_early": 1, "fallbacks": 0,
                    "wasted_ops": 2}),
    "lost_sc": (_lost_sc, [0, 1], 11, (3, 2, 1),
                {"attempts": 2, "rounds": 1, "aborted_vl": 0, "lost_sc": 1,
                 "finished_early": 1, "fallbacks": 0, "wasted_ops": 1}),
    "early_finish": (_early_finish, [0, 1], 11, (2, 1, 1),
                     {"attempts": 1, "rounds": 1, "aborted_vl": 0,
                      "lost_sc": 0, "finished_early": 1, "fallbacks": 0,
                      "wasted_ops": 0}),
    "fallback": (_fallback, [10, 0, 11], 31, (6, 4, 2),
                 {"attempts": 4, "rounds": 2, "aborted_vl": 0, "lost_sc": 2,
                  "finished_early": 0, "fallbacks": 1, "wasted_ops": 3}),
}


@pytest.mark.parametrize("name", sorted(SCRIPTS))
def test_counters_keep_their_identities_in_a_scripted_interleaving(name):
    script, replies, state, persists, expect = SCRIPTS[name]
    core = _core()
    got, ops = script(core)
    assert got == replies
    assert core.nvm.read(core._base(core.S.load())) == state
    c = core.nvm.counters
    assert (c["pwb"], c["pfence"], c["psync"]) == persists
    assert _identities(core, ops) == expect


def _load(trace_dir):
    """Every attempt span of the trace: name -> list of its ids."""
    from jax.profiler import ProfileData
    (path,) = Path(trace_dir).rglob("*.xplane.pb")
    found = defaultdict(list)
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                name = e.name.split("#")[0]
                if name in ATTEMPT_SPANS:
                    found[name].append(dict(e.stats))
    return found


def _scripted_heap_rounds(rt, heap):
    """A lost SC, a VL abandon and a mixed pass (insert and delete_min
    in one batch, served by the per-op host loop) on a PWFComb heap."""
    core = heap.core
    handles = [rt.attach(p) for p in range(rt.n_threads)]
    pre, begin = core._pre_publish, core._begin_attempt
    lose = _once(lambda: handles[1].invoke(heap, "insert", 7))
    abandon = _once(lambda: handles[1].invoke(heap, "insert", 9))

    def pre_publish(slot, p):
        if p == 0:
            lose()
        return pre(slot, p)
    core._pre_publish = pre_publish
    handles[0].invoke(heap, "insert", 3)
    core._pre_publish = pre

    def begin_attempt(slot, p):
        begin(slot, p)
        if p == 0:
            abandon()
    core._begin_attempt = begin_attempt
    handles[0].invoke(heap, "insert", 4)
    core._begin_attempt = begin
    handles[1].announce(heap, "insert", 5)
    handles[0].invoke(heap, "delete_min")
    handles[1].perform(heap)


@pytest.mark.parametrize("on", [True, False], ids=["on", "off"])
def test_attempt_spans_follow_tracing(tmp_path, on):
    import jax
    rt = CombiningRuntime(n_threads=2)
    heap = rt.make("heap", "pwfcomb", vector_apply=True, capacity=16)
    _scripted_heap_rounds(rt, heap)      # compile outside the trace
    before = heap.adapter.degree_stats(heap.core)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    if on:
        tracing.enable()
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        _scripted_heap_rounds(rt, heap)
    finally:
        jax.profiler.stop_trace()
        tracing.disable()
    after = heap.adapter.degree_stats(heap.core)
    found = _load(tmp_path)
    rt.close()
    if not on:
        assert not found
        return
    assert set(found) == set(ATTEMPT_SPANS)
    outcomes = defaultdict(int)
    for ids in found["combine.attempt"]:
        outcomes[ids["outcome"]] += 1
    delta = {k: after[k] - before[k] for k in after}
    assert dict(outcomes) == {"published": delta["rounds"],
                              "vl": delta["aborted_vl"],
                              "sc": delta["lost_sc"]}
    assert delta["aborted_vl"] == delta["lost_sc"] == 1
    assert len(found["combine.attempt"]) == delta["attempts"]
    assert all("adopted" in ids for ids in found["combine.scan"])


def test_degree_stats_carry_the_counters_of_every_instance():
    rt = CombiningRuntime(n_threads=2)
    heap = rt.make("heap", "pwfcomb", capacity=8)
    queue = rt.make("queue", "pwfcomb")
    bound = rt.attach(0).bind(heap)
    bound.insert(4)
    bound.delete_min()
    q = rt.attach(1).bind(queue)
    q.enqueue(1)
    q.dequeue()
    for obj, ops in ((heap, 2), (queue, 2)):
        stats = obj.adapter.degree_stats(obj.core)
        assert set(PWFComb.TRACE_COUNTERS) <= set(stats)
        assert stats["attempts"] == stats["rounds"] == ops
        assert stats["fallbacks"] == stats["wasted_ops"] == 0
    rt.close()


class _CommittedOrder:
    """The announcements every published attempt applied, in the order
    of the S versions they were published at."""

    def __init__(self, core):
        self.passes = {}
        begin, apply_batch, sc = (core._begin_attempt, core._apply_batch,
                                  core.S.sc)
        attempts = {}

        def _begin_attempt(slot, p):
            attempts[p] = []
            return begin(slot, p)

        def _apply_batch(batch, slot, p):
            attempts[p].append([(q, f, a) for q, f, a, _ in batch])
            return apply_batch(batch, slot, p)

        def _sc(version, slot):
            ok = sc(version, slot)
            if ok:                      # slot ids are owner * 2 + index
                self.passes[version] = attempts[slot // 2]
            return ok
        core._begin_attempt, core._apply_batch, core.S.sc = (
            _begin_attempt, _apply_batch, _sc)

    def entries(self):
        return [e for v in sorted(self.passes) for batch in self.passes[v]
                for e in batch]


def test_concurrent_kernel_attempts_match_a_sequential_heap():
    n, cap, pairs = 16, 64, 12
    rt = CombiningRuntime(n_threads=n)
    heap = rt.make("heap", "pwfcomb", vector_apply=True, capacity=cap)
    core = heap.core
    order = _CommittedOrder(core)
    calls0 = vector_rounds.kernel_calls()
    replies = [[] for _ in range(n)]
    start = threading.Barrier(n)

    def client(p):
        bound = rt.attach(p).bind(heap)
        start.wait()
        for i in range(pairs):
            replies[p].append(bound.insert((p * 7919 + i * 104729) % 1000))
            replies[p].append(bound.delete_min())
    threads = [threading.Thread(target=client, args=(p,)) for p in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not any(t.is_alive() for t in threads)
    # the sequential reference, replayed in the committed order
    ref, expect = [], [[] for _ in range(n)]
    for q, func, args in order.entries():
        if func == "HINSERT":
            full = len(ref) >= cap
            if not full:
                heapq.heappush(ref, args)
            expect[q].append(not full)
        else:
            expect[q].append(heapq.heappop(ref) if ref else None)
    assert replies == expect
    assert heap.snapshot() == sorted(ref)
    stats = _identities(core, 2 * n * pairs)
    assert stats["aborted_vl"] + stats["lost_sc"] > 0   # attempts raced
    assert vector_rounds.kernel_calls() > calls0        # through the kernels
    rt.close()


def test_kernel_calls_count_every_call_from_many_threads():
    n, rounds = 16, 40
    faa = (np.int64(0), np.arange(4, dtype=np.int64))
    vector_rounds._run("counter.FAA", *faa)      # compiled before the race
    saved = Counter(vector_rounds._CALLS)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(rounds):
            # a platform's first count is where an unlocked += races
            vector_rounds._CALLS.clear()
            start = threading.Barrier(n)

            def caller():
                start.wait()
                vector_rounds._run("counter.FAA", *faa)
            threads = [threading.Thread(target=caller) for _ in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert vector_rounds.kernel_calls() == n
    finally:
        sys.setswitchinterval(interval)
        vector_rounds._CALLS.update(saved)


def test_kernel_is_built_once_under_concurrent_first_calls():
    name, n = "counter.FAA", 8
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for _ in range(20):
            vector_rounds._KERNELS.pop(name, None)
            start = threading.Barrier(n)
            got = []

            def build():
                start.wait()
                got.append(vector_rounds.kernel(name))
            threads = [threading.Thread(target=build) for _ in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
            assert len(got) == n and all(f is got[0] for f in got)
    finally:
        sys.setswitchinterval(interval)
