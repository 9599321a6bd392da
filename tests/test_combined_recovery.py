"""Recovery after a crash replays the in-flight requests of a PBComb
instance in one combining round (DESIGN.md §3, combined recovery).

Algorithm 1's Recover runs for every thread with an op in flight: line 3
(the re-announcement) for all of them first, then lines 4-6 for each in
turn.  The first unserved thread's round adopts every re-announced
request, so a crash cycle costs one round and one psync per PBComb
instance that holds unserved records, where a replay one record at a
time cost one of each per record.  PWFComb and the baselines keep that
one-at-a-time replay.

Each case is checked against a plain reference applied in thread-id
order, the order the combiner's scan serves the batch in.
"""

import heapq
import random
from collections import deque

import pytest

from repro.api import CombiningRuntime
from repro.core import SimulatedCrash

N = 8


# ------------------------------------------------------------------ #
# plain references, with the adapters' snapshot formats               #
# ------------------------------------------------------------------ #
class _Counter:
    def __init__(self):
        self.v = 0

    def apply(self, op, arg):
        old, self.v = self.v, self.v + arg
        return old

    def snapshot(self):
        return self.v


class _Heap:
    def __init__(self):
        self.h = []

    def apply(self, op, arg):
        if op == "insert":
            heapq.heappush(self.h, arg)
            return True
        return heapq.heappop(self.h) if self.h else None

    def snapshot(self):
        return sorted(self.h)


class _Log:
    def __init__(self):
        self.rows = [(0, None)] * N

    def apply(self, op, arg):
        client, seq, resp = arg
        self.rows[client] = (seq, resp)
        return resp

    def snapshot(self):
        return list(self.rows)


class _Ckpt:
    def __init__(self):
        self.step, self.payload = 0, None

    def apply(self, op, arg):
        step, payload = arg
        if step > self.step:
            self.step, self.payload = step, payload
            return step
        return self.step

    def snapshot(self):
        return {"step": self.step, "payload": self.payload}


class _Queue:
    def __init__(self):
        self.q = deque()

    def apply(self, op, arg):
        if op == "enqueue":
            self.q.append(arg)
            return "ACK"
        return self.q.popleft() if self.q else None

    def snapshot(self):
        return list(self.q)


class _Stack:
    def __init__(self):
        self.s = []

    def apply(self, op, arg):
        if op == "push":
            self.s.append(arg)
            return "ACK"
        return self.s.pop() if self.s else None

    def snapshot(self):
        return self.s[::-1]                  # top first


REFS = {"counter": _Counter, "heap": _Heap, "log": _Log, "ckpt": _Ckpt,
        "queue": _Queue, "stack": _Stack}

_CKPT_STEPS = [3, 1, 5, 2, 8, 4, 7, 6]

#: case -> (kind, preload, one announced (op, arg) per thread, the
#: PBComb instances those requests use, kernel on the device path)
CASES = {
    "counter": ("counter", [("fetch_add", 5)],
                [("fetch_add", p + 1) for p in range(N)], 1, True),
    "heap-insert": ("heap", [("insert", 50), ("insert", 20)],
                    [("insert", (p * 7) % 13) for p in range(N)], 1, True),
    "heap-mixed": ("heap", [("insert", 50), ("insert", 20), ("insert", 40)],
                   [("insert", p * 3) if p % 2 else ("delete_min", None)
                    for p in range(N)], 1, True),
    # sessions 0 and 1 are named twice: the later thread's write wins
    "log": ("log", [("record", (7, 1, 77))],
            [("record", (p % 6, p + 1, 1000 + p)) for p in range(N)], 1,
            True),
    "ckpt": ("ckpt", [("persist", (2, 20))],
             [("persist", (_CKPT_STEPS[p], 100 + p)) for p in range(N)], 1,
             True),
    # two instances: the enqueue round, then the dequeue round
    "queue": ("queue", [("enqueue", 1), ("enqueue", 2)],
              [("enqueue", 10 + p) if p < N // 2 else ("dequeue", None)
               for p in range(N)], 2, False),
    "stack-push": ("stack", [("push", 1)],
                   [("push", 10 + p) for p in range(N)], 1, False),
    "stack-pop": ("stack", [("push", v) for v in range(10)],
                  [("pop", None) for _ in range(N)], 1, False),
}

VECTOR_CASES = [(c, v) for c, spec in CASES.items()
                for v in ((False, True) if spec[4] else (False,))]

#: kinds whose Recover re-announces the seq's parity (not a fresh op)
PARITY = ["counter", "heap-insert", "queue", "stack-push"]


def _a(arg):
    return () if arg is None else (arg,)


def _setup(case, vector=False, n=N):
    kind, preload, ops, _inst, _kernel = CASES[case]
    rt = CombiningRuntime(n_threads=n)
    obj = rt.make(kind, "pbcomb", **({"vector_apply": True} if vector
                                     else {}))
    ref = REFS[kind]()
    hs = [rt.attach(p) for p in range(n)]
    for op, arg in preload:
        assert hs[0].invoke(obj, op, *_a(arg)) == ref.apply(op, arg)
    return rt, obj, ref, hs


def _counts(rt, obj):
    """(recovery_rounds, recovered_ops, psync) so far."""
    stats = obj.adapter.degree_stats(obj.core)
    return (stats["recovery_rounds"], stats["recovered_ops"],
            rt.nvm.counters["psync"])


def _crash_in_round(rt, h, obj):
    """A crash inside the round ``h`` combines; nothing drains, so the
    round never commits."""
    rt.arm_crash(1)
    with pytest.raises(SimulatedCrash):
        h.perform(obj)


# ------------------------------------------------------------------ #
# PBComb: one round per instance                                      #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("case,vector", VECTOR_CASES)
def test_crash_cycle_recovers_in_one_round_per_instance(case, vector):
    """8 announced requests, a crash inside their round, one
    ``recover()``: one committed round and one psync per instance that
    holds unserved records, every record served by them, and the
    replies and state of a reference applied in thread-id order."""
    rt, obj, ref, hs = _setup(case, vector)
    ops, n_inst = CASES[case][2], CASES[case][3]
    for p, (op, arg) in enumerate(ops):
        hs[p].announce(obj, op, *_a(arg))
    _crash_in_round(rt, hs[0], obj)
    rounds0, ops0, psync0 = _counts(rt, obj)
    replies = rt.recover()
    rounds1, ops1, psync1 = _counts(rt, obj)
    assert rounds1 - rounds0 == n_inst
    assert ops1 - ops0 == N
    assert psync1 - psync0 == n_inst
    assert ([replies[(obj.name, p)] for p in range(N)]
            == [ref.apply(op, arg) for op, arg in ops])
    assert obj.snapshot() == ref.snapshot()


@pytest.mark.parametrize("case", ["counter", "heap-insert", "log"])
def test_crash_cycle_of_64_is_one_round_and_one_psync(case):
    """The benchmark's crash cycle: 64 clients announce, the round that
    serves them crashes, and recovery commits exactly one round of 64
    with one psync (one record at a time it was 64 rounds and 64
    psyncs)."""
    kind, _pre, _ops, _inst, _kernel = CASES[case]
    rt, obj, ref, hs = _setup(case, n=64)
    ops = {"counter": lambda p: ("fetch_add", p + 1),
           "heap": lambda p: ("insert", (p * 37) % 101),
           "log": lambda p: ("record", (p % 8, p + 1, p))}[kind]
    ops = [ops(p) for p in range(64)]
    ref.rows = [(0, None)] * 64 if kind == "log" else getattr(ref, "rows",
                                                              None)
    for p, (op, arg) in enumerate(ops):
        hs[p].announce(obj, op, arg)
    _crash_in_round(rt, hs[0], obj)
    rounds0, ops0, psync0 = _counts(rt, obj)
    stats0 = obj.adapter.degree_stats(obj.core)
    replies = rt.recover()
    rounds1, ops1, psync1 = _counts(rt, obj)
    assert (rounds1 - rounds0, ops1 - ops0, psync1 - psync0) == (1, 64, 1)
    assert obj.adapter.degree_stats(obj.core)["rounds"] - \
        stats0["rounds"] == 1
    assert ([replies[(obj.name, p)] for p in range(64)]
            == [ref.apply(op, arg) for op, arg in ops])


@pytest.mark.parametrize("case", PARITY)
def test_record_served_before_the_crash_is_not_reapplied(case):
    """Threads 1-3 were served by a committed round before the crash
    but never took their replies, so they are still in flight.  Their
    re-announced parity equals their deactivate bit: recovery reads
    their replies and applies nothing for them; threads 4-7's requests
    take the one recovery round."""
    rt, obj, ref, hs = _setup(case)
    ops = CASES[case][2]
    for p in range(N // 2):
        hs[p].announce(obj, ops[p][0], *_a(ops[p][1]))
    got = {0: hs[0].perform(obj)}            # serves threads 0-3
    for p in range(N // 2, N):
        hs[p].announce(obj, ops[p][0], *_a(ops[p][1]))
    _crash_in_round(rt, hs[N // 2], obj)
    rounds0, ops0, psync0 = _counts(rt, obj)
    replies = rt.recover()
    rounds1, ops1, psync1 = _counts(rt, obj)
    assert (rounds1 - rounds0, ops1 - ops0, psync1 - psync0) == \
        (1, N // 2, 1)
    assert sorted(replies) == [(obj.name, p) for p in range(1, N)]
    got.update((p, replies[(obj.name, p)]) for p in range(1, N))
    assert [got[p] for p in range(N)] == [ref.apply(op, arg)
                                          for op, arg in ops]
    assert obj.snapshot() == ref.snapshot()


@pytest.mark.parametrize("case", list(CASES))
@pytest.mark.parametrize("crash_at,seed", [(0, None), (1, None),
                                           (1, 3), (3, 5), (3, 11)])
def test_crash_inside_the_recovery_round_then_recover_again(case, crash_at,
                                                            seed):
    """A second crash lands inside the combined recovery round (with a
    late crash point and a draining rng, the round may have become
    durable); the next ``recover()`` still applies every op exactly
    once.  ``CombiningRuntime.recover`` disarms an armed crash, so the
    first recovery runs by hand."""
    rt, obj, ref, hs = _setup(case)
    ops = CASES[case][2]
    seqs = [hs[p].announce(obj, op, *_a(arg))
            for p, (op, arg) in enumerate(ops)]
    _crash_in_round(rt, hs[0], obj)
    obj.adapter.reset_volatile(obj.core)
    rt.arm_crash(crash_at, None if seed is None else random.Random(seed))
    with pytest.raises(SimulatedCrash):
        obj.adapter.recover_many(obj.core, [
            (p, op, arg, seqs[p]) for p, (op, arg) in enumerate(ops)])
    replies = rt.recover()
    want = [ref.apply(op, arg) for op, arg in ops]
    got = [replies[(obj.name, p)] for p in range(N)]
    if CASES[case][0] == "ckpt":
        # exactly once in effect: a re-executed persist of an older
        # step answers with the newer step a durable round left
        assert all(w <= g <= max(_CKPT_STEPS) for g, w in zip(got, want))
    else:
        assert got == want
    assert obj.snapshot() == ref.snapshot()


# ------------------------------------------------------------------ #
# PWFComb and the baselines: one record at a time                     #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("kind", ["counter", "heap"])
def test_pwfcomb_recovers_one_round_per_record(kind):
    """PWFComb keeps the per-record replay: each of the 8 unserved
    records takes a round and a psync of its own, in record order."""
    rt = CombiningRuntime(n_threads=N)
    obj = rt.make(kind, "pwfcomb")
    ref = REFS[kind]()
    hs = [rt.attach(p) for p in range(N)]
    ops = CASES["counter" if kind == "counter" else "heap-insert"][2]
    for p, (op, arg) in enumerate(ops):
        hs[p].announce(obj, op, arg)
    _crash_in_round(rt, hs[0], obj)
    rounds0 = obj.adapter.degree_stats(obj.core)["rounds"]
    psync0 = rt.nvm.counters["psync"]
    replies = rt.recover()
    stats = obj.adapter.degree_stats(obj.core)
    assert stats["rounds"] - rounds0 == N
    assert rt.nvm.counters["psync"] - psync0 == N
    assert "recovery_rounds" not in stats
    assert ([replies[(obj.name, p)] for p in range(N)]
            == [ref.apply(op, arg) for op, arg in ops])
    assert obj.snapshot() == ref.snapshot()


@pytest.mark.parametrize("protocol", ["lock-direct", "lock-undo"])
def test_lock_baseline_recovery_reexecutes_each_record(protocol):
    """A lock baseline is not detectable: recovery re-executes every
    in-flight record once, in record order, as before."""
    rt = CombiningRuntime(n_threads=N)
    obj = rt.make("counter", protocol)
    ref = _Counter()
    assert rt.attach(0).invoke(obj, "fetch_add", 5) == ref.apply(None, 5)
    rt.crash()
    inflight = [(obj.name, p, "fetch_add", 10 * p + 1, 1)
                for p in (3, 1, 2)]
    replies = rt.recover(inflight=inflight)
    assert [replies[(obj.name, p)] for _n, p, _o, _a, _s in inflight] == \
        [ref.apply(None, a) for _n, _p, _o, a, _s in inflight]
    assert obj.snapshot() == ref.snapshot()
