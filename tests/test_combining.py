"""PBComb / PWFComb: linearizability under threads, detectable recovery
under exhaustive crash-point sweeps (paper Sections 3-4)."""

import random
import sys
import threading
import time

import pytest

try:                                   # optional dep: `pip install .[test]`
    from hypothesis import given, settings, strategies as st
except ImportError:                    # property tests skip below
    given = settings = st = None

from repro.core import (NVM, AtomicFloatObject, FetchAddObject, PBComb,
                        PWFComb, SimulatedCrash)
from repro.core.pbcomb import RequestRec

N = 6
OPS = 150


def _run_threads(obj, op):
    results = [[] for _ in range(N)]

    def worker(p):
        seq = 0
        rng = random.Random(p)
        for _ in range(OPS):
            seq += 1
            results[p].append(op(p, seq))
            for _ in range(rng.randint(0, 30)):   # paper's local work
                pass
    ts = [threading.Thread(target=worker, args=(p,)) for p in range(N)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    return results


@pytest.mark.parametrize("proto", [PBComb, PWFComb])
def test_faa_linearizable(proto):
    """k FAA(1) ops must return exactly {0..k-1} (each value once) and
    leave the counter at k — any interleaving violating atomicity breaks
    this."""
    nvm = NVM()
    c = proto(nvm, N, FetchAddObject())
    results = _run_threads(c, lambda p, seq: c.op(p, "FAA", 1, seq))
    flat = sorted(v for vs in results for v in vs)
    assert flat == list(range(N * OPS))


@pytest.mark.parametrize("proto", [PBComb, PWFComb])
def test_atomicfloat(proto):
    nvm = NVM()
    c = proto(nvm, N, AtomicFloatObject())
    _run_threads(c, lambda p, seq: c.op(p, "MUL", 1.0000001, seq))
    # state survived and is the product of all multiplications
    if proto is PBComb:
        final = nvm.read(c._st_base(c._mindex()))
    else:
        final = nvm.read(c._base(c.S.load()))
    assert abs(final - 1.0000001 ** (N * OPS)) < 1e-6


@pytest.mark.parametrize("proto", [PBComb, PWFComb])
def test_combining_persistence_cost(proto):
    """P1: persistence instructions per combining ROUND, not per request
    — with 1 thread issuing k ops, pwbs/op is a small constant; psyncs
    equal rounds."""
    nvm = NVM()
    c = proto(nvm, 2, FetchAddObject())
    for seq in range(1, 51):
        c.op(0, "FAA", 1, seq)
    assert nvm.counters["psync"] == 50            # one per round here
    assert nvm.counters["pwb"] <= 50 * 6


@pytest.mark.parametrize("proto", [PBComb, PWFComb])
@pytest.mark.parametrize("crash_at", range(8))
@pytest.mark.parametrize("drain_seed", [None, 1, 2, 3])
def test_detectable_recovery_crash_sweep(proto, crash_at, drain_seed):
    """Crash at every persistence instruction inside a combining round
    serving 4 requests; after recovery every request must have been
    applied EXACTLY once with the right response (detectability)."""
    nvm = NVM()
    c = proto(nvm, 4, FetchAddObject(), **(
        {} if proto is PBComb else {"backoff": False}))
    seqs = [0] * 4
    seqs[0] += 1
    assert c.op(0, "FAA", 1, seqs[0]) == 0
    for p in range(4):
        seqs[p] += 1
        c.request[p] = RequestRec("FAA", 1, 1 - c.request[p].activate, 1)
    rng = random.Random(drain_seed) if drain_seed else None
    nvm.arm_crash(crash_at, rng)
    try:
        c._perform_request(1)
    except SimulatedCrash:
        pass
    nvm.disarm_crash()
    c.reset_volatile()
    rets = {p: c.recover(p, "FAA", 1, seqs[p]) for p in range(4)}
    if proto is PBComb:
        final = nvm.read(c._st_base(c._mindex()))
    else:
        final = nvm.read(c._base(c.S.load()))
    assert final == 5                              # 1 + 4, exactly once each
    assert sorted(rets.values()) == [1, 2, 3, 4]


if st is not None:
    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 12), st.integers(0, 2 ** 31 - 1),
           st.integers(2, 5))
    def test_property_pbcomb_crash_anywhere(crash_at, seed, n_active):
        """Randomized crash points/drains: post-recovery state is always
        the initial value plus each announced request applied exactly
        once."""
        nvm = NVM()
        c = PBComb(nvm, n_active, FetchAddObject())
        seqs = [1] * n_active
        for p in range(n_active):
            c.request[p] = RequestRec("FAA", 1, 1, 1)
        nvm.arm_crash(crash_at, random.Random(seed))
        try:
            c._perform_request(0)
        except SimulatedCrash:
            pass
        nvm.disarm_crash()
        c.reset_volatile()
        rets = {p: c.recover(p, "FAA", 1, seqs[p]) for p in range(n_active)}
        final = nvm.read(c._st_base(c._mindex()))
        assert final == n_active
        assert sorted(rets.values()) == list(range(n_active))
else:
    def test_property_pbcomb_crash_anywhere():
        pytest.importorskip("hypothesis")


def test_pbcomb_combiner_crash_then_repeat_crash_in_recovery():
    """Recovery functions must themselves be re-invocable after a crash
    during recovery (paper Section 2)."""
    nvm = NVM()
    c = PBComb(nvm, 2, FetchAddObject())
    c.request[0] = RequestRec("FAA", 1, 1, 1)
    nvm.arm_crash(1, random.Random(7))
    try:
        c._perform_request(0)
    except SimulatedCrash:
        pass
    c.reset_volatile()
    # crash again during the recovery's re-execution
    nvm.arm_crash(2, random.Random(8))
    try:
        c.recover(0, "FAA", 1, 1)
    except SimulatedCrash:
        pass
    nvm.disarm_crash()
    c.reset_volatile()
    ret = c.recover(0, "FAA", 1, 1)
    assert ret == 0
    assert nvm.read(c._st_base(c._mindex())) == 1


# --------------------------------------------------------------------- #
# Blocking waiters under a full closed loop (no lost wake-up)           #
# --------------------------------------------------------------------- #
STRESS_THREADS = 64
STRESS_OPS = 200


def _commit_log(core):
    """The adoptions ``(client, func, args)`` of every committed round,
    in the order the combiner applied them: a pass is logged as it
    runs, a round at its unlock (after its psync, under the lock)."""
    rounds, passes = [], []
    apply_batch, pre_unlock = core._apply_batch, core._pre_unlock

    def logged_batch(batch, ind, p):
        passes.extend((q, f, a) for q, f, a, _act in batch)
        return apply_batch(batch, ind, p)

    def logged_unlock(ind, p):
        rounds.append(passes[:])
        passes.clear()
        return pre_unlock(ind, p)
    core._apply_batch, core._pre_unlock = logged_batch, logged_unlock
    return rounds


def _ref_counter():
    state = {"v": 0}

    def apply(func, delta):
        assert func == "FAA"
        old = state["v"]
        state["v"] = old + delta
        return old
    return apply, lambda: state["v"]


def _ref_heap():
    import heapq
    keys = []

    def apply(func, key):
        if func == "HINSERT":
            heapq.heappush(keys, key)
            return True
        assert func == "HDELETEMIN"
        return heapq.heappop(keys) if keys else None
    return apply, lambda: sorted(keys)


def _counter_client(b, p, i):
    d = 1 + (p * 7 + i) % 5
    return d, b.fetch_add(d)


def _heap_client(b, p, i):
    if i % 2:
        return None, b.delete_min()
    k = (p * 1009 + i * 31) % 4096
    return k, b.insert(k)


@pytest.mark.parametrize("kind", ["counter", "heap"])
def test_blocking_waiters_lose_no_wakeup(kind):
    """64 threads in a closed loop through the vector seam: every wait
    ends (a lost wake-up leaves a thread blocked and fails the join),
    each reply and the final state match a plain reference replaying
    the order the combiners committed, and every committed round made
    exactly one psync."""
    from repro.api import CombiningRuntime
    rt = CombiningRuntime(n_threads=STRESS_THREADS)
    extra = {"capacity": 2 * STRESS_THREADS} if kind == "heap" else {}
    obj = rt.make(kind, "pbcomb", vector_apply=True, **extra)
    rounds = _commit_log(obj.core)
    client = _counter_client if kind == "counter" else _heap_client
    replies = [[] for _ in range(STRESS_THREADS)]

    def run(p):
        b = rt.attach(p).bind(obj)
        for i in range(STRESS_OPS):
            replies[p].append(client(b, p, i))
    psyncs = rt.nvm.counters["psync"]
    ts = [threading.Thread(target=run, args=(p,), daemon=True)
          for p in range(STRESS_THREADS)]
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-3)          # more interleavings per round
    try:
        for t in ts:
            t.start()
        deadline = time.monotonic() + 120
        for t in ts:
            t.join(max(0.0, deadline - time.monotonic()))
    finally:
        sys.setswitchinterval(switch)
    assert not any(t.is_alive() for t in ts), "a waiter was never woken"

    stats = obj.adapter.degree_stats(obj.core)
    assert len(rounds) == stats["rounds"]
    assert rt.nvm.counters["psync"] - psyncs == stats["rounds"]
    apply, final = _ref_counter() if kind == "counter" else _ref_heap()
    got = [iter(r) for r in replies]
    n = 0
    for q, func, args in (e for r in rounds for e in r):
        sent, reply = next(got[q])
        assert args == sent
        want = apply(func, args)
        assert (type(reply), reply) == (type(want), want)
        n += 1
    assert n == STRESS_THREADS * STRESS_OPS
    assert all(next(g, None) is None for g in got)
    snap = obj.snapshot()
    assert (sorted(snap) if kind == "heap" else snap) == final()
    rt.close()
