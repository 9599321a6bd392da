"""PBComb's StateRec copy and persist by lines (DESIGN.md §3).

Where the sequential object names its writes (``touch_plan``: the
response log, the checkpoint cell, the bounded rings), a round copies
into StateRec[ind], and persists from it, only the lines the last round
wrote and the lines it writes.  Pinned here:

  * after every copy both StateRecs are equal, and after every commit
    both durable images equal their volatile ones, over random rounds
    with the vector path on and off, duplicate sessions in a batch,
    and empty rounds;
  * the persisted set is exact: the audit finds no unflushed line and
    no redundant pwb;
  * a crash at every persistence instruction of a round recovers the
    state and replies of a plain sequential replay;
  * the first round after a crash copies and persists whole;
  * a 65,536-session log round persists at most 144 + 1 lines;
  * the heap, counter and float keep the paper's whole copy and
    persist, and the shm backend keeps it for every object.
"""

import random
from collections import deque

import pytest

from repro.core import (LINE, NVM, AtomicFloatObject, FetchAddObject,
                        HeapObject, PBComb, SimulatedCrash)
from repro.core.objects import (CheckpointObject, ResponseLogObject,
                                SeqQueueObject, SeqStackObject)
from repro.core.pbcomb import RequestRec

N = 6
SESSIONS = 64
RING = 32
KINDS = ("log", "ckpt", "queue", "stack")


# --------------------------------------------------------------------- #
# plain sequential references                                           #
# --------------------------------------------------------------------- #
class RefLog:
    def __init__(self):
        self.rows = [(0, None)] * SESSIONS

    def apply(self, func, args):
        client, seq, resp = args
        self.rows[client] = (seq, resp)
        return resp

    def snapshot(self):
        return list(self.rows)


class RefCkpt:
    def __init__(self):
        self.step, self.payload = 0, None

    def apply(self, func, args):
        step, payload = args
        if step > self.step:
            self.step, self.payload = step, payload
        return self.step

    def snapshot(self):
        return {"step": self.step, "payload": self.payload}


class RefQueue:
    def __init__(self):
        self.items = deque()

    def apply(self, func, args):
        if func == "ENQ":
            if len(self.items) >= RING:
                return False
            self.items.append(args)
            return "ACK"
        return self.items.popleft() if self.items else None

    def snapshot(self):
        return list(self.items)


class RefStack:
    def __init__(self):
        self.items = []

    def apply(self, func, args):
        if func == "PUSH":
            if len(self.items) >= RING:
                return False
            self.items.append(args)
            return "ACK"
        return self.items.pop() if self.items else None

    def snapshot(self):
        return self.items[::-1]


MAKE = {"log": (lambda: ResponseLogObject(SESSIONS), RefLog),
        "ckpt": (CheckpointObject, RefCkpt),
        "queue": (lambda: SeqQueueObject(RING), RefQueue),
        "stack": (lambda: SeqStackObject(RING), RefStack)}
FUNCS = {"log": ("RECORD",), "ckpt": ("CKPT",), "queue": ("ENQ", "DEQ"),
         "stack": ("PUSH", "POP")}


def _round_ops(kind, rng, threads, fill=0.5):
    """One announcement per thread: int payloads so the kernels pack
    them; one func for the whole round most of the time, so the vector
    path serves it; log sessions named twice in a batch often.  A ring
    enqueues or pushes with probability ``fill``."""
    funcs = FUNCS[kind]

    def draw():
        return funcs[0] if rng.random() < fill else funcs[-1]
    one = draw() if rng.random() < 0.7 else None
    ops, sessions = {}, []
    for p in threads:
        func = one or draw()
        if kind == "log":
            s = (rng.choice(sessions) if sessions and rng.random() < 0.3
                 else rng.randrange(SESSIONS))
            sessions.append(s)
            args = (s, rng.randrange(1, 1 << 40), rng.randrange(-9999, 9999))
        elif kind == "ckpt":
            args = (rng.randrange(40), rng.randrange(1000))
        else:
            args = rng.randrange(1000) if func in ("ENQ", "PUSH") else None
        ops[p] = (func, args)
    return ops


# --------------------------------------------------------------------- #
# a core with its invariants checked at every copy and commit           #
# --------------------------------------------------------------------- #
def _records(nvm, c):
    return [nvm.read_range(b, c.rec_words) for b in c.mem_base]


def _durable(nvm, c):
    return [[nvm.durable_read(a) for a in range(b, b + c.rec_words)]
            for b in c.mem_base]


def _checked_core(kind, vector, audit=False):
    nvm = NVM(1 << 16, audit=audit)
    obj = MAKE[kind][0]()
    c = PBComb(nvm, N, obj, park=False, vector_apply=vector)
    seen = {"copies": 0, "commits": 0}
    begin, pre_unlock = c._begin_round, c._pre_unlock

    def checked_begin(ind, p):
        a, b = _records(nvm, c)
        assert a == b, "StateRecs differ after the copy"
        seen["copies"] += 1
        return begin(ind, p)

    def checked_unlock(ind, p):
        assert _durable(nvm, c) == _records(nvm, c), \
            "durable image differs from the volatile one after the commit"
        seen["commits"] += 1
        return pre_unlock(ind, p)
    c._begin_round, c._pre_unlock = checked_begin, checked_unlock
    return nvm, c, seen


def _announce(c, p, func, args):
    act = 1 - c.request[p].activate
    c.request[p] = RequestRec(func, args, act, 1)
    return act


def _serve(c, ref, ops, combiner):
    """Announce ``ops``, let ``combiner`` serve them in one round, then
    let every other served thread collect its reply the way a handle's
    ``perform`` does (each of those runs an empty round)."""
    for p, (func, args) in ops.items():
        _announce(c, p, func, args)
    want = {p: ref.apply(*ops[p]) for p in sorted(ops)}   # scan order
    got = {combiner: c._perform_request(combiner)}
    for p in sorted(ops):
        if p != combiner:
            got[p] = c._perform_request(p)
    assert got == want


@pytest.mark.parametrize("audit", [False, True], ids=["fused", "audited"])
@pytest.mark.parametrize("vector", [False, True], ids=["per-op", "vector"])
@pytest.mark.parametrize("kind", KINDS)
def test_rounds_by_lines_match_a_sequential_replay(kind, vector, audit):
    nvm, c, seen = _checked_core(kind, vector, audit)
    assert c._by_lines
    ref = MAKE[kind][1]()
    rng = random.Random(f"{kind}/{vector}")
    for r in range(40):
        threads = rng.sample(range(N), rng.randint(1, N))
        # rings: filled past full, then drained past empty
        ops = _round_ops(kind, rng, threads, 0.85 if r < 20 else 0.15)
        _serve(c, ref, ops, rng.choice(threads))
    assert c.obj.snapshot(nvm, c._st_base(c._mindex())) == ref.snapshot()
    assert seen["copies"] == seen["commits"] > 40
    if audit:
        assert nvm.audit.redundant_pwbs == 0
        assert [f for f in nvm.audit.findings if f.gating] == []


def _drive_counts(kind, vector):
    nvm = NVM(1 << 16)
    c = PBComb(nvm, N, MAKE[kind][0](), park=False, vector_apply=vector)
    ref = MAKE[kind][1]()
    rng = random.Random(f"paths/{kind}")
    for _ in range(30):
        threads = rng.sample(range(N), rng.randint(1, N))
        _serve(c, ref, _round_ops(kind, rng, threads), threads[0])
    return dict(nvm.counters), c.copied_words


@pytest.mark.parametrize("kind", KINDS)
def test_vector_and_per_op_rounds_persist_the_same_lines(kind):
    assert _drive_counts(kind, True) == _drive_counts(kind, False)


@pytest.mark.parametrize("drain", [None, 1, 2])
@pytest.mark.parametrize("crash_at", range(5))
@pytest.mark.parametrize("vector", [False, True], ids=["per-op", "vector"])
@pytest.mark.parametrize("kind", KINDS)
def test_crash_at_every_persistence_instruction(kind, vector, crash_at,
                                                drain):
    """Rounds by lines, then a round of every thread with a crash at
    its 1st..4th persistence instruction (or none, at 4), recovery, and
    more rounds: the state and every reply are a plain sequential
    replay's, each request applied exactly once."""
    nvm, c, seen = _checked_core(kind, vector)
    ref = MAKE[kind][1]()
    rng = random.Random(f"crash/{kind}/{crash_at}/{drain}")
    for _ in range(6):
        threads = rng.sample(range(N), rng.randint(1, N))
        _serve(c, ref, _round_ops(kind, rng, threads), threads[0])
    ops = _round_ops(kind, rng, range(N))
    seqs = {p: _announce(c, p, *ops[p]) for p in range(N)}
    want = {p: ref.apply(*ops[p]) for p in range(N)}
    nvm.arm_crash(crash_at, random.Random(drain) if drain else None)
    try:
        c._perform_request(0)
    except SimulatedCrash:
        pass
    nvm.disarm_crash()
    c.reset_volatile()
    got = {p: c.recover(p, *ops[p], seqs[p]) for p in range(N)}
    assert got == want
    assert c.obj.snapshot(nvm, c._st_base(c._mindex())) == ref.snapshot()
    for _ in range(6):
        threads = rng.sample(range(N), rng.randint(1, N))
        _serve(c, ref, _round_ops(kind, rng, threads), threads[0])
    assert c.obj.snapshot(nvm, c._st_base(c._mindex())) == ref.snapshot()


def _whole_lines(c):
    base = c.mem_base[0]
    return (base + c.rec_words - 1) // LINE - base // LINE + 1


@pytest.mark.parametrize("kind", KINDS)
def test_first_round_after_recover_copies_and_persists_whole(kind):
    nvm, c, _seen = _checked_core(kind, vector=False)
    ref = MAKE[kind][1]()
    rng = random.Random(f"whole/{kind}")

    def one_round():
        pwb, copied = nvm.counters["pwb"], c.copied_words
        _serve(c, ref, _round_ops(kind, rng, [0]), 0)
        return nvm.counters["pwb"] - pwb, c.copied_words - copied

    for _ in range(3):
        one_round()
    nvm.crash(random.Random(5))
    c.reset_volatile()
    assert one_round() == (_whole_lines(c) + 1, c.rec_words)
    pwb, copied = one_round()
    if kind != "ckpt":   # ckpt's 14 words: both lines written every round
        assert pwb - 1 < _whole_lines(c) and copied < c.rec_words


def test_session_log_round_at_65536_sessions_persists_few_lines():
    """64 threads recording at random sessions of 65,536: a round
    persists at most its own and the last round's 64 session lines,
    the 16 lines of replies and deactivate bits, and MIndex."""
    n, sessions = 64, 65536
    nvm = NVM(1 << 19)
    c = PBComb(nvm, n, ResponseLogObject(sessions), park=False)
    rng = random.Random(11)
    for r in range(4):
        for p in range(n):
            _announce(c, p, "RECORD",
                      (rng.randrange(sessions), r + 1, rng.randrange(99)))
        pwb, copied = nvm.counters["pwb"], c.copied_words
        c._perform_request(0)
        assert 0 < nvm.counters["pwb"] - pwb <= 144 + 1
        assert c.copied_words - copied <= (64 + 16) * LINE
        for p in range(1, n):          # collect: each an empty round
            c._perform_request(p)
    # each round's lines are copied twice: by the next round and by the
    # first collecting round after it (the rest copy nothing)
    assert c.copied_words <= 4 * 2 * 80 * LINE


def _paper_round(nvm, c, func, args, d):
    before = dict(nvm.counters)
    copied = c.copied_words
    for p in range(1, d):
        _announce(c, p, func, args)
    _announce(c, 0, func, args)
    c._perform_request(0)
    delta = {k: nvm.counters[k] - before[k] for k in before}
    return delta, c.copied_words - copied


@pytest.mark.parametrize("vector", [False, True], ids=["per-op", "vector"])
@pytest.mark.parametrize("obj,func,args", [
    (HeapObject(64), "HINSERT", 5), (FetchAddObject(), "FAA", 1),
    (AtomicFloatObject(), "MUL", 2.0)], ids=["heap", "counter", "float"])
def test_objects_without_a_plan_keep_the_papers_whole_round(obj, func, args,
                                                            vector):
    """Algorithm 2 as written: every round copies the whole StateRec
    and writes it back whole, pwb = its lines + MIndex's, one pfence,
    one psync."""
    nvm = NVM(1 << 16)
    c = PBComb(nvm, 4, obj, park=False, vector_apply=vector)
    assert not c._by_lines
    for _ in range(3):
        delta, copied = _paper_round(nvm, c, func, args, 4)
        assert delta == {"pwb": _whole_lines(c) + 1, "pfence": 1,
                         "psync": 1, "crashes": 0}
        assert copied == c.rec_words


def test_shm_keeps_the_whole_round():
    """Forked workers each hold their own copy of the core, so no
    combiner can know what another process's round wrote: on the shm
    backend every round copies and persists whole."""
    from repro.core.shm import ShmNVM
    nvm = ShmNVM(1 << 16)
    try:
        c = PBComb(nvm, 4, ResponseLogObject(16), park=False)
        assert not c._by_lines
        for r in range(3):
            delta, copied = _paper_round(nvm, c, "RECORD", (3, r + 1, 7), 4)
            assert delta["pwb"] == _whole_lines(c) + 1
            assert copied == c.rec_words
    finally:
        nvm.close()
