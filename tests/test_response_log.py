"""The durable session log's RECORD rounds through the VectorApply seam
(DESIGN.md §11), against a plain last-write-wins table.

  * served path — staged combining rounds through bound handles, with
    sessions repeated inside one batch: replies and the snapshot equal
    an independent dict that applies the records in serving order;
  * kernel against per-op loop — the same schedule with the seam on and
    off gives identical replies, state and persistence counters;
  * crash inside a round — after ``rt.recover()`` every in-flight
    RECORD is in effect exactly once;
  * batch-sized — at 65,536 sessions every array the round sends to the
    device or fetches from it is as long as the batch, and only the
    words of the sessions the batch names are written.
"""

import random

import numpy as np
import pytest

from repro.api import CombiningRuntime
from repro.core import NVM, SimulatedCrash
from repro.core.objects import ResponseLogObject
from repro.kernels import vector_rounds

N = 4
SESSIONS = 256
ROUNDS = 12
PROTOCOLS = ["pbcomb", "pwfcomb"]


def _schedule(seed):
    """ROUNDS batches of N records ``(session, seq, response)``; every
    third batch names one session twice, so last-write-wins within a
    batch is exercised."""
    rng = random.Random(seed)
    rounds = []
    for r in range(ROUNDS):
        batch = [(rng.randrange(SESSIONS), rng.randrange(2 ** 33, 2 ** 62),
                  rng.randrange(-2 ** 62, 2 ** 62)) for _ in range(N)]
        if r % 3 == 0:
            s = batch[0][0]
            batch[2] = (s,) + batch[2][1:]
        rounds.append(batch)
    return rounds


def _drive(protocol, vector, rounds):
    """Staged rounds: handles 1..N-1 announce, handle 0 calls and serves
    the whole batch in client order.  Returns (replies, snapshot,
    persistence counters)."""
    nvm = NVM(1 << 16)
    rt = CombiningRuntime(nvm=nvm, n_threads=N)
    obj = rt.make("log", protocol, vector_apply=vector, n_clients=SESSIONS)
    handles = [rt.attach(p) for p in range(N)]
    bound0 = handles[0].bind(obj)
    replies = []
    for batch in rounds:
        for p in range(1, N):
            handles[p].announce(obj, "record", batch[p])
        replies.append(bound0.record(batch[0]))
        replies.extend(handles[p].perform(obj) for p in range(1, N))
    return replies, obj.snapshot(), dict(nvm.counters)


def _lww(records, table=None):
    """Plain last-write-wins table over SESSIONS, as the snapshot reads."""
    table = dict(table or {})
    for session, seq, response in records:
        table[session] = (seq, response)
    return [table.get(c, (0, None)) for c in range(SESSIONS)]


@pytest.mark.parametrize("protocol", PROTOCOLS)
@pytest.mark.parametrize("seed", [3, 2 ** 33 + 7])
def test_served_records_match_last_write_wins(protocol, seed):
    rounds = _schedule(seed)
    before = vector_rounds.kernel_calls()
    replies, snap, _ = _drive(protocol, True, rounds)
    assert vector_rounds.kernel_calls() - before >= ROUNDS
    flat = [t for batch in rounds for t in batch]
    assert [(type(r), r) for r in replies] == [(int, t[2]) for t in flat]
    assert snap == _lww(flat)


@pytest.mark.parametrize("protocol", PROTOCOLS)
def test_kernel_rounds_equal_the_per_op_loop(protocol):
    rounds = _schedule(11)
    v_replies, v_snap, v_counters = _drive(protocol, True, rounds)
    e_replies, e_snap, e_counters = _drive(protocol, False, rounds)
    assert [(type(r), r) for r in v_replies] == \
        [(type(r), r) for r in e_replies]
    assert v_snap == e_snap
    assert v_counters == e_counters


@pytest.mark.parametrize("crash_at", [0, 1, 2, 4, 6])
def test_crash_inside_a_record_round_applies_each_once(crash_at):
    """Every client announces a RECORD, two of them for one session; a
    crash lands inside the round that serves them.  Recovery replays
    the in-flight records in client order, so the table ends as the
    rounds before it plus this batch, last client winning."""
    rt = CombiningRuntime(n_threads=N)
    obj = rt.make("log", "pbcomb", vector_apply=True, n_clients=SESSIONS)
    handles = [rt.attach(p) for p in range(N)]
    earlier = _schedule(5)[0]
    b0 = handles[0].bind(obj)
    for rec in earlier:
        b0.record(rec)
    batch = [(9, 2 ** 40, -5), (17, 2 ** 41, 6), (9, 2 ** 42, 7),
             (earlier[1][0], 2 ** 43, 8)]
    for p in range(N):
        handles[p].announce(obj, "record", batch[p])
    rt.arm_crash(crash_at, random.Random(29))
    replies = {}
    try:
        replies[0] = handles[0].perform(obj)
    except SimulatedCrash:
        pass
    for (name, p), reply in rt.recover().items():
        if name == obj.name:
            replies[p] = reply
    assert replies == {p: batch[p][2] for p in range(N)}
    assert obj.snapshot() == _lww(earlier + batch)
    # the log keeps serving through the kernel after recovery
    before = vector_rounds.kernel_calls()
    assert b0.record((9, 2 ** 44, 10)) == 10
    assert b0.lookup(9) == (2 ** 44, 10)
    assert vector_rounds.kernel_calls() > before


def test_round_at_65536_sessions_is_sized_by_the_batch(monkeypatch):
    n_clients = 65_536
    log = ResponseLogObject(n_clients)
    nvm = NVM(log.state_words + 64)
    base = nvm.alloc(log.state_words)
    log.init_state(nvm, base)
    batch = [(65_535, 2 ** 40, 1), (7, 2 ** 41, 2), (65_535, 2 ** 42, 3),
             (0, 2 ** 43, 4), (7, 2 ** 44, 5)]
    shapes = []
    run = vector_rounds._run

    def recording(name, *args):
        out = run(name, *args)
        shapes.extend(np.shape(a) for a in args + out)
        return out

    monkeypatch.setattr(vector_rounds, "_run", recording)
    writes = []
    write = nvm.write

    def counted(addr, value):
        writes.append(addr - base)
        write(addr, value)

    monkeypatch.setattr(nvm, "write", counted)
    assert log.vector_apply(nvm, base, "RECORD", batch) == [1, 2, 3, 4, 5]
    assert shapes and set(shapes) == {(len(batch),)}
    # response before seq, once per session, for the batch's last entry
    # of each session (entries 2, 3 and 4), in batch order
    assert writes == [2 * 65_535 + 1, 2 * 65_535, 1, 0, 2 * 7 + 1, 2 * 7]
    snap = log.snapshot(nvm, base)
    assert snap[65_535] == (2 ** 42, 3)
    assert snap[7] == (2 ** 44, 5) and snap[0] == (2 ** 43, 4)
    assert snap[1] == (0, None)


def test_out_of_range_session_declines():
    nvm = NVM(1 << 10)
    log = ResponseLogObject(8)
    base = nvm.alloc(log.state_words)
    log.init_state(nvm, base)
    for bad in (8, -1):
        assert log.vector_apply(nvm, base, "RECORD",
                                [(0, 1, 2), (bad, 1, 2)]) is None
    assert log.snapshot(nvm, base) == [(0, None)] * 8
