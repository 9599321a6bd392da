"""VectorApply seam (DESIGN.md §11): vectorized combining rounds are an
EXACT drop-in for the per-op simulation loop.

The contract under test is exactness-or-decline:

  * equivalence — for every array-valued registry cell that accepts
    ``vector_apply=True`` (counter/heap/log/ckpt x pbcomb/pwfcomb), the
    same staged-announcement workload produces identical responses
    (values AND types), identical structure snapshots, and identical
    NVM persistence counters with the seam on and off.  The vector
    path runs through volatile ``read_range``/``write_range`` only, so
    the modeled trajectory cannot move — the counter equality pins it;
  * engagement — the jitted kernels actually run on the vector side
    (``vector_rounds.kernel_calls()`` advances), so the equivalence is
    not vacuously tested against a permanently-declining seam;
  * decline — heterogeneous rounds, non-int payloads, bignums and other
    unpackable arguments fall back to the per-op loop rather than
    approximate (the kernel packing guards);
  * durability — a crash landing inside a vectorized round replays
    every announced request exactly once, same as the eager rounds
    (the in-flight idiom of tests/test_api_matrix.py).
"""

import random

import pytest

from repro.api import CombiningRuntime, get_adapter
from repro.core import NVM, SimulatedCrash
from repro.core.objects import (AtomicFloatObject, FetchAddObject,
                                HeapObject, ResponseLogObject)
from repro.kernels import vector_rounds

N = 4
ROUNDS = 6

#: Registry cells whose adapters accept ``vector_apply=`` (array-valued
#: structures under a combining protocol).
VECTOR_CELLS = [(k, p) for k in ("counter", "heap", "log", "ckpt")
                for p in ("pbcomb", "pwfcomb")]

#: Per-kind homogeneous round schedule (one op, every thread announces
#: it) with int-only payloads so the kernels can pack them.
_SCHED = {
    "counter": [("fetch_add", lambda p, r: 1)],
    "heap": [("insert", lambda p, r: (p * 31 + r) % 997),
             ("delete_min", None)],
    "log": [("record", lambda p, r: (p, r + 1, p * 1000 + r))],
    "ckpt": [("persist", lambda p, r: (r + 1, r))],
}


def _drive(kind, protocol, vector):
    """Run ROUNDS staged homogeneous combining rounds; every logical
    thread announces, thread 0 performs (serving the whole batch).
    Returns (responses, snapshot, persistence counters)."""
    nvm = NVM(1 << 20)
    rt = CombiningRuntime(nvm=nvm, n_threads=N)
    obj = rt.make(kind, protocol, vector_apply=vector)
    handles = [rt.attach(p) for p in range(N)]
    bound0 = handles[0].bind(obj)
    rets = []
    for r in range(ROUNDS):
        for op, argfn in _SCHED[kind]:
            for p in range(1, N):
                if argfn is None:
                    handles[p].announce(obj, op)
                else:
                    handles[p].announce(obj, op, argfn(p, r))
            fn = getattr(bound0, op)
            rets.append(fn(*(() if argfn is None else (argfn(0, r),))))
            for p in range(1, N):
                rets.append(handles[p].perform(obj))
    return rets, obj.snapshot(), dict(nvm.counters)


def _typed(values):
    """Pair every response with its concrete type: the seam must not
    swap an int for a numpy scalar (or a bool for an int)."""
    return [(type(v).__name__, v) for v in values]


@pytest.mark.parametrize("kind,protocol", VECTOR_CELLS)
def test_vector_equals_eager(kind, protocol):
    before = vector_rounds.kernel_calls()
    v_rets, v_snap, v_counters = _drive(kind, protocol, vector=True)
    engaged = vector_rounds.kernel_calls() - before
    e_rets, e_snap, e_counters = _drive(kind, protocol, vector=False)
    assert _typed(v_rets) == _typed(e_rets)
    assert v_snap == e_snap
    assert v_counters == e_counters          # modeled trajectory pinned
    # every round is homogeneous and int-valued: the kernel must have
    # served them (equivalence is not decline-vs-decline)
    assert engaged >= ROUNDS


@pytest.mark.parametrize("protocol", ["pbcomb", "pwfcomb"])
def test_heterogeneous_round_falls_back(protocol):
    """A round mixing funcs (insert + get_min map to different kernel
    funcs) must decline vectorization and still be correct."""

    def drive(vector):
        nvm = NVM(1 << 20)
        rt = CombiningRuntime(nvm=nvm, n_threads=N)
        obj = rt.make("heap", protocol, vector_apply=vector)
        handles = [rt.attach(p) for p in range(N)]
        b0 = handles[0].bind(obj)
        b0.insert(7)
        handles[1].announce(obj, "insert", 3)
        handles[2].announce(obj, "get_min")
        handles[3].announce(obj, "insert", 11)
        rets = [b0.insert(5)]
        rets += [handles[p].perform(obj) for p in (1, 2, 3)]
        return rets, obj.snapshot(), dict(nvm.counters)

    assert drive(True) == drive(False)


def test_unpackable_payloads_decline():
    """The packing guards: strings, None, bignums and floats-for-int
    slots make vector_apply return None (eager fallback), never an
    approximate batch."""
    nvm = NVM(1 << 16)
    log = ResponseLogObject(8)
    base = nvm.alloc(log.state_words)
    log.init_state(nvm, base)
    assert log.vector_apply(nvm, base, "RECORD",
                            [(0, 1, "a-string")]) is None
    assert log.vector_apply(nvm, base, "RECORD", [(0, 1, None)]) is None
    assert log.vector_apply(nvm, base, "RECORD", [(0, 1, 2 ** 70)]) is None

    ctr = FetchAddObject()
    cbase = nvm.alloc(ctr.state_words)
    ctr.init_state(nvm, cbase)
    assert ctr.vector_apply(nvm, cbase, "FAA", [2 ** 70]) is None
    assert ctr.vector_apply(nvm, cbase, "FAA", [1.5]) is None
    # wrong func for the object declines rather than misapplying
    assert ctr.vector_apply(nvm, cbase, "MUL", [2]) is None

    heap = HeapObject(16)
    hbase = nvm.alloc(heap.state_words)
    heap.init_state(nvm, hbase)
    assert heap.vector_apply(nvm, hbase, "HINSERT", ["x"]) is None


def test_bool_packs_as_int():
    """The documented wrinkle: bool is an int subclass and packs as its
    int value — the batch result must still equal the eager loop."""
    nvm = NVM(1 << 16)
    ctr = FetchAddObject()
    base = nvm.alloc(ctr.state_words)
    ctr.init_state(nvm, base)
    resps = ctr.vector_apply(nvm, base, "FAA", [True, 2, True])
    assert resps == [0, 1, 3]
    assert all(type(v) is int for v in resps)
    assert nvm.read(base) == 4


def test_atomicfloat_mul_round_exact():
    """The paper's AtomicFloat under the seam: the scan kernel performs
    the identical float multiplies in the identical order, so state and
    responses match the eager loop bit-for-bit."""
    args = [1.000001, 0.75, 3.5, 1.25, 0.5, 2.0] * 3
    nvm_v, nvm_e = NVM(1 << 10), NVM(1 << 10)
    obj = AtomicFloatObject()
    bv, be = nvm_v.alloc(1), nvm_e.alloc(1)
    obj.init_state(nvm_v, bv)
    obj.init_state(nvm_e, be)
    resps_v = obj.vector_apply(nvm_v, bv, "MUL", args)
    resps_e = [obj.apply(nvm_e, be, "MUL", a) for a in args]
    assert resps_v == resps_e
    assert nvm_v.read(bv) == nvm_e.read(be)


def test_atomicfloat_mul_declines_on_tpu(monkeypatch):
    """The TPU's float64 is a float32 pair, not IEEE binary64: the MUL
    round declines there (eager loop) while the int64 kernels serve."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    nvm = NVM(1 << 10)
    obj = AtomicFloatObject()
    base = nvm.alloc(obj.state_words)
    obj.init_state(nvm, base)
    assert obj.vector_apply(nvm, base, "MUL", [1.5, 0.25]) is None
    assert nvm.read(base) == 1.0
    ctr = FetchAddObject()
    cbase = nvm.alloc(ctr.state_words)
    ctr.init_state(nvm, cbase)
    assert ctr.vector_apply(nvm, cbase, "FAA", [2, 3]) == [0, 2]


# --------------------------------------------------------------------- #
# Crash inside a vectorized round                                       #
# --------------------------------------------------------------------- #
_ANNOUNCE = {"counter": ("fetch_add", lambda p: 1),
             "heap": ("insert", lambda p: 100 + p),
             "log": ("record", lambda p: (p, 1, 10 + p)),
             "ckpt": ("persist", lambda p: (1, 7))}

CRASH_CELLS = [(k, p) for k, p in VECTOR_CELLS
               if get_adapter(k, p).detectable]


@pytest.mark.parametrize("kind,protocol", CRASH_CELLS)
@pytest.mark.parametrize("crash_at", [0, 2, 4, 6])
def test_crash_mid_vectorized_round_replays_exactly_once(kind, protocol,
                                                         crash_at):
    """Arm a crash inside the combining round that serves N announced
    requests through the vector seam; after recovery the durable state
    equals an eager crash-free run of the same workload and every
    request was applied exactly once."""
    rt = CombiningRuntime(n_threads=N)
    obj = rt.make(kind, protocol, vector_apply=True)
    handles = [rt.attach(p) for p in range(N)]
    op, argfn = _ANNOUNCE[kind]
    for p in range(N):
        handles[p].announce(obj, op, argfn(p))
    rt.arm_crash(crash_at, random.Random(13))
    rets = {}
    try:
        rets[1] = handles[1].perform(obj)
    except SimulatedCrash:
        pass
    replies = rt.recover()
    for p in range(N):
        if (obj.name, p) in replies:
            rets[p] = replies[(obj.name, p)]
    assert len(rets) == N

    # eager, crash-free reference run of the identical workload
    ref_rt = CombiningRuntime(n_threads=N)
    ref = ref_rt.make(kind, protocol, vector_apply=False)
    ref_handles = [ref_rt.attach(p) for p in range(N)]
    for p in range(1, N):
        ref_handles[p].announce(ref, op, argfn(p))
    getattr(ref_handles[0].bind(ref), op)(argfn(0))
    for p in range(1, N):
        ref_handles[p].perform(ref)
    assert obj.snapshot() == ref.snapshot()

    if kind == "counter":
        # FAA multiset linearizability: N replayed FAA(1) responses are
        # exactly {0..N-1} — a lost or doubled application breaks this
        assert sorted(rets.values()) == list(range(N))

    # structure stays usable post-recovery, vector path still on
    b = rt.attach(0).bind(obj)
    if kind == "counter":
        assert b.fetch_add(1) == N
    elif kind == "heap":
        b.insert(-1)
        assert b.get_min() == -1
    elif kind == "log":
        b.record((0, 2, 99))
        assert b.lookup(0) == (2, 99)
    else:
        b.persist((5, 55))
        assert b.latest() == (5, 55)
