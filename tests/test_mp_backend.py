"""Multiprocess worker-pool backend: true-parallel linearizability,
measured combining degree past the GIL, machine-wide crash with live
worker processes, and the 4-process stress coverage for the baseline
race class (DurableMSQueue-style lost-link / mirror regression).

Each test forks real worker processes via
``CombiningRuntime(backend="shm").spawn_workers`` — sizes small enough
for 2-core CI runners.
"""

from collections import Counter

import pytest

from repro.api import CombiningRuntime

ADD_ACKED = {"enqueue", "push", "insert"}
REM = {"dequeue", "pop", "delete_min"}


def _tally(results_iter):
    """(acked adds, non-empty removals) multisets over op results."""
    added, removed = Counter(), Counter()
    for op, arg, ret in results_iter:
        if op in ADD_ACKED and (ret == "ACK" or ret is True):
            added[arg] += 1
        elif op in REM and ret is not None:
            removed[ret] += 1
    return added, removed


def _run_pairs_exact_once(kind, protocol, workers=4, pairs=60):
    rt = CombiningRuntime(n_threads=workers, backend="shm")
    try:
        obj = rt.make(kind, protocol)
        with rt.spawn_workers(workers) as pool:
            res = pool.run_pairs(obj, pairs, collect=True)
        added, removed = _tally(r for rep in res.reports
                                for r in rep.results)
        remaining = Counter(obj.snapshot())
        assert added == removed + remaining, (kind, protocol)
        assert res.ops_done == 2 * workers * pairs
        return obj.adapter.degree_stats(obj.core)
    finally:
        rt.close()


@pytest.mark.parametrize("kind,protocol", [
    ("queue", "pbcomb"), ("queue", "pwfcomb"), ("queue", "durable-ms"),
    ("queue", "lock-direct"), ("stack", "pbcomb"), ("stack", "pwfcomb"),
    ("stack", "dfc")])
def test_exact_once_under_true_parallelism(kind, protocol):
    """Every acked add appears exactly once in removals + final state,
    with 4 processes racing for real (no GIL serialization)."""
    _run_pairs_exact_once(kind, protocol)


def test_measured_degree_exceeds_one():
    """The point of the backend: combining rounds serve multiple
    announcements from OTHER processes.  degree_max is scheduler-robust
    (one >=2 round suffices); the >=2 degree_mean acceptance gate runs
    in mp_bench --check where sizes are bench-scale."""
    stats = _run_pairs_exact_once("queue", "pbcomb", workers=4, pairs=80)
    assert stats is not None and stats["rounds"] > 0
    assert stats["degree_max"] >= 2
    assert stats["ops_combined"] > stats["rounds"]   # mean > 1


def test_degree_stats_none_for_baselines():
    rt = CombiningRuntime(n_threads=2, backend="shm")
    try:
        obj = rt.make("queue", "lock-direct")
        assert obj.adapter.degree_stats(obj.core) is None
    finally:
        rt.close()


# --------------------------------------------------------------------- #
# machine-wide crash with live workers                                  #
# --------------------------------------------------------------------- #
def test_crash_mid_round_with_live_workers_recovers_exactly_once():
    """Arm the shared countdown so the machine halts while 4 worker
    processes are mid-workload; survivors stop on the halted flag,
    every worker reports its in-flight op (the paper's system-support
    contract), and recover(inflight=...) replays them exactly once."""
    rt = CombiningRuntime(n_threads=4, backend="shm")
    try:
        q = rt.make("queue", "pbcomb")
        pool = rt.spawn_workers(4)
        res0 = pool.run_pairs(q, 20, collect=True)
        assert not res0.crashed

        rt.nvm.arm_crash(150)
        res1 = pool.run_pairs(q, 80, collect=True)
        assert res1.crashed, "countdown should fire mid-workload"
        # crashed workers report (obj, tid, op, args, seq) records
        inflight = {(n, t): (op, args, seq)
                    for n, t, op, args, seq in res1.inflight}
        assert all(n == q.name for n, _t in inflight)

        replay = rt.recover(inflight=res1.inflight)
        added, removed = _tally(r for res in (res0, res1)
                                for rep in res.reports
                                for r in (rep.results or []))
        for key, ret in replay.items():
            op, args, _seq = inflight[key]
            if op == "enqueue" and ret == "ACK":
                added[args] += 1
            elif op == "dequeue" and ret is not None:
                removed[ret] += 1
        remaining = Counter(q.snapshot())
        assert added == removed + remaining

        # the same pool keeps working after recovery
        res2 = pool.run_pairs(q, 15)
        assert not res2.crashed and res2.ops_done == 4 * 2 * 15
    finally:
        rt.close()


def test_crash_halts_every_worker_not_just_the_tripper():
    """The halted flag reaches survivors: after one process trips the
    countdown, NO worker keeps completing operations against the dead
    machine (each either finished before the halt or reports crashed)."""
    rt = CombiningRuntime(n_threads=4, backend="shm")
    try:
        q = rt.make("queue", "pbcomb")
        pool = rt.spawn_workers(4)
        rt.nvm.arm_crash(40)
        res = pool.run_pairs(q, 200, collect=True)
        assert len(res.crashed) >= 2, \
            "halt must propagate beyond the tripping process"
        assert rt.nvm.halted
        rt.recover(inflight=res.inflight)
        assert not rt.nvm.halted
    finally:
        rt.close()


# --------------------------------------------------------------------- #
# 4-process stress: the ROADMAP-flagged baseline race class             #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("protocol", ["pbcomb", "pwfcomb"])
def test_heap_stress_four_processes(protocol):
    """4-process stress for the recoverable HEAP — until this PR the
    only structure with zero mp stress coverage.  Exact-once across
    insert/delete_min pairs, plus a post-run quiescent drain that must
    come out sorted (heap order survives true parallelism)."""
    rt = CombiningRuntime(n_threads=4, backend="shm")
    try:
        h = rt.make("heap", protocol)
        with rt.spawn_workers(4) as pool:
            res = pool.run_pairs(h, 100, collect=True)
        added, removed = _tally(r for rep in res.reports
                                for r in rep.results)
        drain = []
        fn = rt.attach(0).invoker(h, "delete_min", arity=0)
        while True:
            v = fn()
            if v is None:
                break
            drain.append(v)
        assert drain == sorted(drain)
        assert added == removed + Counter(drain)
        assert res.ops_done == 4 * 2 * 100
    finally:
        rt.close()


def test_heap_stress_rich_blob_values():
    """The same heap stress with blob-sized tuple values — heap order
    on tuples exercises blob decode on every sift comparison."""
    rt = CombiningRuntime(n_threads=4, backend="shm")
    try:
        h = rt.make("heap", "pbcomb")
        with rt.spawn_workers(4) as pool:
            res = pool.run_pairs(h, 40, collect=True, rich=True)
        added, removed = _tally(r for rep in res.reports
                                for r in rep.results)
        remaining = Counter(h.snapshot())
        assert added == removed + remaining
    finally:
        rt.close()


@pytest.mark.parametrize("protocol", ["durable-ms", "lock-undo"])
def test_baseline_stress_four_processes(protocol):
    """Heavier pairs stress on the per-op-persist baselines whose races
    the GIL used to mask: durable-ms (lost-link + head/tail-mirror
    regression class) and lock-undo (log/update mutual exclusion)."""
    for _round in range(3):
        _run_pairs_exact_once("queue", protocol, workers=4, pairs=120)


def test_durable_ms_head_mirror_never_regresses_under_crash():
    """The PR's audit fix: head/tail NVM mirrors are written inside the
    SC, so a crash can never expose a REGRESSED durable head (which
    would recover into re-serving arbitrarily many already-dequeued
    nodes).  Crash mid-stress, recover, and bound each value's servings
    by the at-least-once contract: at most one duplicate per replayed
    in-flight record (durable-ms is NOT detectable — a crashed op whose
    effect survived is legitimately re-executed; that documented
    duplication is the allowance below, head regression is not).  The
    recovered list must also be acyclic (drain terminates)."""
    rt = CombiningRuntime(n_threads=4, backend="shm")
    try:
        q = rt.make("queue", "durable-ms")
        pool = rt.spawn_workers(4)
        res0 = pool.run_pairs(q, 30, collect=True)
        rt.nvm.arm_crash(120)
        res1 = pool.run_pairs(q, 100, collect=True)
        assert res1.crashed
        replay = rt.recover(inflight=res1.inflight)

        added, removed = _tally(r for res in (res0, res1)
                                for rep in res.reports
                                for r in (rep.results or []))
        inflight = {(n, t): (op, args, seq)
                    for n, t, op, args, seq in res1.inflight}
        for key, ret in replay.items():
            op, args, _seq = inflight[key]
            if op == "enqueue" and ret == "ACK":
                added[args] += 1
            elif op == "dequeue" and ret is not None:
                removed[ret] += 1
        remaining = Counter(q.snapshot())      # terminates: list acyclic
        seen = removed + remaining
        # allowance: one extra serving per replayed in-flight ENQUEUE of
        # that value (its pre-crash effect may have survived durably)
        inflight_enq = Counter(args for (op, args, _s) in inflight.values()
                               if op == "enqueue")
        for v, n in seen.items():
            assert added[v] >= 1, f"value {v} never enqueued"
            assert n <= added[v] + inflight_enq[v], \
                f"value {v} served {n}x for {added[v]} enqueue(s) + " \
                f"{inflight_enq[v]} replay(s) — regressed durable head " \
                "(mirror race)"
    finally:
        rt.close()


# --------------------------------------------------------------------- #
# pool plumbing                                                         #
# --------------------------------------------------------------------- #
def test_spawn_workers_requires_shm_backend():
    rt = CombiningRuntime(n_threads=2)
    with pytest.raises(RuntimeError):
        rt.spawn_workers(2)


def test_spawn_workers_checks_real_substrate_not_kwarg():
    """A pre-built ShmNVM passed via nvm= works even with the default
    backend kwarg (the check looks at the actual NVM, where fork
    sharing is decided), and a thread NVM smuggled past backend="shm"
    cannot happen (the kwarg only governs lazy creation)."""
    from repro.core.shm import ShmNVM
    nvm = ShmNVM(1 << 14)
    try:
        rt = CombiningRuntime(nvm=nvm, n_threads=2)
        q = rt.make("queue", "pbcomb")
        with rt.spawn_workers(2) as pool:
            res = pool.run_pairs(q, 10)
        assert res.ops_done == 40
        rt.close()
        # the injected NVM belongs to the caller: close() left it open
        assert nvm.counters["psync"] > 0
        with pytest.raises(RuntimeError, match="closed"):
            rt.make("queue", "pwfcomb")
    finally:
        nvm.close()


def test_vector_apply_refused_on_shm_backend():
    """Shm rounds run in forked workers, which cannot own the chip: the
    device path is refused there rather than left to fail in a child."""
    rt = CombiningRuntime(n_threads=2, backend="shm")
    try:
        with pytest.raises(ValueError, match="threads backend"):
            rt.make("counter", "pbcomb", vector_apply=True)
        assert rt.objects == {}
    finally:
        rt.close()


def test_spawn_workers_refuses_fork_after_accelerator_init(monkeypatch):
    """Once the parent has initialised a non-CPU JAX backend, forking
    workers is refused before any child starts."""
    import jax  # noqa: F401  (the guard only looks once jax is loaded)
    from jax._src import xla_bridge
    monkeypatch.setattr(xla_bridge, "backends_are_initialized",
                        lambda: True)
    monkeypatch.setattr(xla_bridge, "backends",
                        lambda: {"cpu": None, "tpu": None})
    rt = CombiningRuntime(n_threads=2, backend="shm")
    try:
        rt.make("queue", "pbcomb")
        with pytest.raises(RuntimeError, match="tpu"):
            rt.spawn_workers(2)
        monkeypatch.setattr(xla_bridge, "backends", lambda: {"cpu": None})
        with rt.spawn_workers(2) as pool:     # a CPU backend forks fine
            assert len(pool.tids) == 2
    finally:
        rt.close()


def test_run_ops_explicit_programs():
    rt = CombiningRuntime(n_threads=2, backend="shm")
    try:
        h = rt.make("heap", "pbcomb")
        with rt.spawn_workers(2) as pool:
            res = pool.run_ops(h, {
                0: [("insert", 5), ("insert", 1), ("delete_min", None)],
                1: [("insert", 3), ("insert", 7)]})
        rets = {tid: [r[2] for r in rep]
                for tid, rep in res.results_by_tid().items()}
        assert rets[0][2] in (1, 3)        # min at that moment
        assert sorted(h.snapshot()) == h.snapshot()
        inserted = Counter([5, 1, 3, 7])
        popped = Counter([rets[0][2]])
        assert Counter(h.snapshot()) == inserted - popped
    finally:
        rt.close()


def test_worker_error_propagates():
    rt = CombiningRuntime(n_threads=2, backend="shm")
    try:
        q = rt.make("queue", "pbcomb")
        with rt.spawn_workers(2) as pool:
            with pytest.raises(RuntimeError, match="worker"):
                pool.run_ops(q, {0: [("frobnicate", 1)],
                                 1: [("enqueue", 1)]})
    finally:
        rt.close()
