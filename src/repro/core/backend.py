"""Execution-backend seam: where the protocols get their *volatile
shared* primitives from.

The combining protocols need a handful of shared-between-participants
volatile objects: the combiner-election lock, the announcement board
(Request[0..n-1]), PWFComb's Flush/CombRound arrays and its LL/SC S
reference, a few single-word cells (PBComb's LockVal, PBQueue's
oldTail), plain mutexes, and the measured-degree counters.  Under the
seed's thread model these were ordinary Python objects sharing the
interpreter heap; a multiprocess run needs every one of them backed by
``multiprocessing.shared_memory`` instead (core/shm.py).

``Backend`` is that seam.  Every ``NVM`` owns one (``nvm.backend``) and
the protocols build their volatile state exclusively through it, so the
SAME protocol code runs under both executions:

  * ``ThreadBackend`` (default) — plain ``threading`` primitives and
    interpreter-heap lists, byte-for-byte the seed's behavior (the
    deterministic modeled pass and the gated perf trajectory ride on
    this, so the thread implementations change no instruction
    sequence).
  * ``ShmBackend`` (core/shm.py) — the same interfaces over a shared
    memory segment + lock-striped CAS emulation, fork-inherited by
    worker processes (api/mp.py).

Reset semantics: a crash wipes volatile state.  The thread backend
recreates objects (exactly what the seed did); the shm backend must
instead reset *in place* — worker processes hold fork-inherited
references to the same views, so rebinding to fresh objects in the
recovering process would silently diverge the two sides.  Hence the
``reset_*`` methods: thread backends return fresh objects, shm backends
return the same object with its shared state re-initialized.
"""

from __future__ import annotations

import threading
from typing import Any, List, Optional, Tuple

from .atomics import AtomicInt, AtomicRef, Counters, WaitableInt


class Cell:
    """One shared volatile word with a plain ``value`` attribute
    (PBComb's LockVal, PBQueue's oldTail)."""

    __slots__ = ("value",)

    def __init__(self, value: Any = None) -> None:
        self.value = value


class IntList(list):
    """A shared volatile int array (PWFComb's Flush, CombRound rows).
    ``list`` plus in-place ``fill`` so post-crash resets work on both
    backends through one call."""

    def fill(self, value: int) -> None:
        self[:] = [value] * len(self)


class RequestBoard(list):
    """The announcement board: ``board[p]`` is thread p's RequestRec.

    A plain list of RequestRec objects under threads (``board[p] = rec``
    and in-place field mutation both work, exactly as the seed did); the
    shm variant returns per-slot views into shared memory and copies
    assigned records field-by-field (valid last)."""

    def __init__(self, n_threads: int) -> None:
        from .pbcomb import RequestRec
        super().__init__(RequestRec() for _ in range(n_threads))

    def reset(self) -> None:
        from .pbcomb import RequestRec
        self[:] = [RequestRec() for _ in range(len(self))]


class DegreeStats:
    """Measured combining-degree counters (ROADMAP: the *measured* side
    of the paper's d-requests-per-psync claim).

    One record per combining round: ``rounds`` rounds served
    ``ops_combined`` requests in total; ``degree_max`` is the largest
    single round.  Updated once per round (PBComb: by the elected
    combiner; PWFComb: by the successful publisher), so the mutex is
    off every per-request hot path."""

    __slots__ = ("rounds", "ops_combined", "degree_max", "_mutex")

    def __init__(self) -> None:
        self.rounds = 0
        self.ops_combined = 0
        self.degree_max = 0
        self._mutex = threading.Lock()

    def record(self, served: int) -> None:
        with self._mutex:
            self.rounds += 1
            self.ops_combined += served
            if served > self.degree_max:
                self.degree_max = served

    def snapshot(self) -> dict:
        with self._mutex:
            return {"rounds": self.rounds,
                    "ops_combined": self.ops_combined,
                    "degree_max": self.degree_max}

    def reset(self) -> None:
        with self._mutex:
            self.rounds = 0
            self.ops_combined = 0
            self.degree_max = 0


def merge_degree_stats(snaps) -> Optional[dict]:
    """Aggregate several ``DegreeStats.snapshot()`` dicts (split-queue
    enq+deq instances) into one; None if there are none."""
    snaps = [s for s in snaps if s is not None]
    if not snaps:
        return None
    out = {"rounds": sum(s["rounds"] for s in snaps),
           "ops_combined": sum(s["ops_combined"] for s in snaps),
           "degree_max": max(s["degree_max"] for s in snaps)}
    out["degree_mean"] = (out["ops_combined"] / out["rounds"]
                          if out["rounds"] else 0.0)
    return out


class ThreadBackend:
    """Interpreter-heap primitives: the seed's thread execution model.

    Stateless — every NVM may own its own instance, and the factories
    below are exactly what the protocols constructed inline before the
    seam existed (fresh ``threading`` objects, plain lists)."""

    kind = "threads"

    # ------------- factories ------------------------------------------ #
    def mutex(self):
        return threading.Lock()

    def cell(self, value: Any = None) -> Cell:
        return Cell(value)

    def atomic_int(self, value: int = 0, *, shared: bool = False,
                   counters: Optional[Counters] = None,
                   clock: Optional[Any] = None) -> AtomicInt:
        return AtomicInt(value, shared=shared, counters=counters,
                         clock=clock)

    def waitable_int(self, value: int = 0, *,
                     counters: Optional[Counters] = None) -> WaitableInt:
        """A shared int with ``wait_while`` (PBComb's lock word): here
        its waiters block until a store; the shm backend's poll."""
        return WaitableInt(value, counters=counters)

    def atomic_ref(self, value: Any, *, shared: bool = False,
                   counters: Optional[Counters] = None,
                   clock: Optional[Any] = None,
                   mirror: Optional[Tuple[Any, int]] = None) -> AtomicRef:
        return AtomicRef(value, shared=shared, counters=counters,
                         clock=clock, mirror=mirror)

    def sref(self, nvm: Any, addr: int, value: int,
             counters: Optional[Counters] = None):
        from .pwfcomb import _SRef
        return _SRef(nvm, addr, value, counters)

    def int_array(self, n: int, init: int = 0) -> IntList:
        return IntList([init] * n)

    def int_matrix(self, rows: int, cols: int) -> List[IntList]:
        return [IntList([0] * cols) for _ in range(rows)]

    def request_board(self, n_threads: int) -> RequestBoard:
        return RequestBoard(n_threads)

    def degree_stats(self) -> DegreeStats:
        return DegreeStats()

    # ------------- tuning ---------------------------------------------- #
    def announce_park(self, prob: float, seconds: float
                      ) -> Tuple[float, float]:
        """(probability, duration) of the post-announce park — the
        paper's entry backoff.  The thread backend keeps the protocol's
        own constants (under the GIL a long park buys little: the
        parked thread's timeslice mostly goes to ONE other thread); the
        shm backend widens it, because with true parallelism a running
        combiner adopts every request parked during its round — that is
        what turns announcement overlap into measured degree."""
        return prob, seconds

    # ------------- post-crash resets ----------------------------------- #
    # Thread semantics: volatile state is *recreated* (what the seed's
    # reset_volatile code did); shm backends override these to reset the
    # same shared object in place and return it.
    def reset_mutex(self, m):
        return threading.Lock()

    def reset_atomic_int(self, a: AtomicInt, value: int = 0, *,
                         shared: bool = False,
                         counters: Optional[Counters] = None,
                         clock: Optional[Any] = None) -> AtomicInt:
        return AtomicInt(value, shared=shared, counters=counters,
                         clock=clock)

    def reset_waitable_int(self, a: WaitableInt, value: int = 0, *,
                           counters: Optional[Counters] = None
                           ) -> WaitableInt:
        return WaitableInt(value, counters=counters)

    def reset_atomic_ref(self, a, value: Any, *, shared: bool = False,
                         counters: Optional[Counters] = None,
                         clock: Optional[Any] = None,
                         mirror: Optional[Tuple[Any, int]] = None):
        return AtomicRef(value, shared=shared, counters=counters,
                         clock=clock, mirror=mirror)

    def reset_sref(self, s, nvm: Any, addr: int, value: int,
                   counters: Optional[Counters] = None):
        from .pwfcomb import _SRef
        return _SRef(nvm, addr, value, counters)
