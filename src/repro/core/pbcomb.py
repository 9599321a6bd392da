"""PBComb — the paper's blocking recoverable combining protocol.

Faithful implementation of Algorithms 1 and 2.  Design decisions
(paper Definition 1) and how each respects the persistence principles
(Definition 2):

  1. combiner election: CAS on a *volatile* integer ``Lock`` whose parity
     encodes taken/free; a thread may leave the entry section without ever
     CAS-ing if its request was served (P1: the lock is never persisted).
  2. requests: flat volatile ``Request[0..n-1]`` array (P1 — never
     persisted; ``valid`` bits are reset by a crash, which is exactly what
     recovery needs).
  3. updates applied to a *copy* of the state: 2-slot non-volatile
     ``MemState[0..1]``; the combiner works on slot ``1 - MIndex`` (P3 —
     one contiguous pwb covers state + responses + deactivate bits).
  4. responses: ``ReturnVal[0..n-1]`` inside the StateRec (P3).
  5. served-detection: per-thread ``activate`` (volatile, in Request) vs
     ``Deactivate`` (inside the persisted StateRec).  Only deactivate is
     persisted; the system-provided ``seq`` parity replaces activate at
     recovery (P1).

Per combining round of degree d: pwb(StateRec) + pfence + pwb(MIndex) +
psync — i.e. O(1) persistence instructions for d requests.  Where the
object names its writes (``touch_plan``), the StateRec copy and pwb
cover only the lines in which the two StateRecs differ (DESIGN.md §3).

StateRec NVM layout (contiguous, line-aligned):
    [ st : state_words | ReturnVal[0..n-1] | Deactivate[0..n-1] ]
"""

from __future__ import annotations

import random
import time
from dataclasses import dataclass
from typing import Any, Optional

from . import tracing
from .atomics import Counters
from .nvm import NVM, LineRecorder
from .objects import SeqObject
from .tracing import span


@dataclass(slots=True)
class RequestRec:
    func: Optional[str] = None
    args: Any = None
    activate: int = 0
    valid: int = 0
    # Virtual-clock announce timestamp (ns): a combiner adopting this
    # request merges it Lamport-style, so a round's modeled latency is
    # the max over its participants (unused when no profile is engaged).
    vtime: float = 0.0
    # Announce seqlock (volatile, costs no NVM instruction): odd while
    # an in-place announce is rewriting the fields, bumped even when it
    # publishes.  The paper's Request[p] is a single pointer store (one
    # atomic publication); our field-per-field record needs this so a
    # combiner scanning under TRUE parallelism can never adopt a MIXED
    # record — func from one announcement, args from the next (caught
    # by the mp heap stress: a torn HINSERT/None pair).  Scanners
    # re-check the stamp after reading the fields and skip the record
    # on a mismatch; the writer's announce is then simply "not yet
    # published" for that pass.
    stamp: int = 0
    # perf_counter_ns() of the announce, written only while tracing is
    # on: the combiner that adopts the request counts its queueing time
    t_ns: int = 0


class PBComb:
    # Announce-backoff: a small random fraction of operations parks
    # briefly right after announcing, widening the window in which a
    # concurrent combiner adopts the request into ITS round.  Served
    # ops skip their own round entirely — fewer pwbs/psyncs per op, the
    # very effect combining exists to create (and what the paper's
    # backoff at the protocol entry is for).  Disable with park=False
    # for deterministic single-threaded tests.
    ANNOUNCE_PARK_PROB = 0.03
    ANNOUNCE_PARK_SECONDS = 1e-6   # OS floor applies; "as short as possible"

    # Test-only seeded-bug fixture (repro.fuzz.bugs): when True, the
    # combiner's scan emulates the PR 5 torn-announcement read — args
    # adopted from a STALE generation of the request record, the very
    # mix the seqlock stamp re-check exists to prevent.  Never set
    # directly; tests toggle it via ``seeded_bug("torn-announce")``.
    torn_announce_bug = False

    def __init__(self, nvm: NVM, n_threads: int, obj: SeqObject,
                 counters: Optional[Counters] = None,
                 park: bool = True, vector_apply: bool = False) -> None:
        self.nvm = nvm
        self.n = n_threads
        self.obj = obj
        self._counters = counters
        # VectorApply (DESIGN.md §11): when enabled, a combining pass
        # collects its adoptable announcements first and a homogeneous
        # batch executes as ONE jitted kernel (obj.vector_apply); any
        # decline — mixed funcs, rich payloads, an inexact platform — runs
        # the identical per-op loop.  Off by default: the gated modeled
        # trajectory is produced with the eager path, and the
        # equivalence property tests are what license turning this on.
        self._vector_enabled = bool(vector_apply)
        sw = obj.state_words
        self.state_words = sw
        self.rec_words = sw + 2 * n_threads
        # Copy and persist by lines (DESIGN.md §3).  An object with a
        # ``touch_plan`` writes, on its vector path as in its per-op
        # loop, only the words each op names, so a round copies into
        # StateRec[ind], and persists from it, only the lines the last
        # round wrote and the lines it writes itself, as a LineRecorder
        # notes them.  The heap's kernel rewrites its whole array, and
        # the heap, counter and float keep the paper's whole copy and
        # write-back.  Thread backend only: shm's combiners are forked
        # processes, and none sees the runs another's round left here.
        # ``_last_runs``: the ``(offset, n_words)`` runs of the StateRec
        # in which the two StateRecs may differ; the whole record after
        # a crash, whose drain may leave them different anywhere.
        self._by_lines = (hasattr(obj, "touch_plan")
                          and nvm.backend.kind == "threads")
        self._whole = [[0, self.rec_words]]
        self._last_runs: list = [] if self._by_lines else self._whole
        # the NVM the round body writes through: a LineRecorder while
        # a by-lines round runs
        self._writer: Any = nvm
        self.copied_words = 0
        # --- shared non-volatile variables --------------------------- #
        self.mem_base = [nvm.alloc(self.rec_words) for _ in range(2)]
        self.mindex_addr = nvm.alloc(1)
        nvm.write(self.mindex_addr, 0)
        for ind in range(2):
            obj.init_state(nvm, self.mem_base[ind])
            for q in range(n_threads):
                nvm.write(self._retval_addr(ind, q), None)
                nvm.write(self._deact_addr(ind, q), 0)
        # Initial image must be durable (the paper assumes initialized NVMM).
        nvm.pwb(self.mem_base[0], self.rec_words)
        nvm.pwb(self.mem_base[1], self.rec_words)
        nvm.pwb(self.mindex_addr, 1)
        nvm.psync()
        nvm.reset_counters()
        # --- shared volatile variables -------------------------------- #
        # Everything shared between participants comes from the NVM's
        # execution backend (DESIGN.md §7): interpreter-heap objects on
        # the thread backend, shared-memory views on the multiprocess
        # one.  Combiner-local scratch stays a plain attribute.
        be = nvm.backend
        self.request = be.request_board(n_threads)
        self._clock = nvm.clock
        # Virtual time at which the last committed round's psync landed;
        # waiters picking up a response merge it (Lamport hand-off).  A
        # later round may overwrite it before a slow waiter reads it —
        # merge is a max, so that only ever charges the waiter MORE.
        self._round_end_vt = 0.0
        self.lock = be.waitable_int(0, counters=counters)
        self._lockval = be.cell(0)  # written by the combiner, read by waiters
        # Combiner election (the line 8 CAS) as a non-blocking mutex
        # try-acquire: same atomicity, one C call instead of a guarded
        # compare under a Python-level mutex.  ``lock`` itself is then
        # written only by the elected combiner (plain GIL-atomic store).
        self._elect = be.mutex()
        self.park_enabled = park
        # entry backoff, backend-tuned (wide under true parallelism)
        self._park_prob, self._park_secs = be.announce_park(
            self.ANNOUNCE_PARK_PROB, self.ANNOUNCE_PARK_SECONDS)
        self._rng = random.Random(0x9B5EED)   # seeded: runs reproducible
        # Measured combining degree (requests served per committed
        # round) — the wall-clock counterpart of the modeled degree-4
        # staging; mp_bench and the matrix bench report it.
        self.stats = be.degree_stats()
        self._round_served = 0
        # Where the host time of a round goes (core/tracing.py): lock
        # polls per waiting thread, always counted, each thread in its
        # own slot; announce-to-adoption time and the adoptions timed,
        # summed by combiners (under the lock) while tracing is on.
        # Per process: shm workers' polls stay in their own copies.
        self.waiter_polls = [0] * n_threads
        self.queue_ns = 0
        self.queued_ops = 0
        # Rounds that ``recover_many`` committed, and the requests they
        # served, since the core was made (always counted).
        self.recovery_rounds = 0
        self.recovered_ops = 0

    # LockVal lives in a backend cell so a combiner process's write is
    # visible to waiter processes; property keeps the paper's name.
    @property
    def lockval(self) -> int:
        return self._lockval.value

    @lockval.setter
    def lockval(self, v: int) -> None:
        self._lockval.value = v

    # ---------------- field address helpers --------------------------- #
    def _st_base(self, ind: int) -> int:
        return self.mem_base[ind]

    def _retval_addr(self, ind: int, q: int) -> int:
        return self.mem_base[ind] + self.state_words + q

    def _deact_addr(self, ind: int, q: int) -> int:
        return self.mem_base[ind] + self.state_words + self.n + q

    def _mindex(self) -> int:
        return self.nvm.read(self.mindex_addr)

    # ---------------- public API (Algorithm 1) ------------------------ #
    def op(self, p: int, func: str, args: Any, seq: int) -> Any:
        """PBCOMB(func, args, seq) executed by thread p.

        The announcement mutates p's RequestRec in place instead of
        allocating a fresh record per op.  This is race-safe: p's
        previous request is necessarily served already (p stays inside
        ``_perform_request`` until it is), so a concurrent combiner
        skips the record while ``valid`` is 0 and observes the new
        (func, args, activate) only after ``valid`` flips back to 1.
        """
        req = self.request[p]
        st = req.stamp + 1
        req.stamp = st          # odd: announce in progress (seqlock)
        req.valid = 0
        req.func = func
        req.args = args
        req.activate = 1 - req.activate
        clk = self._clock
        if clk is not None:
            req.vtime = clk.now()
        if tracing.enabled:
            req.t_ns = time.perf_counter_ns()
        req.valid = 1
        req.stamp = st + 1      # even: published
        if self.park_enabled and self._rng.random() < self._park_prob:
            time.sleep(self._park_secs)
            # a combiner may have served the parked request: if its
            # round already psync'd (lock even), return the recorded
            # response without a round of our own (cf. Recover's path)
            nvm = self.nvm
            if self.lock.load() % 2 == 0:
                mindex = nvm.read(self.mindex_addr)
                if req.activate == nvm.read(self._deact_addr(mindex, p)):
                    if clk is not None:
                        clk.merge(self._round_end_vt)
                    return nvm.read(self._retval_addr(mindex, p))
        return self._perform_request(p)

    def recover(self, p: int, func: str, args: Any, seq: int) -> Any:
        """Recovery function (Algorithm 1, lines 3-6).  Called by the
        "system" for every thread that had an operation in flight at crash
        time, with the same arguments (Section 2's system-support
        assumption)."""
        return self.recover_many([(p, func, args, seq % 2)])[0]

    def recover_many(self, recs: list) -> list:
        """Recover for many threads at once, as the system calls it for
        every thread with an op in flight (DESIGN.md §3): line 3 for
        each ``(p, func, args, activate)`` of ``recs`` first, then lines
        4-6 for each in turn.  The first unserved thread's round adopts
        every re-announced request, so the threads share one round and
        one psync, and each later thread finds its request served and
        reads its reply.  Returns the replies in the order of ``recs``."""
        nvm = self.nvm
        for p, func, args, act in recs:
            self.request[p] = RequestRec(func, args, act, 1)    # line 3
        out = []
        for p, _func, _args, act in recs:
            mindex = self._mindex()
            if nvm.read(self._deact_addr(mindex, p)) != act:    # line 4
                out.append(self._perform_request(p))            # line 5
                self.recovery_rounds += 1
                self.recovered_ops += self._round_served
            else:
                out.append(nvm.read(self._retval_addr(mindex, p)))  # line 6
        return out

    def reset_volatile(self) -> None:
        """Re-initialize volatile protocol state after a crash (the crash
        wiped registers/caches/DRAM — Request, Lock, LockVal are volatile).

        The recreated lock keeps the original ``Counters`` reference so
        synchronization-cost measurements keep accumulating in post-crash
        benchmark phases.  Request activate bits are re-seeded from the
        durable deactivate bits (``resync_request``) so a thread whose
        next operation arrives through the normal ``op`` path — not
        ``recover`` — still flips to a fresh parity.

        All through the backend's reset methods: the thread backend
        recreates the objects (the seed's behavior), the shm backend
        resets the shared state in place so fork-inherited views in
        worker processes stay attached."""
        be = self.nvm.backend
        self.request.reset()
        self.lock = be.reset_waitable_int(self.lock, 0,
                                          counters=self._counters)
        self.lockval = 0
        self._elect = be.reset_mutex(self._elect)  # may be held at crash
        self._last_runs = self._whole   # next round copies, persists whole
        self._writer = self.nvm
        for p in range(self.n):
            self.resync_request(p)

    def resync_request(self, p: int) -> None:
        """Re-seed thread p's volatile activate parity from the durable
        deactivate bit (the paper's system hands recovery the in-flight
        seq; for threads with no in-flight op the persisted parity is the
        only survivor of the crash)."""
        deact = self.nvm.read(self._deact_addr(self._mindex(), p))
        self.request[p] = RequestRec(None, None, deact, 0)

    # Line 10's wait is the lock word's own (``backend.waitable_int``).
    # On the thread backend a waiter blocks until the unlock's store
    # opens its gate (``WaitableInt``): a poller would take the GIL on
    # every re-check, and each time the combiner gives the GIL up (a
    # kernel launch, a fetch) it would have to win it back against
    # every poller, a switch interval or more per blocking step.  Shm
    # workers are processes that share no GIL and no ``threading``
    # object, so their word polls, and leaves on the shared ``halted``
    # flag.
    def _wait_while(self, p: int, expected: int) -> None:
        self.waiter_polls[p] += self.lock.wait_while(expected, self.nvm)

    # ---------------- Algorithm 2 ------------------------------------- #
    def _perform_request(self, p: int) -> Any:
        nvm = self.nvm
        clk = self._clock
        while True:
            lval = self.lock.load()                          # line 6
            if lval % 2 == 0:                                # line 7
                if self._elect.acquire(False):               # line 8 (CAS)
                    if self._counters is not None:
                        self._counters.cas_calls += 1
                    if clk is not None:
                        clk.advance(clk.profile.cas_ns)
                    # while _elect is held nobody else stores the lock,
                    # and its last writer left it even — re-read in case
                    # a whole round completed since the line 6 load
                    lval = self.lock.load()
                    self.lock.store(lval + 1)
                    break                                    # p is combiner
                if self._counters is not None:
                    self._counters.cas_calls += 1
                if clk is not None:
                    clk.advance(clk.profile.cas_ns)
                lval += 1                                    # line 9
            self._wait_while(p, lval)                        # line 10
            mindex = self._mindex()
            if self.request[p].activate == nvm.read(self._deact_addr(mindex, p)):  # line 11
                if self.lockval != lval:                     # line 12
                    # Served by an in-flight round: wait for its psync.
                    self._wait_while(p, lval + 2)
                if clk is not None:
                    # Lamport hand-off: the waiter's clock jumps to the
                    # serving round's commit time (max, not sum).
                    clk.merge(self._round_end_vt)
                return nvm.read(self._retval_addr(self._mindex(), p))  # line 13
        return self._combine(p, lval + 1)

    def _combine(self, p: int, lock_val: int) -> Any:
        """Combiner code, Algorithm 2 lines 14-29.  Hot path: addresses
        are derived once per round and NVM accessors bound to locals —
        the loop body is the per-request cost the paper amortizes.
        ``lock_val`` is the (odd) lock value this combiner installed at
        line 8: while the lock is held nobody else writes it, so the
        line 24 read and line 28 increment are plain arithmetic."""
        nvm = self.nvm
        clk = self._clock
        if clk is not None:
            clk.advance(clk.profile.round_ns)   # round fusion bookkeeping
        mindex = nvm.read(self.mindex_addr)
        ind = 1 - mindex                                     # line 14
        base = self.mem_base[ind]
        mbase = self.mem_base[mindex]
        last = self._last_runs
        for off, n in last:                                  # line 15
            nvm.copy_range(base + off, mbase + off, n)
            self.copied_words += n
        if self._by_lines:
            self._writer = LineRecorder(nvm, base, self.rec_words)
        wr = self._writer.write
        self._round_served = 0
        self._begin_round(ind, p)
        retval_base = base + self.state_words
        deact_base = retval_base + self.n
        request = self.request
        served = 0
        # Simulation loop (line 16), iterated to a fixpoint: one pass
        # serves everything announced before it, and a further pass
        # adopts announcements that landed WHILE it ran.  Under the GIL
        # the second pass finds nothing (the scan isn't preempted) and
        # this is the paper's single scan; under true parallelism it is
        # where measured degree comes from — announcers overlap the
        # combiner's applies and still ride this round's single psync.
        # Bounded: a served thread blocks until the round commits, so
        # each thread contributes at most one request per round (at
        # most n passes, typically 2).
        vector = self._vector_enabled
        traced = tracing.enabled
        if traced:
            tracing.set_round(lock_val)
            since = tracing.since_ns
        queue_ns = queued = 0
        n_pass = 0
        while True:
            pass_served = 0
            batch = [] if vector else None
            scan = span("combine.scan", **{"pass": n_pass})
            n_pass += 1
            with scan:
                deacts = nvm.read_range(deact_base, self.n)  # n reads
                for q in range(self.n):                      # line 16
                    req = request[q]
                    # seqlock snapshot: skip records mid-announce, and
                    # re-check the stamp after the field reads so a
                    # mixed (func from one announce, args from the next)
                    # record is never applied — a skipped record is
                    # adopted by a later fixpoint pass or the
                    # announcer's own round
                    s1 = req.stamp
                    act = req.activate
                    if (s1 & 1 or req.valid != 1
                            or act == deacts[q]):                 # line 17
                        continue
                    func, args, vt = req.func, req.args, req.vtime
                    t_ns = req.t_ns if traced else 0
                    if req.stamp != s1:
                        continue
                    if traced and t_ns >= since:
                        queue_ns += time.perf_counter_ns() - t_ns
                        queued += 1
                    if PBComb.torn_announce_bug:
                        args = self._bug_torn_args(q, args)
                    if clk is not None:
                        clk.merge(vt)         # Lamport receive of announce
                    if batch is not None:
                        # VectorApply: adopt now, apply the whole pass
                        # below (merging first is clock-identical —
                        # merge is a max)
                        batch.append((q, func, args, act))
                        continue
                    if traced:                               # lines 18-19
                        with span("combine.host_apply"):
                            ret = self._apply(q, func, args, ind, p)
                    else:
                        ret = self._apply(q, func, args, ind, p)
                    wr(retval_base + q, ret)                     # line 20
                    wr(deact_base + q, act)                      # line 21
                    pass_served += 1
                scan.set_metadata(adopted=len(batch) if vector
                                    else pass_served)
            if batch:
                rets = self._apply_batch(batch, ind, p)
                for (q, _f, _a, act), ret in zip(batch, rets):
                    wr(retval_base + q, ret)                       # line 20
                    wr(deact_base + q, act)                        # line 21
                pass_served = len(batch)
            served += pass_served
            if pass_served == 0:
                break
        pending = self._post_simulation(ind, p)
        self.lockval = lock_val                              # line 24
        runs = last
        if self._by_lines:
            written = self._writer.runs()
            self._writer = nvm
            runs = _union(last, written)
        # lines 22-23 + 25-27 as one fused commit (identical counters,
        # durable effect, and crash-tick behavior — see NVM.commit_round)
        nvm.commit_round([(base + off, n) for off, n in runs],
                         self.mindex_addr, ind, pending=pending)
        if self._by_lines:
            self._last_runs = written
        # Measured degree: requests this committed round served (the
        # loop above plus any eliminated pairs _begin_round recorded).
        self._round_served += served
        self.stats.record(self._round_served)
        if traced:
            self.queue_ns += queue_ns
            self.queued_ops += queued
            tracing.set_round(None)
        if clk is not None:
            self._round_end_vt = clk.now()   # published before the unlock
        self._pre_unlock(ind, p)
        self.lock.store(lock_val + 1)                        # line 28
        self._elect.release()
        # line 29 reads ReturnVal[MIndex][p]; MIndex == ind until the
        # next combiner (which needs the lock we just released) flips it
        return nvm.read(retval_base + p)

    def _bug_torn_args(self, q: int, args: Any) -> Any:
        """Seeded-bug fixture body (``torn_announce_bug``): every third
        adoption of a thread whose PREVIOUS announce carried different
        args gets the stale args — the mixed-generation record a torn
        seqlock read would produce.  The combiner then applies (and
        acks) an op the announcer never asked for, which the history
        checker reports as a conjured/lost value pair."""
        prev = getattr(self, "_bug_prev", None)
        if prev is None:
            prev = self._bug_prev = {}
            self._bug_ctr = 0
        stale = prev.get(q)
        prev[q] = args
        if stale is not None and stale != args and args is not None:
            self._bug_ctr += 1
            if self._bug_ctr % 3 == 0:
                return stale
        return args

    # ---------------- structure hooks --------------------------------- #
    def _apply(self, q: int, func: str, args: Any, ind: int,
               combiner: int) -> Any:
        return self.obj.apply(self._writer, self.mem_base[ind], func, args,
                              ctx=self)

    def _apply_batch(self, batch, ind: int, combiner: int) -> list:
        """One collected combining pass: ``batch`` is the adoptable
        announcements ``[(q, func, args, act), ...]`` in scan order.  A
        homogeneous batch goes through the object's VectorApply seam
        (one jitted kernel — DESIGN.md §11); a heterogeneous batch or a
        seam decline runs the identical per-op loop."""
        func = batch[0][1]
        if all(b[1] == func for b in batch):
            rets = self.obj.vector_apply(
                self._writer, self.mem_base[ind], func,
                [b[2] for b in batch], ctx=self)
            if rets is not None:
                return rets
        with span("combine.host_apply"):
            return [self._apply(q, f, a, ind, combiner)
                    for q, f, a, _act in batch]

    def trace_counters(self) -> dict:
        """Lock polls of waiting threads, the queueing time and count of
        the requests adopted while tracing was on, the words the line-15
        copies moved, and the rounds recovery committed and the requests
        they served, since the core was made."""
        return {"waiter_polls": sum(self.waiter_polls),
                "queue_ns": self.queue_ns, "queued_ops": self.queued_ops,
                "copied_words": self.copied_words,
                "recovery_rounds": self.recovery_rounds,
                "recovered_ops": self.recovered_ops}

    def _begin_round(self, ind: int, combiner: int) -> None:
        """Called after the state copy, before the simulation loop.
        PBStack's elimination pass lives here."""

    def _post_simulation(self, ind: int, combiner: int):
        """Called after the simulation loop, before pwb(StateRec).
        Returns the round's extra NVM ranges to persist ahead of the
        StateRec — PBQueue's enqueue instance reports its ``toPersist``
        node set here (Algorithm 5 line 24) — or None."""
        return None

    def _pre_unlock(self, ind: int, combiner: int) -> None:
        """Called after psync, before the lock release.  PBQueue's enqueue
        instance publishes ``oldTail`` here (Algorithm 5 line 31)."""


def _union(a: list, b: list) -> list:
    """The union of two ordered lists of ``[offset, n_words]`` runs,
    merged where they meet or overlap."""
    out: list = []
    for off, n in sorted(a + b):
        if out and off <= out[-1][0] + out[-1][1]:
            out[-1][1] = max(out[-1][1], off + n - out[-1][0])
        else:
            out.append([off, n])
    return out
