"""PWFComb — the paper's wait-free recoverable combining protocol.

Faithful implementation of Algorithms 3 and 4.  Every thread *pretends*
to be the combiner: it copies the StateRec pointed to by ``S`` into one of
its two private NVM slots, applies all announced valid+active requests to
the copy, persists the copy (one contiguous pwb + pfence), and tries to
publish it with SC(S, ...).  After two failed attempts the thread's own
request is guaranteed served (Herlihy-style helping argument), so it
returns the response recorded in the current StateRec.

Persistence-principle machinery (paper Section 4):
  * ``Index[0..n-1]`` lives *inside* the StateRec so the slot-alternation
    bookkeeping persists together with the state (P3) — without it a
    recovered thread could reuse the slot currently published in S.
  * ``Flush[]`` (volatile) parity tells whether the publishing round's
    pwb(S)+psync already happened, so most threads skip persisting S (P1).
  * ``CombRound[][]`` (volatile) tells a thread which publishing round
    served it, so it only helps persist that round (P2).

Deviations from the paper's pseudocode, documented per the repo's
DESIGN.md:
  * Algorithm 4 line 15 reads ``Flush[lsPtr->pid]`` (the *previous*
    combiner's counter) to derive the round number.  We read the thread's
    own ``Flush[p]`` — the textual description ("p changes Flush[p] to an
    odd value") implies per-thread monotone round numbers, which the
    cross-thread read would break (stale ``CombRound`` entries could alias
    a later round).
  * In the fallback path (lines 38-50) the paper skips persisting S
    whenever ``CombRound`` does not match, even if ``Flush`` is odd.  We
    persist whenever ``Flush`` of the current publisher is odd: there is a
    narrow 3-round overlap window in which the skip could let a thread
    return before any psync of an S value covering its request.  The
    common-case saving (skip when even) is preserved.

LL/VL/SC on S is simulated exactly as in the paper's own evaluation:
a versioned CAS (Section 6).
"""

from __future__ import annotations

import random
import threading
import time
from typing import Any, Optional

from . import tracing
from .atomics import Counters
from .nvm import NVM
from .objects import SeqObject
from .pbcomb import RequestRec
from .tracing import span


class _SRef:
    """Versioned LL/VL/SC reference whose value is mirrored into an NVM
    word under the SC mutex (so pwb(&S) snapshots are never stale)."""

    def __init__(self, nvm: NVM, addr: int, value: int,
                 counters: Optional[Counters] = None) -> None:
        self.nvm = nvm
        self.addr = addr
        self._value = (value, 0)
        self._mutex = threading.Lock()
        self._counters = counters
        self._clock = nvm.clock
        # Recovery rebuilds the mirror from the durable word itself
        # (reset_sref passes nvm.read(addr) back in); rewriting the
        # identical value would dirty the line with nothing new to
        # persist before the recovery psync.
        if nvm.read(addr) != value:
            nvm.write(addr, value)

    def ll(self):
        if self._counters:
            self._counters.shared_reads += 1
        return self._value

    def vl(self, version: int) -> bool:
        return self._value[1] == version

    def sc(self, version: int, new_value: int) -> bool:
        with self._mutex:
            if self._counters:
                self._counters.cas_calls += 1
            if self._clock is not None:
                self._clock.advance(self._clock.profile.cas_ns)
            if self._value[1] == version:
                self._value = (new_value, version + 1)
                self.nvm.write(self.addr, new_value)
                return True
            return False

    def load(self) -> int:
        return self._value[0]


class PWFComb:
    MAX_BACKOFF = 64  # spin iterations; adaptive, tiny on a 1-core host
    ANNOUNCE_PARK_PROB = 0.05
    ANNOUNCE_PARK_SECONDS = 1e-6   # OS floor applies
    #: ``trace_counters`` keys.  An attempt (line 9 reached) ends
    #: published, abandoned at a VL (lines 18, 26) or lost at the SC
    #: (line 31), so attempts == rounds + aborted_vl + lost_sc; an op
    #: ends in a publication of its own, a ``_try_finish`` hit or the
    #: fallback (lines 38-50).  wasted_ops: requests applied in
    #: attempts that did not publish.
    TRACE_COUNTERS = ("attempts", "aborted_vl", "lost_sc",
                      "finished_early", "fallbacks", "wasted_ops")

    def __init__(self, nvm: NVM, n_threads: int, obj: SeqObject,
                 counters: Optional[Counters] = None,
                 backoff: bool = True, vector_apply: bool = False) -> None:
        self.nvm = nvm
        self.n = n_threads
        self.obj = obj
        self._counters = counters
        self.backoff_enabled = backoff
        # VectorApply (DESIGN.md §11): homogeneous simulation passes run
        # as one jitted kernel over the announcement batch; declines
        # fall back to the identical per-op loop.  See PBComb.
        self._vector_enabled = bool(vector_apply)
        sw = obj.state_words
        self.state_words = sw
        # StateRec: st | ReturnVal[n] | Deactivate[n] | Index[n] | pid
        self.rec_words = sw + 3 * n_threads + 1
        # --- shared non-volatile: (n+1) owners x 2 slots + S ---------- #
        self.slot_base = [nvm.alloc(self.rec_words)
                          for _ in range((n_threads + 1) * 2)]
        self.s_addr = nvm.alloc(1)
        dummy = self._slot_id(n_threads, 0)
        for s in range(len(self.slot_base)):
            self._init_rec(s)
        self.S = nvm.backend.sref(nvm, self.s_addr, dummy, counters)
        for s in range(len(self.slot_base)):
            nvm.pwb(self.slot_base[s], self.rec_words)
        nvm.pwb(self.s_addr, 1)
        nvm.psync()
        nvm.reset_counters()
        # --- shared volatile ------------------------------------------ #
        # Shared-between-participants state comes from the execution
        # backend (DESIGN.md §7); per-thread scratch (rng, backoff
        # windows) stays process-local.
        be = nvm.backend
        self.request = be.request_board(n_threads)
        self._clock = nvm.clock
        # Virtual time of the last durable publication (pwb(S)+psync);
        # served threads merge it on pickup — see PBComb._round_end_vt.
        self._round_end_vt = 0.0
        self.flush = be.int_array(n_threads + 1)
        self.comb_round = be.int_matrix(n_threads + 1, n_threads)
        self._rng = random.Random(0xC0FFEE)
        self._backoff_window = [1] * n_threads
        self._flush_mutex = be.mutex()
        # entry backoff, backend-tuned (wide under true parallelism)
        self._park_prob, self._park_secs = be.announce_park(
            self.ANNOUNCE_PARK_PROB, self.ANNOUNCE_PARK_SECONDS)
        # Measured combining degree: requests served per successful
        # publication (the wait-free analogue of PBComb's round).
        self.stats = be.degree_stats()
        # per-thread count of requests a _begin_attempt hook served
        # outside the scan (PWFStack's elimination) — attempts by
        # different threads run concurrently, hence one slot per tid
        self._attempt_served = [0] * n_threads
        # TRACE_COUNTERS, always counted, each thread in its own slot
        self._tally = [dict.fromkeys(self.TRACE_COUNTERS, 0)
                       for _ in range(n_threads)]

    # ---------------- layout helpers ---------------------------------- #
    def _slot_id(self, owner: int, ind: int) -> int:
        return owner * 2 + ind

    def _base(self, slot: int) -> int:
        return self.slot_base[slot]

    def _retval_addr(self, slot: int, q: int) -> int:
        return self._base(slot) + self.state_words + q

    def _deact_addr(self, slot: int, q: int) -> int:
        return self._base(slot) + self.state_words + self.n + q

    def _index_addr(self, slot: int, q: int) -> int:
        return self._base(slot) + self.state_words + 2 * self.n + q

    def _pid_addr(self, slot: int) -> int:
        return self._base(slot) + self.state_words + 3 * self.n

    def _init_rec(self, slot: int) -> None:
        nvm = self.nvm
        self.obj.init_state(nvm, self._base(slot))
        for q in range(self.n):
            nvm.write(self._retval_addr(slot, q), None)
            nvm.write(self._deact_addr(slot, q), 0)
            nvm.write(self._index_addr(slot, q), 0)
        nvm.write(self._pid_addr(slot), self.n)

    # ---------------- public API (Algorithm 3) ------------------------ #
    def op(self, p: int, func: str, args: Any, seq: int) -> Any:
        # Announce in place (line 1).  Mutating the existing RequestRec
        # is race-safe: p's previous request is already served (p was
        # inside _perform_request until then), so scanners skip it while
        # ``valid`` is 0 — and the stamp seqlock (see RequestRec) keeps
        # a truly-parallel scanner from adopting a half-rewritten
        # record.
        req = self.request[p]
        st = req.stamp + 1
        req.stamp = st          # odd: announce in progress
        req.valid = 0
        req.func = func
        req.args = args
        req.activate = 1 - req.activate
        if self._clock is not None:
            req.vtime = self._clock.now()
        req.valid = 1
        req.stamp = st + 1      # even: published
        # line 2 (backoff): a small random fraction of ops parks after
        # announcing so a concurrent pretend-combiner adopts the request
        # into its round — _try_finish then returns the recorded
        # response without a publication of our own (cf. PBComb).
        if self.backoff_enabled:
            if self._rng.random() < self._park_prob:
                time.sleep(self._park_secs)
            else:
                self._backoff(p)
        return self._perform_request(p)

    def recover(self, p: int, func: str, args: Any, seq: int) -> Any:
        self.request[p] = RequestRec(func, args, seq % 2, 1)
        s = self.S.load()
        if self.nvm.read(self._deact_addr(s, p)) != seq % 2:
            return self._perform_request(p)
        return self.nvm.read(self._retval_addr(s, p))

    def reset_volatile(self) -> None:
        """Post-crash volatile re-initialization.  S (non-volatile) is
        rebuilt from its durable NVM word; Request/Flush/CombRound are
        volatile and start fresh.  The rebuilt S keeps the original
        ``Counters`` reference (synchronization-cost measurements must
        keep accumulating after a crash) and request activate bits are
        re-seeded from the published StateRec's deactivate bits."""
        be = self.nvm.backend
        self.S = be.reset_sref(self.S, self.nvm, self.s_addr,
                               self.nvm.read(self.s_addr), self._counters)
        self.request.reset()
        self.flush.fill(0)
        for row in self.comb_round:
            row.fill(0)
        self._flush_mutex = be.reset_mutex(self._flush_mutex)
        for p in range(self.n):
            self.resync_request(p)

    def resync_request(self, p: int) -> None:
        """Re-seed thread p's volatile activate parity from the durable
        deactivate bit of the currently published StateRec."""
        deact = self.nvm.read(self._deact_addr(self.S.load(), p))
        self.request[p] = RequestRec(None, None, deact, 0)

    # ---------------- Algorithm 4 -------------------------------------- #
    def _try_finish(self, p: int):
        """Helping fast path: if p's request was already served by the
        *published* StateRec, ensure that publication is durable (the
        fallback's lines 42-50) and return its recorded response — no
        copy, no simulation, no SC.  The paper reaches this state only
        through the fallback after two failed attempts; checking before
        each attempt removes the duplicated pretend-combiner work that
        dominates under contention (every applied request's response and
        deactivate bit are already in the StateRec S points to)."""
        nvm = self.nvm
        rd = nvm.read
        ls = self.S.load()
        if self.request[p].activate != rd(
                self._base(ls) + self.state_words + self.n + p):
            return False, None
        s_pid = rd(self._pid_addr(ls))
        lval = self.flush[s_pid]
        if lval % 2 == 1:                   # publication not yet flushed
            nvm.pwb_sync(self.s_addr, 1)
            if lval == self.comb_round[s_pid][p]:
                self._cas_flush(s_pid, lval, lval + 1)
        if self._clock is not None:
            self._clock.merge(self._round_end_vt)   # Lamport hand-off
        return True, rd(self._retval_addr(self.S.load(), p))

    def _perform_request(self, p: int) -> Any:
        tally = self._tally[p]
        for _attempt in range(2):                                # line 5
            done, val = self._try_finish(p)
            if done:
                tally["finished_early"] += 1
                return val
            tally["attempts"] += 1
            attempt = span("combine.attempt")
            with attempt:
                outcome, val, applied = self._attempt(p)
                attempt.set_metadata(outcome=outcome)
            if outcome == "published":
                return val
            tally["aborted_vl" if outcome == "vl" else "lost_sc"] += 1
            if applied is not None:          # abandoned after simulating
                tally["wasted_ops"] += applied
                self._backoff(p, grow=True)                      # line 36
        # Fallback (lines 38-50): request guaranteed served by now.
        tally["fallbacks"] += 1
        nvm = self.nvm
        ls = self.S.load()                                       # line 38
        s_pid = nvm.read(self._pid_addr(ls))
        lval = self.flush[s_pid]                                 # line 40
        if lval % 2 == 1:                                        # line 42 (see module doc)
            nvm.pwb_sync(self.s_addr, 1)                         # lines 44-46
            if lval == self.comb_round[s_pid][p]:
                self._cas_flush(s_pid, lval, lval + 1)           # line 48
        if self._clock is not None:
            self._clock.merge(self._round_end_vt)        # Lamport hand-off
        return nvm.read(self._retval_addr(self.S.load(), p))     # line 50

    def _attempt(self, p: int):
        """One attempt, lines 9-35.  Returns ``(outcome, response,
        applied)``: outcome ``published`` (with p's response), ``vl``
        (abandoned at line 18 or 26) or ``sc`` (line 31 lost); applied
        is the number of requests the simulation applied, None where
        line 18 abandoned the attempt before it."""
        nvm = self.nvm
        rd, wr = nvm.read, nvm.write
        clk = self._clock
        sw, n = self.state_words, self.n
        ls, ver = self.S.ll()                                    # line 9
        ind = rd(self._base(ls) + sw + 2 * n + p)                # line 11
        dst = self._slot_id(p, ind)
        dst_base = self._base(dst)
        nvm.copy_range(dst_base, self._base(ls), self.rec_words)  # line 13
        wr(dst_base + sw + 3 * n, p)                             # line 14
        lval = self.flush[p]                                     # line 15 (own, see module doc)
        lval = lval + 1 if lval % 2 == 0 else lval + 2           # lines 16-17
        if not self.S.vl(ver):                                   # line 18
            return "vl", None, None
        self._attempt_served[p] = 0
        self._begin_attempt(dst, p)
        retval_base = dst_base + sw
        deact_base = retval_base + n
        request = self.request
        comb_round = self.comb_round[p]
        traced = tracing.enabled
        served = 0
        batch = [] if self._vector_enabled else None
        scan = span("combine.scan")
        with scan:
            deacts = nvm.read_range(deact_base, n)    # one slice, n reads
            for q in range(n):                                   # line 19
                req = request[q]
                # seqlock snapshot (see RequestRec.stamp): never apply
                # a mixed record; a skipped mid-announce request is
                # simply not-yet-announced for this attempt
                s1 = req.stamp
                act = req.activate
                if s1 & 1 or req.valid != 1 or act == deacts[q]:  # line 20
                    continue
                func, args, vt = req.func, req.args, req.vtime
                if req.stamp != s1:
                    continue
                if clk is not None:
                    clk.merge(vt)          # Lamport receive (announce)
                if batch is not None:
                    # VectorApply: adopt now, apply the pass as one
                    # batch below (merge-first is clock-identical)
                    batch.append((q, func, args, act))
                    continue
                if traced:                                       # lines 21-22
                    with span("combine.host_apply"):
                        ret = self._apply(q, func, args, dst, p)
                else:
                    ret = self._apply(q, func, args, dst, p)
                wr(retval_base + q, ret)                         # line 23
                wr(deact_base + q, act)                          # line 24
                comb_round[q] = lval                             # line 25
                served += 1
            scan.set_metadata(adopted=len(batch) if batch is not None
                              else served)
        if batch:
            rets = self._apply_batch(batch, dst, p)
            for (q, _f, _a, act), ret in zip(batch, rets):
                wr(retval_base + q, ret)                         # line 23
                wr(deact_base + q, act)                          # line 24
                comb_round[q] = lval                             # line 25
            served = len(batch)
        if not self.S.vl(ver):                                   # line 26
            self._attempt_failed(dst, p)
            return "vl", None, served
        index_addr = deact_base + n + p
        wr(index_addr, 1 - rd(index_addr))                       # line 27
        pending = self._pre_publish(dst, p)
        nvm.pwb_fence(dst_base, self.rec_words, pending=pending)  # lines 28-29
        self.flush[p] = lval                                     # line 30
        if not self.S.sc(ver, dst):                              # line 31
            self._attempt_failed(dst, p)
            return "sc", None, served
        nvm.pwb_sync(self.s_addr, 1)                             # lines 32-33
        self._cas_flush(p, lval, lval + 1)                       # line 34
        # Measured degree: requests this publication served in one
        # pwb(S)+psync (scan + eliminated pairs).
        self.stats.record(served + self._attempt_served[p])
        if clk is not None:
            clk.advance(clk.profile.round_ns)
            self._round_end_vt = clk.now()
        # Hook runs after S is durable: safe point to recycle nodes the
        # published round removed.
        self._on_publish_success(dst, p)
        ret = nvm.read(self._retval_addr(self.S.load(), p))     # line 35
        return "published", ret, served

    # ---------------- helpers ------------------------------------------ #
    def _cas_flush(self, i: int, old: int, new: int) -> None:
        # per-instance mutex (guards this instance's flush[] only — a
        # class-level lock would serialize unrelated instances, e.g. a
        # split queue's enqueue and dequeue sides)
        with self._flush_mutex:
            if self.flush[i] == old:
                self.flush[i] = new

    def _apply(self, q: int, func: str, args: Any, slot: int,
               combiner: int) -> Any:
        return self.obj.apply(self.nvm, self._base(slot), func, args, ctx=self)

    def _apply_batch(self, batch, slot: int, combiner: int) -> list:
        """One collected simulation pass: ``batch`` is the adoptable
        announcements ``[(q, func, args, act), ...]`` in scan order.  A
        homogeneous batch goes through the object's VectorApply seam
        (one jitted kernel — DESIGN.md §11); a heterogeneous batch or a
        seam decline runs the identical per-op loop."""
        func = batch[0][1]
        if all(b[1] == func for b in batch):
            rets = self.obj.vector_apply(
                self.nvm, self._base(slot), func,
                [b[2] for b in batch], ctx=self)
            if rets is not None:
                return rets
        with span("combine.host_apply"):
            return [self._apply(q, f, a, slot, combiner)
                    for q, f, a, _act in batch]

    def trace_counters(self) -> dict:
        """``TRACE_COUNTERS`` summed over the threads, since the core
        was made."""
        return {k: sum(t[k] for t in self._tally)
                for k in self.TRACE_COUNTERS}

    # ---------------- structure hooks ---------------------------------- #
    def _begin_attempt(self, slot: int, p: int) -> None:
        """Called after a consistent copy, before the simulation loop."""

    def _pre_publish(self, slot: int, p: int):
        """Called before pwb(StateRec).  Returns the attempt-local node
        allocations to persist ahead of the StateRec (they must be
        durable before S can move), or None."""
        return None

    def _on_publish_success(self, slot: int, p: int) -> None:
        """Called right after a successful SC."""

    def _attempt_failed(self, slot: int, p: int) -> None:
        """Called when an attempt is abandoned (failed VL or SC) — return
        attempt-local node allocations to the pool."""

    PARK_QUANTUM = 1e-5   # seconds per backoff unit (real GIL handoff)

    def _backoff(self, p: int, grow: bool = False) -> None:
        """Adaptive backoff (Algorithm 3 line 2 / Algorithm 4 line 36).
        The window only opens after a failed attempt and closes again on
        success, so the uncontended fast path skips the RNG and the park
        entirely — contention is what the backoff is for.  Parking is a
        real (tiny) sleep, not a bare GIL yield: under CPython a yield
        spinner can win the GIL straight back and starve the publisher
        that would have served this thread's announced request."""
        if not self.backoff_enabled:
            return
        window = self._backoff_window[p]
        if grow:
            window = min(window * 2, self.MAX_BACKOFF)
            self._backoff_window[p] = window
        elif window <= 1:
            return
        else:
            self._backoff_window[p] = max(1, window // 2)
        time.sleep(self._rng.randint(0, window) * self.PARK_QUANTUM)
