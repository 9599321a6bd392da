"""Simulated Non-Volatile Main Memory with explicit epoch persistency.

Faithful model of the paper's memory assumptions (Section 2):

  * Memory is word-addressable; words are grouped into cache lines of
    ``LINE`` words.  Writes go to the *volatile* image (cache).
  * ``pwb(addr)`` queues a write-back of the cache line(s) covering
    ``addr`` — it does NOT wait.  The written-back value is the line's
    content at pwb-issue time (TSO: per-line program order preserved).
  * ``pfence()`` orders: every pwb issued before the fence completes
    before any pwb issued after it ("epochs").
  * ``psync()`` blocks until all previously issued pwbs are durable.
  * *Explicit* epoch persistency (Izraelevitz et al. [35], adopted by the
    paper): a line reaches NVMM **only** via pwb — no spontaneous
    evictions.

Crash semantics (``crash()``): the adversary picks how far the write-back
queue drained — all epochs before some cut are durable, plus an arbitrary
per-line-prefix-respecting subset of the cut epoch.  Everything volatile
is lost (reset to the persisted image).  Tests sweep/randomize the cut to
exercise every reachable post-crash state.

``crash_after_persist_ops`` arms a countdown so a ``SimulatedCrash`` is
raised in the middle of protocol code — this is how the crash-recovery
tests enumerate crash points *inside* the combiner.

Counters expose the paper's performance metrics: pwbs (counted per cache
line, so persistence principle P3 — contiguity — is visible in the
numbers), pfences, psyncs.  ``pwb_nop``/``psync_nop`` reproduce the
ablations of paper Figures 3 and 6.

Batching (DESIGN.md §5): the write-back queue stores line *runs* —
``(first_line, n_lines, snapshot)`` — not individual lines, so a
combining round's one contiguous StateRec pwb is one queue entry, one
slice copy, and one slice drain at psync, however many lines it covers.
``persist_lines`` coalesces several (addr, n_words) ranges into the
union of their cache lines in a single lock acquisition (duplicate lines
within one call count once — the coalescing the flat-combining and MOD
lines of work show is where persistence wins live), and ``copy_range``
gives the combiner's state copy a single slice-assign path.  Snapshots
are Python list slices (C-level pointer memcpy), not numpy arrays: NVM
words hold arbitrary Python payloads (tuples, strings), which object
ndarrays reject in range stores.
"""

from __future__ import annotations

import random
import threading
import time
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple, Union

LINE = 8  # words per simulated cache line


class SimulatedCrash(Exception):
    """Raised when an armed crash countdown fires inside protocol code."""


# --------------------------------------------------------------------- #
# Virtual-clock timing engine (DESIGN.md §6)                            #
# --------------------------------------------------------------------- #
# Host sleep granularity (~250us here) cannot express Optane-scale
# (1-3us) psync latencies, so the wall-clock ``persist_latency`` knob
# distorts rather than models.  The virtual clock instead *counts* time:
# every persistence instruction advances the calling thread's logical
# clock by a profile-defined cost, combining hand-offs merge clocks
# Lamport-style (a round's latency is the max over its participants, not
# the sum), and the makespan max(thread clocks) / ops is a deterministic
# ``modeled_us_per_op`` that survives host drift — the MOD / DFC
# evaluation methodology, machine-checkable in CI.

@dataclass(frozen=True)
class CostProfile:
    """Per-instruction modeled costs, all in nanoseconds."""

    name: str
    pwb_ns: float     # per cache line queued by a pwb (CLWB issue)
    pfence_ns: float  # per pfence (store-fence retire)
    psync_ns: float   # fixed device round trip per psync drain
    seek_ns: float    # per discontiguous run of lines drained (P3 visible)
    line_ns: float    # per line streamed within a contiguous run
    cas_ns: float     # per CAS / LL-SC on a shared word
    round_ns: float   # combiner round fusion/hand-off bookkeeping


#: Built-in profiles.  "wall-clock mode" is not a profile: it is
#: ``profile=None`` plus the pre-existing ``persist_latency`` sleep knob.
PROFILES: Dict[str, CostProfile] = {
    # Optane DCPMM shape: psync in the 1-3us band the ROADMAP names,
    # expensive seeks for scattered lines (XPLine write amplification).
    "optane": CostProfile("optane", pwb_ns=30.0, pfence_ns=30.0,
                          psync_ns=1500.0, seek_ns=300.0, line_ns=60.0,
                          cas_ns=25.0, round_ns=50.0),
    # NVDIMM-N / emulated-DRAM shape: flushes cheap, drains fast.
    "dram": CostProfile("dram", pwb_ns=15.0, pfence_ns=20.0,
                        psync_ns=120.0, seek_ns=30.0, line_ns=8.0,
                        cas_ns=25.0, round_ns=50.0),
    # Battery-backed / eADR shape: the persistence domain covers the
    # caches, so write-backs are ordering tokens, draining is ~free.
    "battery-backed": CostProfile("battery-backed", pwb_ns=5.0,
                                  pfence_ns=10.0, psync_ns=30.0,
                                  seek_ns=0.0, line_ns=0.0,
                                  cas_ns=25.0, round_ns=50.0),
}


def resolve_profile(profile: Union[str, CostProfile, None]
                    ) -> Optional[CostProfile]:
    if profile is None:
        return None
    if isinstance(profile, CostProfile):
        return profile
    try:
        return PROFILES[profile]
    except KeyError:
        raise ValueError(f"unknown cost profile {profile!r}; "
                         f"available: {sorted(PROFILES)}") from None


class VClock:
    """Per-thread logical clocks (ns) advanced by a ``CostProfile``.

    A "thread" is normally the OS thread, but a driver multiplexing many
    logical threads onto one OS thread (the deterministic modeled bench
    pass) rebinds the key with ``bind(logical_id)`` — this is how the
    handle layer charges costs to the handle's tid regardless of which
    OS thread runs the call.

    Merge rule (Lamport): an event that *receives* from another thread
    (a combiner adopting an announced request, a waiter picking up its
    response) merges the sender's timestamp with ``merge`` — so a
    combining round's modeled latency is the max over its participants,
    never the sum.  ``sync_device`` models the single per-DIMM
    write-back engine: concurrent psyncs serialize on device time.
    """

    __slots__ = ("profile", "_times", "_tls", "_device_free",
                 "_device_lock", "_tape")

    def __init__(self, profile: CostProfile) -> None:
        self.profile = profile
        self._times: Dict[Any, float] = {}
        self._tls = threading.local()
        self._device_free = 0.0
        self._device_lock = threading.Lock()
        # Optional event recorder (kernels.scan_replay.ClockTape) for the
        # periodic modeled-replay engine.  Single-threaded drivers only —
        # attach/detach via that module, never while workers run.
        self._tape = None

    def _key(self) -> Any:
        lid = getattr(self._tls, "lid", None)
        return threading.get_ident() if lid is None else lid

    @contextmanager
    def bind(self, logical_id: Any):
        """Charge this OS thread's costs to ``logical_id`` while bound."""
        tls = self._tls
        prev = getattr(tls, "lid", None)
        tls.lid = logical_id
        try:
            yield self
        finally:
            tls.lid = prev

    def now(self) -> float:
        t = self._times.get(self._key(), 0.0)
        if self._tape is not None:
            return self._tape.record_now(self._key(), t)
        return t

    def advance(self, ns: float) -> None:
        key = self._key()
        self._times[key] = self._times.get(key, 0.0) + ns
        if self._tape is not None:
            self._tape.record_adv(key, ns)

    def merge(self, t_ns: float) -> None:
        key = self._key()
        cur = self._times.get(key, 0.0)
        if t_ns > cur:
            self._times[key] = t_ns
        if self._tape is not None:
            self._tape.record_mrg(key, t_ns, cur)

    def sync_device(self, cost_ns: float) -> float:
        """Advance through the (serialized) write-back device: the drain
        starts when both this thread and the device are free."""
        key = self._key()
        with self._device_lock:
            t = self._times.get(key, 0.0)
            if self._device_free > t:
                t = self._device_free
            t += cost_ns
            self._device_free = t
        self._times[key] = t
        if self._tape is not None:
            self._tape.record_dev(key, cost_ns)
        return t

    def max_time_ns(self) -> float:
        """Makespan: the latest clock (modeled elapsed time so far).
        tuple() snapshots the dict atomically under the GIL — concurrent
        threads insert their key on their first clocked instruction."""
        return max(tuple(self._times.values()), default=0.0)

    def reset(self) -> None:
        self._times.clear()
        self._device_free = 0.0


class LineRecorder:
    """An NVM view for a writer whose lines are wanted: every call passes
    through, and each write into ``[lo, lo + n)`` notes the cache lines
    it covers, numbered from ``lo`` (line-aligned).  PBComb writes a
    round's StateRec through one (DESIGN.md §3)."""

    __slots__ = ("nvm", "lo", "hi", "lines")

    def __init__(self, nvm: NVM, lo: int, n: int) -> None:
        self.nvm = nvm
        self.lo = lo
        self.hi = lo + n
        self.lines: set = set()

    def write(self, addr: int, value: Any) -> None:
        self.nvm.write(addr, value)
        if self.lo <= addr < self.hi:
            self.lines.add((addr - self.lo) // LINE)

    def write_range(self, addr: int, values) -> None:
        self.nvm.write_range(addr, values)
        a, b = max(addr, self.lo), min(addr + len(values), self.hi)
        if a < b:
            self.lines.update(range((a - self.lo) // LINE,
                                    (b - 1 - self.lo) // LINE + 1))

    def runs(self) -> List[List[int]]:
        """The noted lines as ``[offset, n_words]`` runs from ``lo``, in
        order, merged where contiguous, cut at ``hi``."""
        runs: List[List[int]] = []
        end = self.hi - self.lo
        for line in sorted(self.lines):
            a = line * LINE
            b = min(a + LINE, end)
            if runs and runs[-1][0] + runs[-1][1] == a:
                runs[-1][1] = b - runs[-1][0]
            else:
                runs.append([a, b - a])
        return runs

    def __getattr__(self, name: str) -> Any:
        return getattr(self.nvm, name)


class NVM:
    #: Machine-off flag: False for the in-process NVM (a SimulatedCrash
    #: unwinds every thread synchronously, so no one keeps running).
    #: The multiprocess ShmNVM overrides this with a shared-memory flag
    #: that surviving worker processes poll in their wait loops.
    halted = False

    #: Device topology: the in-thread NVM models ONE DIMM (a single
    #: write-back device).  The multi-segment ShmNVM overrides this and
    #: the segment accessors below (DESIGN.md §8); they exist here so
    #: benches and the runtime's placement policy run backend-agnostic.
    segments = 1

    def __init__(self, n_words: int = 1 << 20, *, pwb_nop: bool = False,
                 psync_nop: bool = False,
                 persist_latency: float = 0.0,
                 profile: Union[str, CostProfile, None] = None,
                 backend: Optional[Any] = None,
                 audit: bool = False) -> None:
        """``persist_latency``: seconds a psync blocks the calling thread
        (models NVMM write-back latency, ~1-3us on Optane DCPMM; the
        benchmark harness sets it so the paper's cost trends — one psync
        per combining ROUND vs one per op — are visible on a host where
        memory writes are otherwise free).  The sleep happens OUTSIDE the
        queue lock: other threads keep announcing while the combiner
        waits, which is exactly the contention window combining exploits.

        ``profile``: a ``CostProfile`` (or its name) engaging the virtual
        clock — every persistence instruction then advances the calling
        thread's logical clock by the modeled cost instead of sleeping
        (``self.clock``; see module docs / DESIGN.md §6).  The NOP
        ablations compose: a nop'd instruction charges nothing.

        ``backend``: the execution backend the protocols draw their
        volatile shared primitives from (DESIGN.md §7); defaults to the
        thread backend.  The multiprocess path constructs ``ShmNVM``
        with a ``ShmBackend`` instead.

        ``audit``: opt-in persist-ordering detector (DESIGN.md §10) —
        attaches a ``repro.analysis.audit.PersistAudit`` tracking
        per-line flush state and happens-before, exposed as
        ``self.audit``.  Pins ``force_discrete`` so the fused
        persistence sentences take their counter-identical discrete
        fallbacks: counters and modeled costs stay byte-identical to a
        non-audited run.  Silently disabled under the pwb/psync NOP
        ablations (there is no real persistence to audit there).
        """
        if backend is None:
            from .backend import ThreadBackend
            backend = ThreadBackend()
        self.backend = backend
        self.n_words = n_words
        self._vol: List[Any] = [0] * n_words        # volatile (cache) image
        self._dur: List[Any] = [0] * n_words        # durable (NVMM) image
        # Write-back queue: list of epochs; each epoch is an ordered list
        # of line runs (first_line, n_lines, snapshot_of_run_words) taken
        # at pwb-issue time.
        self._epochs: List[List[Tuple[int, int, List[Any]]]] = [[]]
        # Line 0 is reserved: address 0 doubles as the NULL pointer for the
        # linked structures, so no allocation may ever receive it.
        self._alloc_ptr = LINE
        self._lock = threading.Lock()
        self.pwb_nop = pwb_nop
        self.psync_nop = psync_nop
        self.persist_latency = persist_latency
        prof = resolve_profile(profile)
        self.clock: Optional[VClock] = VClock(prof) if prof else None
        # Test knob: force every fused persistence sentence onto its
        # discrete-instruction fallback (the fused-vs-discrete
        # equivalence property tests pin cost and counter equality).
        self.force_discrete = False
        self.counters: Dict[str, int] = {
            "pwb": 0, "pfence": 0, "psync": 0, "crashes": 0}
        # Crash-point injection: countdown on persistence "events".
        self._crash_countdown: Optional[int] = None
        self._crash_rng: Optional[random.Random] = None
        # Instruction-kind crash-point injector (repro.fuzz): consulted
        # at every pwb/pfence/psync tick with the instruction kind, so a
        # fuzzer can land a crash at "the 3rd psync" rather than the
        # aggregate countdown's "the Nth persistence event".  None when
        # disarmed — zero cost on the default path, same contract as the
        # audit seam.
        self._injector: Optional[Any] = None
        self._audit = None
        if audit and not (pwb_nop or psync_nop):
            from ..analysis.audit import PersistAudit   # lazy: no cycle
            self._audit = PersistAudit(self)
            self.force_discrete = True
            self._install_audit_hooks()

    @property
    def audit(self):
        """The attached ``PersistAudit`` (None when auditing is off)."""
        return self._audit

    def _install_audit_hooks(self) -> None:
        """Shadow the hot volatile accessors with auditing wrappers as
        INSTANCE attributes: the default path (audit off) keeps the bare
        class methods, so auditing costs nothing when not engaged.
        Wrapping the *bound* methods resolves subclass overrides
        (ShmNVM) for free."""
        aud = self._audit
        read, write = self.read, self.write
        read_range, write_range = self.read_range, self.write_range
        copy_range = self.copy_range

        def read_a(addr):
            aud.on_read(addr)
            return read(addr)

        def read_range_a(addr, n):
            aud.on_read(addr, n)
            return read_range(addr, n)

        def write_a(addr, value):
            write(addr, value)
            aud.on_write(addr, 1)

        def write_range_a(addr, values):
            write_range(addr, values)
            aud.on_write(addr, len(values))

        def copy_range_a(dst, src, n):
            copy_range(dst, src, n)
            aud.on_write(dst, n)

        self.read = read_a
        self.read_range = read_range_a
        self.write = write_a
        self.write_range = write_range_a
        self.copy_range = copy_range_a

    # ------------------------------------------------------------------ #
    # Allocation                                                         #
    # ------------------------------------------------------------------ #
    def alloc(self, n_words: int, align_line: bool = True,
              segment: Optional[int] = None) -> int:
        """Bump-allocate ``n_words``; line-aligned so P3 layouts are real.
        ``segment`` is accepted for interface parity with the
        multi-segment ShmNVM (this NVM models one DIMM; only 0/None)."""
        if segment not in (None, 0):
            raise ValueError("the in-thread NVM models a single DIMM "
                             f"(segment {segment} does not exist)")
        with self._lock:
            if align_line and self._alloc_ptr % LINE:
                self._alloc_ptr += LINE - self._alloc_ptr % LINE
            base = self._alloc_ptr
            self._alloc_ptr += n_words
            if self._alloc_ptr > self.n_words:
                raise MemoryError("simulated NVMM exhausted")
            return base

    # ------------------------------------------------------------------ #
    # Volatile-image access (normal loads/stores)                        #
    # ------------------------------------------------------------------ #
    def read(self, addr: int) -> Any:
        return self._vol[addr]

    def write(self, addr: int, value: Any) -> None:
        self._vol[addr] = value

    def read_range(self, addr: int, n: int) -> List[Any]:
        return self._vol[addr:addr + n]

    def write_range(self, addr: int, values: List[Any]) -> None:
        self._vol[addr:addr + len(values)] = values

    def copy_range(self, dst: int, src: int, n: int) -> None:
        """Volatile memcpy — the combiner's state copy as one slice
        assignment instead of a read_range/write_range round trip."""
        vol = self._vol
        vol[dst:dst + n] = vol[src:src + n]

    # ------------------------------------------------------------------ #
    # Persistence instructions                                           #
    # ------------------------------------------------------------------ #
    def _tick_crash_point(self, kind: str = "") -> None:
        inj = self._injector
        if inj is not None and inj.tick(kind):
            self._injector = None     # one shot: fire, then disarm
            self.crash(inj.rng)
            raise SimulatedCrash()
        if self._crash_countdown is not None:
            self._crash_countdown -= 1
            if self._crash_countdown < 0:
                self._crash_countdown = None
                self.crash(self._crash_rng)
                raise SimulatedCrash()

    def arm_injector(self, injector: Any) -> None:
        """Attach an instruction-kind crash-point injector: an object
        whose ``tick(kind) -> bool`` is called at every pwb/pfence/psync
        (True = crash NOW, adversarial drain by ``injector.rng``).
        Unlike the ``arm_crash`` countdown, the injector survives
        ``disarm_crash`` — which is what lets a fuzzer crash INSIDE
        ``recover`` (recover's first act is ``disarm_crash``).  Arming
        pins the fused persistence sentences onto their discrete
        fallbacks so ticks land between individual instructions."""
        self._injector = injector

    def disarm_injector(self) -> None:
        self._injector = None

    def pwb(self, addr: int, n_words: int = 1) -> None:
        """Queue write-back of every line covering [addr, addr+n_words).

        One contiguous run is one queue entry and one slice snapshot,
        however many lines it covers; the counter still counts lines.
        """
        first = addr // LINE
        n_lines = (addr + n_words - 1) // LINE - first + 1
        with self._lock:
            if not self.pwb_nop:
                self._epochs[-1].append(
                    (first, n_lines,
                     self._vol[first * LINE:(first + n_lines) * LINE]))
            self.counters["pwb"] += n_lines
        if self.clock is not None and not self.pwb_nop:
            self.clock.advance(n_lines * self.clock.profile.pwb_ns)
        if self._audit is not None:
            self._audit.on_pwb(((first, n_lines),))
        self._tick_crash_point("pwb")

    # Explicit alias: round persistence paths call this so the intent —
    # one coalesced range, not a per-word loop — reads at the call site.
    pwb_range = pwb

    def persist_lines(self, ranges) -> None:
        """Queue write-back of the UNION of cache lines covering several
        ``(addr, n_words)`` ranges — one persistence event, one lock
        acquisition.  Lines named by more than one range are snapshotted
        (and counted) once: this is the cache-line coalescing a combining
        round gets for free by persisting all its node/state touches
        together (P3)."""
        if isinstance(ranges, list) and len(ranges) == 1:
            # single range: plain pwb (same event count, no set/merge)
            addr, n_words = ranges[0]
            self.pwb(addr, n_words)
            return
        runs = self._pending_lines(ranges)
        if not runs:
            return
        n_total = sum(n for _first, n in runs)
        vol = self._vol
        with self._lock:
            if not self.pwb_nop:
                epoch = self._epochs[-1]
                for first, n_lines in runs:
                    epoch.append(
                        (first, n_lines,
                         vol[first * LINE:(first + n_lines) * LINE]))
            self.counters["pwb"] += n_total
        if self.clock is not None and not self.pwb_nop:
            self.clock.advance(n_total * self.clock.profile.pwb_ns)
        if self._audit is not None:
            self._audit.on_pwb(runs)
        self._tick_crash_point("pwb")

    def pfence(self) -> None:
        had_pending = False
        with self._lock:
            self.counters["pfence"] += 1
            if self._epochs[-1]:
                had_pending = True
                self._epochs.append([])
        if self.clock is not None:
            self.clock.advance(self.clock.profile.pfence_ns)
        if self._audit is not None:
            self._audit.on_pfence(had_pending)
        self._tick_crash_point("pfence")

    # ---------------- fused round-commit paths ------------------------ #
    # A combining round ends with a fixed persistence sentence — e.g.
    # PBComb: pwb(StateRec); pfence; MIndex := ind; pwb(&MIndex); psync.
    # Issuing it as four locked calls costs more simulator overhead than
    # the protocol work it models.  The fused paths below execute the
    # SAME sentence under one lock acquisition with identical counter
    # arithmetic and durable effect; whenever an observer could tell the
    # difference — an armed crash countdown (ticks must land *between*
    # instructions), pwb/psync NOP ablations, or a psync cost model —
    # they fall back to the separate instructions.

    def _fast_ok(self) -> bool:
        return (self._crash_countdown is None and self._injector is None
                and not self.pwb_nop
                and not self.psync_nop and not self.persist_latency
                and not self.force_discrete and self._audit is None)

    def _pending_lines(self, pending) -> List[Tuple[int, int]]:
        """Dedupe/merge (addr, n_words) ranges to [first, n_lines] runs
        (same coalescing as persist_lines, for the fused paths)."""
        lines = set()
        add = lines.add
        for addr, n_words in pending:
            first = addr // LINE
            last = (addr + n_words - 1) // LINE
            add(first)
            while first < last:
                first += 1
                add(first)
        runs: List[List[int]] = []
        for line in sorted(lines):
            if runs and line == runs[-1][0] + runs[-1][1]:
                runs[-1][1] += 1
            else:
                runs.append([line, 1])
        return runs

    def pwb_fence(self, addr: int, n_words: int, pending=None) -> None:
        """``[persist_lines(pending);] pwb_range(addr, n_words); pfence()``
        fused.  ``pending`` carries a round's node touches so the whole
        pre-publish persistence sentence is one lock acquisition."""
        if not self._fast_ok():
            if pending:
                self.persist_lines(pending)
            self.pwb_range(addr, n_words)
            self.pfence()
            return
        runs = self._pending_lines(pending) if pending else ()
        first = addr // LINE
        n_lines = (addr + n_words - 1) // LINE - first + 1
        vol = self._vol
        with self._lock:
            epoch = self._epochs[-1]
            n_pending = 0
            for pfirst, pn in runs:
                epoch.append(
                    (pfirst, pn, vol[pfirst * LINE:(pfirst + pn) * LINE]))
                n_pending += pn
            epoch.append(
                (first, n_lines, vol[first * LINE:(first + n_lines) * LINE]))
            self._epochs.append([])
            c = self.counters
            c["pwb"] += n_lines + n_pending
            c["pfence"] += 1
        clock = self.clock
        if clock is not None:
            # Charge the exact advance sequence of the discrete fallback:
            # persist_lines(pending); pwb_range(addr); pfence.
            prof = clock.profile
            if n_pending:
                clock.advance(n_pending * prof.pwb_ns)
            clock.advance(n_lines * prof.pwb_ns)
            clock.advance(prof.pfence_ns)

    def pwb_sync(self, addr: int, n_words: int = 1) -> None:
        """``pwb(addr); psync()`` fused: queue the line(s), then drain
        the whole write-back queue straight to the durable image."""
        if not self._fast_ok():
            self.pwb(addr, n_words)
            self.psync()
            return
        first = addr // LINE
        n_lines = (addr + n_words - 1) // LINE - first + 1
        clock = self.clock
        drained: Optional[List[Tuple[int, int]]] = \
            [] if clock is not None else None
        with self._lock:
            dur, vol = self._dur, self._vol
            for epoch in self._epochs:
                for efirst, en, snap in epoch:
                    dur[efirst * LINE:efirst * LINE + len(snap)] = snap
                    if drained is not None:
                        drained.append((efirst, en))
            a, b = first * LINE, (first + n_lines) * LINE
            dur[a:b] = vol[a:b]
            if drained is not None:
                drained.append((first, n_lines))
            self._epochs = [[]]
            c = self.counters
            c["pwb"] += n_lines
            c["psync"] += 1
        if clock is not None:
            # Exact discrete sequence: pwb(addr); psync().
            clock.advance(n_lines * clock.profile.pwb_ns)
            clock.sync_device(self._drain_cost_ns(drained))

    def commit_round(self, runs, index_addr: int, index_value: Any,
                     pending=None) -> None:
        """PBComb's full round commit (Algorithm 2 lines 22-27):
        ``[persist_lines(pending);] pwb(StateRec); pfence;
        MIndex := v; pwb(&MIndex); psync`` — ``pending`` carries the
        round's node touches (Algorithm 5 line 24).  ``runs`` are the
        ``(addr, n_words)`` runs of the StateRec that may differ from its
        durable image, in order and merged: the whole record as one run
        in the paper's form, fewer lines where PBComb goes by lines
        (DESIGN.md §3).  Their write-back is one persistence event, one
        crash tick even when there are none."""
        if not self._fast_ok():
            if pending:
                self.persist_lines(pending)
            if runs:
                self.persist_lines(runs)
            else:
                self._tick_crash_point("pwb")
            self.pfence()
            self.write(index_addr, index_value)
            self.pwb(index_addr, 1)
            self.psync()
            return
        pruns = self._pending_lines(pending) if pending else ()
        state_runs = [(addr // LINE, (addr + n - 1) // LINE - addr // LINE
                       + 1) for addr, n in runs]
        clock = self.clock
        drained: Optional[List[Tuple[int, int]]] = \
            [] if clock is not None else None
        with self._lock:
            dur, vol = self._dur, self._vol
            # drain epochs queued before this commit, the round's node
            # lines, the StateRec, then MIndex — everything the round's
            # psync would have drained
            for epoch in self._epochs:
                for efirst, en, snap in epoch:
                    dur[efirst * LINE:efirst * LINE + len(snap)] = snap
                    if drained is not None:
                        drained.append((efirst, en))
            n_pending = 0
            for pfirst, pn in pruns:
                a = pfirst * LINE
                b = a + pn * LINE
                dur[a:b] = vol[a:b]
                n_pending += pn
                if drained is not None:
                    drained.append((pfirst, pn))
            n_lines = 0
            for sfirst, sn in state_runs:
                a = sfirst * LINE
                b = a + sn * LINE
                dur[a:b] = vol[a:b]
                n_lines += sn
                if drained is not None:
                    drained.append((sfirst, sn))
            vol[index_addr] = index_value
            iline = index_addr // LINE
            a = iline * LINE
            dur[a:a + LINE] = vol[a:a + LINE]
            if drained is not None:
                drained.append((iline, 1))
            self._epochs = [[]]
            c = self.counters
            c["pwb"] += n_lines + n_pending + 1
            c["pfence"] += 1
            c["psync"] += 1
        if clock is not None:
            # Exact discrete sequence: persist_lines(pending);
            # pwb(StateRec); pfence; pwb(&MIndex); psync — same advance
            # granularity, same drained multiset (duplicates included),
            # so the charged floats are bit-identical to the fallback's.
            prof = clock.profile
            if n_pending:
                clock.advance(n_pending * prof.pwb_ns)
            if state_runs:
                clock.advance(n_lines * prof.pwb_ns)
            clock.advance(prof.pfence_ns)
            clock.advance(1 * prof.pwb_ns)
            clock.sync_device(self._drain_cost_ns(drained))

    # One write-back engine per DIMM: concurrent psyncs serialize on the
    # device (an infinite-bandwidth model would let per-op-persist
    # baselines overlap all their syncs for free).
    _device_lock = threading.Lock()
    SEEK_COST = 4e-6     # per discontiguous run of lines (P3 visible!)
    STREAM_COST = 5e-7   # per line within a contiguous run

    @staticmethod
    def _run_stats(drained: List[Tuple[int, int]]) -> Tuple[int, int]:
        """(discontiguous runs, total lines) over drained (first, n)
        entries.  Lines drained more than once (queued in several
        epochs) count each time — they cost device writes each time.
        Contiguous layouts (persistence principle P3) drain in few runs,
        scattered ones pay a seek per run."""
        drained = sorted(drained)
        runs, prev_end, total_lines = 0, None, 0
        for first, n_lines in drained:
            if prev_end is None or first > prev_end + 1:
                runs += 1
            end = first + n_lines - 1
            prev_end = end if prev_end is None else max(prev_end, end)
            total_lines += n_lines
        return runs, total_lines

    def _drain_cost_ns(self, drained: List[Tuple[int, int]]) -> float:
        """Modeled cost of one psync draining ``drained``: fixed device
        round trip + seek per discontiguous run + stream per line."""
        prof = self.clock.profile
        if not drained:
            return prof.psync_ns
        runs, total_lines = self._run_stats(drained)
        return (prof.psync_ns + runs * prof.seek_ns
                + total_lines * prof.line_ns)

    def psync(self) -> None:
        aud = self._audit
        sync_now = (self.clock.now()
                    if aud is not None and self.clock is not None else 0.0)
        drained: List[Tuple[int, int]] = []
        with self._lock:
            self.counters["psync"] += 1
            if not self.psync_nop:
                dur = self._dur
                for epoch in self._epochs:
                    for first, n_lines, snap in epoch:
                        dur[first * LINE:first * LINE + len(snap)] = snap
                        drained.append((first, n_lines))
                self._epochs = [[]]
        if self.clock is not None and not self.psync_nop:
            self.clock.sync_device(self._drain_cost_ns(drained))
        if aud is not None:
            aud.on_psync(drained, sync_now)
        if drained and self.persist_latency:
            # wall-clock cost model (sleep): same shape as the virtual
            # one, bounded below by host sleep granularity (~250us here,
            # the distortion the virtual clock exists to remove).
            runs, total_lines = self._run_stats(drained)
            cost = (self.persist_latency + runs * self.SEEK_COST
                    + total_lines * self.STREAM_COST)
            with NVM._device_lock:
                time.sleep(cost)
        self._tick_crash_point("psync")

    # ------------------------------------------------------------------ #
    # Crash / recovery                                                   #
    # ------------------------------------------------------------------ #
    def arm_crash(self, after_persist_ops: int,
                  rng: Optional[random.Random] = None, *,
                  lose_segment: Optional[int] = None) -> None:
        """Raise SimulatedCrash after ``after_persist_ops`` more pwb/pfence/
        psync calls (the crash resolves the write-back queue adversarially
        with ``rng``, or deterministically drains nothing if rng is None).

        ``lose_segment`` is the multi-segment ShmNVM's partial-failure
        knob (one DIMM loses all pending write-backs while the others
        drain fully); the in-thread NVM models a single DIMM, so only
        None is accepted here."""
        if lose_segment is not None:
            raise ValueError("the in-thread NVM models a single DIMM "
                             "(lose_segment requires the multi-segment "
                             "ShmNVM)")
        self._crash_countdown = after_persist_ops
        self._crash_rng = rng

    def disarm_crash(self) -> None:
        self._crash_countdown = None

    def crash(self, rng: Optional[random.Random] = None) -> None:
        """System-wide crash.

        Resolves the write-back queue: with ``rng``, a random cut epoch is
        chosen; all earlier epochs drain fully, and a per-line prefix subset
        of the cut epoch drains.  Without ``rng`` nothing pending drains
        (the most adversarial *loss* outcome; note the dual adversarial
        outcome — everything drained — is exercised by rng sweeps).
        Afterwards the volatile image is reset to the durable image.
        """
        with self._lock:
            self.counters["crashes"] += 1
            epochs = self._epochs
            if rng is not None and epochs:
                cut = rng.randint(0, len(epochs) - 1)
                for epoch in epochs[:cut]:
                    for first, _n, snap in epoch:
                        self._dur[first * LINE:first * LINE + len(snap)] = snap
                # Partial drain of the cut epoch: expand its runs back to
                # per-line entries (cold path — only on crash) and keep a
                # prefix per line so same-line program order is respected.
                cut_epoch: List[Tuple[int, List[Any]]] = []
                for first, n_lines, snap in epochs[cut]:
                    for j in range(n_lines):
                        cut_epoch.append(
                            (first + j, snap[j * LINE:(j + 1) * LINE]))
                taken_upto: Dict[int, int] = {}
                for i, (line, _snap) in enumerate(cut_epoch):
                    if rng.random() < 0.5:
                        taken_upto[line] = i
                for i, (line, snap) in enumerate(cut_epoch):
                    if i <= taken_upto.get(line, -1):
                        self._dur[line * LINE:(line + 1) * LINE] = snap
            self._epochs = [[]]
            self._vol = list(self._dur)
            self._crash_countdown = None
        if self._audit is not None:
            self._audit.on_crash()

    # ------------------------------------------------------------------ #
    # Introspection                                                      #
    # ------------------------------------------------------------------ #
    def durable_read(self, addr: int) -> Any:
        return self._dur[addr]

    def pending_lines(self) -> int:
        with self._lock:
            return sum(n for e in self._epochs for _first, n, _snap in e)

    def current_segment(self) -> int:
        """The segment new allocations default to (always 0 here; the
        multi-segment ShmNVM returns its placement-context segment)."""
        return 0

    def placement(self, segment: int):
        """Segment-affinity context manager (interface parity with the
        multi-segment ShmNVM; the single-DIMM NVM only has segment 0)."""
        if segment != 0:
            raise ValueError("the in-thread NVM models a single DIMM "
                             f"(segment {segment} does not exist)")
        return contextmanager(lambda: iter([self]))()

    def segment_counters(self) -> List[Dict[str, int]]:
        """Per-segment device accounting; one entry for the single
        modeled DIMM (mirrors ``ShmNVM.segment_counters``)."""
        return [{"segment": 0, "pwb": self.counters["pwb"],
                 "psync": self.counters["psync"], "ring_spills": 0,
                 "words_used": self._alloc_ptr - LINE}]

    def occupancy(self) -> Dict[str, int]:
        """Memory gauge (mirrors ``ShmNVM.occupancy``): this backend
        has no blob heap, so the footprint is the allocated words at a
        nominal 8 bytes each."""
        words = self._alloc_ptr - LINE
        return {"backend": "threads", "words_used": words,
                "word_bytes": words * 8, "live_chunks": 0,
                "blob_live_bytes": 0, "blob_bump_bytes": 0,
                "occupancy_bytes": words * 8}

    def modeled_time_us(self) -> float:
        """Virtual-clock makespan in microseconds (0.0 when no profile
        is engaged): max over per-thread logical clocks."""
        return self.clock.max_time_ns() / 1e3 if self.clock else 0.0

    def reset_counters(self) -> None:
        for k in self.counters:
            self.counters[k] = 0
        if self._audit is not None:
            self._audit.reset_metrics()
