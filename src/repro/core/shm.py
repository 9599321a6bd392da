"""Shared-memory multiprocess execution backend (DESIGN.md §7, §8).

Under CPython's GIL the *measured* combining degree is pinned near 1 —
only the modeled pass could stage paper-scale rounds (ROADMAP).  This
module moves every word the protocols share into one
``multiprocessing.shared_memory`` segment so fork()ed worker processes
announce/combine against the same board with true parallelism:

  * ``ShmNVM`` — the simulated NVMM (volatile + durable images, the
    epoch write-back rings, pwb/pfence/psync counters, crash countdown
    and the machine-off ``halted`` flag) entirely in shared memory,
    guarded by one fork-inherited lock.  Same public interface and
    crash semantics as ``NVM``; the fused persistence sentences fall
    back to their discrete forms (``_fast_ok`` is False), which keeps
    pwb/pfence/psync counter arithmetic identical to the in-thread
    backend — that is what the replay-equivalence tests pin.
  * ``ShmBackend`` — the ``core.backend`` seam over the same segment:
    lock-striped CAS emulation for AtomicInt/AtomicRef/SRef, shared
    request boards, cells, int arrays, degree counters, and the blob
    heap below.
  * ``BlobHeap`` — a slab/free-list allocator inside the segment for
    variable-length pickled payloads (DESIGN.md §8).  Values that do
    not fit the 16-byte inline word codec (tuples, dicts, long
    strings, big ints, byte strings...) are stored as immutable,
    generation-tagged, refcounted chunks; the word stores a blob REF.
    Payload-before-tag publication order means a torn blob value is
    never observable: readers validate the generation before and after
    copying the bytes and retry the word read on a mismatch.
  * multi-segment NVM (NUMA-ish, ROADMAP follow-up): the word space is
    striped into ``segments`` equal spans, each with its own write-back
    ring, modeled sync device, allocation pointer, and pwb/psync/spill
    accounting.  Structures are placed on segments by the runtime's
    affinity policy (``CombiningRuntime(backend="shm", segments=N)``).

Word encoding: each simulated NVM word (and each board/cell slot) is
``WORD_I64`` int64s — a tag plus 16 payload bytes — covering ints,
None, bools, floats and short strings inline; anything richer goes to
the blob heap when the word belongs to a backend (``_Words`` carries
the heap), or raises ``TypeError`` through the bare module-level
``encode`` (which has no heap to allocate from).

Atomicity notes.  Aligned 8-byte loads/stores through a ``cast('q')``
memoryview are single C-level stores; mutating operations (cas,
fetch_add, SC) additionally serialize through a striped lock, and
multi-i64 slots order payload-before-tag on write (tag-before-payload
on read) with the protocols' own ``valid`` flags providing the
publication barrier — the same discipline the GIL gave the thread
backend for free.

Blob durability model (DESIGN.md §8).  Chunks are immutable for the
lifetime of one allocation (generation): the bytes a pwb would
snapshot are by construction the bytes a later psync drains, so the
epoch ring records blob REFS (pinned via the refcount) rather than
byte copies, and charges the pwb counter with the chunk's cache-line
footprint — payload layout is visible in the numbers, which is the
point (MOD / Fatourou-et-al. FIFO-queue line of work).  A chunk is
reclaimed onto its size-class free list only when no volatile word, no
durable word and no pending ring entry references it, so a post-crash
durable image can always decode every blob it names.

Fork discipline: create the runtime, its structures, and the worker
pool IN THAT ORDER — mp primitives and shared views are inherited by
fork, so everything shared must exist before ``spawn_workers``.
"""

from __future__ import annotations

import atexit
import itertools
import multiprocessing
import os
import pickle
import struct
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

from . import tracing
from .atomics import Counters
from .backend import ThreadBackend
from .nvm import LINE, NVM, SimulatedCrash

WORD_I64 = 3          # int64s per codec word: tag + 2 payload words

# value tags
_T_INT = 0
_T_NONE = 1
_T_FALSE = 2
_T_TRUE = 3
_T_FLOAT = 4
_T_BLOB = 5           # payload a = blob byte offset, b = generation
_T_STR = 16           # tag = _T_STR + utf-8 byte length (0..16)
_STR_MAX = 16

_I64_MIN, _I64_MAX = -(1 << 63), (1 << 63) - 1

#: retries before a blob read declares the word permanently unstable
#: (a torn word would mean a writer died mid-publication, which the
#: payload-before-tag order makes impossible; this bounds the loop)
_STALE_RETRIES = 10_000


def encode(value: Any) -> Tuple[int, int, int]:
    """Python value -> (tag, payload_a, payload_b) for the INLINE word
    domain: ints, None, bools, floats, short strings.  Backend words go
    through ``_Words.set``, which falls back to the blob heap for
    anything this function rejects."""
    if value is None:
        return _T_NONE, 0, 0
    if value is True:
        return _T_TRUE, 0, 0
    if value is False:
        return _T_FALSE, 0, 0
    if type(value) is int:
        if not _I64_MIN <= value <= _I64_MAX:
            raise TypeError(f"int {value!r} exceeds the shm backend's "
                            "64-bit inline word")
        return _T_INT, value, 0
    if type(value) is float:
        return _T_FLOAT, struct.unpack("<q", struct.pack("<d", value))[0], 0
    if type(value) is str:
        raw = value.encode("utf-8")
        if len(raw) > _STR_MAX:
            raise TypeError(f"str {value!r} exceeds {_STR_MAX} utf-8 "
                            "bytes (inline shm word)")
        raw = raw.ljust(_STR_MAX, b"\0")
        return (_T_STR + len(value.encode('utf-8')),
                int.from_bytes(raw[:8], "little", signed=True),
                int.from_bytes(raw[8:], "little", signed=True))
    raise TypeError(
        f"inline shm words store ints, floats, bools, None and short "
        f"strings; got {type(value).__name__}: {value!r} (rich payloads "
        "go through a backend word, which blob-encodes them)")


def decode(tag: int, a: int, b: int) -> Any:
    if tag == _T_INT:
        return a
    if tag == _T_NONE:
        return None
    if tag == _T_TRUE:
        return True
    if tag == _T_FALSE:
        return False
    if tag == _T_FLOAT:
        return struct.unpack("<d", struct.pack("<q", a))[0]
    if _T_STR <= tag <= _T_STR + _STR_MAX:
        raw = (a.to_bytes(8, "little", signed=True)
               + b.to_bytes(8, "little", signed=True))
        return raw[:tag - _T_STR].decode("utf-8")
    if tag == _T_BLOB:
        raise ValueError("blob word needs its backend heap to decode "
                         "(use _Words.get, not the bare decode)")
    raise ValueError(f"corrupt shm word tag {tag}")


# --------------------------------------------------------------------- #
# Blob heap                                                             #
# --------------------------------------------------------------------- #
_BLOB_GRANULE = 64        # bytes: smallest chunk class AND line size for
_BLOB_LINE = 64           # the blob write-back accounting
_BLOB_HDR = 16            # per-chunk in-image header: gen, nbytes
_BLOB_CLASSES = 16        # 64B << 15 = 2MB largest chunk


class BlobHeap:
    """Slab/free-list allocator for variable-length payloads inside the
    backend segment (DESIGN.md §8).

    Chunks are power-of-two size classes (64B..2MB), carved from one
    bump region; a freed chunk goes on its class free list and is only
    re-handed-out there, so chunks never overlap and never change
    class.  Each chunk carries an in-image header ``[gen, nbytes]``
    and side metadata (refcount, authoritative generation, class, free
    link) OUTSIDE the imaged areas, so crash restores never clobber
    allocator state.

    Invariants:
      * a chunk's payload is immutable for the lifetime of one
        generation — publication is alloc+write THEN word publish;
      * ``rc`` counts every volatile word, durable word and pending
        ring-entry reference; reclamation only at rc == 0;
      * ``gen`` is bumped (under the alloc lock) BEFORE a reused
        chunk's payload is rewritten, so a reader holding a stale ref
        observes the mismatch no later than its post-copy check.
    """

    __slots__ = ("mv", "raw", "base_b", "cap_b", "_rc", "_gen", "_cls",
                 "_nxt", "lock", "_meta_heads")

    def __init__(self, backend: "ShmBackend") -> None:
        self.mv = backend.mv
        self.raw = backend.raw
        self.base_b = backend.blob_base * 8       # absolute byte offset
        self.cap_b = backend.blob_bytes
        n_gran = backend.blob_bytes // _BLOB_GRANULE
        side = backend.blob_side_base
        self._rc = side
        self._gen = side + n_gran
        self._cls = side + 2 * n_gran
        self._nxt = side + 3 * n_gran
        self.lock = backend._alloc_lock
        self._meta_heads = _M_CLASS0

    # ------------- allocation ------------------------------------------ #
    def alloc(self, data: bytes) -> Tuple[int, int]:
        """Allocate a chunk, write header+payload, rc=1.  Returns
        (byte offset, generation) — the word's (a, b) payload."""
        mv = self.mv
        need = _BLOB_HDR + len(data)
        cls_b = max(_BLOB_GRANULE, 1 << (need - 1).bit_length())
        ci = (cls_b // _BLOB_GRANULE).bit_length() - 1
        if ci >= _BLOB_CLASSES or cls_b > self.cap_b:
            raise TypeError(f"payload of {len(data)} bytes exceeds the "
                            "blob heap's largest chunk class")
        with self.lock:
            head = mv[self._meta_heads + ci]
            if head:
                off = head - 1
                g = off // _BLOB_GRANULE
                mv[self._meta_heads + ci] = mv[self._nxt + g]
            else:
                off = mv[_M_BLOB_BUMP]
                if off + cls_b > self.cap_b:
                    raise MemoryError(
                        f"shm blob heap exhausted ({self.cap_b} bytes)")
                mv[_M_BLOB_BUMP] = off + cls_b
                g = off // _BLOB_GRANULE
                mv[self._cls + g] = cls_b
            gen = mv[self._gen + g] + 1
            mv[self._gen + g] = gen
            mv[self._rc + g] = 1
            mv[_M_BLOBBED] = 1
            # gen first (stale readers of a reused chunk bail before the
            # payload is overwritten), then length, then the bytes
            qb = (self.base_b + off) // 8
            mv[qb] = gen
            mv[qb + 1] = len(data)
            b0 = self.base_b + off + _BLOB_HDR
            self.raw[b0:b0 + len(data)] = data
            return off, gen

    # ------------- read ------------------------------------------------ #
    def read(self, off: int, gen: int) -> Optional[bytes]:
        """Chunk payload for generation ``gen``, or None when the chunk
        was reallocated since (the caller re-reads the word)."""
        mv = self.mv
        qb = (self.base_b + off) // 8
        if mv[qb] != gen:
            return None
        n = mv[qb + 1]
        b0 = self.base_b + off + _BLOB_HDR
        data = bytes(self.raw[b0:b0 + n])
        if mv[qb] != gen:          # reallocated mid-copy: bytes are torn
            return None
        return data

    # ------------- refcounting ----------------------------------------- #
    def inc(self, off: int) -> None:
        with self.lock:
            self.mv[self._rc + off // _BLOB_GRANULE] += 1

    def try_pin(self, off: int, gen: int) -> bool:
        """Validated pin: take a reference iff the chunk still carries
        ``gen`` and is live.  Raw-copy paths (ring snapshots, StateRec
        copies) use this instead of a blind ``inc`` — between their
        word read and the pin, the word's writer may have released the
        chunk and the allocator re-handed it out; (off, gen) pairs
        never recur, so a stale pair is detected here and the caller
        re-reads the word."""
        with self.lock:
            g = off // _BLOB_GRANULE
            if self.mv[self._gen + g] == gen and self.mv[self._rc + g] > 0:
                self.mv[self._rc + g] += 1
                return True
            return False

    def dec(self, off: int) -> None:
        mv = self.mv
        with self.lock:
            g = off // _BLOB_GRANULE
            rc = mv[self._rc + g] - 1
            mv[self._rc + g] = rc
            if rc == 0:
                cls_b = mv[self._cls + g]
                ci = (cls_b // _BLOB_GRANULE).bit_length() - 1
                mv[self._nxt + g] = mv[self._meta_heads + ci]
                mv[self._meta_heads + ci] = off + 1

    # ------------- accounting / introspection -------------------------- #
    def lines(self, off: int) -> int:
        """Cache-line footprint of the chunk's USED bytes (header +
        payload) — what a pwb of a referencing word writes back."""
        qb = (self.base_b + off) // 8
        return (_BLOB_HDR + self.mv[qb + 1] + _BLOB_LINE - 1) // _BLOB_LINE

    def chunks(self) -> List[Tuple[int, int, int, int]]:
        """[(off, class_bytes, rc, gen)] for every chunk ever carved,
        in address order (allocator-audit introspection for tests)."""
        mv = self.mv
        out = []
        off = 0
        while off < mv[_M_BLOB_BUMP]:
            g = off // _BLOB_GRANULE
            cls_b = mv[self._cls + g]
            out.append((off, cls_b, mv[self._rc + g], mv[self._gen + g]))
            off += cls_b
        return out

    def occupancy(self) -> Dict[str, int]:
        """Live/free chunk accounting (the soak harness's leak gauge)."""
        with self.lock:
            out = {"live_chunks": 0, "live_bytes": 0,
                   "free_chunks": 0, "free_bytes": 0,
                   "bump_bytes": self.mv[_M_BLOB_BUMP],
                   "cap_bytes": self.cap_b}
            for _off, cls_b, rc, _gen in self.chunks():
                if rc > 0:
                    out["live_chunks"] += 1
                    out["live_bytes"] += cls_b
                else:
                    out["free_chunks"] += 1
                    out["free_bytes"] += cls_b
            return out

    # ------------- GC / compaction ------------------------------------- #
    def gc(self) -> Dict[str, int]:
        """Free-space maintenance at a quiescent point: coalesce runs
        of adjacent free chunks into the largest classes that fit,
        retreat the bump pointer over a trailing free run, and rebuild
        the class free lists.  Chunk identity safety: a coalesced-away
        chunk keeps rc == 0 at its old granule, so any stale
        ``try_pin(off, gen)`` fails; (off, gen) pairs still never
        recur because ``alloc`` bumps the generation on every reuse."""
        mv = self.mv
        with self.lock:
            coalesced = retreated = 0
            runs: List[Tuple[int, int, int]] = []   # (start, span, n_chunks)
            start = span = count = 0
            for off, cls_b, rc, _gen in self.chunks():
                if rc == 0:
                    if count == 0:
                        start = off
                    span += cls_b
                    count += 1
                else:
                    if count:
                        runs.append((start, span, count))
                    span = count = 0
            if count:
                # trailing free run: give it back to the bump region
                retreated = span
                for j in range(span // _BLOB_GRANULE):
                    mv[self._cls + start // _BLOB_GRANULE + j] = 0
                mv[_M_BLOB_BUMP] = start
            for rstart, rspan, rcount in runs:
                if rcount < 2:
                    continue
                coalesced += rcount
                for j in range(rspan // _BLOB_GRANULE):
                    mv[self._cls + rstart // _BLOB_GRANULE + j] = 0
                off = rstart
                left = rspan
                max_cls = _BLOB_GRANULE << (_BLOB_CLASSES - 1)
                while left:
                    cls_b = min(1 << left.bit_length() - 1, max_cls)
                    g = off // _BLOB_GRANULE
                    mv[self._cls + g] = cls_b
                    mv[self._rc + g] = 0
                    off += cls_b
                    left -= cls_b
            # rebuild every class free list from the surviving layout
            for ci in range(_BLOB_CLASSES):
                mv[self._meta_heads + ci] = 0
            for off, cls_b, rc, _gen in self.chunks():
                if rc == 0:
                    g = off // _BLOB_GRANULE
                    ci = (cls_b // _BLOB_GRANULE).bit_length() - 1
                    mv[self._nxt + g] = mv[self._meta_heads + ci]
                    mv[self._meta_heads + ci] = off + 1
            return {"coalesced_chunks": coalesced,
                    "bump_retreat_bytes": retreated}

    def _lowest_free_below(self, cls_b: int, below: int) -> Optional[int]:
        """Pop the lowest-offset free chunk of class ``cls_b`` strictly
        below byte offset ``below`` from its free list (caller holds
        the lock)."""
        mv = self.mv
        ci = (cls_b // _BLOB_GRANULE).bit_length() - 1
        best = best_prev = None
        prev = None
        head = mv[self._meta_heads + ci]
        while head:
            off = head - 1
            if off < below and (best is None or off < best):
                best, best_prev = off, prev
            prev = off
            head = mv[self._nxt + off // _BLOB_GRANULE]
        if best is None:
            return None
        nxt = mv[self._nxt + best // _BLOB_GRANULE]
        if best_prev is None:
            mv[self._meta_heads + ci] = nxt
        else:
            mv[self._nxt + best_prev // _BLOB_GRANULE] = nxt
        return best

    def compact(self, word_spans) -> Dict[str, int]:
        """Generation-safe chunk movement: slide live chunks into lower
        free slots of the same class so ``gc()`` can retreat the bump
        pointer.  ``word_spans`` is the [(base_i64, n_words)] list of
        every TAGGED-WORD region that may hold blob refs (the NVM's
        allocated vol+dur spans); a chunk moves only when the refs
        found there account for its ENTIRE refcount — anything also
        referenced from a board slot, a ring snapshot, or a Python-side
        pin stays put.  Movement follows the existing publication
        discipline: fresh generation, header+payload written at the
        destination BEFORE any referring word is switched (gen word
        first, then offset), and the source bytes are left intact, so
        a concurrent reader sees old-or-new, never torn."""
        mv = self.mv
        moved = 0
        with self.lock:
            ref_map: Dict[int, List[int]] = {}
            for base, n in word_spans:
                end = base + WORD_I64 * n
                for o in range(base, end, WORD_I64):
                    if mv[o] == _T_BLOB:
                        ref_map.setdefault(mv[o + 1], []).append(o)
            for off, cls_b, rc, gen in reversed(self.chunks()):
                if rc <= 0:
                    continue
                refs = [o for o in ref_map.get(off, ())
                        if mv[o + 1] == off and mv[o + 2] == gen]
                if len(refs) != rc:
                    continue
                dest = self._lowest_free_below(cls_b, off)
                if dest is None:
                    continue
                gsrc = off // _BLOB_GRANULE
                gd = dest // _BLOB_GRANULE
                gen_d = mv[self._gen + gd] + 1
                mv[self._gen + gd] = gen_d
                nbytes = mv[(self.base_b + off) // 8 + 1]
                qd = (self.base_b + dest) // 8
                mv[qd] = gen_d
                mv[qd + 1] = nbytes
                b_src = self.base_b + off + _BLOB_HDR
                b_dst = self.base_b + dest + _BLOB_HDR
                self.raw[b_dst:b_dst + nbytes] = \
                    self.raw[b_src:b_src + nbytes]
                for o in refs:
                    mv[o + 2] = gen_d
                    mv[o + 1] = dest
                mv[self._rc + gd] = rc
                mv[self._rc + gsrc] = 0
                ci = (cls_b // _BLOB_GRANULE).bit_length() - 1
                mv[self._nxt + gsrc] = mv[self._meta_heads + ci]
                mv[self._meta_heads + ci] = off + 1
                ref_map[dest] = refs
                moved += 1
        return {"moved_chunks": moved}

    def leak_check(self, word_spans) -> Dict[str, int]:
        """Refcount audit: compare each live chunk's rc against the
        refs found in ``word_spans``.  ``excess_rc`` > 0 over EMPTY
        rings and quiesced boards indicates a pin without a matching
        unpin (the class of bug the ring-snapshot re-copy path had)."""
        mv = self.mv
        with self.lock:
            found: Dict[int, int] = {}
            for base, n in word_spans:
                end = base + WORD_I64 * n
                for o in range(base, end, WORD_I64):
                    if mv[o] == _T_BLOB:
                        found[mv[o + 1]] = found.get(mv[o + 1], 0) + 1
            excess = live = 0
            for off, _cls_b, rc, _gen in self.chunks():
                if rc > 0:
                    live += 1
                    excess += max(0, rc - found.get(off, 0))
            return {"live_chunks": live, "excess_rc": excess}


class _Words:
    """Codec-word array view: word i lives at i64 offset
    ``base + WORD_I64 * i`` of the backing memoryview.  ``heap`` (when
    attached to a backend) serves the rich-value fallback."""

    __slots__ = ("mv", "base", "heap")

    def __init__(self, mv, base_i64: int,
                 heap: Optional[BlobHeap] = None) -> None:
        self.mv = mv
        self.base = base_i64
        self.heap = heap

    def get(self, i: int) -> Any:
        o = self.base + WORD_I64 * i
        mv = self.mv
        for _ in range(_STALE_RETRIES):
            t = mv[o]
            if t != _T_BLOB:
                return decode(t, mv[o + 1], mv[o + 2])
            data = self.heap.read(mv[o + 1], mv[o + 2])
            if data is not None:
                return pickle.loads(data)
            # chunk reallocated between the word read and the byte copy:
            # the word necessarily changed too — re-read it
        raise RuntimeError("shm blob word kept changing under the "
                           "reader (writer died mid-publication?)")

    def set(self, i: int, value: Any) -> None:
        o = self.base + WORD_I64 * i
        mv = self.mv
        heap = self.heap
        old_off = mv[o + 1] if (heap is not None and mv[o] == _T_BLOB) \
            else -1
        try:
            t, a, b = encode(value)
        except TypeError:
            if heap is None:
                raise
            a, b = heap.alloc(pickle.dumps(value, protocol=4))
            t = _T_BLOB
        # payload before tag: a reader that sees the new tag sees the
        # new payload (TSO); single-word int updates hinge on mv[o+1].
        # For blobs the chunk bytes were fully written by alloc() above,
        # BEFORE this publication — old-or-new, never torn.
        mv[o + 1] = a
        mv[o + 2] = b
        mv[o] = t
        if old_off >= 0:
            heap.dec(old_off)

    def get_range(self, i: int, n: int) -> List[Any]:
        return [self.get(i + j) for j in range(n)]

    def set_range(self, i: int, values) -> None:
        for j, v in enumerate(values):
            self.set(i + j, v)


# --------------------------------------------------------------------- #
# Backend primitives                                                    #
# --------------------------------------------------------------------- #
class ShmMutex:
    """Mutex over a fork-inherited semaphore.  ``reset`` drains it back
    to exactly one permit — a crashed holder can never be unwound from
    another process, so post-crash recovery forces the released state."""

    __slots__ = ("_sem",)

    def __init__(self, ctx) -> None:
        self._sem = ctx.Semaphore(1)

    def acquire(self, blocking: bool = True) -> bool:
        return self._sem.acquire(blocking)

    def release(self) -> None:
        self._sem.release()

    def __enter__(self):
        self._sem.acquire()
        return self

    def __exit__(self, *exc) -> None:
        self._sem.release()

    def reset(self) -> None:
        while self._sem.acquire(False):
            pass
        self._sem.release()


class ShmAtomicInt:
    """AtomicInt over one shared int64: plain aligned load/store, CAS
    and fetch&add emulated under a striped fork-inherited lock."""

    __slots__ = ("_mv", "_off", "_lock", "_count", "_clock")

    def __init__(self, backend: "ShmBackend", value: int = 0, *,
                 shared: bool = False,
                 counters: Optional[Counters] = None,
                 clock: Optional[Any] = None) -> None:
        self._mv = backend.mv
        self._off = backend.aux_alloc(1)
        self._lock = backend.stripe(self._off)
        self._count = counters if (shared and counters is not None) else None
        self._clock = clock          # always None in shm mode (no profile)
        self._mv[self._off] = value

    def load(self) -> int:
        if self._count is not None:
            self._count.shared_reads += 1
        return self._mv[self._off]

    def store(self, value: int) -> None:
        if self._count is not None:
            self._count.shared_writes += 1
        self._mv[self._off] = value

    def cas(self, old: int, new: int) -> bool:
        with self._lock:
            if self._count is not None:
                self._count.cas_calls += 1
            if self._mv[self._off] == old:
                self._mv[self._off] = new
                if self._count is not None:
                    self._count.shared_writes += 1
                return True
            return False

    def fetch_add(self, delta: int) -> int:
        with self._lock:
            old = self._mv[self._off]
            self._mv[self._off] = old + delta
            if self._count is not None:
                self._count.shared_writes += 1
            return old

    def reset(self, value: int = 0) -> None:
        self._mv[self._off] = value

    # Waiters in other processes hold no GIL this one needs and cannot
    # share a ``threading`` condition, so they poll: a few yields, then
    # a tiny sleep, which also widens the announcement window.
    SPIN_FAST = 3
    PARK_SECONDS = 2e-5

    def wait_while(self, expected: int, nvm: Any) -> int:
        """Poll while the word holds ``expected``; return the polls.
        Machine-off check: a crash in ANOTHER process cannot unwind
        this one, so the loop leaves on the shared ``halted`` flag
        instead of spinning on a lock word the dead combiner never
        releases."""
        spins = 0
        while self.load() == expected:
            if nvm.halted:
                raise SimulatedCrash()
            spins += 1
            time.sleep(0 if spins <= self.SPIN_FAST else self.PARK_SECONDS)
        return spins


class ShmAtomicRef:
    """Versioned LL/VL/SC reference over shared memory (codec value +
    raw version word).  Supports the same ``mirror=(nvm, addr)`` as the
    thread AtomicRef: the mirror write lands inside the SC's critical
    section."""

    __slots__ = ("_words", "_idx", "_mv", "_voff", "_lock", "_count",
                 "_mnvm", "_maddr")

    def __init__(self, backend: "ShmBackend", value: Any, *,
                 shared: bool = False,
                 counters: Optional[Counters] = None,
                 clock: Optional[Any] = None,
                 mirror: Optional[Tuple[Any, int]] = None) -> None:
        off = backend.aux_alloc(WORD_I64 + 1)
        self._words = _Words(backend.mv, off, backend.heap)
        self._idx = 0
        self._mv = backend.mv
        self._voff = off + WORD_I64
        self._lock = backend.stripe(off)
        self._count = counters if (shared and counters is not None) else None
        self._mnvm, self._maddr = mirror if mirror is not None else (None, 0)
        self.reset(value)

    def ll(self) -> Tuple[Any, int]:
        if self._count is not None:
            self._count.shared_reads += 1
        # version first: if it is unchanged after the value read, the
        # value belongs to that version (SC bumps version last)
        ver = self._mv[self._voff]
        return self._words.get(self._idx), ver

    def vl(self, version: int) -> bool:
        if self._count is not None:
            self._count.shared_reads += 1
        return self._mv[self._voff] == version

    def sc(self, version: int, new_value: Any) -> bool:
        with self._lock:
            if self._count is not None:
                self._count.cas_calls += 1
            if self._mv[self._voff] == version:
                self._words.set(self._idx, new_value)
                if self._mnvm is not None:
                    self._mnvm.write(self._maddr, new_value)
                self._mv[self._voff] = version + 1
                if self._count is not None:
                    self._count.shared_writes += 1
                return True
            return False

    def load(self) -> Any:
        if self._count is not None:
            self._count.shared_reads += 1
        return self._words.get(self._idx)

    def reset(self, value: Any) -> None:
        with self._lock:
            self._words.set(self._idx, value)
            # construction / post-crash reset seeds the ref with the
            # mirror word's own durable value — rewriting it would dirty
            # the line with nothing new to persist (see _SRef.__init__)
            if self._mnvm is not None and self._mnvm.read(self._maddr) != value:
                self._mnvm.write(self._maddr, value)
            self._mv[self._voff] = 0


class ShmSRef:
    """PWFComb's S: versioned LL/VL/SC whose value is mirrored into an
    NVM word inside the SC mutex (the shm variant of ``_SRef``)."""

    __slots__ = ("nvm", "addr", "_mv", "_voff", "_soff", "_mutex",
                 "_counters")

    def __init__(self, backend: "ShmBackend", nvm: "ShmNVM", addr: int,
                 value: int, counters: Optional[Counters] = None) -> None:
        off = backend.aux_alloc(2)
        self._mv = backend.mv
        self._soff = off          # slot id (int, raw)
        self._voff = off + 1      # version
        self._mutex = backend.stripe(off)
        self.nvm = nvm
        self.addr = addr
        self._counters = counters
        self.reset(nvm, addr, value)

    def ll(self):
        if self._counters:
            self._counters.shared_reads += 1
        ver = self._mv[self._voff]
        return self._mv[self._soff], ver

    def vl(self, version: int) -> bool:
        return self._mv[self._voff] == version

    def sc(self, version: int, new_value: int) -> bool:
        with self._mutex:
            if self._counters:
                self._counters.cas_calls += 1
            if self._mv[self._voff] == version:
                self._mv[self._soff] = new_value
                self.nvm.write(self.addr, new_value)
                self._mv[self._voff] = version + 1
                return True
            return False

    def load(self) -> int:
        return self._mv[self._soff]

    def reset(self, nvm: "ShmNVM", addr: int, value: int) -> None:
        with self._mutex:
            self._mv[self._soff] = value
            # Post-crash reset passes the durable word's own value back
            # in — rewriting it would dirty the line with nothing new
            # to persist (see _SRef.__init__).
            if nvm.read(addr) != value:
                nvm.write(addr, value)
            self._mv[self._voff] = 0


class ShmCell:
    """One shared codec word with a ``value`` attribute (LockVal,
    oldTail).  Single-word plain loads/stores, like the thread Cell."""

    __slots__ = ("_words",)

    def __init__(self, backend: "ShmBackend", value: Any = None) -> None:
        self._words = _Words(backend.mv, backend.aux_alloc(WORD_I64),
                             backend.heap)
        self._words.set(0, value)

    @property
    def value(self) -> Any:
        return self._words.get(0)

    @value.setter
    def value(self, v: Any) -> None:
        self._words.set(0, v)


class ShmIntArray:
    """Raw shared int64 array (PWFComb's Flush / CombRound rows)."""

    __slots__ = ("_mv", "_off", "_n")

    def __init__(self, mv, off: int, n: int, init: int = 0) -> None:
        self._mv = mv
        self._off = off
        self._n = n
        self.fill(init)

    def __len__(self) -> int:
        return self._n

    def __getitem__(self, i: int) -> int:
        return self._mv[self._off + i]

    def __setitem__(self, i: int, v: int) -> None:
        self._mv[self._off + i] = v

    def fill(self, value: int) -> None:
        mv, off = self._mv, self._off
        for i in range(self._n):
            mv[off + i] = value


# Request-board field offsets (codec words per RequestRec slot).
(_RB_FUNC, _RB_ARGS, _RB_ACT, _RB_VALID, _RB_VTIME, _RB_STAMP, _RB_TNS,
 _RB_WORDS) = 0, 1, 2, 3, 4, 5, 6, 7


class ShmRequestRec:
    """View of one announcement slot; property-per-field so the
    protocols' in-place announce sequence (valid=0 ... valid=1) hits
    shared memory in program order."""

    __slots__ = ("_w", "_b")

    def __init__(self, words: _Words, base_word: int) -> None:
        self._w = words
        self._b = base_word

    @property
    def func(self):
        return self._w.get(self._b + _RB_FUNC)

    @func.setter
    def func(self, v):
        self._w.set(self._b + _RB_FUNC, v)

    @property
    def args(self):
        return self._w.get(self._b + _RB_ARGS)

    @args.setter
    def args(self, v):
        self._w.set(self._b + _RB_ARGS, v)

    @property
    def activate(self):
        return self._w.get(self._b + _RB_ACT)

    @activate.setter
    def activate(self, v):
        self._w.set(self._b + _RB_ACT, v)

    @property
    def valid(self):
        return self._w.get(self._b + _RB_VALID)

    @valid.setter
    def valid(self, v):
        self._w.set(self._b + _RB_VALID, v)

    @property
    def vtime(self):
        return self._w.get(self._b + _RB_VTIME)

    @vtime.setter
    def vtime(self, v):
        self._w.set(self._b + _RB_VTIME, v)

    @property
    def stamp(self):
        return self._w.get(self._b + _RB_STAMP)

    @stamp.setter
    def stamp(self, v):
        self._w.set(self._b + _RB_STAMP, v)

    @property
    def t_ns(self):
        return self._w.get(self._b + _RB_TNS)

    @t_ns.setter
    def t_ns(self, v):
        self._w.set(self._b + _RB_TNS, v)


class ShmRequestBoard(list):
    """Announcement board in shared memory: ``board[p]`` is a live view;
    assigning a RequestRec copies its fields under the announce seqlock
    (stamp odd while rewriting, valid published before the even
    stamp — see ``RequestRec.stamp``)."""

    def __init__(self, backend: "ShmBackend", n_threads: int) -> None:
        words = _Words(backend.mv,
                       backend.aux_alloc(WORD_I64 * _RB_WORDS * n_threads),
                       backend.heap)
        super().__init__(ShmRequestRec(words, _RB_WORDS * p)
                         for p in range(n_threads))
        self.reset()

    def __setitem__(self, p: int, rec: Any) -> None:
        view = list.__getitem__(self, p)
        st = view.stamp + 1
        view.stamp = st                 # odd: rewrite in progress
        view.valid = 0
        view.func = rec.func
        view.args = rec.args
        view.activate = rec.activate
        view.vtime = rec.vtime
        if tracing.enabled:         # read only while tracing is on
            view.t_ns = rec.t_ns
        view.valid = rec.valid
        view.stamp = st + 1             # even: published

    def reset(self) -> None:
        for view in self:
            st = view.stamp + 1
            view.stamp = st
            view.valid = 0
            view.func = None
            view.args = None
            view.activate = 0
            view.vtime = 0.0
            view.t_ns = 0
            view.stamp = st + 1


class ShmDegreeStats:
    """Measured-degree counters in shared memory — combiners in any
    process accumulate into the same three words."""

    __slots__ = ("_mv", "_off", "_lock")

    def __init__(self, backend: "ShmBackend") -> None:
        self._off = backend.aux_alloc(3)
        self._mv = backend.mv
        self._lock = backend.stripe(self._off)
        self.reset()

    def record(self, served: int) -> None:
        mv, off = self._mv, self._off
        with self._lock:
            mv[off] += 1
            mv[off + 1] += served
            if served > mv[off + 2]:
                mv[off + 2] = served

    def snapshot(self) -> dict:
        mv, off = self._mv, self._off
        with self._lock:
            return {"rounds": mv[off], "ops_combined": mv[off + 1],
                    "degree_max": mv[off + 2]}

    def reset(self) -> None:
        mv, off = self._mv, self._off
        with self._lock:
            mv[off] = mv[off + 1] = mv[off + 2] = 0


# --------------------------------------------------------------------- #
# The backend                                                           #
# --------------------------------------------------------------------- #
# machine meta slot indexes (int64)
_M_AUX = 0          # aux-area bump pointer (i64 units, relative)
_M_COUNT = 1        # crash countdown (-1 = disarmed)
_M_SEED = 2         # adversarial-drain seed (-1 = drain nothing)
_M_HALT = 3         # machine-off flag
_M_PWB, _M_PFENCE, _M_PSYNC, _M_CRASHES = 4, 5, 6, 7
_M_SPILLS = 8       # ring-overflow early drains (machine-wide)
_M_BLOBBED = 9      # 1 iff the blob heap ever allocated (fast-path skip)
_M_BLOB_BUMP = 10   # blob-area bump pointer (bytes, relative)
_M_LOSESEG = 11     # segment to LOSE at the next crash (-1 = none):
                    # that DIMM drops every pending write-back while the
                    # surviving segments drain fully (repro.fuzz's
                    # partial-failure class)
_M_CLASS0 = 16      # blob class free-list heads (byte offset + 1; 0=nil)
_META_I64 = _M_CLASS0 + _BLOB_CLASSES

# per-segment meta slots (int64), at seg_meta + s * _SEG_I64
_S_ALLOC = 0        # word bump pointer (absolute word index)
_S_EPOCH = 1        # current epoch id
_S_EFLAG = 2        # 1 iff the current epoch has queued entries
_S_RING = 3         # ring used (i64, relative to this segment's ring)
_S_PWB = 4          # lines written back through this segment's device
_S_PSYNC = 5        # psyncs that ENGAGED this segment's device
_S_SPILLS = 6       # ring-overflow early drains on this segment
_SEG_I64 = 8

_CTR_SLOT = {"pwb": _M_PWB, "pfence": _M_PFENCE, "psync": _M_PSYNC,
             "crashes": _M_CRASHES, "ring_spills": _M_SPILLS}

# ring entry header: [epoch, first_line, n_lines, blob_lines]
_ENT_HDR = 4


class _ShmCounters:
    """Dict-like view of the shared pwb/pfence/psync/crashes slots, so
    ``nvm.counters["pwb"]`` reads the machine-wide count from any
    process."""

    __slots__ = ("_mv",)

    def __init__(self, mv) -> None:
        self._mv = mv

    def __getitem__(self, key: str) -> int:
        return self._mv[_CTR_SLOT[key]]

    def __setitem__(self, key: str, value: int) -> None:
        self._mv[_CTR_SLOT[key]] = value

    def __iter__(self) -> Iterator[str]:
        return iter(_CTR_SLOT)

    def __contains__(self, key: str) -> bool:
        return key in _CTR_SLOT

    def get(self, key: str, default=None):
        return self._mv[_CTR_SLOT[key]] if key in _CTR_SLOT else default

    def keys(self):
        return _CTR_SLOT.keys()

    def snapshot(self) -> Dict[str, int]:
        return {k: self._mv[v] for k, v in _CTR_SLOT.items()}

    def __repr__(self) -> str:
        return f"_ShmCounters({self.snapshot()})"


# ------------------------------------------------------------------ #
# Segment lifecycle (leak-robust unlink)                             #
# ------------------------------------------------------------------ #
# Segments get recognizable names ("psc-<owner pid>-<seq>") so a
# crashed run's leftovers in /dev/shm are attributable and reapable.
# Three layers of cleanup:
#   * ``close()`` unlinks, but only in the owning process — a forked
#     worker (or its atexit) must never unlink a segment the parent is
#     still using;
#   * an atexit hook in the owner unlinks anything close() never
#     reached (exceptions, SIGTERM-with-handlers);
#   * ``reap_orphan_segments()`` removes segments whose owner pid is
#     dead — the kill -9 case nothing in-process can cover.  The
#     runtime calls it on ``recover()``.
_SEG_PREFIX = "psc-"
_SEG_SEQ = itertools.count()
#: name -> (owner pid, SharedMemory): segments created by this process
#: and not yet unlinked
_LIVE_SEGMENTS: Dict[str, Tuple[int, Any]] = {}


def _register_segment(name: str, shm) -> None:
    if not _LIVE_SEGMENTS:
        atexit.register(_reap_at_exit)
    _LIVE_SEGMENTS[name] = (os.getpid(), shm)


def _reap_at_exit() -> None:
    for name in list(_LIVE_SEGMENTS):
        pid, shm = _LIVE_SEGMENTS[name]
        if pid != os.getpid():      # inherited entry in a forked child
            continue
        del _LIVE_SEGMENTS[name]
        try:
            shm.close()
        except (OSError, BufferError):
            pass
        try:
            shm.unlink()
        except (FileNotFoundError, OSError):
            pass


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except ProcessLookupError:
        return False
    except PermissionError:
        return True
    return True


def reap_orphan_segments(shm_dir: str = "/dev/shm") -> List[str]:
    """Unlink ``psc-<pid>-*`` segments whose owner process is dead
    (killed before teardown).  Never touches live owners' segments or
    this process's own.  Returns the reaped names."""
    reaped: List[str] = []
    try:
        names = os.listdir(shm_dir)
    except OSError:
        return reaped
    for name in names:
        if not name.startswith(_SEG_PREFIX):
            continue
        try:
            pid = int(name.split("-")[1])
        except (IndexError, ValueError):
            continue
        if pid == os.getpid() or _pid_alive(pid):
            continue
        try:
            os.unlink(os.path.join(shm_dir, name))
            reaped.append(name)
        except OSError:
            pass
    return reaped


class ShmBackend(ThreadBackend):
    """``core.backend`` seam over one shared-memory segment.

    Inherits the thread backend and overrides every factory whose
    object must be visible across processes; the ``reset_*`` overrides
    reset IN PLACE (fork-inherited views in workers must stay
    attached).  All factories are create-before-fork: call them (i.e.
    build runtimes/structures) before ``spawn_workers``.
    """

    kind = "shm"

    #: striped-lock pool size: enough to make false sharing of stripes
    #: unlikely at 8 workers, few enough to keep fd/semaphore count low.
    N_STRIPES = 16

    #: Entry backoff under true parallelism (see
    #: ``ThreadBackend.announce_park``): park every announcement for
    #: ~one round so a concurrent combiner adopts it — the measured
    #: degree >= 2 the reproduction targets comes from this window.
    #: Tunable per backend instance (mp_bench exposes --park).
    PARK_PROB = 1.0
    PARK_SECONDS = 1e-4

    def __init__(self, data_words: int = 1 << 18, *,
                 aux_i64: int = 1 << 16, ring_i64: int = 1 << 18,
                 segments: int = 1, blob_bytes: int = 1 << 20) -> None:
        from multiprocessing import shared_memory
        if segments < 1:
            raise ValueError(f"segments must be >= 1, got {segments}")
        if blob_bytes % _BLOB_GRANULE:
            raise ValueError("blob_bytes must be a multiple of "
                             f"{_BLOB_GRANULE}")
        self._ctx = multiprocessing.get_context("fork")
        # equal line-aligned word spans per segment
        per = -(-data_words // segments)
        per += (-per) % LINE
        self.data_words = data_words = per * segments
        self.words_per_seg = per
        self.segments = segments
        self.ring_seg = max(_ENT_HDR + LINE * WORD_I64,
                            ring_i64 // segments)
        n_gran = blob_bytes // _BLOB_GRANULE
        total = (_META_I64 + segments * _SEG_I64
                 + 2 * data_words * WORD_I64
                 + segments * self.ring_seg + aux_i64
                 + 4 * n_gran + blob_bytes // 8)
        # recognizable, owner-stamped segment name (see the lifecycle
        # note above ``reap_orphan_segments``); collisions with a stale
        # same-pid leftover are resolved by advancing the sequence
        self._owner_pid = os.getpid()
        while True:
            name = f"{_SEG_PREFIX}{self._owner_pid}-{next(_SEG_SEQ)}"
            try:
                self._shm = shared_memory.SharedMemory(
                    create=True, name=name, size=total * 8)
                break
            except FileExistsError:
                continue
        self.name = name
        _register_segment(name, self._shm)
        self.mv = self._shm.buf.cast("q")
        self.raw = self._shm.buf
        # fresh /dev/shm pages are zero-filled; meta needs non-zeros
        self.mv[_M_COUNT] = -1
        self.mv[_M_SEED] = -1
        self.mv[_M_LOSESEG] = -1
        self.seg_meta = _META_I64
        self.vol_base = self.seg_meta + segments * _SEG_I64
        self.dur_base = self.vol_base + data_words * WORD_I64
        self.ring_base = self.dur_base + data_words * WORD_I64
        self.aux_base = self.ring_base + segments * self.ring_seg
        self.aux_cap = aux_i64
        self.blob_side_base = self.aux_base + aux_i64
        self.blob_bytes = blob_bytes
        self.blob_base = self.blob_side_base + 4 * n_gran
        # per-segment word allocation pointers (segment 0 reserves line
        # 0: address 0 doubles as NULL for the linked structures)
        for s in range(segments):
            self.mv[self.seg_meta + s * _SEG_I64 + _S_ALLOC] = \
                s * per if s else LINE
        self._stripes = [self._ctx.Lock() for _ in range(self.N_STRIPES)]
        self._alloc_lock = self._ctx.Lock()
        self.nvm_lock = self._ctx.Lock()     # guards images/rings/counters
        # one modeled write-back device per segment (wall persist_latency
        # drains serialize per device, not machine-wide)
        self.device_locks = [self._ctx.Lock() for _ in range(segments)]
        self.heap = BlobHeap(self)
        self._closed = False

    # ---------------- segment plumbing --------------------------------- #
    def aux_alloc(self, n_i64: int) -> int:
        """Bump-allocate ``n_i64`` aux slots; absolute i64 offset."""
        with self._alloc_lock:
            used = self.mv[_M_AUX]
            if used + n_i64 > self.aux_cap:
                raise MemoryError("shm backend aux area exhausted "
                                  f"({self.aux_cap} i64)")
            self.mv[_M_AUX] = used + n_i64
            return self.aux_base + used

    def stripe(self, off: int):
        return self._stripes[off % self.N_STRIPES]

    def close(self) -> None:
        """Release the segment.  Safe to call twice, and safe to call
        from a forked worker: only the creating process unlinks (a
        non-owner close releases its own mapping and nothing else)."""
        if self._closed:
            return
        self._closed = True
        self.raw = None
        self.heap = None
        mv, self.mv = self.mv, None
        mv.release()
        self._shm.close()
        if os.getpid() != self._owner_pid:
            return
        _LIVE_SEGMENTS.pop(self.name, None)
        try:
            self._shm.unlink()
        except FileNotFoundError:
            pass

    # ---------------- factories ---------------------------------------- #
    def mutex(self) -> ShmMutex:
        return ShmMutex(self._ctx)

    def cell(self, value: Any = None) -> ShmCell:
        return ShmCell(self, value)

    def atomic_int(self, value: int = 0, *, shared: bool = False,
                   counters: Optional[Counters] = None,
                   clock: Optional[Any] = None) -> ShmAtomicInt:
        return ShmAtomicInt(self, value, shared=shared, counters=counters,
                            clock=clock)

    def waitable_int(self, value: int = 0, *,
                     counters: Optional[Counters] = None) -> ShmAtomicInt:
        return ShmAtomicInt(self, value, shared=True, counters=counters)

    def atomic_ref(self, value: Any, *, shared: bool = False,
                   counters: Optional[Counters] = None,
                   clock: Optional[Any] = None,
                   mirror: Optional[Tuple[Any, int]] = None) -> ShmAtomicRef:
        return ShmAtomicRef(self, value, shared=shared, counters=counters,
                            clock=clock, mirror=mirror)

    def sref(self, nvm: Any, addr: int, value: int,
             counters: Optional[Counters] = None) -> ShmSRef:
        return ShmSRef(self, nvm, addr, value, counters)

    def int_array(self, n: int, init: int = 0) -> ShmIntArray:
        return ShmIntArray(self.mv, self.aux_alloc(n), n, init)

    def int_matrix(self, rows: int, cols: int) -> List[ShmIntArray]:
        return [self.int_array(cols) for _ in range(rows)]

    def request_board(self, n_threads: int) -> ShmRequestBoard:
        return ShmRequestBoard(self, n_threads)

    def degree_stats(self) -> ShmDegreeStats:
        return ShmDegreeStats(self)

    def announce_park(self, prob: float, seconds: float
                      ) -> Tuple[float, float]:
        return self.PARK_PROB, self.PARK_SECONDS

    # ---------------- in-place resets ----------------------------------- #
    def reset_mutex(self, m: ShmMutex) -> ShmMutex:
        m.reset()
        return m

    def reset_atomic_int(self, a: ShmAtomicInt, value: int = 0,
                         **_kw) -> ShmAtomicInt:
        a.reset(value)
        return a

    reset_waitable_int = reset_atomic_int

    def reset_atomic_ref(self, a: ShmAtomicRef, value: Any, *,
                         mirror: Optional[Tuple[Any, int]] = None,
                         **_kw) -> ShmAtomicRef:
        a.reset(value)
        return a

    def reset_sref(self, s: ShmSRef, nvm: Any, addr: int, value: int,
                   counters: Optional[Counters] = None) -> ShmSRef:
        s.reset(nvm, addr, value)
        return s


# --------------------------------------------------------------------- #
# The NVM                                                               #
# --------------------------------------------------------------------- #
class ShmNVM(NVM):
    """Simulated NVMM whose images, write-back rings, counters and crash
    machinery live in the backend's shared segment.

    Same interface and crash semantics as ``NVM`` with these
    multiprocess-specific differences, all visible only to shm runs:

      * fused persistence sentences always take the discrete path
        (identical counters/durability — the fused forms are a
        same-process lock elision that a cross-process lock cannot
        reproduce), so the virtual clock/profile is unsupported here;
      * ``crash()`` additionally raises the shared ``halted`` flag —
        a SimulatedCrash only unwinds the process that hit it, so
        survivors poll the flag from persistence instructions and wait
        loops and stop as if their power was cut.  ``disarm_crash``
        (called by ``CombiningRuntime.recover``) clears it;
      * if a write-back ring fills, the oldest pending write-backs
        are drained to the durable image early (counted in
        ``ring_spills``).  Legal under explicit epoch persistency: the
        lines were pwb'd, the hardware may complete them any time
        before the psync;
      * NUMA-ish segmentation (DESIGN.md §8): the word space is striped
        into ``segments`` spans, each with its own epoch ring, modeled
        sync device, allocation pointer and per-segment accounting
        (``segment_counters()``); ``alloc(..., segment=s)`` or the
        ``placement(s)`` context manager pin a structure to a span;
      * rich word values ride the backend's ``BlobHeap`` — blob-ref
        words charge the referenced chunk's cache-line footprint to
        every pwb that covers them, and the ring pins chunks (by
        refcount) instead of copying their immutable bytes.
    """

    def __init__(self, n_words: int = 1 << 18, *,
                 backend: Optional[ShmBackend] = None,
                 segments: int = 1,
                 pwb_nop: bool = False, psync_nop: bool = False,
                 persist_latency: float = 0.0,
                 audit: bool = False) -> None:
        if backend is None:
            backend = ShmBackend(data_words=n_words, segments=segments)
            n_words = backend.data_words
        elif segments not in (1, backend.segments):
            raise ValueError(
                f"segments={segments} contradicts the supplied backend "
                f"(built with segments={backend.segments}); segmentation "
                "is a property of the segment layout, so pass it where "
                "the backend is constructed")
        if n_words > backend.data_words:
            raise ValueError(f"n_words={n_words} exceeds backend segment "
                             f"({backend.data_words} words)")
        # deliberately NOT calling NVM.__init__: the images live in the
        # segment, and every inherited method that touches them is
        # overridden (the fused sentences dispatch through _fast_ok).
        self.backend = backend
        self.segments = backend.segments
        self.words_per_seg = backend.words_per_seg
        self.n_words = n_words
        self._vol = _Words(backend.mv, backend.vol_base, backend.heap)
        self._dur = _Words(backend.mv, backend.dur_base, backend.heap)
        self._mv = backend.mv
        self._lock = backend.nvm_lock
        self.pwb_nop = pwb_nop
        self.psync_nop = psync_nop
        self.persist_latency = persist_latency
        self.clock = None
        self.force_discrete = False
        self.counters = _ShmCounters(backend.mv)
        self._crash_rng = None
        self._injector = None       # process-local, see _tick_crash_point
        self._default_seg = 0
        # Persist-ordering audit (DESIGN.md §10): per-PROCESS state —
        # sound and complete for in-process drivers (the deterministic
        # analysis sweep); worker processes each see only their own
        # instructions.  The shm NVM has no VClock, so the audit covers
        # the flush-state classes (unflushed/redundant), not order
        # races.  Disabled under the NOP ablations, like the thread NVM.
        self._audit = None
        if audit and not (pwb_nop or psync_nop):
            from ..analysis.audit import PersistAudit   # lazy: no cycle
            self._audit = PersistAudit(self)
            self._install_audit_hooks()

    # ------------------------------------------------------------------ #
    @property
    def halted(self) -> bool:
        return self._mv[_M_HALT] != 0

    def _fast_ok(self) -> bool:
        return False        # fused sentences always take the discrete path

    def _seg_slot(self, s: int, field: int) -> int:
        return self.backend.seg_meta + s * _SEG_I64 + field

    def segment_of(self, addr: int) -> int:
        return min(addr // self.words_per_seg, self.segments - 1)

    # ---------------- allocation --------------------------------------- #
    def current_segment(self) -> int:
        return self._default_seg

    def set_default_segment(self, segment: int) -> None:
        if not 0 <= segment < self.segments:
            raise ValueError(f"segment {segment} out of range "
                             f"(0..{self.segments - 1})")
        self._default_seg = segment

    def placement(self, segment: int):
        """Context manager: allocations inside run on ``segment`` (the
        runtime's structure-affinity policy uses this)."""
        from contextlib import contextmanager

        @contextmanager
        def _cm():
            prev = self._default_seg
            self.set_default_segment(segment)
            try:
                yield self
            finally:
                self._default_seg = prev
        return _cm()

    def alloc(self, n_words: int, align_line: bool = True,
              segment: Optional[int] = None) -> int:
        s = self._default_seg if segment is None else segment
        if not 0 <= s < self.segments:
            raise ValueError(f"segment {s} out of range")
        mv = self._mv
        slot = self._seg_slot(s, _S_ALLOC)
        limit = min(self.n_words, (s + 1) * self.words_per_seg)
        with self._lock:
            ptr = mv[slot]
            if align_line and ptr % LINE:
                ptr += LINE - ptr % LINE
            base = ptr
            ptr += n_words
            if ptr > limit:
                raise MemoryError(
                    f"simulated (shm) NVMM segment {s} exhausted")
            mv[slot] = ptr
            return base

    # ---------------- volatile image ------------------------------------ #
    def read(self, addr: int) -> Any:
        return self._vol.get(addr)

    def write(self, addr: int, value: Any) -> None:
        self._vol.set(addr, value)

    def read_range(self, addr: int, n: int) -> List[Any]:
        return self._vol.get_range(addr, n)

    def write_range(self, addr: int, values) -> None:
        self._vol.set_range(addr, values)

    def copy_range(self, dst: int, src: int, n: int) -> None:
        mv = self._mv
        vb = self.backend.vol_base
        if mv[_M_BLOBBED]:
            # NOTE: _M_BLOBBED is machine-wide and sticky by design —
            # a per-segment flag would be unsound here because a racy
            # source (a PWFComb slot being rewritten mid-copy) can gain
            # its first blob ref AFTER any pre-scan, and aux words have
            # no segment to key a flag on.  The per-word cost is
            # confined to runtimes that actually store rich values.
            # a raw copy duplicates blob refs, so it goes word by word:
            # each source blob ref is VALIDATED-pinned (try_pin) before
            # the dst word is published over the old one — a concurrent
            # writer releasing the source chunk mid-copy is caught by
            # the generation check and that word re-read.  (Non-blob
            # words keep the raw-copy tearing exposure the protocols
            # already discard via their own validation.)
            heap = self.backend.heap
            for j in range(n):
                so = vb + WORD_I64 * (src + j)
                do = vb + WORD_I64 * (dst + j)
                for _ in range(_STALE_RETRIES):
                    t, a, b = mv[so], mv[so + 1], mv[so + 2]
                    if t != _T_BLOB or heap.try_pin(a, b):
                        break
                else:
                    raise RuntimeError("shm blob word kept changing "
                                       "under copy_range")
                old_off = mv[do + 1] if mv[do] == _T_BLOB else -1
                mv[do + 1] = a
                mv[do + 2] = b
                mv[do] = t
                if old_off >= 0:
                    heap.dec(old_off)
            return
        a = vb + WORD_I64 * src
        d = vb + WORD_I64 * dst
        n3 = WORD_I64 * n
        mv[d:d + n3] = mv[a:a + n3]

    def durable_read(self, addr: int) -> Any:
        return self._dur.get(addr)

    # ---------------- write-back rings ----------------------------------- #
    # Per-segment entry layout (i64): [epoch_id, first_line, n_lines,
    #   blob_lines, payload: n_lines * LINE * WORD_I64]
    def _blob_refs_in(self, base_i64: int, n_words: int) -> List[int]:
        """Blob offsets referenced by words at [base_i64, +n_words) of
        the backing view, one per OCCURRENCE (callers dedupe for line
        accounting, keep occurrences for refcounts)."""
        mv = self._mv
        return [mv[o + 1]
                for o in range(base_i64, base_i64 + WORD_I64 * n_words,
                               WORD_I64)
                if mv[o] == _T_BLOB]

    def _blob_lines(self, refs: List[int]) -> int:
        heap = self.backend.heap
        return sum(heap.lines(off) for off in set(refs))

    def _ring_append_locked(self, s: int, first: int,
                            n_lines: int, spill_out=None) -> int:
        """Append one entry to segment ``s``'s ring; returns the blob
        line count charged on top of the word lines.  ``spill_out``
        collects the line runs of any overflow early-drain so the audit
        can retire them without an ordering judgment."""
        mv = self._mv
        size = _ENT_HDR + n_lines * LINE * WORD_I64
        rslot = self._seg_slot(s, _S_RING)
        used = mv[rslot]
        if used + size > self.backend.ring_seg:
            # early completion of pending write-backs (see class doc)
            drained = self._drain_ring_locked(s)
            if spill_out is not None:
                spill_out.extend(drained)
            mv[_M_SPILLS] += 1
            mv[self._seg_slot(s, _S_SPILLS)] += 1
            used = 0
            if size > self.backend.ring_seg:
                raise MemoryError("shm write-back ring smaller than one "
                                  f"pwb of {n_lines} lines")
        o = self.backend.ring_base + s * self.backend.ring_seg + used
        mv[o] = mv[self._seg_slot(s, _S_EPOCH)]
        mv[o + 1] = first
        mv[o + 2] = n_lines
        src = self.backend.vol_base + WORD_I64 * first * LINE
        n3 = n_lines * LINE * WORD_I64
        mv[o + _ENT_HDR:o + _ENT_HDR + n3] = mv[src:src + n3]
        blob_lines = 0
        if mv[_M_BLOBBED]:
            # pin every referenced chunk per occurrence: the ring's
            # snapshot words hold refs, not byte copies — the pin is
            # what keeps the (immutable) bytes around until drain.
            # Pins are VALIDATED (try_pin): a writer racing this pwb
            # may have released the chunk between the slice copy above
            # and here, in which case the fresh word is re-snapshotted
            # (either value is a legal pwb-time capture).
            heap = self.backend.heap
            pinned = []
            for w in range(n_lines * LINE):
                so = o + _ENT_HDR + WORD_I64 * w
                for _ in range(_STALE_RETRIES):
                    if mv[so] != _T_BLOB:
                        break
                    if heap.try_pin(mv[so + 1], mv[so + 2]):
                        pinned.append(mv[so + 1])
                        break
                    vo = src + WORD_I64 * w
                    mv[so:so + WORD_I64] = mv[vo:vo + WORD_I64]
                else:
                    # the entry is abandoned (ring cursor never
                    # advances past it) — release the pins this loop
                    # already took or their chunks leak forever
                    for poff in pinned:
                        heap.dec(poff)
                    raise RuntimeError("shm blob word kept changing "
                                       "under pwb snapshot")
            if pinned:
                blob_lines = self._blob_lines(pinned)
        mv[o + 3] = blob_lines
        mv[rslot] = used + size
        mv[self._seg_slot(s, _S_EFLAG)] = 1
        return blob_lines

    def _ring_entries_locked(self, s: int
                             ) -> List[Tuple[int, int, int, int, int]]:
        """[(epoch, first_line, n_lines, blob_lines, payload_off)]."""
        mv = self._mv
        out = []
        o = self.backend.ring_base + s * self.backend.ring_seg
        end = o + mv[self._seg_slot(s, _S_RING)]
        while o < end:
            n_lines = mv[o + 2]
            out.append((mv[o], mv[o + 1], n_lines, mv[o + 3],
                        o + _ENT_HDR))
            o += _ENT_HDR + n_lines * LINE * WORD_I64
        return out

    def _drain_entry_locked(self, first: int, n_lines: int,
                            payload: int) -> None:
        """Install a snapshot span over the durable image.  The
        snapshot's blob refs were pinned at append time; they become
        the durable words' refs here, so only the refs of the durable
        words being BURIED are released."""
        mv = self._mv
        dst = self.backend.dur_base + WORD_I64 * first * LINE
        n3 = n_lines * LINE * WORD_I64
        if mv[_M_BLOBBED]:
            heap = self.backend.heap
            for off in self._blob_refs_in(dst, n_lines * LINE):
                heap.dec(off)
        mv[dst:dst + n3] = mv[payload:payload + n3]

    def _discard_span_locked(self, payload: int, n_words: int) -> None:
        """Release the pins of a snapshot span that will never drain
        (crash dropped it)."""
        if self._mv[_M_BLOBBED]:
            heap = self.backend.heap
            for off in self._blob_refs_in(payload, n_words):
                heap.dec(off)

    def _drain_ring_locked(self, s: int) -> List[Tuple[int, int]]:
        drained = []
        for _e, first, n_lines, _bl, payload in \
                self._ring_entries_locked(s):
            self._drain_entry_locked(first, n_lines, payload)
            drained.append((first, n_lines))
        self._mv[self._seg_slot(s, _S_RING)] = 0
        self._mv[self._seg_slot(s, _S_EFLAG)] = 0
        return drained

    # ---------------- persistence instructions --------------------------- #
    def _tick_crash_point(self, kind: str = "") -> None:
        mv = self._mv
        if mv[_M_HALT]:
            raise SimulatedCrash()
        inj = self._injector
        if inj is not None and inj.tick(kind):
            # process-LOCAL injector (same seam as the thread NVM): the
            # arming process's own instruction stream trips it — the
            # deterministic in-parent fuzz drivers; the shared countdown
            # below stays the cross-process crash mechanism
            self._injector = None
            self.crash(inj.rng)
            raise SimulatedCrash()
        if mv[_M_COUNT] >= 0:
            with self._lock:
                cd = mv[_M_COUNT]
                if cd < 0:           # another process just fired it
                    fire = False
                else:
                    mv[_M_COUNT] = cd - 1
                    fire = cd - 1 < 0
                if fire:
                    mv[_M_COUNT] = -1
            if fire:
                rng = self._crash_rng
                if rng is None and mv[_M_SEED] >= 0:
                    import random
                    rng = random.Random(mv[_M_SEED])
                self.crash(rng)
                raise SimulatedCrash()

    def _halt_check_locked(self) -> None:
        """Raise before an instruction takes ANY shared effect on a
        powered-off machine.  Must run under ``self._lock``: ``crash``
        raises the flag under the same lock, so a surviving process can
        never slip a ring append or counter bump past the cut."""
        if self._mv[_M_HALT]:
            raise SimulatedCrash()

    def _split_runs(self, runs) -> List[Tuple[int, int, int]]:
        """Split (first_line, n_lines) runs at segment boundaries:
        [(segment, first_line, n_lines)] — each write-back entry lives
        on exactly one device."""
        if self.segments == 1:
            return [(0, first, n) for first, n in runs]
        lps = self.words_per_seg // LINE
        out = []
        for first, n in runs:
            while n:
                s = min(first // lps, self.segments - 1)
                take = n if s == self.segments - 1 \
                    else min(n, (s + 1) * lps - first)
                out.append((s, first, take))
                first += take
                n -= take
        return out

    def _persist_runs(self, runs) -> None:
        """Shared body of pwb/persist_lines: queue every (line) run on
        its segment's ring, count word + blob lines."""
        split = self._split_runs(runs)
        aud = self._audit
        spilled: Optional[list] = [] if aud is not None else None
        mv = self._mv
        with self._lock:
            self._halt_check_locked()
            total = 0
            for s, first, n_lines in split:
                if not self.pwb_nop:
                    blob_lines = self._ring_append_locked(s, first,
                                                          n_lines,
                                                          spilled)
                elif mv[_M_BLOBBED]:
                    refs = self._blob_refs_in(
                        self.backend.vol_base + WORD_I64 * first * LINE,
                        n_lines * LINE)
                    blob_lines = self._blob_lines(refs)
                else:
                    blob_lines = 0
                mv[self._seg_slot(s, _S_PWB)] += n_lines + blob_lines
                total += n_lines + blob_lines
            mv[_M_PWB] += total
        if aud is not None:
            if spilled:
                aud.on_spill(spilled)
            aud.on_pwb([(first, n) for _s, first, n in split])
        self._tick_crash_point("pwb")

    def pwb(self, addr: int, n_words: int = 1) -> None:
        first = addr // LINE
        n_lines = (addr + n_words - 1) // LINE - first + 1
        self._persist_runs([(first, n_lines)])

    pwb_range = pwb

    def persist_lines(self, ranges) -> None:
        if isinstance(ranges, list) and len(ranges) == 1:
            addr, n_words = ranges[0]
            self.pwb(addr, n_words)
            return
        runs = self._pending_lines(ranges)
        if not runs:
            return
        self._persist_runs(runs)

    def pfence(self) -> None:
        mv = self._mv
        had_pending = False
        with self._lock:
            self._halt_check_locked()
            mv[_M_PFENCE] += 1
            for s in range(self.segments):
                if mv[self._seg_slot(s, _S_EFLAG)]:
                    had_pending = True
                    mv[self._seg_slot(s, _S_EPOCH)] += 1
                    mv[self._seg_slot(s, _S_EFLAG)] = 0
        if self._audit is not None:
            self._audit.on_pfence(had_pending)
        self._tick_crash_point("pfence")

    def psync(self) -> None:
        drained_by_seg: Dict[int, List[Tuple[int, int]]] = {}
        mv = self._mv
        with self._lock:
            self._halt_check_locked()
            mv[_M_PSYNC] += 1
            if not self.psync_nop:
                for s in range(self.segments):
                    if mv[self._seg_slot(s, _S_RING)]:
                        drained_by_seg[s] = self._drain_ring_locked(s)
                        # one device round trip per ENGAGED segment —
                        # this is the per-segment psync accounting the
                        # NUMA-ish model exists to expose
                        mv[self._seg_slot(s, _S_PSYNC)] += 1
        if self._audit is not None:
            # no VClock on the shm NVM: sync_now=0 disables the order
            # check, leaving the flush-state classes active
            self._audit.on_psync(
                [r for d in drained_by_seg.values() for r in d], 0.0)
        if drained_by_seg and self.persist_latency:
            for s, drained in drained_by_seg.items():
                runs, total_lines = self._run_stats(drained)
                cost = (self.persist_latency + runs * self.SEEK_COST
                        + total_lines * self.STREAM_COST)
                with self.backend.device_locks[s]:
                    time.sleep(cost)
        self._tick_crash_point("psync")

    # ---------------- crash / recovery ----------------------------------- #
    def arm_crash(self, after_persist_ops: int, rng=None, *,
                  lose_segment: Optional[int] = None) -> None:
        """Shared countdown: WHICHEVER process issues the
        ``after_persist_ops``-th next persistence instruction crashes
        the machine.  ``rng`` governs the adversarial drain when the
        arming process itself trips the countdown; a different process
        falls back to a seed captured here (same distribution, not the
        same draw) — pass ``rng=None`` for the deterministic
        drain-nothing cut either way.

        ``lose_segment``: partial-failure policy for the crash this arms
        — that segment's DIMM loses every pending write-back while all
        other segments drain fully (the maximally skewed per-device
        power-loss cut, repro.fuzz's segment-loss class).  Overrides the
        rng drain policy; shared, so whichever process trips the
        countdown applies it."""
        mv = self._mv
        if lose_segment is not None and \
                not 0 <= lose_segment < self.segments:
            raise ValueError(f"lose_segment {lose_segment} out of range "
                             f"(0..{self.segments - 1})")
        self._crash_rng = rng
        mv[_M_SEED] = (-1 if rng is None
                       else hash(rng.getstate()) & 0x7FFFFFFF)
        mv[_M_LOSESEG] = -1 if lose_segment is None else lose_segment
        mv[_M_COUNT] = after_persist_ops

    def disarm_crash(self) -> None:
        """Disarm any countdown AND clear the machine-off flag — the
        runtime's ``recover`` calls this first, which is exactly when
        the machine powers back on.

        Powering on is also when the volatile word image is restored
        from the durable one (with the blob refcount fix-up).  Doing it
        here rather than in ``crash()`` is deliberate: at crash time
        surviving worker processes may still be unwinding (plain stores
        between two persistence instructions), so a restore racing them
        could corrupt the blob refcounts; by the time the parent calls
        ``recover`` every worker has reported and parked — the restore
        scans run quiesced.  Until power-on, reads of the volatile
        image are reads of a dead machine's RAM (nothing meaningful);
        the durable image is fully resolved at crash time."""
        mv = self._mv
        with self._lock:
            mv[_M_COUNT] = -1
            mv[_M_LOSESEG] = -1
            if mv[_M_HALT]:
                self._restore_volatile_locked()
                mv[_M_HALT] = 0
        self._crash_rng = None

    def _restore_volatile_locked(self) -> None:
        """vol := dur, with the blob refs of the buried volatile words
        released and the restored (durable) refs duplicated.  Chunks
        are immutable while referenced, so the restored refs decode
        against the very bytes the durable words were drained with —
        no blob image copy exists or is needed."""
        mv = self._mv
        heap = self.backend.heap
        blobbed = bool(mv[_M_BLOBBED])
        if blobbed:
            for s in range(self.segments):
                start, end = self._seg_word_span(s)
                for off in self._blob_refs_in(
                        self.backend.vol_base + WORD_I64 * start,
                        end - start):
                    heap.dec(off)
        n3 = self.backend.data_words * WORD_I64
        mv[self.backend.vol_base:self.backend.vol_base + n3] = \
            mv[self.backend.dur_base:self.backend.dur_base + n3]
        if blobbed:
            for s in range(self.segments):
                start, end = self._seg_word_span(s)
                for off in self._blob_refs_in(
                        self.backend.vol_base + WORD_I64 * start,
                        end - start):
                    heap.inc(off)

    def _seg_word_span(self, s: int) -> Tuple[int, int]:
        """Allocated [start, end) word range of segment ``s`` (the only
        words a blob-ref rescan needs to walk)."""
        start = s * self.words_per_seg + (LINE if s == 0 else 0)
        return start, self._mv[self._seg_slot(s, _S_ALLOC)]

    def crash(self, rng=None) -> None:
        mv = self._mv
        with self._lock:
            mv[_M_CRASHES] += 1
            blobbed = bool(mv[_M_BLOBBED])
            lose_seg = mv[_M_LOSESEG]
            mv[_M_LOSESEG] = -1
            for s in range(self.segments):
                entries = self._ring_entries_locked(s)
                drained_snaps: set = set()      # payload line offsets
                if lose_seg >= 0:
                    # segment-loss cut: the lost DIMM drains NOTHING;
                    # every surviving segment drains its whole ring —
                    # the most skewed per-device power-loss outcome
                    if s != lose_seg:
                        for _e, first, n_lines, _bl, payload in entries:
                            self._drain_entry_locked(first, n_lines,
                                                     payload)
                            for j in range(n_lines):
                                drained_snaps.add(
                                    payload + j * LINE * WORD_I64)
                elif rng is not None and entries:
                    # mirror NVM.crash per segment: epochs = distinct
                    # ids in order plus a trailing empty epoch when the
                    # current one is empty
                    distinct: List[int] = []
                    for e, _f, _n, _bl, _p in entries:
                        if not distinct or distinct[-1] != e:
                            distinct.append(e)
                    n_epochs = len(distinct) + \
                        (0 if mv[self._seg_slot(s, _S_EFLAG)] else 1)
                    cut = rng.randint(0, n_epochs - 1)
                    for e, first, n_lines, _bl, payload in entries:
                        if e in distinct[:cut]:
                            self._drain_entry_locked(first, n_lines,
                                                     payload)
                            for j in range(n_lines):
                                drained_snaps.add(
                                    payload + j * LINE * WORD_I64)
                    if cut < len(distinct):
                        cut_id = distinct[cut]
                        cut_epoch: List[Tuple[int, int]] = []
                        for e, first, n_lines, _bl, payload in entries:
                            if e == cut_id:
                                for j in range(n_lines):
                                    cut_epoch.append(
                                        (first + j,
                                         payload + j * LINE * WORD_I64))
                        taken_upto: Dict[int, int] = {}
                        for i, (line, _snap) in enumerate(cut_epoch):
                            if rng.random() < 0.5:
                                taken_upto[line] = i
                        for i, (line, snap) in enumerate(cut_epoch):
                            if i <= taken_upto.get(line, -1):
                                self._drain_entry_locked(line, 1, snap)
                                drained_snaps.add(snap)
                if blobbed:
                    # release the pins of every snapshot line the
                    # adversary dropped (drained lines transferred
                    # their pins to the durable words)
                    for _e, _first, n_lines, _bl, payload in entries:
                        for j in range(n_lines):
                            snap = payload + j * LINE * WORD_I64
                            if snap not in drained_snaps:
                                self._discard_span_locked(snap, LINE)
                mv[self._seg_slot(s, _S_RING)] = 0
                mv[self._seg_slot(s, _S_EFLAG)] = 0
                mv[self._seg_slot(s, _S_EPOCH)] = 0
            mv[_M_COUNT] = -1
            # machine off until disarm_crash — which is also where the
            # volatile image restore (and its blob-ref fix-up) happens:
            # surviving processes may still be mid-store right now, and
            # power-on is the first quiesced point (see disarm_crash)
            mv[_M_HALT] = 1
        if self._audit is not None:
            self._audit.on_crash()

    # ---------------- introspection -------------------------------------- #
    def pending_lines(self) -> int:
        with self._lock:
            return sum(n + bl
                       for s in range(self.segments)
                       for _e, _f, n, bl, _p in
                       self._ring_entries_locked(s))

    def segment_counters(self) -> List[Dict[str, int]]:
        """Per-segment device accounting: write-back lines, engaged
        psyncs, ring spills, allocated words."""
        mv = self._mv
        out = []
        for s in range(self.segments):
            start, end = self._seg_word_span(s)
            out.append({"segment": s,
                        "pwb": mv[self._seg_slot(s, _S_PWB)],
                        "psync": mv[self._seg_slot(s, _S_PSYNC)],
                        "ring_spills": mv[self._seg_slot(s, _S_SPILLS)],
                        "words_used": max(0, end - start)})
        return out

    def reset_counters(self) -> None:
        mv = self._mv
        for slot in _CTR_SLOT.values():
            mv[slot] = 0
        for s in range(self.segments):
            for f in (_S_PWB, _S_PSYNC, _S_SPILLS):
                mv[self._seg_slot(s, f)] = 0
        if self._audit is not None:
            self._audit.reset_metrics()

    def occupancy(self) -> Dict[str, int]:
        """Machine-wide memory gauge for the soak harness: allocated
        word footprint plus live blob bytes."""
        words = sum(sc["words_used"] for sc in self.segment_counters())
        heap = self.backend.heap.occupancy()
        word_bytes = words * WORD_I64 * 8
        return {"backend": "shm", "words_used": words,
                "word_bytes": word_bytes,
                "live_chunks": heap["live_chunks"],
                "blob_live_bytes": heap["live_bytes"],
                "blob_bump_bytes": heap["bump_bytes"],
                "occupancy_bytes": word_bytes + heap["live_bytes"]}

    def _blob_word_spans(self) -> List[Tuple[int, int]]:
        """Tagged-word (base_i64, n_words) regions that may hold blob
        refs: the allocated vol+dur span of every segment."""
        spans = []
        for s in range(self.segments):
            start, end = self._seg_word_span(s)
            if end > start:
                spans.append((self.backend.vol_base + WORD_I64 * start,
                              end - start))
                spans.append((self.backend.dur_base + WORD_I64 * start,
                              end - start))
        return spans

    def gc_blobs(self, compact: bool = True) -> Dict[str, int]:
        """Blob-heap GC pass (quiescent-point maintenance, e.g. from
        ``CombiningRuntime.quiesce``): optionally compact live chunks
        downward, then coalesce free space and retreat the bump
        pointer.  Requires empty write-back rings — ring snapshots pin
        chunks by ref, and a moved chunk must not leave a stale ref in
        an entry that drains later; callers psync first."""
        mv = self._mv
        with self._lock:
            for s in range(self.segments):
                if mv[self._seg_slot(s, _S_RING)]:
                    raise RuntimeError("gc_blobs needs empty write-back "
                                       "rings; psync before collecting")
            heap = self.backend.heap
            out = {"moved_chunks": 0}
            if compact and mv[_M_BLOBBED]:
                out = heap.compact(self._blob_word_spans())
            out.update(heap.gc())
            return out

    def blob_leak_check(self) -> Dict[str, int]:
        """Refcount audit over the word images (see
        ``BlobHeap.leak_check``); call with empty rings and quiesced
        boards for an exact answer."""
        return self.backend.heap.leak_check(self._blob_word_spans())

    def close(self) -> None:
        self._vol = self._dur = self._mv = None
        self.counters = None
        self.backend.close()
