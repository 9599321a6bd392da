"""Sequential object interface used by the combining protocols.

A combiner applies announced requests to the ``st`` field of a StateRec
living inside simulated NVMM.  Objects define how many NVM words their
state occupies and how to apply a request to it.  This is the paper's
"derive a recoverable implementation of any data structure from its
sequential implementation" interface (Section 8).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

from .nvm import NVM
from .tracing import span


def _vector():
    """The vectorized round bodies (repro.kernels.vector_rounds),
    imported on the first ``vector_apply`` so that runs which never
    vectorize do not import jax.  A failed import raises."""
    from ..kernels import vector_rounds
    return vector_rounds


class SeqObject:
    """A sequential object whose state lives in ``state_words`` NVM words."""

    state_words: int = 1
    #: ops guaranteed never to write state.  The lock baselines skip
    #: the whole persistence sentence for these (nothing to flush, and
    #: the response only depends on state already psync'd under the
    #: same lock).  Ops that merely MAY be no-ops (stale CKPT, DEQ on
    #: empty) are not listed: the per-op-persist baselines pay their
    #: unconditional fence+psync there — the wasted work the audit's
    #: redundancy metric exists to expose.
    READ_ONLY: frozenset = frozenset()

    def init_state(self, nvm: NVM, st_base: int) -> None:
        raise NotImplementedError

    def apply(self, nvm: NVM, st_base: int, func: str, args: Any,
              ctx: Optional[Any] = None) -> Any:
        """Apply request ``(func, args)`` to state at ``st_base``; return the
        response.  ``ctx`` is the running combiner instance — structure
        implementations use it to record extra NVM ranges to persist
        (PBQueue's ``toPersist``)."""
        raise NotImplementedError

    def vector_apply(self, nvm: NVM, st_base: int, func: str,
                     args_list: List[Any],
                     ctx: Optional[Any] = None) -> Optional[List[Any]]:
        """VectorApply seam: apply a HOMOGENEOUS batch of ``func``
        announcements (one per combined request, in announcement order)
        as a single jitted kernel over the packed argument array, and
        return the per-request responses — or None to make the combiner
        fall back to d per-op ``apply`` calls.

        The contract is exactness-or-decline: an implementation may only
        return a response list if the resulting state words and
        responses are identical to what the per-op loop would produce
        (repro.kernels.vector_rounds documents the packing guards that
        enforce this).  State is read and written through the volatile
        ``read_range``/``write_range`` accessors, which cost zero NVM
        persistence instructions — the enclosing round's commit sentence
        persists the StateRec exactly as before, so modeled counters are
        untouched by the vector path.  An object with a ``touch_plan``
        writes only the words its per-op loop writes: PBComb persists
        the lines a round wrote (DESIGN.md §3), and both paths must
        persist the same ones.  The base object declines always:
        vectorization is opt-in per structure."""
        return None


class AtomicFloatObject(SeqObject):
    """The paper's synthetic benchmark object (Section 6, Figures 1-3):
    ``AtomicFloat(O, k)`` reads v, stores v*k, returns v."""

    state_words = 1

    def init_state(self, nvm: NVM, st_base: int) -> None:
        nvm.write(st_base, 1.0)

    def apply(self, nvm, st_base, func, args, ctx=None):
        v = nvm.read(st_base)
        nvm.write(st_base, v * args)
        return v

    def vector_apply(self, nvm, st_base, func, args_list, ctx=None):
        if func != "MUL":
            return None
        with span("seam.gather"):
            v = nvm.read(st_base)
        out = _vector().mul_round(v, args_list)
        if out is None:
            return None
        v, resps = out
        with span("seam.scatter"):
            nvm.write(st_base, v)
        return resps


class FetchAddObject(SeqObject):
    """Fetch&Add counter — handy for linearizability checking (the multiset
    of responses of k FAA(1) ops must be exactly {0..k-1})."""

    state_words = 1

    def init_state(self, nvm: NVM, st_base: int) -> None:
        nvm.write(st_base, 0)

    def apply(self, nvm, st_base, func, args, ctx=None):
        v = nvm.read(st_base)
        nvm.write(st_base, v + args)
        return v

    def vector_apply(self, nvm, st_base, func, args_list, ctx=None):
        if func != "FAA":
            return None
        with span("seam.gather"):
            v = nvm.read(st_base)
        out = _vector().faa_round(v, args_list)
        if out is None:
            return None
        v, resps = out
        with span("seam.scatter"):
            nvm.write(st_base, v)
        return resps


class SeqQueueObject(SeqObject):
    """Bounded sequential FIFO entirely inside the StateRec.

    State layout: word 0 = head index, word 1 = tail index, words
    2..capacity+1 = ring buffer (indices grow monotonically; the slot is
    ``index % capacity``).  Used by the lock/undo-log baselines so the
    protocol matrix covers ``queue`` for every protocol — the linked
    PBQueue/PWFQueue keep their node-based representation.
    """

    def __init__(self, capacity: int = 1024) -> None:
        self.capacity = capacity
        self.state_words = capacity + 2

    def init_state(self, nvm: NVM, st_base: int) -> None:
        nvm.write_range(st_base, [0] * (self.capacity + 2))

    def apply(self, nvm, st_base, func, args, ctx=None):
        head, tail = nvm.read(st_base), nvm.read(st_base + 1)
        if func == "ENQ":
            if tail - head >= self.capacity:
                return False                      # full
            nvm.write(st_base + 2 + tail % self.capacity, args)
            nvm.write(st_base + 1, tail + 1)
            return "ACK"
        if func == "DEQ":
            if head == tail:
                return None                       # empty
            v = nvm.read(st_base + 2 + head % self.capacity)
            nvm.write(st_base, head + 1)
            return v
        raise ValueError(f"unknown queue op {func}")

    def vector_apply(self, nvm, st_base, func, args_list, ctx=None):
        if func not in ("ENQ", "DEQ"):
            return None
        with span("seam.gather"):
            head, tail = nvm.read(st_base), nvm.read(st_base + 1)
            if type(head) is not int or type(tail) is not int:
                return None
            ring = nvm.read_range(st_base + 2, self.capacity)
        out = _vector().queue_round(ring, head, tail, func, args_list)
        if out is None:
            return None
        ring2, h2, t2, resps = out
        with span("seam.scatter"):
            # only the words the per-op loop writes: head on a dequeue,
            # tail and each filled slot on an enqueue
            cap = self.capacity
            for i in range(tail, t2):
                nvm.write(st_base + 2 + i % cap, ring2[i % cap])
            if t2 != tail:
                nvm.write(st_base + 1, t2)
            if h2 != head:
                nvm.write(st_base, h2)
        return resps

    def touch_plan(self, nvm: NVM, st_base: int, func: str,
                   args: Any) -> List[Tuple[int, int]]:
        """(offset, n_words) ranges the next ``apply`` will modify —
        lets the lock baselines persist/log only the touched lines
        (their documented scattered-per-op cost shape) instead of the
        whole bounded buffer."""
        head, tail = nvm.read(st_base), nvm.read(st_base + 1)
        if func == "ENQ":
            if tail - head >= self.capacity:
                return []
            return [(1, 1), (2 + tail % self.capacity, 1)]
        return [] if head == tail else [(0, 1)]

    def snapshot(self, nvm: NVM, st_base: int) -> List[Any]:
        head, tail = nvm.read(st_base), nvm.read(st_base + 1)
        return [nvm.read(st_base + 2 + i % self.capacity)
                for i in range(head, tail)]


class SeqStackObject(SeqObject):
    """Bounded sequential LIFO entirely inside the StateRec.

    State layout: word 0 = size, words 1..capacity = the array.  Used by
    the lock/undo-log baselines so the protocol matrix covers ``stack``
    for every protocol.
    """

    def __init__(self, capacity: int = 1024) -> None:
        self.capacity = capacity
        self.state_words = capacity + 1

    def init_state(self, nvm: NVM, st_base: int) -> None:
        nvm.write_range(st_base, [0] * (self.capacity + 1))

    def apply(self, nvm, st_base, func, args, ctx=None):
        size = nvm.read(st_base)
        if func == "PUSH":
            if size >= self.capacity:
                return False                      # full
            nvm.write(st_base + 1 + size, args)
            nvm.write(st_base, size + 1)
            return "ACK"
        if func == "POP":
            if size == 0:
                return None                       # empty
            v = nvm.read(st_base + size)
            nvm.write(st_base, size - 1)
            return v
        raise ValueError(f"unknown stack op {func}")

    def vector_apply(self, nvm, st_base, func, args_list, ctx=None):
        if func not in ("PUSH", "POP"):
            return None
        with span("seam.gather"):
            size = nvm.read(st_base)
            if type(size) is not int:
                return None
            arr = nvm.read_range(st_base + 1, self.capacity)
        out = _vector().stack_round(arr, size, func, args_list)
        if out is None:
            return None
        arr2, s2, resps = out
        with span("seam.scatter"):
            # only the words the per-op loop writes: the size, and each
            # pushed slot
            if s2 != size:
                nvm.write(st_base, s2)
            if s2 > size:
                nvm.write_range(st_base + 1 + size, arr2[size:s2])
        return resps

    def touch_plan(self, nvm: NVM, st_base: int, func: str,
                   args: Any) -> List[Tuple[int, int]]:
        """See ``SeqQueueObject.touch_plan``."""
        size = nvm.read(st_base)
        if func == "PUSH":
            if size >= self.capacity:
                return []
            return [(0, 1), (1 + size, 1)]
        return [] if size == 0 else [(0, 1)]

    def snapshot(self, nvm: NVM, st_base: int) -> List[Any]:
        size = nvm.read(st_base)
        return [nvm.read(st_base + 1 + i)
                for i in range(size - 1, -1, -1)]   # top first


class ResponseLogObject(SeqObject):
    """Durable response log — the serving engine's completion path as a
    sequential object (DESIGN.md §8).

    State layout: client c owns words ``2c`` (last seq) and ``2c + 1``
    (last response).  Responses are rich payloads (token lists, dicts):
    on the shm backend they ride the blob heap; the thread backend's
    Python-object words hold them natively.

    Ops:
      * ``RECORD (client, seq, response)`` — overwrite c's pair; returns
        the response.  Idempotent: replaying a RECORD with the same
        arguments is a no-op in effect, which is what makes the
        adapter's crash replay exactly-once *in effect* without leaning
        on the protocol's per-thread announce parity (a batched
        RECORD_MANY advances the handle seq by more than one, so parity
        detectability does not apply here).
      * ``RECORD_MANY ((client, seq, response), ...)`` — one combining
        round persists every completion of a serving round together
        (one contiguous StateRec write, one psync).
      * ``LOOKUP client`` — (seq, response) pair; the paper's Recover
        reads this to answer re-announced requests from the log.
    """

    READ_ONLY = frozenset({"LOOKUP"})

    def __init__(self, n_clients: int = 8) -> None:
        self.n_clients = n_clients
        self.state_words = 2 * n_clients

    def init_state(self, nvm: NVM, st_base: int) -> None:
        nvm.write_range(st_base, [0, None] * self.n_clients)

    def _record(self, nvm, st_base, client, seq, response) -> None:
        if not 0 <= client < self.n_clients:
            raise ValueError(f"client {client} out of range "
                             f"(log has {self.n_clients} slots)")
        # response before seq: a torn StateRec can never pair a new seq
        # with an old response (same publication discipline as the words)
        nvm.write(st_base + 2 * client + 1, response)
        nvm.write(st_base + 2 * client, seq)

    def apply(self, nvm, st_base, func, args, ctx=None):
        if func == "RECORD":
            client, seq, response = args
            self._record(nvm, st_base, client, seq, response)
            return response
        if func == "RECORD_MANY":
            for client, seq, response in args:
                self._record(nvm, st_base, client, seq, response)
            return tuple(r for _c, _s, r in args)
        if func == "LOOKUP":
            c = args
            return (nvm.read(st_base + 2 * c),
                    nvm.read(st_base + 2 * c + 1))
        raise ValueError(f"unknown log op {func}")

    def vector_apply(self, nvm, st_base, func, args_list, ctx=None):
        # KV/log record batches: d RECORDs in one kernel that flags the
        # last write to each client, then only those words are written
        # (RECORD_MANY batches are tuples-of-tuples — eager path).
        if func != "RECORD":
            return None
        if not all(isinstance(t, (tuple, list)) and len(t) == 3
                   for t in args_list):
            return None
        out = _vector().log_round(self.n_clients, args_list)
        if out is None:
            return None
        writes, resps = out
        with span("seam.scatter"):
            for client, seq, resp in writes:
                # response before seq — same torn-StateRec discipline as
                # the eager ``_record``
                nvm.write(st_base + 2 * client + 1, resp)
                nvm.write(st_base + 2 * client, seq)
        return resps

    def touch_plan(self, nvm: NVM, st_base: int, func: str,
                   args: Any) -> List[Tuple[int, int]]:
        if func == "RECORD":
            return [(2 * args[0], 2)]
        if func == "RECORD_MANY":
            return [(2 * c, 2) for c, _s, _r in args]
        return []

    def snapshot(self, nvm: NVM, st_base: int) -> List[Tuple[int, Any]]:
        return [(nvm.read(st_base + 2 * c), nvm.read(st_base + 2 * c + 1))
                for c in range(self.n_clients)]


class CheckpointObject(SeqObject):
    """Checkpoint cell — the sharded-checkpoint commit as a sequential
    object: one (step, payload) pair, newest step wins (exactly the
    ``PBCombCheckpointer``'s object semantics, but living in NVM words
    so the shm backend can combine checkpoint announcements from real
    worker processes).

    Ops:
      * ``CKPT (step, payload)`` — install iff ``step`` advances the
        durable step; response is the step now current (monotone, so
        crash replay is idempotent: a replayed CKPT that already took
        effect — or was superseded — changes nothing).
      * ``CKPTGET`` — the (step, payload) pair.
    """

    state_words = 2
    READ_ONLY = frozenset({"CKPTGET"})

    def init_state(self, nvm: NVM, st_base: int) -> None:
        nvm.write_range(st_base, [0, None])

    def apply(self, nvm, st_base, func, args, ctx=None):
        if func == "CKPT":
            step, payload = args
            cur = nvm.read(st_base)
            if step > cur:
                # payload before step: a torn StateRec never pairs a
                # new step with an old payload
                nvm.write(st_base + 1, payload)
                nvm.write(st_base, step)
                return step
            return cur
        if func == "CKPTGET":
            return (nvm.read(st_base), nvm.read(st_base + 1))
        raise ValueError(f"unknown checkpoint op {func}")

    def vector_apply(self, nvm, st_base, func, args_list, ctx=None):
        if func != "CKPT":
            return None
        if not all(isinstance(t, (tuple, list)) and len(t) == 2
                   for t in args_list):
            return None
        with span("seam.gather"):
            step = nvm.read(st_base)
        out = _vector().ckpt_round(step, args_list)
        if out is None:
            return None
        st, pl, resps = out
        if pl is not None:       # some element advanced the step
            with span("seam.scatter"):
                # payload before step — same torn-StateRec discipline
                nvm.write(st_base + 1, pl)
                nvm.write(st_base, st)
        return resps

    def touch_plan(self, nvm: NVM, st_base: int, func: str,
                   args: Any) -> List[Tuple[int, int]]:
        if func == "CKPT" and args[0] > nvm.read(st_base):
            return [(0, 2)]
        return []

    def snapshot(self, nvm: NVM, st_base: int) -> Dict[str, Any]:
        return {"step": nvm.read(st_base),
                "payload": nvm.read(st_base + 1)}


class HeapObject(SeqObject):
    """Bounded sequential min-heap (paper Section 5, PBHEAP).

    State layout: word 0 = current size, words 1..capacity = the array.
    Supports HINSERT / HDELETEMIN / HGETMIN.
    """

    READ_ONLY = frozenset({"HGETMIN"})

    def __init__(self, capacity: int) -> None:
        self.capacity = capacity
        self.state_words = capacity + 1

    def init_state(self, nvm: NVM, st_base: int) -> None:
        nvm.write_range(st_base, [0] * (self.capacity + 1))

    # -- sequential helpers on NVM words ------------------------------- #
    def _get(self, nvm, b, i):
        return nvm.read(b + 1 + i)

    def _set(self, nvm, b, i, v):
        nvm.write(b + 1 + i, v)

    def apply(self, nvm, st_base, func, args, ctx=None):
        size = nvm.read(st_base)
        if func == "HGETMIN":
            return self._get(nvm, st_base, 0) if size > 0 else None
        if func == "HINSERT":
            if size >= self.capacity:
                return False
            i = size
            self._set(nvm, st_base, i, args)
            while i > 0:
                parent = (i - 1) // 2
                if self._get(nvm, st_base, parent) <= self._get(nvm, st_base, i):
                    break
                a = self._get(nvm, st_base, parent)
                b_ = self._get(nvm, st_base, i)
                self._set(nvm, st_base, parent, b_)
                self._set(nvm, st_base, i, a)
                i = parent
            nvm.write(st_base, size + 1)
            return True
        if func == "HDELETEMIN":
            if size == 0:
                return None
            top = self._get(nvm, st_base, 0)
            last = self._get(nvm, st_base, size - 1)
            size -= 1
            nvm.write(st_base, size)
            if size > 0:
                self._set(nvm, st_base, 0, last)
                i = 0
                while True:
                    l, r = 2 * i + 1, 2 * i + 2
                    smallest = i
                    if l < size and self._get(nvm, st_base, l) < self._get(nvm, st_base, smallest):
                        smallest = l
                    if r < size and self._get(nvm, st_base, r) < self._get(nvm, st_base, smallest):
                        smallest = r
                    if smallest == i:
                        break
                    a = self._get(nvm, st_base, i)
                    b_ = self._get(nvm, st_base, smallest)
                    self._set(nvm, st_base, i, b_)
                    self._set(nvm, st_base, smallest, a)
                    i = smallest
            return top
        raise ValueError(f"unknown heap op {func}")

    def vector_apply(self, nvm, st_base, func, args_list, ctx=None):
        # heap key-array ops: a homogeneous HINSERT/HDELETEMIN round is
        # one lax.scan over the announcements, each step sifting via a
        # lax.while_loop on the packed key array
        if func not in ("HINSERT", "HDELETEMIN"):
            return None
        with span("seam.gather"):
            size = nvm.read(st_base)
            if type(size) is not int:
                return None
            arr = nvm.read_range(st_base + 1, self.capacity)
        out = _vector().heap_round(arr, size, func, args_list)
        if out is None:
            return None
        arr2, size2, resps = out
        with span("seam.scatter"):
            nvm.write(st_base, size2)
            nvm.write_range(st_base + 1, arr2)
        return resps
