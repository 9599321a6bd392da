"""CombiningRuntime — one owner for NVM, structures, announcement
boards, and crash/recovery.

The runtime is the "machine": it owns the simulated NVMM, every
recoverable structure living in it (registered via ``make`` /
``register``), every announcement board handed to combiner-style
components, and the per-thread handles.  Crashing the machine and
recovering it is then ONE call each, for *all* registered structures at
once:

    rt = CombiningRuntime(n_threads=4)
    q = rt.make("queue", "pbcomb")
    s = rt.make("stack", "pwfcomb")
    h = rt.attach(0)
    h.bind(q).enqueue(1); h.bind(s).push(2)
    rt.crash()            # adversarial write-back drain, volatile wiped
    rt.recover()          # every structure reset + in-flight replayed

``recover`` performs, in order: (1) disarm any pending crash countdown,
(2) wipe every announcement board (volatile, P1), (3) rebuild each
structure's volatile protocol state (locks, request arrays, S refs,
pending-link redo...), (4) replay every in-flight operation recorded by
the handles — the paper's system-support contract, one
``recover_many`` call per object — returning the responses keyed by
(object name, thread id).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

from ..core.atomics import Counters
from ..core.nvm import NVM
from .board import AnnounceBoard
from .handle import BATCH, Handle, bind
from .registry import get_adapter


class RecoverableObject:
    """A registered structure: core implementation + its adapter."""

    def __init__(self, name: str, core: Any, adapter: Any,
                 runtime: "CombiningRuntime") -> None:
        self.name = name
        self.core = core
        self.adapter = adapter
        self.runtime = runtime

    @property
    def kind(self) -> str:
        return self.adapter.kind

    @property
    def protocol(self) -> str:
        return self.adapter.protocol

    @property
    def detectable(self) -> bool:
        return self.adapter.detectable

    def snapshot(self) -> Any:
        """Comparable view of the logical state (drain order for linked
        structures, sorted keys for heaps, the value for counters)."""
        return self.adapter.snapshot(self.core)

    def bind(self, handle: Handle):
        return bind(handle, self)

    def __repr__(self) -> str:
        return f"<RecoverableObject {self.name}>"


class CombiningRuntime:
    def __init__(self, nvm: Optional[NVM] = None, n_threads: int = 8,
                 counters: Optional[Counters] = None,
                 nvm_words: Optional[int] = None,
                 profile: Optional[Any] = None,
                 backend: str = "threads",
                 segments: int = 1) -> None:
        """``profile`` (a cost-profile name or ``CostProfile``) engages
        the virtual clock on the lazily created NVM; ignored when an
        ``nvm`` is passed in (its own profile governs).

        ``backend`` selects the execution substrate for the lazily
        created NVM: ``"threads"`` (default, interpreter-heap volatile
        state) or ``"shm"`` (everything shared lives in a
        ``multiprocessing.shared_memory`` segment so
        ``spawn_workers(n)`` can fork true-parallel workers against it;
        DESIGN.md §7).  The shm backend has no virtual clock, so it
        rejects ``profile``.  ``nvm_words`` defaults per backend
        (2M words threads / 256K shm — the shm image is materialized
        in /dev/shm, not grown lazily by the interpreter).

        ``segments`` (shm only, DESIGN.md §8): stripe the NVM into that
        many NUMA-ish spans, one write-back ring + modeled sync device
        each; ``make`` places structures round-robin across them (or
        pass ``segment=`` explicitly) and ``segment_stats()`` reports
        the per-device accounting."""
        if backend not in ("threads", "shm"):
            raise ValueError(f"unknown backend {backend!r}; "
                             "expected 'threads' or 'shm'")
        if backend == "shm" and profile is not None:
            raise ValueError("the shm backend is wall-clock only: the "
                             "virtual clock's Lamport merges would need "
                             "cross-process clock state (use the thread "
                             "backend for modeled runs)")
        if segments != 1 and backend != "shm":
            raise ValueError("multi-segment NVM is a property of the shm "
                             "backend (the thread NVM models one DIMM)")
        self.nvm = nvm
        self.n_threads = n_threads
        self.counters = counters
        self._nvm_words = nvm_words
        self._profile = profile
        self._backend_kind = backend
        self._segments = segments
        self._next_segment = 0         # round-robin placement cursor
        self._placement: Dict[str, int] = {}
        self._owns_nvm = nvm is None   # close() releases only what we made
        self._closed = False
        self._pools: list = []
        self.objects: Dict[str, RecoverableObject] = {}
        self.boards: Dict[str, AnnounceBoard] = {}
        self._handles: Dict[int, Handle] = {}
        # (object name, tid) -> (op, args, seq) | (BATCH, calls, 0)
        self._inflight: Dict[Tuple[str, int], Tuple[str, Any, int]] = {}

    # ------------------ construction ----------------------------------- #
    def _ensure_nvm(self) -> NVM:
        """The NVM is created lazily: runtimes that only hand out boards
        (e.g. the serving engine's) never allocate a memory image."""
        if self._closed:
            raise RuntimeError("runtime is closed")
        if self.nvm is None:
            if self._backend_kind == "shm":
                from ..core.shm import ShmNVM
                self.nvm = ShmNVM(self._nvm_words or 1 << 18,
                                  segments=self._segments)
            else:
                self.nvm = NVM(self._nvm_words or 1 << 21,
                               profile=self._profile)
        return self.nvm

    def make(self, kind: str, protocol: str = "pbcomb",
             name: Optional[str] = None, segment: Optional[int] = None,
             **kw) -> RecoverableObject:
        """Create + register a recoverable structure from the registry.

        ``segment`` pins the structure's NVM allocations to one segment
        of a multi-segment shm NVM; by default structures are placed
        round-robin (the affinity policy — each structure's psyncs then
        drain through its own modeled device, DESIGN.md §8)."""
        adapter = get_adapter(kind, protocol)
        nvm = self._ensure_nvm()
        if kw.get("vector_apply") and getattr(nvm.backend, "kind",
                                              None) == "shm":
            raise ValueError(
                "vector_apply=True needs the threads backend: shm rounds "
                "run in forked worker processes, and a forked child "
                "cannot use the chip its parent holds")
        if nvm.segments > 1:
            if segment is None:
                segment = self._next_segment
                self._next_segment = (segment + 1) % nvm.segments
            with nvm.placement(segment):
                core = adapter.create(nvm, self.n_threads,
                                      counters=self.counters, **kw)
        else:
            if segment not in (None, 0):
                raise ValueError(
                    f"segment {segment} out of range: this runtime's "
                    "NVM models a single device (construct with "
                    "backend='shm', segments=N to get more)")
            segment = 0
            core = adapter.create(nvm, self.n_threads,
                                  counters=self.counters, **kw)
        if name is None:
            base = f"{kind}/{protocol}"
            name, i = base, 1
            while name in self.objects:
                i += 1
                name = f"{base}#{i}"
        obj = self.register(name, core, adapter)
        self._placement[name] = segment
        return obj

    def register(self, name: str, core: Any,
                 adapter: Any) -> RecoverableObject:
        """Register an externally built core under this runtime's crash/
        recovery umbrella (the registry path uses this too)."""
        if name in self.objects:
            raise ValueError(f"object name {name!r} already registered")
        obj = RecoverableObject(name, core, adapter, self)
        self.objects[name] = obj
        return obj

    def board(self, name: str, n_slots: int,
              on_announce=None) -> AnnounceBoard:
        """A shared announcement board, reset by ``recover`` like every
        other piece of volatile state."""
        if name in self.boards:
            raise ValueError(f"board name {name!r} already registered")
        b = AnnounceBoard(n_slots, on_announce)
        self.boards[name] = b
        return b

    def attach(self, thread_id: int) -> Handle:
        """Per-thread handle; re-attaching returns the same handle (its
        seq counters must survive crashes — they are the paper's
        system-maintained consecutive sequence numbers)."""
        if thread_id not in self._handles:
            self._handles[thread_id] = Handle(self, thread_id)
        return self._handles[thread_id]

    def spawn_workers(self, n_workers: int, tids=None):
        """Fork ``n_workers`` processes, each driving one per-process
        Handle against this runtime's shared-memory board (repro.api.mp
        — requires ``backend="shm"``).  Create every structure FIRST:
        the children inherit the runtime by fork.

            rt = CombiningRuntime(n_threads=4, backend="shm")
            q = rt.make("queue", "pbcomb")
            with rt.spawn_workers(4) as pool:
                res = pool.run_pairs(q, 500)
            print(q.adapter.degree_stats(q.core))   # measured degree
        """
        # check the REAL substrate (covers a pre-built nvm= passed to
        # __init__ in either direction, not just the backend kwarg);
        # reject the lazy thread case BEFORE materializing a ~2M-word
        # NVM whose only purpose would be raising this error
        if ((self.nvm is None and self._backend_kind != "shm")
                or (self.nvm is not None
                    and getattr(self.nvm.backend, "kind", None) != "shm")):
            raise RuntimeError(
                "spawn_workers needs a shared-memory NVM "
                "(CombiningRuntime(backend='shm') or nvm=ShmNVM(...)): "
                "thread-backend volatile state lives on the interpreter "
                "heap and would be copied, not shared, by fork")
        self._ensure_nvm()
        from .mp import WorkerPool
        pool = WorkerPool(self, n_workers, tids)
        self._pools.append(pool)
        return pool

    def degree_stats(self) -> Dict[str, Any]:
        """Measured combining-degree counters per registered object
        (None for protocols that do not combine)."""
        return {name: obj.adapter.degree_stats(obj.core)
                for name, obj in self.objects.items()}

    def quiesce(self, gc_blobs: bool = True) -> Dict[str, Any]:
        """Advance every registered structure's durable reclamation
        boundaries, then (shm backend, ``gc_blobs=True``) coalesce and
        compact the blob heap.  Call only at a quiescent point — no
        requests in flight anywhere (a fleet wave boundary, a drained
        bench phase).  Returns per-object reclaim summaries plus the
        blob-GC summary when it ran."""
        nvm = self._ensure_nvm()
        out: Dict[str, Any] = {}
        for name, obj in self.objects.items():
            res = obj.adapter.quiesce(obj.core)
            if res is not None:
                out[name] = res
        gc = getattr(nvm, "gc_blobs", None)
        if gc_blobs and gc is not None:
            nvm.psync()            # drain every write-back ring first
            out["blob_gc"] = gc()
        return out

    def occupancy(self) -> Dict[str, Any]:
        """Backend memory accounting (see ``NVM.occupancy``)."""
        return self._ensure_nvm().occupancy()

    def segment_stats(self) -> Dict[str, Any]:
        """Per-segment device accounting + the structure placement map
        (which object allocates on which modeled DIMM)."""
        nvm = self._ensure_nvm()
        return {"segments": nvm.segments,
                "counters": nvm.segment_counters(),
                "placement": dict(self._placement)}

    def close(self) -> None:
        """Stop any worker pools and release backend resources (the shm
        segment, if this runtime created it — an ``nvm=`` passed into
        the constructor belongs to the caller and is left open).
        Idempotent; the runtime rejects further use afterwards."""
        if self._closed:
            return
        self._closed = True
        for pool in self._pools:
            pool.close()
        self._pools.clear()
        if self._owns_nvm:
            nvm_close = getattr(self.nvm, "close", None)
            if nvm_close is not None:
                nvm_close()
        self.nvm = None

    def __enter__(self) -> "CombiningRuntime":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # ------------------ crash simulation ------------------------------- #
    def arm_crash(self, after_persist_ops: int, rng=None,
                  **policy) -> None:
        """Arm a SimulatedCrash inside protocol code (crash-point
        enumeration); pair with ``recover``.  Extra keywords (e.g. the
        multi-segment ShmNVM's ``lose_segment`` partial-failure policy)
        pass through to the NVM."""
        self._ensure_nvm().arm_crash(after_persist_ops, rng, **policy)

    def crash(self, rng=None) -> None:
        """Full-machine crash: adversarial write-back drain, volatile
        image reset to the durable one."""
        if self.nvm is not None:
            self.nvm.crash(rng)

    def recover(self, inflight=None) -> Dict[Tuple[str, int], Any]:
        """One-call recovery for everything the runtime owns.  Returns
        the replayed in-flight responses keyed (object name, tid).

        ``inflight``: extra in-flight records from OTHER processes —
        ``[(obj_name, tid, op, args, seq), ...]`` as reported by a
        crashed worker pool (``PoolResult.inflight``).  The runtime's
        own records and the reported ones are replayed together; on the
        shm backend ``disarm_crash`` also clears the machine-off flag,
        so recovery is what powers the machine back on for every
        surviving worker."""
        if self.nvm is not None:
            self.nvm.disarm_crash()
        if self._backend_kind == "shm":
            # a crashed worker process leaves its own psc-* segments
            # behind (its atexit never ran) — recovery is the natural
            # point to sweep segments whose owner pid is dead
            from ..core.shm import reap_orphan_segments
            reap_orphan_segments()
        for b in self.boards.values():
            b.reset()
        for obj in self.objects.values():
            obj.adapter.reset_volatile(obj.core)
        # snapshot + clear IN PLACE: handle invokers captured this dict
        # at bind time, so reassigning it would orphan every bound proxy
        # created before the recover (their in-flight records would land
        # in a dead dict and never replay)
        inflight_map = dict(self._inflight)
        self._inflight.clear()
        for name, tid, op, args, seq in inflight or ():
            inflight_map[(name, tid)] = (op, args, seq)
        responses: Dict[Tuple[str, int], Any] = {}
        records: Dict[str, list] = {}
        for (name, tid), (op, a, seq) in inflight_map.items():
            obj = self.objects.get(name)
            if obj is None:
                continue
            if op == BATCH:
                responses[(name, tid)] = obj.adapter.recover_batch(
                    obj.core, tid, a)
            else:
                records.setdefault(name, []).append((tid, op, a, seq))
        # each object's single records in one call, so a combining core
        # serves them in one round (DESIGN.md §3)
        for name, recs in records.items():
            obj = self.objects[name]
            replies = obj.adapter.recover_many(obj.core, recs)
            for (tid, _op, _a, _seq), ret in zip(recs, replies):
                responses[(name, tid)] = ret
        return responses


def make_recoverable(kind: str, protocol: str = "pbcomb", *,
                     runtime: Optional[CombiningRuntime] = None,
                     n_threads: int = 8, **kw) -> RecoverableObject:
    """Factory shortcut: a recoverable ``kind`` under ``protocol``.

    Without an explicit runtime a fresh one is created and reachable as
    ``obj.runtime`` — so one-liners still get crash()/recover()/attach().
    """
    rt = runtime or CombiningRuntime(n_threads=n_threads)
    return rt.make(kind, protocol, **kw)
