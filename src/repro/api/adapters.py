"""Structure adapters: one calling convention over every recoverable
structure and baseline.

The paper's claim is a *universal* recipe — any sequential data
structure becomes a recoverable concurrent one — but the seed exposed
each implementation through an ad-hoc convention (``PBComb.op(p, func,
args, seq)``, ``PBQueue.enqueue(p, value, seq)``, per-class recovery
dances).  An adapter normalizes exactly four things per structure:

  * **ops** — sugar-name -> (protocol func tag, seq group, default arg),
    e.g. ``enqueue -> ("ENQ", "enq", None)``.  The *seq group* matters
    for the split-instance queues: detectability parity is per combining
    instance, so the runtime keeps one seq counter per (object, group).
  * **invoke / recover** — the normal path and the paper's Recover path
    with identical signatures.
  * **reset_volatile / snapshot** — post-crash volatile rebuild and a
    comparable view of the logical state (for crash/recovery checks).
  * **announce / perform** — optional (detectable combining protocols
    only): split an op into its announcement and the combining phase so
    crash-point tests can enumerate crashes *inside* a round that is
    serving many announced requests.

Adapters are stateless; all state lives in the wrapped core object.
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Optional, Tuple

from ..core.atomics import Counters
from ..core.nvm import NVM
from ..core.objects import (CheckpointObject, FetchAddObject, HeapObject,
                            ResponseLogObject, SeqQueueObject,
                            SeqStackObject)
from ..core.pbcomb import PBComb, RequestRec
from ..core.pwfcomb import PWFComb
from ..structures import (DFCStack, DurableMSQueue, LockDirectObject,
                          LockUndoLogObject, PBHeap, PBQueue, PBStack,
                          PWFQueue, PWFStack)


class OpSpec(NamedTuple):
    func: str               # protocol func tag ("ENQ", "PUSH", "FAA", ...)
    group: str              # seq-counter group (parity is per instance)
    default: Any = None     # args value for zero-arg sugar ("read" -> 0)


QUEUE_OPS = {"enqueue": OpSpec("ENQ", "enq"),
             "dequeue": OpSpec("DEQ", "deq")}
STACK_OPS = {"push": OpSpec("PUSH", "main"),
             "pop": OpSpec("POP", "main")}
HEAP_OPS = {"insert": OpSpec("HINSERT", "main"),
            "delete_min": OpSpec("HDELETEMIN", "main"),
            "get_min": OpSpec("HGETMIN", "main")}
COUNTER_OPS = {"fetch_add": OpSpec("FAA", "main", 1),
               "read": OpSpec("FAA", "main", 0)}
LOG_OPS = {"record": OpSpec("RECORD", "main"),
           "lookup": OpSpec("LOOKUP", "main")}
CKPT_OPS = {"persist": OpSpec("CKPT", "main"),
            "latest": OpSpec("CKPTGET", "main")}


class StructureAdapter:
    """Base adapter: subclasses set ``kind``/``protocol``/``OPS`` and
    implement the structure-specific pieces."""

    kind: str = ""
    protocol: str = ""
    detectable: bool = False     # exactly-once recovery of in-flight ops
    can_announce: bool = False   # announce/perform split available
    OPS: Dict[str, OpSpec] = {}

    # ---------------- construction ------------------------------------ #
    def create(self, nvm: NVM, n_threads: int,
               counters: Optional[Counters] = None, **kw) -> Any:
        raise NotImplementedError

    # ---------------- normal + recovery paths ------------------------- #
    def _spec(self, op: str) -> OpSpec:
        try:
            return self.OPS[op]
        except KeyError:
            raise ValueError(
                f"{self.kind}/{self.protocol} has no op {op!r}; "
                f"supported: {sorted(self.OPS)}") from None

    def _args(self, op: str, args: Any) -> Any:
        return self._spec(op).default if args is None else args

    def invoke(self, core: Any, p: int, op: str, args: Any,
               seq: int) -> Any:
        raise NotImplementedError

    def bind_op(self, core: Any, op: str):
        """Pre-resolved ``fn(p, args, seq)`` for one (core, op) pair —
        handles cache these so the hot invoke path stops re-resolving op
        strings and OpSpecs per call.  The default wraps ``invoke``;
        adapters whose cores expose a direct entry override it to bind
        the core method itself."""
        self._spec(op)                  # validate (raises ValueError)
        invoke = self.invoke

        def fn(p: int, args: Any, seq: int) -> Any:
            return invoke(core, p, op, args, seq)
        return fn

    def bind_parts(self, core: Any, op: str):
        """Optional deeper binding: ``(entry, func, default)`` such that
        ``entry(p, func, args-or-default, seq)`` IS the operation — lets
        the handle skip one wrapper frame per call.  None means "use
        bind_op"."""
        return None

    def recover(self, core: Any, p: int, op: str, args: Any,
                seq: int) -> Any:
        spec = self._spec(op)
        return core.recover(p, spec.func, self._args(op, args), seq)

    def recover_batch(self, core: Any, p: int,
                      calls: List[Tuple[str, Any, int]]) -> List[Any]:
        return [self.recover(core, p, op, args, seq)
                for op, args, seq in calls]

    def recover_many(self, core: Any,
                     records: List[Tuple[int, str, Any, int]]) -> List[Any]:
        """Every in-flight ``(p, op, args, seq)`` of one object after a
        crash; the replies in the order of ``records``.  One record at a
        time, unless the core can recover them together."""
        return [self.recover(core, p, op, args, seq)
                for p, op, args, seq in records]

    # ---------------- optional paths ----------------------------------- #
    invoke_batch = None   # type: Optional[Any]  # set by batching adapters

    def announce(self, core: Any, p: int, op: str, args: Any,
                 seq: int) -> None:
        raise NotImplementedError(f"{self.protocol} cannot pre-announce")

    def perform(self, core: Any, p: int, op: str) -> Any:
        raise NotImplementedError(f"{self.protocol} cannot pre-announce")

    # ---------------- crash plumbing ----------------------------------- #
    def reset_volatile(self, core: Any) -> None:
        core.reset_volatile()

    # ---------------- reclamation -------------------------------------- #
    def quiesce(self, core: Any) -> Optional[dict]:
        """Advance the structure's durable reclamation boundaries at a
        quiescent point (no requests in flight).  Structures without a
        reclaimer return None."""
        return None

    def snapshot(self, core: Any) -> Any:
        raise NotImplementedError

    # ---------------- measured-degree accounting ------------------------ #
    def degree_stats(self, core: Any) -> Optional[dict]:
        """Measured combining-degree counters (rounds / ops_combined /
        degree_mean / degree_max) accumulated by the core since creation
        (or the last ``reset_degree_stats``), or None for protocols that
        do not combine (the per-op-persist baselines)."""
        return None

    def reset_degree_stats(self, core: Any) -> None:
        """Zero the degree counters (benchmarks call this after their
        warmup so degree_max reflects only the measured window)."""


# --------------------------------------------------------------------- #
# Combining-protocol adapters (PBComb / PWFComb families)               #
# --------------------------------------------------------------------- #
class _CombiningAdapter(StructureAdapter):
    """Shared logic for cores built from PBComb/PWFComb instances."""

    detectable = True
    can_announce = True

    def _instance(self, core: Any, op: str) -> Any:
        """The combining instance serving ``op`` (split queues override)."""
        return core

    def invoke(self, core, p, op, args, seq):
        spec = self._spec(op)
        return self._instance(core, op).op(p, spec.func,
                                           self._args(op, args), seq)

    def bind_op(self, core, op):
        spec = self._spec(op)
        inst_op = self._instance(core, op).op
        func, default = spec.func, spec.default

        def fn(p: int, args: Any, seq: int) -> Any:
            return inst_op(p, func, default if args is None else args, seq)
        return fn

    def bind_parts(self, core, op):
        spec = self._spec(op)
        return (self._instance(core, op).op, spec.func, spec.default)

    def announce(self, core, p, op, args, seq):
        spec = self._spec(op)
        inst = self._instance(core, op)
        rec = RequestRec(spec.func, self._args(op, args),
                         1 - inst.request[p].activate, 1)
        clk = inst.nvm.clock
        if clk is not None:
            rec.vtime = clk.now()   # combiner merges this (Lamport)
        inst.request[p] = rec

    def perform(self, core, p, op):
        return self._instance(core, op)._perform_request(p)

    def _recover_activate(self, inst: PBComb, p: int, seq: int) -> int:
        """The activate bit Recover re-announces: the seq's parity."""
        return seq % 2

    def recover_many(self, core, records):
        """PBComb instances recover their records together
        (``PBComb.recover_many``): one round per instance that holds an
        unserved record.  PWFComb keeps the one-at-a-time default."""
        groups: Dict[PBComb, Tuple[list, list]] = {}
        for i, (p, op, args, seq) in enumerate(records):
            inst = self._instance(core, op)
            if not isinstance(inst, PBComb):
                return super().recover_many(core, records)
            spec = self._spec(op)
            at, recs = groups.setdefault(inst, ([], []))
            at.append(i)
            recs.append((p, spec.func, self._args(op, args),
                         self._recover_activate(inst, p, seq)))
        out: List[Any] = [None] * len(records)
        for inst, (at, recs) in groups.items():
            for i, ret in zip(at, inst.recover_many(recs)):
                out[i] = ret
        return out

    def _instances(self, core):
        """The distinct combining instances behind this core (split
        queues have two; everything else one)."""
        return list({id(self._instance(core, op)): self._instance(core, op)
                     for op in self.OPS}.values())

    def degree_stats(self, core):
        """The degree counters, with each instance's ``trace_counters``
        summed over the instances: PBComb's waiter polls, queueing time
        while traced, copied words and recovery rounds; PWFComb's
        attempts, how each ended, and the requests they wasted."""
        from ..core.backend import merge_degree_stats
        insts = self._instances(core)
        out = merge_degree_stats([inst.stats.snapshot() for inst in insts])
        for inst in insts:
            if isinstance(inst, (PBComb, PWFComb)):
                for k, v in inst.trace_counters().items():
                    out[k] = out.get(k, 0) + v
        return out

    def reset_degree_stats(self, core):
        for inst in self._instances(core):
            inst.stats.reset()


def _pb_st(core: PBComb) -> int:
    return core._st_base(core._mindex())


def _pwf_st(core: PWFComb) -> int:
    return core._base(core.S.load())


class PBQueueAdapter(_CombiningAdapter):
    kind, protocol, OPS = "queue", "pbcomb", QUEUE_OPS

    def create(self, nvm, n_threads, counters=None, **kw):
        return PBQueue(nvm, n_threads, counters=counters, **kw)

    def _instance(self, core, op):
        return core.enq if op == "enqueue" else core.deq

    def snapshot(self, core):
        return core.drain()


class PWFQueueAdapter(PBQueueAdapter):
    protocol = "pwfcomb"

    def create(self, nvm, n_threads, counters=None, **kw):
        return PWFQueue(nvm, n_threads, counters=counters, **kw)

    def quiesce(self, core):
        return core.quiesce()


class PBStackAdapter(_CombiningAdapter):
    kind, protocol, OPS = "stack", "pbcomb", STACK_OPS

    def create(self, nvm, n_threads, counters=None, **kw):
        return PBStack(nvm, n_threads, counters=counters, **kw)

    def snapshot(self, core):
        return core.drain()


class PWFStackAdapter(PBStackAdapter):
    protocol = "pwfcomb"

    def create(self, nvm, n_threads, counters=None, **kw):
        return PWFStack(nvm, n_threads, counters=counters, **kw)

    def quiesce(self, core):
        return core.quiesce()


class PBHeapAdapter(_CombiningAdapter):
    kind, protocol, OPS = "heap", "pbcomb", HEAP_OPS

    def create(self, nvm, n_threads, counters=None, capacity=256,
               vector_apply=False, **kw):
        return PBHeap(nvm, n_threads, capacity=capacity, counters=counters,
                      vector_apply=vector_apply)

    def snapshot(self, core):
        base = _pb_st(core)
        size = core.nvm.read(base)
        return sorted(core.nvm.read(base + 1 + i) for i in range(size))


class PWFHeapAdapter(_CombiningAdapter):
    """The wait-free heap the paper leaves implicit: HeapObject is a
    SeqObject, so PWFComb transforms it exactly like PBComb does."""

    kind, protocol, OPS = "heap", "pwfcomb", HEAP_OPS

    def create(self, nvm, n_threads, counters=None, capacity=256, **kw):
        return PWFComb(nvm, n_threads, HeapObject(capacity),
                       counters=counters, **kw)

    def snapshot(self, core):
        base = _pwf_st(core)
        size = core.nvm.read(base)
        return sorted(core.nvm.read(base + 1 + i) for i in range(size))


class _ObjSnapshotMixin:
    """Snapshot through the wrapped SeqObject's own ``snapshot`` (the
    log/checkpoint objects define one; the combining cores expose the
    current StateRec base)."""

    _st = staticmethod(_pb_st)

    def snapshot(self, core):
        return core.obj.snapshot(core.nvm, self._st(core))


class _ReexecuteMixin:
    """Crash replay by re-executing the op as a fresh announcement, for
    objects whose op is idempotent (the log's seq guard, the
    checkpoint's step guard)."""

    def recover(self, core, p, op, args, seq):
        spec = self._spec(op)
        return self._instance(core, op).op(p, spec.func,
                                           self._args(op, args), seq)

    def _recover_activate(self, inst, p, seq):
        """A fresh announcement's flipped bit, as ``recover``'s ``op``."""
        return 1 - inst.request[p].activate


class PBLogAdapter(_ReexecuteMixin, _ObjSnapshotMixin, _CombiningAdapter):
    """Durable response log under PBComb — the serving engine's
    completion path as a registry structure (DESIGN.md §8).

    Crash replay is IDEMPOTENT re-execution instead of the per-thread
    announce-parity Recover: a batched RECORD_MANY advances the handle
    seq by the batch size, so seq parity no longer mirrors the announce
    bit — but re-applying a RECORD with identical (client, seq,
    response) is a no-op in effect, which gives the same exactly-once
    *effect* guarantee the parity path provides."""

    kind, protocol, OPS = "log", "pbcomb", LOG_OPS

    def create(self, nvm, n_threads, counters=None, n_clients=None,
               vector_apply=False, **kw):
        return PBComb(nvm, n_threads,
                      ResponseLogObject(n_clients or n_threads),
                      counters=counters, vector_apply=vector_apply)

    def recover_batch(self, core, p, calls):
        triples = tuple(self._args(op, args) for op, args, _seq in calls)
        return list(core.op(p, "RECORD_MANY", triples, calls[-1][2]))

    def invoke_batch(self, core, p, calls):
        """All completions of a round in ONE combining round — one
        contiguous StateRec write, one psync (what the serving engine's
        ``invoke_many`` completion path rides on)."""
        if any(op != "record" for op, _a, _s in calls):
            return [self.invoke(core, p, op, a, s) for op, a, s in calls]
        triples = tuple(a for _op, a, _s in calls)
        return list(core.op(p, "RECORD_MANY", triples, calls[-1][2]))

    def last_record(self, core, client: int):
        """(seq, response) currently logged for ``client`` — the
        paper's Recover reads this to answer re-announced requests
        without re-executing them."""
        base = self._st(core)
        return (core.nvm.read(base + 2 * client),
                core.nvm.read(base + 2 * client + 1))


class PWFLogAdapter(PBLogAdapter):
    protocol = "pwfcomb"
    _st = staticmethod(_pwf_st)

    def create(self, nvm, n_threads, counters=None, n_clients=None, **kw):
        return PWFComb(nvm, n_threads,
                       ResponseLogObject(n_clients or n_threads),
                       counters=counters, **kw)


class PBCkptAdapter(_ReexecuteMixin, _ObjSnapshotMixin, _CombiningAdapter):
    """Checkpoint cell under PBComb: d announcers' persist requests ride
    one combining round/psync; newest step wins.  Replay is idempotent
    (the step guard), same reasoning as PBLogAdapter."""

    kind, protocol, OPS = "ckpt", "pbcomb", CKPT_OPS

    def create(self, nvm, n_threads, counters=None, vector_apply=False, **kw):
        return PBComb(nvm, n_threads, CheckpointObject(),
                      counters=counters, vector_apply=vector_apply)


class PWFCkptAdapter(PBCkptAdapter):
    protocol = "pwfcomb"
    _st = staticmethod(_pwf_st)

    def create(self, nvm, n_threads, counters=None, **kw):
        return PWFComb(nvm, n_threads, CheckpointObject(),
                       counters=counters, **kw)


class PBCounterAdapter(_CombiningAdapter):
    kind, protocol, OPS = "counter", "pbcomb", COUNTER_OPS

    def create(self, nvm, n_threads, counters=None, vector_apply=False, **kw):
        return PBComb(nvm, n_threads, FetchAddObject(), counters=counters,
                      vector_apply=vector_apply)

    def snapshot(self, core):
        return core.nvm.read(_pb_st(core))


class PWFCounterAdapter(_CombiningAdapter):
    kind, protocol, OPS = "counter", "pwfcomb", COUNTER_OPS

    def create(self, nvm, n_threads, counters=None, **kw):
        return PWFComb(nvm, n_threads, FetchAddObject(),
                       counters=counters, **kw)

    def snapshot(self, core):
        return core.nvm.read(_pwf_st(core))


# --------------------------------------------------------------------- #
# Baseline adapters (Section 6 competitors)                             #
# --------------------------------------------------------------------- #
_SEQ_OBJ = {"queue": SeqQueueObject, "stack": SeqStackObject,
            "heap": HeapObject, "counter": FetchAddObject,
            "log": ResponseLogObject, "ckpt": CheckpointObject}
_KIND_OPS = {"queue": QUEUE_OPS, "stack": STACK_OPS,
             "heap": HEAP_OPS, "counter": COUNTER_OPS,
             "log": LOG_OPS, "ckpt": CKPT_OPS}


class _DirectOpAdapter(StructureAdapter):
    """Shared dispatch for cores exposing ``core.op(p, func, args, seq)``
    directly (lock baselines, DFC)."""

    def invoke(self, core, p, op, args, seq):
        spec = self._spec(op)
        return core.op(p, spec.func, self._args(op, args), seq)

    def bind_op(self, core, op):
        spec = self._spec(op)
        core_op = core.op
        func, default = spec.func, spec.default

        def fn(p: int, args: Any, seq: int) -> Any:
            return core_op(p, func, default if args is None else args, seq)
        return fn

    def bind_parts(self, core, op):
        spec = self._spec(op)
        return (core.op, spec.func, spec.default)


class LockAdapter(_DirectOpAdapter):
    """Coarse-lock baselines over any SeqObject (direct or undo-log)."""

    detectable = False

    def __init__(self, kind: str, undo: bool) -> None:
        self.kind = kind
        self.protocol = "lock-undo" if undo else "lock-direct"
        self.OPS = _KIND_OPS[kind]
        self._cls = LockUndoLogObject if undo else LockDirectObject
        self._obj_cls = _SEQ_OBJ[kind]

    def create(self, nvm, n_threads, counters=None, capacity=1024,
               n_clients=None, **kw):
        cls = self._obj_cls
        if cls is FetchAddObject or cls is CheckpointObject:
            obj = cls()
        elif cls is ResponseLogObject:
            obj = cls(n_clients or n_threads)
        else:
            obj = cls(capacity)
        return self._cls(nvm, n_threads, obj)

    def snapshot(self, core):
        nvm, base, obj = core.nvm, core.st_base, core.obj
        if hasattr(obj, "snapshot"):
            return obj.snapshot(nvm, base)
        if self.kind == "counter":
            return nvm.read(base)
        size = nvm.read(base)                    # HeapObject layout
        return sorted(nvm.read(base + 1 + i) for i in range(size))


class DurableMSQueueAdapter(StructureAdapter):
    kind, protocol, OPS = "queue", "durable-ms", QUEUE_OPS
    detectable = False

    def create(self, nvm, n_threads, counters=None, **kw):
        return DurableMSQueue(nvm, n_threads, **kw)

    def invoke(self, core, p, op, args, seq):
        if op == "enqueue":
            return core.enqueue(p, self._args(op, args), seq)
        return core.dequeue(p, seq)

    def bind_op(self, core, op):
        self._spec(op)
        if op == "enqueue":
            enq = core.enqueue
            return lambda p, args, seq: enq(p, args, seq)
        deq = core.dequeue
        return lambda p, args, seq: deq(p, seq)

    def snapshot(self, core):
        return core.drain()


class DFCStackAdapter(_DirectOpAdapter):
    kind, protocol, OPS = "stack", "dfc", STACK_OPS
    # DFC persists announcements and done-marks, and recover() uses them
    # as a fast path — but the combiner psyncs once per ROUND, so under
    # the explicit-epoch model a mid-round crash can drain the structural
    # update while dropping the done-mark (or vice versa).  Exactly-once
    # replay of in-flight ops is therefore not guaranteed; don't claim it.
    detectable = False
    # DFC announcements live in NVMM, so the announce/perform split is
    # natural: announce persists the request record (pwb+pfence — the
    # per-thread persistence DFC pays that PBComb avoids), perform runs
    # the combiner loop.  The modeled bench pass uses this to stage
    # rounds of a fixed combining degree deterministically.
    can_announce = True

    def create(self, nvm, n_threads, counters=None, **kw):
        return DFCStack(nvm, n_threads, **kw)

    def announce(self, core, p, op, args, seq):
        spec = self._spec(op)
        nvm = core.nvm
        base = core.ann_base[p]
        nvm.write(base, spec.func)
        nvm.write(base + 1, self._args(op, args))
        nvm.write(base + 2, seq)
        nvm.pwb(base, 3)
        nvm.pfence()
        if nvm.clock is not None:
            core._ann_vt[p] = nvm.clock.now()

    def perform(self, core, p, op):
        return core.perform(p)

    def degree_stats(self, core):
        from ..core.backend import merge_degree_stats
        return merge_degree_stats([core.stats.snapshot()])

    def reset_degree_stats(self, core):
        core.stats.reset()

    def snapshot(self, core):
        return core.drain()
