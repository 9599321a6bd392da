"""Worker-pool runner: per-process Handles driving the shared board.

``CombiningRuntime.spawn_workers(n)`` forks ``n`` worker processes,
each owning one logical thread id (a ``Handle``).  Everything the
protocols share — NVM images, announcement boards, locks, degree
counters — already lives in the runtime's shm backend, so the children
inherit working views by fork; nothing structural crosses a pipe.

Op dispatch is pickle-free: commands name objects and ops by STRING
(plus primitive args), and each worker resolves them locally through
``runtime.objects[name]`` + ``handle.invoker`` — i.e. through the same
cached ``bind_op`` fast path the thread benches use.  Only primitive
tuples travel over the queues.

Crash protocol: a ``SimulatedCrash`` (armed countdown, or the shared
``halted`` flag raised by a crash in another process) unwinds the
worker's current command; the worker reports its in-flight records —
``(obj_name, tid, op, args, seq)``, the paper's system-support
contract — plus everything it completed, and waits for the next
command.  The parent then calls ``runtime.recover(inflight=...)`` with
the reported records and may keep using the same pool.

Fork discipline: spawn AFTER every ``runtime.make`` call; objects
created later would not exist in the children.
"""

from __future__ import annotations

import multiprocessing
import queue as queue_mod
import sys
import threading
import time
import traceback
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

from ..core.nvm import SimulatedCrash

#: op-pair per kind for the canonical add/remove workload
_PAIR_OPS = {"queue": ("enqueue", "dequeue"),
             "stack": ("push", "pop"),
             "heap": ("insert", "delete_min"),
             "counter": ("fetch_add", "read")}

#: padding appended to rich pair values so they exceed the 16-byte
#: inline word codec and exercise the blob heap (DESIGN.md §8)
_RICH_PAD = "blob-payload-padding-" * 2


def rich_value(tid: int, i: int):
    """The rich (blob-sized) pair value: producer and index stay
    extractable as value[0]/value[1] for the order checkers."""
    return (tid, i, _RICH_PAD)


def toy_tokens(client: int, seq: int, gen_len: int) -> List[int]:
    """Deterministic toy generation for the serving workload — pure
    function of (client, seq) so a checker can recompute the expected
    response content of any record."""
    t = (client * 31 + seq) % 97 or 1
    out = []
    for _ in range(gen_len):
        out.append(t)
        t = (t + 1) % 97 or 1
    return out


def serving_response(client: int, seq: int, gen_len: int) -> dict:
    return {"client": client, "seq": seq,
            "tokens": toy_tokens(client, seq, gen_len)}


def checkpoint_payload(tid: int, step: int, payload_words: int) -> dict:
    return {"step": step, "writer": tid,
            "shard": [float(tid * 1000 + step)] * payload_words}


@dataclass
class WorkerReport:
    """One worker's outcome for one pool command."""

    tid: int
    status: str                    # "done" | "crashed" | "error"
    ops_done: int = 0
    elapsed_s: float = 0.0
    results: Optional[List[Tuple[str, Any, Any]]] = None
    inflight: List[Tuple[str, int, str, Any, int]] = field(
        default_factory=list)
    error: Optional[str] = None
    #: per-request end-to-end latencies (seconds) of the open-loop
    #: command, measured from each request's INTENDED arrival time —
    #: coordinated-omission-free (None for other commands)
    latencies: Optional[List[float]] = None


@dataclass
class PoolResult:
    """Aggregate of one pool command across all workers."""

    wall_s: float
    reports: List[WorkerReport]

    @property
    def ops_done(self) -> int:
        return sum(r.ops_done for r in self.reports)

    @property
    def crashed(self) -> List[WorkerReport]:
        return [r for r in self.reports if r.status == "crashed"]

    @property
    def inflight(self) -> List[Tuple[str, int, str, Any, int]]:
        """All in-flight records the workers reported (feed to
        ``runtime.recover(inflight=...)``)."""
        return [rec for r in self.reports for rec in r.inflight]

    @property
    def latencies(self) -> List[float]:
        """All open-loop request latencies (seconds from intended
        arrival to durable completion) across workers."""
        return [v for r in self.reports for v in (r.latencies or ())]

    def results_by_tid(self) -> Dict[int, List[Tuple[str, Any, Any]]]:
        return {r.tid: (r.results or []) for r in self.reports}

    def partition_inflight(self, killed_tids
                           ) -> Tuple[List[Tuple[str, int, str, Any, int]],
                                      List[Tuple[str, int, str, Any, int]]]:
        """Split the in-flight records into (survivors, lost) by worker
        tid — the worker-kill partial-failure scenario recovers with the
        survivors' records only and registers the killed workers' as
        lost (their clients died with them, so their outcome is
        UNKNOWN rather than replayable)."""
        killed = set(killed_tids)
        survivors, lost = [], []
        for rec in self.inflight:
            (lost if rec[1] in killed else survivors).append(rec)
        return survivors, lost


def _collect_inflight(runtime) -> List[Tuple[str, int, str, Any, int]]:
    recs = [(name, tid, op, args, seq)
            for (name, tid), (op, args, seq) in runtime._inflight.items()]
    runtime._inflight.clear()
    return recs


def _worker_main(runtime, tid: int, cmdq, resq, barrier) -> None:
    handle = runtime.attach(tid)
    invokers: Dict[Tuple[str, str], Any] = {}

    def invoker(obj_name: str, op: str):
        key = (obj_name, op)
        fn = invokers.get(key)
        if fn is None:
            obj = runtime.objects[obj_name]
            fn = handle.invoker(obj, op)      # bind_op fast path
            invokers[key] = fn
        return fn

    while True:
        cmd = cmdq.get()
        kind = cmd[0]
        if kind == "stop":
            resq.put((tid, "stopped", None))
            return
        barrier.wait()
        done = 0
        results: Optional[list] = None
        latencies: Optional[list] = None
        try:
            if kind == "pairs":
                _k, obj_name, add_op, rem_op, n_ops, base, collect, \
                    rich, start = cmd
                add = invoker(obj_name, add_op)
                rem = invoker(obj_name, rem_op)
                results = [] if collect else None
                t0 = time.perf_counter()
                for i in range(n_ops):
                    # record each op the moment it returns: a crash in
                    # the remove must not lose the completed (durable,
                    # acked) add that preceded it
                    v = rich_value(tid, start + i) if rich \
                        else base + start + i
                    ra = add(v)
                    done += 1
                    if results is not None:
                        results.append((add_op, v, ra))
                    rr = rem(None)
                    done += 1
                    if results is not None:
                        results.append((rem_op, None, rr))
                elapsed = time.perf_counter() - t0
            elif kind == "serve":
                # serving completion path: each request's toy generation
                # is computed locally, its (rich) response RECORDed into
                # the shared durable log — the op the engine's
                # completion rounds combine (DESIGN.md §8)
                _k, obj_name, n_reqs, gen_len, seq_base, collect = cmd
                rec = invoker(obj_name, "record")
                results = [] if collect else None
                t0 = time.perf_counter()
                for i in range(seq_base + 1, seq_base + n_reqs + 1):
                    resp = serving_response(tid, i, gen_len)
                    ret = rec((tid, i, resp))
                    done += 1
                    if results is not None:
                        results.append(("record", (tid, i), ret))
                elapsed = time.perf_counter() - t0
            elif kind == "ckpt":
                # checkpoint commit path: every worker announces
                # "persist my step-r state" with a payload pytree;
                # newest step wins, d announcements ride one psync
                _k, obj_name, rounds, payload_words, step_base, \
                    collect = cmd
                per = invoker(obj_name, "persist")
                results = [] if collect else None
                t0 = time.perf_counter()
                for r in range(step_base + 1, step_base + rounds + 1):
                    payload = checkpoint_payload(tid, r, payload_words)
                    ret = per((r, payload))
                    done += 1
                    if results is not None:
                        results.append(("persist", r, ret))
                elapsed = time.perf_counter() - t0
            elif kind == "ops":
                _k, obj_name, ops, collect = cmd
                results = [] if collect else None
                t0 = time.perf_counter()
                for op, arg in ops:
                    ret = invoker(obj_name, op)(arg)
                    done += 1
                    if results is not None:
                        results.append((op, arg, ret))
                elapsed = time.perf_counter() - t0
            elif kind == "openloop":
                # open-loop serving leg (DESIGN.md §9): enqueue each
                # scheduled request into the shard ingress at its
                # INTENDED arrival time, pull a small admission window
                # back out, serve most-urgent-first (deadline heap from
                # serving/scheduler), RECORD the response into the
                # durable log.  Latency is measured from the intended
                # arrival carried INSIDE the request value, so a
                # backed-up worker inflates the recorded tail instead of
                # silently deferring load (coordinated-omission-free).
                from ..serving.scheduler import PriorityAdmission
                _k, ingress_name, log_name, schedule, gen_len, batch, \
                    collect = cmd
                enq = invoker(ingress_name, "enqueue")
                deq = invoker(ingress_name, "dequeue")
                log_obj = runtime.objects[log_name]
                admission = PriorityAdmission(window=batch)
                results = [] if collect else None
                latencies = []
                # all workers share the barrier release as the schedule
                # epoch; perf_counter is CLOCK_MONOTONIC (system-wide on
                # Linux), so cross-worker latency attribution only sees
                # the barrier-release skew
                t0 = time.perf_counter()

                def pull_and_serve(limit: int) -> int:
                    nonlocal done
                    pulled = 0
                    while pulled < limit:
                        v = deq()
                        done += 1
                        if results is not None:
                            results.append(("dequeue", None, v))
                        if v is None:
                            break
                        admission.offer(v)
                        pulled += 1
                    # serve the admitted window most-urgent-first and
                    # RECORD every completion in ONE batched call —
                    # invoke_many's RECORD_MANY path, so one combining
                    # round persists the whole window's completions
                    # (the serving engine's completion idiom, §8)
                    admitted = list(admission.admit())
                    if admitted:
                        calls = [(log_obj, "record",
                                  (r[0], r[1],
                                   serving_response(r[0], r[1],
                                                    gen_len)))
                                 for r in admitted]
                        rets = handle.invoke_many(calls)
                        now = time.perf_counter() - t0
                        for r, ret in zip(admitted, rets):
                            done += 1
                            if results is not None:
                                results.append(("record",
                                                (r[0], r[1]), ret))
                            latencies.append(now - r[2])
                    return pulled

                for i, (t_rel, client, seq, prio) in enumerate(schedule):
                    now = time.perf_counter() - t0
                    if t_rel > now:
                        time.sleep(t_rel - now)
                    req = (client, seq, t_rel, prio)
                    ra = enq(req)
                    done += 1
                    if results is not None:
                        results.append(("enqueue", req, ra))
                    # keep ingesting while the next arrival is already
                    # due: a burst runs as an enqueue storm (maximum
                    # combining) and serving catches up in the drain —
                    # open-loop semantics put the backlog into the
                    # measured latency either way
                    if (i + 1 >= len(schedule)
                            or schedule[i + 1][0]
                            > time.perf_counter() - t0):
                        pull_and_serve(batch)
                # drain the residual backlog (including requests
                # enqueued by slower peers); a few consecutive empty
                # polls means this worker sees a quiesced ingress.
                # An EMPTY schedule means this worker has elastically
                # left the shard: it must not serve at all.
                empties = 0
                while schedule and empties < 3:
                    if pull_and_serve(batch) == 0:
                        empties += 1
                        time.sleep(1e-3)
                    else:
                        empties = 0
                elapsed = time.perf_counter() - t0
            else:
                raise ValueError(f"unknown pool command {kind!r}")
            resq.put((tid, "done", {"ops": done, "elapsed": elapsed,
                                    "results": results,
                                    "latencies": latencies}))
        except SimulatedCrash:
            resq.put((tid, "crashed",
                      {"ops": done, "results": results,
                       "latencies": latencies,
                       "inflight": _collect_inflight(runtime)}))
        except BaseException:
            resq.put((tid, "error", traceback.format_exc()))


def _refuse_fork_holding_accelerator() -> None:
    """Raise if this process has initialised a non-CPU JAX backend: a
    forked child cannot use the chip its parent holds, so its kernels
    would fail or hang.  Probes without initialising any backend."""
    if "jax" not in sys.modules:
        return
    # jax has no public probe that leaves uninitialised backends alone
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return
    held = sorted(p for p in xla_bridge.backends() if p != "cpu")
    if held:
        raise RuntimeError(
            f"refusing to fork workers: this process holds the {held} "
            "JAX backend, which forked children cannot use; drive the "
            "device from one process (threads backend)")


class WorkerPool:
    """``n`` fork()ed processes, each driving one Handle against the
    runtime's shared-memory board.  See module docstring for the
    command/crash protocol."""

    def __init__(self, runtime, n_workers: int,
                 tids: Optional[Sequence[int]] = None) -> None:
        if n_workers < 1:
            raise ValueError("need at least one worker")
        if tids is None:
            tids = range(n_workers)
        tids = list(tids)
        if len(tids) != n_workers:
            raise ValueError("len(tids) != n_workers")
        if max(tids) >= runtime.n_threads:
            raise ValueError(f"tids {tids} exceed runtime.n_threads="
                             f"{runtime.n_threads}")
        _refuse_fork_holding_accelerator()
        self.runtime = runtime
        self.tids = tids
        ctx = multiprocessing.get_context("fork")
        self._barrier = ctx.Barrier(n_workers + 1)
        self._cmdqs = [ctx.SimpleQueue() for _ in tids]
        # results ride a full mp.Queue (not SimpleQueue): its timeout-
        # capable get lets _run notice a worker that died without
        # reporting (OOM-kill, segfault) instead of blocking forever
        self._resq = ctx.Queue()
        # attach every handle BEFORE forking so parent and children
        # agree on the handle objects (seq state then lives with the
        # worker; the parent replays crashes from reported records)
        for tid in tids:
            runtime.attach(tid)
        self._procs = [
            ctx.Process(target=_worker_main,
                        args=(runtime, tid, cmdq, self._resq,
                              self._barrier),
                        daemon=True)
            for tid, cmdq in zip(tids, self._cmdqs)]
        for p in self._procs:
            p.start()
        self._closed = False

    # ------------------ command execution ------------------------------ #
    def _run(self, cmds: List[tuple]) -> PoolResult:
        if self._closed:
            raise RuntimeError("pool is closed")
        for cmdq, cmd in zip(self._cmdqs, cmds):
            cmdq.put(cmd)
        try:
            # timed: a worker that dies before reaching the barrier
            # must break it (and every waiter out) instead of hanging
            # the parent past the dead-worker detection below
            self._barrier.wait(timeout=60.0)
        except threading.BrokenBarrierError:
            dead = [t for t, p in zip(self.tids, self._procs)
                    if not p.is_alive()]
            raise RuntimeError(
                f"worker(s) tid={dead or 'unknown'} never reached the "
                "run barrier (died?); pool state is unrecoverable — "
                "close() and respawn") from None
        t0 = time.perf_counter()
        reports: List[WorkerReport] = []
        for _ in self.tids:
            while True:
                try:
                    tid, status, payload = self._resq.get(timeout=5.0)
                    break
                except queue_mod.Empty:
                    reported = {r.tid for r in reports}
                    dead = [t for t, p in zip(self.tids, self._procs)
                            if t not in reported and not p.is_alive()]
                    if dead:
                        raise RuntimeError(
                            f"worker(s) tid={dead} died without "
                            "reporting (killed?); pool state is "
                            "unrecoverable — close() and respawn")
            if status == "done":
                reports.append(WorkerReport(
                    tid, status, ops_done=payload["ops"],
                    elapsed_s=payload["elapsed"],
                    results=payload["results"],
                    latencies=payload["latencies"]))
            elif status == "crashed":
                reports.append(WorkerReport(
                    tid, status, ops_done=payload["ops"],
                    results=payload["results"],
                    latencies=payload["latencies"],
                    inflight=payload["inflight"]))
            else:
                reports.append(WorkerReport(tid, "error", error=payload))
        wall = time.perf_counter() - t0
        reports.sort(key=lambda r: r.tid)
        errors = [r for r in reports if r.status == "error"]
        if errors:
            raise RuntimeError("worker(s) failed:\n"
                               + "\n".join(r.error for r in errors))
        return PoolResult(wall_s=wall, reports=reports)

    def run_pairs(self, obj, n_pairs: int, *, collect: bool = False,
                  value_base: int = 1_000_000, rich: bool = False,
                  index_base: int = 0) -> PoolResult:
        """Every worker runs ``n_pairs`` add/remove pairs against
        ``obj`` (the structure-matrix workload), values disjoint per
        worker.  ``rich=True`` wraps each value in a blob-sized tuple
        (``rich_value``) so the run exercises the shm blob heap;
        ``index_base`` continues the per-producer index numbering
        across successive commands (crash sweeps need distinct values
        per case for the order checkers).  Returns wall time measured
        across ALL workers."""
        add_op, rem_op = _PAIR_OPS[obj.kind]
        return self._run([
            ("pairs", obj.name, add_op, rem_op, n_pairs,
             tid * value_base, collect, rich, index_base)
            for tid in self.tids])

    def run_serving(self, obj, n_reqs: int, *, gen_len: int = 16,
                    seq_base: int = 0,
                    collect: bool = False) -> PoolResult:
        """Every worker completes ``n_reqs`` toy generations and
        RECORDs the responses into the shared ``log`` structure — the
        serving engine's durable completion path under true
        parallelism.  ``seq_base`` continues a client's consecutive
        seq numbering across successive commands."""
        return self._run([
            ("serve", obj.name, n_reqs, gen_len, seq_base, collect)
            for _tid in self.tids])

    def run_checkpoint(self, obj, rounds: int, *,
                       payload_words: int = 32, step_base: int = 0,
                       collect: bool = False) -> PoolResult:
        """Every worker announces ``rounds`` checkpoint persists with a
        ``payload_words``-word shard payload against the shared
        ``ckpt`` structure (newest step wins)."""
        return self._run([
            ("ckpt", obj.name, rounds, payload_words, step_base, collect)
            for _tid in self.tids])

    def run_ops(self, obj, ops_by_tid: Dict[int, List[Tuple[str, Any]]],
                *, collect: bool = True) -> PoolResult:
        """Explicit per-worker op lists: ``{tid: [(op, arg), ...]}``."""
        return self._run([
            ("ops", obj.name, list(ops_by_tid.get(tid, ())), collect)
            for tid in self.tids])

    def run_open_loop(self, ingress, log,
                      schedules: Dict[int, List[Tuple[float, int, int,
                                                      float]]],
                      *, gen_len: int = 8, batch: int = 4,
                      collect: bool = False) -> PoolResult:
        """Open-loop traffic window (the fleet's serving leg): each
        worker executes its ``[(t_rel, client, seq, priority), ...]``
        schedule — ENQUEUE into ``ingress`` at the intended arrival
        offset, admit up to ``batch`` pending requests by deadline
        priority, serve each (toy generation) and RECORD the response
        into ``log`` — then drains the residual backlog.  Workers
        absent from ``schedules`` run an empty schedule and serve
        NOTHING this window, which is how the fleet expresses elastic
        leave without respawning the pool.  ``PoolResult.latencies``
        carries the coordinated-omission-free per-request latencies."""
        return self._run([
            ("openloop", ingress.name, log.name,
             list(schedules.get(tid, ())), gen_len, batch, collect)
            for tid in self.tids])

    # ------------------ lifecycle -------------------------------------- #
    def close(self, timeout: float = 5.0) -> None:
        """Stop the workers (idempotent).  Stragglers are terminated —
        only after the join timeout, so a held shm lock is never left
        dangling by a healthy worker."""
        if self._closed:
            return
        self._closed = True
        for cmdq in self._cmdqs:
            cmdq.put(("stop",))
        deadline = time.monotonic() + timeout
        for p in self._procs:
            p.join(max(0.1, deadline - time.monotonic()))
            if p.is_alive():
                p.terminate()
                p.join(1.0)

    def __enter__(self) -> "WorkerPool":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
