"""Framework-level benchmarks: the paper's technique applied to the
training/serving runtime (beyond the paper's own tables).

* checkpoint_bench — ShardedCheckpointer (combining commit) vs the naive
  per-host scheme: psyncs per round and wall time.
* serving_bench — combining batcher vs a lock-per-request server on the
  same toy model: throughput + persistence ops per request.
* structure_matrix_bench — every (kind, protocol) registry entry under
  the same threaded workload via the unified runtime/handle API:
  throughput + persistence ops per op, protocols iterated generically.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Any, Dict, List

import numpy as np

from repro.api import CombiningRuntime, entries
from repro.core import merge_degree_stats
from repro.persist.sharded import (NaiveShardedCheckpointer,
                                   ShardedCheckpointer)
from repro.persist.store import MemStore
from repro.serving.engine import CombiningEngine

from . import modeled


FSYNC_LATENCY = 2e-3      # modeled storage fsync cost per psync


def structure_matrix_bench(kinds=("queue", "stack"), n_threads: int = 4,
                           ops_per_thread: int = 300,
                           runs: int = 5) -> List[Dict[str, Any]]:
    """One workload, every protocol: the registry makes the paper's
    Section 6 comparison a loop instead of a class list.  Each cell is
    the MEDIAN over ``runs`` fresh runtimes — single-shot wall clock
    under a thread scheduler is far too noisy to trend across PRs, and
    a mean is still hostage to one descheduled run."""
    out = []
    for kind in kinds:
        for k, proto in entries(kind):
            total = 2 * n_threads * ops_per_thread
            times, pwbs, pfences, psyncs = [], [], [], []
            degree_snaps = []
            for _run in range(runs):
                rt = CombiningRuntime(n_threads=n_threads)
                obj = rt.make(kind, proto)
                barrier = threading.Barrier(n_threads + 1)

                def worker(p):
                    b = rt.attach(p).bind(obj)
                    add = b.enqueue if kind == "queue" else b.push
                    rem = b.dequeue if kind == "queue" else b.pop
                    barrier.wait()
                    for i in range(ops_per_thread):
                        add(p * 1000000 + i)
                        rem()

                ts = [threading.Thread(target=worker, args=(p,))
                      for p in range(n_threads)]
                for t in ts:
                    t.start()
                gc.collect()          # keep allocator churn out of the run
                barrier.wait()        # thread startup is not protocol cost
                t0 = time.perf_counter()
                for t in ts:
                    t.join()
                times.append(time.perf_counter() - t0)
                c = rt.nvm.counters
                pwbs.append(c["pwb"])
                pfences.append(c["pfence"])
                psyncs.append(c["psync"])
                degree_snaps.append(obj.adapter.degree_stats(obj.core))
            degree = merge_degree_stats(degree_snaps)
            el = sorted(times)[runs // 2]
            row = {"name": f"{kind}/{proto}",
                   "us_per_op": el / total * 1e6,
                   "ops_per_s": total / el,
                   "pwb_per_op": sum(pwbs) / runs / total,
                   "pfence_per_op": sum(pfences) / runs / total,
                   "psync_per_op": sum(psyncs) / runs / total,
                   **modeled.modeled_cell(kind, proto)}
            if degree is not None and degree["rounds"]:
                # measured combining degree (GIL pins wall runs near 1;
                # mp_bench is where paper-scale degrees are measured)
                row["degree_mean"] = degree["degree_mean"]
                row["degree_max"] = degree["degree_max"]
            out.append(row)
    return out


def vector_round_bench(kinds=("counter", "heap", "log"),
                       degrees=(16, 256, 4096), iters: int = 60,
                       runs: int = 5) -> List[Dict[str, Any]]:
    """Combining-round body, vectorized vs per-op, across batch sizes.

    Times exactly what the VectorApply seam replaces (DESIGN.md §11):
    one committed round's simulation pass over ``d`` homogeneous
    announced requests, ``obj.vector_apply`` (one jitted kernel) against
    the identical per-op ``obj.apply`` loop, on the same sequential
    object and state words.  Announce/seqlock/persistence costs are
    deliberately excluded — they are identical on both sides and at
    paper-scale degrees they drown the signal being measured.

    The degree sweep is the honest result: on a CPU host the jitted
    kernel pays a fixed dispatch cost (~tens of us), so the per-op loop
    wins at paper-scale degrees (d≈threads) and the kernel wins once
    rounds batch hundreds-to-thousands of requests (the fleet admission
    window / RECORD_MANY shape).  Both sides of the crossover are
    checked in so the trend is visible in every trajectory.

    Rows are wall-only (``vector_apply`` column; ``profile`` absent →
    never gated).  The seam does no persistence — the round body is
    pure volatile compute, its persistence sentence happens outside the
    measured region — so the pwb/pfence/psync columns are exactly 0.
    """
    from repro.core import NVM
    from repro.core.objects import (FetchAddObject, HeapObject,
                                    ResponseLogObject)

    def mk(kind, d):
        # each entry: (object, [(func, args)...] making one state-neutral
        # iteration — heap pairs an insert round with a delete round)
        if kind == "counter":
            return FetchAddObject(), [("FAA", [1] * d)]
        if kind == "heap":
            return (HeapObject(max(1024, 2 * d)),
                    [("HINSERT", [(i * 31) % 100_000 for i in range(d)]),
                     ("HDELETEMIN", [None] * d)])
        return (ResponseLogObject(max(256, d)),
                [("RECORD", [(i % max(256, d), i + 1, i)
                             for i in range(d)])])

    out = []
    for kind in kinds:
        for d in degrees:
            obj, batches = mk(kind, d)
            nvm = NVM(1 << 22)
            base = nvm.alloc(obj.state_words)
            obj.init_state(nvm, base)
            for f, a in batches:
                if obj.vector_apply(nvm, base, f, a) is None:
                    raise RuntimeError(f"{kind}/d{d}: the VectorApply "
                                       f"seam declined a packable {f} "
                                       "round")
            ops = d * len(batches)
            for vec in (False, True):
                times = []
                for _run in range(runs):
                    gc.collect()
                    t0 = time.perf_counter()
                    if vec:
                        for _ in range(iters):
                            for f, a in batches:
                                obj.vector_apply(nvm, base, f, a)
                    else:
                        for _ in range(iters):
                            for f, batch in batches:
                                for a in batch:
                                    obj.apply(nvm, base, f, a)
                    times.append(time.perf_counter() - t0)
                el = sorted(times)[runs // 2] / iters
                out.append({"name": f"{kind}/d{d}/"
                                    f"{'vector' if vec else 'per-op'}",
                            "us_per_op": el / ops * 1e6,
                            "ops_per_s": ops / el,
                            "pwb_per_op": 0.0, "pfence_per_op": 0.0,
                            "psync_per_op": 0.0,
                            "vector_apply": vec})
    return out


def checkpoint_bench(n_hosts: int = 8, rounds: int = 20,
                     shard_kb: int = 256) -> List[Dict[str, Any]]:
    payload = {"w": np.zeros(shard_kb * 256, np.float32)}  # shard_kb KiB
    tmpl = [payload] * n_hosts
    out = []

    store = MemStore(persist_latency=FSYNC_LATENCY)
    ck = ShardedCheckpointer(store, n_hosts, tmpl)
    t0 = time.perf_counter()
    for step in range(1, rounds + 1):
        for h in range(n_hosts):
            ck.write_shard(h, payload, step)
        assert ck.try_commit(step)
    el = time.perf_counter() - t0
    out.append({"name": f"PBComb-sharded({n_hosts} hosts)",
                "us_per_op": el / rounds * 1e6,
                "ops_per_s": rounds / el,
                "pwb_per_op": store.counters["pwb"] / rounds,
                "pfence_per_op": store.counters["pfence"] / rounds,
                "psync_per_op": store.counters["psync"] / rounds})

    store = MemStore(persist_latency=FSYNC_LATENCY)
    nk = NaiveShardedCheckpointer(store, n_hosts, tmpl)
    t0 = time.perf_counter()
    for step in range(1, rounds + 1):
        for h in range(n_hosts):
            nk.write_shard(h, payload, step)
    el = time.perf_counter() - t0
    out.append({"name": f"naive-per-host({n_hosts} hosts)",
                "us_per_op": el / rounds * 1e6,
                "ops_per_s": rounds / el,
                "pwb_per_op": store.counters["pwb"] / rounds,
                "pfence_per_op": store.counters["pfence"] / rounds,
                "psync_per_op": store.counters["psync"] / rounds})
    return out


class _LockServer:
    """Baseline: one request at a time, per-request persist."""

    def __init__(self, prefill, decode, store):
        self.prefill = prefill
        self.decode = decode
        self.store = store
        self.lock = threading.Lock()

    def submit(self, client, prompt, max_tokens, seq):
        with self.lock:
            toks, kvs = self.prefill([prompt])
            seqtoks = [toks[0]]
            for _ in range(max_tokens - 1):
                nxt = self.decode(kvs, [seqtoks[-1]])
                seqtoks.append(nxt[0])
            self.store.pwb(f"resp.{client}", repr(seqtoks).encode())
            self.store.pfence()
            self.store.psync()
            return {"tokens": seqtoks}


def serving_bench(n_clients: int = 8, reqs_per_client: int = 6,
                  gen_len: int = 16) -> List[Dict[str, Any]]:
    def prefill_batch(prompts):
        time.sleep(0.0005 + 0.0001 * len(prompts))   # batched step cost
        return [max(1, sum(p) % 97) for p in prompts], \
            [list(p) for p in prompts]

    def decode_batch(kvs, last):
        time.sleep(0.0005 + 0.0001 * len(last))
        return [(t + 1) % 97 or 1 for t in last]

    out = []
    total = n_clients * reqs_per_client

    def drive(submit):
        def client(c):
            for r in range(reqs_per_client):
                submit(c, (c, r), gen_len, r + 1)
        ts = [threading.Thread(target=client, args=(c,))
              for c in range(n_clients)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        return time.perf_counter() - t0

    store = MemStore()
    eng = CombiningEngine(n_clients, prefill_batch_fn=prefill_batch,
                          decode_batch_fn=decode_batch,
                          n_kv_slots=n_clients, max_batch=n_clients,
                          store=store, eos_token=-1)
    eng.start()
    el = drive(lambda c, p, m, s: eng.submit(c, p, m, s, timeout=120))
    eng.stop()
    out.append({"name": "CombiningEngine",
                "us_per_op": el / total * 1e6,
                "ops_per_s": total / el,
                "pwb_per_op": store.counters["pwb"] / total,
                "pfence_per_op": store.counters["pfence"] / total,
                "psync_per_op": store.counters["psync"] / total})

    store2 = MemStore()
    srv = _LockServer(prefill_batch, decode_batch, store2)
    el = drive(lambda c, p, m, s: srv.submit(c, p, m, s))
    out.append({"name": "lock-per-request",
                "us_per_op": el / total * 1e6,
                "ops_per_s": total / el,
                "pwb_per_op": store2.counters["pwb"] / total,
                "pfence_per_op": store2.counters["pfence"] / total,
                "psync_per_op": store2.counters["psync"] / total})
    return out
