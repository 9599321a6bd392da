#!/usr/bin/env python3
"""Chip smoke test: vectorized combining rounds on one TPU, end to end.

    python chip_smoke.py

Builds a threads-backend ``CombiningRuntime`` with 256 logical threads
and drives staged combining rounds of degree 256 over the heap, log,
counter and ckpt cells under PBComb and PWFComb, at a 65,536-word state
(heap capacity, preloaded to half; log clients).  In every round 255
handles announce, handle 0 performs and serves the whole round as one
jitted kernel, and the rest collect their responses.  Each cell is
checked three ways:

  * responses and the final snapshot against a plain sequential
    reference written here (heapq, a dict, an int);
  * the same against the identical schedule with ``vector_apply=False``;
  * NVM persistence counters identical between the two runs.

Every round must be served by a kernel whose output lives on the TPU.
One crash inside a vectorized round per cell must recover with every
in-flight request applied exactly once.  The AtomicFloat float64 MUL
kernel must equal the per-op loop bit for bit, or the seam must decline
it on this platform.

Each earlier line of output is one JSON object per phase; its wall times
are host-clock seconds, and a cell's first round includes compilation.
The last line is ``{"ok": true, "device": {...}}``, printed only when
every phase passed.  Exits non-zero, with no such line, when JAX's first
device is not a TPU or the repository's ``src/`` is not beside it.
"""

from __future__ import annotations

import heapq
import json
import random
import statistics
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
N_THREADS = 256          # logical threads, and so the round degree
WIDTH = 65_536           # heap capacity and log clients
ROUNDS = 16              # counter/log/ckpt rounds; heap mixed rounds
SEED = 0
BIG = 1 << 62            # keys, payloads and responses span int64
CELLS = [(k, p) for k in ("heap", "log", "counter", "ckpt")
         for p in ("pbcomb", "pwfcomb")]


# ------------------------------------------------------------------ #
# plain sequential references (independent of repro)                 #
# ------------------------------------------------------------------ #
class RefHeap:
    def __init__(self, width):
        self.cap, self.keys = width, []

    def apply(self, op, arg):
        if op == "insert":
            if len(self.keys) >= self.cap:
                return False
            heapq.heappush(self.keys, arg)
            return True
        return heapq.heappop(self.keys) if self.keys else None

    def snapshot(self):
        return sorted(self.keys)


class RefCounter:
    def __init__(self, width):
        self.value = 0

    def apply(self, op, delta):
        old, self.value = self.value, self.value + delta
        return old

    def snapshot(self):
        return self.value


class RefLog:
    def __init__(self, width):
        self.width, self.last = width, {}

    def apply(self, op, triple):
        client, seq, resp = triple
        self.last[client] = (seq, resp)
        return resp

    def snapshot(self):
        return [self.last.get(c, (0, None)) for c in range(self.width)]


class RefCkpt:
    def __init__(self, width):
        self.step, self.payload = 0, None

    def apply(self, op, pair):
        step, payload = pair
        if step > self.step:
            self.step, self.payload = step, payload
        return self.step

    def snapshot(self):
        return {"step": self.step, "payload": self.payload}


REFS = {"heap": RefHeap, "counter": RefCounter, "log": RefLog,
        "ckpt": RefCkpt}


# ------------------------------------------------------------------ #
# schedules: [(op, per-handle args or None)] per round               #
# ------------------------------------------------------------------ #
def schedule(kind, n, width, rng):
    def keys():
        return [rng.randrange(-BIG, BIG) for _ in range(n)]

    if kind == "heap":
        rounds = [("insert", keys()) for _ in range(width // 2 // n)]
        for _ in range(ROUNDS // 2):
            rounds += [("delete_min", None), ("insert", keys())]
        return rounds
    if kind == "counter":
        return [("fetch_add", [rng.randrange(-(1 << 40), 1 << 40)
                               for _ in range(n)]) for _ in range(ROUNDS)]
    if kind == "log":
        return [("record", [(c, r + 1, rng.randrange(-BIG, BIG))
                            for c in rng.sample(range(width), n)])
                for r in range(ROUNDS)]
    return [("persist", [(r * n + rng.randrange(2 * n),
                          rng.randrange(-BIG, BIG)) for _ in range(n)])
            for r in range(ROUNDS)]


def crash_round(kind, ref, n, width, rng):
    """One round whose responses do not depend on the order recovery
    replays it in, so exactly-once shows in their multiset."""
    if kind == "heap":
        return "insert", [rng.randrange(-BIG, BIG) for _ in range(n)]
    if kind == "counter":
        return "fetch_add", [1] * n
    if kind == "log":
        return "record", [(c, ROUNDS + 1, rng.randrange(-BIG, BIG))
                          for c in rng.sample(range(width), n)]
    step = ref.step + 1
    return "persist", [(step, -step)] * n


def nvm_words(kind, protocol, n, width):
    """Words for one cell: PBComb keeps 2 StateRecs, PWFComb 2(n+1), each
    holding the state plus at most 3n+1 per-thread words."""
    from repro.core import LINE
    state = {"heap": width + 1, "log": 2 * width, "counter": 1,
             "ckpt": 2}[kind]
    records = 2 if protocol == "pbcomb" else 2 * (n + 1)
    return records * (state + 3 * n + 1 + LINE) + (1 << 16)


def typed(values):
    return [(type(v).__name__, v) for v in values]


# ------------------------------------------------------------------ #
# phases                                                             #
# ------------------------------------------------------------------ #
def drive(kind, protocol, vector, rounds, n, width):
    """Run the staged rounds on a fresh runtime.  Returns the runtime,
    the object, responses in (round, handle) order and round walls."""
    from repro.api import CombiningRuntime
    rt = CombiningRuntime(n_threads=n,
                          nvm_words=nvm_words(kind, protocol, n, width))
    size = {"heap": {"capacity": width}, "log": {"n_clients": width}}
    obj = rt.make(kind, protocol, vector_apply=vector,
                  **size.get(kind, {}))
    handles = [rt.attach(p) for p in range(n)]
    bound0 = handles[0].bind(obj)
    rets, walls = [], []
    for op, args in rounds:
        t0 = time.perf_counter()
        for p in range(1, n):
            handles[p].announce(obj, op, *(() if args is None
                                           else (args[p],)))
        rets.append(getattr(bound0, op)(*(() if args is None
                                          else (args[0],))))
        rets.extend(handles[p].perform(obj) for p in range(1, n))
        walls.append(time.perf_counter() - t0)
    return rt, obj, rets, walls


def crash_phase(rt, obj, kind, ref, n, width, rng):
    """Crash inside one vectorized round, recover, and check every
    in-flight request took effect exactly once."""
    from repro.core import SimulatedCrash
    op, args = crash_round(kind, ref, n, width, rng)
    handles = [rt.attach(p) for p in range(n)]
    for p in range(n):
        handles[p].announce(obj, op, args[p])
    rt.arm_crash(2, random.Random(SEED))
    got, fired = {}, False
    try:
        got[1] = handles[1].perform(obj)
    except SimulatedCrash:
        fired = True
    replies = rt.recover()
    for p in range(n):
        if (obj.name, p) in replies:
            got[p] = replies[(obj.name, p)]
    want = [ref.apply(op, a) for a in args]
    return {"fired": fired, "replied": len(got),
            "exactly_once": (len(got) == n
                             and sorted(got.values(), key=repr)
                             == sorted(want, key=repr)
                             and obj.snapshot() == ref.snapshot())}


def cell_phase(kind, protocol, platform, n, width):
    from repro.kernels import vector_rounds
    rng = random.Random(f"{SEED}/{kind}")
    rounds = schedule(kind, n, width, rng)
    ref = REFS[kind](width)
    ref_rets = [ref.apply(op, None if args is None else args[p])
                for op, args in rounds for p in range(n)]

    before = vector_rounds.kernel_calls()
    rt, obj, e_rets, e_walls = drive(kind, protocol, False, rounds, n,
                                     width)
    eager_calls = vector_rounds.kernel_calls() - before
    e_snap, e_counters = obj.snapshot(), dict(rt.nvm.counters)
    rt.close()

    before = vector_rounds.kernel_calls()
    on_chip = vector_rounds.kernel_calls(platform)
    rt, obj, v_rets, v_walls = drive(kind, protocol, True, rounds, n,
                                     width)
    calls = vector_rounds.kernel_calls() - before
    calls_on_chip = vector_rounds.kernel_calls(platform) - on_chip
    v_snap, v_counters = obj.snapshot(), dict(rt.nvm.counters)
    ref_snap = ref.snapshot()

    before = vector_rounds.kernel_calls(platform)
    crash = crash_phase(rt, obj, kind, ref, n, width, rng)
    crash["kernel_calls"] = vector_rounds.kernel_calls(platform) - before
    rt.close()

    checks = {
        "responses_vs_reference": typed(v_rets) == typed(ref_rets),
        "responses_vs_eager": typed(v_rets) == typed(e_rets),
        "snapshot_vs_reference": v_snap == ref_snap,
        "snapshot_vs_eager": v_snap == e_snap,
        "counters_identical": v_counters == e_counters,
        "every_round_on_chip": calls == calls_on_chip == len(rounds),
        "eager_ran_no_kernel": eager_calls == 0,
        "crash_exactly_once": crash["fired"] and crash["exactly_once"]
                              and crash["kernel_calls"] >= 1,
    }
    return {"phase": "cell", "cell": f"{kind}/{protocol}",
            "state_words": obj.core.state_words, "degree": n,
            "rounds": len(rounds), "kernel_calls": calls,
            f"kernel_calls_{platform}": calls_on_chip,
            "counters": v_counters, "crash": crash, **checks,
            "vector_first_round_s_incl_compile": v_walls[0],
            "vector_round_s_median": statistics.median(v_walls[1:]),
            "eager_round_s_median": statistics.median(e_walls[1:]),
            "ok": all(checks.values())}


def mul_phase(platform, n):
    """The AtomicFloat float64 MUL kernel against the per-op loop, bit
    for bit; the seam serves the round only where the two agree."""
    import jax
    import numpy as np

    from repro.core import NVM, AtomicFloatObject
    from repro.kernels import vector_rounds
    rng = random.Random(f"{SEED}/mul")
    factors = [rng.uniform(0.5, 2.0) for _ in range(n)]
    value, want = 1.0, []
    for k in factors:
        want.append(value)
        value *= k
    with jax.enable_x64(True):
        v, outs = vector_rounds.kernel("float.MUL")(
            np.float64(1.0), np.asarray(factors, dtype=np.float64))
    devices = sorted({d.platform for d in outs.devices()})
    got = [float(x) for x in np.asarray(outs)] + [float(v)]
    mismatched = sum(a.hex() != b.hex() for a, b in zip(got, want + [value]))
    exact = mismatched == 0

    nvm = NVM(1 << 10)
    obj = AtomicFloatObject()
    base = nvm.alloc(obj.state_words)
    obj.init_state(nvm, base)
    resps = obj.vector_apply(nvm, base, "MUL", factors)
    if exact:
        seam = "served" if resps is not None else "declined"
        seam_ok = ([r.hex() for r in resps or []] == [w.hex() for w in want]
                   and nvm.read(base).hex() == value.hex())
    else:
        seam = "declined" if resps is None else "served"
        seam_ok = resps is None
    return {"phase": "float_mul", "degree": n, "output_on": devices,
            "kernel_exact": exact, "mismatched_words": mismatched,
            "seam": seam,
            "ok": devices == [platform] and seam_ok}


def smoke(platform, n=N_THREADS, width=WIDTH):
    """Run every phase, print one JSON line each; True if all passed."""
    ok = True
    phases = [(f"{k}/{p}", lambda k=k, p=p: cell_phase(k, p, platform, n,
                                                         width))
              for k, p in CELLS]
    phases.append(("float_mul", lambda: mul_phase(platform, n)))
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            line = phase()
        except Exception as e:         # report it, run the other phases
            traceback.print_exc()
            line = {"phase": name, "error": repr(e), "ok": False}
        line["wall_s"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
        ok = ok and line["ok"]
    return ok


def main():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    sys.path.insert(0, str(ROOT / "src"))
    from repro.kernels import vector_rounds
    cache = vector_rounds.use_compile_cache(ROOT / ".jax_cache")
    print(json.dumps({"phase": "setup", "compile_cache": cache,
                      "jax": jax.__version__}), flush=True)
    if not smoke(dev.platform):
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
