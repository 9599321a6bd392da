#!/usr/bin/env python3
"""Chip benchmark of the combining runtime: closed-loop client threads on
the served path.

    python3 chipbench/run.py --workload heap_pb.pairs --seed 7 \
        --seconds 20 --trace 0

One process holds one TPU.  The cell named by ``--workload`` in
``BENCHMARK.json`` names a configuration (``configs/<config>.json``: the
object, protocol, sizes and client count), a traffic mix
(``traffic/<traffic>.json``: data naming the op pattern, argument draws
and client loop, each a file of ``patterns/``, ``args/`` and ``loops/``,
from which each client's op stream is drawn from the seed) and its
metrics (``metrics/<metric>.py``: one reader each).  The
run builds a threads-backend ``CombiningRuntime`` with
``vector_apply=True``, loads every round-body kernel the cell can meet
(every batch length from 1 to the client count) from the compile cache,
preloads the object, starts one OS thread per client bound to its own
handle, warms up, and measures for ``--seconds``.  After the window it
runs crash-inside-a-round cycles and times ``recover()``, checks every
reply, the state and the recovered state against the plain reference
(``refs/<kind>.py``), and prints the result as the last line of stdout.
With ``--trace 1`` the window is traced and the cell's per-layer metrics
are reported instead of its end-to-end ones.

Exits non-zero, printing no result, when JAX's devices are not TPUs or
are fewer than the cell asks for, when the program (``src/`` beside this
directory) is missing, or when anything compiled inside the window.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()      # set-up is timed from here

import argparse                # noqa: E402
import contextlib              # noqa: E402
import gc                      # noqa: E402
import importlib.util          # noqa: E402
import json                    # noqa: E402
import os                      # noqa: E402
import random                  # noqa: E402
import shutil                  # noqa: E402
import sys                     # noqa: E402
import tempfile                # noqa: E402
import threading               # noqa: E402
from pathlib import Path       # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import check, probes, trace  # noqa: E402

#: crash after this many persistence instructions of the crash round:
#: past the StateRec's write-back and fence, before its psync
CRASH_AFTER = 2
JOIN_SECONDS = 120


class RunError(Exception):
    """The run cannot give a valid measurement; no result is printed."""


# ------------------------------------------------------------------ #
# the cell, from BENCHMARK.json and the files it names               #
# ------------------------------------------------------------------ #
def load_module(path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    return cell_from(cells[name], bench)


def cell_from(entry, bench):
    """A workload entry with the files it names read in."""
    cell = dict(entry)
    cell["config_data"] = json.loads(
        (HERE / "configs" / f"{cell['config']}.json").read_text())
    cell["traffic_data"] = json.loads(
        (HERE / "traffic" / f"{cell['traffic']}.json").read_text())
    cell["end_to_end"] = bench["end_to_end"]
    cell["per_layer"] = bench["per_layer"]
    return cell


class Mix:
    """A traffic mix: the data of ``traffic/<mix>.json`` with the code it
    names.  Its op pattern, argument draws and client loop are files of
    ``patterns/``, ``args/`` and ``loops/`` beside this one, so a new
    shape of traffic is a new file and every mix of known shapes is data
    alone."""

    def __init__(self, data):
        self.data = data
        spec = data["pattern"]
        self._pattern = load_module(HERE / "patterns" / f"{spec['kind']}.py")
        self._draws = {op: (load_module(HERE / "args" / f"{a['kind']}.py"),
                            a) for op, a in data.get("args", {}).items()}
        self.client = load_module(HERE / "loops" / f"{data['loop']}.py").Client

    def ops(self):
        """Every op the mix can call."""
        return self._pattern.names(self.data["pattern"])

    def draw(self, op, rng):
        """One argument of ``op``, or None for an op that takes none."""
        if op not in self._draws:
            return None
        mod, spec = self._draws[op]
        return mod.draw(spec, rng)

    def stream(self, seed, client):
        """Client ``client``'s endless ``(op, arg)`` stream: the mix's
        pattern, ops and arguments drawn from the seed."""
        rng = random.Random(f"{seed}/client/{client}")
        for op in self._pattern.ops(self.data["pattern"], rng):
            yield op, self.draw(op, rng)


# ------------------------------------------------------------------ #
# the program                                                        #
# ------------------------------------------------------------------ #
def import_program():
    """The runtime from ``src/`` beside this directory, and never from
    anywhere else."""
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        raise RunError(f"the program is missing: no {src / 'repro'}")
    sys.path.insert(0, str(src))
    import repro
    where = {Path(p).resolve() for p in repro.__path__}
    if where != {src / "repro"}:
        raise RunError(f"repro imported from {where}, not {src}")
    from repro.api import CombiningRuntime
    from repro.core import LINE, NVM, SimulatedCrash
    from repro.kernels import vector_rounds
    return {"CombiningRuntime": CombiningRuntime, "LINE": LINE, "NVM": NVM,
            "SimulatedCrash": SimulatedCrash, "vector_rounds": vector_rounds}


def nvm_words(config, line):
    """Words for one object: PBComb keeps 2 StateRecs, PWFComb 2(n+1),
    each the state plus at most 3n+1 per-thread words."""
    n = config["clients"]
    records = 2 if config["protocol"] == "pbcomb" else 2 * (n + 1)
    return records * (config["state_words"] + 3 * n + 1 + line) + (1 << 16)


def warm_kernels(prog, obj, mix, requests, clients):
    """Call the object's seam once per op of the mix and batch length
    1..clients on a scratch copy of its state, so every round-body
    kernel the window can meet is compiled or loaded now."""
    seq = obj.core.obj
    nvm = prog["NVM"](seq.state_words + 4 * prog["LINE"])
    base = nvm.alloc(seq.state_words)
    rng = random.Random("warm")
    calls = 0
    for op in sorted(mix.ops()):
        func, default = requests[op]
        for d in range(1, clients + 1):
            seq.init_state(nvm, base)
            args = [mix.draw(op, rng) for _ in range(d)]
            args = [default if a is None else a for a in args]
            # the class's seam: a probe on the instance must not run here
            if type(seq).vector_apply(seq, nvm, base, func, args) is not None:
                calls += 1
    return calls


def preload(prog, rt, obj, config, mix, seed):
    """Fill the object in staged rounds of one request per client: the
    first ``clients - 1`` handles announce, handle 0 calls and serves
    the whole round.  Returns ``(op, args)`` in the order applied."""
    spec = config.get("preload")
    if not spec:
        return None, []
    op, n = spec["op"], config["clients"]
    rng = random.Random(f"{seed}/preload")
    args = [mix.draw(op, rng) for _ in range(spec["count"])]
    handles = [rt.attach(p) for p in range(n)]
    announced = set()
    for r in range(0, len(args), n):
        chunk = args[r:r + n]
        for p in range(1, len(chunk)):
            handles[p].announce(obj, op, chunk[p])
            announced.add(p)
        handles[0].invoke(obj, op, chunk[0])
    for p in sorted(announced):         # collect: served already
        handles[p].perform(obj)
    return op, args


# ------------------------------------------------------------------ #
# recovery and counters                                              #
# ------------------------------------------------------------------ #
def crash_cycles(prog, rt, obj, mix, seed):
    """Per cycle every client announces one op of the mix, a crash is
    armed inside the combining round that serves them, and ``recover()``
    replays the in-flight requests.  Returns the recover walls and
    ``(op, args, replies)`` per cycle."""
    n = rt.n_threads
    handles = [rt.attach(p) for p in range(n)]
    walls, cycles = [], []
    for i, op in enumerate(mix.data["crash_cycles"]):
        rng = random.Random(f"{seed}/crash/{i}")
        args = [mix.draw(op, rng) for _ in range(n)]
        for p in range(n):
            handles[p].announce(obj, op, *(() if args[p] is None
                                           else (args[p],)))
        # which write-backs the crash drains decides how much recovery
        # replays: drawn alike for every seed, so every run does the
        # same recovery work
        rt.arm_crash(CRASH_AFTER, random.Random(f"crash/{i}"))
        got = {}
        try:
            got[0] = handles[0].perform(obj)
        except prog["SimulatedCrash"]:
            pass
        t0 = time.perf_counter()
        replies = rt.recover()
        walls.append(time.perf_counter() - t0)
        for p in range(n):
            if (obj.name, p) in replies:
                got[p] = replies[(obj.name, p)]
        cycles.append((op, args, [got.get(p, check.MISSING)
                                  for p in range(n)]))
    return walls, cycles


def counters(rt, obj, vr, platform, seam):
    stats = obj.adapter.degree_stats(obj.core)
    c = rt.nvm.counters
    return {"pwb": c["pwb"], "pfence": c["pfence"], "psync": c["psync"],
            "rounds": stats["rounds"], "ops_combined": stats["ops_combined"],
            "kernel_calls": vr.kernel_calls(platform), "seam_ops": seam.ops}


def memory_peak(device):
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ------------------------------------------------------------------ #
# one run                                                            #
# ------------------------------------------------------------------ #
def run_cell(cell, seed, seconds, traced, devices, config=None,
             control_bits=None, fault=None, keep_trace=None, log=print):
    """Everything of a run after the chip check.  ``config`` overrides
    the cell's configuration (tests run it small); ``control_bits`` puts
    the reference in the program's place at that precision; ``fault``
    breaks the round body (``probes.FAULTS``); ``keep_trace`` is a
    directory to copy the traced run's profile into.  Returns the result
    object, with the compared numbers last under ``checks``."""
    prog = import_program()
    vr = prog["vector_rounds"]
    config = config or cell["config_data"]
    mix = Mix(cell["traffic_data"])
    ref_mod = load_module(HERE / "refs" / f"{config['kind']}.py")
    device = devices[0]
    platform = device.platform
    compiles = probes.CompileCounter()

    rt = prog["CombiningRuntime"](n_threads=config["clients"],
                                  nvm_words=nvm_words(config, prog["LINE"]))
    obj = rt.make(config["kind"], config["protocol"],
                  vector_apply=config["vector_apply"], **config["make"])
    requests = {op: (spec.func, spec.default)
                for op, spec in obj.adapter.OPS.items()}
    if control_bits:
        probes.install_control(obj, ref_mod.Control(config, control_bits))
    if fault:
        probes.install_fault(obj, fault)
    t = time.perf_counter()
    warm_calls = warm_kernels(prog, obj, mix, requests,
                              config["clients"])
    warm_s = time.perf_counter() - t
    t = time.perf_counter()
    preloaded = preload(prog, rt, obj, config, mix, seed)
    preload_s = time.perf_counter() - t

    order = probes.OrderLog(obj.core, config["protocol"])
    seam = probes.SeamCount(obj)
    if traced:
        probes.install_spans(obj, config["protocol"])
    go, stop = threading.Event(), threading.Event()
    clients = [mix.client(rt.attach(p).bind(obj), mix.stream(seed, p),
                          go, stop, mix.data)
               for p in range(config["clients"])]
    for c in clients:
        c.start()
    go.set()
    time.sleep(mix.data.get("warmup_s", 0))
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if traced else None
    if traced:
        import jax
        # the harness's spans and the device's ops; JAX's Python tracer,
        # on by default, would record every call of 64 threads
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    compiles_before = compiles.events
    setup_cache = dict(compiles.cache)
    c0 = counters(rt, obj, vr, platform, seam)
    t_start = time.perf_counter_ns()
    setup_s = time.perf_counter() - T0
    window = contextlib.nullcontext()
    if traced:
        from jax.profiler import TraceAnnotation
        window = TraceAnnotation(trace.WINDOW)
    with window:
        time.sleep(seconds)
    t_end = time.perf_counter_ns()
    c1 = counters(rt, obj, vr, platform, seam)
    window_compiles = compiles.events - compiles_before
    if traced:
        jax.profiler.stop_trace()
    stop.set()
    deadline = time.monotonic() + JOIN_SECONDS
    for c in clients:
        c.join(max(0.0, deadline - time.monotonic()))
    if any(c.is_alive() for c in clients):
        raise RunError(f"clients still running {JOIN_SECONDS} s after the "
                       "window closed; errors: "
                       + "".join(c.error for c in clients if c.error))
    compiles.close()
    errors = [c.error for c in clients if c.error]
    memory_peak_bytes = memory_peak(device)

    entries = order.entries()
    window_state = obj.snapshot()
    recovery_s, cycles = crash_cycles(prog, rt, obj, mix, seed)
    rt.crash()          # nothing pending drains: read back the durable state
    rt.recover()
    final_state = obj.snapshot()
    rt.close()
    del rt, obj, order
    gc.collect()

    done = [c.done for c in clients]
    lat = sorted((t1 - t0) * 1e-9 for c in clients for t0, t1 in c.t_ns
                 if t_start <= t1 <= t_end)
    attempted = sum(1 for c in clients for t0, t1 in c.t_ns
                    if t0 <= t_end and t1 >= t_start)
    log(json.dumps({"phase": "run", "workload": cell["name"], "seed": seed,
                    "window_compiles": window_compiles,
                    "kernels_warmed": warm_calls, "warm_s": warm_s,
                    "setup_cache": setup_cache,
                    "preload_s": preload_s, "ops_in_window": len(lat),
                    "ops_checked": sum(map(len, done)),
                    "counters_delta": {k: c1[k] - c0[k] for k in c0},
                    "client_errors": len(errors)}), flush=True)
    for e in errors:
        print(e, file=sys.stderr)
    if window_compiles:
        raise RunError(f"{window_compiles} compilation events inside the "
                       "window: not a valid measurement")

    checks = check.compare(ref_mod.Ref(config), preloaded, done, entries,
                           requests, window_state, cycles, final_state)
    wrong = checks["wrong_replies"] + checks["lost_or_extra_ops"]
    correct = not errors and all(checks[k] <= lim
                                 for k, lim in check.LIMITS.items())

    tr = None
    if traced:
        xplane = trace.find_xplane(trace_dir)
        tr = trace.reduce(trace.load(xplane))
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(xplane, Path(keep_trace) / xplane.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
    obs = {"window_s": (t_end - t_start) * 1e-9, "n_ops": len(lat),
           "latencies_s": lat, "recovery_s": recovery_s, "setup_s": setup_s,
           "delta": {k: c1[k] - c0[k] for k in c0}, "trace": tr}
    metrics = {}
    for m in cell["per_layer" if traced else "end_to_end"]:
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(obs)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = {"platform": platform, "kind": device.device_kind,
           "count": len(devices),
           "memory_peak_bytes": memory_peak_bytes}
    result = {"correct": correct, "attempted": attempted,
              "failed": wrong + len(errors), "metrics": metrics,
              "device": dev}
    if tr is not None:
        dev["busy_s"] = tr["busy_s"]
        dev["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["device_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    result["checks"] = {k: {"value": checks[k], "limit": check.LIMITS[k]}
                        for k in check.LIMITS}
    return result


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control-bits", type=int, default=None,
                    help="put the plain reference in the program's place "
                         "at this integer precision (the control; never "
                         "part of a benchmark run)")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the traced run's .xplane.pb into this "
                         "directory")
    return ap.parse_args(argv)


def use_compile_cache(jax):
    """JAX's persistent compilation cache, in ``JAX_COMPILATION_CACHE_DIR``
    where that is set, else at the fixed path ``.jax_cache`` in the
    checkout; every compile is kept."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def main(argv=None):
    args = parse_args(argv)
    cell = load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: needs a TPU, JAX's first device is "
              f"{devices[0].platform!r}", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"chipbench: {cell['name']} needs {cell['chips']} chips, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2
    use_compile_cache(jax)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          devices, control_bits=args.control_bits,
                          keep_trace=args.keep_trace)
    except RunError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
