"""Cells cut to a size the CPU runs in a second, for the tests."""

import jax

from chipbench import run

CLIENTS = 4
CAPACITY = 256

BENCH = run.json.loads((run.ROOT / "BENCHMARK.json").read_text())
#: configurations the harness supports that no cell of the benchmark
#: runs yet, rehearsed here so that a later cell of data alone finds
#: them working: PWFComb's serving-order probe and spans
REHEARSED = [{"name": "heap_pwf.pairs", "config": "heap_pwf",
              "traffic": "pairs", "chips": 1}]
CELLS = [w["name"] for w in BENCH["workloads"] + REHEARSED]


def small_cell(name):
    """``(cell, config)`` of workload ``name`` with 4 clients, a heap of
    256 keys preloaded to half, and a short client warm-up."""
    entry = next(w for w in BENCH["workloads"] + REHEARSED
                 if w["name"] == name)
    cell = run.cell_from(entry, BENCH)
    cell["traffic_data"] = dict(cell["traffic_data"], warmup_s=0.1)
    config = dict(cell["config_data"], clients=CLIENTS)
    if config["kind"] == "heap":
        config.update(capacity=CAPACITY, state_words=CAPACITY + 1,
                      make={"capacity": CAPACITY},
                      preload={"op": "insert", "count": CAPACITY // 2})
    return cell, config


def run_small(name, seconds=0.3, traced=False, **kw):
    cell, config = small_cell(name)
    return run.run_cell(cell, 2**33 + 5, seconds, traced, jax.devices(),
                        config=config, log=lambda line, **_: None, **kw)
