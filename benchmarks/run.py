"""Benchmark entry point: one function per paper table/figure plus the
framework-level benches; prints human-readable tables as it goes, a
``name,us_per_call,derived`` CSV at the end, and — with ``--json`` — a
machine-readable result file so every PR extends a real perf trajectory.

Run:  PYTHONPATH=src python -m benchmarks.run [--quick] [--json PATH]
                                              [--profile NAME]

JSON schema (``bench.v2``, superset of v1)::

    {"schema": "bench.v2", "tag": "<tag>", "quick": bool,
     "profile": "optane",
     "rows": [{"name": "<table>/<impl>",
               "us_per_op": float,          # wall clock (host-noisy)
               "pwbs_per_op": float,        # wall-run counters
               "psyncs_per_op": float,
               "modeled_us_per_op": float|null,     # virtual clock —
               "modeled_pwbs_per_op": float|null,   # deterministic,
               "modeled_psyncs_per_op": float|null, # byte-identical
               "profile": "optane"|null,            # across runs
               "degree_mean": float|null,   # measured combining degree
               "degree_max": int|null,              # (never gated)
               "ring_spills": int|null,             # shm rows only
               "redundant_pwbs_per_op": float|null}, ...]}  # --audit only

``--audit`` rebuilds every NVM (modeled and wall) with the persist
audit attached (repro.analysis.audit): rows then carry
``redundant_pwbs_per_op`` — the paper's minimality claim as a number,
deterministic for rows with a modeled replay.  The audited NVM pins
``force_discrete``, whose counters/costs are property-tested identical
to the fused paths, so modeled columns do not move; the gated baseline
is nevertheless produced WITHOUT ``--audit`` (the column stays null and
is never gated).

The ``modeled_*`` columns come from the fixed-schedule virtual-clock
pass (benchmarks/modeled.py): byte-identical across runs and hosts,
they are the columns CI's perf gate (benchmarks/perf_gate.py) diffs
against the checked-in BENCH_baseline.json — counters at zero
tolerance.  Rows without a modeled replay (checkpoint/serving) carry
nulls and are not gated.

``--quick`` runs every bench at tiny sizes (seconds, CI perf-smoke);
absolute wall numbers are then meaningless but the modeled columns are
the same as a full run's, which is what makes the gate valid in CI.
The smoke test (tests/test_bench_json.py) pins the schema plus the
paper's core claim: pbcomb/pwfcomb rows spend at most ~one psync per
op — one psync per combining ROUND.

Column-by-column contract for this and every other emitted schema
(bench.mp.v2, bench.fleet.v1, analysis.sweep.v1): docs/BENCH_SCHEMAS.md.
"""

from __future__ import annotations

import argparse
import sys

sys.path.insert(0, "src")                      # repo-root invocation

from repro.core import PROFILES

from benchmarks import framework_benches, modeled, paper_figures
from benchmarks.common import atomic_write_json, csv_rows, print_rows


def collect(quick: bool = False):
    """Run every bench; returns (csv_lines, json_rows)."""
    csv: list = []
    json_rows: list = []

    if quick:
        nt, ops = 3, 120
        heap_sizes = (64, 128)
        matrix_kw = dict(n_threads=3, ops_per_thread=40, runs=2)
        vector_kw = dict(degrees=(16, 256), iters=10, runs=2)
        ckpt_kw = dict(n_hosts=2, rounds=3, shard_kb=16)
        serve_kw = dict(n_clients=2, reqs_per_client=2, gen_len=4)
    else:
        nt, ops = paper_figures.N_THREADS, paper_figures.OPS
        heap_sizes = (64, 128, 256, 512, 1024)
        matrix_kw = {}
        vector_kw = {}
        ckpt_kw = {}
        serve_kw = {}

    def add(table: str, title: str, rows) -> None:
        print_rows(title, rows)
        csv.extend(csv_rows(rows, table))
        json_rows.extend(
            {"name": f"{table}/{r['name']}",
             "us_per_op": round(r["us_per_op"], 3),
             "pwbs_per_op": round(r["pwb_per_op"], 3),
             "psyncs_per_op": round(r["psync_per_op"], 3),
             "modeled_us_per_op":
                 None if "modeled_us_per_op" not in r
                 else round(r["modeled_us_per_op"], 3),
             "modeled_pwbs_per_op":
                 None if "modeled_pwb_per_op" not in r
                 else round(r["modeled_pwb_per_op"], 3),
             "modeled_psyncs_per_op":
                 None if "modeled_psync_per_op" not in r
                 else round(r["modeled_psync_per_op"], 3),
             "profile": r.get("profile"),
             # measured combining degree (combining protocols only;
             # host-noisy like the wall columns — never gated)
             "degree_mean":
                 None if "degree_mean" not in r
                 else round(r["degree_mean"], 3),
             "degree_max": r.get("degree_max"),
             # VectorApply seam rows (vector_rounds table): which side
             # of the jitted-kernel/per-op pair this row timed (null
             # everywhere else; wall-only, never gated)
             "vector_apply": r.get("vector_apply"),
             # ring-overflow early write-back completions, surfaced as
             # their own column instead of folded into pwb counts (shm
             # rows only; the thread NVM's epoch queue cannot spill)
             "ring_spills": r.get("ring_spills"),
             # minimality metric from the persist audit (--audit only;
             # modeled replays report it deterministically)
             "redundant_pwbs_per_op":
                 None if "redundant_pwb_per_op" not in r
                 else round(r["redundant_pwb_per_op"], 3)}
            for r in rows)

    add("fig1_atomicfloat",
        "Fig 1/2 — persistent AtomicFloat (throughput, pwbs/op)",
        paper_figures.fig1_atomicfloat(nt, ops))
    add("fig3_no_psync", "Fig 3 — AtomicFloat with psync as NOP",
        paper_figures.fig3_no_psync(nt, ops))
    add("fig4_queues", "Fig 4/5 — persistent queues (throughput, pwbs/op)",
        paper_figures.fig4_queues(nt, ops))
    add("fig6_queues_no_pwb", "Fig 6 — queues with pwb as NOP (pure sync cost)",
        paper_figures.fig6_queues_no_pwb(nt, ops))
    add("fig7a_stacks", "Fig 7a — persistent stacks (+elim/recycle ablations)",
        paper_figures.fig7a_stacks(nt, ops))
    add("fig7b_heap", f"Fig 7b — PBHeap across sizes {heap_sizes}",
        paper_figures.fig7b_heap(nt, ops, sizes=heap_sizes))
    add("fig8_modeled",
        f"Fig 8 — modeled cost, '{modeled.DEFAULT_PROFILE}' profile "
        "(deterministic virtual clock; us/op IS modeled)",
        paper_figures.fig8_modeled())

    t1 = paper_figures.table1_counters(nt, ops)
    print("\n## Table 1 — shared-location traffic per op (volatile mode)")
    print(f"{'impl':12s} {'reads/op':>9s} {'writes/op':>10s} {'cas/op':>7s}")
    for r in t1:
        print(f"{r['name']:12s} {r['reads_per_op']:9.2f} "
              f"{r['writes_per_op']:10.2f} {r['cas_per_op']:7.2f}")
        csv.append(f"table1/{r['name']},0,"
                   f"reads/op={r['reads_per_op']:.2f};"
                   f"writes/op={r['writes_per_op']:.2f}")

    add("matrix", "Framework — protocol matrix via the unified runtime API",
        framework_benches.structure_matrix_bench(**matrix_kw))
    add("vector_rounds",
        "Framework — combining-round body: jitted VectorApply kernel vs "
        "per-op loop (degree sweep; wall-only)",
        framework_benches.vector_round_bench(**vector_kw))
    add("checkpoint",
        "Framework — sharded checkpoint commit (combining vs naive)",
        framework_benches.checkpoint_bench(**ckpt_kw))
    add("serving", "Framework — serving (combining batcher vs lock/request)",
        framework_benches.serving_bench(**serve_kw))

    return csv, json_rows


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(
        description="Persistent-software-combining benchmark suite")
    ap.add_argument("--json", metavar="PATH",
                    help="write machine-readable results (bench.v2) here, "
                         "e.g. BENCH_pr3.json")
    ap.add_argument("--quick", action="store_true",
                    help="tiny sizes for CI perf-smoke (wall timings "
                         "meaningless; modeled columns unchanged)")
    ap.add_argument("--tag", default=None,
                    help="trajectory tag recorded in the JSON (defaults "
                         "to the --json filename stem)")
    ap.add_argument("--profile", default=modeled.DEFAULT_PROFILE,
                    choices=sorted(PROFILES),
                    help="virtual-clock cost profile for the modeled "
                         "columns (default: %(default)s)")
    ap.add_argument("--audit", action="store_true",
                    help="attach the persist audit to every NVM: rows "
                         "gain redundant_pwbs_per_op (modeled columns "
                         "unchanged; the gated baseline is produced "
                         "without this flag)")
    args = ap.parse_args(argv)

    modeled.DEFAULT_PROFILE = args.profile
    modeled.AUDIT = args.audit
    csv, json_rows = collect(quick=args.quick)

    print("\n# CSV: name,us_per_call,derived")
    for line in csv:
        print(line)

    if args.json:
        tag = args.tag
        if tag is None:
            stem = args.json.rsplit("/", 1)[-1]
            tag = stem[len("BENCH_"):-len(".json")] \
                if stem.startswith("BENCH_") and stem.endswith(".json") \
                else stem
        doc = {"schema": "bench.v2", "tag": tag, "quick": args.quick,
               "profile": args.profile, "audit": args.audit,
               "rows": json_rows}
        atomic_write_json(args.json, doc)
        print(f"\n(wrote {len(json_rows)} rows to {args.json})")


if __name__ == "__main__":
    main()
