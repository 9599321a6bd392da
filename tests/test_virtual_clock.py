"""Virtual-clock NVM timing engine (DESIGN.md §6).

Pins the three contracts the modeled perf trajectory rests on:

  * fused round sentences (pwb_fence / pwb_sync / commit_round) charge
    EXACTLY what their discrete-instruction fallbacks would — same
    floats, same counters, same durable image — under every profile;
  * the deterministic modeled bench pass is byte-identical across runs,
    and reproduces the paper's relative ordering (PBComb < DFC <
    durable-MS / locks) at Optane latencies;
  * Lamport clock merging: a combining round's modeled latency is the
    max over its participants, not the sum — and crash countdowns armed
    mid-round still land on durable prefixes with the clock engaged.
"""

import random
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

try:                                   # optional dep: `pip install .[test]`
    from hypothesis import given, settings, strategies as st
except ImportError:
    given = settings = st = None

from repro.api import CombiningRuntime
from repro.core import (NVM, PROFILES, PBComb, RequestRec, SimulatedCrash,
                        VClock)
from repro.structures import PBStack

from benchmarks import modeled


# ------------------------------------------------------------------ #
# VClock unit behavior                                               #
# ------------------------------------------------------------------ #
def test_vclock_bind_advance_merge():
    clk = VClock(PROFILES["optane"])
    with clk.bind(0):
        clk.advance(100.0)
        assert clk.now() == 100.0
    with clk.bind(1):
        assert clk.now() == 0.0
        clk.merge(250.0)
        assert clk.now() == 250.0
        clk.merge(10.0)                      # merge is a max, monotone
        assert clk.now() == 250.0
    with clk.bind(0):
        assert clk.now() == 100.0            # per-logical-thread clocks
    assert clk.max_time_ns() == 250.0


def test_vclock_device_serializes():
    clk = VClock(PROFILES["optane"])
    with clk.bind(0):
        clk.sync_device(1000.0)
        assert clk.now() == 1000.0
    with clk.bind(1):
        # device busy until t=1000: this thread's psync queues behind it
        clk.sync_device(1000.0)
        assert clk.now() == 2000.0


def test_unknown_profile_rejected():
    with pytest.raises(ValueError):
        NVM(1 << 12, profile="nvram-of-theseus")


# ------------------------------------------------------------------ #
# Fused sentence == discrete fallback (cost, counters, durability)   #
# ------------------------------------------------------------------ #
def _prepared(profile, force):
    nvm = NVM(1 << 14, profile=profile)
    nvm.force_discrete = force
    base = nvm.alloc(80)
    idx = nvm.alloc(1)
    for i in range(80):
        nvm.write(base + i, i * 3 + 1)
    nvm.reset_counters()
    nvm.clock.reset()
    return nvm, base, idx


def _observe(nvm):
    return (nvm.clock.now(), dict(nvm.counters),
            [nvm.durable_read(a) for a in range(nvm._alloc_ptr)])


def _prior_traffic(nvm, base, prior):
    for off, n in prior:
        nvm.pwb(base + off, n)
    nvm.pfence()


PENDING_CASES = [None, [], [(0, 1)], [(5, 3), (40, 2)],
                 [(0, 8), (3, 9), (70, 1)]]
PRIOR_CASES = [[], [(2, 1)], [(60, 10), (0, 2)]]


@pytest.mark.parametrize("profile", sorted(PROFILES))
@pytest.mark.parametrize("pending", PENDING_CASES)
@pytest.mark.parametrize("prior", PRIOR_CASES)
def test_commit_round_fused_equals_discrete(profile, pending, prior):
    results = []
    for force in (False, True):
        nvm, base, idx = _prepared(profile, force)
        _prior_traffic(nvm, base, prior)
        pend = None if pending is None else \
            [(base + off, n) for off, n in pending]
        nvm.commit_round([(base, 40)], idx, 1, pending=pend)
        results.append(_observe(nvm))
    assert results[0] == results[1]          # floats bit-equal too


STATE_RUN_CASES = [[], [(8, 8)], [(0, 16), (32, 8), (72, 8)]]


@pytest.mark.parametrize("profile", sorted(PROFILES))
@pytest.mark.parametrize("runs", STATE_RUN_CASES)
@pytest.mark.parametrize("prior", PRIOR_CASES)
def test_commit_round_by_runs_fused_equals_discrete(profile, runs, prior):
    """A StateRec written back as several runs of lines, or as none
    (PBComb by lines): the fused commit equals its discrete fallback."""
    results = []
    for force in (False, True):
        nvm, base, idx = _prepared(profile, force)
        _prior_traffic(nvm, base, prior)
        nvm.commit_round([(base + off, n) for off, n in runs], idx, 1,
                         pending=[(base + 3, 2)])
        results.append(_observe(nvm))
    assert results[0] == results[1]


@pytest.mark.parametrize("profile", sorted(PROFILES))
@pytest.mark.parametrize("pending", PENDING_CASES)
def test_pwb_fence_fused_equals_discrete(profile, pending):
    results = []
    for force in (False, True):
        nvm, base, _idx = _prepared(profile, force)
        pend = None if pending is None else \
            [(base + off, n) for off, n in pending]
        nvm.pwb_fence(base, 24, pending=pend)
        results.append(_observe(nvm))
    assert results[0] == results[1]


@pytest.mark.parametrize("profile", sorted(PROFILES))
@pytest.mark.parametrize("prior", PRIOR_CASES)
def test_pwb_sync_fused_equals_discrete(profile, prior):
    results = []
    for force in (False, True):
        nvm, base, _idx = _prepared(profile, force)
        _prior_traffic(nvm, base, prior)
        nvm.pwb_sync(base + 17, 2)
        results.append(_observe(nvm))
    assert results[0] == results[1]


if st is not None:
    @settings(max_examples=40, deadline=None)
    @given(st.sampled_from(sorted(PROFILES)),
           st.lists(st.tuples(st.integers(0, 75), st.integers(1, 12)),
                    max_size=5),
           st.lists(st.tuples(st.integers(0, 75), st.integers(1, 12)),
                    max_size=3),
           st.integers(1, 60))
    def test_property_commit_round_cost_equivalence(profile, pending,
                                                    prior, state_words):
        """The satellite property: a fused commit_round's modeled cost
        equals the sum of its discrete-instruction fallback under every
        profile, for arbitrary pending/prior line traffic."""
        results = []
        for force in (False, True):
            nvm, base, idx = _prepared(profile, force)
            _prior_traffic(nvm, base, prior)
            pend = [(base + off, n) for off, n in pending]
            nvm.commit_round([(base, state_words)], idx, 1,
                             pending=pend or None)
            results.append(_observe(nvm))
        assert results[0] == results[1]
else:
    def test_property_commit_round_cost_equivalence():
        pytest.importorskip("hypothesis")


# ------------------------------------------------------------------ #
# Deterministic modeled pass + paper ordering                        #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("cell", [("queue", "pbcomb"),
                                  ("queue", "pwfcomb"),
                                  ("queue", "durable-ms"),
                                  ("stack", "dfc"),
                                  ("counter", "lock-undo")])
def test_modeled_cell_byte_identical(cell):
    kind, proto = cell
    assert modeled.modeled_cell(kind, proto) == \
        modeled.modeled_cell(kind, proto)


def test_modeled_fig1_byte_identical():
    for name in modeled.FIG1_IMPLS:
        assert modeled.modeled_fig1(name) == modeled.modeled_fig1(name)


def test_modeled_ordering_matches_paper():
    """The paper's headline relative ordering at Optane latencies:
    combining (PBComb) beats detectable flat combining (DFC) beats the
    per-op-persist competitors (durable MS queue, locks)."""
    pb = modeled.modeled_cell("queue", "pbcomb")
    pbs = modeled.modeled_cell("stack", "pbcomb")
    dfc = modeled.modeled_cell("stack", "dfc")
    ms = modeled.modeled_cell("queue", "durable-ms")
    ld = modeled.modeled_cell("queue", "lock-direct")
    lu = modeled.modeled_cell("queue", "lock-undo")
    assert pbs["modeled_us_per_op"] < dfc["modeled_us_per_op"]
    assert dfc["modeled_us_per_op"] < ms["modeled_us_per_op"]
    for worse in (ms, ld, lu):
        assert pb["modeled_us_per_op"] < worse["modeled_us_per_op"]
    # and the why: one psync per round vs one per op
    assert pb["modeled_psync_per_op"] < 0.5 < ms["modeled_psync_per_op"]


def test_round_latency_is_max_not_sum():
    """Three announced requests served by one round: every participant
    lands at the round's end (merge), the device is paid ONCE, and the
    makespan is far below three sequential per-op persists."""
    rt = CombiningRuntime(n_threads=3, profile="optane")
    c = rt.make("counter", "pbcomb")
    handles = [rt.attach(p) for p in range(3)]
    rt.nvm.reset_counters()
    rt.nvm.clock.reset()
    handles[1].announce(c, "fetch_add", 1)
    handles[2].announce(c, "fetch_add", 1)
    handles[0].bind(c).fetch_add(1)
    assert rt.nvm.counters["psync"] == 1         # one round, one psync
    clk = rt.nvm.clock
    prof = clk.profile
    combiner_t = clk._times[0]
    # makespan == the combiner's clock, and well under 3 discrete
    # psync round trips (what per-op persistence would charge)
    assert clk.max_time_ns() == combiner_t
    assert combiner_t < 3 * prof.psync_ns
    assert c.snapshot() == 3


# ------------------------------------------------------------------ #
# Crash countdowns with the clock engaged                            #
# ------------------------------------------------------------------ #
@pytest.mark.parametrize("crash_at", range(10))
@pytest.mark.parametrize("seed", [None, 13])
def test_crash_mid_round_durable_prefix_with_clock(crash_at, seed):
    """Arming a crash countdown forces the discrete instruction path
    (ticks land BETWEEN instructions); with a profile engaged the same
    sweep must still recover every announced op exactly once."""
    nvm = NVM(1 << 20, profile="optane")
    s = PBStack(nvm, 3)
    s.op(0, "PUSH", "base", 1)
    t_before = nvm.clock.max_time_ns()
    for p in range(3):
        s.request[p] = RequestRec("PUSH", f"v{p}",
                                  1 - s.request[p].activate, 1)
    nvm.arm_crash(crash_at, random.Random(seed) if seed else None)
    try:
        s._perform_request(0)
    except SimulatedCrash:
        pass
    nvm.disarm_crash()
    s.reset_volatile()
    seqs = {0: 2, 1: 1, 2: 1}
    rets = {p: s.recover(p, "PUSH", f"v{p}", seqs[p]) for p in range(3)}
    assert all(r == "ACK" for r in rets.values())
    content = s.drain()
    assert sorted(content[:-1]) == ["v0", "v1", "v2"]
    assert content[-1] == "base"
    # logical time is monotone through crash + recovery
    assert nvm.clock.max_time_ns() >= t_before


@pytest.mark.parametrize("crash_at", range(8))
def test_runtime_crash_recover_with_clock(crash_at):
    """Full-machine crash through the runtime/handle surface with the
    clock engaged: acknowledged prefix intact, in-flight op replayed."""
    rt = CombiningRuntime(n_threads=2, profile="dram")
    q = rt.make("queue", "pbcomb")
    b = rt.attach(0).bind(q)
    b.enqueue("a")
    b.enqueue("b")
    rt.arm_crash(crash_at, random.Random(crash_at))
    try:
        b.enqueue("c")
    except SimulatedCrash:
        pass
    rt.crash(random.Random(crash_at + 1))
    rt.recover()
    content = q.snapshot()
    assert content[:2] == ["a", "b"]
    assert all(v == "c" for v in content[2:]) and len(content) <= 3
