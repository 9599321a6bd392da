"""Scan-replay engine (kernels/scan_replay.py): the periodic modeled
pass fast-forwarded through a taped ``lax.scan`` is EXACT, not
approximate.

The exactness contract: replay performs the identical IEEE-754 double
operations in the identical order the eager simulator would have, so
every modeled column — us/op, pwbs/op, psyncs/op — is byte-identical
between ``engine="scan"`` and ``engine="eager"``.  Anything the tape
cannot verify as periodic falls back to the eager loop for every round
(aperiodic geometry, audit NVMs, clockless NVMs, runs too short to
amortize the taped window).  ``modeled_matrix`` rides this engine to
gate the full registry at depths the eager simulator could not afford
in CI.
"""

import pytest

from benchmarks import modeled
from repro.api import registry
from repro.core import NVM
from repro.kernels import scan_replay
from repro.kernels.scan_replay import (ClockTape, _next_pow2,
                                       _replay_python, periodic_run)

#: Every registry cell of a scan-safe (allocation-free) kind.
SCAN_CELLS = [(k, p) for k in sorted(modeled._SCAN_SAFE_KINDS)
              for p in registry.protocols_for(k)]

_MODELED_KEYS = ("modeled_us_per_op", "modeled_pwb_per_op",
                 "modeled_pfence_per_op", "modeled_psync_per_op")


@pytest.mark.parametrize("kind,protocol", SCAN_CELLS)
def test_scan_replay_byte_identical_to_eager(kind, protocol):
    scan = modeled.modeled_cell(kind, protocol, rounds=512, engine="scan")
    eager = modeled.modeled_cell(kind, protocol, rounds=512,
                                 engine="eager")
    for key in _MODELED_KEYS:
        assert scan[key] == eager[key], (key, scan[key], eager[key])
    # the steady state of an allocation-free cell verifies: periods
    # were actually replayed, not eagerly simulated under a new name
    assert scan["replay_engine"] == "scan"


def test_engine_auto_split():
    """``auto`` replays allocation-free kinds and leaves node-pool
    kinds (whose chunk-refill periods defeat bounded verification) on
    the eager simulator."""
    safe = modeled.modeled_cell("counter", "pbcomb", rounds=512,
                                engine="auto")
    assert safe["replay_engine"] in ("scan", "python")
    pool = modeled.modeled_cell("queue", "pbcomb", rounds=64,
                                engine="auto")
    assert "replay_engine" not in pool


def test_short_run_falls_back_exactly():
    scan = modeled.modeled_cell("counter", "pbcomb", rounds=10,
                                engine="scan")
    eager = modeled.modeled_cell("counter", "pbcomb", rounds=10,
                                 engine="eager")
    assert scan["replay_engine"] == "eager"
    for key in _MODELED_KEYS:
        assert scan[key] == eager[key]


def test_periodic_run_declines_unsupported_nvms():
    ran = []
    info = periodic_run(NVM(1 << 12), ran.append, 5)   # no virtual clock
    assert info == {"engine": "eager", "reason": "short-or-unsupported"}
    assert ran == list(range(5))

    nvm = NVM(1 << 12, profile="optane", audit=True)   # audit attached
    ran.clear()
    info = periodic_run(nvm, ran.append, 1000)
    assert info == {"engine": "eager", "reason": "short-or-unsupported"}
    assert ran == list(range(1000))


def _persist_round(nvm, r, burst_every):
    """One synthetic modeled round; a psync burst every
    ``burst_every`` rounds sets the geometry's period."""
    with nvm.clock.bind(0):
        nvm.write(0, r)
        nvm.pwb(0)
        nvm.pfence()
        if r % burst_every == 0:
            nvm.psync()


def test_aperiodic_tape_falls_back_exactly():
    """Period-3 geometry matches no candidate period (L..8L powers of
    two): the engine must refuse and run every round eagerly."""
    rounds = 200
    nvm = NVM(1 << 12, profile="optane")
    info = periodic_run(nvm, lambda r: _persist_round(nvm, r, 3), rounds)
    assert info == {"engine": "eager", "reason": "aperiodic"}

    ref = NVM(1 << 12, profile="optane")
    for r in range(rounds):
        _persist_round(ref, r, 3)
    assert dict(nvm.counters) == dict(ref.counters)
    assert nvm.clock.max_time_ns() == ref.clock.max_time_ns()


@pytest.mark.parametrize("rounds", [100, 1000, 4096 + 7])
def test_synthetic_periodic_replay_exact(rounds):
    """Power-of-two geometry verifies; replayed clocks and counters are
    byte-identical to the all-eager run, tail rounds included."""
    nvm = NVM(1 << 12, profile="optane")
    info = periodic_run(nvm, lambda r: _persist_round(nvm, r, 4), rounds)
    assert info["engine"] in ("scan", "python")
    assert info["replayed_periods"] > 0

    ref = NVM(1 << 12, profile="optane")
    for r in range(rounds):
        _persist_round(ref, r, 4)
    assert dict(nvm.counters) == dict(ref.counters)
    assert nvm.clock.max_time_ns() == ref.clock.max_time_ns()
    assert nvm.clock._device_free == ref.clock._device_free


def test_tpu_backend_replays_in_python(monkeypatch):
    """A TPU backend's float64 is a float32 pair: the replay stays in
    plain Python there and is still byte-identical to the eager run."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    rounds = 1000
    nvm = NVM(1 << 12, profile="optane")
    info = periodic_run(nvm, lambda r: _persist_round(nvm, r, 4), rounds)
    assert info["engine"] == "python"
    assert info["replayed_periods"] > 0
    ref = NVM(1 << 12, profile="optane")
    for r in range(rounds):
        _persist_round(ref, r, 4)
    assert dict(nvm.counters) == dict(ref.counters)
    assert nvm.clock.max_time_ns() == ref.clock.max_time_ns()


def test_replay_jax_matches_python_reference():
    """The jitted fori/scan replay computes exactly what the pure-python
    arithmetic reference does on a synthetic event tape."""
    A, M, D, N_, NOOP = (scan_replay._ADV, scan_replay._MRG,
                         scan_replay._DEV, scan_replay._NOW,
                         scan_replay._MRGC_NOOP)
    events = [(N_, 0, 0.0, 0), (A, 0, 3.5, 0), (N_, 1, 0.0, 0),
              (M, 1, 0.0, 2), (D, 1, 7.25, 0), (A, 1, 1.5, 0),
              (M, 0, 0.0, 3), (NOOP, 0, 123.0, 0)]
    times0, device0 = [10.0, 4.0], 6.0
    ring0, nc0 = [9.0, 2.0, 5.5, 1.0], 11
    k = 57
    py_t, py_d = _replay_python(list(times0), device0, list(ring0), nc0,
                                events, k)
    jx_t, jx_d = scan_replay._replay_jax(list(times0), device0,
                                         list(ring0), nc0, events, k)
    assert jx_t == py_t
    assert jx_d == py_d


def test_tape_provenance_and_helpers():
    tape = ClockTape()
    t = tape.record_now("a", 5.0)
    assert isinstance(t, scan_replay.TapedTime) and t == 5.0 and t.idx == 0
    tape.record_mrg("b", t, 3.0)                 # taped operand -> _MRG
    tape.record_mrg("b", 2.0, 3.0)               # stale no-op constant
    tape.record_mrg("b", 9.0, 3.0)               # live constant: poison
    tape.mark_round()
    kinds = [e[0] for e in tape.rounds[0]]
    assert kinds == [scan_replay._NOW, scan_replay._MRG,
                     scan_replay._MRGC_NOOP, scan_replay._MRGC_LIVE]
    assert tape.rounds[0][1][3] == 1             # src_rel provenance
    assert [_next_pow2(n) for n in (1, 2, 3, 9)] == [1, 2, 4, 16]


def test_modeled_matrix_rows():
    """The CI-gated full-registry matrix: one deterministic row per
    (kind, protocol) cell, wall columns null, replay engine recorded."""
    rows = modeled.modeled_matrix()
    names = [r["name"] for r in rows]
    assert len(names) == len(set(names))
    expected = {f"modeled_matrix/{k}/{p}" for k in registry.kinds()
                for p in registry.protocols_for(k)}
    assert set(names) == expected
    for r in rows:
        kind = r["name"].split("/")[1]
        assert r["us_per_op"] is None and r["pwbs_per_op"] is None
        assert r["psyncs_per_op"] is None
        assert r["modeled_us_per_op"] > 0
        assert r["modeled_pwbs_per_op"] >= 0
        assert r["profile"] == modeled.DEFAULT_PROFILE
        if kind in modeled._SCAN_SAFE_KINDS:
            assert r["rounds"] == modeled.MATRIX_ROUNDS
            assert r["replay_engine"] in ("scan", "python")
        else:
            assert r["rounds"] == modeled.MATRIX_ROUNDS_EAGER
            assert r["replay_engine"] == "eager"
