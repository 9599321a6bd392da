"""Periodic modeled-replay engine: ``lax.scan`` over taped clock events.

The deterministic modeled pass (``benchmarks/modeled.py``) drives every
registry cell single-threaded through the virtual-clock NVM.  Its cost
trajectory is a pure function of the op schedule, and the schedule is
periodic — so after a warmup the per-round clock/counter deltas settle
into an exactly repeating pattern.  This module exploits that:

  1. run a warmup window eagerly;
  2. attach a :class:`ClockTape` to the ``VClock`` and keep running
     eagerly while recording every clocked event — ``advance`` /
     ``merge`` / ``sync_device`` / ``now`` — with Lamport *provenance*:
     ``now()`` returns a :class:`TapedTime` (a float subclass tagged
     with its tape ordinal) so a later ``merge`` records *which* event
     produced its operand, not just the value;
  3. verify periodicity structurally: candidate periods ``P`` in
     ``{L, 2L, 4L, 8L}`` schedule lengths, accepted iff the last four
     ``P``-round chunks have byte-identical event tuples AND identical
     per-chunk NVM-counter deltas;
  4. replay the remaining ``k`` whole periods as arithmetic on the tape
     — a jitted f64 ``lax.scan`` over the period's event array inside a
     ``fori_loop`` over periods (plain Python arithmetic on a TPU
     backend, whose float64 is not IEEE binary64) — then write the
     final clocks / device horizon / counters back and run any
     remainder rounds eagerly.

Exactness contract: the replay performs the *identical* IEEE-754 double
operations, in the identical order, that the eager simulator would have
performed (one add per ``advance``, one max per ``merge``, one max+add
per ``sync_device``), so the modeled columns are byte-identical to an
all-eager run — property-tested in ``tests/test_modeled_scan.py``.  Any
cell whose tape refuses to verify (aperiodic geometry, a non-no-op
constant merge, an audit NVM, or a run too short to amortize the taped
window) falls back to the eager loop for every round — honest, never
approximate.

Threading: tapes hook the clock's hot path and are not thread-safe.
Attach only from single-threaded drivers (the modeled pass); never
while workers run.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Tuple

from . import vector_rounds

__all__ = ["TapedTime", "ClockTape", "periodic_run"]

# Event kinds (tape + replay encodings).
_ADV, _MRG, _DEV, _NOW, _MRGC_NOOP, _MRGC_LIVE = 0, 1, 2, 3, 4, 5


class TapedTime(float):
    """A clock reading tagged with the tape ordinal of the ``now()``
    that produced it — ``merge`` provenance for the replay engine."""

    __slots__ = ("idx",)

    def __new__(cls, value: float, idx: int) -> "TapedTime":
        self = float.__new__(cls, value)
        self.idx = idx
        return self


class ClockTape:
    """Recorder attached to ``VClock._tape`` by :func:`periodic_run`.

    Events are per-round lists of tuples ``(kind, lid, val, src)`` with
    clock keys densified to ``lid`` indices (stable across rounds);
    ``now()`` values are kept verbatim for ring seeding."""

    def __init__(self) -> None:
        self.rounds: List[List[Tuple[int, int, float, int]]] = []
        self._cur: List[Tuple[int, int, float, int]] = []
        self.now_vals: List[float] = []
        self.now_count = 0
        self._lids: Dict[Any, int] = {}

    def _lid(self, key: Any) -> int:
        lid = self._lids.get(key)
        if lid is None:
            lid = self._lids[key] = len(self._lids)
        return lid

    # ------------- hooks called from VClock ---------------------------- #
    def record_now(self, key: Any, t: float) -> TapedTime:
        idx = self.now_count
        self.now_count = idx + 1
        self.now_vals.append(float(t))
        self._cur.append((_NOW, self._lid(key), 0.0, 0))
        return TapedTime(t, idx)

    def record_adv(self, key: Any, ns: float) -> None:
        self._cur.append((_ADV, self._lid(key), float(ns), 0))

    def record_mrg(self, key: Any, value: float, cur: float) -> None:
        if type(value) is TapedTime:
            # src is relative in now-ordinal space: constant per period
            # when the schedule is periodic.
            self._cur.append((_MRG, self._lid(key), 0.0,
                              self.now_count - value.idx))
        else:
            # A stamp from before the tape attached.  A no-op merge
            # stays a no-op forever (clocks are monotone), so it can be
            # replayed as nothing; a live constant merge cannot be
            # extrapolated and poisons verification.
            kind = _MRGC_NOOP if value <= cur else _MRGC_LIVE
            self._cur.append((kind, self._lid(key), float(value), 0))

    def record_dev(self, key: Any, cost_ns: float) -> None:
        self._cur.append((_DEV, self._lid(key), float(cost_ns), 0))

    def mark_round(self) -> None:
        self.rounds.append(self._cur)
        self._cur = []


# --------------------------------------------------------------------- #
# Replay (python reference + jitted lax.scan)                           #
# --------------------------------------------------------------------- #
def _next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p <<= 1
    return p


def _replay_python(times: List[float], device: float, ring: List[float],
                   nc: int, events, k: int
                   ) -> Tuple[List[float], float]:
    R = len(ring)
    for _ in range(k):
        for kind, lid, val, src in events:
            if kind == _ADV:
                times[lid] = times[lid] + val
            elif kind == _MRG:
                s = ring[(nc - src) % R]
                if s > times[lid]:
                    times[lid] = s
            elif kind == _DEV:
                t = times[lid]
                if device > t:
                    t = device
                t += val
                device = t
                times[lid] = t
            elif kind == _NOW:
                ring[nc % R] = times[lid]
                nc += 1
            # _MRGC_NOOP: nothing — verified no-op under monotone clocks
    return times, device


_SCAN_CACHE: Dict[Tuple[int, int, int], Any] = {}


def _replay_jax(times, device, ring, nc, events, k):
    jax, jnp, lax = vector_rounds._jx()
    E, R, nlid = len(events), len(ring), len(times)

    with jax.enable_x64(True):
        fn = _SCAN_CACHE.get((E, R, nlid))
        if fn is None:
            def run(T, D, ring, nc, kinds, lids, vals, srcs, k):
                def per_event(carry, ev):
                    T, D, ring, nc = carry
                    kind, lid, val, src = ev
                    t = T[lid]
                    # all four candidate updates; `where` selects the
                    # one the eager simulator would have performed
                    t_adv = t + val
                    t_mrg = jnp.maximum(t, ring[(nc - src) % R])
                    t_dev = jnp.maximum(t, D) + val
                    new_t = jnp.where(kind == _ADV, t_adv,
                            jnp.where(kind == _MRG, t_mrg,
                            jnp.where(kind == _DEV, t_dev, t)))
                    is_now = kind == _NOW
                    return ((T.at[lid].set(new_t),
                             jnp.where(kind == _DEV, t_dev, D),
                             jnp.where(is_now, ring.at[nc % R].set(t),
                                       ring),
                             nc + jnp.where(is_now, 1, 0)), None)

                def per_period(_i, carry):
                    return lax.scan(per_event, carry,
                                    (kinds, lids, vals, srcs))[0]

                return lax.fori_loop(0, k, per_period, (T, D, ring, nc))

            fn = _SCAN_CACHE[(E, R, nlid)] = jax.jit(run)

        import numpy as np
        kinds = np.asarray([e[0] for e in events], dtype=np.int64)
        lids = np.asarray([e[1] for e in events], dtype=np.int64)
        vals = np.asarray([e[2] for e in events], dtype=np.float64)
        srcs = np.asarray([e[3] for e in events], dtype=np.int64)
        T, D, ring_o, _nc = fn(
            np.asarray(times, dtype=np.float64), np.float64(device),
            np.asarray(ring, dtype=np.float64), np.int64(nc),
            kinds, lids, vals, srcs, np.int64(k))
        return [float(x) for x in T], float(D)


# --------------------------------------------------------------------- #
# Driver                                                                #
# --------------------------------------------------------------------- #
def periodic_run(nvm, round_fn: Callable[[int], None], total_rounds: int,
                 sched_len: int = 1) -> Dict[str, Any]:
    """Run ``round_fn(r)`` for ``r in range(total_rounds)``, replaying
    the periodic middle through the tape engine when it verifies.

    Returns an info dict: ``engine`` is ``"scan"`` when periods were
    replayed through the jitted scan, ``"python"`` when they were
    replayed in plain Python (a TPU backend, or periods that moved
    counters but no clock), or ``"eager"`` with a ``reason`` when every
    round ran the simulator.
    The NVM's modeled counters and virtual clocks end byte-identical to
    an all-eager run either way.
    """
    clk = getattr(nvm, "clock", None)
    L = max(1, int(sched_len))
    warm, taped = 8 * L, 32 * L
    if (clk is None or getattr(nvm, "audit", None) is not None
            or total_rounds < warm + taped + 2 * L):
        for r in range(total_rounds):
            round_fn(r)
        return {"engine": "eager", "reason": "short-or-unsupported"}

    for r in range(warm):
        round_fn(r)

    tape = ClockTape()
    snaps = [dict(nvm.counters)]
    clk._tape = tape
    try:
        for i in range(taped):
            round_fn(warm + i)
            tape.mark_round()
            snaps.append(dict(nvm.counters))
    finally:
        clk._tape = None

    chosen = None
    for P in (L, 2 * L, 4 * L, 8 * L):
        chunks = [sum((tape.rounds[i] for i in range(taped - c * P,
                                                     taped - (c - 1) * P)),
                      []) for c in (4, 3, 2, 1)]
        deltas = [{key: snaps[taped - (c - 1) * P].get(key, 0)
                   - snaps[taped - c * P].get(key, 0)
                   for key in snaps[taped]} for c in (4, 3, 2, 1)]
        if (all(ch == chunks[0] for ch in chunks[1:])
                and all(d == deltas[0] for d in deltas[1:])
                and not any(e[0] == _MRGC_LIVE for e in chunks[0])):
            chosen = (P, chunks[-1], deltas[-1])
            break

    consumed = warm + taped
    if chosen is None:
        for r in range(consumed, total_rounds):
            round_fn(r)
        return {"engine": "eager", "reason": "aperiodic"}

    P, events, delta = chosen
    k, tail = divmod(total_rounds - consumed, P)
    engine = "eager"
    if k and events:
        max_src = max((e[3] for e in events if e[0] == _MRG), default=0)
        R = _next_pow2(max_src + 1)
        nc = tape.now_count
        ring = [0.0] * R
        for j, v in enumerate(tape.now_vals[-R:]):
            ring[(nc - min(R, len(tape.now_vals)) + j) % R] = v
        keys = list(tape._lids)
        times = [float(clk._times.get(key, 0.0)) for key in keys]
        if vector_rounds._jx()[0].default_backend() == "tpu":
            # float64 on the TPU is a float32 pair, not IEEE binary64
            times, device = _replay_python(times, clk._device_free,
                                           ring, nc, events, k)
            engine = "python"
        else:
            times, device = _replay_jax(times, clk._device_free,
                                        ring, nc, events, k)
            engine = "scan"
        for key, t in zip(keys, times):
            clk._times[key] = t
        clk._device_free = device
        for key, d in delta.items():
            if d:
                nvm.counters[key] = nvm.counters.get(key, 0) + k * d
    elif k:
        # clock-silent periods: only the counters move
        for key, d in delta.items():
            if d:
                nvm.counters[key] = nvm.counters.get(key, 0) + k * d
        engine = "python"

    for i in range(tail):
        round_fn(consumed + k * P + i)
    return {"engine": engine, "period_rounds": P, "replayed_periods": k,
            "events_per_period": len(events)}
