"""What the harness installs on the program's instances at run time.

Nothing here edits the program: each probe replaces a method on one
instance (the protocol core, its sequential object or its NVM) with a
wrapper that calls the original.

* ``OrderLog`` records the serving order the combiner chose: the
  announcements of every committed pass, in the order it applied them.
  The reference replays that order (``check.compare``).
* ``SeamCount`` counts the requests the object's ``vector_apply``
  served, so the requests that reached the round-body kernel can be
  read beside the combiner's own counters.
* ``install_spans`` writes a ``TraceAnnotation`` around the calls into
  each layer, for the traced run only.
* ``install_control`` and ``install_fault`` put a lower-precision
  reference, or a broken round body, in the program's place; the
  benchmark's own runs use neither.
"""

from __future__ import annotations

import threading


class OrderLog:
    """Committed passes of a PBComb or PWFComb core, in serving order.

    PBComb: a round's passes are committed when the round releases its
    lock (``_pre_unlock`` runs after the round's psync, under the lock).
    PWFComb: an attempt's passes are committed when its SC on ``S``
    succeeds; the SC's version orders them, since a successful SC at
    version v published the state every later attempt copies."""

    def __init__(self, core, protocol):
        self._committed = {}
        if protocol == "pbcomb":
            self._wrap_pbcomb(core)
        elif protocol == "pwfcomb":
            self._wrap_pwfcomb(core)
        else:
            raise ValueError(f"no serving-order probe for {protocol!r}")

    def _wrap_pbcomb(self, core):
        begin, apply_batch, pre_unlock = (core._begin_round, core._apply_batch,
                                          core._pre_unlock)
        state = {"passes": []}

        def _begin_round(ind, p):
            state["passes"] = []
            return begin(ind, p)

        def _apply_batch(batch, ind, p):
            state["passes"].append([(q, f, a) for q, f, a, _ in batch])
            return apply_batch(batch, ind, p)

        def _pre_unlock(ind, p):
            self._committed[len(self._committed)] = state["passes"]
            return pre_unlock(ind, p)

        core._begin_round = _begin_round
        core._apply_batch = _apply_batch
        core._pre_unlock = _pre_unlock

    def _wrap_pwfcomb(self, core):
        begin, apply_batch = core._begin_attempt, core._apply_batch
        sc = core.S.sc
        attempts = {}

        def _begin_attempt(slot, p):
            attempts[p] = []
            return begin(slot, p)

        def _apply_batch(batch, slot, p):
            attempts[p].append([(q, f, a) for q, f, a, _ in batch])
            return apply_batch(batch, slot, p)

        def sc_logged(version, new_slot):
            ok = sc(version, new_slot)
            if ok:                       # slot ids are owner * 2 + index
                self._committed[version] = attempts[new_slot // 2]
            return ok

        core._begin_attempt = _begin_attempt
        core._apply_batch = _apply_batch
        core.S.sc = sc_logged

    def entries(self):
        """Every committed announcement ``(client, func, args)``, in the
        order the combiners applied them."""
        return [e for k in sorted(self._committed)
                for passed in self._committed[k] for e in passed]


class SeamCount:
    """Requests served through the sequential object's ``vector_apply``
    (a pass the seam did not decline), in every attempt: a PWFComb
    attempt that loses its SC counts too."""

    def __init__(self, obj):
        self.ops = 0
        lock = threading.Lock()
        seq = obj.core.obj
        vector_apply = seq.vector_apply

        def counted(nvm, st_base, func, args_list, ctx=None):
            rets = vector_apply(nvm, st_base, func, args_list, ctx)
            if rets is not None:
                with lock:
                    self.ops += len(args_list)
            return rets
        seq.vector_apply = counted


def _annotated(fn, name):
    from jax.profiler import TraceAnnotation

    def wrapper(*args, **kw):
        with TraceAnnotation(name):
            return fn(*args, **kw)
    return wrapper


def install_spans(obj, protocol):
    """Spans around the layers of one combining round: ``combine`` (the
    round: PBComb's ``_combine``, PWFComb's simulation passes), ``copy``
    (the StateRec copy), ``seam`` (the object's ``vector_apply``) and
    ``commit`` (the persistence sentence)."""
    core, nvm = obj.core, obj.core.nvm
    if protocol == "pbcomb":
        core._combine = _annotated(core._combine, "combine")
        nvm.commit_round = _annotated(nvm.commit_round, "commit")
    else:
        core._apply_batch = _annotated(core._apply_batch, "combine")
        nvm.pwb_fence = _annotated(nvm.pwb_fence, "commit")
        nvm.pwb_sync = _annotated(nvm.pwb_sync, "commit")
    nvm.copy_range = _annotated(nvm.copy_range, "copy")
    core.obj.vector_apply = _annotated(core.obj.vector_apply, "seam")


def install_control(obj, control):
    """Serve every request of ``obj`` with ``control`` (a reference's
    ``Control``) in place of the program's sequential object."""
    seq = obj.core.obj
    seq.apply = control.apply
    seq.vector_apply = control.vector_apply


def _unchanged(seq):
    apply, vector_apply = seq.apply, seq.vector_apply

    def restoring(fn):
        def call(nvm, st_base, func, args, ctx=None):
            before = nvm.read_range(st_base, seq.state_words)
            rets = fn(nvm, st_base, func, args, ctx)
            nvm.write_range(st_base, before)
            return rets
        return call
    return restoring(apply), restoring(vector_apply)


def _half_batch(seq):
    apply, vector_apply = seq.apply, seq.vector_apply
    last = {"skip": False, "reply": None}

    def apply_half(nvm, st_base, func, args, ctx=None):
        # the per-op loop leaves out every second request of a pass
        last["skip"] = not last["skip"]
        if not last["skip"]:
            last["reply"] = apply(nvm, st_base, func, args, ctx)
        return last["reply"]

    def vector_half(nvm, st_base, func, args_list, ctx=None):
        half = max(1, len(args_list) // 2)
        rets = vector_apply(nvm, st_base, func, args_list[:half], ctx)
        if rets is None:
            return None
        return rets + [rets[-1]] * (len(args_list) - half)
    return apply_half, vector_half


def _altered_reply(r):
    return not r if type(r) is bool else 0 if r is None else r + 1


def _altered(seq):
    apply, vector_apply = seq.apply, seq.vector_apply

    def apply_altered(nvm, st_base, func, args, ctx=None):
        return _altered_reply(apply(nvm, st_base, func, args, ctx))

    def vector_altered(nvm, st_base, func, args_list, ctx=None):
        rets = vector_apply(nvm, st_base, func, args_list, ctx)
        if rets:
            rets[0] = _altered_reply(rets[0])
        return rets
    return apply_altered, vector_altered


#: broken round bodies, in the kernel and in the per-op loop alike: the
#: state returned unchanged; half of the batch left out, the rest given
#: a copy of a reply; a reply altered where it is produced
FAULTS = {"state_unchanged": _unchanged, "half_batch": _half_batch,
          "answer_altered": _altered}


def install_fault(obj, name):
    seq = obj.core.obj
    seq.apply, seq.vector_apply = FAULTS[name](seq)


class CompileCounter:
    """Counts JAX's tracing and compilation events.  Each jit cache miss
    traces, lowers, and compiles or loads from the persistent cache;
    ``cache`` counts the persistent cache's hits and misses, so a set-up
    that compiled is told from one that loaded."""

    PREFIX = "/jax/core/compile/"
    CACHE = "/jax/compilation_cache/"

    def __init__(self):
        import jax.monitoring
        self.events = 0
        self.cache = {"cache_hits": 0, "cache_misses": 0}
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **kw):
        if event.startswith(self.PREFIX):
            with self._lock:
                self.events += 1

    def _on_event(self, event, **kw):
        name = event[len(self.CACHE):]
        if event.startswith(self.CACHE) and name in self.cache:
            with self._lock:
                self.cache[name] += 1

    def close(self):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._on)
        jax.monitoring.unregister_event_listener(self._on_event)
