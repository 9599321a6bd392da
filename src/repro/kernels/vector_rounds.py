"""Jitted combiner-round bodies: the VectorApply seam's compute side.

The paper's combiner holds d announced requests when it commits a
round; the repo's thesis (ROADMAP "Combining-as-vectorization") is that
this batch should execute as ONE compiled kernel instead of d
interpreted Python calls.  This module holds those kernels: for each
array-valued sequential object (counter, heap, bounded queue/stack,
response log, checkpoint cell) the round body is a pure function over a
packed announcement array, compiled once per (kind, op) signature with
``jax.jit`` and driven by ``lax.scan`` in announcement order — the
haliax ``Stacked``/``hax.scan`` pattern (SNIPPETS.md §§2-3): compile
once, scan over homogeneous elements instead of unrolling.

Contract with ``SeqObject.vector_apply`` (core/objects.py):

  * Exactness: a kernel must produce byte-identical state words and
    responses to the per-op Python loop, or the caller must fall back.
    Kernels therefore run in 64-bit (``jax.enable_x64(True)`` scoped
    to this module's calls — the model substrate stays f32) and
    the packing guards reject anything that is not a plain Python int
    (or float, for the AtomicFloat kernel): rich payloads, huge ints,
    None — all take the eager path.  One documented wrinkle: ``bool``
    payloads pack as ints (bool subclasses int), so a ``True`` stored
    through the eager path decodes as ``1`` through the vector path;
    int-keyed workloads (every bench and property test) are unaffected.
  * NVM counters: kernels never touch NVM.  The caller gathers state
    with ``read_range`` and scatters with ``write_range`` — volatile
    accessors that cost zero persistence instructions and zero modeled
    time, so the round's persistence sentence (and the gated modeled
    trajectory) is untouched by vectorization.
  * Declines are semantic or per-platform: an entry returns None for a
    batch it cannot pack (see above), or for a kernel that is not exact
    on the default backend (``float.MUL`` on ``tpu``, DESIGN.md §11),
    never because the device path is missing.  JAX is a core
    dependency, so a failed import or compile raises.

Kernels are cached in ``_KERNELS`` keyed by kind+op name; ``jax.jit``'s
own cache handles shape/dtype retraces (batch size d and state width
vary per instance).
"""

from __future__ import annotations

import os
from collections import Counter
from typing import Any, List, Optional, Sequence, Tuple

import numpy as np

from ..core.tracing import span

_KERNELS: dict = {}
#: jitted-round invocations per platform of the device that held the
#: kernel's output ("cpu", "tpu", ...)
_CALLS: Counter = Counter()


def _jx():
    """(jax, jnp, lax), imported on the first vectorized round so that
    importing the combining core does not pay for jax.  Kernels trace and
    dispatch under ``jax.enable_x64(True)``."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    return jax, jnp, lax


def use_compile_cache(default_dir: "os.PathLike[str] | str") -> str:
    """Keep jax's persistent compilation cache in the directory that
    ``JAX_COMPILATION_CACHE_DIR`` names or, where that is unset, in
    ``default_dir`` (give a fixed path: the path is part of the cache's
    key).  Every compile is cached, the sub-second round bodies too.
    Call it from an entry point before the first kernel, never at
    import.  Returns the directory in use."""
    jax = _jx()[0]
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.fspath(default_dir)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def kernel_calls(platform: Optional[str] = None) -> int:
    """Jitted-round invocations so far, on every platform or on the one
    named.  Tests assert the vector path actually engaged rather than
    declined; the chip smoke asserts the rounds ran on the TPU."""
    if platform is None:
        return sum(_CALLS.values())
    return _CALLS[platform]


# ------------------------------------------------------------------ #
# packing guards                                                     #
# ------------------------------------------------------------------ #
def pack_ints(values: Sequence[Any]) -> Optional[np.ndarray]:
    """Batch args as int64, or None if any element is not a plain int
    (a bignum outside int64 range must decline, not raise)."""
    if not all(type(v) is int or type(v) is bool for v in values):
        return None
    try:
        return np.asarray(values, dtype=np.int64)
    except OverflowError:
        return None


def pack_floats(values: Sequence[Any]) -> Optional[np.ndarray]:
    if not all(type(v) is float for v in values):
        return None
    return np.asarray(values, dtype=np.float64)


def pack_state(words: Sequence[Any]) -> Optional[np.ndarray]:
    """State words as int64 — relies on numpy's inference: a list with
    any float/None/str/bignum element does not infer to int64."""
    try:
        arr = np.asarray(words)
    except (TypeError, ValueError, OverflowError):  # pragma: no cover
        return None
    return arr if arr.dtype == np.int64 else None


def pack_state_f64(words: Sequence[Any]) -> Optional[np.ndarray]:
    if not all(type(v) is float for v in words):
        return None
    return np.asarray(words, dtype=np.float64)


# ------------------------------------------------------------------ #
# kernel builders (pure functions of packed arrays)                  #
# ------------------------------------------------------------------ #
def kernel(name: str):
    """The jitted round body named ``kind.FUNC`` (a key of
    ``BUILDERS``), built once.  Trace, lower and call it under
    ``jax.enable_x64(True)``.  Its function is named after the key
    (``heap_HINSERT``), so its module is ``jit_heap_HINSERT`` in a
    profile."""
    fn = _KERNELS.get(name)
    if fn is None:
        jax, jnp, lax = _jx()
        body = BUILDERS[name](jnp, lax)
        body.__name__ = body.__qualname__ = name.replace(".", "_")
        with jax.enable_x64(True):
            fn = jax.jit(body)
        _KERNELS[name] = fn
    return fn


def _run(name: str, *args):
    """Invoke a cached kernel under the x64 scope (dispatch must see the
    same dtypes tracing saw) and return numpy results.  The last
    argument is the batch: one element per request."""
    with span("seam.dispatch", kernel=name, d=len(args[-1])):
        jax = _jx()[0]
        fn = kernel(name)
        with jax.enable_x64(True):
            out = fn(*args)
        (device,) = out[0].devices()
        _CALLS[device.platform] += 1
    with span("seam.fetch"):
        host = tuple(np.asarray(o) for o in out)
        # freeing the device outputs can give up the GIL as well
        del out
    return host


def _faa_builder(jnp, lax):
    # int64 addition is associative and exact, so the sequential scan
    # collapses to a cumulative sum — each op's response is the value
    # before its own delta.  (MUL below must stay a true scan: float
    # products are order-sensitive and the contract is byte-exactness.)
    def k(v, xs):
        tot = jnp.cumsum(xs)
        return v + tot[-1], v + (tot - xs)
    return k


def _mul_builder(jnp, lax):
    def k(v, xs):
        def step(c, x):
            return c * x, c
        c, outs = lax.scan(step, v, xs)
        return c, outs
    return k


def _heap_insert_builder(jnp, lax):
    def k(arr, size, xs):
        cap = arr.shape[0]

        def sift_up(arr, i):
            def cond(c):
                a, j = c
                p = (j - 1) // 2
                return (j > 0) & (a[p] > a[j])

            def body(c):
                a, j = c
                p = (j - 1) // 2
                hi, lo = a[p], a[j]
                return a.at[p].set(lo).at[j].set(hi), p

            arr, _ = lax.while_loop(cond, body, (arr, i))
            return arr

        def step(carry, x):
            arr, size = carry
            full = size >= cap
            inserted = sift_up(arr.at[size].set(x), size)
            arr2 = jnp.where(full, arr, inserted)
            size2 = jnp.where(full, size, size + 1)
            return (arr2, size2), jnp.where(full, 0, 1)

        (arr, size), ok = lax.scan(step, (arr, size), xs)
        return arr, size, ok
    return k


def _heap_delete_builder(jnp, lax):
    def k(arr, size, xs):
        def step(carry, _x):
            arr, size = carry
            empty = size == 0
            top = arr[0]
            last = arr[jnp.maximum(size - 1, 0)]
            size2 = jnp.maximum(size - 1, 0)

            def smallest(a, i):
                l, r = 2 * i + 1, 2 * i + 2
                s = jnp.where((l < size2) & (a[l] < a[i]), l, i)
                s = jnp.where((r < size2) & (a[r] < a[s]), r, s)
                return s

            def cond(c):
                a, i = c
                return smallest(a, i) != i

            def body(c):
                a, i = c
                s = smallest(a, i)
                hi, lo = a[i], a[s]
                return a.at[i].set(lo).at[s].set(hi), s

            # the eager loop only moves `last` down when the heap stays
            # non-empty; size2 == 0 leaves the array words untouched
            sifted, _ = lax.while_loop(
                cond, body, (arr.at[0].set(last), jnp.int64(0)))
            arr2 = jnp.where(empty | (size2 == 0), arr, sifted)
            return (arr2, size2), (top, jnp.where(empty, 0, 1))

        (arr, size), (tops, ok) = lax.scan(step, (arr, size), xs)
        return arr, size, tops, ok
    return k


def _queue_builder(enq: bool):
    def builder(jnp, lax):
        if enq:
            def step_factory(cap):
                def step(carry, x):
                    arr, head, tail = carry
                    full = tail - head >= cap
                    arr2 = jnp.where(full, arr, arr.at[tail % cap].set(x))
                    tail2 = jnp.where(full, tail, tail + 1)
                    return (arr2, head, tail2), jnp.where(full, 0, 1)
                return step

            def k(arr, head, tail, xs):
                (arr, head, tail), ok = lax.scan(
                    step_factory(arr.shape[0]), (arr, head, tail), xs)
                return arr, head, tail, ok
        else:
            def k(arr, head, tail, xs):
                cap = arr.shape[0]

                def step(carry, _x):
                    arr, head, tail = carry
                    empty = head == tail
                    v = arr[head % cap]
                    head2 = jnp.where(empty, head, head + 1)
                    return (arr, head2, tail), (v, jnp.where(empty, 0, 1))

                (arr, head, tail), (vals, ok) = lax.scan(
                    step, (arr, head, tail), xs)
                return arr, head, tail, vals, ok
        return k
    return builder


def _stack_builder(push: bool):
    def builder(jnp, lax):
        if push:
            def k(arr, size, xs):
                cap = arr.shape[0]

                def step(carry, x):
                    arr, size = carry
                    full = size >= cap
                    arr2 = jnp.where(full, arr, arr.at[size].set(x))
                    size2 = jnp.where(full, size, size + 1)
                    return (arr2, size2), jnp.where(full, 0, 1)

                (arr, size), ok = lax.scan(step, (arr, size), xs)
                return arr, size, ok
        else:
            def k(arr, size, xs):
                def step(carry, _x):
                    arr, size = carry
                    empty = size == 0
                    v = arr[jnp.maximum(size - 1, 0)]
                    size2 = jnp.maximum(size - 1, 0)
                    return (arr, size2), (v, jnp.where(empty, 0, 1))

                (arr, size), (vals, ok) = lax.scan(step, (arr, size), xs)
                return arr, size, vals, ok
        return k
    return builder


def _log_builder(jnp, lax):
    # The log's resp words can hold rich (non-packable) payloads from
    # earlier eager RECORDs, so this kernel never reads existing state:
    # it sees only the batch's (client, seq, resp) columns and flags each
    # entry no later entry of the batch names the same client for
    # (last write wins, in batch order).  The caller scatters only the
    # flagged entries, so nothing here is as wide as the log.
    def k(cs, ss, rs):
        idx = jnp.arange(cs.shape[0])
        later = idx[None, :] > idx[:, None]
        overwritten = jnp.any((cs[:, None] == cs[None, :]) & later, axis=1)
        return ~overwritten, ss, rs
    return k


def _ckpt_builder(jnp, lax):
    # The existing payload word may be a rich (or None) object, so the
    # kernel never reads it: the caller only overwrites the pair when
    # some batch element advanced the step, and then the winning
    # payload comes from the batch itself.
    def k(step0, steps, payloads):
        def step(carry, x):
            st, pl, advanced = carry
            s, p = x
            adv = s > st
            st2 = jnp.where(adv, s, st)
            return (st2, jnp.where(adv, p, pl), advanced | adv), st2

        (st, pl, advanced), outs = lax.scan(
            step, (step0, jnp.int64(0), False), (steps, payloads))
        return st, pl, advanced, outs
    return k


#: every round body, keyed ``kind.FUNC``
BUILDERS = {
    "counter.FAA": _faa_builder,
    "float.MUL": _mul_builder,
    "heap.HINSERT": _heap_insert_builder,
    "heap.HDELETEMIN": _heap_delete_builder,
    "queue.ENQ": _queue_builder(True),
    "queue.DEQ": _queue_builder(False),
    "stack.PUSH": _stack_builder(True),
    "stack.POP": _stack_builder(False),
    "log.RECORD": _log_builder,
    "ckpt.CKPT": _ckpt_builder,
}


# ------------------------------------------------------------------ #
# per-structure entry points (numpy in, numpy out, None = fall back) #
# ------------------------------------------------------------------ #
def faa_round(value: Any, deltas: Sequence[Any]):
    if type(value) is not int:
        return None
    with span("seam.gather"):
        xs = pack_ints(deltas)
    if xs is None:
        return None
    v, outs = _run("counter.FAA", np.int64(value), xs)
    with span("seam.scatter"):
        return int(v), outs.tolist()


def mul_round(value: Any, factors: Sequence[Any]):
    if type(value) is not float or _jx()[0].default_backend() == "tpu":
        # the TPU carries float64 as a pair of float32s, whose products
        # differ from IEEE binary64 in the last bits (DESIGN.md §11)
        return None
    with span("seam.gather"):
        xs = pack_floats(factors)
    if xs is None:
        return None
    v, outs = _run("float.MUL", np.float64(value), xs)
    with span("seam.scatter"):
        return float(v), outs.tolist()


def heap_round(arr_words: Sequence[Any], size: Any, func: str,
               args: Sequence[Any]):
    """One homogeneous heap round (HINSERT or HDELETEMIN) over the full
    key array.  Returns (new_words, new_size, responses) or None."""
    if type(size) is not int or func not in ("HINSERT", "HDELETEMIN"):
        return None
    with span("seam.gather"):
        arr = pack_state(arr_words)
        xs = (pack_ints(args) if func == "HINSERT"
              else np.zeros(len(args), dtype=np.int64))
    if arr is None or xs is None:
        return None
    if func == "HINSERT":
        arr2, size2, ok = _run("heap.HINSERT", arr, np.int64(size), xs)
        with span("seam.scatter"):
            return arr2.tolist(), int(size2), [bool(o) for o in ok]
    arr2, size2, tops, ok = _run("heap.HDELETEMIN", arr, np.int64(size), xs)
    with span("seam.scatter"):
        resps = [int(t) if o else None for t, o in zip(tops, ok)]
        return arr2.tolist(), int(size2), resps


def queue_round(ring_words: Sequence[Any], head: Any, tail: Any,
                func: str, args: Sequence[Any]):
    if (type(head) is not int or type(tail) is not int
            or func not in ("ENQ", "DEQ")):
        return None
    with span("seam.gather"):
        arr = pack_state(ring_words)
        xs = (pack_ints(args) if func == "ENQ"
              else np.zeros(len(args), dtype=np.int64))
    if arr is None or xs is None:
        return None
    if func == "ENQ":
        arr2, h2, t2, ok = _run(
            "queue.ENQ", arr, np.int64(head), np.int64(tail), xs)
        with span("seam.scatter"):
            resps: List[Any] = ["ACK" if o else False for o in ok]
            return arr2.tolist(), int(h2), int(t2), resps
    arr2, h2, t2, vals, ok = _run(
        "queue.DEQ", arr, np.int64(head), np.int64(tail), xs)
    with span("seam.scatter"):
        resps = [int(v) if o else None for v, o in zip(vals, ok)]
        return arr2.tolist(), int(h2), int(t2), resps


def stack_round(arr_words: Sequence[Any], size: Any, func: str,
                args: Sequence[Any]):
    if type(size) is not int or func not in ("PUSH", "POP"):
        return None
    with span("seam.gather"):
        arr = pack_state(arr_words)
        xs = (pack_ints(args) if func == "PUSH"
              else np.zeros(len(args), dtype=np.int64))
    if arr is None or xs is None:
        return None
    if func == "PUSH":
        arr2, s2, ok = _run("stack.PUSH", arr, np.int64(size), xs)
        with span("seam.scatter"):
            resps: List[Any] = ["ACK" if o else False for o in ok]
            return arr2.tolist(), int(s2), resps
    arr2, s2, vals, ok = _run("stack.POP", arr, np.int64(size), xs)
    with span("seam.scatter"):
        resps = [int(v) if o else None for v, o in zip(vals, ok)]
        return arr2.tolist(), int(s2), resps


def log_round(n_clients: int, triples: Sequence[Tuple[Any, Any, Any]]):
    """A batch of RECORD announcements, last write wins in batch order.
    Returns ``(writes, responses)`` where writes is a list of
    ``(client, seq, resp)`` — the last entry of the batch for each
    client it names, in batch order — or None.  Host work and transfers
    are the batch's length, whatever ``n_clients``."""
    with span("seam.gather"):
        cs = pack_ints([t[0] for t in triples])
        ss = pack_ints([t[1] for t in triples])
        rs = pack_ints([t[2] for t in triples])
    if cs is None or ss is None or rs is None:
        return None
    if len(cs) and (cs.min() < 0 or cs.max() >= n_clients):
        return None                      # eager path raises — keep it
    last, seqs, resps = _run("log.RECORD", cs, ss, rs)
    with span("seam.scatter"):
        keep = np.flatnonzero(last)
        writes = list(zip(cs[keep].tolist(), seqs[keep].tolist(),
                          resps[keep].tolist()))
        return writes, resps.tolist()


def ckpt_round(step: Any, pairs: Sequence[Tuple[Any, Any]]):
    """A batch of CKPT announcements (newest step wins).  Returns
    ``(new_step, new_payload_or_None_if_unchanged, responses)``."""
    if type(step) is not int:
        return None
    with span("seam.gather"):
        ss = pack_ints([p[0] for p in pairs])
        ps = pack_ints([p[1] for p in pairs])
    if ss is None or ps is None:
        return None
    st, pl, advanced, outs = _run("ckpt.CKPT", np.int64(step), ss, ps)
    with span("seam.scatter"):
        return int(st), (int(pl) if advanced else None), \
            [int(o) for o in outs]
