"""Plain sequential min-heap: the reference for the heap configurations.

``Ref`` is copied from the repository's chip smoke test (``RefHeap``),
so that a change to the program cannot move it.  It imports nothing of
the program.  ``Control`` is the same reference put in the program's
place with its keys held in fewer bits: the step that would tempt a
later change, since the TPU has no native 64-bit integers.
"""

from __future__ import annotations

import heapq
from collections import Counter


class Ref:
    def __init__(self, config):
        self.cap, self.keys = config["capacity"], []

    def apply(self, op, arg):
        if op == "insert":
            if len(self.keys) >= self.cap:
                return False
            heapq.heappush(self.keys, arg)
            return True
        if op == "delete_min":
            return heapq.heappop(self.keys) if self.keys else None
        raise ValueError(f"unknown heap op {op!r}")

    def snapshot(self):
        return sorted(self.keys)

    def apply_round(self, op, args, replies):
        """One round in which every client announced ``op`` and the
        combiner chose the order.  A round of inserts only, or of
        delete_mins only, gives the same multiset of replies in every
        order.  Applies the round; returns how many replies that
        multiset does not account for."""
        want = Counter((type(r).__name__, r)
                       for r in (self.apply(op, a) for a in args))
        got = Counter((type(r).__name__, r) for r in replies)
        return sum((want - got).values())


def wrap(value, bits):
    """``value`` as a two's-complement integer of ``bits`` bits."""
    half = 1 << (bits - 1)
    return (value + half) % (2 * half) - half


class Control:
    """The reference in the program's place, with ``bits``-bit keys.

    Stands in for the heap object's ``apply`` and ``vector_apply`` on
    the words of the program's layout (word 0 the size, then the key
    array), so the combining protocol, its persistence and its recovery
    run as they do for the program."""

    def __init__(self, config, bits):
        self.cap, self.bits = config["capacity"], bits

    def vector_apply(self, nvm, st_base, func, args_list, ctx=None):
        keys = nvm.read_range(st_base + 1, nvm.read(st_base))
        out = []
        for a in args_list:
            if func == "HINSERT":
                full = len(keys) >= self.cap
                if not full:
                    heapq.heappush(keys, wrap(a, self.bits))
                out.append(not full)
            else:
                out.append(heapq.heappop(keys) if keys else None)
        nvm.write(st_base, len(keys))
        nvm.write_range(st_base + 1, keys)
        return out

    def apply(self, nvm, st_base, func, args, ctx=None):
        return self.vector_apply(nvm, st_base, func, [args], ctx)[0]
