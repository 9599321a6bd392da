"""Plain sequential Fetch&Add counter: the reference for the counter
configurations.

``Ref`` is copied from the repository's chip smoke test
(``RefCounter``), so that a change to the program cannot move it.  It
imports nothing of the program.  ``Control`` is the same reference put
in the program's place with its value held in fewer bits.
"""

from __future__ import annotations


class Ref:
    def __init__(self, config):
        self.value = 0

    def apply(self, op, delta):
        if op != "fetch_add":
            raise ValueError(f"unknown counter op {op!r}")
        old, self.value = self.value, self.value + delta
        return old

    def snapshot(self):
        return self.value

    def apply_round(self, op, deltas, replies):
        """One round in which every client announced ``fetch_add`` and
        the combiner chose the order.  The replies must chain: one of
        them is the value before the round, and each next one is the
        previous plus that client's delta.  Applies the round; returns
        how many replies no chain reaches."""
        by_value = {}
        for c, r in enumerate(replies):
            by_value.setdefault((type(r).__name__, r), []).append(c)
        cur, left = self.value, len(deltas)
        while left:
            cands = by_value.get(("int", cur))
            if not cands:
                break
            c = cands.pop()
            cur += deltas[c]
            left -= 1
        self.value += sum(deltas)
        return left


def wrap(value, bits):
    """``value`` as a two's-complement integer of ``bits`` bits."""
    half = 1 << (bits - 1)
    return (value + half) % (2 * half) - half


class Control:
    """The reference in the program's place, with a ``bits``-bit value:
    stands in for the counter object's ``apply`` and ``vector_apply`` on
    its one state word."""

    def __init__(self, config, bits):
        self.bits = bits

    def vector_apply(self, nvm, st_base, func, args_list, ctx=None):
        v, out = nvm.read(st_base), []
        for d in args_list:
            out.append(v)
            v = wrap(v + wrap(d, self.bits), self.bits)
        nvm.write(st_base, v)
        return out

    def apply(self, nvm, st_base, func, args, ctx=None):
        return self.vector_apply(nvm, st_base, func, [args], ctx)[0]
