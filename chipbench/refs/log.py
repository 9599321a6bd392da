"""Plain session table: the reference for the response-log
configurations.

One ``(seq, response)`` row per session, overwritten by every
``record``, the reply being the response: the table of Ongaro's
thesis (2014, Sec. 6.3).  It imports nothing of the program.
``Control`` is the same table put in the program's place with its seq
and response held in fewer bits.
"""

from __future__ import annotations


class Ref:
    def __init__(self, config):
        self.rows = [(0, None)] * config["make"]["n_clients"]

    def apply(self, op, arg):
        if op != "record":
            raise ValueError(f"unknown log op {op!r}")
        session, seq, response = arg
        self.rows[session] = (seq, response)
        return response

    def snapshot(self):
        """Every session's ``(seq, response)``, in session order."""
        return list(self.rows)

    def apply_round(self, op, args, replies):
        """One round in which every client announced ``record`` and a
        crash landed inside it.  Each client's reply is its own
        response, in any order.  Recovery replays the in-flight records
        in client order, so of two records for one session the later
        client's stays.  Applies the round; returns how many replies
        differ."""
        return sum((type(r).__name__, r) != (type(want).__name__, want)
                   for r, want in zip(replies,
                                      [self.apply(op, a) for a in args]))


def wrap(value, bits):
    """``value`` as a two's-complement integer of ``bits`` bits."""
    half = 1 << (bits - 1)
    return (value + half) % (2 * half) - half


class Control:
    """The reference in the program's place, with ``bits``-bit seqs and
    responses: stands in for the log object's ``apply`` and
    ``vector_apply`` on the words of the program's layout (word ``2c``
    the seq of session c, ``2c + 1`` its response), response before
    seq."""

    def __init__(self, config, bits):
        self.bits = bits

    def vector_apply(self, nvm, st_base, func, args_list, ctx=None):
        out = []
        for session, seq, response in args_list:
            response = wrap(response, self.bits)
            nvm.write(st_base + 2 * session + 1, response)
            nvm.write(st_base + 2 * session, wrap(seq, self.bits))
            out.append(response)
        return out

    def apply(self, nvm, st_base, func, args, ctx=None):
        return self.vector_apply(nvm, st_base, func, [args], ctx)[0]
