"""The comparison that decides ``correct``.

Every reply the clients received, in the timed window and around it, is
compared with the plain reference replaying the serving order the
combiners chose (``probes.OrderLog``).  Every operation a client
completed has to appear in that order exactly once, in the client's own
program order, as the request the client made.  The object's state is
compared after the window, and its durable state after the crash
cycles, where every in-flight request has to be applied exactly once.
Each number is a count with the limit 0.
"""

from __future__ import annotations

from collections import Counter

#: a reply the recovery never gave
MISSING = object()

LIMITS = {"wrong_replies": 0, "lost_or_extra_ops": 0,
          "state_diff_after_window": 0, "recovery_wrong_replies": 0,
          "state_diff_after_recovery": 0}


def typed(value):
    return (type(value).__name__, value)


def state_diff(got, want):
    """Words by which two snapshots differ: the size of the multiset
    difference of two key lists, or 0/1 for a single value."""
    if isinstance(want, list):
        if not isinstance(got, list):
            return len(want) + 1
        a, b = Counter(map(typed, got)), Counter(map(typed, want))
        return sum(((a - b) + (b - a)).values())
    return int(typed(got) != typed(want))


def compare(ref, preload, clients, order, requests, window_state, cycles,
            final_state):
    """Counts of what the program got wrong, under ``LIMITS``' names.

    ``preload``: ``(op, args)`` applied in staged rounds before the
    window.  ``clients``: per client, its completed ``(op, arg, reply)``
    in program order.  ``order``: committed ``(client, func, args)`` in
    serving order.  ``requests``: op -> ``(func, default arg)``, the
    request the program makes of a call.  ``cycles``: per crash cycle,
    ``(op, args, replies)`` with one entry per client."""
    op, args = preload
    for a in args:
        ref.apply(op, a)
    pos = [0] * len(clients)
    wrong = lost = 0
    for q, func, fargs in order:
        if pos[q] >= len(clients[q]):
            lost += 1                    # applied, never completed
            continue
        op, arg, reply = clients[q][pos[q]]
        pos[q] += 1
        want_func, default = requests[op]
        if (func, fargs) != (want_func, default if arg is None else arg):
            lost += 1                    # served something else
        if typed(reply) != typed(ref.apply(op, arg)):
            wrong += 1
    lost += sum(len(c) - p for c, p in zip(clients, pos))
    window_diff = state_diff(window_state, ref.snapshot())
    recovery_wrong = sum(ref.apply_round(op, args, replies)
                         for op, args, replies in cycles)
    return {"wrong_replies": wrong, "lost_or_extra_ops": lost,
            "state_diff_after_window": window_diff,
            "recovery_wrong_replies": recovery_wrong,
            "state_diff_after_recovery": state_diff(final_state,
                                                    ref.snapshot())}
