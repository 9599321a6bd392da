"""Requests served per committed combining round over the window, from
the combiner's own degree counters (``core.stats``)."""


def read(obs):
    d = obs["delta"]
    return d["ops_combined"] / d["rounds"] if d["rounds"] else None
