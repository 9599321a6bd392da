"""Time from a request's announcement to its adoption by a combining
pass, averaged over the requests adopted in the window, from the
program's ``queue_ns`` and ``queued_ops`` counters (counted while its
tracing is on)."""


def read(obs):
    d = obs["delta"]
    if not d.get("queued_ops"):
        return None
    return d["queue_ns"] / d["queued_ops"] * 1e-6
