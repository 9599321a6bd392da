"""Time from the end of the last device op of a seam call to the end of
its ``seam.fetch``: how late the host notices the finished kernel,
averaged over the seam calls in which a device op ends (device planes
that hold ops only)."""


def read(obs):
    prog = (obs["trace"] or {}).get("program") or {}
    wake = prog.get("wake_s")
    if not wake:
        return None
    return sum(wake) / len(wake) * 1e3
