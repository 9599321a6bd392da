"""Round-body kernel calls whose output lived on the device, per
committed combining round, over the window.  Failed PWFComb attempts
and extra fixpoint passes raise it; passes the seam declines lower it."""


def read(obs):
    d = obs["delta"]
    return d["kernel_calls"] / d["rounds"] if d["rounds"] else None
