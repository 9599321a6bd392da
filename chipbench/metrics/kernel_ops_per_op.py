"""Requests the round-body kernel served per request the combiners
committed, over the window.  A mixed pass, or one the seam declines,
runs the per-op host loop instead and lowers it; PWFComb attempts that
lose their SC raise it, since their kernel work is thrown away."""


def read(obs):
    d = obs["delta"]
    if not d["ops_combined"]:
        return None
    return d["seam_ops"] / d["ops_combined"]
