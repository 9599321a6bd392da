"""Simulated attempts per published round, from the ``combine`` spans the
harness wraps around PBComb's ``_combine`` (one per round: 1.0) or
PWFComb's simulation passes (one per attempt that applied requests,
published or not) in the traced run, over the committed rounds."""


def read(obs):
    span = (obs["trace"] or {}).get("spans", {}).get("combine")
    rounds = obs["delta"]["rounds"]
    if not span or not span["count"] or not rounds:
        return None
    return span["count"] / rounds
