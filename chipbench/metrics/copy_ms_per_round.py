"""Host time in the combiner's StateRec copies, from the ``copy`` spans
the harness wraps around ``nvm.copy_range`` in the traced run, per
committed round."""


def read(obs):
    span = (obs["trace"] or {}).get("spans", {}).get("copy")
    rounds = obs["delta"]["rounds"]
    if not span or not span["count"] or not rounds:
        return None
    return span["total_s"] / rounds * 1e3
