"""Host time in the combiner's announcement scans (``combine.scan``
spans) per committed round."""


def read(obs):
    prog = ((obs["trace"] or {}).get("program") or {}).get("spans", {})
    rounds = obs["delta"]["rounds"]
    if "combine.scan" not in prog or not rounds:
        return None
    return prog["combine.scan"]["total_s"] / rounds * 1e3
