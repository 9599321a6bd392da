"""Host wall time inside the object's ``vector_apply`` per call, from the
``seam`` spans the harness wraps around it in the traced run."""


def read(obs):
    span = (obs["trace"] or {}).get("spans", {}).get("seam")
    if not span or not span["count"]:
        return None
    return span["total_s"] / span["count"] * 1e3
