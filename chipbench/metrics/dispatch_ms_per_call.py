"""Host time transferring a kernel's arguments and launching it
(``seam.dispatch`` spans) per dispatch."""


def read(obs):
    prog = ((obs["trace"] or {}).get("program") or {}).get("spans", {})
    span = prog.get("seam.dispatch")
    if not span or not span["count"]:
        return None
    return span["total_s"] / span["count"] * 1e3
