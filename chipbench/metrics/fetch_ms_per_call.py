"""Host time from a kernel's launch returning to its outputs on the host
(``seam.fetch`` spans: waiting on the device, the copy back, getting the
interpreter lock back) per fetch."""


def read(obs):
    prog = ((obs["trace"] or {}).get("program") or {}).get("spans", {})
    span = prog.get("seam.fetch")
    if not span or not span["count"]:
        return None
    return span["total_s"] / span["count"] * 1e3
