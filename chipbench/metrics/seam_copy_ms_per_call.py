"""Host time moving the state image across the seam, reading and packing
it (``seam.gather``) and unpacking and writing it (``seam.scatter``), per
seam call (``seam`` span)."""


def read(obs):
    tr = obs["trace"] or {}
    prog = (tr.get("program") or {}).get("spans", {})
    seam = tr.get("spans", {}).get("seam")
    if "seam.gather" not in prog or not seam or not seam["count"]:
        return None
    copy = sum(prog[n]["total_s"] for n in ("seam.gather", "seam.scatter")
               if n in prog)
    return copy / seam["count"] * 1e3
