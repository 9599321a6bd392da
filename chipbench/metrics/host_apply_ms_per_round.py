"""Host time in the per-op loop of mixed or declined passes
(``combine.host_apply`` spans) per committed round: 0 where the program
wrote its spans and no such pass ran."""


def read(obs):
    prog = ((obs["trace"] or {}).get("program") or {}).get("spans", {})
    rounds = obs["delta"]["rounds"]
    if "combine.scan" not in prog or not rounds:
        return None
    host = prog.get("combine.host_apply", {"total_s": 0.0})
    return host["total_s"] / rounds * 1e3
