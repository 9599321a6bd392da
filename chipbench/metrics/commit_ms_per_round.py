"""Host time in the persistence sentence, from the ``commit`` spans the
harness wraps around the NVM's commit in the traced run, per committed
round."""


def read(obs):
    span = (obs["trace"] or {}).get("spans", {}).get("commit")
    rounds = obs["delta"]["rounds"]
    if not span or not span["count"] or not rounds:
        return None
    return span["total_s"] / rounds * 1e3
