"""Device busy time in the traced window per committed combining round.
Every device operation in this system is a round body."""


def read(obs):
    tr, rounds = obs["trace"], obs["delta"]["rounds"]
    if not tr or not tr.get("busy_s") or not rounds:
        return None
    return tr["busy_s"] / rounds * 1e3
