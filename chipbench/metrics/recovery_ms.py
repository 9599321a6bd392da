"""Wall time of ``CombiningRuntime.recover()`` after a crash inside a
combining round, averaged over the run's crash cycles."""


def read(obs):
    walls = obs["recovery_s"]
    return sum(walls) / len(walls) * 1e3 if walls else None
