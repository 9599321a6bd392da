"""Seconds from process start to the first timed operation: imports,
device start, the compile cache, kernel warm-up, preload and the
clients' warm-up."""


def read(obs):
    return obs["setup_s"]
