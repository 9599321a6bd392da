"""Operations completed inside the window, over the window's seconds."""


def read(obs):
    return obs["n_ops"] / obs["window_s"]
