"""Cache lines written back (pwb) per completed operation over the
window, from the NVM's counters."""


def read(obs):
    return obs["delta"]["pwb"] / obs["n_ops"] if obs["n_ops"] else None
