"""psync instructions per completed operation over the window, from the
NVM's counters: the persistence cost that NVM latency would charge on
real hardware and the simulated NVM does not."""


def read(obs):
    return obs["delta"]["psync"] / obs["n_ops"] if obs["n_ops"] else None
